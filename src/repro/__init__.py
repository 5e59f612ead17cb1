"""repro — a JAX/Pallas PERMANOVA system for TPU, reproducing and extending
*Comparing CPU and GPU compute of PERMANOVA on MI300A* (Sfiligoi, PEARC25).

Layers:
  core/       PERMANOVA statistics engine (the paper's contribution)
  engine/     hardware-aware execution layer: s_W impl registry,
              planner/autotuner, streaming permutation scheduler
  pipeline/   end-to-end features->p-value subsystem: distance impl
              registry, joint two-stage planner, dense/stream/fused
              materialization bridges, batched pipeline_many
  kernels/    Pallas TPU kernels for the hot loops (+ jnp oracles)
  obs/        zero-dependency telemetry: trace spans (Chrome/Perfetto
              export), compile/traffic counters, predicted-vs-measured
              bandwidth reconciliation (obs.report)
  serve/      always-on PERMANOVA service (shape buckets, deadlines,
              batching, degradation)
  runtime/    fault tolerance: heartbeats, elastic permutation blocks,
              stragglers, fault injection
  checkpoint/ sharded checkpoints with async write + resume
  data/       synthetic microbiome studies and the on-disk slab cache
  launch/     mesh construction, permanova/serve drivers
  hw.py       peak rates of the devices, keyed by device_kind
"""

__version__ = "1.0.0"

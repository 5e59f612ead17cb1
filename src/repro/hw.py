"""Peak rates of the devices this system runs on, keyed by `device_kind`.

`device_spec()` returns the entry for the device JAX reports
(`jax.devices()[0].device_kind`). A device that is not in the table is an
error: no device borrows another device's numbers. Every entry names its
source. These are datasheet ceilings for the roofline model and the
traffic reports, never measurements.

The MI300A constants below are the paper's own measurements. They serve
the paper-comparison tables (Fig. 1, STREAM) only and model no device
this code runs on.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops_bf16: float  # FLOP/s per chip
    peak_flops_f32: float   # FLOP/s per chip (MXU f32 ~= 1/2 bf16 on v5e-class)
    hbm_bandwidth: float    # B/s per chip
    hbm_bytes: float        # HBM capacity per chip
    ici_link_bandwidth: float  # B/s per link
    ici_links: int          # links per chip
    vmem_bytes: float       # on-chip vector memory
    source: str = ""
    mxu_tile: int = 128     # systolic array dim
    vpu_lanes: tuple = (8, 128)


TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    peak_flops_f32=98.5e12,
    hbm_bandwidth=819e9,
    hbm_bytes=16 * 1024**3,
    ici_link_bandwidth=50e9,
    ici_links=4,
    vmem_bytes=128 * 1024**2,
    source=("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
            "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip"),
)

# The CPU backend the tests run on. Nominal host-DRAM model values so the
# traffic reports have a reference on CPU runs; not a measurement of any
# host, and never reported as a device metric.
HOST_CPU = ChipSpec(
    name="cpu",
    peak_flops_bf16=1e12,
    peak_flops_f32=1e12,
    hbm_bandwidth=64e9,
    hbm_bytes=64 * 1024**3,
    ici_link_bandwidth=0.0,
    ici_links=0,
    vmem_bytes=32 * 1024**2,
    source="nominal host model for CPU test runs (not measured)",
)

# jax device_kind -> spec (a v5e chip reports "TPU v5 lite")
DEVICES = {
    "TPU v5 lite": TPU_V5E,
    "cpu": HOST_CPU,
}


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind


def device_spec(kind: Optional[str] = None) -> ChipSpec:
    """Spec of `kind` (default: the first local device). Unknown kinds
    raise: a v5e or host number must never stand in for another device."""
    kind = device_kind() if kind is None else kind
    try:
        return DEVICES[kind]
    except KeyError:
        raise KeyError(f"no peak-rate entry for device_kind {kind!r}; "
                       f"known: {sorted(DEVICES)} (add one with its "
                       "source to repro.hw.DEVICES)") from None


# Paper's machine, for the Fig.1 / STREAM comparison tables only.
MI300A_CPU_STREAM_TRIAD = 0.209e12   # B/s measured (paper App. A2)
MI300A_GPU_STREAM_TRIAD = 3.160e12   # B/s measured (paper App. A2)
MI300A_HBM_PEAK = 5.3e12             # B/s datasheet

# Paper's benchmark workload (Fig. 1)
PAPER_N_DIMS = 25145
PAPER_N_PERMS = 3999

TARGET = TPU_V5E


def ridge_point_bf16(chip: ChipSpec = TARGET) -> float:
    """FLOP/byte where the chip transitions memory-bound -> compute-bound."""
    return chip.peak_flops_bf16 / chip.hbm_bandwidth


def ridge_point_f32(chip: ChipSpec = TARGET) -> float:
    return chip.peak_flops_f32 / chip.hbm_bandwidth

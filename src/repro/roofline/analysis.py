"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch x shape x mesh) cell — seconds per step if the chip
hit its peak on each subsystem (DESIGN.md / spec):

  compute    = HLO_FLOPs / peak_FLOP/s
  memory     = HLO_bytes / HBM_bw
  collective = collective_bytes / ICI_link_bw

With GSPMD, `compiled.cost_analysis()` describes the PER-DEVICE program, so
dividing by per-chip peaks directly yields the per-step time bound (equal to
the spec's global/(chips x peak) form). collective_bytes is NOT in
cost_analysis: we parse the optimized HLO text and sum operand bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

MODEL_FLOPS = 6*N*D (train) or 2*N*D (inference), N = active params,
D = tokens; the ratio MODEL_FLOPS / (HLO_FLOPs * chips) exposes remat /
redundant-compute waste.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

from repro import hw

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s4": 1, "u4": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_TYPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _type_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def parse_collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind operand bytes summed over the module.

    For each instruction line mentioning a collective op, sums the byte
    sizes of type literals appearing AFTER the op name (the operand list);
    falls back to the result type when operands are printed as bare names.
    """
    out = {k: 0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        for op in COLLECTIVES:
            # match e.g. " = bf16[..] all-gather(" / "all-reduce-start("
            idx = line.find(f" {op}")
            if idx < 0 or f" {op}" not in line:
                continue
            if f"{op}(" not in line and f"{op}-start(" not in line \
                    and f"{op}-done(" not in line:
                continue
            if f"{op}-done(" in line:
                continue  # counted at -start
            tail = line[idx:]
            operand_types = _TYPE_RE.findall(tail)
            if operand_types:
                size = sum(_type_bytes(d, s) for d, s in operand_types)
            else:
                head_types = _TYPE_RE.findall(line[:idx])
                size = sum(_type_bytes(d, s) for d, s in head_types)
            out[op] += size
            counts[op] += 1
            break
    out["_counts"] = counts
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float                 # per-device flops (loop-aware)
    hbm_bytes: float             # per-device HBM bytes (loop-aware)
    collective_bytes: float      # per-device collective operand bytes
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    collective_detail: dict
    model_flops_total: float = 0.0
    useful_flops_ratio: float = 0.0
    xla_flops: float = 0.0       # raw cost_analysis (loop bodies once)
    xla_bytes: float = 0.0

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze_compiled(compiled, *, chips: int,
                     chip: hw.ChipSpec = hw.TARGET,
                     dtype_flops: str = "bf16",
                     model_flops_total: float = 0.0) -> RooflineTerms:
    """Authoritative source: the loop-aware HLO-text analyzer (XLA's
    cost_analysis counts while bodies once — see roofline/hlo_cost.py).
    XLA's raw numbers are retained as diagnostics."""
    from repro.roofline.hlo_cost import loop_aware_cost

    cost = compiled.cost_analysis() or {}
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    la = loop_aware_cost(hlo)
    flops = float(la.flops)
    hbm_bytes = float(la.bytes)
    coll_bytes = float(la.coll_bytes)
    coll = dict(la.coll_by_kind)

    peak = (chip.peak_flops_bf16 if dtype_flops == "bf16"
            else chip.peak_flops_f32)
    compute_s = flops / peak
    memory_s = hbm_bytes / chip.hbm_bandwidth
    collective_s = coll_bytes / chip.ici_link_bandwidth
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    ratio = 0.0
    if flops > 0 and model_flops_total > 0:
        ratio = model_flops_total / (flops * chips)
    return RooflineTerms(
        flops=flops, hbm_bytes=hbm_bytes, collective_bytes=coll_bytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, collective_detail=coll,
        model_flops_total=model_flops_total, useful_flops_ratio=ratio,
        xla_flops=xla_flops, xla_bytes=xla_bytes)


def active_param_fraction_tree(param_axes, cfg):
    """Per-leaf activity factor: MoE expert weights count top_k/E."""
    if cfg.moe_n_experts == 0:
        return None
    frac = cfg.moe_top_k / cfg.moe_n_experts

    def one(axes):
        return frac if "expert" in axes else 1.0

    import jax
    return jax.tree.map(one, param_axes,
                        is_leaf=lambda x: isinstance(x, tuple))


def model_flops(cfg, params_abs, param_axes, *, tokens: int,
                kind: str) -> float:
    """6*N_active*D (train) / 2*N_active*D (inference)."""
    import jax
    import numpy as np
    fracs = active_param_fraction_tree(param_axes, cfg)
    total = 0.0
    leaves = jax.tree.leaves(params_abs)
    if fracs is None:
        frac_leaves = [1.0] * len(leaves)
    else:
        frac_leaves = jax.tree.leaves(fracs)
    for p, f in zip(leaves, frac_leaves):
        total += float(np.prod(p.shape)) * f
    factor = 6.0 if kind == "train" else 2.0
    return factor * total * tokens

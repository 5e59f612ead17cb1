"""Roofline accounting for the PERMANOVA kernels on the TPU v5e:
arithmetic intensity per variant at the paper's shape, and the
predicted time per 1000 permutations. This is the quantitative version of
the paper's CPU-vs-GPU finding, recast for VPU vs MXU (DESIGN.md sec. 2-3).
"""

from __future__ import annotations

from repro import hw

N = hw.PAPER_N_DIMS
PERMS = 1000
GROUPS = 8


def run(emit):
    chip = hw.TPU_V5E
    mat_bytes = 4.0 * N * N
    ridge = hw.ridge_point_bf16(chip)
    emit("pa_roofline/ridge_point_bf16", 0.0,
         f"{ridge:.1f} flop/byte (v5e)")

    cases = {
        # (flops per perm, mat2 bytes streamed per perm)
        "brute":     (3.0 * N * N / 2, mat_bytes / 2),   # triangle
        "permblock16": (3.0 * N * N / 2, mat_bytes / 2 / 16),
        "matmul_pb64": (2.0 * N * N * GROUPS + 2.0 * N * N * GROUPS,
                        mat_bytes / 64),
    }
    for name, (flops, bytes_) in cases.items():
        ai = flops / bytes_
        t_mem = bytes_ * PERMS / chip.hbm_bandwidth
        t_cmp = flops * PERMS / chip.peak_flops_bf16
        bound = "compute" if t_cmp > t_mem else "memory"
        emit(f"pa_roofline/{name}", max(t_mem, t_cmp) / PERMS * 1e6,
             f"ai={ai:.1f} flop/B mem_s={t_mem:.3f} compute_s={t_cmp:.3f} "
             f"per 1k perms -> {bound}-bound")

"""Share of its roofline taken by the features megakernel, per test.

Work is the algorithm's: stage 1 once per test over the n (n - 1) / 2
pairs (Bray-Curtis: subtract, absolute value, add per pair and feature;
Jaccard: one multiply-add per pair and feature), then the s_W work of
sw_roofline. Recomputing stage 1 for every permutation chunk is the
kernel's choice and is not work. Bytes: the feature table read once. The
stage-1 operations of Bray-Curtis run on the VPU and are still measured
against the MXU's bf16 peak, so that share reads low by construction.
"""

# the megakernel, by the name its pallas_call gives the HLO op
KERNELS = r"^%fused_sw_rows(_cols)?\b"
STAGE1_OPS = {"braycurtis": 3, "jaccard": 2}


def work(n: int, d: int, n_perms: int, metric: str):
    pairs = n * (n - 1) // 2
    ops = STAGE1_OPS[metric] * d * pairs + 2 * pairs * (n_perms + 1)
    return ops, 4 * n * d


def read(ctx):
    from bench import devtrace
    tr = ctx.trace
    k = devtrace.kernel_s(tr, KERNELS) / max(tr.tests, 1)
    if k <= 0:
        return None
    c = ctx.config
    ops, nbytes = work(c["n"], c["d"], ctx.traffic["n_perms"],
                       ctx.traffic["metric"])
    least = max(ops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / k

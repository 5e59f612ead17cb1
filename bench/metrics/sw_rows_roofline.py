"""Share of its roofline taken by the row-slab s_W kernel of the
multi-chip path, per test.

Work is the algorithm's, split evenly over the cell's chips: one
multiply-add per pair i < j per permutation, n (n - 1) (P + 1) / chips
operations, and D^2 read once, 4 n^2 / chips bytes, per chip. The least
time is the larger of operations over the bf16 MXU peak and bytes over
HBM bandwidth, as sw_roofline reckons it. Kernel time is the largest,
over the cell's chips, of the device time of the kernel's ops in the
window over the tests in it: the psum waits for the slowest chip.
"""

# the row-slab kernel, by the name its pallas_call gives the HLO op
KERNELS = r"^%sw_matmul_rows_partial\b"


def work(n: int, n_perms: int, chips: int):
    return n * (n - 1) * (n_perms + 1) / chips, 4 * n * n / chips


def read(ctx):
    from bench import devtrace
    tr = ctx.trace
    k = max(devtrace.kernel_s(tr, KERNELS, device=d)
            for d in range(ctx.chips)) / max(tr.tests, 1)
    if k <= 0:
        return None
    ops, nbytes = work(ctx.config["n"], ctx.traffic["n_perms"], ctx.chips)
    least = max(ops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / k

"""Share of its roofline taken by the s_W contraction, per test.

Work is the algorithm's, whatever kernel runs it: one multiply-add per
pair i < j per permutation, n (n - 1) (P + 1) operations, and D^2 read
once, 4 n^2 bytes. The least time is the larger of operations over the
bf16 MXU peak (the only published one) and bytes over HBM bandwidth; at
the paper's cell the operations bind. Kernel time is the device time of
the s_W kernels in the window over the tests in it.
"""

# the s_W kernels, by the name their pallas_call gives the HLO op
KERNELS = r"^%(permanova_sw|sw_matmul_rows_partial)\b"


def work(n: int, n_perms: int):
    return n * (n - 1) * (n_perms + 1), 4 * n * n


def read(ctx):
    from bench import devtrace
    tr = ctx.trace
    k = devtrace.kernel_s(tr, KERNELS) / max(tr.tests, 1)
    if k <= 0:
        return None
    ops, nbytes = work(ctx.config["n"], ctx.traffic["n_perms"])
    least = max(ops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / k

"""Device time of the collectives, ms per test on device 0: the
all-reduce ops (the psum of the s_W partials over 'model', the sum
behind s_T) and any other collective op the compiler put in. An
all-reduce on device 0 lasts until every chip has joined it, so the wait
for the slowest chip is inside. Overlapping start and done ops count
once. None where the window has no collective op, as in a one-chip
program.
"""

import re

# HLO opcodes of collectives, sync or as async start/done pairs
COLLECTIVE = re.compile(r"\s(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?\(")


def read(ctx):
    from bench import devtrace
    tr = ctx.trace
    lo, hi = tr.window
    ops = [op for op in devtrace.clip(tr.ops.get(0, []), lo, hi)
           if COLLECTIVE.search(op[0])]
    if not ops:
        return None
    busy = sum(e - s for s, e in devtrace.union(ops))
    return busy / 1e6 / max(tr.tests, 1)

"""Device time of the permutation-label layer, ms per test: the ops whose
HLO op_name carries the program's `engine.labels` scope (the key
folding, the shuffle's sorts, the grouping[perm] gather and the
identity-first select of every s_W step). None where no op carries it,
as in a program without the scope."""

SCOPE = "engine.labels"


def read(ctx):
    from bench import devscope
    tr = ctx.trace
    return devscope.scoped_ms(devscope.window_ops(), tr.window, tr.tests,
                              SCOPE)

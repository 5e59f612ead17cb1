"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips: 1 - union of op intervals / window."""


def read(ctx):
    from bench import devtrace
    tr = ctx.trace
    busy = devtrace.busy_s(tr, list(range(ctx.chips)))
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / tr.window_s)

"""Device-idle ms per test that the program's own host path causes: the
idle gaps whose innermost open span is one of the program's (engine.*,
fusedk.*, ...), not the benchmark's bench.*. device.idle is the whole
idle share; this is the program's part of it. None where the window has
no device op or no program span."""


def _program(name: str) -> bool:
    return not name.startswith("bench.") and name != "no span"


def read(ctx):
    from bench import devtrace
    tr = ctx.trace
    lo, hi = tr.window
    if devtrace.busy_s(tr, [0]) <= 0:
        return None
    if not any(_program(n) and s < hi and e > lo for n, s, e in tr.spans):
        return None
    gaps = devtrace.idle_gaps(tr, k=None)
    idle = sum(g for name, g in gaps if _program(name))
    return 1e3 * idle / max(tr.tests, 1)

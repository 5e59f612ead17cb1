"""Peak device memory after the window, fullest chip, in GB (1e9 bytes).
It moves test_s through the trade of chunk size against working set."""


def read(ctx):
    return ctx.peak_bytes / 1e9 if ctx.peak_bytes > 0 else None

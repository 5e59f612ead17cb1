"""Backend compiles inside the measured window (the program's
jax.backend_compiles counter); set-up warms every shape, so this is 0."""


def read(ctx):
    return float(ctx.compiles)

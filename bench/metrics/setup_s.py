"""Set-up: process start to window start (JAX and chip init, weights and
frames from the seed, ``register``, warm-up from the compile cache)."""


def read(ctx):
    return ctx.setup_s

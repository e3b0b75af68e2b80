"""Seconds JAX spent tracing, lowering and compiling during set-up, from
its monitoring events (``/jax/core/compile/*``)."""


def read(ctx):
    return ctx.compile_s

"""Compilations (or compile-cache loads) inside the traced window of a gap
run, from jax.monitoring's compile events; should read 0."""


def read(ctx):
    return ctx.clock.compiles_between(*ctx.window_wall)

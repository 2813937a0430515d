"""Seeded violations for the `f64-without-x64` rule."""

import jax.numpy as jnp


def timings(n):
    return jnp.zeros((n,), jnp.float64)  # VIOLATION


def guarded(n):
    import jax

    with jax.enable_x64(True):
        return jnp.zeros((n,), jnp.float64)  # ok: enable_x64 in scope

"""Pure-jnp oracles for the Pallas kernels (the ground truth in tests)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sdca import solve_subproblem_indices


@functools.partial(jax.jit, static_argnames=("k",))
def topk_filter_ref(dw: jax.Array, k: int):
    """Exact top-k split (ties toward lower index): (sent, residual, mask)."""
    mag = jnp.abs(dw)
    _, idx = jax.lax.top_k(mag, k)
    mask = jnp.zeros(dw.shape, bool).at[idx].set(True)
    sent = jnp.where(mask, dw, jnp.zeros_like(dw))
    return sent, dw - sent, mask


def sdca_inner_ref(w_eff, alpha, X, y, norms_sq, lam, n_global, sigma_prime, idx,
                   *, loss="ridge"):
    """vmapped-over-workers SDCA epoch with explicit visit order."""
    fn = functools.partial(solve_subproblem_indices, loss=loss)
    dalpha, v = jax.vmap(fn, in_axes=(0, 0, 0, 0, 0, None, None, None, 0))(
        w_eff, alpha, X, y, norms_sq, lam, n_global, sigma_prime, idx)
    return dalpha, v

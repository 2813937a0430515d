"""Pallas TPU kernel for the worker's SDCA inner loop (Algorithm 2, line 4).

Runs H sequential ridge-SDCA coordinate steps on one worker partition with the
whole working set resident in on-chip memory:

    state: dalpha (n_k,) in SMEM, v (d,) in VMEM   [updated through refs]
    step : i = idx[h]
           z     = (w_eff + sigma' v) . x_i
           delta = (y_i - a_i - z) / (1 + sigma' ||x_i||^2 / (lambda n))
           dalpha[i] += delta ;  v += delta/(lambda n) * x_i

The loop is *inherently sequential* (each step reads the v written by the
previous one), so there is no MXU mapping -- this is a VPU/latency kernel. The
TPU adaptation vs. a CPU/GPU implementation is residency: the (n_k, d) data
tile, w_eff and the evolving v never leave VMEM during the H steps, so HBM
traffic is one read of the partition + O(n_k + d) instead of H * O(d).

Grid = workers (one program per partition, matching the paper's K workers).
Per-coordinate values -- the visit order, alpha, y, ||x_i||^2 and dalpha --
are (1, 1, .) SMEM blocks read and written as scalars; the partition, w_eff
and v are (1, ., d) VMEM blocks, and a step reads its row with a dynamic
sublane slice.

Capacity contract: ``ops.sdca_epoch`` checks the VMEM and SMEM budgets
(double-buffered blocks) and raises for a shape over either.  Ridge only (the
paper's experiments); :func:`repro.kernels.ref.sdca_inner_ref` covers the
rest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sdca_kernel(scal_ref,  # SMEM (2,): [lam * n, sigma_prime]
                 idx_ref,  # SMEM (1, 1, H) int32 visit order
                 alpha_ref,  # SMEM (1, 1, n_k)
                 y_ref,  # SMEM (1, 1, n_k)
                 norms_ref,  # SMEM (1, 1, n_k)
                 w_ref,  # VMEM (1, 1, d) w_eff
                 x_ref,  # VMEM (1, n_k, d) the partition
                 dalpha_ref,  # out SMEM (1, 1, n_k)
                 v_ref,  # out VMEM (1, 1, d)
                 ):
    n_k = x_ref.shape[1]
    h_steps = idx_ref.shape[2]
    lam_n = scal_ref[0]
    sigma_p = scal_ref[1]

    def zero(i, carry):
        dalpha_ref[0, 0, i] = jnp.float32(0.0)
        return carry

    jax.lax.fori_loop(0, n_k, zero, 0)
    v_ref[...] = jnp.zeros_like(v_ref)
    w_eff = w_ref[0]  # (1, d)

    def body(h, carry):
        i = idx_ref[0, 0, h]
        x_i = x_ref[0, pl.ds(i, 1), :]  # (1, d)
        a_i = alpha_ref[0, 0, i] + dalpha_ref[0, 0, i]
        z_i = jnp.sum(w_eff * x_i) + sigma_p * jnp.sum(v_ref[0] * x_i)
        q_i = sigma_p * norms_ref[0, 0, i] / lam_n
        delta = (y_ref[0, 0, i] - a_i - z_i) / (1.0 + q_i)
        dalpha_ref[0, 0, i] = dalpha_ref[0, 0, i] + delta
        v_ref[0] = v_ref[0] + (delta / lam_n) * x_i
        return carry

    jax.lax.fori_loop(0, h_steps, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sdca_inner_pallas(
    w_eff: jax.Array,  # (K, d)
    alpha: jax.Array,  # (K, n_k)
    X: jax.Array,  # (K, n_k, d)
    y: jax.Array,  # (K, n_k)
    norms_sq: jax.Array,  # (K, n_k)
    lam: float,
    n_global: int,
    sigma_prime: float,
    idx: jax.Array,  # (K, H) int32 visit order per worker
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """All-K-workers SDCA epoch; returns (dalpha (K,n_k), v (K,d))."""
    K, n_k, d = X.shape
    H = idx.shape[1]
    scal = jnp.array([lam * n_global, sigma_prime], jnp.float32)

    def scalars(width):  # one worker's (1, 1, width) row of a (K, 1, width)
        return pl.BlockSpec((1, 1, width), lambda k: (k, 0, 0),
                            memory_space=pltpu.SMEM)

    row = pl.BlockSpec((1, 1, d), lambda k: (k, 0, 0))
    dalpha, v = pl.pallas_call(
        _sdca_kernel,
        grid=(K,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM), scalars(H),
            scalars(n_k), scalars(n_k), scalars(n_k), row,
            pl.BlockSpec((1, n_k, d), lambda k: (k, 0, 0)),
        ],
        out_specs=[scalars(n_k), row],
        out_shape=[
            jax.ShapeDtypeStruct((K, 1, n_k), X.dtype),
            jax.ShapeDtypeStruct((K, 1, d), X.dtype),
        ],
        interpret=interpret,
    )(scal, idx.reshape(K, 1, H), alpha.reshape(K, 1, n_k),
      y.reshape(K, 1, n_k), norms_sq.reshape(K, 1, n_k),
      w_eff.reshape(K, 1, d), X)
    return dalpha.reshape(K, n_k), v.reshape(K, d)

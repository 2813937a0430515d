"""Public wrappers around the Pallas kernels.

The kernels compile for the TPU by default.  ``interpret=True`` runs them
through the Pallas interpreter instead, which is how the CPU tests call
them.  The pure-jnp oracles in :mod:`repro.kernels.ref` are called by name;
no wrapper swaps one in.  A shape outside a kernel's contract raises with
the reason.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import sdca_inner
from repro.kernels.topk_filter import topk_filter_pallas


def topk_filter(dw: jax.Array, k: int, *, interpret: bool = False):
    """Message filter F: returns (sent, residual, mask). See Algorithm 2."""
    return topk_filter_pallas(dw, k, interpret=interpret)


def sdca_epoch(w_eff, alpha, X, y, norms_sq, lam, n_global, sigma_prime, idx,
               *, loss: str = "ridge", interpret: bool = False):
    """Every worker's SDCA pass on the kernel: (dalpha (K,n_k), v (K,d)).

    Worker ``k`` runs ``idx[k]`` against ``X`` with ``w_eff[k]`` and its
    rows of ``alpha``, ``y`` and ``norms_sq``, one kernel call per worker.
    Raises ``ValueError`` for a loss without a closed-form step or a shape
    over the kernel's VMEM or SMEM budget;
    :func:`repro.kernels.ref.sdca_inner_ref` is the jnp path for those.
    """
    K, n_k, d = X.shape
    why = sdca_inner.refusal(loss, n_k, d, idx.shape[1])
    if why is not None:
        raise ValueError(f"{why}; use repro.kernels.ref.sdca_inner_ref")
    passes = [sdca_inner.sdca_pass(
        w_eff[k], alpha[k], X, k, y[k], norms_sq[k], lam, n_global,
        sigma_prime, idx[k], loss=loss, interpret=interpret)
        for k in range(K)]
    return (jnp.stack([p.delta_alpha for p in passes]),
            jnp.stack([p.v for p in passes]))

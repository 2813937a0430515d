"""Public wrappers around the Pallas kernels.

The kernels compile for the TPU by default.  ``interpret=True`` runs them
through the Pallas interpreter instead, which is how the CPU tests call
them.  The pure-jnp oracles in :mod:`repro.kernels.ref` are called by name;
no wrapper swaps one in.  A shape outside a kernel's contract raises with
the reason.
"""

from __future__ import annotations

import jax

from repro.kernels.sdca_inner import sdca_inner_pallas
from repro.kernels.topk_filter import topk_filter_pallas

# VMEM contract for the SDCA kernel, in f32 elements of one worker's block:
# its (n_k, d) partition plus the w_eff and v rows, padded to the (8, 128)
# tile.  The pipeline double-buffers each block, and the TPU v5e compiler's
# default scoped-VMEM limit is 16 MiB = 2 x 2,097,152 f32; the budget keeps
# a margin below that (tests/test_tpu_compile.py compiles the widest
# admitted shape).
_SDCA_VMEM_BUDGET = 2_000_000
# SMEM contract: one worker's visit order plus its alpha, y, norms and
# dalpha rows, double-buffered, in 4-byte scalars; v5e has 1 MiB of SMEM
# and the compiler refuses H + 4 * n_k = 140,000.
_SDCA_SMEM_BUDGET = 120_000


def sdca_vmem_elements(n_k: int, d: int) -> int:
    """Padded f32 elements of one worker's VMEM block set (see the budget)."""
    return (-(-n_k // 8) * 8 + 2) * (-(-d // 128) * 128)


def topk_filter(dw: jax.Array, k: int, *, interpret: bool = False):
    """Message filter F: returns (sent, residual, mask). See Algorithm 2."""
    return topk_filter_pallas(dw, k, interpret=interpret)


def sdca_epoch(w_eff, alpha, X, y, norms_sq, lam, n_global, sigma_prime, idx,
               *, loss: str = "ridge", interpret: bool = False):
    """All-workers ridge SDCA epoch on the kernel: (dalpha (K,n_k), v (K,d)).

    Raises ``ValueError`` for a loss other than ridge or a shape over the
    kernel's VMEM or SMEM contract; :func:`repro.kernels.ref.sdca_inner_ref`
    is the jnp path for those.
    """
    K, n_k, d = X.shape
    H = idx.shape[1]
    if loss != "ridge":
        raise ValueError(f"the SDCA kernel implements ridge only, got loss "
                         f"{loss!r}; use repro.kernels.ref.sdca_inner_ref")
    need = sdca_vmem_elements(n_k, d)
    if need > _SDCA_VMEM_BUDGET:
        raise ValueError(
            f"partition (n_k={n_k}, d={d}) needs {need} f32 elements of "
            f"VMEM per worker, over the kernel's budget of "
            f"{_SDCA_VMEM_BUDGET}; use repro.kernels.ref.sdca_inner_ref")
    if H + 4 * n_k > _SDCA_SMEM_BUDGET:
        raise ValueError(
            f"H={H} steps over n_k={n_k} coordinates need H + 4 * n_k = "
            f"{H + 4 * n_k} SMEM scalars per worker, over the kernel's budget "
            f"of {_SDCA_SMEM_BUDGET}; use repro.kernels.ref.sdca_inner_ref")
    return sdca_inner_pallas(w_eff, alpha, X, y, norms_sq, lam, n_global,
                             sigma_prime, idx, interpret=interpret)

"""Alternative local solvers the paper points to (Sec. III-B1).

The paper uses plain SDCA with uniform sampling but explicitly lists the
drop-in alternatives: Accelerated Prox-SDCA (Shalev-Shwartz & Zhang 2013/14)
and importance sampling (Zhang & Xiao 2015). Both are implemented here on the
same subproblem interface as ``sdca.solve_subproblem`` so any ACPD run can
swap them via ``MethodConfig``-level composition (see tests for the
convergence comparison).

* ``solve_subproblem_importance``: coordinates sampled with probability
  p_i proportional to (1 + sigma' ||x_i||^2 / (lam n)) -- the smoothness-
  proportional distribution -- with the update unchanged (the coordinate
  maximizer is exact, so no step-size reweighting is needed for ascent).
* ``solve_subproblem_accelerated``: outer Catalyst-style acceleration around
  the SDCA inner loop: solve a sequence of kappa-regularized subproblems at
  extrapolated points y_t = alpha_t + beta (alpha_t - alpha_{t-1}).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.objectives import HIGHEST, LossName
from repro.core.sdca import (LocalSolveResult, solve_subproblem,
                             solve_subproblem_indices)


@partial(jax.jit, static_argnames=("loss", "num_steps"))
def solve_subproblem_importance(
    w_eff: jax.Array,
    alpha: jax.Array,
    X: jax.Array,
    y: jax.Array,
    norms_sq: jax.Array,
    lam: float,
    n_global: int,
    sigma_prime: float,
    key: jax.Array,
    *,
    loss: LossName,
    num_steps: int,
) -> LocalSolveResult:
    """SDCA with smoothness-proportional (importance) sampling."""
    q = 1.0 + sigma_prime * norms_sq / (lam * n_global)
    p = q / jnp.sum(q)
    idx = jax.random.choice(key, norms_sq.shape[0], (num_steps,), p=p)
    return solve_subproblem_indices(
        w_eff, alpha, X, y, norms_sq, lam, n_global, sigma_prime,
        idx.astype(jnp.int32), loss=loss)


@partial(jax.jit, static_argnames=("loss", "num_steps", "num_rounds"))
def solve_subproblem_accelerated(
    w_eff: jax.Array,
    alpha: jax.Array,
    X: jax.Array,
    y: jax.Array,
    norms_sq: jax.Array,
    lam: float,
    n_global: int,
    sigma_prime: float,
    key: jax.Array,
    *,
    loss: LossName,
    num_steps: int,
    num_rounds: int = 4,
    beta: float = 0.5,
) -> LocalSolveResult:
    """Catalyst-style accelerated SDCA: extrapolated restarts of the inner
    solver. Total coordinate steps = num_steps (split across rounds), so the
    comparison against plain SDCA is work-normalized."""
    n_k = X.shape[0]
    inner = max(1, num_steps // num_rounds)

    def round_body(carry, k):
        dalpha_prev, dalpha, v = carry
        # extrapolate in the dual
        momentum = beta * (dalpha - dalpha_prev)
        da_y = dalpha + momentum
        v_y = v + jnp.matmul(X.T, momentum, precision=HIGHEST) / (
            lam * n_global)
        idx = jax.random.randint(k, (inner,), 0, n_k, dtype=jnp.int32)
        res = solve_subproblem_indices(
            w_eff + sigma_prime * v_y, alpha + da_y, X, y, norms_sq, lam,
            n_global, sigma_prime, idx, loss=loss)
        return (dalpha, da_y + res.delta_alpha, v_y + res.v), None

    keys = jax.random.split(key, num_rounds)
    init = (jnp.zeros_like(alpha), jnp.zeros_like(alpha), jnp.zeros_like(w_eff))
    (_, dalpha, v), _ = jax.lax.scan(round_body, init, keys)
    return LocalSolveResult(dalpha, v)


# ---------------------------------------------------------------------------
# Local-solver registry.
#
# The CoCoA-lineage protocols in repro.core.engine (protocol="cocoa" /
# "cocoa_plus") draw their per-worker subproblem solver from here via
# ``MethodConfig.local_solver`` instead of hard-wiring SDCA, which is exactly
# the freedom the CoCoA framework (Jaggi et al., arXiv:1409.1458) advertises:
# any local solver achieving a Theta-approximate subproblem solution plugs
# into the same aggregation.  Every entry shares one signature:
#
#     solver(w_eff, alpha, X, y, norms_sq, lam, n_global, sigma_prime, key,
#            *, loss, num_steps) -> LocalSolveResult
#
# so protocols can vmap an entry across the worker axis unchanged.
# ---------------------------------------------------------------------------

_SOLVERS = {}


def register_solver(name: str):
    """Decorator (usable as a plain call too): add a local solver under
    ``name`` -- same extension pattern as the protocol/compressor/delay
    registries."""

    def deco(fn):
        _SOLVERS[name] = fn
        return fn

    return deco


register_solver("sdca")(solve_subproblem)
register_solver("importance")(solve_subproblem_importance)
register_solver("accelerated")(solve_subproblem_accelerated)


def available_solvers() -> tuple[str, ...]:
    return tuple(sorted(_SOLVERS))


def get_solver(name: str):
    """Resolve a ``MethodConfig.local_solver`` name; ValueError lists the
    registry on a miss (same error contract as protocols/compressors)."""
    try:
        return _SOLVERS[name]
    except KeyError:
        raise ValueError(
            f"unknown local solver {name!r}; available: {available_solvers()}"
        ) from None

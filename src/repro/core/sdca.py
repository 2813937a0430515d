"""Local SDCA solver for the CoCoA+-style subproblem G_k^{sigma'} (Eq. 7-8).

Each worker k holds a partition ``X_k: (n_k, d)``, ``y_k: (n_k,)`` and, per
round, runs ``H`` sequential stochastic dual coordinate-ascent steps on

    max_{dalpha}  -(1/n) sum_{i in P_k} phi_i*(-(alpha + dalpha)_i)
                  -(1/n) w_eff^T A_k dalpha
                  -(lambda sigma'/2) || (1/(lambda n)) A_k dalpha ||^2

with ``w_eff = w_k + gamma * dw_residual`` (Algorithm 2, line 4) held fixed.
The accumulated local primal delta ``v = (1/(lambda n)) A_k dalpha`` is carried
through the loop so each coordinate step sees the effective margin
``z_i = (w_eff + sigma' * v)^T x_i``.

Closed-form coordinate maximizers:

* ridge:           delta = (y_i - a_i - z_i) / (1 + q_i)
* smoothed hinge:  b* = clip((1 - y z + q_i a_y) / (g + q_i), 0, 1); delta = y (b* - a_y)
* logistic:        Newton on b = y*alpha in (0,1) (8 damped steps)

where ``a_i`` is the current dual value (alpha_i + dalpha_i),
``q_i = sigma' ||x_i||^2 / (lambda n)`` and ``g`` the hinge smoothing.

The plain (single-machine) SDCA of Shalev-Shwartz & Zhang 2013 is the special
case sigma'=1, w_eff=0-initialized global w: see ``sdca_reference`` below,
which the tests use as the convergence oracle.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.objectives import HIGHEST, LossName, _HINGE_SMOOTHING


class LocalSolveResult(NamedTuple):
    delta_alpha: jax.Array  # (n_k,) the raw subproblem solution Delta alpha_[k]
    v: jax.Array  # (d,)  (1/(lambda n)) A_k Delta alpha_[k]


def _coordinate_delta(
    loss: LossName,
    a: jax.Array,  # current dual value alpha_i + dalpha_i
    z: jax.Array,  # effective margin (w_eff + sigma' v)^T x_i
    y: jax.Array,
    q: jax.Array,  # sigma' ||x_i||^2 / (lambda n)
) -> jax.Array:
    """Closed-form/Newton maximizer of the 1-D coordinate subproblem."""
    if loss == "ridge":
        return (y - a - z) / (1.0 + q)
    if loss == "smoothed_hinge":
        g = _HINGE_SMOOTHING
        a_y = y * a
        b = jnp.clip((1.0 - y * z + q * a_y) / (g + q), 0.0, 1.0)
        return y * (b - a_y)
    if loss == "logistic":
        eps = 1e-6
        a_y = jnp.clip(y * a, eps, 1.0 - eps)
        b = a_y
        # Damped Newton on f'(b) = log((1-b)/b) - y z - q (b - a_y).
        for _ in range(8):
            fp = jnp.log1p(-b) - jnp.log(b) - y * z - q * (b - a_y)
            fpp = -1.0 / (b * (1.0 - b)) - q
            b = jnp.clip(b - fp / fpp, eps, 1.0 - eps)
        return y * (b - a_y)
    raise ValueError(f"unknown loss {loss!r}")


@partial(jax.jit, static_argnames=("loss",))
def solve_subproblem_indices(
    w_eff: jax.Array,  # (d,)
    alpha: jax.Array,  # (n_k,) current local dual variables
    X: jax.Array,  # (n_k, d)
    y: jax.Array,  # (n_k,)
    norms_sq: jax.Array,  # (n_k,) precomputed ||x_i||^2
    lam: float,
    n_global: int,
    sigma_prime: float,
    idx: jax.Array,  # (H,) int32 coordinate visit order
    *,
    loss: LossName,
) -> LocalSolveResult:
    """H sequential SDCA steps with an explicit visit order (kernel oracle)."""

    def body(carry, i):
        dalpha, v = carry
        x_i = X[i]
        a_i = alpha[i] + dalpha[i]
        z_i = (jnp.dot(w_eff, x_i, precision=HIGHEST)
               + sigma_prime * jnp.dot(v, x_i, precision=HIGHEST))
        q_i = sigma_prime * norms_sq[i] / (lam * n_global)
        delta = _coordinate_delta(loss, a_i, z_i, y[i], q_i)
        dalpha = dalpha.at[i].add(delta)
        v = v + (delta / (lam * n_global)) * x_i
        return (dalpha, v), None

    init = (jnp.zeros_like(alpha), jnp.zeros_like(w_eff))
    (dalpha, v), _ = jax.lax.scan(body, init, idx)
    return LocalSolveResult(dalpha, v)


def visit_order(key: jax.Array, n_k: int, num_steps: int) -> jax.Array:
    """A local pass's ``num_steps`` coordinates, uniform with replacement."""
    # Explicit dtype: the default follows the x64 flag, and the scan-fused
    # executor traces this under enable_x64 -- int64 draws would consume the
    # PRNG differently and break executor bit-equivalence.
    return jax.random.randint(key, (num_steps,), 0, n_k, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("loss", "num_steps"))
def solve_subproblem(
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
    """H sequential SDCA steps with uniform sampling (Alg. 2 line 4)."""
    idx = visit_order(key, X.shape[0], num_steps)
    return solve_subproblem_indices(
        w_eff, alpha, X, y, norms_sq, lam, n_global, sigma_prime, idx, loss=loss)


def solve_subproblem_all(w_all, alpha, X, y, norms_sq, lam, n_global, sigma_prime,
                         keys, *, loss: LossName, num_steps: int) -> LocalSolveResult:
    """vmapped over the worker axis: all K workers solve simultaneously."""
    fn = partial(solve_subproblem, loss=loss, num_steps=num_steps)
    return jax.vmap(fn, in_axes=(0, 0, 0, 0, 0, None, None, None, 0))(
        w_all, alpha, X, y, norms_sq, lam, n_global, sigma_prime, keys)


@partial(jax.jit, static_argnames=("loss", "num_epochs"))
def sdca_reference(
    X: jax.Array,  # (n, d) single-machine data
    y: jax.Array,  # (n,)
    lam: float,
    key: jax.Array,
    *,
    loss: LossName,
    num_epochs: int,
) -> tuple[jax.Array, jax.Array]:
    """Single-machine SDCA (SSZ'13) oracle: returns (alpha, w).

    This is the K=1, sigma'=1, gamma=1 case with w maintained exactly via the
    primal-dual relation; the distributed methods must converge to the same
    optimum (tests assert this).
    """
    n, d = X.shape
    norms_sq = jnp.sum(X * X, axis=-1)
    idx = jax.random.randint(key, (num_epochs * n,), 0, n, dtype=jnp.int32)

    def body(carry, i):
        alpha, w = carry
        x_i = X[i]
        z_i = jnp.dot(w, x_i, precision=HIGHEST)
        q_i = norms_sq[i] / (lam * n)
        delta = _coordinate_delta(loss, alpha[i], z_i, y[i], q_i)
        alpha = alpha.at[i].add(delta)
        w = w + (delta / (lam * n)) * x_i
        return (alpha, w), None

    (alpha, w), _ = jax.lax.scan(body, (jnp.zeros(n, X.dtype), jnp.zeros(d, X.dtype)), idx)
    return alpha, w

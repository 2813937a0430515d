"""Primal/dual objectives for l2-regularized empirical risk minimization.

The paper (ACPD, Huo & Huang 2019) optimizes

    P(w) = (1/n) sum_i phi_i(w^T x_i) + (lambda/2) ||w||^2          (Eq. 2)

through its Fenchel dual

    D(alpha) = (1/n) sum_i -phi_i*(-alpha_i) - (lambda/2) || (1/(lambda n)) A alpha ||^2   (Eq. 3)

with the primal-dual map  w(alpha) = (1/(lambda n)) A alpha  (Eq. 5) and the
duality gap G(alpha) = P(w(alpha)) - D(alpha) used as the convergence monitor.

Losses implemented (all 1/mu-smooth as required by Assumption 2):

* ``ridge``          phi_i(z) = (z - y_i)^2 / 2            (paper's experiments, Eq. 25)
* ``smoothed_hinge`` phi_i(z) = smoothed hinge with smoothing ``mu`` (Shalev-Shwartz & Zhang 2013)
* ``logistic``       phi_i(z) = log(1 + exp(-y_i z))

Data layout: partitions are stacked, ``X: (K, n_k, d)``, ``y: (K, n_k)``,
mirroring the paper's K workers with evenly partitioned data (n = K * n_k).
A global view is just a reshape.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import tracing

LossName = Literal["ridge", "smoothed_hinge", "logistic"]

# Full f32 products: a TPU runs a DEFAULT-precision f32 dot in one bf16
# pass, and the gap is a small difference of two such sums.
HIGHEST = jax.lax.Precision.HIGHEST

# Smoothing constant for the smoothed hinge (gamma-bar in SSZ'13); phi is
# (1/mu)-smooth with mu == _HINGE_SMOOTHING.
_HINGE_SMOOTHING = 1.0


@dataclasses.dataclass(frozen=True)
class Problem:
    """An l2-regularized ERM instance partitioned over K workers.

    Attributes:
      X: (K, n_k, d) stacked feature partitions (rows are samples).
      y: (K, n_k) labels; +-1 for classification losses, real for ridge.
      lam: l2 regularization strength (lambda in the paper).
      loss: which phi to use.
    """

    X: jax.Array
    y: jax.Array
    lam: float
    loss: LossName = "ridge"

    @property
    def num_workers(self) -> int:
        return self.X.shape[0]

    @property
    def n_per_worker(self) -> int:
        return self.X.shape[1]

    @property
    def n(self) -> int:
        return self.X.shape[0] * self.X.shape[1]

    @property
    def d(self) -> int:
        return self.X.shape[2]

    def global_X(self) -> jax.Array:
        return self.X.reshape(self.n, self.d)

    def global_y(self) -> jax.Array:
        return self.y.reshape(self.n)


# ---------------------------------------------------------------------------
# phi and phi* for each loss.
# Conventions follow the paper: the dual objective sums -phi_i*(-alpha_i), and
# the "dual feasible direction" u_i^t satisfies -u_i^t in d phi_i(w^T x_i).
# ---------------------------------------------------------------------------


def phi(loss: LossName, z: jax.Array, y: jax.Array) -> jax.Array:
    """Pointwise loss phi_i(z) with label y_i."""
    if loss == "ridge":
        return 0.5 * (z - y) ** 2
    if loss == "smoothed_hinge":
        g = _HINGE_SMOOTHING
        m = y * z
        return jnp.where(
            m >= 1.0,
            0.0,
            jnp.where(m <= 1.0 - g, 1.0 - m - 0.5 * g, (1.0 - m) ** 2 / (2.0 * g)),
        )
    if loss == "logistic":
        # log(1 + exp(-y z)) computed stably.
        return jnp.logaddexp(0.0, -y * z)
    raise ValueError(f"unknown loss {loss!r}")


def neg_conj(loss: LossName, alpha: jax.Array, y: jax.Array) -> jax.Array:
    """-phi_i*(-alpha_i): the per-sample term of the dual objective (Eq. 3).

    For ridge (Eq. 25):          alpha*y - alpha^2/2
    For smoothed hinge:          y*alpha - (mu/2) alpha^2   on y*alpha in [0,1], -inf outside
    For logistic:                -(a log a + (1-a) log(1-a)) with a = y*alpha in (0,1)
    """
    if loss == "ridge":
        return alpha * y - 0.5 * alpha**2
    if loss == "smoothed_hinge":
        g = _HINGE_SMOOTHING
        a = y * alpha
        feasible = (a >= 0.0) & (a <= 1.0)
        val = a - 0.5 * g * a**2
        return jnp.where(feasible, val, -jnp.inf)
    if loss == "logistic":
        a = y * alpha
        eps = 1e-12
        a = jnp.clip(a, eps, 1.0 - eps)
        ent = -(a * jnp.log(a) + (1.0 - a) * jnp.log1p(-a))
        feasible = (y * alpha > 0.0) & (y * alpha < 1.0)
        return jnp.where(feasible, ent, -jnp.inf)
    raise ValueError(f"unknown loss {loss!r}")


def dual_feasible_direction(loss: LossName, z: jax.Array, y: jax.Array) -> jax.Array:
    """u_i with -u_i in d phi_i(z_i); used by the gap analysis and tests."""
    if loss == "ridge":
        return -(z - y)
    if loss == "smoothed_hinge":
        g = _HINGE_SMOOTHING
        m = y * z
        grad = jnp.where(m >= 1.0, 0.0, jnp.where(m <= 1.0 - g, -1.0, (m - 1.0) / g)) * y
        return -grad
    if loss == "logistic":
        grad = -y * jax.nn.sigmoid(-y * z)
        return -grad
    raise ValueError(f"unknown loss {loss!r}")


def smoothness_mu(loss: LossName) -> float:
    """phi is (1/mu)-smooth; returns mu (strong-convexity constant of phi*)."""
    if loss == "ridge":
        return 1.0
    if loss == "smoothed_hinge":
        return _HINGE_SMOOTHING
    if loss == "logistic":
        return 4.0
    raise ValueError(f"unknown loss {loss!r}")


# ---------------------------------------------------------------------------
# Objectives.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("loss",))
def primal_objective(w: jax.Array, X: jax.Array, y: jax.Array, lam: float, *, loss: LossName) -> jax.Array:
    """P(w) over stacked partitions X:(K,n_k,d), y:(K,n_k)."""
    z = jnp.einsum("knd,d->kn", X, w, precision=HIGHEST)
    n = z.size
    return (jnp.sum(phi(loss, z, y)) / n
            + 0.5 * lam * jnp.vdot(w, w, precision=HIGHEST))


@partial(jax.jit, static_argnames=("loss",))
def dual_objective(alpha: jax.Array, X: jax.Array, y: jax.Array, lam: float, *, loss: LossName) -> jax.Array:
    """D(alpha) over stacked partitions, alpha:(K,n_k)."""
    n = alpha.size
    w_alpha = primal_from_dual(alpha, X, lam)
    return (jnp.sum(neg_conj(loss, alpha, y)) / n
            - 0.5 * lam * jnp.vdot(w_alpha, w_alpha, precision=HIGHEST))


@jax.jit
def primal_from_dual(alpha: jax.Array, X: jax.Array, lam: float) -> jax.Array:
    """w(alpha) = (1/(lambda n)) A alpha  (Eq. 5), A = [x_1 .. x_n] in R^{d x n}."""
    n = alpha.size
    return jnp.einsum("knd,kn->d", X, alpha, precision=HIGHEST) / (lam * n)


@partial(jax.jit, static_argnames=("loss",))
def duality_gap(alpha: jax.Array, X: jax.Array, y: jax.Array, lam: float, *, loss: LossName) -> jax.Array:
    """G(alpha) = P(w(alpha)) - D(alpha) >= 0; the paper's convergence monitor."""
    w_alpha = primal_from_dual(alpha, X, lam)
    return primal_objective(w_alpha, X, y, lam, loss=loss) - dual_objective(alpha, X, y, lam, loss=loss)


def gap_certificate(problem: Problem, alpha: jax.Array, w: jax.Array | None = None) -> dict[str, float]:
    """Convenience: all monitored quantities for logging/benchmarks.

    If ``w`` (e.g. the server's sparsified model) is given, also reports
    P(w_server) - D(alpha), which is what a deployed system would monitor when
    the exact primal-dual relation is broken by the practical filter variant.
    """
    X, y, lam, loss = problem.X, problem.y, problem.lam, problem.loss
    w_alpha = primal_from_dual(alpha, X, lam)
    p = primal_objective(w_alpha, X, y, lam, loss=loss)
    dv = dual_objective(alpha, X, y, lam, loss=loss)
    values = {"primal": p, "dual": dv, "gap": p - dv}
    if w is not None:
        p_srv = primal_objective(w, X, y, lam, loss=loss)
        values["primal_server"] = p_srv
        values["gap_server"] = p_srv - dv
    # Every dispatch is queued before the first blocking read.
    with tracing.span("repro.certificate.sync"):
        return {k: tracing.host_read(v) for k, v in values.items()}

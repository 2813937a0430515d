"""The benchmark's dataset generator: LIBSVM-shaped sparse rows from a seed.

Same semantics as the repository's ``data/synthetic.make_linear_problem``,
vectorised over rows so that RCV1 and URL widths build in a second:

* feature popularity follows a Zipf law, ``p_j ~ 1 / j**zipf_exponent``;
* each row draws ``max(min_nnz, Poisson(nnz_per_row))`` distinct features
  without replacement, proportional to ``p`` (successive sampling: draws
  with replacement in order, repeats skipped);
* values are standard normal, then every row is scaled to unit norm
  (Assumption 1 of the paper, ``||x_i|| = 1``);
* labels are ``sign(x_i . w*)`` for a ``w*`` supported on ``d // 64``
  uniformly chosen features, each flipped with probability ``label_noise``.

The rows are i.i.d., so the original's shuffle is a no-op and worker ``k``
holds rows ``k * n_k .. (k + 1) * n_k - 1``.

The host draws only the sparse triplets (``cols``, ``vals``: a few MB).  The
dense ``(K, n_k, d)`` array the program stores is built on the device by one
jitted scatter, so no dense copy of ``X`` ever crosses from host to device.
The plain reference (:mod:`bench.reference`) reads the same triplets.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sparse:
    """Rows in padded form: ``cols``/``vals`` are ``(n, width)``; padding and
    skipped repeats hold column 0 with value 0, which adds nothing."""

    cols: np.ndarray  # int32
    vals: np.ndarray  # float32, rows of unit norm
    y: np.ndarray  # float32, +-1
    d: int

    @property
    def n(self) -> int:
        return self.cols.shape[0]

    def nnz_per_row(self) -> np.ndarray:
        return np.count_nonzero(self.vals, axis=1)


def shape_of(config: dict) -> tuple[int, int, int]:
    """``(K, n_k, d)`` of a configuration file."""
    ds = config["dataset"]
    K = config["cluster"]["workers"]
    rows = ds["rows"]
    if rows % K:
        raise ValueError(f"{config['name']}: {rows} rows do not split over "
                         f"{K} workers")
    return K, rows // K, ds["features"]


def row_width(nnz_per_row: float) -> int:
    """Padded row width: the Poisson mean plus eight standard deviations,
    rounded up to a multiple of 8 (a longer row has odds under 1e-15)."""
    return 8 * math.ceil((nnz_per_row + 8 * math.sqrt(nnz_per_row) + 8) / 8)


def generate(config: dict, seed: int) -> Sparse:
    """The configuration's rows and labels, drawn from ``seed``."""
    ds = config["dataset"]
    _, _, d = shape_of(config)
    n = ds["rows"]
    lam_nnz = float(ds["nnz_per_row"])
    width = row_width(lam_nnz)
    draws = width + width // 2  # repeats are skipped, so draw spare
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0xDA7A]))

    popularity = 1.0 / np.arange(1, d + 1, dtype=np.float64) ** ds[
        "zipf_exponent"]
    cdf = np.cumsum(popularity)
    cdf /= cdf[-1]
    lengths = np.minimum(np.maximum(ds["min_nnz"],
                                    rng.poisson(lam_nnz, n)), width)
    drawn = np.minimum(np.searchsorted(cdf, rng.random((n, draws)),
                                       side="right"), d - 1)

    # First occurrence of each feature in draw order, then the first
    # ``lengths[i]`` of those: sampling without replacement.
    order = np.argsort(drawn, axis=1, kind="stable")
    ranked = np.take_along_axis(drawn, order, axis=1)
    first_sorted = np.ones_like(ranked, dtype=bool)
    first_sorted[:, 1:] = ranked[:, 1:] != ranked[:, :-1]
    first = np.empty_like(first_sorted)
    np.put_along_axis(first, order, first_sorted, axis=1)
    slot = np.cumsum(first, axis=1) - 1
    keep = first & (slot < lengths[:, None])
    r, c = np.nonzero(keep)
    cols = np.zeros((n, width), np.int32)
    cols[r, slot[r, c]] = drawn[r, c]
    mask = np.zeros((n, width), bool)
    mask[r, slot[r, c]] = True

    vals = rng.standard_normal((n, width)) * mask
    vals /= np.linalg.norm(vals, axis=1, keepdims=True)
    vals = vals.astype(np.float32)

    support = rng.choice(d, size=max(8, d // 64), replace=False)
    w_star = np.zeros(d)
    w_star[support] = rng.standard_normal(support.size)
    margin = np.sum(vals * w_star[cols], axis=1)
    y = np.sign(margin + 1e-9)
    y[rng.random(n) < ds["label_noise"]] *= -1.0
    y[y == 0] = 1.0
    return Sparse(cols=cols, vals=vals, y=y.astype(np.float32), d=d)


def device_arrays(sparse: Sparse, K: int, n_k: int, device=None):
    """``(X, y)`` on the device in the program's layout: ``X`` dense
    ``(K, n_k, d)`` float32 scattered from the triplets in one jitted call,
    ``y`` ``(K, n_k)``."""
    import jax
    import jax.numpy as jnp

    d = sparse.d

    @jax.jit
    def dense(cols, vals):
        rows = jnp.broadcast_to(jnp.arange(cols.shape[0])[:, None],
                                cols.shape)
        X = jnp.zeros((K * n_k, d), jnp.float32).at[rows, cols].add(vals)
        return X.reshape(K, n_k, d)

    cols = jax.device_put(sparse.cols, device)
    vals = jax.device_put(sparse.vals, device)
    X = dense(cols, vals)
    y = jax.device_put(sparse.y.reshape(K, n_k), device)
    return X, y

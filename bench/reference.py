"""The plain reference: the ridge certificate in float64 from the sparse rows.

It imports nothing of the program and reads nothing the program made except
the answers under test (a dual vector ``alpha``, a primal vector ``w`` and
the numbers the program reported about them).  It works on the benchmark's
own triplets (:class:`bench.data.Sparse`), not on the dense array the
program holds, and in float64 on the host:

    w(alpha) = (1 / (lam n)) sum_i alpha_i x_i                   (Eq. 5)
    P(w)     = (1/n) sum_i (x_i . w - y_i)^2 / 2 + (lam/2) ||w||^2
    D(alpha) = (1/n) sum_i (alpha_i y_i - alpha_i^2 / 2)
               - (lam/2) ||w(alpha)||^2                          (Eq. 3, 25)

``control=True`` computes the same quantities as a float32 program whose
dots run at a TPU's ``Precision.HIGH`` does: each product taken from
operands rounded to float32 and split into two bfloat16 parts, the
low-by-low product dropped, and every sum accumulated in float32.  That is
the control of the correctness check: the certificate one precision step
below the ``highest`` that the configurations state.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def _split_bf16(x) -> tuple[np.ndarray, np.ndarray]:
    """float32 -> (hi, lo) bfloat16 parts, as float64 values."""
    import ml_dtypes

    x = np.asarray(x, np.float32)
    hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    return hi.astype(np.float64), lo.astype(np.float64)


def _mul(a, b, control: bool) -> np.ndarray:
    """Elementwise a*b: float64, or a bf16x3 (``HIGH``) product in float32."""
    if not control:
        return np.asarray(a, np.float64) * np.asarray(b, np.float64)
    a_hi, a_lo = _split_bf16(a)
    b_hi, b_lo = _split_bf16(b)
    return (a_hi * b_hi + a_hi * b_lo + a_lo * b_hi).astype(np.float32)


def _acc(control: bool):
    return np.float32 if control else np.float64


@dataclasses.dataclass(frozen=True)
class Certificate:
    primal: float  # P(w(alpha))
    dual: float  # D(alpha)
    gap: float  # P(w(alpha)) - D(alpha)
    w_alpha: np.ndarray  # w(alpha)


def primal_from_dual(sparse, lam: float, alpha, control: bool = False):
    """w(alpha) for ``alpha`` in row order (any shape with n entries)."""
    alpha = np.asarray(alpha).reshape(sparse.n, 1)
    contrib = _mul(sparse.vals, alpha, control)
    if control:
        w = np.zeros(sparse.d, np.float32)
        np.add.at(w, sparse.cols.ravel(), contrib.ravel())
        return w / np.float32(lam * sparse.n)
    w = np.bincount(sparse.cols.ravel(), weights=contrib.ravel(),
                    minlength=sparse.d)
    return w / (lam * sparse.n)


def primal(sparse, lam: float, w, control: bool = False) -> float:
    """P(w) for the ridge loss."""
    acc = _acc(control)
    w = np.asarray(w, acc)
    z = np.sum(_mul(sparse.vals, w[sparse.cols], control), axis=1, dtype=acc)
    reg = np.sum(_mul(w, w, control), dtype=acc)
    loss = np.mean(acc(0.5) * (z - sparse.y.astype(acc)) ** 2, dtype=acc)
    return float(loss + acc(0.5 * lam) * reg)


def certificate(sparse, lam: float, alpha, control: bool = False
                ) -> Certificate:
    """The duality-gap certificate of ``alpha`` (ridge loss)."""
    acc = _acc(control)
    alpha = np.asarray(alpha, acc).reshape(sparse.n)
    w_alpha = primal_from_dual(sparse, lam, alpha, control)
    p = primal(sparse, lam, w_alpha, control)
    y = sparse.y.astype(acc)
    dv = float(np.mean(alpha * y - acc(0.5) * alpha ** 2, dtype=acc)
               - acc(0.5 * lam) * np.sum(_mul(w_alpha, w_alpha, control),
                                         dtype=acc))
    return Certificate(primal=p, dual=dv, gap=p - dv, w_alpha=w_alpha)


def initial_gap(sparse) -> float:
    """The gap at alpha = 0, w = 0: P(0) - D(0) = mean(y^2) / 2."""
    return float(np.mean(np.asarray(sparse.y, np.float64) ** 2) / 2)

"""Cross-implementation top-k agreement (no hypothesis required).

Three implementations of the paper's message filter must agree with exact
top-k on tie-free inputs:

* ``core.filter.topk_mask_exact``  -- jnp oracle (sort-based, exact by
  construction; included so every case exercises the shared contract);
* ``core.exchange.threshold_for_topk`` -- two-round histogram threshold used
  by the deep-net exchange layer;
* ``kernels.ops.topk_filter``      -- the Pallas histogram-select kernel
  (through the Pallas interpreter here).

The histogram implementations resolve magnitudes to one refined bucket
(~0.4% ratio), so the shared cases use ladder magnitudes with pairwise gaps
of >= 0.6% -- unambiguous for every implementation, including after bfloat16
quantization (eps = 2^-8 ~ 0.39%) -- with random signs and order.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exchange
from repro.core import filter as flt
from repro.kernels import ops

# (d, k, seed): shared across all three implementations.
CASES = [
    (257, 1, 0),
    (257, 16, 1),
    (1024, 8, 2),
    (1024, 200, 3),
    (2048, 64, 4),
    (2048, 1024, 5),
]
DTYPES = [jnp.float32, jnp.bfloat16]
_IDS = [f"d{d}-k{k}" for d, k, _ in CASES]


def _tie_free_input(d: int, seed: int, dtype) -> jnp.ndarray:
    """Geometric magnitude ladder, shuffled with random signs.

    The pairwise gap must clear bfloat16's worst-case quantum (2^-7 ~ 0.78%
    just below a power of two) so the values stay distinct after rounding,
    while the total dynamic range stays within the histogram filters' 2^-22
    selection floor for every k we test -- hence the exponent range grows
    with d (gap ~ 2*r/d in log2) but is capped at +-12.
    """
    rng = np.random.default_rng(seed)
    r = min(12.0, 0.0065 * d)
    exponents = np.linspace(-r, r, d)
    mags = np.exp2(exponents).astype(np.float32)
    signs = rng.choice([-1.0, 1.0], size=d).astype(np.float32)
    x = rng.permutation(mags * signs)
    out = jnp.asarray(x).astype(dtype)
    # sanity: the construction really is tie-free at this dtype
    assert len(np.unique(np.abs(np.asarray(out, np.float32)))) == d
    return out


def _exact_topk_indices(x: jnp.ndarray, k: int) -> set[int]:
    mags = np.abs(np.asarray(x, np.float32))
    return set(np.argsort(-mags)[:k].tolist())


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("d,k,seed", CASES, ids=_IDS)
def test_threshold_for_topk_matches_exact(d, k, seed, dtype):
    x = _tie_free_input(d, seed, dtype)
    t = exchange.threshold_for_topk(x, jnp.int32(k))
    kept = np.flatnonzero(np.abs(np.asarray(x, np.float32)) >= float(t))
    assert set(kept.tolist()) == _exact_topk_indices(x, k)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("d,k,seed", CASES, ids=_IDS)
def test_kernel_topk_filter_matches_exact(d, k, seed, dtype):
    x = _tie_free_input(d, seed, dtype)
    sent, resid, mask = ops.topk_filter(x, k, interpret=True)
    kept = set(np.flatnonzero(np.asarray(mask)).tolist())
    assert kept == _exact_topk_indices(x, k)
    # conservation is part of the shared contract
    assert bool(jnp.all(sent + resid == x))


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("d,k,seed", CASES, ids=_IDS)
def test_jnp_oracle_matches_exact(d, k, seed, dtype):
    x = _tie_free_input(d, seed, dtype)
    res = flt.topk_mask_exact(x, k)
    kept = set(np.flatnonzero(np.asarray(res.mask)).tolist())
    assert kept == _exact_topk_indices(x, k)
    assert bool(jnp.all(res.sent + res.residual == x))

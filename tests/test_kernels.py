"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + properties.

The kernels run through the Pallas interpreter here (``interpret=True``);
tests/test_tpu_compile.py compiles them for the TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# topk_filter kernel.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d", [257, 1024, 4096, 50000])
@pytest.mark.parametrize("k_frac", [0.001, 0.02, 0.25])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_kernel_sweep(d, k_frac, dtype):
    rng = np.random.default_rng(d)
    k = max(1, int(k_frac * d))
    x = jnp.asarray(rng.standard_normal(d).astype(np.float32)).astype(dtype)
    sent, resid, mask = ops.topk_filter(x, k, interpret=True)
    s_ref, r_ref, m_ref = ref.topk_filter_ref(x, k)
    # exact contracts
    assert int(mask.sum()) == k
    assert bool(jnp.all(sent + resid == x))  # bitwise conservation
    # value contract: kept mass within one refined bucket of exact top-k
    mass = float(jnp.abs(sent.astype(jnp.float32)).sum())
    mass_ref = float(jnp.abs(s_ref.astype(jnp.float32)).sum())
    assert mass >= 0.999 * mass_ref


@settings(max_examples=15, deadline=None)
@given(st.integers(64, 3000), st.integers(0, 2**31 - 1))
def test_topk_kernel_property(d, seed):
    rng = np.random.default_rng(seed)
    k = max(1, d // 17)
    x = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    sent, resid, mask = ops.topk_filter(x, k, interpret=True)
    assert int(mask.sum()) == k
    assert bool(jnp.all(sent + resid == x))
    kept_min = float(jnp.min(jnp.where(mask, jnp.abs(x), jnp.inf)))
    drop_max = float(jnp.max(jnp.where(mask, 0.0, jnp.abs(x))))
    # banded contract: kept >= dropped up to one refined bucket. The ladder
    # spans 2^22 in 64 buckets, so the refined bucket ratio is
    # exp(ln(2^22)/63^2) ~ 1.004 -> allow 0.6%.
    assert kept_min >= drop_max * (1 - 6e-3) - 1e-6


def test_topk_kernel_few_nonzeros():
    """k above the number of non-negligible entries: keep what exists."""
    x = jnp.zeros(2048).at[jnp.array([3, 500, 1999])].set(
        jnp.array([1.0, -2.0, 0.5]))
    sent, resid, mask = ops.topk_filter(x, 100, interpret=True)
    assert int(mask.sum()) <= 100
    kept = set(np.flatnonzero(np.asarray(sent)).tolist())
    assert {3, 500, 1999} <= kept
    assert bool(jnp.all(sent + resid == x))


# ---------------------------------------------------------------------------
# sdca_inner kernel.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("K,n_k,d,H", [(1, 32, 128, 64), (4, 64, 256, 200),
                                       (3, 128, 512, 150), (8, 16, 1024, 50)])
def test_sdca_kernel_sweep(K, n_k, d, H):
    rng = np.random.default_rng(K * 1000 + n_k)
    X = jnp.asarray(rng.standard_normal((K, n_k, d)).astype(np.float32)) / np.sqrt(d)
    y = jnp.asarray(np.sign(rng.standard_normal((K, n_k))).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((K, d)).astype(np.float32) * 0.1)
    alpha = jnp.asarray(rng.standard_normal((K, n_k)).astype(np.float32) * 0.05)
    norms = jnp.sum(X * X, axis=-1)
    idx = jnp.asarray(rng.integers(0, n_k, (K, H)).astype(np.int32))
    lam, n, sp = 1e-3, K * n_k, 2.0
    da_k, v_k = ops.sdca_epoch(w, alpha, X, y, norms, lam, n, sp, idx,
                              interpret=True)
    da_r, v_r = ref.sdca_inner_ref(w, alpha, X, y, norms, lam, n, sp, idx)
    np.testing.assert_allclose(np.asarray(da_k), np.asarray(da_r),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_r),
                               rtol=1e-5, atol=1e-6)


def test_sdca_kernel_capacity_fallback():
    """Over-VMEM partitions and non-ridge losses raise with the reason; the
    jnp oracle is called by name for them."""
    K, n_k, d, H = 1, 64, 70000, 8  # n_k * d over the VMEM budget
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((K, n_k, d)).astype(np.float32)) * 0.01
    y = jnp.ones((K, n_k), jnp.float32)
    norms = jnp.sum(X * X, axis=-1)
    idx = jnp.zeros((K, H), jnp.int32)
    args = (jnp.zeros((K, d)), jnp.zeros((K, n_k)), X, y, norms, 1e-3, 64,
            1.0, idx)
    with pytest.raises(ValueError, match="VMEM"):
        ops.sdca_epoch(*args, interpret=True)
    with pytest.raises(ValueError, match="ridge only"):
        ops.sdca_epoch(*args, loss="logistic", interpret=True)
    with pytest.raises(ValueError, match="SMEM"):
        ops.sdca_epoch(jnp.zeros((K, 128)), jnp.zeros((K, 64)), X[:, :, :128],
                       y, norms, 1e-3, 64, 1.0,
                       jnp.zeros((K, 200_000), jnp.int32), interpret=True)
    da, v = ref.sdca_inner_ref(*args)
    assert np.isfinite(np.asarray(da)).all()

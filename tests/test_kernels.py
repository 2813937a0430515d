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
# sdca_inner kernel: one worker's pass against the stacked X.
# ---------------------------------------------------------------------------

# The kernel sums the two dot products in another order than the jnp path;
# both are float32, so they agree to a few ulps of each step, compounded
# over H steps.
SDCA_RTOL, SDCA_ATOL = 1e-5, 1e-6


def _sdca_operands(K, n_k, d, H, seed, *, repeats=False):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.standard_normal((K, n_k, d)).astype(np.float32)) / np.sqrt(d)
    y = jnp.asarray(np.sign(rng.standard_normal((K, n_k))).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((K, d)).astype(np.float32) * 0.1)
    alpha = jnp.asarray(rng.standard_normal((K, n_k)).astype(np.float32) * 0.05)
    norms = jnp.sum(X * X, axis=-1)
    idx = rng.integers(0, n_k, (K, H)).astype(np.int32)
    if repeats:  # every coordinate visited back to back, then again later
        idx[:, 1::2] = idx[:, 0::2][:, :idx[:, 1::2].shape[1]]
    return X, y, w, alpha, norms, jnp.asarray(idx)


# (K, n_k, d, H): d off the 128 lanes, n_k off the 8 sublanes, K >= 3, H
# over n_k; (16, 13) and (8, 13) are tiled by worker, the rest by row.
SDCA_SHAPES = [(1, 32, 128, 64), (4, 64, 256, 200), (3, 128, 512, 150),
               (8, 16, 1024, 50), (3, 13, 1000, 40), (5, 21, 4999, 30),
               (16, 13, 300, 40), (8, 13, 640, 100), (3, 5, 200, 12)]


@pytest.mark.parametrize("loss", ["ridge", "smoothed_hinge"])
@pytest.mark.parametrize("K,n_k,d,H", SDCA_SHAPES)
def test_sdca_kernel_sweep(K, n_k, d, H, loss):
    """ops.sdca_epoch (one kernel call per worker) against the vmapped jnp
    path, every worker of the stacked X."""
    X, y, w, alpha, norms, idx = _sdca_operands(K, n_k, d, H, K * 1000 + n_k)
    if loss == "smoothed_hinge":
        alpha = jnp.clip(alpha * y, 0.0, 1.0) * y  # feasible duals
    lam, n, sp = 1e-3, K * n_k, 2.0
    da_k, v_k = ops.sdca_epoch(w, alpha, X, y, norms, lam, n, sp, idx,
                               loss=loss, interpret=True)
    da_r, v_r = ref.sdca_inner_ref(w, alpha, X, y, norms, lam, n, sp, idx,
                                   loss=loss)
    np.testing.assert_allclose(np.asarray(da_k), np.asarray(da_r),
                               rtol=SDCA_RTOL, atol=SDCA_ATOL)
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_r),
                               rtol=SDCA_RTOL, atol=SDCA_ATOL)


@pytest.mark.parametrize("loss", ["ridge", "smoothed_hinge"])
@pytest.mark.parametrize("K,n_k,d,H,k", [
    (3, 13, 1000, 40, 2), (5, 21, 4999, 60, 3), (16, 13, 300, 50, 11),
    (4, 24, 256, 90, 1)])
@pytest.mark.parametrize("repeats", [False, True], ids=["uniform", "repeats"])
def test_sdca_pass_matches_jnp_path(loss, K, n_k, d, H, k, repeats):
    """Worker k's kernel pass equals solve_subproblem_indices on X[k] with
    the same visit order, repeated coordinates and H > n_k included."""
    from repro.core.sdca import solve_subproblem_indices
    from repro.kernels.sdca_inner import sdca_pass

    X, y, w, alpha, norms, idx = _sdca_operands(K, n_k, d, H, 7 * k + H,
                                                repeats=repeats)
    if loss == "smoothed_hinge":
        alpha = jnp.clip(alpha * y, 0.0, 1.0) * y  # feasible duals
    lam, n, sp = 1e-3, K * n_k, 2.0
    da, v = sdca_pass(w[k], alpha[k], X, k, y[k], norms[k], lam, n, sp,
                      idx[k], loss=loss, interpret=True)
    da_r, v_r = solve_subproblem_indices(w[k], alpha[k], X[k], y[k],
                                         norms[k], lam, n, sp, idx[k],
                                         loss=loss)
    np.testing.assert_allclose(np.asarray(da), np.asarray(da_r),
                               rtol=SDCA_RTOL, atol=SDCA_ATOL)
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_r),
                               rtol=SDCA_RTOL, atol=SDCA_ATOL)


def test_sdca_pass_vmapped():
    """Under vmap over (w_eff, k, visit order) with X shared, each
    element's pass is its own (Pallas loops over the batch; a lag sweep
    vmapped over one cell traces the kernel so)."""
    from repro.core.sdca import solve_subproblem_indices
    from repro.kernels.sdca_inner import sdca_pass

    K, n_k, d, H = 4, 16, 256, 30
    X, y, w, alpha, norms, idx = _sdca_operands(K, n_k, d, H, 3)
    lam, n, sp = 1e-3, K * n_k, 2.0
    ks = jnp.array([1, 3, 3], jnp.int32)
    ws, idxs = w[:3], idx[:3]
    da, v = jax.vmap(lambda w_, k_, i_: sdca_pass(
        w_, alpha[k_], X, k_, y[k_], norms[k_], lam, n, sp, i_,
        interpret=True))(ws, ks, idxs)
    for b, k in enumerate(ks.tolist()):
        da_r, v_r = solve_subproblem_indices(ws[b], alpha[k], X[k], y[k],
                                             norms[k], lam, n, sp, idxs[b],
                                             loss="ridge")
        np.testing.assert_allclose(np.asarray(da[b]), np.asarray(da_r),
                                   rtol=SDCA_RTOL, atol=SDCA_ATOL)
        np.testing.assert_allclose(np.asarray(v[b]), np.asarray(v_r),
                                   rtol=SDCA_RTOL, atol=SDCA_ATOL)


@pytest.mark.parametrize("platform,loss,n_k,d,H,expect", [
    ("tpu", "ridge", 1265, 47_236, 1265, True),  # rcv1: 3.4 MB VMEM
    ("tpu", "smoothed_hinge", 1265, 47_236, 1265, True),
    ("tpu", "logistic", 1265, 47_236, 1265, False),  # Newton, no closed form
    ("tpu", "ridge", 32, 3_231_961, 32, False),  # URL: 233 MB of VMEM
    ("cpu", "ridge", 1265, 47_236, 1265, False),
    ("tpu", "ridge", 20_000, 1024, 40_001, False),  # SMEM: 120,001 scalars
    ("tpu", "ridge", 20_000, 1024, 40_000, True),
])
def test_solve_kernel_applies(platform, loss, n_k, d, H, expect):
    from repro.kernels import sdca_inner

    assert sdca_inner.applies(platform, loss, n_k, d, H) is expect


@pytest.mark.parametrize("cells,expect", [(1, True), (2, False), (64, False)])
def test_solve_kernel_fits_one_cell_at_a_time(cells, expect):
    """A pass vmapped over several runs (a lag sweep's cells) keeps the
    vectorised jnp pass: the kernel would run the cells one by one."""
    from repro.kernels import sdca_inner

    assert sdca_inner.fits("ridge", 1265, 47_236, 1265, cells) is expect
    why = sdca_inner.refusal("ridge", 1265, 47_236, 1265, cells)
    assert (why is None) is expect
    if not expect:
        assert f"vmapped over {cells} runs" in why


def test_sdca_kernel_capacity_fallback():
    """Over-VMEM widths, over-SMEM passes and losses without a closed-form
    step raise with the reason; the jnp oracle is called by name for them."""
    K, n_k, d, H = 1, 8, 400_000, 8  # w_eff, v and two 8-row buffers > 24 MiB
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((K, n_k, d)).astype(np.float32)) * 0.01
    y = jnp.ones((K, n_k), jnp.float32)
    norms = jnp.sum(X * X, axis=-1)
    idx = jnp.zeros((K, H), jnp.int32)
    args = (jnp.zeros((K, d)), jnp.zeros((K, n_k)), X, y, norms, 1e-3, 8,
            1.0, idx)
    with pytest.raises(ValueError, match="VMEM"):
        ops.sdca_epoch(*args, interpret=True)
    with pytest.raises(ValueError, match="closed-form"):
        ops.sdca_epoch(*args, loss="logistic", interpret=True)
    with pytest.raises(ValueError, match="SMEM"):
        ops.sdca_epoch(jnp.zeros((K, 128)), jnp.zeros((K, n_k)),
                       X[:, :, :128], y, norms, 1e-3, 8, 1.0,
                       jnp.zeros((K, 200_000), jnp.int32), interpret=True)
    da, v = ref.sdca_inner_ref(*args)
    assert np.isfinite(np.asarray(da)).all()

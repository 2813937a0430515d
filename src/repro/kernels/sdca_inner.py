"""Pallas TPU kernel for one worker's local SDCA pass (Algorithm 2, line 4).

Runs the H sequential coordinate steps of
:func:`repro.core.sdca.solve_subproblem_indices` for worker ``k`` as ONE
kernel invocation, reading its rows straight out of the stacked data:

    operands: X (K, n_k, d)               HBM, never copied or sliced
              k, idx (H,)                 SMEM (scalar prefetch)
              alpha_k, y_k, norms_k       SMEM (n_k,)
              w_eff (1, d)                VMEM, resident for the pass
    outputs : dalpha (n_k,) SMEM,  v (1, d) VMEM, resident for the pass
    step h  : i = idx[h]
              z      = w_eff . x_i + sigma' (v . x_i)
              delta  = the loss's closed-form coordinate step
              dalpha[i] += delta ;  v += delta / (lambda n) * x_i

Each step reads ``dalpha[i]`` after earlier steps wrote it, so visit orders
with repeated coordinates (sampling with replacement) are exact.  The rows
arrive by DMA into two row buffers: row h+1 is fetched while step h
computes.  Products and sums are f32 on the VPU (what ``precision=HIGHEST``
asks of the jnp path); the result equals the jnp path's up to summation
order.

A DMA cannot cut one row out of the (8, 128)-tiled HBM array, so a fetch
moves the whole tiles that hold row ``(k, i)``: its 8-row tile block,
lanes padded to whole tiles (padding is never read), and the step reads
the row's sublane.  XLA stores X in whole tiles, and Mosaic types the
operand so, with the padding inside its bounds; it refuses a block that
starts off a tile (a start clamped to the last 8 logical rows does not
compile).  The interpreter is given X padded the same way
(:func:`_tile_padded`), so it fetches what the chip fetches.  Which axis
is tiled follows X's layout: the TPU tiles the stacked axis with less
padding (:func:`_tiled_by_worker`), so the kernel views X with that axis
second and XLA passes it without a copy.

:func:`applies` is the one test of whether a pass runs here: the platform
is a TPU, the loss has a closed-form step, the VMEM and SMEM sets fit
their budgets, and the pass is not vmapped over several runs (a sweep's
cells).  Everything else runs the jnp path unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sdca import LocalSolveResult, _coordinate_delta

SUBLANES, LANES = 8, 128
# Losses whose coordinate step is closed form (logistic's Newton loop is not).
LOSSES = ("ridge", "smoothed_hinge")
# The scoped-VMEM limit the kernel sets, and the budget of its VMEM set
# (w_eff, v and the two row buffers) under it.  v5e has 128 MiB of VMEM;
# tests/test_tpu_compile.py compiles the widest admitted d.
VMEM_LIMIT_BYTES = 32 * 2**20
VMEM_BUDGET_BYTES = 24 * 2**20
# SMEM budget in 4-byte scalars: the visit order plus alpha, y, norms and
# dalpha.  v5e has 1 MiB of SMEM.
SMEM_BUDGET = 120_000


def _padded(d: int) -> int:
    return -(-d // LANES) * LANES


def vmem_bytes(d: int) -> int:
    """VMEM of the pass: w_eff and v, one lane-padded row each, and two
    (8, padded d) row buffers."""
    return 4 * (2 + 2 * SUBLANES) * _padded(d)


def smem_scalars(n_k: int, steps: int) -> int:
    """SMEM scalars of the pass: the visit order and four (n_k,) vectors."""
    return steps + 4 * n_k


def refusal(loss: str, n_k: int, d: int, steps: int,
            cells: int = 1) -> str | None:
    """Why a pass of ``steps`` steps over a (n_k, d) block, vmapped over
    ``cells`` runs, cannot run on the kernel, or None where it can."""
    if loss not in LOSSES:
        return (f"the SDCA kernel needs a closed-form step {LOSSES}, got "
                f"loss {loss!r}")
    if vmem_bytes(d) > VMEM_BUDGET_BYTES:
        return (f"d={d} needs {vmem_bytes(d)} bytes of VMEM for w_eff, v "
                f"and the row buffers, over the kernel's budget of "
                f"{VMEM_BUDGET_BYTES}")
    if smem_scalars(n_k, steps) > SMEM_BUDGET:
        return (f"H={steps} steps over n_k={n_k} coordinates need H + 4 * "
                f"n_k = {smem_scalars(n_k, steps)} SMEM scalars, over the "
                f"kernel's budget of {SMEM_BUDGET}")
    if cells > 1:
        return (f"a pass vmapped over {cells} runs would run them on the "
                f"kernel one after another (Pallas loops over a batched "
                f"scalar-prefetch operand), where the jnp pass runs them as "
                f"one vectorised pass")
    return None


def fits(loss: str, n_k: int, d: int, steps: int, cells: int = 1) -> bool:
    """The shape and loss half of :func:`applies`, known at trace time;
    ``cells`` is how many runs the caller vmaps the pass over."""
    return refusal(loss, n_k, d, steps, cells) is None


def applies(platform: str, loss: str, n_k: int, d: int, steps: int) -> bool:
    """Whether a local pass of ``steps`` steps over a (n_k, d) block runs on
    this kernel, on the platform ``platform`` the data lives on.  The traced
    round asks :func:`fits` and takes its TPU branch by
    ``lax.platform_dependent``; the host asks here."""
    return platform == "tpu" and fits(loss, n_k, d, steps)


def _tiled_by_worker(K: int, n_k: int) -> bool:
    """Whether the TPU's default layout of a (K, n_k, d) f32 array tiles its
    (K, d) planes, i.e. stores it as (n_k, K, d): it picks the second-minor
    axis that pads the array less, the row axis on a tie."""
    pad = lambda m: -(-m // SUBLANES) * SUBLANES
    return K * pad(n_k) > n_k * pad(K)


def _tile_padded(x: jax.Array) -> jax.Array:
    """``x`` as the TPU lays it out in HBM: its two minor axes padded to
    whole (8, 128) tiles.  Mosaic types the kernel's view of X so (a
    (3, 13, 1000) array is a ``memref<3x16x1024>``) and slices it in whole
    tiles only; the interpreter gets the padded copy, so it fetches the
    windows the chip fetches."""
    rows = -(-x.shape[1] // SUBLANES) * SUBLANES
    return jnp.pad(x, ((0, 0), (0, rows - x.shape[1]),
                       (0, _padded(x.shape[2]) - x.shape[2])))


def _pass_kernel(k_ref, idx_ref, scal_ref, alpha_ref, y_ref, norms_ref,
                 w_ref, x_hbm, dalpha_ref, v_ref, rows, sems, *, loss,
                 by_worker):
    n_k = alpha_ref.shape[0]
    steps = idx_ref.shape[0]
    d = w_ref.shape[1]
    cols = rows.shape[2]
    k = k_ref[0]
    lam_n = scal_ref[0]
    sigma_p = scal_ref[1]

    def fetch(h, slot):  # x_hbm is (n_k, K, d) when by_worker, else X
        i = idx_ref[h]
        major, minor = (i, k) if by_worker else (k, i)
        start = pl.multiple_of(minor // SUBLANES * SUBLANES, SUBLANES)
        return pltpu.make_async_copy(
            x_hbm.at[major, pl.ds(start, SUBLANES), pl.ds(0, cols)],
            rows.at[slot], sems.at[slot])

    fetch(0, 0).start()

    def zero(i, carry):
        dalpha_ref[i] = jnp.float32(0.0)
        return carry

    jax.lax.fori_loop(0, n_k, zero, None)
    v_ref[...] = jnp.zeros_like(v_ref)

    def step(h, carry):
        slot = h % 2

        @pl.when(h + 1 < steps)
        def _():
            fetch(h + 1, 1 - slot).start()

        fetch(h, slot).wait()
        i = idx_ref[h]
        x = rows[slot, pl.ds((k if by_worker else i) % SUBLANES, 1),
                 pl.ds(0, d)]
        v = v_ref[...]
        a_i = alpha_ref[i] + dalpha_ref[i]
        z = jnp.sum(w_ref[...] * x) + sigma_p * jnp.sum(v * x)
        q = sigma_p * norms_ref[i] / lam_n
        delta = _coordinate_delta(loss, a_i, z, y_ref[i], q)
        dalpha_ref[i] = dalpha_ref[i] + delta
        v_ref[...] = v + (delta / lam_n) * x
        return carry

    jax.lax.fori_loop(0, steps, step, None)


@functools.partial(jax.jit, static_argnames=("loss", "interpret"))
def sdca_pass(w_eff, alpha_k, X, k, y_k, norms_k, lam, n_global, sigma_prime,
              idx, *, loss: str = "ridge",
              interpret: bool = False) -> LocalSolveResult:
    """Worker ``k``'s SDCA pass over the visit order ``idx`` (H,) against
    the stacked ``X`` (K, n_k, d); ``alpha_k``, ``y_k``, ``norms_k`` are its
    (n_k,) rows and ``w_eff`` (d,) its effective model.  Returns the same
    ``(delta_alpha, v)`` as :func:`repro.core.sdca.solve_subproblem_indices`
    on ``X[k]``.  The caller checks :func:`fits`."""
    K, n_k, d = X.shape
    by_worker = _tiled_by_worker(K, n_k)
    scal = jnp.stack([lam * n_global, sigma_prime]).astype(jnp.float32)
    x_view = jnp.transpose(X, (1, 0, 2)) if by_worker else X
    operands = (jnp.reshape(k, (1,)).astype(jnp.int32), idx.astype(jnp.int32),
                scal, alpha_k, y_k, norms_k, w_eff.reshape(1, d),
                _tile_padded(x_view) if interpret else x_view)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    # The kernel is traced with 64-bit types off: the scan executors trace
    # their rounds under enable_x64, and Mosaic lowers no 64-bit integer.
    with jax.enable_x64(False):
        dalpha, v = pl.pallas_call(
            functools.partial(_pass_kernel, loss=loss, by_worker=by_worker),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(),
                in_specs=[smem, smem, smem, smem, vmem,
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=[smem, vmem],
                scratch_shapes=[
                    pltpu.VMEM((2, SUBLANES, _padded(d)), X.dtype),
                    pltpu.SemaphoreType.DMA((2,))]),
            out_shape=[jax.ShapeDtypeStruct((n_k,), X.dtype),
                       jax.ShapeDtypeStruct((1, d), X.dtype)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=interpret, name="sdca_pass",
        )(*operands)
    return LocalSolveResult(dalpha, v.reshape(d))

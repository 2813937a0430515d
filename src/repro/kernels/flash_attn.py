"""Pallas TPU flash-attention forward kernel (GQA, causal).

The serving/prefill hot-spot of the framework. Grid is
(batch, kv_head, q_group, q_block, kv_block) with the kv_block axis innermost
and sequential: the (bq, hd) output tile plus the online-softmax running
statistics (m, l) live in VMEM scratch across kv steps, and only the final
normalized tile is written back -- HBM traffic is one read of Q + nq reads of
K/V tiles + one write of O, the flash roofline.

GQA without replication: the K/V BlockSpec index maps ignore the q_group axis,
so all G query groups of one KV head stream the same K/V tiles (no jnp.repeat
materialization).

Causality is handled two ways: fully-masked kv blocks are skipped via
``@pl.when`` (on real hardware this prunes ~half the MXU work; the jnp path
can't skip without breaking differentiability -- this asymmetry is the reason
the kernel exists), and the diagonal block applies the elementwise mask.

Training and sliding-window layers use the jnp custom-VJP path
(models/flash.py); this kernel covers the fwd-only inference path and is
validated against that implementation in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_fwd_kernel(q_ref,  # (1, 1, 1, bq, hd)
                      k_ref,  # (1, 1, bk, hd)
                      v_ref,  # (1, 1, bk, hd)
                      o_ref,  # (1, 1, 1, bq, hd)
                      m_scr,  # VMEM (bq,)
                      l_scr,  # VMEM (bq,)
                      acc_scr,  # VMEM (bq, hd)
                      *, causal: bool, sm_scale: float, bq: int, bk: int,
                      nk: int, seq_len: int):
    qi = pl.program_id(3)
    kj = pl.program_id(4)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Skip blocks strictly above the diagonal (causal).
    run = (not causal) or (kj * bk <= qi * bq + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0, 0].astype(jnp.float32) * sm_scale  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = q @ k.T  # (bq, bk)
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_len
        if causal:
            mask &= kpos <= qpos
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + p @ v
        m_scr[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention_fwd_pallas(
    q: jax.Array,  # (B, S, KV, G, hd) -- NOT pre-scaled
    k: jax.Array,  # (B, S, KV, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, S, KV, G, hd). Pads S to block multiples internally."""
    B, S, KV, G, hd = q.shape
    bq = min(block_q, max(8, S))
    bk = min(block_k, max(8, S))
    nq = -(-S // bq)
    nk = -(-S // bk)
    Sq, Sk = nq * bq, nk * bk
    sm_scale = hd**-0.5

    qt = jnp.pad(q, ((0, 0), (0, Sq - S), (0, 0), (0, 0), (0, 0)))
    qt = qt.transpose(0, 2, 3, 1, 4)  # (B, KV, G, Sq, hd)
    kt = jnp.pad(k, ((0, 0), (0, Sk - S), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, Sk - S), (0, 0), (0, 0))).transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               sm_scale=sm_scale, bq=bq, bk=bk, nk=nk,
                               seq_len=S)
    out = pl.pallas_call(
        kernel,
        grid=(B, KV, G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, hd),
                         lambda b, h, g, i, j: (b, h, g, i, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, g, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, g, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, bq, hd),
                               lambda b, h, g, i, j: (b, h, g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 3, 1, 2, 4)[:, :S]

"""Pallas flash-attention forward kernel vs the jnp flash oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attn import flash_attention_fwd_pallas
from repro.models.flash import FlashSpec, flash_attention


@pytest.mark.parametrize(
    "B,S,KV,G,hd,causal,blk",
    [(2, 64, 2, 2, 16, True, 16), (1, 100, 1, 3, 32, True, 32),  # ragged pad
     (2, 48, 2, 1, 16, False, 16),  # encoder
     (1, 128, 4, 2, 64, True, 64), (1, 96, 2, 2, 16, True, 32)],
)
def test_pallas_flash_matches_jnp(B, S, KV, G, hd, causal, blk):
    rng = np.random.default_rng(S)
    q = jnp.asarray(rng.standard_normal((B, S, KV, G, hd)).astype(np.float32)) * 0.4
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)).astype(np.float32)) * 0.4
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)).astype(np.float32))
    out_k = flash_attention_fwd_pallas(q, k, v, causal=causal, block_q=blk,
                                       block_k=blk, interpret=True)
    out_r = flash_attention(q * (hd**-0.5), k, v,
                            FlashSpec(causal, None, blk, blk, None))
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=2e-5)


def test_pallas_flash_bf16():
    rng = np.random.default_rng(7)
    B, S, KV, G, hd = 1, 64, 2, 2, 32
    q = (jnp.asarray(rng.standard_normal((B, S, KV, G, hd)).astype(np.float32))
         * 0.4).astype(jnp.bfloat16)
    k = (jnp.asarray(rng.standard_normal((B, S, KV, hd)).astype(np.float32))
         * 0.4).astype(jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    out_k = flash_attention_fwd_pallas(q, k, v, block_q=32, block_k=32,
                                       interpret=True)
    out_r = flash_attention((q.astype(jnp.float32) * hd**-0.5).astype(jnp.bfloat16),
                            k, v, FlashSpec(True, None, 32, 32, None))
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), atol=3e-2)

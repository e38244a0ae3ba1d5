"""jit'd wrapper for the flash-attention kernel (head-dim padding policy)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import flash_attention_pallas


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 256,
                    block_k: int = 256, interpret: bool | None = None):
    """GQA flash attention; pads head_dim up to a 128 multiple (MXU lanes)."""
    D = q.shape[-1]
    Dp = -(-D // 128) * 128
    if Dp != D:
        padf = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, Dp - D)])
        # zero-padded head dims do not change q.k^T nor add output mass, but
        # the softmax scale must use the ORIGINAL D — kernel derives it from
        # the padded shape, so rescale q to compensate.
        q = padf(q) * (Dp / D) ** 0.5
        k, v = padf(k), padf(v)
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                 block_k=block_k, interpret=interpret)
    return out[..., :D]

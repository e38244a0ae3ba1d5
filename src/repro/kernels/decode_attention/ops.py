"""jit'd wrapper for split-KV decode attention."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import decode_attention_pallas


@partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, length, *, block_k: int = 512,
                     interpret: bool | None = None):
    D = q.shape[-1]
    Dp = -(-D // 128) * 128
    if Dp != D:
        padf = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, Dp - D)])
        q = padf(q) * (Dp / D) ** 0.5
        k_cache, v_cache = padf(k_cache), padf(v_cache)
    out = decode_attention_pallas(q, k_cache, v_cache, length,
                                  block_k=block_k, interpret=interpret)
    return out[..., :D]

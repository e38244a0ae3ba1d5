"""Split-KV decode attention (flash-decoding) — Pallas TPU.

Decode is the paper's memory-bound phase (Memory-1): the whole KV cache is
streamed once from HBM per token. The kernel tiles the KV sequence across the
grid so multiple blocks' HBM streams overlap (the TPU analogue of the paper's
"two engines aggregate more bandwidth than one"), carrying online-softmax
stats in VMEM. Blocks past ``length`` are skipped entirely via ``pl.when`` —
compute AND the HBM stream — using a scalar-prefetch length operand.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, block_k: int, n_kv: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[0]
    k_start = ki * block_k

    @pl.when(k_start < length)      # skip fully-invalid KV blocks
    def _():
        q = q_ref[0].astype(jnp.float32)            # [G, D]
        k = k_ref[0].astype(jnp.float32)            # [bk, D]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def decode_attention_pallas(q, k_cache, v_cache, length, *, block_k: int = 512,
                            interpret: bool | None = None):
    """q [B,Hq,D]; caches [B,Smax,Hkv,D]; length: int32 scalar (valid prefix)."""
    B, Hq, D = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    block_k = min(block_k, S)
    assert S % block_k == 0
    scale = 1.0 / math.sqrt(D)
    n_kv = S // block_k

    qp = q.reshape(B, Hkv, G, D).transpose(0, 1, 2, 3).reshape(B * Hkv, G, D)
    kp = k_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vp = v_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    lens = jnp.full((1,), length, jnp.int32)

    kern = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                             n_kv=n_kv)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * Hkv, n_kv),
            in_specs=[
                pl.BlockSpec((1, G, D), lambda b, ik, lens: (b, 0, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, ik, lens: (b, ik, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, ik, lens: (b, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, G, D), lambda b, ik, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, G, D), q.dtype),
        interpret=resolve_interpret(interpret),
    )(lens, qp, kp, vp)
    return out.reshape(B, Hkv, G, D).reshape(B, Hq, D)

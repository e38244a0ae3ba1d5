"""Causal GQA flash attention (prefill path) — Pallas TPU.

Grid (batch*kv_head, q_blocks, kv_blocks); online softmax with fp32 (m, l,
acc) VMEM scratch carried across the innermost kv sweep. Causality is
exploited structurally: fully-masked kv blocks are skipped via ``pl.when``
(zero MXU work), the diagonal block is masked elementwise — the same
"skip-aligned-blocks / handle-ragged-remainder" split HeteroInfer applies at
the engine level.

Block shapes: q rows x 128-lane kv columns; head_dim is the minor dim and
must be 128-aligned for MXU efficiency (pad at the ops layer otherwise).
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, block_q: int, block_k: int, n_kv: int,
                  causal: bool, g: int):
    """q_ref: [block_q*g, D] (G query heads packed row-major per position),
    k_ref/v_ref: [block_k, D]. One (bq, bk) tile per invocation."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # skip blocks entirely in the causal future (no compute issued at all)
    run = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _():
        q = q_ref[0].astype(jnp.float32)            # [bq*g, D]
        k = k_ref[0].astype(jnp.float32)            # [bk, D]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q * g, block_k), 0) // g
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q * g, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, block_q: int = 256,
                           block_k: int = 256, interpret: bool | None = None):
    """q [B,Sq,Hq,D]; k,v [B,Sk,Hkv,D]; GQA handled by packing the G=Hq/Hkv
    query heads of one KV head into the q-block rows."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    scale = 1.0 / math.sqrt(D)

    # [B*Hkv, Sq*G, D]: row-major (position, group) packing
    qp = q.reshape(B, Sq, Hkv, G, D).transpose(0, 2, 1, 3, 4) \
          .reshape(B * Hkv, Sq * G, D)
    kp = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    vp = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)

    n_kv = Sk // block_k
    grid = (B * Hkv, Sq // block_q, n_kv)
    kern = functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                             block_k=block_k, n_kv=n_kv, causal=causal, g=G)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q * G, D), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, iq, ik: (b, ik, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, iq, ik: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q * G, D), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, Sq * G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q * G, 1), jnp.float32),
            pltpu.VMEM((block_q * G, 1), jnp.float32),
            pltpu.VMEM((block_q * G, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qp, kp, vp)
    return out.reshape(B, Hkv, Sq, G, D).transpose(0, 2, 1, 3, 4) \
              .reshape(B, Sq, Hq, D)

"""Shared model layers (pure functions over param pytrees).

Attention is blockwise with an online softmax (O(S * block) memory) so 32k
prefill and 4k training never materialize S^2 score tensors in the pure-JAX
path. Every internal loop honors ``unroll``: ``lax.scan`` normally (small HLO,
fast compiles), Python loop in cost-probe mode (so ``cost_analysis`` sees the
full FLOP count; see roofline methodology in DESIGN.md §6).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import matmul_any

NEG_INF = -1e30


def tp_all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    """Reassemble a column-sharded tensor along its LAST axis, concatenating
    the per-shard blocks in shard order. Every output column is produced by
    exactly one shard with the same reduction order as the unsharded matmul,
    so tensor-parallel execution under this gather is bit-exact with the
    single-device path (no psum-of-partials reassociation)."""
    return jax.lax.all_gather(x, axis_name, axis=x.ndim - 1, tiled=True)


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    """fp32 statistics WITHOUT materializing an fp32 copy of x: the square/
    convert fuse into the reduction; the big tensors stay in compute dtype
    (§Perf train/i2 — fp32 norm copies dominated HBM traffic)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * scale * w.astype(x.dtype)


def rope_freqs(dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D]; positions: [S] or [B, S]."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta))          # [D/2]
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [B, S, D/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def remat_policy_of(cfg):
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return jax.checkpoint_policies.nothing_saveable


def _scan_or_unroll(step, carry, xs_leaves, n: int, unroll: bool):
    """scan over leading axis of each leaf in xs_leaves, or a Python loop."""
    if not unroll:
        carry, _ = jax.lax.scan(lambda c, xs: (step(c, xs), None), carry, xs_leaves)
        return carry
    for i in range(n):
        carry = step(carry, jax.tree.map(lambda a: a[i], xs_leaves))
    return carry


def blockwise_attention(
    q: jax.Array,                 # [B, Sq, Hq, D]
    k: jax.Array,                 # [B, Sk, Hkv, D]
    v: jax.Array,                 # [B, Sk, Hkv, D]
    *,
    q_pos: jax.Array,             # [Sq] or [B, Sq] int32 absolute positions
    kv_pos: jax.Array,            # [Sk] int32
    causal: bool = True,
    block_k: int = 1024,
    unroll: bool = False,
) -> jax.Array:
    """Online-softmax attention over KV blocks (GQA-aware). fp32 accumulation.

    Causal masking is positional (kv_pos <= q_pos), which also masks unwritten
    KV-cache slots during decode (their kv_pos exceeds the query position).
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Sq, Hkv, G, D)
    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None, :], (B, Sq))

    block_k = min(block_k, Sk)
    padded = bool(Sk % block_k)
    if padded:                            # pad KV to a block multiple; padded
        pad = block_k - Sk % block_k      # slots get kv_pos = INT_MAX -> masked
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, pad), constant_values=jnp.iinfo(jnp.int32).max)
        Sk += pad
    max_kv_pos = None if causal else kv_pos[-1 - (pad if padded else 0)]
    nb = Sk // block_k
    kb = k.reshape(B, nb, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, block_k, Hkv, D).transpose(1, 0, 2, 3, 4)
    pb = kv_pos.reshape(nb, block_k)

    m0 = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)

    masked = causal or padded

    def step(carry, blk):
        m, l, acc = carry
        k_b, v_b, p_b = blk
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k_b,
                       preferred_element_type=jnp.float32) * scale
        if masked:
            if causal:
                mask = p_b[None, None, :] <= q_pos[:, :, None]      # [B,Sq,bk]
            else:  # bidirectional but padded: validity only
                mask = jnp.broadcast_to((p_b <= max_kv_pos)[None, None, :],
                                        (B, Sq, block_k))
            mask = mask[:, :, None, None, :]
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if masked:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p.astype(v_b.dtype), v_b,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = _scan_or_unroll(step, (m0, l0, a0), (kb, vb, pb), nb, unroll)
    out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


def dense_attention(q, k, v, *, q_pos, kv_pos, causal=True) -> jax.Array:
    """Reference O(S^2)-memory attention (oracle for tests)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(D)
    if causal:
        if q_pos.ndim == 1:
            q_pos = jnp.broadcast_to(q_pos[None, :], (B, Sq))
        mask = kv_pos[None, None, :] <= q_pos[:, :, None]
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, Hq, D).astype(q.dtype)


# ---------------------------------------------------------------- attention --

def init_attention(key, cfg) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "wq": (jax.random.normal(k1, (d, cfg.n_heads * hd)) * s).astype(dt),
        "wk": (jax.random.normal(k2, (d, cfg.n_kv_heads * hd)) * s).astype(dt),
        "wv": (jax.random.normal(k3, (d, cfg.n_kv_heads * hd)) * s).astype(dt),
        "wo": (jax.random.normal(k4, (cfg.n_heads * hd, d)) * s).astype(dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def _qkv_rope(p: dict, x: jax.Array, cfg, positions: jax.Array, hetero_ctx):
    """Shared projection front-end: q/k/v matmuls, qk-norm, RoPE at the
    tokens' absolute positions. Used by both the dense-cache and paged
    attention paths so their numerics are identical by construction."""
    B, S, _ = x.shape
    hd = cfg.head_dim
    mm = hetero_ctx.matmul if hetero_ctx is not None else matmul_any
    q = mm(x, p["wq"], name="wq").reshape(B, S, cfg.n_heads, hd)
    k = mm(x, p["wk"], name="wk").reshape(B, S, cfg.n_kv_heads, hd)
    v = mm(x, p["wv"], name="wv").reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v, mm


def attention(
    p: dict, x: jax.Array, cfg, *,
    positions: jax.Array,
    cache: Optional[dict] = None,        # {"k","v": [B,Smax,Hkv,D], "pos": [Smax]}
    cache_index: Optional[jax.Array] = None,
    unroll: bool = False,
    hetero_ctx=None,
):
    """GQA attention. If ``cache`` is given, new K/V are written at
    ``cache_index`` and attention runs over the whole (masked) cache.
    Returns (out, new_cache_kv or None)."""
    B, S, d = x.shape
    hd = cfg.head_dim
    q, k, v, mm = _qkv_rope(p, x, cfg, positions, hetero_ctx)

    causal = not cfg.encoder_only
    if cache is not None and S == 1:
        from repro.distributed.sharding import split_kv_active
        idx0 = jnp.asarray(cache_index)
        if split_kv_active() and idx0.ndim == 0:
            from repro.distributed.split_kv import split_kv_decode_update_attend
            o, ck, cv = split_kv_decode_update_attend(
                q, k, v, cache["k"], cache["v"], idx0.astype(jnp.int32))
            out = mm(o.reshape(B, S, cfg.n_heads * hd), p["wo"], name="wo")
            return out, {"k": ck, "v": cv}
    if cache is not None:
        idx = jnp.asarray(cache_index)
        if idx.ndim == 1:        # per-slot indices (continuous batching)
            upd = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
                c, u, (i, 0, 0)))
            ck = upd(cache["k"], k.astype(cache["k"].dtype), idx)
            cv = upd(cache["v"], v.astype(cache["v"].dtype), idx)
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0))
        Smax = ck.shape[1]
        kv_pos = jnp.arange(Smax, dtype=jnp.int32)
        o = blockwise_attention(q, ck, cv, q_pos=positions, kv_pos=kv_pos,
                                causal=True, block_k=cfg.attn_block_k,
                                unroll=unroll)
        new_kv = {"k": ck, "v": cv}
    else:
        kv_pos = jnp.arange(S, dtype=jnp.int32)
        o = blockwise_attention(q, k, v, q_pos=positions, kv_pos=kv_pos,
                                causal=causal, block_k=cfg.attn_block_k,
                                unroll=unroll)
        new_kv = None
    out = mm(o.reshape(B, S, cfg.n_heads * hd), p["wo"], name="wo")
    return out, new_kv


def quantize_kv_slot(x: jax.Array, scale_dtype=jnp.bfloat16,
                     tp_axis: Optional[str] = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Per-token-slot symmetric int8 KV quantization. x: [T, Hkv, D] ->
    (codes int8 [T, Hkv, D], scale [T]).

    One scalar scale per (token, tensor) slot, ROUNDED TO THE STORAGE DTYPE
    BEFORE the codes are computed: codes quantize against the value the
    gather will actually multiply by, so quantize-on-scatter composes
    bit-exactly across any chunking of the same token stream (prefill vs
    decode vs mixed vs verify writes). An all-zero slot stores scale 0 —
    it dequantizes to exactly 0, like an unwritten fp pool slot.

    Under head-sharded tensor parallelism (``tp_axis`` set inside a
    shard_map) each shard sees only its local KV heads, but the slot scale
    is defined over ALL heads — a pmax over the tp axis recovers the exact
    global amax (max-of-maxes is exact), so codes and scales stay
    bit-identical to the single-device pool.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-2, -1))   # [T]
    if tp_axis is not None:
        amax = jax.lax.pmax(amax, tp_axis)
    s_stored = jnp.where(amax > 0, amax / 127.0, 0.0).astype(scale_dtype)
    denom = jnp.where(s_stored == 0, 1.0, s_stored.astype(jnp.float32))
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / denom[..., None, None]),
                     -127, 127).astype(jnp.int8)
    return codes, s_stored


def dequant_kv_ref(codes: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Reference expansion of an int8 KV pool tensor (codes [..., T, Hkv, D]
    x scale [..., T]) — the conformance oracle and the gather-side math."""
    return (codes.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None, None]).astype(dtype)


def paged_attention(
    p: dict, x: jax.Array, cfg, *,
    positions: jax.Array,           # [S] or [B, S] absolute token positions
    pool: dict,                     # whole pool: {"k","v": [L,NB,BS,Hkv,D]}
    #                                 (+ "k_scale","v_scale": [L,NB,BS] if int8)
    layer,                          # this layer's index on the pool's L axis
    block_table: jax.Array,         # [B, NBmax] int32 pool block ids (0=null)
    unroll: bool = False,
    hetero_ctx=None,
    tp_axis: Optional[str] = None,
):
    """GQA attention over a paged KV pool (serving/paged_cache.py).

    Logical position ``t`` of request ``b`` lives at slot
    ``(layer, block_table[b, t // BS], t % BS)`` of the whole pool. New K/V
    are scattered there, in place (``.at[...].set`` on the pool the layer
    scan carries — no layer slice is cut out or stacked back); reads gather
    the request's pages at ``layer`` straight from the whole pool into a
    ``[B, NBmax*BS]`` view whose slot index IS the logical position, so the
    standard positional causal mask handles stale pool contents and
    null-block padding exactly like the dense path masks unwritten cache
    slots.

    An int8 pool (``k_scale``/``v_scale`` present) quantizes on scatter —
    per-slot codes + one scale scalar per (slot, tensor) — and dequantizes
    inside the gather, so equal pool memory holds ~2x the blocks while the
    attention math itself stays in compute precision.

    With ``tp_axis`` set (inside a shard_map whose mesh axis carries the KV
    heads), ``cfg`` holds the LOCAL head counts, the pool leaves are local
    head slices, and the whole scatter/gather/softmax runs shard-local; the
    only collectives are the head gather before ``wo`` and the output-column
    gather after it (both [B, S, d]-sized, bit-exact concatenations).

    Returns (out, updated whole pool dict with the same keys).
    """
    B, S, _ = x.shape
    hd = cfg.head_dim
    BS, Hkv, D = pool["k"].shape[2:]
    quant = "k_scale" in pool
    q, k, v, mm = _qkv_rope(p, x, cfg, positions, hetero_ctx)

    pos = (positions if positions.ndim == 2
           else jnp.broadcast_to(positions[None, :], (B, S))).astype(jnp.int32)
    blk = jnp.take_along_axis(block_table, pos // BS, axis=1)     # [B, S]
    at = (layer, blk.reshape(-1), (pos % BS).reshape(-1))         # [B*S] slots
    k = k.reshape(B * S, Hkv, D)
    v = v.reshape(B * S, Hkv, D)
    new_pool = {}
    if quant:
        k, k_sc = quantize_kv_slot(k, pool["k_scale"].dtype, tp_axis)
        v, v_sc = quantize_kv_slot(v, pool["v_scale"].dtype, tp_axis)
        new_pool["k_scale"] = pool["k_scale"].at[at].set(k_sc)
        new_pool["v_scale"] = pool["v_scale"].at[at].set(v_sc)
    new_pool["k"] = pool["k"].at[at].set(k.astype(pool["k"].dtype))
    new_pool["v"] = pool["v"].at[at].set(v.astype(pool["v"].dtype))

    # the same gather semantics as ``jnp.take`` (out-of-range ids fill)
    pages = lambda a: a.at[layer, block_table].get(mode="fill")
    NBmax = block_table.shape[1]
    ck = pages(new_pool["k"]).reshape(B, NBmax * BS, Hkv, D)
    cv = pages(new_pool["v"]).reshape(B, NBmax * BS, Hkv, D)
    if quant:
        ck = dequant_kv_ref(ck, pages(new_pool["k_scale"]).reshape(
            B, NBmax * BS), q.dtype)
        cv = dequant_kv_ref(cv, pages(new_pool["v_scale"]).reshape(
            B, NBmax * BS), q.dtype)
    kv_pos = jnp.arange(NBmax * BS, dtype=jnp.int32)
    o = blockwise_attention(q, ck, cv, q_pos=pos, kv_pos=kv_pos,
                            causal=True, block_k=cfg.attn_block_k,
                            unroll=unroll)
    o = o.reshape(B, S, cfg.n_heads * hd)
    if tp_axis is not None:
        o = tp_all_gather(o, tp_axis)       # local heads -> full head dim
    out = mm(o, p["wo"], name="wo")
    if tp_axis is not None:
        out = tp_all_gather(out, tp_axis)   # wo is output-column sharded
    return out, new_pool


# ---------------------------------------------------------------------- ffn --

def init_swiglu(key, d: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    s = 1.0 / math.sqrt(d)
    dt = jnp.dtype(dtype)
    return {
        "w_gate": (jax.random.normal(k1, (d, d_ff)) * s).astype(dt),
        "w_up": (jax.random.normal(k2, (d, d_ff)) * s).astype(dt),
        "w_down": (jax.random.normal(k3, (d_ff, d)) / math.sqrt(d_ff)).astype(dt),
    }


def swiglu(p: dict, x: jax.Array, hetero_ctx=None,
           tp_axis: Optional[str] = None) -> jax.Array:
    """With ``tp_axis`` set, w_gate/w_up are column-sharded (local d_ff
    slice) and w_down is output-column sharded: the hidden activation and
    the output are reassembled with bit-exact tiled all-gathers instead of
    a psum of row-parallel partials (which would reassociate the d_ff
    reduction and drift from the single-device numerics)."""
    mm = hetero_ctx.matmul if hetero_ctx is not None else matmul_any
    g = mm(x, p["w_gate"], name="w_gate")
    u = mm(x, p["w_up"], name="w_up")
    h = jax.nn.silu(g) * u
    if tp_axis is not None:
        h = tp_all_gather(h, tp_axis)       # local d_ff columns -> full d_ff
    out = mm(h, p["w_down"], name="w_down")
    if tp_axis is not None:
        out = tp_all_gather(out, tp_axis)   # w_down output-column sharded
    return out


# ----------------------------------------------------------------- lm head --

def chunked_ce_loss(emb_out: jax.Array, h: jax.Array, targets: jax.Array,
                    *, chunk: int, unroll: bool = False) -> jax.Array:
    """Cross-entropy without materializing [B, S, V]: scan over seq chunks.

    emb_out: [D, V] (output head, possibly tied transpose); h: [B, S, D];
    targets: [B, S] int32. Returns mean loss (fp32).

    The per-chunk logits are constrained to shard over the model axis on V
    (§Perf train/i1): an unsharded [B, c, V] fp32 logits buffer dominates
    HBM traffic at 100k-class vocabs; V-sharding divides it by the TP width
    (logsumexp then reduces over the sharded axis -> one tiny all-reduce).
    """
    from repro.distributed.sharding import logits_constraint

    B, S, D = h.shape
    chunk = min(chunk, S)
    while S % chunk:            # largest divisor of S at most `chunk`
        chunk -= 1
    nc = S // chunk
    hc = h.reshape(B, nc, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, nc, chunk).transpose(1, 0, 2)

    # checkpointed: WITHOUT this, autodiff-of-scan stacks every chunk's fp32
    # logits as residuals — ~12 GB/device at dbrx scale (§Perf train/i2);
    # recomputing the chunk logits in backward costs one extra head matmul.
    @jax.checkpoint
    def step(carry, xs):
        hi, ti = xs
        logits = logits_constraint(matmul_any(hi, emb_out).astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ti[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - gold)

    total = _scan_or_unroll(step, jnp.zeros((), jnp.float32), (hc, tc), nc,
                            unroll)
    return total / (B * S)

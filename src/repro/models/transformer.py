"""Decoder-only (and encoder-only) transformer LM: dense / MoE / VLM / audio.

Layer stack runs under ``lax.scan`` over stacked per-layer params (small HLO;
the production posture for 1000+-node compile times). ``unroll=True`` switches
every loop to Python for the roofline cost probes.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.partition import matmul_any
from repro.distributed.sharding import hidden_constraint

from .layers import (attention, chunked_ce_loss, init_attention, init_swiglu,
                     paged_attention, rms_norm, swiglu)
from .moe import init_moe, moe_ffn


def init_params(key, cfg) -> dict:
    dt = jnp.dtype(cfg.param_dtype)
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    d, v = cfg.d_model, cfg.vocab_size
    params = {
        "embed": (jax.random.normal(k_emb, (v, d)) * 0.02).astype(dt),
        "final_norm": jnp.ones((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = (jax.random.normal(k_head, (d, v)) / math.sqrt(d)).astype(dt)

    def init_layer(k):
        ka, kf = jax.random.split(k)
        lp = {
            "attn_norm": jnp.ones((d,), dt),
            "attn": init_attention(ka, cfg),
            "ffn_norm": jnp.ones((d,), dt),
        }
        if cfg.moe:
            lp["moe"] = init_moe(kf, cfg)
        else:
            lp["ffn"] = init_swiglu(kf, d, cfg.d_ff, cfg.param_dtype)
        return lp

    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params["layers"] = jax.vmap(init_layer)(layer_keys)
    return params


def _layer(lp, x, cfg, *, positions, kv=None, cache_index=None, unroll=False,
           hetero_ctx=None, paged=None, tp_axis=None):
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    if paged is not None:
        attn_out, new_kv = paged_attention(
            lp["attn"], h, cfg, positions=positions,
            pool=paged["pool"], layer=paged["layer"],
            block_table=paged["block_table"], unroll=unroll,
            hetero_ctx=hetero_ctx, tp_axis=tp_axis)
    else:
        attn_out, new_kv = attention(lp["attn"], h, cfg, positions=positions,
                                     cache=kv, cache_index=cache_index,
                                     unroll=unroll, hetero_ctx=hetero_ctx)
    x = x + attn_out
    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    if cfg.moe:
        ffn_out, aux = moe_ffn(lp["moe"], h, cfg, hetero_ctx=hetero_ctx)
    else:
        ffn_out, aux = swiglu(lp["ffn"], h, hetero_ctx=hetero_ctx,
                              tp_axis=tp_axis), jnp.zeros((), jnp.float32)
    return hidden_constraint(x + ffn_out), new_kv, aux


def _embed(params, inputs, cfg):
    if inputs.dtype in (jnp.int32, jnp.int64):
        return params["embed"][inputs].astype(jnp.dtype(cfg.compute_dtype))
    return inputs.astype(jnp.dtype(cfg.compute_dtype))   # modality-stub embeddings


def _run_layers(params, x, cfg, *, positions, cache=None, cache_index=None,
                unroll=False, hetero_ctx=None):
    """Apply all layers; returns (x, new_cache_kv_stacked, aux_sum)."""
    L = cfg.n_layers

    def body(x, lp, kv):
        return _layer(lp, x, cfg, positions=positions, kv=kv,
                      cache_index=cache_index, unroll=unroll,
                      hetero_ctx=hetero_ctx)

    if unroll:
        new_ks, new_vs, aux = [], [], jnp.zeros((), jnp.float32)
        for i in range(L):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            kv = (None if cache is None else
                  {"k": cache["k"][i], "v": cache["v"][i]})
            x, nkv, a = body(x, lp, kv)
            aux = aux + a
            if nkv is not None:
                new_ks.append(nkv["k"]); new_vs.append(nkv["v"])
        nc = ({"k": jnp.stack(new_ks), "v": jnp.stack(new_vs)}
              if new_ks else None)
        return x, nc, aux

    if cache is None:
        def step(carry, lp):
            x, aux = carry
            fn = body
            if cfg.remat:
                from .layers import remat_policy_of
                fn = jax.checkpoint(lambda x, lp: body(x, lp, None)[::2],
                                    policy=remat_policy_of(cfg))
                x2, a = fn(x, lp)
            else:
                x2, _, a = body(x, lp, None)
            return (x2, aux + a), None
        (x, aux), _ = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
        return x, None, aux

    def step(carry, xs):
        x, aux = carry
        lp, k_l, v_l = xs
        x2, nkv, a = body(x, lp, {"k": k_l, "v": v_l})
        return (x2, aux + a), (nkv["k"], nkv["v"])

    (x, aux), (nk, nv) = jax.lax.scan(
        step, (x, jnp.zeros((), jnp.float32)),
        (params["layers"], cache["k"], cache["v"]))
    return x, {"k": nk, "v": nv}, aux


def _head_matrix(params, cfg):
    return (params["embed"].T if cfg.tie_embeddings else params["head"])


def _head_logits(params, x, cfg, hetero_ctx=None, tp_axis=None):
    """LM-head matmul — a partitionable site like any other (the latency
    table profiles it as "head"), so inference paths route it through the
    HeteroCtx when one is given. Under tensor parallelism an untied head is
    vocab-column sharded: local logits are gathered along V (bit-exact
    column concatenation); a tied head reads the replicated embedding and
    needs no collective."""
    if hetero_ctx is not None:
        y = hetero_ctx.matmul(x, _head_matrix(params, cfg), name="head")
    else:
        y = matmul_any(x, _head_matrix(params, cfg))
    y = y.astype(jnp.float32)
    if tp_axis is not None and not cfg.tie_embeddings:
        from .layers import tp_all_gather
        y = tp_all_gather(y, tp_axis)
    return y


def loss_fn(params, inputs, targets, cfg, *, unroll=False):
    """Training objective: next-token CE (+ MoE aux). inputs [B,S] or [B,S,D]."""
    S = inputs.shape[1]
    x = _embed(params, inputs, cfg)
    positions = jnp.arange(S, dtype=jnp.int32)
    x, _, aux = _run_layers(params, x, cfg, positions=positions, unroll=unroll)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    ce = chunked_ce_loss(_head_matrix(params, cfg), x, targets,
                         chunk=cfg.loss_chunk, unroll=unroll)
    return ce + 0.01 * aux / max(cfg.n_layers, 1), {"ce": ce, "aux": aux}


def forward_hidden(params, inputs, cfg, *, unroll=False):
    """Full-sequence hidden states (no cache) — used by encoder eval."""
    S = inputs.shape[1]
    x = _embed(params, inputs, cfg)
    positions = jnp.arange(S, dtype=jnp.int32)
    x, _, _ = _run_layers(params, x, cfg, positions=positions, unroll=unroll)
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "index": jnp.zeros((), jnp.int32)}


def prefill(params, tokens, cache, cfg, *, start_index=0, unroll=False,
            hetero_ctx=None):
    """Process a prompt (or prompt chunk, for chunked prefill), write the
    cache at [start_index, start_index+S), return last-token logits."""
    B, S = tokens.shape[0], tokens.shape[1]
    x = _embed(params, tokens, cfg)
    positions = start_index + jnp.arange(S, dtype=jnp.int32)
    x, nkv, _ = _run_layers(params, x, cfg, positions=positions,
                            cache=cache, cache_index=start_index,
                            unroll=unroll, hetero_ctx=hetero_ctx)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(params, x[:, -1:, :], cfg, hetero_ctx)
    return logits, {"k": nkv["k"], "v": nkv["v"],
                    "index": jnp.asarray(start_index + S, jnp.int32)}


def prefill_slot(params, cache, tokens, slot, start, cfg, *, chunk: int):
    """Prefill one prompt chunk of one request into ``slot`` of a batched
    dense cache (``[L, B, S, Hkv, D]``): slice the slot out, run
    :func:`prefill` at ``start``, write the updated KV back. Shared by the
    dense continuous batcher's admission path and the speculative
    decoder's draft-lane prefill (serving/spec.py). ``chunk`` is unused in
    the body — callers jit with ``static_argnames=('chunk',)`` so each
    bucket length keys its own compiled graph."""
    sub = {"k": jax.lax.dynamic_slice_in_dim(cache["k"], slot, 1, axis=1),
           "v": jax.lax.dynamic_slice_in_dim(cache["v"], slot, 1, axis=1),
           "index": start}
    logits, new = prefill(params, tokens[None, :], sub, cfg,
                          start_index=start)
    cache = dict(cache)
    cache["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], new["k"], slot, axis=1)
    cache["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], new["v"], slot, axis=1)
    return logits, cache


# ------------------------------------------------------------ paged cache --

def init_paged_cache(cfg, *, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, kv_quant: Optional[str] = None
                     ) -> dict:
    """Shared KV page pool: ``[L, num_blocks, block_size, Hkv, D]`` per
    tensor. Block 0 is the null block (see serving/paged_cache.py).

    ``kv_quant='int8'`` stores int8 codes plus one scale scalar per
    (layer, slot, tensor) — ``k_scale``/``v_scale`` ``[L, NB, BS]`` in
    bfloat16, quantized-on-scatter and dequantized in the attention gather
    (models/layers.py::paged_attention). Zero scales mark unwritten slots.
    """
    shape = (cfg.n_layers, num_blocks, block_size, cfg.n_kv_heads,
             cfg.head_dim)
    if kv_quant is None:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if kv_quant != "int8":
        raise ValueError(f"unsupported kv_quant {kv_quant!r}")
    sshape = shape[:3]
    return {"k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.bfloat16),
            "v_scale": jnp.zeros(sshape, jnp.bfloat16)}


def _over_layers(params, cfg, body, carry, unroll):
    """``carry = body(layer_params, layer_index, *carry)`` for every layer:
    a ``lax.scan`` over (layer params, layer index), or a Python loop when
    ``unroll``. Returns the last carry."""
    if unroll:
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            carry = body(lp, i, *carry)
        return carry
    carry, _ = jax.lax.scan(
        lambda c, xs: (body(*xs, *c), None), carry,
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    return carry


def _run_layers_paged(params, x, cfg, *, positions, pool, block_table,
                      unroll=False, hetero_ctx=None, tp_axis=None):
    """Like ``_run_layers`` but attention reads/writes the paged pool. The
    pool — a pytree of ``[L, ...]`` leaves (K/V tensors plus the int8
    pool's scale planes) — is carried whole through the layer scan with the
    hidden state; the scan runs over (layer params, layer index), and each
    layer writes its new K/V into the pool in place and gathers its pages
    from it at its index. Carrying the pool, instead of scanning it as
    ``xs``/``ys``, lets XLA alias it: no program copies the whole pool.
    Returns the updated pool."""
    def body(lp, i, x, pool):
        x, pool, _ = _layer(lp, x, cfg, positions=positions, unroll=unroll,
                            hetero_ctx=hetero_ctx, tp_axis=tp_axis,
                            paged={"pool": pool, "layer": i,
                                   "block_table": block_table})
        return x, pool

    return _over_layers(params, cfg, body, (x, pool), unroll)


def paged_prefill(params, tokens, pool, cfg, *, block_table, start_index=0,
                  unroll=False, hetero_ctx=None, tp_axis=None):
    """Prefill a prompt chunk into the request's pages. tokens: [B, S];
    block_table: [B, NBmax]. ``start_index`` is a scalar (uniform batches —
    chunked prefill resuming at the chunk offset, or a cached-prefix suffix
    resuming after the resident prefix) or [B] per-lane starts (the
    ``paged_verify`` nonzero-start machinery, generalized here so batched
    suffix prefill can resume each lane at its own cached-prefix length).
    Returns (last-token logits, updated pool)."""
    S = tokens.shape[1]
    x = _embed(params, tokens, cfg)
    start_index = jnp.asarray(start_index, jnp.int32)
    steps = jnp.arange(S, dtype=jnp.int32)
    positions = (start_index[:, None] + steps[None, :]
                 if start_index.ndim == 1 else start_index + steps)
    x, pool = _run_layers_paged(params, x, cfg, positions=positions,
                                pool=pool, block_table=block_table,
                                unroll=unroll, hetero_ctx=hetero_ctx,
                                tp_axis=tp_axis)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(params, x[:, -1:, :], cfg, hetero_ctx, tp_axis)
    return logits, pool


def paged_verify(params, tokens, pool, cfg, *, block_table, start_index,
                 unroll=False, hetero_ctx=None, tp_axis=None):
    """Speculative-decoding verification step: append ``tokens`` ([B, K+1] —
    each lane's pending token plus its K drafted tokens) after each lane's
    cached prefix and return PER-POSITION logits over all K+1 positions.

    Generalizes the two existing paged inference steps: ``paged_prefill``
    runs many tokens but emits only last-token logits; ``paged_decode_step``
    emits per-position logits but for one token (this is the K=0 case).
    Verification needs both: every position's logits feed the greedy
    accept/reject rule (serving/sampler.py::greedy_verify), and rejected
    positions are reclaimed afterwards by ``PagedKVCache.truncate_to``
    (stale pool slots past the accepted prefix are masked positionally and
    rewritten before any later query attends them, so rollback is free on
    the device side).

    ``start_index``: [B] per-lane write positions (like ``paged_decode_step``
    lengths), or a scalar for uniform batches. The K-token matmuls are an
    M=K+1-shaped site class of their own — a ``hetero_ctx`` built with
    ``verify_ks`` routes them through the solver's VERIFY decisions.
    Returns (logits [B, K+1, V], updated pool).
    """
    S = tokens.shape[1]
    start_index = jnp.asarray(start_index, jnp.int32)
    steps = jnp.arange(S, dtype=jnp.int32)
    positions = (start_index[:, None] + steps[None, :]
                 if start_index.ndim == 1 else start_index + steps)
    x = _embed(params, tokens, cfg)
    x, pool = _run_layers_paged(params, x, cfg, positions=positions,
                                pool=pool, block_table=block_table,
                                unroll=unroll, hetero_ctx=hetero_ctx,
                                tp_axis=tp_axis)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(params, x, cfg, hetero_ctx, tp_axis)
    return logits, pool


def mixed_step(params, decode_tokens, prefill_tokens, pool, cfg, *,
               decode_tables, decode_lengths, prefill_table, prefill_start=0,
               unroll=False, hetero_ctx=None, tp_axis=None):
    """Stage-parallel mixed batch: ONE dispatch runs a batched paged decode
    step for every lane AND one prefill chunk of an admitting request,
    sharing one paged pool, carried through the layer scan and written in
    place by both streams (paper §4.1-§4.3 applied at stage level: decode
    is the memory-bound flexible-path stream, the aligned prefill chunk is
    the compute-bound MXU-path stream, and running them concurrently is
    what fills both the compute and bandwidth envelopes).

    decode_tokens: [W, 1]; prefill_tokens: [1, C]; decode_tables: [W, NBmax];
    decode_lengths: [W]; prefill_table: [1, NBmax]. The two streams touch
    disjoint pool blocks (the allocator never shares a block), so fusion is
    an execution-schedule change, never a numerics change. Decode lanes stay
    on the flexible path (no hetero_ctx — they are Memory-1 bound); the
    prefill chunk routes its matmuls through ``hetero_ctx`` when given.

    Returns (decode_logits [W, 1, V], prefill_logits [1, 1, V], pool).
    """
    xd = _embed(params, decode_tokens, cfg)
    xp = _embed(params, prefill_tokens, cfg)
    C = prefill_tokens.shape[1]
    dec_pos = decode_lengths[:, None].astype(jnp.int32)
    pre_pos = prefill_start + jnp.arange(C, dtype=jnp.int32)

    def body(lp, i, xd, xp, pool):
        # decode lanes first (flexible path), prefill chunk second
        # (solver-planned path); order is arbitrary — disjoint block tables
        xd2, pool, _ = _layer(lp, xd, cfg, positions=dec_pos, unroll=unroll,
                              tp_axis=tp_axis,
                              paged={"pool": pool, "layer": i,
                                     "block_table": decode_tables})
        xp2, pool, _ = _layer(lp, xp, cfg, positions=pre_pos, unroll=unroll,
                              hetero_ctx=hetero_ctx, tp_axis=tp_axis,
                              paged={"pool": pool, "layer": i,
                                     "block_table": prefill_table})
        return xd2, xp2, pool

    xd, xp, pool = _over_layers(params, cfg, body, (xd, xp, pool), unroll)

    xd = rms_norm(xd, params["final_norm"], cfg.norm_eps)
    dec_logits = _head_logits(params, xd, cfg, None, tp_axis)  # flexible path
    xp = rms_norm(xp, params["final_norm"], cfg.norm_eps)
    pre_logits = _head_logits(params, xp[:, -1:, :], cfg, hetero_ctx, tp_axis)
    return dec_logits, pre_logits, pool


def paged_decode_step(params, token, pool, cfg, *, block_tables, lengths,
                      unroll=False, hetero_ctx=None, tp_axis=None):
    """One batched decode step over the page pool. token: [B, 1];
    block_tables: [B, NBmax]; lengths: [B] per-request write positions.
    Inactive lanes (length 0, null table) sink writes into the null block.
    Returns (logits [B, 1, V], updated pool)."""
    x = _embed(params, token, cfg)
    positions = lengths[:, None].astype(jnp.int32)
    x, pool = _run_layers_paged(params, x, cfg, positions=positions,
                                pool=pool, block_table=block_tables,
                                unroll=unroll, hetero_ctx=hetero_ctx,
                                tp_axis=tp_axis)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(params, x, cfg, hetero_ctx, tp_axis)
    return logits, pool


def decode_step(params, token, cache, cfg, *, unroll=False, hetero_ctx=None):
    """One autoregressive step. token: [B, 1] int32. Returns (logits, cache).
    ``cache['index']`` may be a scalar (uniform batch) or [B] per-slot
    lengths (continuous batching)."""
    idx = cache["index"]
    x = _embed(params, token, cfg)
    positions = (idx[:, None].astype(jnp.int32) if jnp.ndim(idx) == 1
                 else jnp.full((1,), idx, jnp.int32))
    x, nkv, _ = _run_layers(params, x, cfg, positions=positions,
                            cache=cache, cache_index=idx, unroll=unroll,
                            hetero_ctx=hetero_ctx)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _head_logits(params, x, cfg, hetero_ctx)
    return logits, {"k": nkv["k"], "v": nkv["v"], "index": idx + 1}

"""Uniform model interface over all families.

``build_model(cfg)`` returns a ``Model`` whose members are pure functions:
    init(rng) -> params
    loss(params, inputs, targets) -> (loss, metrics)        [train objective]
    init_cache(batch, max_len, dtype) -> cache
    prefill(params, tokens, cache) -> (last_logits, cache)
    decode_step(params, token, cache) -> (logits, cache)
Encoder-only archs expose ``encode`` instead of prefill/decode.

Attention-family models additionally expose the paged-KV trio used by the
serving scheduler (serving/scheduler.py::PagedBatcher):
    init_paged_cache(num_blocks, block_size, dtype) -> pool
    paged_prefill(params, tokens, pool, block_table, start_index)
        -> (last_logits, pool)
    paged_decode_step(params, token, pool, block_tables, lengths)
        -> (logits, pool)
    paged_verify(params, tokens, pool, block_table, start_index)
        -> (per_position_logits, pool)
``paged_verify`` is the speculative-decoding verification step (one
dispatch scores a lane's pending token plus its K drafted tokens —
serving/spec.py); ``paged_decode_step`` is also the body of the fused-window
decode scan
(core/sync.py::paged_decode_window): it must stay a pure pool -> pool
function of statically-shaped operands so a ``lax.scan`` can carry the pool
across a whole window with zero host round-trips. Inside each step the
layer scan carries the whole pool too, and every layer writes its K/V into
it in place, so no step program holds a second copy of the pool. ``mixed_step`` is the
stage-parallel variant: one dispatch runs every decode lane AND one prefill
chunk of an admitting request against the same pool (the scheduler's
mixed-batch mode), with the same purity/static-shape contract.

All accept ``unroll=`` (roofline cost probes) and ``hetero_ctx=`` (the
HeteroInfer partitioned-matmul context) keyword args where meaningful; the
context covers every partitionable site, including the LM head
(``transformer._head_logits``). Partitioning is an execution schedule, never
a numerics change — any hetero_ctx mode must generate identical tokens.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax.numpy as jnp

from . import mamba2, rwkv6, transformer


@dataclass(frozen=True)
class Model:
    cfg: Any
    init: Callable
    loss: Callable
    init_cache: Optional[Callable]
    prefill: Optional[Callable]
    decode_step: Optional[Callable]
    encode: Optional[Callable] = None
    # paged KV cache (attention-family models only; None for SSM/RWKV whose
    # recurrent state is O(1) and needs no paging)
    init_paged_cache: Optional[Callable] = None
    paged_prefill: Optional[Callable] = None
    paged_decode_step: Optional[Callable] = None
    # speculative decoding: K+1-position verification in one dispatch
    paged_verify: Optional[Callable] = None
    # stage-parallel mixed batch: one dispatch = batched paged decode step
    # for all lanes + one prefill chunk, sharing a single pool write
    mixed_step: Optional[Callable] = None


def build_model(cfg) -> Model:
    if cfg.rwkv is not None:
        mod = rwkv6
    elif cfg.ssm is not None:
        mod = mamba2
    else:
        mod = transformer

    init = partial(mod.init_params, cfg=cfg)
    loss = partial(mod.loss_fn, cfg=cfg)
    if cfg.encoder_only:
        return Model(cfg=cfg, init=init, loss=loss, init_cache=None,
                     prefill=None, decode_step=None,
                     encode=partial(transformer.forward_hidden, cfg=cfg))
    paged = {}
    if mod is transformer:
        paged = dict(
            init_paged_cache=partial(transformer.init_paged_cache, cfg),
            paged_prefill=partial(transformer.paged_prefill, cfg=cfg),
            paged_decode_step=partial(transformer.paged_decode_step, cfg=cfg),
            paged_verify=partial(transformer.paged_verify, cfg=cfg),
            mixed_step=partial(transformer.mixed_step, cfg=cfg),
        )
    return Model(
        cfg=cfg, init=init, loss=loss,
        init_cache=partial(mod.init_cache, cfg),
        prefill=partial(mod.prefill, cfg=cfg),
        decode_step=partial(mod.decode_step, cfg=cfg),
        **paged,
    )

"""Heterogeneous speculative decoding: draft on the flexible path, verify
K+1 tokens per target dispatch on the solver-planned path, roll back the
paged KV cache to the accepted prefix.

The paper's characterization (§3, §4.2) leaves decode stranded: the
aligned/NPU-style path only pays off at 128-stage token counts, while
decode (M=1) is memory-bound flexible-path work — the same stage-level gap
measured by *When NPUs Are Not Always Faster* (arXiv:2605.27435) and the
on-device decode bottleneck in *Understanding LLMs in Your Pockets*
(arXiv:2410.03613). Speculative decoding converts decode into M=K+1
verification batches — precisely the stage-shaped workload the aligned path
accelerates, and the one decode-side workload whose M the SCHEDULER gets to
choose. Three pieces, spread across the stack:

  * **Draft** — a small model (`SpecConfig.draft`, e.g. ``smollm-135m``; or
    the target itself for self-speculation) greedily proposes K tokens per
    round on the flexible path. :class:`DraftLanes` holds the per-lane
    draft caches (one batched dense cache, per-lane write cursors), with
    the K-step draft loop either host-synced or fused into ONE on-device
    ``lax.scan`` dispatch (``sync='device'``, §4.3 applied to the draft).
  * **Verify** — ONE target-model dispatch
    (``models/transformer.py::paged_verify``) scores all K+1 positions
    (pending token + K drafts) over cached-prefix + appended tokens,
    routed through a ``HeteroCtx`` whose plan includes the solver's VERIFY
    site class (``core/solver.py::solve_verify`` — M = lanes*(K+1) lands in
    act/hybrid territory). Greedy acceptance
    (``serving/sampler.py::greedy_verify``) is lossless: emitted tokens are
    bit-identical to per-token greedy decoding of the target, whatever the
    drafts were.
  * **Rollback** — rejected positions are reclaimed token-level by
    ``PagedKVCache.truncate_to`` (whole blocks past the accepted prefix
    return to the free list, inside the admission reservation); stale pool
    slots are masked positionally and rewritten before any later query
    attends them, so rollback costs nothing on the device side.

:class:`SpecDecoder` is the single-stream engine (one request, lanes=1);
``serving/scheduler.py::PagedBatcher(spec=...)`` runs the same round
batched across decode lanes. This is the first subsystem where TWO models
coexist in one serving process.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import build_model

from .paged_cache import PagedKVCache
from .sampler import greedy_verify


@dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding settings.

    ``draft``: the draft model — a config name from ``repro/configs``
    (e.g. ``"smollm-135m"``), a ``ModelConfig`` instance, or None for
    self-speculation (the target drafts for itself: the acceptance-rate
    upper bound, useful for benchmarks). ``smoke`` resolves a name via
    ``get_smoke_config`` instead of ``get_config``. ``k`` is the
    speculation length: drafts per round, so up to k+1 tokens emitted per
    target dispatch. Only greedy verification is implemented — it is the
    arm whose output stream is provably identical to non-speculative
    greedy decoding.
    """
    k: int = 4
    draft: Any = None                # name | ModelConfig | None (self-draft)
    smoke: bool = False              # name resolution: smoke-scale configs
    greedy: bool = True

    def resolve_draft(self, target_cfg):
        """Resolve ``draft`` to a ModelConfig and validate the pairing."""
        if self.k < 1:
            raise ValueError(f"speculation length k must be >= 1, got {self.k}")
        if not self.greedy:
            raise NotImplementedError(
                "only greedy verification is implemented")
        d = self.draft
        if isinstance(d, str):
            from repro.configs import get_config, get_smoke_config
            d = get_smoke_config(d) if self.smoke else get_config(d)
        elif d is None:
            d = target_cfg
        if d.vocab_size != target_cfg.vocab_size:
            raise ValueError(
                f"draft {d.name} (vocab {d.vocab_size}) and target "
                f"{target_cfg.name} (vocab {target_cfg.vocab_size}) must "
                "share one token space for speculative decoding")
        if d.encoder_only or d.rwkv is not None or d.ssm is not None:
            raise ValueError(f"draft {d.name}: drafting needs a decoder "
                             "attention-family model")
        return d


class DraftLanes:
    """Per-lane draft-model caches behind one batched dense KV cache.

    Each of ``lanes`` decode lanes owns a slot (its 'draft cache'): a
    ``[lanes, max_len]`` dense KV region plus a host-authoritative write
    cursor. Prompts prefill bucket-chunked into their slot; each draft
    round runs k+1 greedy steps — feeding the pending token, then each
    draft including the k-th, so a fully-accepted round leaves no cache
    hole — and rollback is a cursor reset (stale slots past the cursor are
    positionally masked and rewritten before any later query attends them,
    the same invariant the paged pool relies on).

    ``sync='host'`` dispatches each draft step separately;
    ``sync='device'`` fuses the whole round into one jitted ``lax.scan``
    (``core/sync.py::generate_on_device`` — fast sync applied to the
    draft). ``dispatches`` counts every draft-model dispatch (prefill
    chunks included); the spec win is measured in TARGET dispatches, but
    the draft-side cost stays observable.
    """

    def __init__(self, cfg, params, *, lanes: int, max_len: int,
                 buckets=(64, 128, 256), sync: str = "host", dtype=None,
                 tracer=None):
        from .trace import NULL_TRACER
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.W = lanes
        self.max_len = max_len
        self.buckets = tuple(sorted(buckets))
        self.sync = sync
        dtype = dtype if dtype is not None else jnp.dtype(cfg.compute_dtype)
        self.cache = self.model.init_cache(batch=lanes, max_len=max_len,
                                           dtype=dtype)
        self.cache["index"] = jnp.zeros((lanes,), jnp.int32)
        self.idx = np.zeros((lanes,), np.int32)   # per-lane write cursors
        self.dispatches = 0
        self._step = jax.jit(self.model.decode_step, donate_argnums=(2,))
        from repro.models import transformer
        self._prefill_piece = jax.jit(partial(transformer.prefill_slot,
                                              cfg=cfg),
                                      static_argnames=("chunk",),
                                      donate_argnums=(1,))

    def prefill(self, lane: int, prompt: np.ndarray) -> None:
        """Bucket-chunked prompt prefill into ``lane``'s slot."""
        from .scheduler import bucket_chunks   # deferred: avoids a cycle
        idx = 0
        for c in bucket_chunks(len(prompt), self.buckets):
            piece = jnp.asarray(prompt[idx: idx + c], jnp.int32)
            with self.tracer.dispatch("draft_prefill_chunk", track="draft",
                                      args={"lane": lane, "chunk": c,
                                            "start": idx}):
                _, self.cache = self._prefill_piece(
                    self.params, self.cache, piece, jnp.asarray(lane),
                    jnp.asarray(idx, jnp.int32), chunk=c)
            self.dispatches += 1
            self.tracer.count("draft_dispatches")
            idx += c
        self.idx[lane] = len(prompt)

    def draft(self, last: np.ndarray, k: int) -> np.ndarray:
        """One draft round: feed each lane's pending token (``last`` [W, 1])
        and greedily roll k+1 steps forward. Returns drafts [W, k] (the
        k+1-th prediction is discarded — that step exists to WRITE the
        k-th draft's KV so full acceptance leaves the cache gapless).
        Inactive lanes draft garbage that the caller discards."""
        cache = {**self.cache, "index": jnp.asarray(self.idx)}
        tok = jnp.asarray(last, jnp.int32)
        with self.tracer.dispatch("spec_draft", track="draft",
                                  args={"k": k, "sync": self.sync,
                                        "lanes": self.W}):
            if self.sync == "device":
                from repro.core.sync import generate_on_device
                toks, self.cache = generate_on_device(self.model, self.params,
                                                      tok, cache, k + 1)
                self.dispatches += 1
                self.tracer.count("draft_dispatches")
            else:
                outs = []
                for _ in range(k + 1):
                    logits, cache = self._step(self.params, tok, cache)
                    tok = jnp.argmax(logits[:, -1, :], axis=-1
                                     ).astype(jnp.int32)[:, None]
                    outs.append(tok[:, 0])
                    self.dispatches += 1
                    self.tracer.count("draft_dispatches")
                self.cache = cache
                toks = jnp.stack(outs, axis=1)
            toks = toks[:, :k]
            with self.tracer.span("draft_sync", track="draft", cat="sync"):
                toks = np.asarray(toks)
        self.idx = self.idx + np.int32(k + 1)
        return toks

    def rollback(self, lane: int, n_tokens: int) -> None:
        """Reset ``lane``'s cursor to the accepted token count — the whole
        draft-side rollback (stale cache beyond it is masked/rewritten)."""
        self.idx[lane] = n_tokens


class SpecDecoder:
    """Single-stream speculative decoding over the paged KV pool.

    One request at a time: prompt prefills through the (optional)
    solver-planned ``HeteroCtx``, then rounds of draft (flexible path) →
    ``paged_verify`` (one target dispatch, VERIFY-planned matmuls) →
    ``greedy_verify`` acceptance → ``truncate_to`` rollback, until the
    token budget (or ``eos_id``) is hit. Greedy outputs are identical to
    per-token greedy decoding of the target — drafting only changes how
    many target dispatches that stream costs.

    The serving-scale version of the same round is
    ``serving/scheduler.py::PagedBatcher(spec=...)``; this class is the
    paper-faithful single-stream arm the benchmarks sweep.
    """

    def __init__(self, cfg, params=None, *, spec: SpecConfig = SpecConfig(),
                 draft_params=None, num_blocks: Optional[int] = None,
                 block_size: int = 32, max_len: int = 512,
                 buckets=(64, 128, 256), engine_mode: Optional[str] = None,
                 sync: str = "host", eos_id: Optional[int] = None,
                 cache_dtype=None, seed: int = 0):
        if sync not in ("host", "device"):
            raise ValueError(f"sync must be 'host' or 'device', got {sync!r}")
        self.cfg = cfg
        self.model = build_model(cfg)
        if self.model.paged_verify is None:
            raise ValueError(f"{cfg.name}: speculative decoding requires an "
                             "attention-family target model")
        self.params = params if params is not None else self.model.init(
            jax.random.PRNGKey(seed))
        self.spec = spec
        self.eos_id = eos_id
        self.buckets = tuple(sorted(buckets))
        self.max_len = max_len
        dtype = (cache_dtype if cache_dtype is not None
                 else jnp.dtype(cfg.compute_dtype))
        num_blocks = (num_blocks if num_blocks is not None
                      else 1 + -(-(max_len + spec.k) // block_size))
        self.kv = PagedKVCache(cfg, num_blocks=num_blocks,
                               block_size=block_size, dtype=dtype)

        draft_cfg = spec.resolve_draft(cfg)
        self.draft_cfg = draft_cfg
        if draft_params is None:
            draft_params = (self.params if draft_cfg is cfg else
                            build_model(draft_cfg).init(
                                jax.random.PRNGKey(seed + 1)))
        self.drafts = DraftLanes(draft_cfg, draft_params, lanes=1,
                                 max_len=max_len + spec.k + 1,
                                 buckets=buckets, sync=sync,
                                 dtype=jnp.float32 if dtype == jnp.float32
                                 else None)

        if engine_mode is not None:
            from repro.core.engine import build_hetero_ctx
            self.ctx = build_hetero_ctx(
                cfg, engine_mode,
                sync_mode="fast" if sync == "device" else "host",
                verify_ks=((spec.k, 1),))
            vctx = self.ctx.for_verify(spec.k, 1)
        else:
            self.ctx = vctx = None
        self._prefill = jax.jit(partial(self.model.paged_prefill,
                                        hetero_ctx=self.ctx),
                                donate_argnums=(2,))
        self._verify = jax.jit(partial(self.model.paged_verify,
                                       hetero_ctx=vctx),
                               donate_argnums=(2,))
        self._accept = jax.jit(greedy_verify)
        # observability: the spec win is target dispatches vs emitted tokens
        self.rounds = 0
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.prefill_dispatches = 0
        self.verify_dispatches = 0
        self.emitted_tokens = 0

    def stats(self) -> dict:
        """Counter snapshot, same contract as the batchers' ``stats()``."""
        return {
            "spec_k": self.spec.k,
            "draft_model": self.draft_cfg.name,
            "rounds": self.rounds,
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            "acceptance_rate": (self.accepted_tokens /
                                max(self.drafted_tokens, 1)),
            "prefill_dispatches": self.prefill_dispatches,
            "verify_dispatches": self.verify_dispatches,
            "draft_dispatches": self.drafts.dispatches,
            "target_dispatches": (self.prefill_dispatches +
                                  self.verify_dispatches),
            "emitted_tokens": self.emitted_tokens,
        }

    def generate(self, prompt: np.ndarray, max_new_tokens: int = 16
                 ) -> list[int]:
        """Greedy-generate ``max_new_tokens`` tokens after ``prompt``
        ([S] int32). Returns the emitted token list."""
        from .scheduler import bucket_chunks   # deferred: avoids a cycle
        S = len(prompt)
        if S + max_new_tokens + self.spec.k > self.max_len:
            raise ValueError(f"prompt {S} + budget {max_new_tokens} exceeds "
                             f"max_len {self.max_len}")
        seq = self.kv.open_sequence(prompt_tokens=S,
                                    total_tokens=S + max_new_tokens)
        bt = jnp.asarray(seq.table)[None]
        idx, logits = 0, None
        for c in bucket_chunks(S, self.buckets):
            piece = jnp.asarray(prompt[idx: idx + c], jnp.int32)
            logits, self.kv.pool = self._prefill(
                self.params, piece[None], self.kv.pool, block_table=bt,
                start_index=jnp.asarray(idx, jnp.int32))
            self.prefill_dispatches += 1
            idx += c
        seq.length = S
        self.drafts.prefill(0, np.asarray(prompt))

        k = self.spec.k
        out = [int(jnp.argmax(logits[0, -1]))]
        budget = max_new_tokens - 1
        if self.eos_id is not None and out[0] == self.eos_id:
            budget = 0
        while budget > 0:
            # coverage: only rows the acceptance rule can emit are read, so
            # growth is capped by the remaining budget (stays inside the
            # admission reservation); writes past it sink in the null block
            self.kv.grow_to(seq, seq.length + min(k + 1, budget))
            # re-snapshot the table EVERY round: grow_to/truncate_to mutate
            # the host-side seq.table, and a stale device copy would alias
            # newly-grown positions into the null block
            bt = jnp.asarray(seq.table)[None]
            last = np.asarray([[out[-1]]], np.int32)
            drafts = self.drafts.draft(last, k)                  # [1, k]
            tokens = np.concatenate([last, drafts], axis=1)      # [1, k+1]
            logits, self.kv.pool = self._verify(
                self.params, jnp.asarray(tokens), self.kv.pool,
                block_table=bt,
                start_index=jnp.asarray([seq.length], jnp.int32))
            self.verify_dispatches += 1
            emitted, n_emit = self._accept(jnp.asarray(drafts), logits)
            round_budget = budget
            e = min(int(n_emit[0]), budget)
            toks = [int(t) for t in np.asarray(emitted)[0, :e]]
            if self.eos_id is not None and self.eos_id in toks:
                toks = toks[: toks.index(self.eos_id) + 1]
                budget = len(toks)                       # exhausted below
            self.rounds += 1
            # acceptance rate counts only drafts whose verification row was
            # budget-covered (rows past the coverage score null-block
            # garbage) and only acceptances that actually emitted — neither
            # side of the ratio may include schedule-truncated drafts
            self.drafted_tokens += min(k, round_budget)
            self.accepted_tokens += min(int(n_emit[0]) - 1, len(toks))
            out.extend(toks)
            budget -= len(toks)
            new_len = seq.length + len(toks)
            self.kv.truncate_to(seq, new_len)            # paged rollback
            seq.length = new_len
            self.drafts.rollback(0, new_len)             # draft rollback
        self.emitted_tokens += len(out)
        self.kv.close_sequence(seq)
        return out

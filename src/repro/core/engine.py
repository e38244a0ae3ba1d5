"""HeteroInfer inference engine (paper §4.4 "Inference Engine", Fig 11).

Offline: profiler -> solver -> PartitionPlan (graphs "generated in advance").
Online: per request, pick the prefill strategy for the ACTUAL sequence length
and run decode with fast synchronization.

Engine modes (the paper's eval arms):
  'xla'            — flexible-path only            (= MNN/MLC GPU-only)
  'mxu'            — aligned-path only, pad to buckets (= llm.npu/PI-2 NPU-only)
  'hetero-layer'   — per-op affinity (§4.1)
  'hetero-tensor'  — solver-driven tensor partitioning (§4.2)

Prefill strategies for dynamic lengths (paper §5.3.2 / Fig 14):
  'online-prepare' — (re)trace+compile at the exact length each time
  'padding'        — pad every matmul's token dim to the next bucket
  'pipe'           — sequential standard-bucket chunked prefill (NPU-pipe)
  'hetero'         — standard-bucket chunks + ragged remainder chunk
                     (multi-tensor activation partitioning, Fig 9)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import build_model

from .partition import HeteroCtx
from .profiler import LatencyTable, STANDARD_BUCKETS, profile_analytic
from .solver import PartitionSolver, PartitionPlan
from .sync import fence, generate_host_loop, generate_on_device


def build_plan(cfg, *, sync_mode: str = "fast",
               table: Optional[LatencyTable] = None, mixed_pairs=(),
               verify_ks=(), extra_ms=(),
               weight_quant: Optional[str] = None) -> tuple[LatencyTable,
                                                            PartitionPlan]:
    """Offline phase (paper Fig 11 left half): profile the model's weight
    shapes, then solve the per-(site, M) partitioning decisions. Shared by
    the single-stream engine and the paged serving scheduler so both run
    the SAME solver-planned execution. ``mixed_pairs``: (prefill chunk,
    decode width) pairs the mixed-batch scheduler will fuse — solved into
    ``plan.mixed_decisions`` (strategy MIXED). ``verify_ks``: (k, lanes)
    speculative-verification shapes the spec decoder will dispatch —
    solved into ``plan.verify_decisions`` (the VERIFY site class).
    ``extra_ms``: extra token counts added to the solve grid — the
    prefix-cache scheduler's suffix-chunk lengths, so warm-path chunks get
    first-class solved decisions. ``weight_quant`` (None | 'int8' |
    'w4a16'): profile and solve against the quantized weight-stream bytes —
    memory-bound decode shapes re-plan when the weight HBM traffic halves
    (or quarters), so a quantized deployment gets its own plan."""
    table = table or profile_analytic(cfg, weight_quant=weight_quant)
    solver = PartitionSolver(table, sync_mode=sync_mode,
                             weight_quant=weight_quant)
    return table, solver.solve(cfg, mixed_pairs=mixed_pairs,
                               verify_ks=verify_ks, extra_ms=extra_ms)


def build_hetero_ctx(cfg, mode: str, *, sync_mode: str = "fast",
                     mixed_pairs=(),
                     verify_ks=(), extra_ms=(),
                     weight_quant: Optional[str] = None) -> HeteroCtx:
    """Profile + solve + wrap in the HeteroCtx that models thread through
    every matmul site (including the LM head)."""
    _, plan = build_plan(cfg, sync_mode=sync_mode, mixed_pairs=mixed_pairs,
                         verify_ks=verify_ks, extra_ms=extra_ms,
                         weight_quant=weight_quant)
    return HeteroCtx(mode=mode, plan=plan)


def dispatch_prediction(plan, cfg, *, m=None, steps: int = 1,
                        mixed=None, verify=None):
    """Decision tags + predicted duration for ONE scheduler dispatch.

    Returns ``(tags, total_us)`` where tags is a tuple of
    ``(site, M, strategy, t_us, count)`` — one per partitionable site —
    and ``count`` folds in how many times that site's matmul runs inside
    the dispatch: ``steps`` forward passes, each hitting every
    non-``head`` site ``cfg.n_layers`` times and ``head`` once (mirroring
    :meth:`InferenceEngine.predicted_prefill_us`). Exactly one shape
    selector applies: ``m`` (plain M-token dispatch, nearest-grid-M
    lookup — decode widths and off-bucket chunks resolve the same way
    HeteroCtx picks kernels), ``mixed=(m_prefill, m_decode)`` (fused
    stage-parallel step) or ``verify=(k, lanes)`` (spec verification).
    The serving tracer attaches these tags to each dispatch span and the
    drift aggregator scores them against measured durations. ``plan=None``
    (no engine mode, no solver) yields ``((), 0.0)`` — untagged spans."""
    if plan is None:
        return (), 0.0
    sites = sorted({s for (s, _) in plan.decisions})
    tags, total = [], 0.0
    for site in sites:
        if verify is not None:
            k, lanes = verify
            dec = plan.verify_decision(site, k, lanes) \
                or plan.lookup(site, lanes * (k + 1))
        elif mixed is not None:
            mp, md = mixed
            dec = plan.mixed_decision(site, mp, md) \
                or plan.lookup(site, mp + md)
        else:
            dec = plan.lookup(site, 1 if m is None else m)
        if dec is None:
            continue
        count = steps * (1 if site == "head" else cfg.n_layers)
        tags.append((site, dec.M, dec.strategy, dec.t_us, count))
        total += dec.t_us * count
    return tuple(tags), total


@dataclass
class EngineStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    compile_s: float = 0.0
    n_compiles: int = 0
    prefill_tokens: int = 0
    decode_tokens: int = 0

    def tokens_per_s(self) -> dict:
        return {
            "prefill_tok_s": self.prefill_tokens / self.prefill_s
            if self.prefill_s else 0.0,
            "decode_tok_s": self.decode_tokens / self.decode_s
            if self.decode_s else 0.0,
        }


class InferenceEngine:
    def __init__(self, cfg, params=None, *, mode: str = "hetero-tensor",
                 prefill_strategy: str = "hetero", fast_sync: bool = True,
                 table: Optional[LatencyTable] = None,
                 plan: Optional[PartitionPlan] = None,
                 buckets: tuple = STANDARD_BUCKETS,
                 max_len: int = 2048,
                 use_kernels: bool = True, rng=None, clock=None):
        # EngineStats timing reads the injected clock (serving/telemetry
        # Clock protocol) — MonotonicClock by default, FakeClock in tests
        # keeps tier-1 free of wall-clock reads.
        if clock is None:
            from repro.serving.telemetry import MonotonicClock
            clock = MonotonicClock()
        self.clock = clock
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params if params is not None else self.model.init(
            rng if rng is not None else jax.random.PRNGKey(0))
        self.mode = mode
        self.prefill_strategy = prefill_strategy
        self.fast_sync = fast_sync
        self.buckets = tuple(sorted(buckets))
        self.max_len = max_len
        if plan is None:
            self.table, self.plan = build_plan(
                cfg, sync_mode="fast" if fast_sync else "host", table=table)
        else:
            self.table, self.plan = table or profile_analytic(cfg), plan
        # use_kernels: route MXU-path matmuls through the Pallas kernel
        # (compiled on a TPU, interpreted elsewhere — repro.kernels; CPU
        # wall-times of the MXU path are NOT representative of silicon).
        self.ctx = (HeteroCtx(mode=mode, plan=self.plan) if use_kernels
                    else None)
        self.stats = EngineStats()
        self._prefill_cache: dict = {}

    # ------------------------------------------------------------- helpers --
    def _jit_prefill(self, chunk_len: int):
        """One compiled graph per chunk length ('graphs generated in
        advance'); a NEW length costs a trace+compile — the cost
        Online-prepare pays per request and bucketing amortizes."""
        key = ("prefill", chunk_len)
        new = key not in self._prefill_cache
        if new:
            self._prefill_cache[key] = jax.jit(
                partial(self.model.prefill, hetero_ctx=self.ctx),
                donate_argnums=(2,))
            self.stats.n_compiles += 1
        return self._prefill_cache[key], new

    def _bucket_chunks(self, S: int) -> list[tuple[int, int]]:
        """Split S into (chunk_graph_size, true_tokens) pieces."""
        if self.prefill_strategy in ("online-prepare", "padding"):
            return [(S, S)]     # padding happens inside matmuls (PAD decisions)
        if self.prefill_strategy == "pipe":
            # NPU-pipe: standard-size chunks over the first S-1 tokens (the
            # tail padded to the smallest bucket), then an EXACT 1-token
            # chunk so last-token logits come from the true final position.
            chunks, rem = [], S - 1
            for b in sorted(self.buckets, reverse=True):
                while rem >= b:
                    chunks.append((b, b))
                    rem -= b
            if rem:
                chunks.append((min(self.buckets), rem))       # padded tail
            chunks.append((1, 1))
            return chunks
        chunks, rem = [], S
        for b in sorted(self.buckets, reverse=True):
            while rem >= b:
                chunks.append((b, b))
                rem -= b
        if rem:
            chunks.append((rem, rem))   # hetero: ragged remainder (XLA path)
        return chunks

    # -------------------------------------------------------------- public --
    def generate(self, prompt: jax.Array, max_new_tokens: int = 32,
                 greedy: bool = True) -> jax.Array:
        """prompt: [B, S] int32. Returns [B, max_new_tokens]."""
        B, S = prompt.shape
        # pipe's padded tail may write up to min(buckets)-1 slots past S;
        # without headroom the dynamic_update_slice would CLAMP and corrupt
        # earlier cache slots.
        pad_headroom = (min(self.buckets) if self.prefill_strategy == "pipe"
                        else 0)
        total = S + max_new_tokens + pad_headroom
        cache = self.model.init_cache(
            batch=B, max_len=total,
            dtype=jnp.dtype(self.cfg.compute_dtype))

        t0 = self.clock.now()
        chunks = self._bucket_chunks(S)
        idx = 0
        logits = None
        for c, take in chunks:
            piece = prompt[:, idx: idx + take]
            if take < c:                # pipe-mode padded tail
                piece = jnp.pad(piece, ((0, 0), (0, c - take)))
            fn, new = self._jit_prefill(c)
            tc = self.clock.now()
            logits, cache = fn(self.params, piece, cache, start_index=idx)
            if new:                     # first call pays trace+compile
                fence(logits)
                self.stats.compile_s += self.clock.now() - tc
            idx += take
        cache = {**cache, "index": jnp.asarray(S, jnp.int32)}
        fence(logits)
        self.stats.prefill_s += self.clock.now() - t0
        self.stats.prefill_tokens += B * S

        first = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)[:, None]
        t0 = self.clock.now()
        n_more = max_new_tokens - 1
        if n_more > 0:
            gen = generate_on_device if self.fast_sync else generate_host_loop
            toks, cache = gen(self.model, self.params, first, cache, n_more)
            out = jnp.concatenate([first, toks], axis=1)
        else:
            out = first
        fence(out)
        self.stats.decode_s += self.clock.now() - t0
        self.stats.decode_tokens += B * max_new_tokens
        return out

    # --------------------------------------------------- analytic latencies --
    def predicted_prefill_us(self, S: int) -> float:
        """Solver-predicted prefill matmul latency for length S (per layer
        set), used by the paper-faithful latency benchmarks."""
        total = 0.0
        for site in self.table.sites:
            if site == "head":
                continue
            dec = PartitionSolver(self.table,
                                  sync_mode="fast" if self.fast_sync else "host"
                                  ).solve_site(site, max(S, 1))
            total += dec.t_us
        return total * self.cfg.n_layers

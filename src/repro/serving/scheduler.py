"""Continuous-batching serving schedulers: dense slots and paged blocks.

``ContinuousBatcher`` (the dense baseline) runs slot-based continuous
batching over a preallocated ``[max_batch, max_len]`` KV cache: requests
join free slots, prefill runs per-request as bucket-chunked pieces (the
engine's activation-centric strategy applied at the scheduler level),
decode steps run batched across all active slots with PER-SLOT cache
indices. Finished slots free immediately and the queue backfills
(orca-style iteration-level scheduling).

``PagedBatcher`` rebases the same loop on the paged KV pool
(serving/paged_cache.py): admission is gated by FREE BLOCKS rather than
fixed slots, so many short requests can share the memory one long request
used to reserve under the dense scheme, and the queue backfills at block
granularity — the KV-capacity lever the paper's unified-memory analysis
(§3, §4.2) identifies as the mobile serving bottleneck.

The paged batcher additionally fuses the HeteroInfer engine into the
serving path (docs/heterogeneous-execution.md):

  * ``sync='device'`` — fast-sync decode (§4.3): one jitted ``lax.scan``
    runs a ``window`` of paged decode steps per dispatch, so the scheduler
    pays one host round-trip per WINDOW instead of per token (the paper's
    ~400us-clFinish-per-kernel problem, at serving batch widths).
    ``sync='host'`` keeps the per-token host-synced loop as the measurable
    baseline arm.
  * ``engine_mode=...`` — solver-planned prefill (§4.1/§4.2): admission-time
    prefill chunks route every matmul through a ``HeteroCtx`` whose
    ``PartitionSolver`` plan was solved offline for this model, with one
    compiled graph per chunk length ('graphs generated in advance').
  * ``mixed_batch=True`` — stage-parallel mixed batching (§4.1-§4.3 at the
    stage level): each scheduler step coalesces ONE bucket-sized prefill
    chunk of the admitting request with the decode step/window of every
    running lane into a single jitted dispatch (``transformer.mixed_step``
    / the mixed ``paged_decode_window``), sharing one paged-pool write.
    Decode (memory-bound, flexible path) and the prefill chunk
    (compute-bound, aligned MXU path) run concurrently — the SoC's full
    compute AND bandwidth envelopes — so admission stops costing its own
    dispatches and never stalls decode.
  * ``spec=SpecConfig(...)`` — heterogeneous speculative decoding
    (serving/spec.py): each scheduler step becomes one ROUND — the draft
    model proposes K tokens per lane on the flexible path (per-lane draft
    caches, one fused draft dispatch under ``sync='device'``), ONE
    ``paged_verify`` target dispatch scores all lanes' K+1 positions
    through the solver's VERIFY-planned matmuls, greedy acceptance emits
    1..K+1 tokens per lane, and ``PagedKVCache.truncate_to`` reclaims the
    rejected tail block-granularly. Decode's per-token dispatch tax drops
    to per-round; greedy outputs stay bit-identical to the non-spec arms.

Both batchers expose one ``stats() -> dict`` counter snapshot (dispatches,
steps, fusion and speculation counters) — the contract the benches assert
on and ``serve.py --stats`` prints.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import build_model

from .paged_cache import PagedKVCache, SequenceBlocks
from .sampler import SamplerConfig, greedy_verify, sample
from .spec import DraftLanes, SpecConfig
from .telemetry import MonotonicClock
from .trace import NULL_TRACER


def _named(name: str, fn, **bound):
    """``partial(fn, **bound)`` as a plain function called ``name``: jit
    names its program after it (``jit_<name>``), where a partial of a
    partial lowers as ``jit__unknown``. Made once per batcher, so its
    identity is stable for jit's caches and as a static argument."""
    def call(*args, **kw):
        return fn(*args, **bound, **kw)
    call.__name__ = call.__qualname__ = name
    return call


def bucket_chunks(S: int, buckets: tuple) -> list[int]:
    """Greedy bucket decomposition of a prompt length: aligned chunks take
    the static fast path, the ragged tail takes the flexible path. Shared
    by the dense and paged batchers so both chunk prefill identically."""
    chunks, rem = [], S
    for bk in sorted(buckets, reverse=True):
        while rem >= bk:
            chunks.append(bk)
            rem -= bk
    if rem:
        chunks.append(rem)
    return chunks


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [S] int32
    max_new_tokens: int = 16
    output: list = field(default_factory=list)
    done: bool = False
    # TTFT stages, stamped by PagedBatcher on its clock: submitted, first
    # prefill chunk dispatched, first token on the host, and the end of the
    # step that placed it (when ingress can stream it)
    t_submit: Optional[float] = None
    t_prefill: Optional[float] = None
    t_first: Optional[float] = None
    t_placed: Optional[float] = None


def _validate_submit(req: Request, live_rids) -> None:
    """Shared submit guard: an empty prompt has no first token to sample
    (prefill would dispatch a zero-length chunk) and a request id already
    queued or in flight would make two streams indistinguishable — both
    raise here instead of failing obscurely mid-schedule. Finished ids may
    be reused (replay waves and preemption resumes depend on it)."""
    if len(req.prompt) == 0:
        raise ValueError(f"request {req.rid}: empty prompt — a request "
                         "must carry at least one prompt token")
    if req.rid in live_rids:
        raise ValueError(f"request {req.rid}: duplicate id — a request "
                         "with this id is already queued or in flight")


class ContinuousBatcher:
    def __init__(self, cfg, params=None, *, max_batch: int = 4,
                 max_len: int = 512, buckets=(64, 128, 256),
                 sampler: SamplerConfig = SamplerConfig(), seed: int = 0,
                 weight_quant: str | None = None, tracer=None):
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params if params is not None else self.model.init(
            jax.random.PRNGKey(seed))
        self.weight_quant = weight_quant
        if weight_quant is not None:
            from repro.models.quant import quantize_params
            self.params = quantize_params(self.params, cfg, weight_quant)
        self.B, self.S = max_batch, max_len
        self.buckets = tuple(sorted(buckets))
        self.sampler = sampler
        self.rng = jax.random.PRNGKey(seed)

        # the cache holds activations: compute dtype, as the paged pool does
        self.cache = self.model.init_cache(
            batch=max_batch, max_len=max_len,
            dtype=jnp.dtype(cfg.compute_dtype))
        self.cache["index"] = jnp.zeros((max_batch,), jnp.int32)
        self.slots: list[Optional[Request]] = [None] * max_batch
        self.queue: list[Request] = []
        self.budget: list[int] = [0] * max_batch
        self.lengths: list[int] = [0] * max_batch   # python-side slot lengths
        self.peak_active = 0           # max concurrent requests observed
        self.decode_dispatches = 0     # batched decode steps issued
        self.decode_steps = 0          # per-slot tokens decoded
        self.prefill_dispatches = 0    # prefill chunk dispatches issued

        self._decode = jax.jit(self.model.decode_step, donate_argnums=(2,))
        from repro.models import transformer
        self._prefill_piece = jax.jit(partial(transformer.prefill_slot,
                                              cfg=cfg),
                                      static_argnames=("chunk",),
                                      donate_argnums=(1,))

    @property
    def busy(self) -> bool:
        """Work outstanding: queued requests or occupied slots (same
        contract as ``PagedBatcher.busy`` — what external tick-drivers
        loop on)."""
        return bool(self.queue or any(s is not None for s in self.slots))

    # ------------------------------------------------------------ plumbing --
    def submit(self, req: Request):
        _validate_submit(req, {r.rid for r in self.queue}
                         | {s.rid for s in self.slots if s is not None})
        self.queue.append(req)

    def _admit(self):
        for b in range(self.B):
            if self.slots[b] is None and self.queue:
                req = self.queue.pop(0)
                self.slots[b] = req
                S = len(req.prompt)
                logits, idx = None, 0
                for c in bucket_chunks(S, self.buckets):
                    piece = jnp.asarray(req.prompt[idx: idx + c], jnp.int32)
                    with self.tracer.dispatch(
                            "prefill_chunk", track="prefill",
                            args={"rid": req.rid, "chunk": c, "start": idx}):
                        logits, self.cache = self._prefill_piece(
                            self.params, self.cache, piece,
                            jnp.asarray(b), jnp.asarray(idx, jnp.int32),
                            chunk=c)
                    self.prefill_dispatches += 1
                    self.tracer.count("prefill_dispatches")
                    idx += c
                self.cache["index"] = self.cache["index"].at[b].set(S)
                self.lengths[b] = S
                self.rng, k = jax.random.split(self.rng)
                first = int(sample(logits[:, -1, :], k, self.sampler)[0])
                req.output.append(first)
                self.budget[b] = req.max_new_tokens - 1
                if self.budget[b] <= 0:     # satisfied at prefill: don't
                    req.done = True         # overproduce a decode token
                    self.slots[b] = None
                    self.lengths[b] = 0

    # ----------------------------------------------------------------- run --
    def step(self):
        """One scheduler tick: admit waiting requests, one batched decode."""
        self._admit()
        active = [b for b in range(self.B) if self.slots[b] is not None]
        self.peak_active = max(self.peak_active, len(active))
        self.tracer.gauge("peak_active", self.peak_active)
        if not active:
            return False
        last = np.zeros((self.B, 1), np.int32)
        for b in active:
            last[b, 0] = self.slots[b].output[-1]
        self.rng, k = jax.random.split(self.rng)
        # decode_step itself advances every slot's index by one
        with self.tracer.dispatch("decode_step", track="decode",
                                  args={"active": len(active)}):
            logits, self.cache = self._decode(self.params,
                                              jnp.asarray(last), self.cache)
            toks = sample(logits[:, -1, :], k, self.sampler)
            with self.tracer.span("step_sync", track="decode", cat="sync"):
                toks = np.asarray(toks)
        self.decode_dispatches += 1
        self.tracer.count("decode_dispatches")
        for b in active:
            req = self.slots[b]
            req.output.append(int(toks[b]))
            self.budget[b] -= 1
            self.lengths[b] += 1
            self.decode_steps += 1
            self.tracer.count("decode_steps")
            if self.budget[b] <= 0 or self.lengths[b] + 1 >= self.S:
                req.done = True
                self.slots[b] = None           # free slot; queue backfills
                self.lengths[b] = 0
        return True

    def stats(self) -> dict:
        """Unified counter snapshot (same contract as ``PagedBatcher.stats``):
        dispatches actually issued vs tokens produced."""
        return {
            "peak_active": self.peak_active,
            "decode_dispatches": self.decode_dispatches,
            "decode_steps": self.decode_steps,
            "prefill_dispatches": self.prefill_dispatches,
            "fused_steps": 0,
            "total_dispatches": (self.decode_dispatches +
                                 self.prefill_dispatches),
        }

    def run(self, requests: list[Request], max_ticks: int = 10_000):
        for r in requests:
            self.submit(r)
        ticks = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and ticks < max_ticks:
            self.step()
            ticks += 1
        return requests


# --------------------------------------------------------------- paged ------

@dataclass
class _PagedLane:
    """One decode lane: the request plus its pool bookkeeping."""
    req: Request
    seq: SequenceBlocks
    budget: int = 0


@dataclass
class _Admission:
    """A request whose prefill is in flight under mixed batching: its blocks
    are already reserved, its prompt drains one bucket-sized chunk per
    scheduler step, each chunk fused into that step's decode dispatch."""
    req: Request
    seq: SequenceBlocks
    chunks: list                       # remaining chunk lengths
    idx: int = 0                       # prompt tokens resident so far
    #                                    (cache-hit prefix + prefilled)


class PagedBatcher:
    """Continuous batching over the paged KV pool.

    Admission is by free blocks: a request is admitted when
    ``ceil((len(prompt) + max_new_tokens) / block_size)`` blocks can be
    reserved, regardless of how many other requests are in flight (up to
    ``decode_width`` compiled decode lanes). Prompt blocks are allocated at
    admission; generation blocks are allocated lazily as decode crosses
    block boundaries (drawing on the admission-time reservation, so growth
    never fails mid-flight). Finished requests return their blocks and the
    queue backfills immediately.

    Decode runs as ONE jitted graph of static width ``decode_width``:
    inactive lanes carry a null block table and length 0, sinking their
    writes into the pool's null block. With ``sync='device'`` that graph is
    a fused WINDOW of ``window`` decode steps (core/sync.py
    ``paged_decode_window``): block tables are pre-grown on the host to
    cover the whole window's writes, per-lane budgets/EOS are masked inside
    the scan, and lengths/blocks are reconciled on the host after each
    window — one dispatch per window instead of per token.

    ``engine_mode`` in {'xla', 'mxu', 'hetero-layer', 'hetero-tensor'}
    routes prefill matmuls through the solver-planned HeteroCtx
    (partitioning is an execution schedule, never a numerics change, so
    greedy outputs are identical across engine modes and sync arms).

    ``mixed_batch=True`` turns on stage-parallel mixed batching: admission
    prefill no longer runs as its own dispatches. Instead one request at a
    time holds an ``_Admission`` ticket and each scheduler step fuses its
    next prompt chunk (capped at ``max_prefill_chunk_per_step`` tokens)
    into the decode dispatch of the running lanes — ``model.mixed_step``
    under ``sync='host'``, a chunk-carrying ``paged_decode_window`` under
    ``sync='device'``. Chunks only fall back to standalone prefill
    dispatches when no lane is decoding. Fusion reorders dispatches, never
    math: the two streams touch disjoint pool blocks, so greedy outputs
    stay token-identical to the admit-then-decode arms.

    ``spec=SpecConfig(k=K, draft=...)`` (or just ``spec=K``) turns on
    speculative decoding (serving/spec.py, greedy sampler only): each step
    is one round — K drafts per lane from the draft model's per-lane
    caches, ONE batched ``paged_verify`` target dispatch over every lane's
    pending+draft tokens (the solver's VERIFY site class), greedy
    acceptance, token-level pool rollback via ``truncate_to``. The draft
    loop is host-stepped under ``sync='host'`` and one fused on-device
    scan under ``sync='device'``; the TARGET pays one dispatch per round
    either way, which is the counter the benches compare. Mutually
    exclusive with ``mixed_batch`` (both re-purpose the step loop).

    ``prefix_cache=True`` turns on automatic prefix caching
    (serving/paged_cache.py): closed sequences retire their full blocks
    into a chain-hash-indexed cache instead of freeing them, admission
    shares every consecutively-matching block (refcounted, copy-on-write
    when the hit covers the whole prompt), and prefill runs only the
    uncached suffix — strictly fewer prefill dispatches and fresh-block
    allocations on shared-system-prompt traffic, with greedy outputs
    bit-identical to the cold path (cached KV was computed from the same
    tokens at the same positions). Eviction is LRU over refcount-0 cached
    blocks, so retention never reduces admissible capacity.

    ``weight_quant`` in {'int8', 'w4a16'} serves QUANTIZED weights: params
    are rewritten to QuantWeight containers at construction, every matmul
    site (prefill chunk, decode window, mixed step, verify) dispatches the
    in-VMEM-dequant MXU kernels under a HeteroCtx or the dequantize-then-
    matmul fallback without one — the same dequantized values either way,
    so engine modes and sync arms remain token-identical. ``kv_quant='int8'``
    stores the paged pool as int8 codes with per-token-slot bf16 scales
    (quantize-on-scatter, dequant-on-gather): equal pool memory holds ~2x
    the token blocks, which is the serving-capacity lever on a
    capacity-bound SoC. Both compose with windows, mixed batching,
    speculation (draft caches stay fp), and the prefix cache (cached blocks
    retire/share/CoW as int8 codes + scales).
    """

    def __init__(self, cfg, params=None, *, num_blocks: int = 65,
                 block_size: int = 32, max_blocks_per_seq: int | None = None,
                 decode_width: int = 8, buckets=(64, 128, 256),
                 sampler: SamplerConfig = SamplerConfig(), seed: int = 0,
                 cache_dtype=None, sync: str = "host", window: int = 8,
                 engine_mode: str | None = None, eos_id: int | None = None,
                 mixed_batch: bool = False,
                 max_prefill_chunk_per_step: int | None = None,
                 spec: SpecConfig | int | None = None,
                 spec_draft_params=None,
                 prefix_cache: bool = False,
                 weight_quant: str | None = None,
                 kv_quant: str | None = None,
                 mesh=None, tracer=None):
        if sync not in ("host", "device"):
            raise ValueError(f"sync must be 'host' or 'device', got {sync!r}")
        if mesh is not None and engine_mode is not None:
            raise ValueError(
                "engine_mode and mesh are mutually exclusive: the hetero "
                "engine partitions matmuls within one device, tensor "
                "parallelism partitions them across the mesh")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if isinstance(spec, int):
            spec = SpecConfig(k=spec)
        if spec is not None and mixed_batch:
            raise ValueError("spec mode and mixed_batch are mutually "
                             "exclusive")
        if spec is not None and sampler.temperature > 0.0:
            raise ValueError("spec mode implements greedy verification only;"
                             " use a temperature-0 sampler")
        if max_prefill_chunk_per_step is not None \
                and max_prefill_chunk_per_step < 1:
            raise ValueError("max_prefill_chunk_per_step must be >= 1, got "
                             f"{max_prefill_chunk_per_step}")
        from repro.models.quant import WEIGHT_FORMATS, quantize_params
        if weight_quant is not None and weight_quant not in WEIGHT_FORMATS:
            raise ValueError(f"weight_quant must be one of {WEIGHT_FORMATS} "
                             f"(or None), got {weight_quant!r}")
        if kv_quant not in (None, "int8"):
            raise ValueError(f"kv_quant must be 'int8' or None, "
                             f"got {kv_quant!r}")
        self.cfg = cfg
        self.model = build_model(cfg)
        if self.model.paged_decode_step is None:
            raise ValueError(f"{cfg.name}: paged KV cache requires an "
                             "attention-family model")
        self.params = params if params is not None else self.model.init(
            jax.random.PRNGKey(seed))
        self.weight_quant = weight_quant
        self.kv_quant = kv_quant
        if weight_quant is not None:
            # fp params in, QuantWeight-carrying params out: every matmul
            # site downstream (prefill chunks, decode windows, mixed steps,
            # verify) sees the quantized weights — dequantized identically
            # whether the HeteroCtx MXU kernels or the plan-free fallback
            # runs them, so engine modes stay token-identical
            self.params = quantize_params(self.params, cfg, weight_quant)
        # the fp activation dtype: pool storage when KV is unquantized, and
        # always the draft-lane cache dtype (draft caches stay fp)
        fp_dtype = (cache_dtype if cache_dtype is not None
                    else jnp.dtype(cfg.compute_dtype))
        self.block_size = block_size
        self.prefix_cache = prefix_cache
        # the layout object owns physical placement (single-device identity
        # or head-sharded tensor parallelism over mesh's 'model' axis); all
        # scheduler bookkeeping below it stays replicated/device-agnostic
        from repro.serving.layout import make_layout
        self.mesh = mesh
        self.layout = make_layout(cfg, mesh)
        # observability: NULL_TRACER (shared no-op) unless the caller wires
        # a live Tracer — the pool and draft lanes record into the same one
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # the TTFT stage counters' clock: the tracer's, when one is wired
        self.clock = (self.tracer.clock if self.tracer.enabled
                      else MonotonicClock())
        self.kv = PagedKVCache(
            cfg, num_blocks=num_blocks, block_size=block_size,
            max_blocks_per_seq=max_blocks_per_seq,
            dtype=fp_dtype, prefix_cache=prefix_cache, kv_quant=kv_quant,
            layout=self.layout if mesh is not None else None,
            tracer=self.tracer)
        self.W = decode_width
        self.buckets = tuple(sorted(buckets))
        self.sampler = sampler
        self.rng = jax.random.PRNGKey(seed)
        self.lanes: list[Optional[_PagedLane]] = [None] * decode_width
        self.queue: list[Request] = []
        self.peak_active = 0
        self.sync = sync
        self.window = window
        self.eos_id = eos_id
        self.engine_mode = engine_mode
        self.mixed_batch = mixed_batch
        # mixed-batch admission chunking: bucket-sized pieces no larger than
        # the per-step cap (so one step never fuses more than one cap's worth
        # of compute-bound prefill into the latency-sensitive decode path)
        cap = max_prefill_chunk_per_step
        self.max_prefill_chunk_per_step = cap
        self.admit_buckets = (self.buckets if cap is None else
                              (tuple(b for b in self.buckets if b <= cap)
                               or (cap,)))
        self._admitting: Optional[_Admission] = None
        self.spec = spec
        if engine_mode is not None:
            from repro.core.engine import build_hetero_ctx
            self.ctx = build_hetero_ctx(
                cfg, engine_mode,
                sync_mode="fast" if sync == "device" else "host",
                # offline-plan completeness, not a runtime input: fusion is
                # structural (mixed_step), but the saved plan records the
                # solver's MIXED costing of the (chunk bucket, decode width)
                # pairs this scheduler fuses, for analysis/benchmarks
                mixed_pairs=(tuple((b, decode_width)
                                   for b in self.admit_buckets)
                             if mixed_batch else ()),
                # VERIFY site class: the M = W*(K+1) verification dispatches
                # this scheduler issues in spec mode
                verify_ks=(((spec.k, decode_width),)
                           if spec is not None else ()),
                # prefix caching introduces one NEW chunk-length family:
                # suffixes start at block boundaries, so block-MULTIPLE
                # chunks below the smallest bucket become common — add
                # them to the solve grid (the M=1 full-hit logits re-run
                # is already on it). Ragged suffix tails remain arbitrary
                # lengths and use the same nearest-M fallback the cold
                # path's ragged remainders always used.
                extra_ms=(tuple(range(block_size, min(self.buckets),
                                      block_size))
                          if prefix_cache else ()),
                # quantized weight-stream bytes change the roofline: the
                # solver re-plans memory-bound (decode-width) shapes
                weight_quant=weight_quant)
        else:
            self.ctx = None
        # the solved plan (None without an engine mode) backs the tracer's
        # dispatch decision tags and the plan-drift report
        self._plan = self.ctx.plan if self.ctx is not None else None
        # observability: host dispatches actually issued vs tokens produced —
        # the fused-window win is decode dispatches << decode steps; the
        # mixed-batch win is prefill chunks riding decode dispatches for free
        # (fused_steps up, prefill_dispatches down, total_dispatches down)
        self.decode_dispatches = 0
        self.decode_steps = 0
        self.prefill_dispatches = 0      # standalone prefill-chunk dispatches
        self.fused_steps = 0             # prefill chunks fused into decode
        self.preemptions = 0             # lanes evicted mid-flight (ingress)
        # TTFT stages, seconds summed over the first tokens placed: submit
        # -> first prefill chunk dispatched (earlier prefills, the FCFS wait
        # for blocks) -> first token on the host -> end of the step that
        # placed it (the rest of that step: the decode window)
        self.first_tokens = 0
        self.prefill_wait_s = 0.0
        self.prefill_host_s = 0.0
        self.first_token_hold_s = 0.0
        self._placed: list[Request] = []  # first tokens placed this step
        # speculative decoding counters (spec mode): the win is
        # verify_dispatches << decode_steps; acceptance_rate explains it
        self.spec_rounds = 0             # per-lane speculation rounds
        self.drafted_tokens = 0          # K drafts offered per lane-round
        self.accepted_tokens = 0         # drafts the target verified correct
        self.verify_dispatches = 0       # batched paged_verify dispatches

        # the four paged inference paths, as the layout executes them: the
        # model's own entry points on a single device, shard_map-wrapped TP
        # variants over a mesh (stable callables — one jit cache each)
        paged_fns = self.layout.step_fns(self.model, self.params)

        if spec is not None:
            if self.model.paged_verify is None:
                raise ValueError(f"{cfg.name}: speculative decoding requires"
                                 " an attention-family target model")
            draft_cfg = spec.resolve_draft(cfg)
            self.draft_cfg = draft_cfg
            if spec_draft_params is None:
                spec_draft_params = (
                    self.params if draft_cfg is cfg else
                    build_model(draft_cfg).init(jax.random.PRNGKey(seed + 1)))
            # the longest admissible request bounds the draft cache; +k+1
            # slots absorb the round's overshooting draft writes
            self.drafts = DraftLanes(
                draft_cfg, spec_draft_params, lanes=decode_width,
                max_len=self.kv.max_blocks_per_seq * block_size + spec.k + 1,
                buckets=self.buckets, sync=sync,
                dtype=fp_dtype,       # draft caches stay fp under kv_quant
                tracer=self.tracer)
            vctx = (self.ctx.for_verify(spec.k, decode_width)
                    if self.ctx is not None else None)
            self._verify = jax.jit(_named("paged_verify",
                                          paged_fns["paged_verify"],
                                          hetero_ctx=vctx),
                                   donate_argnums=(2,))
            self._accept = jax.jit(greedy_verify)
        else:
            self.drafts = None

        # TP placement happens AFTER DraftLanes capture self.params: draft
        # lanes keep a deliberately-replicated (single-device) copy, so the
        # draft stream stays collective-free and bit-identical to the TP=1
        # draft; only the target model's weights shard
        self.params = self.layout.place_params(self.params)

        # the solver plan is baked in at trace time ('graphs generated in
        # advance'): jit compiles one graph per chunk length, so standard
        # buckets hit the compile cache and only a novel ragged remainder
        # pays the trace+compile that bucketing amortizes
        self._prefill = jax.jit(_named("paged_prefill",
                                       paged_fns["paged_prefill"],
                                       hetero_ctx=self.ctx),
                                donate_argnums=(2,))
        self._decode = jax.jit(paged_fns["paged_decode_step"],
                               donate_argnums=(2,))
        # the fused-window scan body: None = the model's own step (single
        # device); the layout's shard_map step under TP (stable identity,
        # it is a static arg of the jitted window)
        self._decode_step_fn = (paged_fns["paged_decode_step"]
                                if mesh is not None else None)
        # stable callables (one jit cache each) for the mixed-batch arms:
        # decode lanes stay on the flexible path, the chunk gets the ctx
        self._mixed_step_fn = _named("mixed_step", paged_fns["mixed_step"],
                                     hetero_ctx=self.ctx)
        self._mixed = jax.jit(self._mixed_step_fn, donate_argnums=(3,))

    def _dispatch_span(self, kind: str, track: str, specs=(), **args):
        """Context manager for one traced dispatch: ``specs`` is a sequence
        of ``dispatch_prediction`` kwarg dicts (a fused window is mixed
        first step + plain decode rest, hence a sequence) whose decision
        tags and predicted cost annotate the span and feed the drift
        report. With the tracer disabled NOTHING here runs — no prediction
        lookup, no event — preserving the zero-overhead contract."""
        tr = self.tracer
        if not tr.enabled:
            return tr.dispatch(kind)
        from repro.core.engine import dispatch_prediction
        tags, total = [], 0.0
        for sp in specs:
            t, p = dispatch_prediction(self._plan, self.cfg, **sp)
            tags.extend(t)
            total += p
        return tr.dispatch(kind, track=track, tags=tuple(tags),
                           predicted_us=total, args=args)

    @property
    def total_dispatches(self) -> int:
        """Host dispatches issued end-to-end (prefill + decode; a fused
        mixed step counts once — that's the point). In spec mode this is
        TARGET-model dispatches; the draft model's are tracked separately
        (``stats()['draft_dispatches']``)."""
        return self.decode_dispatches + self.prefill_dispatches

    def stats(self) -> dict:
        """Unified counter snapshot: every ad-hoc dispatch/fusion/
        speculation counter behind one dict — what ``serve.py --stats``
        prints and the benches assert on. Spec-mode keys appear only when
        speculation is on (``target_dispatches`` == ``total_dispatches``:
        draft-model work is deliberately kept out of the headline
        number)."""
        s = {
            "tp": self.layout.tp,
            "peak_active": self.peak_active,
            "decode_dispatches": self.decode_dispatches,
            "decode_steps": self.decode_steps,
            "prefill_dispatches": self.prefill_dispatches,
            "fused_steps": self.fused_steps,
            "first_tokens": self.first_tokens,
            "prefill_wait_s": self.prefill_wait_s,
            "prefill_host_s": self.prefill_host_s,
            "first_token_hold_s": self.first_token_hold_s,
            "preemptions": self.preemptions,
            "total_dispatches": self.total_dispatches,
        }
        s.update(self.kv.prefix_stats())
        if self.spec is not None:
            s.update({
                "spec_k": self.spec.k,
                "draft_model": self.draft_cfg.name,
                "spec_rounds": self.spec_rounds,
                "drafted_tokens": self.drafted_tokens,
                "accepted_tokens": self.accepted_tokens,
                "acceptance_rate": (self.accepted_tokens /
                                    max(self.drafted_tokens, 1)),
                "verify_dispatches": self.verify_dispatches,
                "draft_dispatches": self.drafts.dispatches,
                "target_dispatches": self.total_dispatches,
            })
        return s

    @property
    def busy(self) -> bool:
        """Work outstanding: queued requests, an open admission ticket, or
        occupied decode lanes. External tick-drivers (benchmarks, tests)
        loop on this instead of reaching into scheduler state."""
        return bool(self.queue or self._admitting is not None
                    or any(lane is not None for lane in self.lanes))

    # ------------------------------------------------------------ plumbing --
    def submit(self, req: Request):
        live = {r.rid for r in self.queue}
        live.update(lane.req.rid for lane in self.lanes if lane is not None)
        if self._admitting is not None:
            live.add(self._admitting.req.rid)
        _validate_submit(req, live)
        req.t_submit = self.clock.now()
        self.queue.append(req)

    def _try_open(self, req: Request) -> Optional[SequenceBlocks]:
        """Admission gate shared by both admission paths: validate the
        request fits the pool at all, then reserve its blocks (or return
        None to wait FCFS for blocks to free)."""
        S = len(req.prompt)
        total = S + req.max_new_tokens   # generation headroom, see step()
        need = self.kv.blocks_for(total)
        if need > min(self.kv.max_blocks_per_seq, self.kv.num_blocks - 1):
            raise ValueError(
                f"request {req.rid} needs {need} blocks "
                f"({total} tokens @ block_size={self.block_size}) but the "
                f"pool can never supply more than "
                f"{min(self.kv.max_blocks_per_seq, self.kv.num_blocks - 1)}"
                " per request — raise num_blocks/max_blocks_per_seq")
        if not self.kv.can_admit(total):
            return None
        return self.kv.open_sequence(
            prompt_tokens=S, total_tokens=total,
            token_ids=req.prompt if self.prefix_cache else None)

    def _first_token(self, logits) -> int:
        """Sample a request's first token from its prefill's last logits:
        the host dispatches the sampling, then waits here for the prefill
        (``first_token_sync`` holds the wait alone)."""
        self.rng, k = jax.random.split(self.rng)
        first = sample(logits[:, -1, :], k, self.sampler)[0]
        with self.tracer.span("first_token_sync", track="prefill",
                              cat="sync"):
            return int(first)

    def _place(self, req: Request, seq: SequenceBlocks, first: int) -> int:
        """Prefill done: record the prefill-sampled token and occupy a lane
        (returned so spec mode can target the lane's draft cache). Closes
        the request's prefill stages; its hold closes at the step's end."""
        req.t_first = self.clock.now()
        wait = req.t_prefill - req.t_submit
        host = req.t_first - req.t_prefill
        self.first_tokens += 1
        self.prefill_wait_s += wait
        self.prefill_host_s += host
        self.tracer.count("first_tokens")
        self.tracer.count("prefill_wait_s", wait)
        self.tracer.count("prefill_host_s", host)
        self._placed.append(req)
        seq.length = len(req.prompt)
        req.output.append(first)
        budget = req.max_new_tokens - 1
        if self.eos_id is not None and first == self.eos_id:
            budget = 0                  # satisfied at prefill, like max=1
        lane = next(i for i in range(self.W) if self.lanes[i] is None)
        self.lanes[lane] = _PagedLane(req=req, seq=seq, budget=budget)
        return lane

    def _admit(self):
        """Admit-then-decode (the baseline arm): whole prompts prefill as
        their own chunk dispatches before the request joins a lane. With
        the prefix cache on, ``seq.cached_tokens`` positions are already
        resident (shared blocks) and prefill covers only the uncached
        suffix — chunking starts at the cached boundary."""
        for lane in range(self.W):
            if self.lanes[lane] is not None or not self.queue:
                continue
            seq = self._try_open(self.queue[0])
            if seq is None:
                break                    # FCFS: wait for blocks to free
            req = self.queue.pop(0)
            with self.tracer.span("prefill_request", track="prefill",
                                  args={"rid": req.rid}):
                req.t_prefill = self.clock.now()
                logits = self._prefill_prompt(req.prompt, seq, req.rid)
                lane = self._place(req, seq, self._first_token(logits))
            if self.spec is not None and self.lanes[lane] is not None \
                    and self.lanes[lane].budget > 0:
                # the draft model consumes the prompt too (its lane cache
                # must mirror the target's token stream before drafting)
                self.drafts.prefill(lane, req.prompt)

    def _prefill_prompt(self, prompt, seq: SequenceBlocks, rid: int):
        """Prefill ``prompt`` into ``seq``'s blocks as bucket-sized chunk
        dispatches, starting after the ``seq.cached_tokens`` positions
        already resident; returns the last chunk's logits [1, 1, V]."""
        bt = jnp.asarray(seq.table)[None]
        idx, logits = seq.cached_tokens, None
        for c in bucket_chunks(len(prompt) - seq.cached_tokens,
                               self.buckets):
            piece = jnp.asarray(prompt[idx: idx + c], jnp.int32)
            with self._dispatch_span("prefill_chunk", "prefill",
                                     ({"m": c},), rid=rid,
                                     chunk=c, start=idx):
                logits, self.kv.pool = self._prefill(
                    self.params, piece[None], self.kv.pool,
                    block_table=bt,
                    start_index=jnp.asarray(idx, jnp.int32))
            self.prefill_dispatches += 1
            self.tracer.count("prefill_dispatches")
            idx += c
        return logits

    def prompt_logits(self, prompt) -> jax.Array:
        """Last-position logits [V] of ``prompt`` through this batcher's
        own admission prefill (chunking, plan, layout and pool), with no
        request admitted: the logits a request's first token is sampled
        from. The blocks it borrows are returned before it does."""
        seq = self.kv.open_sequence(prompt_tokens=len(prompt),
                                    total_tokens=len(prompt))
        try:
            return self._prefill_prompt(prompt, seq, -1)[0, -1]
        finally:
            self.kv.close_sequence(seq)

    def _start_admission(self):
        """Mixed batching: take ONE admission ticket at a time. A free lane
        is required up front (lanes only free while the ticket is open, so
        it stays available for `_place` at the end of the prefill)."""
        if self._admitting is not None or not self.queue:
            return
        if all(lane is not None for lane in self.lanes):
            return
        seq = self._try_open(self.queue[0])
        if seq is None:
            return
        req = self.queue.pop(0)
        self._admitting = _Admission(
            req=req, seq=seq, idx=seq.cached_tokens,
            chunks=bucket_chunks(len(req.prompt) - seq.cached_tokens,
                                 self.admit_buckets))

    def _admission_chunk(self):
        """Pop the admitting request's next chunk as device operands:
        (tokens [1, C], block table [1, NBmax], start index)."""
        adm = self._admitting
        if adm.req.t_prefill is None:
            adm.req.t_prefill = self.clock.now()
        c = adm.chunks.pop(0)
        piece = jnp.asarray(adm.req.prompt[adm.idx: adm.idx + c],
                            jnp.int32)[None]
        start = adm.idx
        adm.idx += c
        return piece, jnp.asarray(adm.seq.table)[None], start

    def _finish_admission(self, pre_logits):
        """Last chunk landed: sample the prefill token and occupy the lane
        reserved at `_start_admission`. The prompt's chunks rode earlier
        dispatches, so ``prefill_request`` here holds the sync alone."""
        adm, self._admitting = self._admitting, None
        with self.tracer.span("prefill_request", track="prefill",
                              args={"rid": adm.req.rid}):
            self._place(adm.req, adm.seq, self._first_token(pre_logits))

    def _close_lane(self, lane: int) -> _PagedLane:
        """Return lane ``lane``'s pool references (shared by finish and
        preemption): with the prefix cache on, full blocks of the WRITTEN
        token stream retire under their chain hash — KV position p holds
        the p-th token of prompt + output in every serving mode, and the
        last sampled token's KV is never written, so slice to
        ``seq.length``."""
        st = self.lanes[lane]
        ids = None
        if self.prefix_cache:
            ids = np.concatenate([
                np.asarray(st.req.prompt, np.int64),
                np.asarray(st.req.output, np.int64)])[:st.seq.length]
        self.kv.close_sequence(st.seq, token_ids=ids)
        self.lanes[lane] = None
        return st

    def _finish(self, lane: int):
        self._close_lane(lane).req.done = True

    def preempt(self, lane: int) -> Request:
        """Evict lane ``lane`` mid-flight, freeing its pool blocks for
        higher-priority work, and return its (unfinished) request. With the
        prefix cache on the evicted KV RETIRES instead of freeing, so a
        resume that re-submits ``prompt + output`` with the remaining
        budget hash-matches the retired blocks and re-prefills only the
        uncached suffix (recompute-on-resume through PR 5's cache). Under
        greedy decoding the resumed continuation is bit-identical to the
        un-preempted stream: the resume prompt IS the stream so far, and
        prefill logits at a position equal decode logits at that position.
        The scheduler caller (serving/ingress.py) owns the re-queueing."""
        st = self.lanes[lane]
        if st is None:
            raise ValueError(f"preempt of idle lane {lane}")
        if st.budget <= 0:
            raise ValueError(f"preempt of finishing lane {lane}: it frees "
                             "itself on the next step")
        self.preemptions += 1
        self.tracer.count("preemptions")
        self.tracer.instant("lane_preempt", track="scheduler",
                            args={"lane": lane, "rid": st.req.rid})
        if self.drafts is not None:
            self.drafts.rollback(lane, 0)   # stale draft cache: cursor home
        return self._close_lane(lane).req

    # ----------------------------------------------------------------- run --
    def step(self):
        """One tick: admit by free blocks, one batched paged decode — a
        single host-synced step (sync='host') or a fused window of
        ``self.window`` steps in one dispatch (sync='device'). Under mixed
        batching the admitting request's next prompt chunk rides the same
        dispatch; a standalone prefill dispatch happens only when no lane
        is decoding. First tokens placed in the step are held until it
        returns, when ingress can stream them: that hold is counted here."""
        progressed = self._step()
        if self._placed:
            now = self.clock.now()
            for req in self._placed:
                req.t_placed = now
                hold = now - req.t_first
                self.first_token_hold_s += hold
                self.tracer.count("first_token_hold_s", hold)
            self._placed.clear()
        return progressed

    def _step(self):
        if self.mixed_batch:
            self._start_admission()
        else:
            self._admit()
        active = [i for i in range(self.W) if self.lanes[i] is not None]
        self.peak_active = max(
            self.peak_active,
            len(active) + (self._admitting is not None))
        self.tracer.gauge("peak_active", self.peak_active)
        # zero-budget admissions (max_new_tokens == 1, or EOS sampled at
        # prefill) finish without a decode step
        for i in list(active):
            if self.lanes[i].budget <= 0:
                self._finish(i)
                active.remove(i)

        if self.spec is not None:
            if not active:
                return False
            self._spec_round(active)
            return True

        adm_chunk = pre_logits = None
        if self._admitting is not None:
            adm_chunk = self._admission_chunk()
            last_chunk = not self._admitting.chunks
            if not active:
                # nothing decoding: the chunk pays its own dispatch
                piece, bt, start = adm_chunk
                c = int(piece.shape[1])
                with self._dispatch_span("prefill_chunk", "prefill",
                                         ({"m": c},),
                                         rid=self._admitting.req.rid,
                                         chunk=c, start=start):
                    pre_logits, self.kv.pool = self._prefill(
                        self.params, piece, self.kv.pool, block_table=bt,
                        start_index=jnp.asarray(start, jnp.int32))
                self.prefill_dispatches += 1
                self.tracer.count("prefill_dispatches")
            elif self.sync == "device":
                pre_logits = self._decode_window(active, adm_chunk)
            else:
                pre_logits = self._decode_tick(active, adm_chunk)
            if last_chunk:
                self._finish_admission(pre_logits)
            return True

        if not active:
            return False
        if self.sync == "device":
            self._decode_window(active)
        else:
            self._decode_tick(active)
        return True

    def _spec_round(self, active):
        """One speculative round across all active lanes: K drafts per lane
        from the per-lane draft caches (K+1 flexible-path steps — one fused
        on-device scan under ``sync='device'``), ONE batched ``paged_verify``
        target dispatch over every lane's pending+draft tokens (M = W*(K+1),
        the solver's VERIFY site class), greedy acceptance on the host, then
        token-level rollback: ``truncate_to`` returns whole pool blocks past
        each lane's accepted prefix and the draft caches reset their
        cursors. Emits 1..K+1 verified tokens per lane per target dispatch;
        the stream is bit-identical to the non-spec greedy arms."""
        k = self.spec.k
        with self.tracer.span("verify_prep", track="verify"):
            tables = np.zeros((self.W, self.kv.max_blocks_per_seq),
                              np.int32)
            starts = np.zeros((self.W,), np.int32)
            last = np.zeros((self.W, 1), np.int32)
            for i in active:
                st = self.lanes[i]
                # coverage capped by the remaining budget: only rows the
                # acceptance rule can emit are ever read, so growth stays
                # inside the admission reservation; writes past the covered
                # blocks sink into the null block like any masked lane
                self.kv.grow_to(st.seq,
                                st.seq.length + min(k + 1, st.budget))
                tables[i] = st.seq.table
                starts[i] = st.seq.length
                last[i, 0] = st.req.output[-1]
            tables, starts = jnp.asarray(tables), jnp.asarray(starts)
        drafts = self.drafts.draft(last, k)                    # [W, k]
        tokens = np.concatenate([last, drafts], axis=1)        # [W, k+1]
        with self._dispatch_span("paged_verify", "verify",
                                 ({"verify": (k, self.W)},),
                                 k=k, lanes=len(active)):
            logits, self.kv.pool = self._verify(
                self.params, jnp.asarray(tokens), self.kv.pool,
                block_table=tables, start_index=starts)
            emitted, n_emit = self._accept(jnp.asarray(drafts), logits)
            with self.tracer.span("verify_sync", track="verify",
                                  cat="sync"):
                emitted, n_emit = np.asarray(emitted), np.asarray(n_emit)
        self.verify_dispatches += 1
        self.decode_dispatches += 1      # the round's one TARGET dispatch
        self.tracer.count("verify_dispatches")
        self.tracer.count("decode_dispatches")
        with self.tracer.span("reconcile", track="verify"):
            for i in active:
                st = self.lanes[i]
                e = min(int(n_emit[i]), st.budget)
                toks = [int(t) for t in emitted[i, :e]]
                hit_eos = self.eos_id is not None and self.eos_id in toks
                if hit_eos:
                    toks = toks[: toks.index(self.eos_id) + 1]
                self.spec_rounds += 1
                self.tracer.count("spec_rounds")
                # acceptance rate counts only drafts whose verification row
                # was budget-covered (rows past the coverage score
                # null-block garbage) and only acceptances that actually
                # emitted — neither side of the ratio may include
                # schedule-truncated drafts
                accepted = min(int(n_emit[i]) - 1, len(toks))
                self.drafted_tokens += min(k, st.budget)
                self.accepted_tokens += accepted
                self.tracer.count("drafted_tokens", min(k, st.budget))
                self.tracer.count("accepted_tokens", accepted)
                st.req.output.extend(toks)
                st.budget -= len(toks)
                self.decode_steps += len(toks)
                self.tracer.count("decode_steps", len(toks))
                new_len = st.seq.length + len(toks)
                self.kv.truncate_to(st.seq, new_len)    # paged rollback
                st.seq.length = new_len
                self.drafts.rollback(i, new_len)        # draft rollback
                if st.budget <= 0 or hit_eos:
                    self._finish(i)

    def _decode_tick(self, active, adm_chunk=None):
        """Host-synced baseline arm: ONE decode step, one dispatch + host
        round-trip per generated token (the paper's GPU-2/clFinish cost).
        With ``adm_chunk`` the dispatch is the fused ``mixed_step`` —
        decode step ⊕ prefill chunk — and the chunk's last-token logits
        are returned."""
        with self.tracer.span("step_prep", track="decode"):
            tables = np.zeros((self.W, self.kv.max_blocks_per_seq), np.int32)
            lengths = np.zeros((self.W,), np.int32)
            last = np.zeros((self.W, 1), np.int32)
            for i in active:
                st = self.lanes[i]
                self.kv.maybe_grow(st.seq)   # next write may cross a block
                tables[i] = st.seq.table
                lengths[i] = st.seq.length
                last[i, 0] = st.req.output[-1]
            last, tables, lengths = (
                jnp.asarray(a) for a in (last, tables, lengths))
            self.rng, k = jax.random.split(self.rng)
        pre_logits = None
        if adm_chunk is None:
            with self._dispatch_span("decode_step", "decode",
                                     ({"m": self.W},), active=len(active)):
                logits, self.kv.pool = self._decode(
                    self.params, last, self.kv.pool,
                    block_tables=tables, lengths=lengths)
                toks = sample(logits[:, -1, :], k, self.sampler)
                with self.tracer.span("step_sync", track="decode", cat="sync"):
                    toks = np.asarray(toks)
        else:
            piece, bt, start = adm_chunk
            c = int(piece.shape[1])
            with self._dispatch_span("mixed_step", "decode",
                                     ({"mixed": (c, self.W)},),
                                     active=len(active), chunk=c):
                logits, pre_logits, self.kv.pool = self._mixed(
                    self.params, last, piece, self.kv.pool,
                    decode_tables=tables, decode_lengths=lengths,
                    prefill_table=bt,
                    prefill_start=jnp.asarray(start, jnp.int32))
                toks = sample(logits[:, -1, :], k, self.sampler)
                with self.tracer.span("step_sync", track="decode", cat="sync"):
                    toks = np.asarray(toks)
            self.fused_steps += 1
            self.tracer.count("fused_steps")
        self.decode_dispatches += 1
        self.tracer.count("decode_dispatches")
        with self.tracer.span("reconcile", track="decode"):
            for i in active:
                st = self.lanes[i]
                tok = int(toks[i])
                st.req.output.append(tok)
                st.seq.length += 1
                st.budget -= 1
                self.decode_steps += 1
                self.tracer.count("decode_steps")
                if st.budget <= 0 or (self.eos_id is not None
                                      and tok == self.eos_id):
                    self._finish(i)
        return pre_logits

    def _decode_window(self, active, adm_chunk=None):
        """Fast-sync arm (§4.3 at serving widths): ONE dispatch runs up to
        ``self.window`` decode steps for every lane. Each lane's block
        table is pre-grown to cover its whole window (bounded by its
        remaining budget, so growth stays inside the admission-time
        reservation); the device masks lanes that exhaust their budget or
        hit EOS mid-window; the host then reconciles outputs, lengths and
        blocks from the returned valid mask. With ``adm_chunk`` the window
        additionally carries the prefill chunk (fused into its first step)
        and returns the chunk's last-token logits."""
        from repro.core.sync import paged_decode_window

        w = self.window
        with self.tracer.span("window_prep", track="decode"):
            tables = np.zeros((self.W, self.kv.max_blocks_per_seq), np.int32)
            lengths = np.zeros((self.W,), np.int32)
            remaining = np.zeros((self.W,), np.int32)
            last = np.zeros((self.W, 1), np.int32)
            for i in active:
                st = self.lanes[i]
                steps = min(w, st.budget)
                # window writes positions length .. length+steps-1, all
                # inside the admission reservation (length+steps <=
                # prompt+max_new)
                self.kv.grow_to(st.seq, st.seq.length + steps)
                tables[i] = st.seq.table
                lengths[i] = st.seq.length
                remaining[i] = steps
                last[i, 0] = st.req.output[-1]
            last, tables, lengths, remaining = (
                jnp.asarray(a) for a in (last, tables, lengths, remaining))
            self.rng, sub = jax.random.split(self.rng)
        pre_logits = None
        if adm_chunk is None:
            # the compiled window always runs w full-width steps (finished
            # lanes are masked, not skipped) — predict what executes
            with self._dispatch_span("decode_window", "decode",
                                     ({"m": self.W, "steps": w},),
                                     window=w, active=len(active)):
                toks, valid, self.kv.pool, _, _ = paged_decode_window(
                    self.model, self.params, last, self.kv.pool, tables,
                    lengths, remaining, sub, w,
                    sampler=self.sampler, eos_id=self.eos_id,
                    decode_step_fn=self._decode_step_fn)
                with self.tracer.span("window_sync", track="decode",
                                      cat="sync"):
                    toks, valid = np.asarray(toks), np.asarray(valid)
        else:
            piece, bt, start = adm_chunk
            c = int(piece.shape[1])
            # first scan step fuses the chunk (MIXED decision), the w-1
            # remaining steps are plain full-width decode
            specs = [{"mixed": (c, self.W)}]
            if w > 1:
                specs.append({"m": self.W, "steps": w - 1})
            with self._dispatch_span("mixed_window", "decode", specs,
                                     window=w, active=len(active), chunk=c):
                toks, valid, pre_logits, self.kv.pool, _, _ = \
                    paged_decode_window(
                        self.model, self.params, last, self.kv.pool, tables,
                        lengths, remaining, sub, w,
                        sampler=self.sampler, eos_id=self.eos_id,
                        prefill_tokens=piece, prefill_table=bt,
                        prefill_start=start,
                        mixed_step_fn=self._mixed_step_fn,
                        decode_step_fn=self._decode_step_fn)
                with self.tracer.span("window_sync", track="decode",
                                      cat="sync"):
                    toks, valid = np.asarray(toks), np.asarray(valid)
            self.fused_steps += 1
            self.tracer.count("fused_steps")
        self.decode_dispatches += 1
        self.tracer.count("decode_dispatches")
        with self.tracer.span("reconcile", track="decode"):
            for i in active:
                st = self.lanes[i]
                emitted = [int(t) for t in toks[i][valid[i]]]
                st.req.output.extend(emitted)
                st.seq.length += len(emitted)
                st.budget -= len(emitted)
                self.decode_steps += len(emitted)
                self.tracer.count("decode_steps", len(emitted))
                hit_eos = (self.eos_id is not None
                           and self.eos_id in emitted)
                if st.budget <= 0 or hit_eos:
                    self._finish(i)
        return pre_logits

    def run(self, requests: list[Request], max_ticks: int = 10_000):
        for r in requests:
            self.submit(r)
        ticks = 0
        while self.busy and ticks < max_ticks:
            self.step()
            ticks += 1
        return requests

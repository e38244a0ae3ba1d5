"""Serving launcher: ``python -m repro.launch.serve --arch qwen3-1.7b --smoke
--mode hetero-tensor --strategy hetero --requests 8``.

Runs the HeteroInfer engine (single-stream, paper-faithful), the dense
continuous batcher (--batched), or the paged-KV batcher (--batched --paged,
with --block-size / --max-blocks / --decode-width sizing the shared pool)
on synthetic prompts and prints tok/s.

Paged mode fuses the engine into the serving path
(docs/heterogeneous-execution.md):
  --sync device     fused-window decode: one dispatch per --window decode
                    steps instead of per token (fast sync, §4.3)
  --sync host       per-token host-synced decode (the baseline arm)
  --engine-mode M   solver-planned prefill: admission-time prefill matmuls
                    run the PartitionSolver plan through HeteroCtx (§4.1/4.2)
  --mixed-batch     stage-parallel mixed batching: each step fuses one
                    prefill chunk of the admitting request into the decode
                    dispatch of the running lanes (§4.1-§4.3 at stage level)
  --max-prefill-chunk N
                    cap on prefill tokens fused per step (--mixed-batch)
  --spec-k K        speculative decoding: K drafts per round, one batched
                    K+1-position verify dispatch of the target per round
                    (serving/spec.py; VERIFY-planned matmuls under
                    --engine-mode)
  --spec-draft M    draft model config name (e.g. smollm-135m); omit for
                    self-speculation (the target drafts for itself)
  --prefix-cache    automatic prefix caching: closed sequences retire full
                    KV blocks into a content-hash cache, new admissions
                    share matching blocks and prefill only the uncached
                    suffix (pair with --shared-prefix to shape the
                    workload; --decode-width < --requests staggers closes
                    so later admissions actually hit)
  --weight-quant Q  serve quantized weights (int8 | w4a16): matmul sites
                    carry int8/packed-int4 codes + per-channel scales and
                    dispatch the in-VMEM-dequant MXU kernels (models/quant)
  --kv-quant int8   int8 paged KV pool: quantize-on-scatter with per-slot
                    bf16 scales — equal pool memory holds ~2x the tokens
  --tp N            tensor-parallel serving over an N-wide ``model`` mesh
                    axis: weights and the paged KV pool shard head-wise
                    (serving/layout.py), host bookkeeping stays replicated,
                    greedy streams stay bit-identical to --tp 1 (on CPU,
                    export XLA_FLAGS=--xla_force_host_platform_device_count=N
                    first; incompatible with --engine-mode)
  --stats           print the scheduler's unified stats() counter dict

Weights are random, drawn from --seed, as are the prompts: lengths uniform
in [--min-prompt-len, --prompt-len). ``main`` returns a ``ServeResult`` for
callers that check what was served (chip_smoke.py).

Batched serving always runs through the async ingress
(serving/ingress.py): every request is timestamped against the wall clock
(serving/telemetry.py) and the run reports TTFT / TPOT / queue-delay
p50/p95/p99 plus goodput — in CLOSED-loop mode (default: all requests
arrive at t=0) as well as open loop:
  --open-loop       requests arrive on a seeded schedule instead of all
                    at once — the latency a real user sees under load
  --arrival P       arrival process: poisson (memoryless) or burst
                    (on-off at the same long-run rate)
  --rate R          mean arrival rate, requests/second
  --slo-ms MS       TTFT SLO: goodput counts only requests under it
  --priority-mix F  fraction of requests submitted LOW priority; blocked
                    high-priority arrivals may preempt their lanes (paged)
  --watermark N     admission backpressure: defer while admitting would
                    leave fewer than N free+cached blocks (paged)
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass

import numpy as np


@dataclass
class ServeResult:
    """What one batched serving run produced: the batcher (with its params
    and pool), the request handles in submission order, and the prompts."""
    batcher: object
    handles: list
    prompts: list


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", default="hetero-tensor",
                    choices=["xla", "mxu", "hetero-layer", "hetero-tensor"])
    ap.add_argument("--strategy", default="hetero",
                    choices=["online-prepare", "padding", "pipe", "hetero"])
    ap.add_argument("--no-fast-sync", action="store_true")
    ap.add_argument("--batched", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="use the paged (block-table) KV cache batcher")
    ap.add_argument("--block-size", type=int, default=32,
                    help="tokens per KV block (paged mode)")
    ap.add_argument("--max-blocks", type=int, default=0,
                    help="pool size in blocks; 0 = sized from --requests")
    ap.add_argument("--decode-width", type=int, default=8,
                    help="compiled decode lanes (paged mode)")
    ap.add_argument("--sync", default="host", choices=["host", "device"],
                    help="paged decode arm: per-token host-synced loop vs "
                         "fused on-device windows (one dispatch per window)")
    ap.add_argument("--window", type=int, default=8,
                    help="decode steps per fused dispatch (--sync device)")
    ap.add_argument("--engine-mode", default=None,
                    choices=["xla", "mxu", "hetero-layer", "hetero-tensor"],
                    help="solver-planned paged prefill: route prefill "
                         "matmuls through the HeteroCtx in this mode")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop token id (paged mode)")
    ap.add_argument("--mixed-batch", action="store_true",
                    help="stage-parallel mixed batching: fuse admission "
                         "prefill chunks into decode dispatches")
    ap.add_argument("--max-prefill-chunk", type=int, default=None,
                    metavar="N", dest="max_prefill_chunk",
                    help="max prefill tokens fused per scheduler step "
                         "(--mixed-batch; default: largest bucket)")
    ap.add_argument("--spec-k", type=int, default=None, metavar="K",
                    dest="spec_k",
                    help="speculative decoding: K drafts per round "
                         "(paged mode)")
    ap.add_argument("--spec-draft", default=None, metavar="ARCH",
                    dest="spec_draft",
                    help="draft model config name (--spec-k; default: the "
                         "target drafts for itself)")
    ap.add_argument("--prefix-cache", action="store_true",
                    dest="prefix_cache",
                    help="automatic prefix caching: closed sequences retire "
                         "full KV blocks into a content-hash cache; new "
                         "admissions share matching blocks and prefill only "
                         "the uncached suffix (paged mode)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="LEN",
                    dest="shared_prefix",
                    help="give every request the same LEN-token system "
                         "prompt prefix (the prefix-cache workload shape)")
    ap.add_argument("--weight-quant", default=None, dest="weight_quant",
                    choices=["int8", "w4a16"],
                    help="serve quantized weights: int8 or packed-int4 "
                         "(W4A16) codes with per-output-channel scales "
                         "(paged mode)")
    ap.add_argument("--kv-quant", default=None, dest="kv_quant",
                    choices=["int8"],
                    help="quantize the paged KV pool to int8 codes with "
                         "per-token-slot scales (paged mode)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel width: shard weights + paged KV "
                         "pool over an N-wide 'model' mesh axis "
                         "(paged mode; needs N visible devices)")
    ap.add_argument("--stats", action="store_true",
                    help="print the scheduler's stats() counter dict")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    dest="trace_out",
                    help="write a Chrome trace-event JSON of the run "
                         "(Perfetto-loadable; batched mode)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    dest="metrics_out",
                    help="write a Prometheus-style text snapshot of the "
                         "run's counters/gauges/histograms (batched mode)")
    ap.add_argument("--plan-drift", action="store_true", dest="plan_drift",
                    help="print the solver plan-vs-actual drift table "
                         "(predicted vs observed us per (site, M, strategy);"
                         " needs --engine-mode for decision tags)")
    ap.add_argument("--open-loop", action="store_true", dest="open_loop",
                    help="open-loop serving: requests arrive on a seeded "
                         "schedule (--arrival/--rate) instead of all at t=0")
    ap.add_argument("--arrival", default="poisson",
                    choices=["poisson", "burst"],
                    help="arrival process (--open-loop)")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="mean arrival rate, requests/s (--open-loop)")
    ap.add_argument("--arrival-seed", type=int, default=0,
                    dest="arrival_seed",
                    help="seed for the arrival schedule (--open-loop)")
    ap.add_argument("--slo-ms", type=float, default=None, dest="slo_ms",
                    metavar="MS",
                    help="TTFT SLO in ms: goodput counts only requests "
                         "whose first token lands under it")
    ap.add_argument("--priority-mix", type=float, default=0.0,
                    dest="priority_mix", metavar="F",
                    help="fraction of requests submitted LOW priority "
                         "(preemptible by blocked high-priority arrivals; "
                         "paged mode)")
    ap.add_argument("--watermark", type=int, default=0,
                    help="admission backpressure: defer admission while it "
                         "would leave fewer than N free+cached blocks "
                         "(paged mode)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=300)
    ap.add_argument("--min-prompt-len", type=int, default=8,
                    dest="min_prompt_len",
                    help="shortest prompt (batched mode; before any "
                         "--shared-prefix)")
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args(argv)
    if (args.sync == "device" or args.engine_mode or args.eos_id is not None
            or args.mixed_batch or args.spec_k is not None
            or args.prefix_cache or args.weight_quant or args.kv_quant
            or args.tp > 1) \
            and not (args.batched and args.paged):
        ap.error("--sync device / --engine-mode / --eos-id / --mixed-batch "
                 "/ --spec-k / --prefix-cache / --weight-quant / --kv-quant "
                 "/ --tp apply to the paged batcher: add --batched --paged")
    if args.tp < 1:
        ap.error("--tp must be >= 1")
    if args.tp > 1 and args.engine_mode:
        ap.error("--tp and --engine-mode are mutually exclusive: the hetero "
                 "engine and the device mesh are separate axes")
    if args.max_prefill_chunk is not None and not args.mixed_batch:
        ap.error("--max-prefill-chunk applies to --mixed-batch")
    if args.spec_draft is not None and args.spec_k is None:
        ap.error("--spec-draft applies to --spec-k")
    if args.spec_k is not None and args.mixed_batch:
        ap.error("--spec-k and --mixed-batch are mutually exclusive")
    if args.open_loop and not args.batched:
        ap.error("--open-loop applies to the batched servers: add --batched")
    if (args.priority_mix or args.watermark) \
            and not (args.batched and args.paged):
        ap.error("--priority-mix / --watermark apply to the paged batcher: "
                 "add --batched --paged")
    if not 0.0 <= args.priority_mix <= 1.0:
        ap.error("--priority-mix must be in [0, 1]")
    if (args.trace_out or args.metrics_out or args.plan_drift) \
            and not args.batched:
        ap.error("--trace-out / --metrics-out / --plan-drift trace the "
                 "batched servers: add --batched")

    if not 8 <= args.min_prompt_len < args.prompt_len:
        ap.error("--min-prompt-len must be in [8, --prompt-len)")

    import jax
    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rng = np.random.default_rng(args.seed)

    if args.batched:
        from repro.serving.scheduler import ContinuousBatcher, PagedBatcher
        from repro.serving.telemetry import MonotonicClock
        from repro.serving.trace import Tracer
        max_len = args.prompt_len + args.new_tokens + 8
        # all serving timing flows through the injectable clock: the same
        # Telemetry machinery the deterministic tests pin, on a wall clock
        clock = MonotonicClock()
        tracing = bool(args.trace_out or args.metrics_out or args.plan_drift)
        tracer = Tracer(clock) if tracing else None
        if args.paged:
            spec = None
            if args.spec_k is not None:
                from repro.serving.spec import SpecConfig
                spec = SpecConfig(k=args.spec_k, draft=args.spec_draft,
                                  smoke=args.smoke)
            mesh = None
            if args.tp > 1:
                from repro.launch.mesh import make_host_mesh
                mesh = make_host_mesh(1, args.tp)
            num_blocks = args.max_blocks or (
                1 + args.requests * -(-max_len // args.block_size))
            # cap per-request tables at the longest possible request, not
            # the pool size: attention gathers a [W, NBmax*block_size] KV
            # view, so NBmax drives per-step cost
            cb = PagedBatcher(cfg, num_blocks=num_blocks,
                              block_size=args.block_size,
                              max_blocks_per_seq=-(-max_len
                                                   // args.block_size),
                              decode_width=args.decode_width,
                              sync=args.sync, window=args.window,
                              engine_mode=args.engine_mode,
                              eos_id=args.eos_id,
                              mixed_batch=args.mixed_batch,
                              max_prefill_chunk_per_step=args.max_prefill_chunk,
                              spec=spec, prefix_cache=args.prefix_cache,
                              weight_quant=args.weight_quant,
                              kv_quant=args.kv_quant, mesh=mesh,
                              seed=args.seed, tracer=tracer)
            label = (f"paged (bs={args.block_size}, "
                     f"blocks={num_blocks}, W={args.decode_width}, "
                     f"sync={args.sync}"
                     + (f", tp={args.tp}" if args.tp > 1 else "")
                     + (f", window={args.window}" if args.sync == "device"
                        else "")
                     + (f", engine={args.engine_mode}" if args.engine_mode
                        else "")
                     + (", mixed" if args.mixed_batch else "")
                     + (", prefix-cache" if args.prefix_cache else "")
                     + (f", weights={args.weight_quant}"
                        if args.weight_quant else "")
                     + (f", kv={args.kv_quant}" if args.kv_quant else "")
                     + (f", spec k={args.spec_k} "
                        f"draft={args.spec_draft or 'self'}"
                        if spec else "") + ")")
        else:
            cb = ContinuousBatcher(cfg, max_batch=4, max_len=max_len,
                                   seed=args.seed, tracer=tracer)
            label = "batched"
        if args.shared_prefix >= args.prompt_len - 8:
            ap.error("--shared-prefix must leave at least 8 tokens of "
                     "per-request tail below --prompt-len")
        sys_prompt = rng.integers(0, cfg.vocab_size,
                                  args.shared_prefix).astype(np.int32)
        lo = max(8, args.min_prompt_len - args.shared_prefix)
        prompts = [np.concatenate([
            sys_prompt,
            rng.integers(0, cfg.vocab_size,
                         rng.integers(lo, args.prompt_len
                                      - args.shared_prefix)
                         ).astype(np.int32)])
            for _ in range(args.requests)]
        from repro.serving.ingress import AsyncServer, arrival_times, \
            open_loop_workload
        server = AsyncServer(cb, clock=clock,
                             admit_watermark=args.watermark)
        prios = [0 if rng.random() < args.priority_mix else 1
                 for _ in range(args.requests)]
        if args.open_loop:
            t_arr = arrival_times(args.arrival, args.rate, args.requests,
                                  args.arrival_seed)
        else:
            t_arr = np.zeros(args.requests)    # closed loop: all at t=0
        t0 = clock.now()
        handles = server.run_sync(open_loop_workload(
            prompts, [args.new_tokens] * args.requests, t0 + t_arr, prios))
        dt = clock.now() - t0
        tok = sum(len(h.tokens) for h in handles)
        loop = (f"open-loop {args.arrival}@{args.rate}/s" if args.open_loop
                else "closed-loop")
        print(f"{label}: {loop}, {args.requests} reqs, {tok} tokens in "
              f"{dt:.2f}s ({tok / dt:.1f} tok/s, peak concurrency "
              f"{cb.peak_active})")
        rep = server.report(slo_ms=args.slo_ms)
        for m in ("ttft_ms", "tpot_ms", "queue_delay_ms"):
            s = rep[m]
            if s["n"]:
                print(f"  {m.removesuffix('_ms')}: p50 {s['p50']:.1f} ms, "
                      f"p95 {s['p95']:.1f} ms, p99 {s['p99']:.1f} ms "
                      f"(n={s['n']})")
        good = rep["goodput_req_s"]
        print(f"  goodput: {good:.2f} req/s"
              + (f" under TTFT SLO {args.slo_ms:.0f} ms "
                 f"({100 * rep['slo_attainment']:.0f}% attainment)"
                 if args.slo_ms is not None else " (no SLO given)")
              + (f", {rep['preemptions']} preemptions"
                 if rep["preemptions"] else ""))
        if args.paged:
            print(f"  decode: {cb.decode_dispatches} host dispatches for "
                  f"{cb.decode_steps} decoded tokens "
                  f"({cb.decode_steps / max(cb.decode_dispatches, 1):.1f} "
                  f"tokens/dispatch)")
            print(f"  prefill: {cb.prefill_dispatches} standalone dispatches,"
                  f" {cb.fused_steps} chunks fused into decode dispatches "
                  f"({cb.total_dispatches} host dispatches total)")
            if args.spec_k is not None:
                s = cb.stats()
                print(f"  spec: {s['verify_dispatches']} verify dispatches, "
                      f"acceptance {s['acceptance_rate']:.2f} "
                      f"({s['accepted_tokens']}/{s['drafted_tokens']} drafts,"
                      f" draft={s['draft_model']})")
            if args.prefix_cache:
                s = cb.stats()
                print(f"  prefix-cache: {s['prefix_hits']} hit admissions, "
                      f"{s['prefix_tokens_reused']} prompt tokens reused, "
                      f"{s['cached_blocks']} blocks retained, "
                      f"{s['evictions']} evictions, "
                      f"{s['cow_copies']} CoW copies")
        if args.stats:
            print(f"  stats: {server.stats()}")
        if tracer is not None:
            if args.trace_out:
                tracer.save_chrome(args.trace_out)
                print(f"  trace: {tracer.n_events} events "
                      f"({tracer.dropped} dropped) -> {args.trace_out}")
            if args.metrics_out:
                tracer.save_prometheus(args.metrics_out)
                print(f"  metrics: -> {args.metrics_out}")
            if args.plan_drift:
                print(tracer.drift.format_table())
        return ServeResult(batcher=cb, handles=handles, prompts=prompts)

    from repro.core.engine import InferenceEngine
    eng = InferenceEngine(cfg, mode=args.mode, prefill_strategy=args.strategy,
                          fast_sync=not args.no_fast_sync,
                          max_len=args.prompt_len + args.new_tokens + 8,
                          rng=jax.random.PRNGKey(args.seed))
    prompt = rng.integers(0, cfg.vocab_size,
                          (1, args.prompt_len)).astype(np.int32)
    toks = eng.generate(jax.numpy.asarray(prompt), args.new_tokens)
    print(f"mode={args.mode} strategy={args.strategy} out={toks.shape} "
          f"{eng.stats.tokens_per_s()}")


if __name__ == "__main__":
    main()

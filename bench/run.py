#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `BENCHMARK.json`'s ``workloads``: a configuration
(`bench/configs/<config>.json`) under a traffic mix
(`bench/traffic/<mix>.json`). The run makes the weights from the seed,
builds the program's paged batcher behind its `AsyncServer`, serves a
warm-up set that compiles every program the mix can dispatch, then sends
the mix for a lead-in and a window of ``--seconds``, and drains. Set-up
(``setup_s``) is everything before the lead-in.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window's last seconds by the readers in `bench/metrics/<metric>.py`.

After the window the program's state is freed, and a sample of the window's
finished requests, drawn from the seed, is compared with the float32
reference (`bench/reference.py`); `bench/checks/<cell>.json` holds the
limits. Every number compared is printed beside its limit as the last lines
of standard error and, under ``checks``, last in the result line: the last
line of standard output.

Without a TPU, with fewer chips than the cell asks for, or on a device
missing from `bench/peaks.json`, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# the profiler traces this many seconds, from as many before the window's
# close. Stopping it holds the host for seconds (4-15 s on a v5e host), in
# which no request is admitted; at the close that delays only the few still
# queued, and no arrival of the window comes after it. The trace lasts its
# full length from when it started, as a long scheduler tick can delay
# the start.
TRACE_SECONDS = 4.0
# the correctness sample: the longest request, then others drawn from the
# seed, until both floors are met or the cap is reached
SAMPLE_TOKENS, SAMPLE_MIN, SAMPLE_MAX = 300, 4, 16


class NoChip(RuntimeError):
    """The machine cannot run this cell."""


def load_json(path: Path):
    return json.loads(path.read_text())


def cell_spec(name: str) -> dict:
    """The workload entry ``name`` with its configuration, mix, metrics and
    checks resolved from `BENCHMARK.json` and their files."""
    from bench.model import load_config
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = load_config(w["config"])
    config["name"] = w["config"]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {"workload": w, "config": config,
            "mix": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
            "check": load_json(BENCH / "checks" / f"{name}.json"),
            "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
            "per_layer": [m for m in spec["per_layer"] if applies(m)]}


def device_info(chips: int) -> tuple[dict, dict]:
    """(device stamp, peaks): the run's accelerator as JAX reports it and
    its row of `bench/peaks.json`. Raises NoChip where there is none."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {dev.platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    peaks = load_json(BENCH / "peaks.json")
    if dev.device_kind not in peaks:
        raise NoChip(f"device kind {dev.device_kind!r} is not in "
                     f"bench/peaks.json")
    return ({"platform": dev.platform, "kind": dev.device_kind,
             "count": len(devices)}, peaks[dev.device_kind])


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def build_server(spec: dict, seed: int, traffic, tracer, clock, recorder,
                 program: dict | None = None):
    """The system under test: the program's paged batcher with the
    configuration's deployment sizes (``program`` adds batcher options, as
    a control does), behind its `AsyncServer`."""
    from bench.model import make_weights, model_config
    from repro.serving.ingress import AsyncServer
    from repro.serving.scheduler import PagedBatcher
    c = spec["config"]
    dep = dict(c["deployment"])
    block = dep["block_size"]
    params = make_weights(c, seed)
    batcher = PagedBatcher(
        model_config(c), params, seed=0, tracer=tracer,
        max_blocks_per_seq=math.ceil(traffic.max_context / block),
        **dep, **(program or {}))
    del params
    return AsyncServer(batcher, clock=clock, telemetry=recorder,
                       tracer=tracer)


def warm_up(server, recorder, traffic) -> None:
    """Serve, untimed, one request per chunk length the mix can dispatch
    (each bucket and every ragged tail), so that whatever the program
    compiles, it compiles here."""
    import numpy as np
    from bench.client import submit_kwargs
    from bench.traffic import Request, warmup_lengths
    b = server.batcher
    lengths = warmup_lengths(traffic.min_prompt, traffic.max_prompt,
                             b.buckets)
    rng = np.random.default_rng(0)
    arrivals = []
    for i, n in enumerate(lengths):
        req = Request(-1 - i, rng.integers(0, traffic.vocab_size, n,
                                           dtype=np.int32), 2, None)
        recorder.expect(req)
        arrivals.append((server.clock.now(), submit_kwargs(req)))
    asyncio.run(server.run(arrivals))
    recorder.records.clear()


def window_temp_bytes(batcher) -> int | None:
    """Temporaries of the fused decode window, the largest step program, as
    its compiled ``memory_analysis()`` gives them: the device's
    ``peak_bytes_in_use`` leaves them out on this runtime. The window is
    lowered with the arguments the batcher hands it, so the program is
    found in the compile cache. None where the program has no such
    window."""
    import jax
    import jax.numpy as jnp
    try:
        from repro.core.sync import _paged_window
        b = batcher
        step = b._decode_step_fn or b.model.paged_decode_step
        lanes = jnp.zeros((b.W,), jnp.int32)
        lowered = _paged_window.lower(
            b.params, jnp.zeros((b.W, 1), jnp.int32), b.kv.pool,
            jnp.zeros((b.W, b.kv.max_blocks_per_seq), jnp.int32), lanes,
            lanes, jax.random.split(b.rng, b.window), decode_step=step,
            n_steps=b.window, sampler=b.sampler, eos_id=b.eos_id)
    except (ImportError, AttributeError, TypeError):
        return None
    return int(lowered.compile().memory_analysis().temp_size_in_bytes)


def in_flight(records, t: float) -> int:
    """Requests that had arrived by ``t`` and not yet finished."""
    return sum(r.arrival <= t and not (r.done and r.finish <= t)
               for r in records)


def sample_requests(records, seed: int) -> list:
    """The correctness sample: the finished window request with the most
    served tokens, then others in an order drawn from the seed."""
    import numpy as np
    done = sorted((r for r in records if r.done), key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if len(out) >= SAMPLE_MAX or (n >= SAMPLE_TOKENS
                                      and len(out) >= SAMPLE_MIN):
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def check_outputs(spec, seed, traffic, sample, streams):
    """Every number compared, each with its limit, and the widest gap (a
    reading, not compared)."""
    from bench.reference import Reference, served_gaps
    import numpy as np
    c = spec["config"]
    ref = Reference(c, seed, seq_len=traffic.max_context,
                    n_pos=int(spec["mix"]["output_tokens"]["max"]))
    gaps = np.concatenate([served_gaps(ref, *streams[r.rid])
                           for r in sample]) if sample else np.zeros(0)
    # the mean, not the widest gap: see PERF.md, "How correct is decided"
    return {"mean_logit_gap": {"value": float(gaps.mean()) if gaps.size
                               else 0.0,
                               "limit": spec["check"]["mean_logit_gap"]},
            "tokens_compared": {"value": int(gaps.size), "limit": SAMPLE_MIN,
                                "at_least": True}}, float(gaps.max(initial=0.0))


class Session:
    """A cell's system under test, set up once: the server over the
    program's batcher with its weights from the seed, warmed up. Each
    :meth:`serve` then sends one lead-in and window of traffic."""

    def __init__(self, spec: dict, *, seed: int, trace: bool,
                 program: dict | None = None):
        from bench import client
        from bench.traffic import Traffic
        self.spec, self.seed, self.trace = spec, seed, trace
        self.cache_dir = enable_compile_cache()
        self.watch = client.CompileWatch()
        self.clock = client.Clock()
        self.recorder = client.Recorder(self.clock)
        self.tracer = (client.annotating_tracer(self.clock, self.recorder)
                       if trace else None)
        c = spec["config"]
        # the sizes of the mix, which fix the block table's width
        sizes = Traffic(spec["mix"], seed=seed, seconds=1.0,
                        vocab_size=c["vocab_size"])
        self.server = build_server(spec, seed, sizes, self.tracer,
                                   self.clock, self.recorder, program)
        warm_up(self.server, self.recorder, sizes)
        self.temp_bytes = window_temp_bytes(self.server.batcher)
        self.next_id = 0
        self.trace_cost: dict = {}

    def serve(self, mix: dict, seconds: float) -> "RunData":
        """Send ``mix``'s lead-in and a window of ``seconds``, drain, and
        return what the readers read (the program's state stays)."""
        import jax
        from bench import client, xtrace
        from bench.costs import Costs
        from bench.traffic import Traffic
        c = self.spec["config"]
        clock, watch, server = self.clock, self.watch, self.server
        if any(not h.done for h in server.handles):
            # an earlier window's requests still in flight finish first
            asyncio.run(server.run())
        self.recorder.records.clear()
        traffic = Traffic(mix, seed=self.seed, seconds=seconds,
                          vocab_size=c["vocab_size"], first_id=self.next_id)
        closed = mix["loop"] == "closed"
        schedule = [] if closed else traffic.schedule()
        close = float(seconds)
        deadline = close + traffic.drain_s
        clock.origin = time.monotonic() + traffic.lead_in_s
        marks: dict = {}

        def mark(name):
            def fn():
                marks[name] = (clock.now(), watch.compiles,
                               dict(server.batcher.stats()))
            return fn

        hooks = [(0.0, mark("open")), (close, mark("close"))]
        trace_dir = ROOT / ".bench_trace"
        if self.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)

            def start():
                t = clock.now()
                clock.annotate = True
                jax.profiler.start_trace(str(trace_dir))
                self.trace_cost["start_s"] = clock.now() - t
                mark("trace_start")()
                return (clock.now() + TRACE_SECONDS, stop)

            def stop():
                mark("trace_stop")()
                t = clock.now()
                jax.profiler.stop_trace()
                self.trace_cost["stop_s"] = clock.now() - t
                clock.annotate = False

            hooks.append((max(0.0, close - TRACE_SECONDS), start))
        if closed:
            asyncio.run(client.drive_closed(
                server, self.recorder, traffic,
                lanes=c["deployment"]["decode_width"], close=close,
                deadline=deadline, hooks=hooks))
        else:
            asyncio.run(client.drive_open(server, self.recorder, schedule,
                                          deadline=deadline, hooks=hooks))
        for name in ("open", "close"):
            if name not in marks:
                mark(name)()
        if self.trace and "trace_stop" not in marks:
            mark("trace_stop")()
            jax.profiler.stop_trace()
            clock.annotate = False
        self.next_id = traffic.next_id
        all_records = list(self.recorder.records.values())
        data = RunData(
            spec=self.spec, seconds=close, deadline=deadline, marks=marks,
            records=[r for r in all_records if 0.0 <= r.arrival < close],
            all_records=all_records, costs=Costs(c), traffic=traffic,
            served={h.rid: list(h.tokens) for h in server.handles})
        if self.trace:
            t0, t1 = marks["trace_start"][0], marks["trace_stop"][0]
            data.ticks = [t for t in self.tracer.ticks
                          if t0 <= t["t0"] and t["t1"] <= t1]
            self.tracer.ticks.clear()
            data.device = xtrace.reduce(trace_dir)
        return data

    def close(self) -> None:
        """Free the program's state on the device."""
        self.server = self.tracer = None
        gc.collect()


def read_metrics(data: "RunData", metric_specs) -> dict:
    metrics = {}
    for m in metric_specs:
        value = load_reader(m["name"])(data)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def run_cell(spec: dict, *, seed: int, seconds: float, trace: bool,
             device: dict, peak: dict, program: dict | None = None,
             t_start: float = T_START) -> dict:
    """One run of a cell: set-up, lead-in, window, drain, metrics, checks.
    Returns the result line's object, with an ``info`` entry for an
    earlier line."""
    import jax
    import numpy as np
    session = Session(spec, seed=seed, trace=trace, program=program)
    setup_s = time.monotonic() - t_start
    compiles_setup = session.watch.compiles
    data = session.serve(spec["mix"], seconds)
    data.setup_s, data.peak = setup_s, peak
    live_peak = int((jax.devices()[0].memory_stats() or {}).get(
        "peak_bytes_in_use", 0))
    device = dict(device,
                  memory_peak_bytes=live_peak + (session.temp_bytes or 0))
    if trace:
        device.update(busy_s=data.device["busy_s"],
                      window_s=data.device["window_s"])
    stats = dict(session.server.batcher.stats())
    watch = session.watch
    session.close()        # the reference runs with the program's state freed

    metrics = read_metrics(
        data, spec["per_layer"] if trace else spec["end_to_end"])
    c = spec["config"]
    records = data.records
    streams = {r.rid: (r.prompt, data.served[r.rid])
               for r in records if r.done}
    failed = sum(not r.done for r in records)
    malformed = sum(len(served) != r.max_new
                    or not all(0 <= t < c["vocab_size"] for t in served)
                    for r in records if r.done
                    for served in [streams[r.rid][1]])
    checks, max_gap = check_outputs(spec, seed, data.traffic,
                                    sample_requests(records, seed), streams)
    checks.update({"unfinished": {"value": failed, "limit": 0},
                   "malformed_streams": {"value": malformed, "limit": 0}})
    correct = all(
        (ch["value"] >= ch["limit"]) if ch.get("at_least")
        else (ch["value"] <= ch["limit"]) for ch in checks.values())

    marks = data.marks
    lag = [r.sent - r.arrival for r in records
           if spec["mix"]["loop"] == "open" and not math.isnan(r.sent)]
    info = {"cell": spec["workload"]["name"], "seed": seed,
            "compile_cache": session.cache_dir, "setup_s": setup_s,
            "compiles_setup": compiles_setup,
            "compiles_in_window": marks["close"][1] - marks["open"][1],
            "compiles_after_window": watch.compiles - marks["close"][1],
            "cache_hits": watch.hits, "cache_misses": watch.misses,
            "compile_s": watch.seconds,
            "generator_lag_max_s": max(lag) if lag else 0.0,
            "generator_lag_p95_s": (float(np.percentile(lag, 95))
                                    if lag else 0.0),
            "live_peak_bytes": live_peak,
            "window_temp_bytes": session.temp_bytes,
            "in_flight_at_open": in_flight(data.all_records,
                                           marks["open"][0]),
            "attempted": len(records), "failed": failed, "stats": stats,
            "max_logit_gap": max_gap,
            "trace_cost": session.trace_cost}
    if trace:
        info["in_flight_at_trace_start"] = in_flight(
            data.all_records, marks["trace_start"][0])
    out = {"correct": bool(correct), "attempted": len(records),
           "failed": failed, "metrics": metrics, "device": device,
           "info": info}
    if trace:
        out["breakdown"] = data.device["breakdown"]
    out["checks"] = checks
    return out


class RunData:
    """What a metric reader gets: the run's client records, set-up time and
    cost model, and in a traced run the scheduler ticks inside the traced
    interval and the reduced device trace."""

    def __init__(self, **kw):
        self.ticks: list = []
        self.device: dict = {}
        self.setup_s = self.peak = None
        self.__dict__.update(kw)

    @property
    def config(self) -> dict:
        return self.spec["config"]

    @property
    def trace_interval(self) -> tuple[float, float]:
        return self.marks["trace_start"][0], self.marks["trace_stop"][0]


def check_lines(checks: dict) -> list[str]:
    lines = []
    for name, ch in checks.items():
        rel = ">=" if ch.get("at_least") else "<="
        lines.append(f"check {name} {ch['value']!r} limit {rel} "
                     f"{ch['limit']!r}")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = cell_spec(args.workload)
    try:
        device, peak = device_info(spec["workload"]["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = run_cell(spec, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), device=device, peak=peak)
    print("info " + json.dumps(out.pop("info"), sort_keys=True), flush=True)
    for line in check_lines(out["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""TTFT stages the program counts, per first token.

`PagedBatcher.stats()` sums, over the first tokens it places
(``first_tokens``), the seconds from a request's submit to its first
prefill chunk (``prefill_wait_s``), to its first token on the host
(``prefill_host_s``), and to the end of the scheduler step that placed it,
when ingress streams it (``first_token_hold_s``). A reader takes the
counters' change over the stretch of the window the profiler does not
slow: from the window's open to the trace's start, or to the window's
close in a run without a trace.

Run as a script, it runs a cell as `bench/run.py` does and prints, before
the same result line, a ``stages_ms`` line: each window request's TTFT
split into the ingress queue wait and the batcher's stamps of the same
request, their medians and means, the counters' means over the readers'
stretch and, in a traced run, the device's idle time split by what the
host was doing (`bench/host_spans.py` ``idle_split``).

    python bench/stages.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--tracer 1]

``--tracer 1`` with ``--trace 0`` attaches the program's own `Tracer`
with the profiler off: against a run without it at the same seed, that
is the cost of the program's tracing.
"""
from __future__ import annotations

import statistics
import sys
from pathlib import Path

STAGES = ("queue_wait", "prefill_wait", "prefill_host", "first_token_hold")
COUNTERS = ("prefill_wait_s", "prefill_host_s", "first_token_hold_s")


def stage_ms(run, key: str):
    """Milliseconds per first token placed of the counter ``key`` over the
    stretch; None where the program has no such counter (a program that
    predates it) or placed no first token in the stretch."""
    marks = run.marks
    start = marks.get("open")
    end = marks.get("trace_start") or marks.get("close")
    if start is None or end is None:
        return None
    return _per_first_token(start[2], end[2], key)


def _per_first_token(a: dict, b: dict, key: str):
    if any(k not in s for s in (a, b) for k in (key, "first_tokens")):
        return None
    n = b["first_tokens"] - a["first_tokens"]
    if n <= 0:
        return None
    return 1e3 * (b[key] - a[key]) / n


def decompose(records, requests, close: float) -> dict:
    """Each window request's TTFT (first token streamed − arrival, on the
    client's clock) as the ingress queue wait (admission − arrival) and the
    batcher's stages from the stamps of the request it placed first
    (``requests``, by id), in ms; ``rest`` is what the stages leave out,
    the drain. Requests without a first token or a placed request are left
    out."""
    rows = []
    for r in records:
        q = requests.get(r.rid)
        if not (0.0 <= r.arrival < close) or not r.tokens or q is None \
                or q.t_placed is None:
            continue
        row = {"ttft": r.tokens[0] - r.arrival,
               "queue_wait": r.admit - r.arrival,
               "prefill_wait": q.t_prefill - q.t_submit,
               "prefill_host": q.t_first - q.t_prefill,
               "first_token_hold": q.t_placed - q.t_first}
        row["rest"] = row["ttft"] - sum(row[s] for s in STAGES)
        rows.append({k: 1e3 * v for k, v in row.items()})
    keys = ("ttft",) + STAGES + ("rest",)
    if not rows:
        return {"n": 0}
    return {"n": len(rows),
            "median": {k: statistics.median(x[k] for x in rows)
                       for k in keys},
            "mean": {k: statistics.fmean(x[k] for x in rows) for k in keys}}


def run_staged(spec, *, seed, seconds, trace, device, peak, tracer=False):
    """`bench/run.py`'s ``run_cell``, keeping what the decomposition needs:
    the first `Request` the batcher took under each id, the client's
    records and the counters' means over the stretch the readers take.
    Returns (result, stages)."""
    import bench.run as run
    from repro.serving.trace import Tracer

    kept: dict = {}

    class Staged(run.Session):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            b = self.server.batcher
            submit = b.submit

            def keep(req):
                kept["requests"].setdefault(req.rid, req)
                return submit(req)
            b.submit = keep
            kept.update(session=self, requests={})

        def serve(self, mix, seconds):
            kept["data"] = super().serve(mix, seconds)
            return kept["data"]

    def build_server(spec, seed, traffic, tracer_, clock, *a, **kw):
        if tracer and tracer_ is None:
            tracer_ = Tracer(clock)
        return build(spec, seed, traffic, tracer_, clock, *a, **kw)

    session_cls, build = run.Session, run.build_server
    run.Session, run.build_server = Staged, build_server
    try:
        out = run.run_cell(spec, seed=seed, seconds=seconds, trace=trace,
                           device=device, peak=peak)
    finally:
        run.Session, run.build_server = session_cls, build
    stages = decompose(kept["session"].recorder.records.values(),
                       kept["requests"], float(seconds))
    stages["counters_ms"] = {k: stage_ms(kept["data"], k) for k in COUNTERS}
    stages["tracer"] = bool(tracer or trace)
    if trace:
        from bench.host_spans import TRACE_DIR, idle_split
        from bench.xtrace import newest_xplane, read_planes
        try:
            planes = read_planes(newest_xplane(TRACE_DIR))
        except FileNotFoundError:
            planes = []
        stages["idle_split_s"] = idle_split(planes) if planes else None
    return out, stages


def main(argv=None) -> int:
    import argparse
    import json
    root = Path(__file__).resolve().parents[1]
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import bench.run as run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tracer", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = run.cell_spec(args.workload)
    try:
        device, peak = run.device_info(spec["workload"]["chips"])
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out, stages = run_staged(spec, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), device=device,
                             peak=peak, tracer=bool(args.tracer))
    print("info " + json.dumps(out.pop("info"), sort_keys=True), flush=True)
    print("stages_ms " + json.dumps(stages, sort_keys=True), flush=True)
    for line in run.check_lines(out["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

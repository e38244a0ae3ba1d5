"""Arithmetic shared by the metric readers in `bench/metrics/`.

Client-side latencies are taken over every request whose scheduled arrival
falls in the window. A request that failed or had not finished when the
run drained sorts above every finished one: it stands in at the time from
its arrival to the drain's deadline.

The traced quantities pair the scheduler ticks inside the traced interval
(their dispatches and the tokens they streamed) with the device time of
the step programs in the same interval. The profiler starts and stops
between ticks, and every tick ends in a host sync, so the programs in the
trace are exactly those of those ticks.
"""
from __future__ import annotations

import math
from collections import defaultdict

from bench.costs import least_time


def percentile(values, q: float):
    """Linearly interpolated percentile at fractional rank (n-1)·q/100 (a
    copy of the program's `serving/telemetry.py` arithmetic); None for no
    values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    pos = (len(xs) - 1) * (q / 100.0)
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi:
        return xs[lo]
    return xs[lo] * (1.0 - (pos - lo)) + xs[hi] * (pos - lo)


def _late(run, r) -> float:
    return run.deadline - r.arrival


def ttft_ms(run) -> list[float]:
    return [1e3 * ((r.tokens[0] - r.arrival) if r.done else _late(run, r))
            for r in run.records]


def tpot_ms(run) -> list[float]:
    """(last − first token) / (n − 1) of each request with n >= 2."""
    out = []
    for r in run.records:
        if not r.done:
            out.append(1e3 * _late(run, r))
        elif len(r.tokens) >= 2:
            out.append(1e3 * (r.tokens[-1] - r.tokens[0])
                       / (len(r.tokens) - 1))
    return out


def queue_wait_ms(run) -> list[float]:
    return [1e3 * ((r.admit - r.arrival) if not math.isnan(r.admit)
                   else _late(run, r)) for r in run.records]


# ----------------------------------------------------------- traced runs --

def program_seconds(run, kind: str):
    """(device seconds, executions) of the step programs of ``kind``
    (``decode`` or ``prefill``) in the trace."""
    d = run.device.get("programs", {}).get(kind)
    return (d["seconds"], d["count"]) if d else (0.0, 0)


def decode_windows(run):
    """For each decode window in the traced ticks, the context of every
    token it produced, step by step: a list of steps, each a list of
    contexts (positions attended, the new one included)."""
    recs = {r.rid: r for r in run.all_records}
    out = []
    for tick in run.ticks:
        if not any(k == "decode_window" for k, _ in tick["dispatches"]):
            continue
        per_lane = defaultdict(list)
        for rid, j in tick["tokens"]:
            if j >= 1:                # token 0 comes from the prefill
                per_lane[rid].append(recs[rid].prompt_len + j)
        steps = max((len(v) for v in per_lane.values()), default=0)
        out.append([[v[s] for v in per_lane.values() if s < len(v)]
                    for s in range(steps)])
    return out


def prefill_chunks(run):
    """(start, length) of every prefill chunk dispatched in the traced
    ticks."""
    return [(int(a["start"]), int(a["chunk"])) for tick in run.ticks
            for k, a in tick["dispatches"] if k == "prefill_chunk"]


def decode_least_seconds(run) -> float:
    return sum(least_time(*run.costs.decode_step(step), run.peak)
               for window in decode_windows(run) for step in window)


def prefill_least_seconds(run) -> float:
    return sum(least_time(*run.costs.prefill_chunk(s, n), run.peak)
               for s, n in prefill_chunks(run))


def model_flops(run) -> float:
    """FLOPs of every prompt and output token processed in the traced
    ticks: the layers and attention of each, the head of each output."""
    c = run.costs
    f = 0.0
    for s, n in prefill_chunks(run):
        f += 2.0 * n * c.layer_params + c.attn_flops(n * s + n * (n + 1) / 2)
    for window in decode_windows(run):
        for step in window:
            f += sum(2.0 * c.layer_params + c.attn_flops(x) for x in step)
    n_out = sum(len(t["tokens"]) for t in run.ticks)
    return f + n_out * 2.0 * c.head_params


def in_flight_seconds(run) -> float:
    """The part of the traced interval in which at least one request had
    arrived and not yet finished."""
    t0, t1 = run.trace_interval
    spans = sorted((max(r.arrival, t0), min(r.finish if r.done else t1, t1))
                   for r in run.all_records if not math.isnan(r.arrival))
    total, cur_a, cur_b = 0.0, None, None
    for a, b in spans:
        if b <= a:
            continue
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def share(part: float, whole: float):
    """``part`` over ``whole`` in percent; None where there is nothing to
    read."""
    if not whole or part <= 0:
        return None
    return 100.0 * part / whole


def idle_share(run):
    """Share of the traced interval in which no operation ran on the
    device, in percent; None where none ran."""
    d = run.device
    if not d.get("window_s") or not d.get("busy_s"):
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])


def mfu(run):
    """Model FLOPs of the prompt and output tokens processed in the traced
    interval over the seconds of it in which a request was in flight times
    the chip's peak, in percent."""
    return share(model_flops(run),
                 in_flight_seconds(run) * run.peak["flops_per_s"])

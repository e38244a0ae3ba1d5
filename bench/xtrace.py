"""Reduce a profiler trace (`.xplane.pb`) to what the per-layer metrics read.

- ``busy_s``: the union of the intervals in which an operation ran on a
  device, averaged over the devices that ran any; ``window_s``: the traced
  interval, from the first to the last event of the trace's host threads.
- ``programs``: device seconds and executions of the step programs, found
  by the names the trace gives the jitted functions (`PROGRAMS`).
- ``breakdown``: the device operations that took most time (innermost
  operations only, as the loops that hold them would count their time
  again; named by their HLO name), and the longest idle gaps, each named
  by the innermost program span (a `TraceAnnotation` of the benchmark's
  tracer) that the host was in at the gap's middle.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path

# step programs, by a part of the name the trace gives the jitted function.
# The batcher jits its prefill through a `functools.partial` of a partial,
# which the trace names `jit__unknown`; no other program of a run is
# jitted that way.
PROGRAMS = {"decode": ("jit__paged_window(", "jit__paged_mixed_window("),
            "prefill": ("jit_paged_prefill(", "jit__unknown(")}
# host spans that attribute an idle gap, innermost first
SPANS = ("prefill_chunk", "decode_window", "fused_window", "await_arrival",
         "tick")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10


def newest_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals) -> list[tuple[float, float]]:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _leaves(events):
    """The events that hold no other event of the line."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[1] >= e[2] or nxt[2] > e[2]:
            out.append(e)
    return out


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def reduce_planes(planes) -> dict:
    """The reduction over ``planes``: (name, {line name: [(event name,
    start ns, end ns)]}) pairs, as :func:`read_planes` gives them."""
    devices, host_spans, host_extent = [], [], []
    for pname, lines in planes:
        if pname.startswith("/device:"):
            if lines.get(OPS_LINE):
                devices.append(lines)
        elif pname.startswith("/host:"):
            for evs in lines.values():
                for n, a, b in evs:
                    host_extent += [a, b]
                    if n in SPANS:
                        host_spans.append((n, a, b))
    window_ns = (max(host_extent) - min(host_extent)) if host_extent else 0
    busy, ops, programs = [], defaultdict(float), defaultdict(
        lambda: {"seconds": 0.0, "count": 0})
    gaps = []
    for lines in devices:
        op_iv = []
        for n, a, b in _leaves(lines[OPS_LINE]):
            ops[n.split(" = ")[0].lstrip("%")] += (b - a) * 1e-9
        for n, a, b in lines[OPS_LINE]:
            op_iv.append((a, b))
        u = _union(op_iv)
        busy.append(sum(b - a for a, b in u) * 1e-9)
        gaps += [(a1, b0) for (_, a1), (b0, _) in zip(u, u[1:])]
        for n, a, b in lines.get(MODULES_LINE, ()):
            for kind, pats in PROGRAMS.items():
                if any(p in n for p in pats):
                    programs[kind]["seconds"] += (b - a) * 1e-9
                    programs[kind]["count"] += 1
    rank = {n: i for i, n in enumerate(SPANS)}

    def span_at(t):
        inside = [n for n, a, b in host_spans if a <= t <= b]
        return min(inside, key=rank.get) if inside else "no_span"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": window_ns * 1e-9,
        "programs": dict(programs),
        "breakdown": {
            "device_ops": [[n, s] for n, s in sorted(
                ops.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[span_at((a + b) / 2), (b - a) * 1e-9]
                          for a, b in gaps[:TOP]]},
    }


def read_planes(path) -> list:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    return [(pl.name, {ln.name: _events(ln) for ln in pl.lines})
            for pl in pd.planes]


def reduce(trace_dir) -> dict:
    return reduce_planes(read_planes(newest_xplane(trace_dir)))

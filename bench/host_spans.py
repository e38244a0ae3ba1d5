"""The device waiting on the host, read from the server's own spans.

``host_gap_share``: the share of the traced interval (from the first to
the last event of the host threads, as `bench/xtrace.py` takes it) in
which no step program (``XLA Modules``) runs on the device while the host
is inside a scheduler ``tick`` but in none of the spans where it waits on
the device (``SYNCS``) and not in ``await_arrival``, where it has nothing
to do. The server opens these spans as profiler annotations; a program
that does not has no sync span in its trace and reads nothing. Spans are
joined per name, so one nested in another of the same name counts once.
Averaged over the devices that ran an operation, like ``busy_s``.

``idle_split`` places all of the device's idle time by the same spans, for
`bench/stages.py` to print.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path

from bench.xtrace import (MODULES_LINE, OPS_LINE, _union, newest_xplane,
                          read_planes)

TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_trace"
SYNCS = ("first_token_sync", "window_sync", "step_sync", "verify_sync",
         "draft_sync")
TICK, IDLE_HOST = "tick", "await_arrival"


def _minus(keep, cut) -> list[tuple[float, float]]:
    """The parts of the disjoint sorted intervals ``keep`` outside those
    of ``cut``."""
    out, j = [], 0
    for a, b in keep:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > a:
                out.append((a, cut[k][0]))
            a = max(a, cut[k][1])
            k += 1
        if a < b:
            out.append((a, b))
    return out


def _spans(planes):
    """(host spans by name, host event bounds, [(operations, modules)]
    of each device that ran an operation)."""
    spans, extent, devices = defaultdict(list), [], []
    for pname, lines in planes:
        if pname.startswith("/device:"):
            if lines.get(OPS_LINE):
                devices.append((lines[OPS_LINE],
                                lines.get(MODULES_LINE, ())))
        elif pname.startswith("/host:"):
            for evs in lines.values():
                for n, a, b in evs:
                    extent += [a, b]
                    spans[n].append((a, b))
    return spans, extent, devices


def _readable(spans, devices) -> bool:
    """A device ran an operation and the host has a tick and a sync span."""
    return bool(devices) and TICK in spans and any(s in spans for s in SYNCS)


def host_gap(planes):
    """(seconds, traced seconds) of device time lost to host work, from
    ``planes`` as `xtrace.read_planes` gives them; None where the trace has
    no sync span, no tick or no device operation."""
    spans, extent, devices = _spans(planes)
    if not _readable(spans, devices):
        return None
    waiting = _union(iv for n in SYNCS + (IDLE_HOST,) for iv in spans[n])
    host_work = _minus(_union(spans[TICK]), waiting)
    lost = [sum(b - a for a, b in _minus(
        host_work, _union((a, b) for _, a, b in modules)))
        for _, modules in devices]
    return sum(lost) / len(lost) * 1e-9, (max(extent) - min(extent)) * 1e-9


def idle_split(planes):
    """The device's idle seconds in the traced interval (no operation
    runs), by where they lie: ``in_programs`` (inside a step program),
    ``under_syncs`` (the host in a sync span, waiting on the device),
    ``host_work`` (in a tick, outside syncs: what ``host_gap`` counts) and
    ``outside_ticks`` (awaiting arrivals, the profiler's start and stop);
    averaged over the devices. None where ``host_gap`` reads nothing."""
    spans, extent, devices = _spans(planes)
    if not _readable(spans, devices):
        return None
    whole = [(min(extent), max(extent))]
    syncs = _union(iv for n in SYNCS for iv in spans[n])
    work = _minus(_union(spans[TICK]),
                  _union(iv for n in SYNCS + (IDLE_HOST,)
                         for iv in spans[n]))
    out = defaultdict(float)
    for ops, modules in devices:
        idle = _minus(whole, _union((a, b) for _, a, b in ops))
        outside = _minus(idle, _union((a, b) for _, a, b in modules))
        rest = _minus(outside, syncs)
        away = _minus(rest, work)
        for key, ivs in (("idle", idle), ("outside_programs", outside),
                         ("rest", rest), ("outside_ticks", away)):
            out[key] += sum(b - a for a, b in ivs) * 1e-9 / len(devices)
    return {"idle": out["idle"],
            "in_programs": out["idle"] - out["outside_programs"],
            "under_syncs": out["outside_programs"] - out["rest"],
            "host_work": out["rest"] - out["outside_ticks"],
            "outside_ticks": out["outside_ticks"],
            "traced": (max(extent) - min(extent)) * 1e-9}


def host_gap_share(run):
    """``host_gap`` of the run's trace in percent of the traced interval;
    None in a run without a trace or where ``host_gap`` reads nothing."""
    if not run.device:
        return None
    try:
        got = host_gap(read_planes(newest_xplane(TRACE_DIR)))
    except FileNotFoundError:
        return None
    if got is None or not got[1]:
        return None
    return 100.0 * got[0] / got[1]

"""The device waiting on host work, read from the server's spans in a
profiler trace: hand-built planes and a trace recorded on the chip."""
import gzip
import json
from pathlib import Path

import pytest

from bench.host_spans import host_gap, idle_split
from bench.xtrace import reduce_planes

RECORDED = Path(__file__).with_name("data") / "chat_spans.planes.json.gz"
MS = 1_000_000


def _planes(host):
    """Four idle stretches of the device: 20-25 ms inside a program (no
    operation runs, the module does), 0-10 under `admit`, 70-100 under
    `window_sync`, 100-130 under `await_arrival`."""
    return [
        ("/host:CPU", {"python": host}),
        ("/device:TPU:0", {
            "XLA Modules": [("jit_paged_prefill(1)", 10 * MS, 40 * MS),
                            ("jit__paged_window(2)", 40 * MS, 70 * MS),
                            ("jit__paged_window(3)", 130 * MS, 200 * MS)],
            "XLA Ops": [("fusion.1", 10 * MS, 20 * MS),
                        ("fusion.2", 25 * MS, 40 * MS),
                        ("fusion.3", 40 * MS, 70 * MS),
                        ("fusion.4", 130 * MS, 200 * MS)]}),
    ]


HOST = [("tick", 0, 100 * MS), ("tick", 0, 100 * MS),     # nested twice
        ("admit", 0, 10 * MS), ("decode_window", 40 * MS, 100 * MS),
        ("window_sync", 45 * MS, 100 * MS), ("window_sync", 45 * MS, 100 * MS),
        ("await_arrival", 100 * MS, 130 * MS), ("tick", 130 * MS, 200 * MS)]


def test_only_host_work_outside_syncs_counts():
    lost, window = host_gap(_planes(HOST))
    assert window == pytest.approx(0.2)
    assert lost == pytest.approx(0.010)          # the 0-10 ms under admit
    idle = 1 - reduce_planes(_planes(HOST))["busy_s"] / window
    assert lost / window <= idle


def test_idle_split_places_each_idle_stretch():
    got = idle_split(_planes(HOST))
    want = {"idle": 0.075, "in_programs": 0.005, "under_syncs": 0.030,
            "host_work": 0.010, "outside_ticks": 0.030, "traced": 0.2}
    assert got == pytest.approx(want)
    assert got["host_work"] == pytest.approx(host_gap(_planes(HOST))[0])


def test_a_program_without_sync_spans_reads_nothing():
    host = [e for e in HOST if e[0] != "window_sync"]
    assert host_gap(_planes(host)) is None
    assert idle_split(_planes(host)) is None
    assert host_gap([("/host:CPU", {"python": HOST})]) is None


def test_recorded_chip_trace():
    """A traced tick of qwen3-1.7b.chat on one TPU v5 lite: the server's
    spans on the profiler's clock, the prefill under its own name."""
    planes = json.loads(gzip.open(RECORDED).read())
    names = {n for p, lines in planes if p.startswith("/host:")
             for evs in lines.values() for n, _, _ in evs}
    assert {"tick", "admit", "drain", "prefill_request", "prefill_chunk",
            "first_token_sync", "window_prep", "decode_window",
            "window_sync", "reconcile"} <= names
    assert "fused_window" not in names
    d = reduce_planes(planes)
    assert d["programs"]["prefill"]["count"] >= 1
    modules = {n.split("(")[0] for p, lines in planes
               if p.startswith("/device:")
               for n, _, _ in lines.get("XLA Modules", ())}
    assert "jit_paged_prefill" in modules and "jit__unknown" not in modules
    lost, window = host_gap(planes)
    assert 0 < lost / window <= 1 - d["busy_s"] / d["window_s"]
    split = idle_split(planes)
    assert split["host_work"] == pytest.approx(lost)
    assert split["idle"] == pytest.approx(d["window_s"] - d["busy_s"])
    assert split["idle"] == pytest.approx(sum(
        split[k] for k in ("in_programs", "under_syncs", "host_work",
                           "outside_ticks")))

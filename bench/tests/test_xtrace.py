"""The trace reduction: busy and idle time, device time per step program,
and idle gaps named by the host span they fell in."""
import gzip
import json
from pathlib import Path

import pytest

from bench.xtrace import reduce_planes

RECORDED = Path(__file__).with_name("data") / "chat_tick.planes.json.gz"


def test_reduce_planes_by_hand():
    ms = 1_000_000
    planes = [
        ("/host:CPU", {"python": [
            ("tick", 0, 100 * ms), ("prefill_chunk", 1 * ms, 2 * ms),
            ("decode_window", 30 * ms, 31 * ms)]}),
        ("/device:TPU:0", {
            "XLA Modules": [("jit_paged_prefill(1)", 2 * ms, 12 * ms),
                            ("jit__paged_window(2)", 40 * ms, 90 * ms)],
            "XLA Ops": [("fusion.1", 2 * ms, 7 * ms),
                        ("fusion.2", 6 * ms, 12 * ms),
                        ("fusion.1", 40 * ms, 90 * ms)]}),
    ]
    d = reduce_planes(planes)
    assert d["window_s"] == pytest.approx(0.1)
    assert d["busy_s"] == pytest.approx(0.060)
    assert d["programs"]["prefill"] == {"seconds": pytest.approx(0.010),
                                        "count": 1}
    assert d["programs"]["decode"]["count"] == 1
    ops = dict(d["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.055)
    # the one gap, 12..40 ms, has its middle (26 ms) inside the tick only
    assert d["breakdown"]["idle_gaps"] == [["tick", pytest.approx(0.028)]]


def test_no_device_plane_reads_nothing():
    d = reduce_planes([("/host:CPU", {"python": [("tick", 0, 10)]})])
    assert d["busy_s"] == 0.0 and d["programs"] == {}


def test_recorded_chip_trace():
    """The reducer's inputs from a trace recorded on one TPU v5 lite
    (qwen3-1.7b.chat): two prefill chunks and the first fused decode window
    of a tick, with the host spans around them."""
    planes = json.loads(gzip.open(RECORDED).read())
    d = reduce_planes(planes)
    assert d["programs"]["decode"]["count"] == 1
    assert d["programs"]["decode"]["seconds"] == pytest.approx(0.776967, 1e-5)
    assert d["programs"]["prefill"]["count"] == 2
    assert d["busy_s"] == pytest.approx(0.863243, 1e-5)
    assert 0 < d["busy_s"] <= d["window_s"]
    ops = [n for n, _ in d["breakdown"]["device_ops"]]
    assert ops[0] == "broadcast_select_fusion.8"
    assert not any(n.startswith("while") for n in ops)
    assert {n for n, _ in d["breakdown"]["idle_gaps"]} <= {
        "tick", "decode_window", "prefill_chunk"}

"""The metric readers on a hand-made run: two scheduler ticks, one with a
prefill chunk and a decode window, and a device trace of known times."""
import math

import pytest

from bench.client import Record
from bench.costs import Costs, least_time
from bench.run import RunData, load_reader
from smoke import PEAK, SMOKE_CONFIG


def _run():
    c = dict(SMOKE_CONFIG)
    a = Record(0, list(range(10)), 4, arrival=0.0, admit=0.1,
               tokens=[0.2, 0.3, 0.3, 0.3], finish=0.3)
    b = Record(1, list(range(20)), 2, arrival=0.1, admit=0.1,
               tokens=[0.2, 0.3], finish=0.3)
    ticks = [
        {"t0": 0.1, "t1": 0.2, "tokens": [(0, 0), (1, 0)],
         "dispatches": [("prefill_chunk", {"start": 0, "chunk": 10}),
                        ("prefill_chunk", {"start": 0, "chunk": 16}),
                        ("prefill_chunk", {"start": 16, "chunk": 4})]},
        {"t0": 0.2, "t1": 0.3, "tokens": [(0, 1), (1, 1), (0, 2), (0, 3)],
         "dispatches": [("decode_window", {"window": 4, "active": 2})]},
    ]
    marks = {"trace_start": (0.0, 0, {"decode_steps": 0,
                                      "decode_dispatches": 0}),
             "trace_stop": (0.5, 0, {"decode_steps": 4,
                                     "decode_dispatches": 1})}
    device = {"busy_s": 0.2, "window_s": 0.5,
              "programs": {"decode": {"seconds": 0.04, "count": 1},
                           "prefill": {"seconds": 0.03, "count": 3}}}
    return RunData(spec={"config": c}, seconds=1.0, deadline=61.0,
                   records=[a, b], all_records=[a, b], costs=Costs(c),
                   peak=PEAK, marks=marks, ticks=ticks, device=device,
                   setup_s=5.0)


def test_decode_readers():
    run = _run()
    costs = run.costs
    # step 1: lanes 0 and 1 attend 10+1 and 20+1 positions; steps 2, 3:
    # lane 0 alone at 12 and 13
    least = (least_time(*costs.decode_step([11, 21]), PEAK)
             + least_time(*costs.decode_step([12]), PEAK)
             + least_time(*costs.decode_step([13]), PEAK))
    assert load_reader("decode_roofline")(run) == pytest.approx(
        100 * least / 0.04)
    assert load_reader("decode_step_ms")(run) == pytest.approx(40 / 4)


def test_prefill_readers():
    run = _run()
    least = sum(least_time(*run.costs.prefill_chunk(s, n), PEAK)
                for s, n in ((0, 10), (0, 16), (16, 4)))
    assert load_reader("prefill_roofline")(run) == pytest.approx(
        100 * least / 0.03)
    assert load_reader("prefill_ms_per_ktok")(run) == pytest.approx(
        30 / (30 / 1000))


def test_whole_step_and_device_readers():
    run = _run()
    c = run.costs
    flops = (sum(2 * n * c.layer_params
                 + 4 * c.L * c.hq * c.hd * (n * s + n * (n + 1) / 2)
                 for s, n in ((0, 10), (0, 16), (16, 4)))
             + sum(2 * c.layer_params + c.attn_flops(x)
                   for x in (11, 21, 12, 13))
             + 6 * 2 * c.head_params)
    # in flight from 0 to 0.3 s of the 0.5 s traced
    assert load_reader("mfu.tpot")(run) == pytest.approx(
        100 * flops / (0.3 * PEAK["flops_per_s"]))
    assert load_reader("idle_share.chat")(run) == pytest.approx(60.0)


def test_client_readers():
    run = _run()
    assert load_reader("ttft_p50_ms")(run) == pytest.approx(150.0)
    # TPOTs 0.1/3 s (request 0) and 0.1 s (request 1)
    assert load_reader("tpot_p70_ms")(run) == pytest.approx(
        0.3 * 100 / 3 + 0.7 * 100)
    assert load_reader("queue_wait_p70_ms")(run) == pytest.approx(
        0.7 * 100 + 0.3 * 0)
    assert load_reader("setup_s")(run) == 5.0


def test_unfinished_request_sorts_last():
    run = _run()
    run.records[1].finish = math.nan
    assert load_reader("ttft_p70_ms")(run) == pytest.approx(
        0.7 * 1e3 * (61.0 - 0.1) + 0.3 * 200.0)

"""The TTFT stage readers on hand-built marks: the change of the program's
counters from the window's open to the trace's start, per first token."""
import pytest

from bench.run import RunData, load_reader


def _run(marks):
    return RunData(spec={"config": {}}, marks=marks)


def _stats(first, wait, hold):
    return {"first_tokens": first, "prefill_wait_s": wait,
            "first_token_hold_s": hold, "decode_steps": 0}


def test_counters_per_first_token_to_the_trace_start():
    marks = {"open": (0.0, 0, _stats(3, 1.0, 2.0)),
             "trace_start": (47.0, 0, _stats(7, 1.6, 5.2)),
             "close": (51.0, 0, _stats(9, 9.0, 9.0))}
    run = _run(marks)
    assert load_reader("prefill_wait_ms")(run) == pytest.approx(150.0)
    assert load_reader("first_token_hold_ms")(run) == pytest.approx(800.0)


def test_untraced_run_reads_to_the_close():
    run = _run({"open": (0.0, 0, _stats(0, 0.0, 0.0)),
                "close": (51.0, 0, _stats(4, 0.2, 3.0))})
    assert load_reader("prefill_wait_ms")(run) == pytest.approx(50.0)
    assert load_reader("first_token_hold_ms")(run) == pytest.approx(750.0)


@pytest.mark.parametrize("marks", [
    # a program that predates the counters
    {"open": (0.0, 0, {"decode_steps": 0}),
     "trace_start": (47.0, 0, {"decode_steps": 9})},
    # no first token placed in the stretch
    {"open": (0.0, 0, _stats(2, 1.0, 1.0)),
     "trace_start": (47.0, 0, _stats(2, 1.0, 1.0))},
    # no mark to start from
    {"close": (51.0, 0, _stats(2, 1.0, 1.0))},
])
def test_nothing_to_read(marks):
    run = _run(marks)
    assert load_reader("prefill_wait_ms")(run) is None
    assert load_reader("first_token_hold_ms")(run) is None


def test_decompose_splits_each_ttft_into_stages():
    from types import SimpleNamespace as NS
    from bench.stages import decompose
    recs = [NS(rid=1, arrival=1.0, admit=1.2, tokens=[2.0]),
            NS(rid=2, arrival=2.0, admit=2.1, tokens=[3.0]),
            NS(rid=3, arrival=-1.0, admit=0.0, tokens=[1.0]),   # lead-in
            NS(rid=4, arrival=3.0, admit=3.1, tokens=[])]       # no token
    reqs = {1: NS(t_submit=10.0, t_prefill=10.1, t_first=10.3,
                  t_placed=10.79),
            2: NS(t_submit=20.0, t_prefill=20.0, t_first=20.2,
                  t_placed=20.7),
            3: NS(t_submit=0.0, t_prefill=0.0, t_first=0.0, t_placed=0.0)}
    got = decompose(recs, reqs, close=51.0)
    assert got["n"] == 2
    mean = got["mean"]
    assert mean["ttft"] == pytest.approx(1000.0)
    assert mean["queue_wait"] == pytest.approx(150.0)
    assert mean["prefill_wait"] == pytest.approx(50.0)
    assert mean["prefill_host"] == pytest.approx(200.0)
    assert mean["first_token_hold"] == pytest.approx(495.0)
    assert mean["rest"] == pytest.approx(105.0)
    assert got["median"]["rest"] == pytest.approx(105.0)
    assert decompose(recs[2:], reqs, close=51.0) == {"n": 0}


@pytest.mark.parametrize("tracer", [False, True])
def test_a_served_cell_adds_up_to_its_ttft(tracer, monkeypatch):
    """At smoke size on the CPU, with and without the program's own
    `Tracer`: the stages of every window request leave only the drain."""
    import bench.run as run
    from bench.stages import STAGES, run_staged
    from smoke import DEVICE, PEAK, smoke_spec
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    out, st = run_staged(smoke_spec(), seed=2**33 + 5, seconds=1.5,
                         trace=False, device=DEVICE, peak=PEAK,
                         tracer=tracer)
    assert out["correct"], out["checks"]
    assert st["n"] == out["attempted"] > 0
    assert st["tracer"] is tracer
    mean = st["mean"]
    assert 0.0 <= mean["rest"] < 0.1 * mean["ttft"]
    assert mean["ttft"] == pytest.approx(
        sum(mean[s] for s in STAGES) + mean["rest"])
    assert st["counters_ms"]["first_token_hold_s"] > 0
    assert st["counters_ms"]["prefill_host_s"] > 0
    assert run.Session.__name__ == "Session"

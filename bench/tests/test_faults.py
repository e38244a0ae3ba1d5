"""The whole of a run at smoke size on the CPU, past the look for a chip:
sound runs come out correct, and each fault the cell can have, planted in
the timed path, makes `correct` come out false. The control (the program
one precision below what the configuration states) fails too."""
import time

import jax.numpy as jnp
import pytest

import bench.run as run
from bench.limits import readings
from smoke import DEVICE, PEAK, SMOKE_CONFIG, smoke_spec

F32 = dict(SMOKE_CONFIG, torch_dtype="float32")


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")


def cell(spec, seed):
    return run.run_cell(spec, seed=seed, seconds=1.5, trace=False,
                        device=DEVICE, peak=PEAK, t_start=time.monotonic())


@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_sound_run_is_correct(seed):
    out = cell(smoke_spec(), seed)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["info"]["compiles_in_window"] == 0
    assert list(out)[-1] == "checks"


def _decode_keeps_state(monkeypatch):
    from repro.models import transformer
    real = transformer.paged_decode_step

    def step(params, token, pool, cfg, **kw):
        logits, _ = real(params, token, pool, cfg, **kw)
        return logits, pool
    monkeypatch.setattr(transformer, "paged_decode_step", step)


def _prefill_keeps_state(monkeypatch):
    from repro.models import transformer
    real = transformer.paged_prefill

    def prefill(params, tokens, pool, cfg, **kw):
        logits, _ = real(params, tokens, pool, cfg, **kw)
        return logits, pool
    monkeypatch.setattr(transformer, "paged_prefill", prefill)


def _decode_token_altered(monkeypatch):
    from repro.core import sync
    real = sync._masked_step

    def step(*a, **kw):
        (tok, pool, lengths, rem), (nxt, active), extra = real(*a, **kw)
        bad = jnp.where(jnp.arange(nxt.shape[0]) == 0, (nxt + 1) % 256, nxt)
        return (bad[:, None], pool, lengths, rem), (bad, active), extra
    monkeypatch.setattr(sync, "_masked_step", step)


def _first_token_altered(monkeypatch):
    from repro.serving import scheduler
    real = scheduler.sample
    monkeypatch.setattr(scheduler, "sample",
                        lambda *a, **kw: (real(*a, **kw) + 1) % 256)


@pytest.mark.parametrize("fault", [
    _decode_keeps_state, _prefill_keeps_state, _decode_token_altered,
    _first_token_altered])
def test_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = cell(smoke_spec(), 3)
    assert not out["correct"], out["checks"]


def test_control_one_precision_below_is_not_correct():
    """A float32 configuration, held to a limit set for float32: the same
    program computing in bfloat16 fails it."""
    assert cell(smoke_spec(config=F32, limit=1e-4), 5)["correct"]
    assert not cell(smoke_spec(config=SMOKE_CONFIG, limit=1e-4), 5)["correct"]


def test_limits_reads_the_program_and_its_int8_control():
    spec = smoke_spec()
    for program in ({}, {"weight_quant": "int8"}):
        rows = readings(spec, [1, 2], 1.5, program)
        assert [r["seed"] for r in rows] == [1, 2]
        assert all(r["unfinished"] == 0 and r["mean_logit_gap"] >= 0
                   and r["max_logit_gap"] >= r["mean_logit_gap"]
                   and r["tokens_compared"] > 0 for r in rows)


def test_closed_loop_run_is_correct():
    """The closed-loop driver, kept for an offline batch cell (PERF.md
    section 7): clients keep two requests per lane in flight."""
    mix = {k: v for k, v in smoke_spec()["mix"].items() if k != "arrival"}
    mix.update(loop="closed", outstanding_per_lane=2)
    out = cell(smoke_spec(mix=mix), 4)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 8 and out["failed"] == 0

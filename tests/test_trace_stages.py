"""The server's own spans on the profiler's clock, the TTFT stage counters,
and the step programs' names: every tracer hook opens a
``jax.profiler.TraceAnnotation`` (nested as the work is), the stages add up
to each request's time to first token under FakeClock, they reconcile with
the tracer's counters on every serving path, and the batcher's jitted
programs lower under names of their own."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving.ingress import AsyncServer
from repro.serving.scheduler import PagedBatcher
from repro.serving.spec import SpecConfig
from repro.serving.telemetry import FakeClock
from repro.serving.trace import NULL_TRACER, Tracer, counter_reconciliation

BS = 16
SPANS = {"tick", "admit", "drain", "await_arrival", "prefill_request",
         "prefill_chunk", "first_token_sync", "window_prep", "decode_window",
         "mixed_window", "window_sync", "step_prep", "decode_step",
         "mixed_step", "step_sync", "verify_prep", "paged_verify",
         "verify_sync", "spec_draft", "draft_sync", "reconcile"}
ARMS = {"host": dict(sync="host"),
        "device": dict(sync="device", window=3),
        "mixed": dict(sync="device", window=3, mixed_batch=True),
        "spec": dict(sync="device", spec=SpecConfig(k=2))}
STAGES = ("prefill_wait_s", "prefill_host_s", "first_token_hold_s")


def _cost_model(kind, predicted_us):
    """Virtual seconds per dispatch: prefill chunks 2 ms, windows 8 ms."""
    return {"prefill_chunk": 2e-3, "decode_window": 8e-3,
            "mixed_window": 8e-3}.get(kind, 1e-3)


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each opened
    name with the innermost enclosing name of ``SPANS``."""

    def __init__(self):
        self.opened: list[tuple[str, str | None]] = []
        self._stack: list[str] = []

    def __call__(self, name, **_):
        rec = self

        class _Ann:
            def __enter__(self):
                outer = [n for n in rec._stack if n in SPANS]
                rec.opened.append((name, outer[-1] if outer else None))
                rec._stack.append(name)

            def __exit__(self, *exc):
                assert rec._stack.pop() == name
                return False

        return _Ann()


def _workload(cfg, seed=0):
    """Five requests on two lanes: three at once (the third queues for a
    lane), one soon after, and one after the rest have finished (the
    server sleeps for it)."""
    rng = np.random.default_rng(seed)
    times = (0.0, 0.0, 0.0, 0.02, 5.0)
    return [(t, dict(prompt=rng.integers(0, cfg.vocab_size, 12 + 9 * i
                                         ).astype(np.int32),
                     max_new_tokens=5, rid=i))
            for i, t in enumerate(times)]


def _serve(cfg, params, arm, *, traced=True):
    clock = FakeClock()
    tracer = Tracer(clock, cost_model=_cost_model) if traced else None
    pb = PagedBatcher(cfg, params, num_blocks=12, block_size=BS,
                      max_blocks_per_seq=4, decode_width=2, buckets=(32,),
                      cache_dtype=jnp.float32, tracer=tracer, **ARMS[arm])
    server = AsyncServer(pb, clock=clock)
    server.run_sync(_workload(cfg))
    pb.kv.assert_drained()
    return server, tracer


@pytest.fixture(scope="module", params=sorted(ARMS))
def served(request, smoke_model):
    cfg, _, params = smoke_model
    return request.param, _serve(cfg, params, request.param)


# ------------------------------------------------------------ annotations --

@pytest.mark.tier1
def test_tracer_hooks_open_profiler_annotations(monkeypatch):
    rec = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    tr = Tracer(FakeClock())
    with tr.span("tick"):
        with tr.dispatch("decode_window"):
            with tr.span("window_sync", cat="sync"):
                tr.instant("lane_preempt")
    assert rec.opened == [("tick", None), ("decode_window", "tick"),
                          ("window_sync", "decode_window")]
    rec.opened.clear()
    with NULL_TRACER.span("tick"):
        with NULL_TRACER.dispatch("decode_window"):
            with NULL_TRACER.span("window_sync", cat="sync"):
                NULL_TRACER.instant("lane_preempt")
    assert rec.opened == []


@pytest.mark.tier1
def test_served_spans_nest_on_the_profiler_clock(smoke_model, monkeypatch):
    """A traced AsyncServer run over the fused-window batcher opens every
    host, sync and dispatch span as an annotation, inside the span whose
    work it is; the default NULL_TRACER run opens none."""
    cfg, _, params = smoke_model
    rec = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    _serve(cfg, params, "device")
    parents: dict = {}
    for name, outer in rec.opened:
        parents.setdefault(name, set()).add(outer)
    want = {"tick": {None}, "await_arrival": {None}, "admit": {"tick"},
            "drain": {"tick"}, "prefill_request": {"tick"},
            "prefill_chunk": {"prefill_request"},
            "first_token_sync": {"prefill_request"},
            "window_prep": {"tick"}, "decode_window": {"tick"},
            "window_sync": {"decode_window"}, "reconcile": {"tick"}}
    assert {n: parents.get(n) for n in want} == want
    assert "fused_window" not in parents
    # in each tick: admit, then the step's spans, then drain
    names = [n for n, _ in rec.opened]
    first = names.index("window_prep")
    tick = max(i for i in range(first) if names[i] == "tick")
    assert names[tick + 1] == "admit"
    assert names.index("drain", first) > names.index("reconcile", first)

    rec.opened.clear()
    _serve(cfg, params, "device", traced=False)
    assert not [n for n, _ in rec.opened if n in SPANS]


# ------------------------------------------------------------ TTFT stages --

@pytest.mark.tier1
def test_ttft_stages_add_up_to_time_to_first_token(served):
    """queue wait + prefill wait + prefill on the host + first-token hold
    is each request's first-token stamp minus its arrival, to the
    microsecond: the drain, the one stage left out, takes no virtual time.
    The counters are the stages' sums."""
    arm, (server, _) = served
    tel = server.telemetry.traces
    reqs = [e.cur_req for e in server._order]
    sums = dict.fromkeys(STAGES, 0.0)
    for req in reqs:
        tr = tel[req.rid]
        stages = dict(zip(STAGES, (req.t_prefill - req.t_submit,
                                   req.t_first - req.t_prefill,
                                   req.t_placed - req.t_first)))
        assert min(stages.values()) >= 0.0, (arm, req.rid, stages)
        assert tr.queue_delay + sum(stages.values()) == pytest.approx(
            tr.first_token_t - tr.enqueue_t, abs=1e-6), (arm, req.rid)
        for k, v in stages.items():
            sums[k] += v
    st = server.stats()
    assert st["first_tokens"] == len(reqs)
    for k in STAGES:
        assert st[k] == pytest.approx(sums[k], abs=1e-9), (arm, k)
    assert st["prefill_host_s"] > 0, arm
    if arm == "mixed":
        # ingress hands over one admission at a time and the first token
        # is sampled after the window that carried the last chunk: the
        # wait is all queue wait, and nothing holds the token
        assert st["prefill_wait_s"] == st["first_token_hold_s"] == 0, arm
    else:
        # the second prefill of a tick waits behind the first, and every
        # first token placed before a step's decode waits out that decode
        assert st["prefill_wait_s"] > 0 and st["first_token_hold_s"] > 0, arm


@pytest.mark.tier1
def test_stage_counters_reconcile(served):
    arm, (server, tracer) = served
    st = server.stats()
    assert counter_reconciliation(tracer, st) == {}, arm
    assert st["first_tokens"] == 5, arm
    for k in ("first_tokens",) + STAGES:
        assert tracer.counter_value(k) == st[k], (arm, k)


# ---------------------------------------------------------- program names --

def _lower(pb, program):
    W, nb = pb.W, pb.kv.max_blocks_per_seq
    i32 = jnp.int32
    if program == "paged_prefill":
        return pb._prefill.lower(
            pb.params, jnp.zeros((1, 32), i32), pb.kv.pool,
            block_table=jnp.zeros((1, nb), i32),
            start_index=jnp.asarray(0, i32))
    if program == "mixed_step":
        return pb._mixed.lower(
            pb.params, jnp.zeros((W, 1), i32), jnp.zeros((1, 32), i32),
            pb.kv.pool, decode_tables=jnp.zeros((W, nb), i32),
            decode_lengths=jnp.zeros((W,), i32),
            prefill_table=jnp.zeros((1, nb), i32),
            prefill_start=jnp.asarray(0, i32))
    return pb._verify.lower(
        pb.params, jnp.zeros((W, 3), i32), pb.kv.pool,
        block_table=jnp.zeros((W, nb), i32),
        start_index=jnp.zeros((W,), i32))


@pytest.mark.tier1
@pytest.mark.parametrize("program", ["paged_prefill", "mixed_step",
                                     "paged_verify"])
def test_step_programs_lower_under_their_names(smoke_model, program):
    cfg, _, params = smoke_model
    pb = PagedBatcher(cfg, params, num_blocks=12, block_size=BS,
                      max_blocks_per_seq=4, decode_width=2, buckets=(32,),
                      cache_dtype=jnp.float32,
                      spec=SpecConfig(k=2) if program == "paged_verify"
                      else None)
    text = _lower(pb, program).as_text()
    assert f"module @jit_{program} " in text
    assert "_unknown" not in text

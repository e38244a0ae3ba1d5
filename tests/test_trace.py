"""Tracing subsystem (serving/trace.py): byte-deterministic artifacts under
FakeClock, span/flow structural integrity, zero-overhead-when-off, exact
Prometheus exposition, plan-drift accounting, and the stats() schema
contract the reconciliation rides on."""
import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from repro.serving.scheduler import PagedBatcher, Request
from repro.serving.telemetry import FakeClock
from repro.serving.trace import (
    DriftAggregator, MetricsRegistry, NULL_TRACER, STATS_COUNTER_KEYS,
    STATS_GAUGE_KEYS, Tracer, counter_reconciliation)

_ROOT = Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location(
    "check_trace", _ROOT / "scripts" / "check_trace.py")
check_trace = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(check_trace)


def _cost_model(kind, predicted_us):
    # deterministic virtual cost: the solver's prediction, floored at 10us
    return max(predicted_us, 10.0) * 1e-6


def _traced_run(cfg, params, *, seed=0, n_req=3, new_tokens=6, **kw):
    """One deterministic PagedBatcher run under a traced FakeClock.
    Returns (batcher, tracer, outputs)."""
    tracer = Tracer(FakeClock(), cost_model=_cost_model)
    pb = PagedBatcher(cfg, params, num_blocks=25, block_size=16,
                      max_blocks_per_seq=6, decode_width=3, buckets=(32, 64),
                      cache_dtype=np.float32, tracer=tracer, **kw)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 12 + 7 * i
                                        ).astype(np.int32),
                    max_new_tokens=new_tokens)
            for i in range(n_req)]
    for r in reqs:
        pb.submit(r)
    for _ in range(10_000):
        if not pb.busy:
            break
        pb.step()
    pb.kv.assert_drained()
    return pb, tracer, [list(r.output) for r in reqs]


# ------------------------------------------------------------ determinism --

def test_trace_bitwise_identical_across_reruns(smoke_model, tmp_path):
    """The headline determinism contract: two identical runs under FakeClock
    produce byte-identical Chrome trace files and Prometheus snapshots."""
    cfg, _, params = smoke_model
    paths = []
    for i in range(2):
        _, tracer, _ = _traced_run(cfg, params, sync="device", window=2,
                                   engine_mode="hetero-tensor")
        p = tracer.save_chrome(tmp_path / f"trace{i}.json")
        (tmp_path / f"metrics{i}.prom").write_text(tracer.to_prometheus())
        paths.append(p)
    b0, b1 = (p.read_bytes() for p in paths)
    assert b0 == b1
    m0, m1 = ((tmp_path / f"metrics{i}.prom").read_bytes() for i in range(2))
    assert m0 == m1
    # and the artifact is structurally valid (monotone ts, paired B/E,
    # resolvable flows) per the CI checker
    assert check_trace.validate(json.loads(b0.decode())) == []


def test_traced_run_matches_untraced_tokens(smoke_model):
    """Tracing is observation only: token output with the tracer attached
    is identical to the default (NULL_TRACER) run, and the default run
    records nothing."""
    cfg, _, params = smoke_model
    _, tracer, traced_out = _traced_run(cfg, params, sync="device", window=2)

    pb = PagedBatcher(cfg, params, num_blocks=25, block_size=16,
                      max_blocks_per_seq=6, decode_width=3, buckets=(32, 64),
                      cache_dtype=np.float32, sync="device", window=2)
    assert pb.tracer is NULL_TRACER
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 12 + 7 * i
                                        ).astype(np.int32),
                    max_new_tokens=6)
            for i in range(3)]
    for r in reqs:
        pb.submit(r)
    while pb.busy:
        pb.step()
    assert [list(r.output) for r in reqs] == traced_out
    assert tracer.n_events > 0


def test_null_tracer_records_nothing():
    """Every NullTracer hook is a no-op returning a live context."""
    with NULL_TRACER.span("x"):
        with NULL_TRACER.dispatch("y", tags=(("wq", 1, "pad", 3.0, 1),)):
            NULL_TRACER.instant("z")
            NULL_TRACER.request_event("enqueue", 0)
            NULL_TRACER.count("decode_steps")
            NULL_TRACER.gauge("peak_active", 4)
    assert NULL_TRACER.enabled is False


# ------------------------------------------------------- event structure --

def test_span_nesting_and_flow_integrity(smoke_model):
    cfg, _, params = smoke_model
    _, tracer, _ = _traced_run(cfg, params, sync="host",
                               engine_mode="hetero-tensor")
    trace = tracer.to_chrome()
    assert check_trace.validate(trace) == []
    events = trace["traceEvents"]
    names = {e["name"] for e in events if e["ph"] == "B"}
    assert "prefill_chunk" in names and "decode_step" in names
    # every dispatch B carries its solver decisions
    for e in events:
        if e["ph"] == "B" and e.get("cat") == "dispatch" \
                and e["name"] in ("prefill_chunk", "decode_step"):
            decs = e["args"]["decisions"]
            assert decs and all(
                set(d) == {"site", "M", "strategy", "t_us", "count"}
                for d in decs)


def test_request_flow_arrows():
    """Lifecycle -> Chrome flow mapping: 's' at enqueue, 't' mid-life,
    'f' (with bp=e) at finish, id = rid — and the checker resolves it."""
    tr = Tracer(FakeClock())
    for rid in (0, 1):
        tr.request_event("enqueue", rid)
        tr.request_event("admit", rid, track="scheduler")
    tr.request_event("preempt", 1, track="scheduler")
    tr.request_event("resume", 1, track="scheduler")
    for rid in (0, 1):
        tr.request_event("finish", rid)
    trace = tr.to_chrome()
    assert check_trace.validate(trace) == []
    flows = [e for e in trace["traceEvents"] if e["ph"] in ("s", "t", "f")]
    assert [(e["ph"], e["id"]) for e in flows] == [
        ("s", 0), ("t", 0), ("s", 1), ("t", 1), ("t", 1), ("t", 1),
        ("f", 0), ("f", 1)]
    assert all(e["bp"] == "e" for e in flows if e["ph"] == "f")
    # a dangling flow (started, never finished) is a checker violation
    tr2 = Tracer(FakeClock())
    tr2.request_event("enqueue", 7)
    errs = check_trace.validate(tr2.to_chrome())
    assert any("never finished" in e for e in errs)


def test_ring_buffer_bounds_memory():
    clk = FakeClock()
    tr = Tracer(clk, capacity=8)
    for i in range(20):
        tr.instant(f"e{i}")
    assert len(tr.events) == 8
    assert tr.n_events == 20 and tr.dropped == 12
    assert tr.to_chrome()["otherData"] == {"dropped_events": 12,
                                           "total_events": 20}
    with pytest.raises(ValueError):
        Tracer(clk, capacity=0)


def test_cost_model_advances_fake_clock():
    clk = FakeClock()
    tr = Tracer(clk, cost_model=lambda kind, pred: 0.002)
    with tr.dispatch("decode_step"):
        pass
    assert clk.now() == pytest.approx(0.002)
    b, e = tr.events
    assert (b["ph"], e["ph"]) == ("B", "E")
    assert e["ts"] - b["ts"] == 2000          # 2ms in integer microseconds


# ---------------------------------------------------------------- metrics --

def test_prometheus_snapshot_exact():
    """Pin the exposition format byte-for-byte on a tiny registry."""
    m = MetricsRegistry(buckets=(100.0, 1000.0))
    m.count("decode_steps", 3)
    m.count("dispatches", kind="decode_step")
    m.count("dispatches", 2, kind="prefill_chunk")
    m.gauge("peak_active", 4)
    m.observe("dispatch_us", 50.0, kind="decode_step")
    m.observe("dispatch_us", 500.0, kind="decode_step")
    m.observe("dispatch_us", 5000.0, kind="decode_step")
    assert m.to_prometheus() == (
        "# HELP repro_decode_steps_total decode_steps (counter)\n"
        "# TYPE repro_decode_steps_total counter\n"
        "repro_decode_steps_total 3\n"
        "# HELP repro_dispatches_total dispatches (counter)\n"
        "# TYPE repro_dispatches_total counter\n"
        'repro_dispatches_total{kind="decode_step"} 1\n'
        'repro_dispatches_total{kind="prefill_chunk"} 2\n'
        "# HELP repro_peak_active peak_active (gauge)\n"
        "# TYPE repro_peak_active gauge\n"
        "repro_peak_active 4\n"
        "# HELP repro_dispatch_us dispatch_us (histogram)\n"
        "# TYPE repro_dispatch_us histogram\n"
        'repro_dispatch_us_bucket{kind="decode_step",le="100"} 1\n'
        'repro_dispatch_us_bucket{kind="decode_step",le="1000"} 2\n'
        'repro_dispatch_us_bucket{kind="decode_step",le="+Inf"} 3\n'
        'repro_dispatch_us_sum{kind="decode_step"} 5550\n'
        'repro_dispatch_us_count{kind="decode_step"} 3\n')


def test_metrics_value_lookup():
    m = MetricsRegistry()
    assert m.value("never_touched") == 0
    m.count("a", 2)
    m.count("a", 3)
    m.gauge("g", 7)
    assert m.value("a") == 5 and m.value("g") == 7


# ------------------------------------------------------------- plan drift --

def test_drift_contradiction_flagged():
    """Two strategies at the same (site, M): flag when the one measured
    fastest is not the one predicted fastest, and only then."""
    d = DriftAggregator()
    d.record("wq", 64, "pad", predicted_us=10.0, observed_us=30.0)
    d.record("wq", 64, "split", predicted_us=20.0, observed_us=15.0)
    rep = d.report()
    assert len(rep["rows"]) == 2 and d.n_decisions == 2
    (c,) = rep["contradictions"]
    assert c["planned"] == "pad" and c["faster"] == "split"
    assert "CONTRADICTION" in d.format_table()

    agree = DriftAggregator()
    agree.record("wq", 64, "pad", predicted_us=10.0, observed_us=12.0)
    agree.record("wq", 64, "split", predicted_us=20.0, observed_us=25.0)
    assert agree.report()["contradictions"] == []
    # a single observed strategy has no ordering to contradict
    solo = DriftAggregator()
    solo.record("wq", 64, "pad", predicted_us=10.0, observed_us=99.0)
    assert solo.report()["contradictions"] == []


def test_drift_rows_cover_every_plan_site(smoke_model):
    """Acceptance criterion: a (site, M, strategy) residual row exists for
    every decision the engine-mode run exercised."""
    cfg, _, params = smoke_model
    pb, tracer, _ = _traced_run(cfg, params, sync="device", window=2,
                                engine_mode="hetero-tensor")
    plan_sites = {s for (s, _) in pb.ctx.plan.decisions}
    rows = tracer.drift.report()["rows"]
    assert {r["site"] for r in rows} == plan_sites
    for r in rows:
        assert r["n"] > 0 and r["predicted_us"] > 0
        assert r["residual_us"] == pytest.approx(
            r["observed_us"] - r["predicted_us"])
    assert "decision rows" in tracer.drift.format_table()


def test_dispatch_prediction_and_nearest_m_lookup(smoke_model):
    from repro.core.engine import build_hetero_ctx, dispatch_prediction
    cfg, _, params = smoke_model
    ctx = build_hetero_ctx(cfg, mode="hetero-tensor")
    plan = ctx.plan
    # nearest-M: an unsolved M resolves to the closest solved one
    (site, some_m), dec = next(iter(plan.decisions.items()))
    assert plan.lookup(site, some_m) is dec
    ms = sorted({m for (s, m) in plan.decisions if s == site})
    nearest = plan.lookup(site, ms[-1] + 10_000)
    assert nearest is plan.decisions[(site, ms[-1])]
    assert plan.lookup("no_such_site", 1) is None
    # predictions: every plan site tagged, count folds in layers and steps
    tags, total = dispatch_prediction(plan, cfg, m=1, steps=4)
    assert {t[0] for t in tags} == {s for (s, _) in plan.decisions}
    for (s, m, strat, t_us, count) in tags:
        assert count == 4 * (1 if s == "head" else cfg.n_layers)
    assert total == pytest.approx(sum(t * c for (_, _, _, t, c) in tags))
    # no plan -> no tags, zero cost (the disabled / xla-mode path)
    assert dispatch_prediction(None, cfg, m=1) == ((), 0.0)


# --------------------------------------------------------- stats contract --

def test_stats_schema_collision_free(smoke_model):
    """The merged AsyncServer.stats() namespace: batcher base keys, prefix
    keys, spec keys and ingress keys never collide, and value types are
    stable — the schema the exposition and reconciliation depend on."""
    base = {"tp", "peak_active", "decode_dispatches", "decode_steps",
            "prefill_dispatches", "fused_steps", "first_tokens",
            "prefill_wait_s", "prefill_host_s", "first_token_hold_s",
            "preemptions", "total_dispatches"}
    prefix = {"prefix_hits", "prefix_tokens_reused", "evictions",
              "cow_copies", "cached_blocks"}
    spec = {"spec_k", "draft_model", "spec_rounds", "drafted_tokens",
            "accepted_tokens", "acceptance_rate", "verify_dispatches",
            "draft_dispatches", "target_dispatches"}
    ingress = {"ingress_ticks", "ingress_preemptions", "ingress_deferrals"}
    for a, b in ((base, prefix), (base, spec), (base, ingress),
                 (prefix, spec), (prefix, ingress), (spec, ingress)):
        assert not (a & b), f"stats key collision: {a & b}"
    # every mirrored counter/gauge key must belong to exactly one group
    mirrored = set(STATS_COUNTER_KEYS) | set(STATS_GAUGE_KEYS)
    assert mirrored <= (base | prefix | spec | ingress)

    cfg, _, params = smoke_model
    pb, _, _ = _traced_run(cfg, params, sync="host", prefix_cache=True)
    s = pb.stats()
    assert base | prefix <= set(s)
    for k, v in s.items():
        assert isinstance(v, (int, float, str, np.integer)), (k, type(v))
        if k in STATS_COUNTER_KEYS or k in STATS_GAUGE_KEYS:
            # counts are integers; the TTFT stage counters are seconds
            want = float if k.endswith("_s") else (int, np.integer)
            assert isinstance(v, want), (k, type(v))


def test_counter_reconciliation_exact(smoke_model):
    """Tracer counters mirror the scheduler's python counters exactly on a
    real run — and a deliberate skew is caught."""
    cfg, _, params = smoke_model
    pb, tracer, _ = _traced_run(cfg, params, sync="device", window=2,
                                prefix_cache=True)
    assert counter_reconciliation(tracer, pb.stats()) == {}
    # B-event counts agree with the dispatch counters, per kind
    by_kind: dict = {}
    for e in tracer.events:
        if e["ph"] == "B" and e.get("cat") == "dispatch":
            by_kind[e["name"]] = by_kind.get(e["name"], 0) + 1
    s = pb.stats()
    assert by_kind.get("prefill_chunk", 0) == s["prefill_dispatches"]
    decode_kinds = ("decode_step", "decode_window", "mixed_step",
                    "mixed_window", "paged_verify")
    assert sum(by_kind.get(k, 0) for k in decode_kinds) \
        == s["decode_dispatches"]
    # a skewed ledger is reported, not hidden
    skewed = dict(s)
    skewed["decode_steps"] += 1
    mism = counter_reconciliation(tracer, skewed)
    assert set(mism) == {"decode_steps"}

"""repolint (repro.analysis): per-rule fixtures, pragma/baseline workflow,
CLI exit codes, and the live-tree-clean self-check.

Fixture violations live in files written to tmp trees, never in this file
itself — the live-tree self-check walks tests/ too.
"""
from __future__ import annotations

import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis.core import (Baseline, load_module, parse_pragmas,
                                 run_repolint)
from repro.analysis.schema import SchemaConfig, StatsSource, \
    check_schema_contract

REPO = Path(__file__).resolve().parents[1]
AST_RULES = ("use-after-donate", "determinism", "jit-hygiene", "host-sync")


def make_tree(tmp_path: Path, files: dict) -> Path:
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return tmp_path


def lint(tmp_path, files, rules=AST_RULES):
    return run_repolint(make_tree(tmp_path, files), rules=rules)


def rules_hit(report):
    return sorted({f.rule for f in report.findings})


# ------------------------------------------------------------ determinism --

def test_determinism_true_positives(tmp_path):
    rep = lint(tmp_path, {"src/a.py": """\
        import time
        import random
        from time import sleep as zz
        import numpy as np
        import datetime

        def f():
            t = time.time()
            zz(0.1)
            r = random.random()
            np.random.seed(0)
            d = datetime.datetime.now()
            return t, r, d
    """}, rules=("determinism",))
    assert len(rep.findings) == 5
    assert rules_hit(rep) == ["determinism"]
    lines = {f.line for f in rep.findings}
    assert lines == {8, 9, 10, 11, 12}


def test_determinism_allowlists(tmp_path):
    rep = lint(tmp_path, {
        # telemetry.py IS the clock: monotonic allowed there, only there
        "src/repro/serving/telemetry.py": """\
            import time
            def now():
                return time.monotonic()
        """,
        # benchmarks measure wall time: perf_counter allowed, sleep not
        "benchmarks/bench_x.py": """\
            import time
            def bench():
                return time.perf_counter()
        """,
        # seeded generators are the sanctioned RNG
        "src/b.py": """\
            import numpy as np
            def g():
                return np.random.default_rng(0).normal()
        """,
    }, rules=("determinism",))
    assert rep.findings == []


def test_determinism_monotonic_banned_elsewhere(tmp_path):
    rep = lint(tmp_path, {"src/c.py": """\
        import time
        def f():
            return time.monotonic()
    """}, rules=("determinism",))
    assert len(rep.findings) == 1


# ------------------------------------------------------- use-after-donate --

def test_use_after_donate_true_positive(tmp_path):
    rep = lint(tmp_path, {"src/d.py": """\
        import jax

        class Sched:
            def __init__(self, fn):
                self._decode = jax.jit(fn, donate_argnums=(1,))

            def step(self, params):
                logits, new_pool = self._decode(params, self.pool)
                return logits, self.pool.shape   # read of donated buffer
    """}, rules=("use-after-donate",))
    assert len(rep.findings) == 1
    f = rep.findings[0]
    assert f.rule == "use-after-donate" and f.line == 9
    assert "self.pool" in f.message


def test_use_after_donate_rebind_is_clean(tmp_path):
    rep = lint(tmp_path, {"src/e.py": """\
        import jax

        class Sched:
            def __init__(self, fn):
                self._decode = jax.jit(fn, donate_argnums=(1,))

            def step(self, params):
                logits, self.pool = self._decode(params, self.pool)
                return logits, self.pool         # rebound: fine
    """}, rules=("use-after-donate",))
    assert rep.findings == []


def test_use_after_donate_loop_carried(tmp_path):
    # donate in iteration N, read (as the call argument) in iteration N+1
    # without a rebind — only visible on the second pass over the loop body
    rep = lint(tmp_path, {"src/f.py": """\
        import jax

        @jax.jit
        def _noop(c):
            return c

        step = jax.jit(_noop, donate_argnums=(0,))

        def run(cache, n):
            out = []
            for _ in range(n):
                logits = step(cache)   # cache never rebound
                out.append(logits)
            return out
    """}, rules=("use-after-donate",))
    assert len(rep.findings) >= 1
    assert all(f.rule == "use-after-donate" for f in rep.findings)


def test_use_after_donate_branch_return_is_clean(tmp_path):
    # the donating call's branch returns: the fall-through path never saw
    # the donation (the core/sync.py paged_decode_window shape)
    rep = lint(tmp_path, {"src/g.py": """\
        import jax

        win = jax.jit(lambda p: p, donate_argnums=(0,))
        mixed = jax.jit(lambda p, q: p, donate_argnums=(0,))

        def dispatch(pool, is_plain, extra):
            if is_plain:
                return win(pool)
            return mixed(pool, extra)
    """}, rules=("use-after-donate",))
    assert rep.findings == []


# ------------------------------------------------------------ jit-hygiene --

def test_jit_hygiene_loop_and_hot_fn(tmp_path):
    rep = lint(tmp_path, {"src/h.py": """\
        import jax

        def run(fns, x):
            for fn in fns:
                y = jax.jit(fn)(x)       # fresh wrapper every iteration
            return y

        class Engine:
            def step(self, x):
                return jax.jit(self.fwd)(x)   # re-jit per step
    """}, rules=("jit-hygiene",))
    assert len(rep.findings) == 2
    assert {f.line for f in rep.findings} == {5, 10}


def test_jit_hygiene_builders_and_tests_exempt(tmp_path):
    rep = lint(tmp_path, {
        "src/i.py": """\
            import jax

            def make_train_step(fn):
                return jax.jit(fn, donate_argnums=(0,))   # built once: fine

            def build_serve_step(fn):
                return jax.jit(fn)
        """,
        "tests/test_i.py": """\
            import jax

            def test_decode_step():
                out = jax.jit(lambda x: x)(1)
        """,
    }, rules=("jit-hygiene",))
    assert rep.findings == []


def test_jit_hygiene_pool_carrying_needs_donation(tmp_path):
    files = {"src/j.py": """\
        import jax

        def paged_decode_step(params, tok, pool):
            return tok, pool

        f = jax.jit(paged_decode_step)
    """}
    rep = lint(tmp_path, files, rules=("jit-hygiene",))
    assert len(rep.findings) == 1
    assert "donate_argnums" in rep.findings[0].message


def test_jit_hygiene_pool_carrying_outside_src_is_clean(tmp_path):
    # same snippet under tests/: jitting once without donation is harmless
    rep = lint(tmp_path, {"tests/j2.py": """\
        import jax

        def paged_decode_step(params, tok, pool):
            return tok, pool

        f = jax.jit(paged_decode_step)
    """}, rules=("jit-hygiene",))
    assert rep.findings == []


# -------------------------------------------------------------- host-sync --

def test_host_sync_block_until_ready_placement(tmp_path):
    rep = lint(tmp_path, {
        "src/k.py": """\
            import jax
            def f(x):
                jax.block_until_ready(x)
        """,
        "src/repro/core/sync.py": """\
            import jax
            def fence(x):
                jax.block_until_ready(x)   # the sanctioned site
        """,
        "benchmarks/bench_k.py": """\
            import jax
            def bench(x):
                jax.block_until_ready(x)
        """,
    }, rules=("host-sync",))
    assert len(rep.findings) == 1
    assert rep.findings[0].path == "src/k.py"


def test_host_sync_traced_body_sinks(tmp_path):
    rep = lint(tmp_path, {"src/l.py": """\
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            y = x + 1
            if y > 0:              # implicit bool() on traced value
                return y
            n = np.asarray(y)      # host pull inside the trace
            return y.item()        # and another
    """}, rules=("host-sync",))
    assert len(rep.findings) == 3


def test_host_sync_shape_branching_is_static(tmp_path):
    rep = lint(tmp_path, {"src/m.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def pad(q):
            D = q.shape[-1]
            if D % 128:            # trace-time static: fine
                q = jnp.pad(q, ((0, 0), (0, 128 - D % 128)))
            assert q.ndim == 2     # also static
            return q
    """}, rules=("host-sync",))
    assert rep.findings == []


def test_host_sync_scan_body_checked(tmp_path):
    rep = lint(tmp_path, {"src/n.py": """\
        import jax

        def body(carry, x):
            if carry:              # traced: lax.scan operand
                x = x + 1
            return carry, x

        def run(xs):
            return jax.lax.scan(body, 0, xs)
    """}, rules=("host-sync",))
    assert len(rep.findings) == 1


# --------------------------------------------------------- schema-contract --

_SCHEMA_CFG = SchemaConfig(
    trace_relpath="src/pkg/trace.py",
    docs_relpath="docs/obs.md",
    sources=(StatsSource("src/pkg/sched.py", "B", "stats", "b",
                         merged=False),),
    snapshot_keys=())

_SCHEMA_TRACE = """\
    STATS_COUNTER_KEYS = ("hits",)
    STATS_GAUGE_KEYS = ("depth",)
"""
_SCHEMA_SCHED = """\
    class B:
        def stats(self):
            return {"hits": self.hits, "depth": self.d}

        def tick(self):
            self.tracer.count("hits")
            self.tracer.gauge("depth", 1)
"""
_SCHEMA_DOCS = """\
    ## Metrics exposition

    - counters: `hits`; plus `dispatches{kind=...}`.
    - gauges: `depth`.
"""


def _schema_findings(tmp_path, files):
    root = make_tree(tmp_path, files)
    modules = [m for m in (load_module(p, root)
                           for p in sorted(root.rglob("*.py"))) if m]
    return check_schema_contract(root, modules, config=_SCHEMA_CFG)


def test_schema_contract_consistent_tree_is_clean(tmp_path):
    assert _schema_findings(tmp_path, {
        "src/pkg/trace.py": _SCHEMA_TRACE,
        "src/pkg/sched.py": _SCHEMA_SCHED,
        "docs/obs.md": _SCHEMA_DOCS}) == []


def test_schema_contract_catches_unregistered_counter(tmp_path):
    sched = _SCHEMA_SCHED.replace(
        'self.tracer.count("hits")',
        'self.tracer.count("hits")\n'
        '            self.tracer.count("misses")')
    found = _schema_findings(tmp_path, {
        "src/pkg/trace.py": _SCHEMA_TRACE,
        "src/pkg/sched.py": sched,
        "docs/obs.md": _SCHEMA_DOCS})
    assert any("misses" in f.message and "STATS_COUNTER_KEYS" in f.message
               for f in found)


def test_schema_contract_catches_stats_key_without_producer(tmp_path):
    trace = _SCHEMA_TRACE.replace('("hits",)', '("hits", "orphan")')
    found = _schema_findings(tmp_path, {
        "src/pkg/trace.py": trace,
        "src/pkg/sched.py": _SCHEMA_SCHED,
        "docs/obs.md": _SCHEMA_DOCS})
    msgs = "\n".join(f.message for f in found)
    assert "orphan" in msgs and "stats()" in msgs


def test_schema_contract_catches_docs_drift(tmp_path):
    docs = _SCHEMA_DOCS.replace("`hits`; plus", "`stale_name`; plus")
    found = _schema_findings(tmp_path, {
        "src/pkg/trace.py": _SCHEMA_TRACE,
        "src/pkg/sched.py": _SCHEMA_SCHED,
        "docs/obs.md": docs})
    msgs = "\n".join(f.message for f in found)
    assert "hits" in msgs and "stale_name" in msgs


def test_schema_contract_checks_span_tables(tmp_path):
    """Sync spans (``cat="sync"``) and host spans are held to their own
    docs tables, in both directions."""
    sched = _SCHEMA_SCHED.replace(
        'self.tracer.gauge("depth", 1)',
        'self.tracer.gauge("depth", 1)\n'
        '            with self.tracer.span("wait", cat="sync"):\n'
        '                pass\n'
        '            with self.tracer.span("prep"):\n'
        '                pass')
    docs = _SCHEMA_DOCS + """
    **Sync spans**

    | name | meaning |
    |---|---|
    | `wait` | the host waits |

    **Host spans**

    | name | meaning |
    |---|---|
    | `prep` | host work |
"""
    files = {"src/pkg/trace.py": _SCHEMA_TRACE, "src/pkg/sched.py": sched}
    assert _schema_findings(tmp_path / "ok",
                            {**files, "docs/obs.md": docs}) == []
    found = _schema_findings(tmp_path / "drift", {
        **files, "docs/obs.md": docs.replace("`wait`", "`stale`")})
    msgs = "\n".join(f.message for f in found)
    assert "sync span 'wait' is emitted but missing" in msgs
    assert "names 'stale' but no code emits it" in msgs
    found = _schema_findings(tmp_path / "host", {
        **files, "docs/obs.md": docs.replace("| `prep` | host work |\n", "")})
    assert any("host span 'prep'" in f.message for f in found)


def test_schema_contract_collision_between_merged_groups(tmp_path):
    cfg = SchemaConfig(
        trace_relpath="src/pkg/trace.py", docs_relpath="docs/obs.md",
        sources=(StatsSource("src/pkg/sched.py", "B", "stats", "b",
                             merged=True),
                 StatsSource("src/pkg/pool.py", "P", "pool_stats", "p",
                             merged=True)),
        snapshot_keys=())
    root = make_tree(tmp_path, {
        "src/pkg/trace.py": _SCHEMA_TRACE,
        "src/pkg/sched.py": _SCHEMA_SCHED,
        "src/pkg/pool.py": """\
            class P:
                def pool_stats(self):
                    return {"hits": 0}     # collides with B.stats
        """,
        "docs/obs.md": _SCHEMA_DOCS})
    modules = [m for m in (load_module(p, root)
                           for p in sorted(root.rglob("*.py"))) if m]
    found = check_schema_contract(root, modules, config=cfg)
    assert any("collides" in f.message for f in found)


# --------------------------------------------------------- pragma workflow --

def test_pragma_suppresses_with_reason(tmp_path):
    rep = lint(tmp_path, {"src/p.py": """\
        import time
        def f():
            return time.time()  # repolint: disable=determinism -- fixture
    """}, rules=("determinism",))
    assert rep.findings == [] and rep.suppressed == 1


def test_pragma_without_reason_is_a_finding(tmp_path):
    rep = lint(tmp_path, {"src/q.py": """\
        import time
        def f():
            return time.time()  # repolint: disable=determinism
    """}, rules=("determinism",))
    # suppression still applies, but the bare pragma itself is flagged
    assert rep.suppressed == 1
    assert [f.rule for f in rep.findings] == ["pragma"]
    assert "no reason" in rep.findings[0].message


def test_unused_and_unknown_pragmas_are_findings(tmp_path):
    rep = lint(tmp_path, {"src/r.py": """\
        x = 1  # repolint: disable=determinism -- suppresses nothing
        y = 2  # repolint: disable=no-such-rule -- typo'd rule name
    """}, rules=("determinism",))
    msgs = sorted(f.message for f in rep.findings)
    assert len(msgs) == 2
    assert any("unused pragma" in m for m in msgs)
    assert any("unknown rule" in m for m in msgs)


def test_pragma_parser():
    pragmas = parse_pragmas([
        "x = 1  # repolint: disable=determinism,host-sync -- two rules",
        "y = 2",
    ])
    assert list(pragmas) == [1]
    assert pragmas[1].rules == ("determinism", "host-sync")
    assert pragmas[1].reason == "two rules"


# ------------------------------------------------------- baseline workflow --

def test_baseline_round_trip(tmp_path):
    files = {"src/s.py": """\
        import time
        def f():
            return time.time()
    """}
    root = make_tree(tmp_path, files)
    rep = run_repolint(root, rules=("determinism",))
    assert len(rep.new) == 1

    bpath = root / "baseline.json"
    Baseline.from_findings(rep.findings).save(bpath)
    rep2 = run_repolint(root, rules=("determinism",),
                        baseline=Baseline.load(bpath))
    assert rep2.ok and rep2.new == [] and rep2.stale == []

    # fingerprints are line-number independent: edits above don't churn
    (root / "src/s.py").write_text(
        "import time\n\n\ndef f():\n    return time.time()\n")
    rep3 = run_repolint(root, rules=("determinism",),
                        baseline=Baseline.load(bpath))
    assert rep3.ok

    # fixing the finding makes the baseline entry stale -> not ok
    (root / "src/s.py").write_text("def f():\n    return 0\n")
    rep4 = run_repolint(root, rules=("determinism",),
                        baseline=Baseline.load(bpath))
    assert not rep4.ok and len(rep4.stale) == 1


# ------------------------------------------------------------------- CLI ---

def _cli(*args):
    return subprocess.run(
        [sys.executable, str(REPO / "scripts" / "repolint.py"), *args],
        capture_output=True, text=True)


def test_cli_exit_codes(tmp_path):
    root = make_tree(tmp_path, {"src/t.py": "def f():\n    return 0\n"})
    clean = _cli("--root", str(root), "--rules", "determinism", "--check")
    assert clean.returncode == 0, clean.stdout + clean.stderr

    (root / "src/t.py").write_text("import time\nT = time.time()\n")
    dirty = _cli("--root", str(root), "--rules", "determinism", "--check")
    assert dirty.returncode == 1
    assert "[determinism]" in dirty.stdout and "FAIL" in dirty.stdout


def test_cli_list_rules():
    out = _cli("--list-rules")
    assert out.returncode == 0
    for rule in ("use-after-donate", "determinism", "jit-hygiene",
                 "host-sync", "schema-contract"):
        assert rule in out.stdout


# --------------------------------------------------------- live-tree gate --

def test_live_tree_is_clean():
    """The committed tree has zero findings and an empty baseline — every
    grandfathered issue was fixed or pragma'd with a reason."""
    report = run_repolint(REPO)
    assert report.findings == [], "\n".join(
        f.render() for f in report.findings)
    assert report.ok
    assert report.n_files > 100   # really walked the tree

    baseline = Baseline.load(REPO / ".repolint-baseline.json")
    assert sum(baseline.counts.values()) == 0

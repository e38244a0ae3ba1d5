"""Per-module AST rules: use-after-donate, determinism, jit-hygiene,
host-sync.

Every rule is intentionally repo-custom: allowlists and name patterns
below encode THIS codebase's conventions (the injectable Clock in
serving/telemetry.py, the sanctioned sync sites in core/sync.py, the
pool-carrying jit entry points of the paged serving stack). Rules are
conservative by construction — they resolve names through the module's
import aliases and track only what can be decided locally, so a clean
report means the discipline provably holds at every site the rule can
see, and a finding is near-certainly real.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Optional

from .core import Module, dotted_name, register_rule, resolve

# =========================================================== determinism ====
#
# Ban ambient wall-clock / RNG outside the injectable Clock. The tier-1
# determinism contract (telemetry.FakeClock, seeded generators) only holds
# if nothing reads the real clock or global RNG state behind its back.
# Allowlist: serving/telemetry.py IS the clock (time.monotonic), and
# benchmarks measure wall time by definition (perf_counter only — never
# sleep). Everything else is a finding: fix (inject a Clock) or pragma
# with a reason.

_DETERMINISM_BANNED = {
    "time.time": (),
    "time.sleep": (),
    "time.monotonic": ("src/repro/serving/telemetry.py",),
    "time.monotonic_ns": ("src/repro/serving/telemetry.py",),
    "time.perf_counter": ("benchmarks/",),
    "time.perf_counter_ns": ("benchmarks/",),
    "datetime.datetime.now": (),
    "datetime.datetime.utcnow": (),
    "datetime.datetime.today": (),
    "datetime.date.today": (),
}
# global-state RNGs: the stdlib random module and numpy's legacy module-
# level API. Seeded generator objects (np.random.default_rng(seed),
# jax.random.PRNGKey) are the sanctioned sources and are NOT flagged.
_LEGACY_NP_RANDOM = {
    "seed", "rand", "randn", "randint", "random", "random_sample", "choice",
    "shuffle", "permutation", "uniform", "normal", "standard_normal",
}


def _allowed(relpath: str, prefixes: tuple) -> bool:
    return any(relpath == p or relpath.startswith(p) for p in prefixes)


@register_rule("determinism")
def check_determinism(mod: Module) -> list:
    findings = []

    def flag(node, name):
        findings.append(mod.finding(
            "determinism", node,
            f"{name} is banned outside the injectable Clock "
            f"(serving/telemetry.py) — thread a Clock through, or pragma "
            f"with a reason"))

    for node in ast.walk(mod.tree):
        name = None
        if isinstance(node, (ast.Attribute, ast.Name)):
            name = resolve(dotted_name(node), mod.aliases)
        if name is None:
            continue
        if name in _DETERMINISM_BANNED:
            if not _allowed(mod.relpath, _DETERMINISM_BANNED[name]):
                flag(node, name)
        elif name.startswith("random.") and name.count(".") == 1:
            flag(node, name)
        elif name.startswith("numpy.random.") \
                and name.rsplit(".", 1)[1] in _LEGACY_NP_RANDOM:
            flag(node, name + " (global-state RNG; use "
                 "np.random.default_rng(seed))")
    # dedupe: a.b inside a.b.c walks both nodes; keep the outermost match
    seen, out = set(), []
    for f in findings:
        key = (f.line, f.message)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


# ======================================================= jit construction ===

_JIT_NAMES = {"jax.jit"}
_SHARD_MAP_NAMES = {
    "jax.experimental.shard_map.shard_map",
    "jax.experimental.shard_map",
    "jax.shard_map",
}
# functions whose first positional arg threads a KV pool / dense cache that
# the serving stack re-binds from the jitted call's return: jitting them
# without donation doubles peak pool memory on real backends
_POOL_CARRYING = {
    "paged_prefill", "paged_decode_step", "paged_verify", "mixed_step",
    "decode_step", "prefill", "prefill_slot", "_cow_copy", "train_step",
}
# functions re-jitted per call churn the trace cache: anything named like a
# per-step/per-tick/per-request entry point must not CONSTRUCT a jit.
# Builder/factory functions (build_*, make_*) construct the jit ONCE by
# design, and a test jitting locally is harmless — both are exempt.
_HOT_FN_RE = re.compile(r"(^_?(step|tick)$)|(_step$)|(_tick$)|(^generate)")
_HOT_FN_EXEMPT = ("build_", "make_", "create_", "test_",
                  "_build_", "_make_", "_create_")


def _is_hot_fn(name: str) -> bool:
    return bool(_HOT_FN_RE.search(name)) \
        and not name.startswith(_HOT_FN_EXEMPT)
# the donation sub-check applies to library code only — a test or bench
# jitting a pool-carrying fn once, without donation, is harmless
_DONATION_CHECK_PREFIXES = ("src/",)


def _jit_callee_name(call: ast.Call, aliases: dict) -> Optional[str]:
    """Best-effort name of what's being jitted: Name, last attr of an
    Attribute, a constant-string Subscript key (``paged_fns["paged_verify"]``),
    or a ``functools.partial(...)``'s first argument, recursively."""
    if not call.args:
        return None
    arg = call.args[0]
    if isinstance(arg, ast.Call) \
            and resolve(dotted_name(arg.func), aliases) == "functools.partial":
        return _jit_callee_name(arg, aliases)
    if isinstance(arg, ast.Name):
        return arg.id
    if isinstance(arg, ast.Attribute):
        return arg.attr
    if isinstance(arg, ast.Subscript):
        sl = arg.slice
        if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
            return sl.value
    return None


def _has_donation(call: ast.Call) -> bool:
    return any(kw.arg in ("donate_argnums", "donate_argnames")
               for kw in call.keywords)


@register_rule("jit-hygiene")
def check_jit_hygiene(mod: Module) -> list:
    findings = []

    class V(ast.NodeVisitor):
        def __init__(self):
            self.loop_depth = 0
            self.fn_stack: list[str] = []

        def visit_For(self, node):
            self._loop(node)

        def visit_While(self, node):
            self._loop(node)

        def _loop(self, node):
            self.loop_depth += 1
            self.generic_visit(node)
            self.loop_depth -= 1

        def visit_FunctionDef(self, node):
            self.fn_stack.append(node.name)
            # decorators evaluate at def time, outside the body
            saved, self.loop_depth = self.loop_depth, self.loop_depth
            for d in node.decorator_list:
                self.visit(d)
            body_saved = self.loop_depth
            for stmt in node.body:
                self.visit(stmt)
            self.loop_depth = saved if body_saved == saved else body_saved
            self.fn_stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Call(self, node):
            name = resolve(dotted_name(node.func), mod.aliases)
            is_jit = name in _JIT_NAMES
            is_smap = name in _SHARD_MAP_NAMES
            if is_jit or is_smap:
                what = "jax.jit" if is_jit else "shard_map"
                if self.loop_depth > 0:
                    findings.append(mod.finding(
                        "jit-hygiene", node,
                        f"{what} constructed inside a loop — every "
                        f"iteration builds a fresh wrapper with its own "
                        f"trace cache (retrace churn); hoist it out"))
                elif self.fn_stack and _is_hot_fn(self.fn_stack[-1]):
                    findings.append(mod.finding(
                        "jit-hygiene", node,
                        f"{what} constructed inside per-call function "
                        f"{self.fn_stack[-1]!r} — re-jitting on every "
                        f"call retraces; cache the jitted callable"))
                if is_jit and not _has_donation(node) \
                        and _allowed(mod.relpath, _DONATION_CHECK_PREFIXES):
                    callee = _jit_callee_name(node, mod.aliases)
                    if callee in _POOL_CARRYING:
                        findings.append(mod.finding(
                            "jit-hygiene", node,
                            f"jax.jit({callee}) without donate_argnums — "
                            f"pool/cache-carrying functions must donate "
                            f"their buffer or peak memory doubles"))
            self.generic_visit(node)

    V().visit(mod.tree)
    return findings


# =========================================================== donated jits ===
#
# Shared collection used by use-after-donate and host-sync: which local
# names are jax.jit-wrapped, and which argument positions they donate.

@dataclass
class JitBindings:
    #: binding name ("step", "self._decode", "_cow_copy") -> donated argnums
    donated: dict = field(default_factory=dict)
    #: names of local FunctionDefs that end up inside a jit (traced bodies)
    traced_fns: dict = field(default_factory=dict)  # name -> static argnames


def _literal_argnums(call: ast.Call) -> tuple:
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            try:
                v = ast.literal_eval(kw.value)
            except ValueError:
                return ()
            return tuple(v) if isinstance(v, (tuple, list)) else (int(v),)
    return ()


def _literal_static_argnames(call: ast.Call) -> tuple:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            try:
                v = ast.literal_eval(kw.value)
            except ValueError:
                return ()
            return tuple(v) if isinstance(v, (tuple, list)) else (str(v),)
    return ()


def _as_jit_call(node: ast.AST, aliases: dict) -> Optional[ast.Call]:
    """The jax.jit(...) Call behind ``jax.jit(...)`` or
    ``partial(jax.jit, ...)`` (decorator form), else None."""
    if not isinstance(node, ast.Call):
        return None
    name = resolve(dotted_name(node.func), aliases)
    if name in _JIT_NAMES:
        return node
    if name == "functools.partial" and node.args:
        if resolve(dotted_name(node.args[0]), aliases) in _JIT_NAMES:
            return node
    return None


def collect_jit_bindings(mod: Module) -> JitBindings:
    jb = JitBindings()

    def record_fn_target(call: ast.Call):
        """If the jitted thing is a local function name (possibly through
        partial), remember its body is traced."""
        name = _jit_callee_name(call, mod.aliases)
        if name:
            jb.traced_fns.setdefault(name, _literal_static_argnames(call))

    for node in ast.walk(mod.tree):
        # decorated defs: @jax.jit (bare), @jax.jit(...) or
        # @partial(jax.jit, donate_argnums=...)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if resolve(dotted_name(dec), mod.aliases) in _JIT_NAMES:
                    jb.traced_fns.setdefault(node.name, ())
                    continue
                call = _as_jit_call(dec, mod.aliases)
                if call is not None:
                    jb.traced_fns.setdefault(
                        node.name, _literal_static_argnames(call))
                    nums = _literal_argnums(call)
                    if nums:
                        jb.donated[node.name] = nums
        # assignments: <target> = jax.jit(fn, donate_argnums=...)
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            call = _as_jit_call(node.value, mod.aliases)
            if call is None or resolve(dotted_name(call.func),
                                       mod.aliases) not in _JIT_NAMES:
                continue
            record_fn_target(call)
            target = dotted_name(node.targets[0])
            if target is None:
                continue
            nums = _literal_argnums(call)
            if nums:
                jb.donated[target] = nums
    return jb


# ======================================================== use-after-donate ==
#
# A donated buffer is dead the moment the jitted call is issued: XLA may
# alias its memory for the output. The serving discipline is rebind-in-the-
# same-statement (``logits, self.kv.pool = self._decode(..., self.kv.pool)``).
# This rule walks each function linearly, marks donated argument names dead
# at the call, clears them on (re)store, and flags any read in between.
# CPU runs mask these bugs (donation is a no-op there) — which is exactly
# why a static rule, not a test, has to hold the line.

def _terminates(stmts: list) -> bool:
    """True if the block's last statement unconditionally leaves it."""
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Break, ast.Continue))


class _DonateWalker:
    def __init__(self, mod: Module, bindings: dict, findings: list):
        self.mod = mod
        self.bindings = bindings    # callable name -> donated argnums
        self.findings = findings
        self.dead: dict[str, tuple] = {}   # name -> (callee, line)

    # ------------------------------------------------------------- events --
    def read(self, name: str, node):
        for dead_name, (callee, line) in self.dead.items():
            if name == dead_name or name.startswith(dead_name + "."):
                self.findings.append(self.mod.finding(
                    "use-after-donate", node,
                    f"{name} is read after being donated to {callee}() at "
                    f"line {line} — the buffer may already be aliased; "
                    f"rebind it from the call's return first"))
                return

    def store(self, name: str):
        for dead_name in list(self.dead):
            if dead_name == name or dead_name.startswith(name + "."):
                del self.dead[dead_name]

    # -------------------------------------------------------- expressions --
    def eval_expr(self, node):
        """Process reads and donating calls in evaluation-ish order."""
        if node is None:
            return
        # only the outermost chain matters; inner Attribute/Name nodes
        # repeat a prefix of the same chain and would double-report
        inner: set = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute):
                v = sub.value
                while isinstance(v, ast.Attribute):
                    inner.add(id(v))
                    v = v.value
                if isinstance(v, ast.Name):
                    inner.add(id(v))
        for sub in ast.walk(node):
            if isinstance(sub, (ast.Name, ast.Attribute)) \
                    and id(sub) not in inner \
                    and isinstance(getattr(sub, "ctx", None), ast.Load):
                name = dotted_name(sub)
                if name:
                    self.read(name, sub)
        # donations fire after the reads they contain
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                callee = dotted_name(sub.func)
                nums = self.bindings.get(callee or "")
                if not nums:
                    continue
                for pos in nums:
                    if pos < len(sub.args):
                        name = dotted_name(sub.args[pos])
                        if name:
                            self.dead[name] = (callee, sub.lineno)

    def store_target(self, t):
        if isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                self.store_target(e)
        elif isinstance(t, ast.Starred):
            self.store_target(t.value)
        elif isinstance(t, (ast.Name, ast.Attribute)):
            name = dotted_name(t)
            if name:
                self.store(name)
        elif isinstance(t, ast.Subscript):
            # storing INTO a container reads the container
            name = dotted_name(t.value)
            if name:
                self.read(name, t)
            self.eval_expr(t.slice)

    # --------------------------------------------------------- statements --
    def exec_block(self, stmts):
        for s in stmts:
            self.exec_stmt(s)

    def exec_stmt(self, s):
        if isinstance(s, ast.Assign):
            self.eval_expr(s.value)
            for t in s.targets:
                self.store_target(t)
        elif isinstance(s, ast.AugAssign):
            self.eval_expr(s.value)
            self.eval_expr(s.target)       # aug-assign reads the target
            self.store_target(s.target)
        elif isinstance(s, ast.AnnAssign):
            self.eval_expr(s.value)
            if s.value is not None:
                self.store_target(s.target)
        elif isinstance(s, (ast.Expr, ast.Return)):
            self.eval_expr(s.value)
        elif isinstance(s, ast.Delete):
            for t in s.targets:
                name = dotted_name(t)
                if name:
                    self.store(name)
        elif isinstance(s, ast.If):
            self.eval_expr(s.test)
            before = dict(self.dead)
            self.exec_block(s.body)
            after_body = self.dead
            self.dead = dict(before)
            self.exec_block(s.orelse)
            # dead after the If when dead on ANY path that falls through —
            # a branch ending in return/raise/break/continue never reaches
            # the statements after the If
            body_falls = not _terminates(s.body)
            else_falls = not s.orelse or not _terminates(s.orelse)
            if body_falls and else_falls:
                self.dead = {**self.dead, **after_body}
            elif body_falls:
                self.dead = after_body
        elif isinstance(s, (ast.For, ast.AsyncFor)):
            self.eval_expr(s.iter)
            self.store_target(s.target)
            before = dict(self.dead)
            # two passes: catch loop-carried use-after-donate (a donate in
            # iteration N read by iteration N+1 without a rebind)
            self.exec_block(s.body)
            self.exec_block(s.body)
            self.exec_block(s.orelse)
            self.dead = {**before, **self.dead}
        elif isinstance(s, ast.While):
            self.eval_expr(s.test)
            before = dict(self.dead)
            self.exec_block(s.body)
            self.exec_block(s.body)
            self.exec_block(s.orelse)
            self.dead = {**before, **self.dead}
        elif isinstance(s, ast.Try):
            self.exec_block(s.body)
            for h in s.handlers:
                self.exec_block(h.body)
            self.exec_block(s.orelse)
            self.exec_block(s.finalbody)
        elif isinstance(s, (ast.With, ast.AsyncWith)):
            for item in s.items:
                self.eval_expr(item.context_expr)
                if item.optional_vars is not None:
                    self.store_target(item.optional_vars)
            self.exec_block(s.body)
        elif isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            pass   # different frame; handled by its own walk
        else:
            for sub in ast.iter_child_nodes(s):
                if isinstance(sub, ast.expr):
                    self.eval_expr(sub)


def _class_self_bindings(cls: ast.ClassDef, mod: Module) -> dict:
    """``self.X = jax.jit(..., donate_argnums=...)`` across all methods."""
    out = {}
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            call = _as_jit_call(node.value, mod.aliases)
            if call is None:
                continue
            target = dotted_name(node.targets[0])
            nums = _literal_argnums(call)
            if target and target.startswith("self.") and nums:
                out[target] = nums
    return out


@register_rule("use-after-donate")
def check_use_after_donate(mod: Module) -> list:
    findings: list = []
    jb = collect_jit_bindings(mod)
    module_bindings = dict(jb.donated)
    # donated functions imported from sibling modules resolve through the
    # alias map at call sites; the registry here stays module-local, so a
    # `from x import f` of a donated f is covered when x is in this repo
    # and f was collected by ITS module walk — cross-module call sites use
    # the local name, which the import maps to the same donated positions.
    # (In this codebase all donated callables are used module-locally or
    # via self-attributes, so local collection is sufficient.)

    def walk_function(fn, bindings):
        w = _DonateWalker(mod, bindings, findings)
        w.exec_block(fn.body)

    for node in mod.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            walk_function(node, module_bindings)
            for sub in ast.walk(node):
                if sub is not node and isinstance(
                        sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    # nested defs see module + their own enclosing bindings
                    walk_function(sub, module_bindings)
        elif isinstance(node, ast.ClassDef):
            self_bindings = _class_self_bindings(node, mod)
            for meth in node.body:
                if isinstance(meth, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    local = dict(module_bindings)
                    local.update(self_bindings)
                    # plus any function-local `f = jax.jit(...)` bindings
                    for sub in ast.walk(meth):
                        if isinstance(sub, ast.Assign) \
                                and len(sub.targets) == 1:
                            call = _as_jit_call(sub.value, mod.aliases)
                            if call is None:
                                continue
                            t = dotted_name(sub.targets[0])
                            nums = _literal_argnums(call)
                            if t and nums and not t.startswith("self."):
                                local[t] = nums
                    walk_function(meth, local)
    # function-local bindings for module-level functions
    for node in mod.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            local = {}
            for sub in ast.walk(node):
                if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
                    call = _as_jit_call(sub.value, mod.aliases)
                    if call is not None:
                        t = dotted_name(sub.targets[0])
                        nums = _literal_argnums(call)
                        if t and nums:
                            local[t] = nums
            if local:
                w = _DonateWalker(mod, local, findings)
                w.exec_block(node.body)
    # dedupe (module-level defs are walked with module bindings AND local
    # bindings; identical findings collapse)
    seen, out = set(), []
    for f in findings:
        key = (f.line, f.message)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


# =============================================================== host-sync ==
#
# The paper's thesis: unplanned host<->device synchronization points are
# where heterogeneous engines lose. Two checks:
#   1. block_until_ready is only legal at the sanctioned sync sites
#      (core/sync.py — the module whose JOB is synchronization) and in
#      benchmarks (which time against the device by definition).
#   2. Inside traced bodies (functions that end up under jax.jit, and
#      closures handed to lax control flow), pulling a traced value to the
#      host — .item(), np.asarray/np.array, bool()/int()/float(),
#      jax.device_get, or branching on it — either crashes at trace time
#      or silently pins a sync point into the hot loop.

_BLOCK_ALLOWED = ("src/repro/core/sync.py", "benchmarks/")
_NP_SINKS = {"numpy.asarray", "numpy.array", "numpy.asanyarray"}
_LAX_CONTROL = {
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map", "jax.checkpoint",
    "jax.remat", "jax.vmap", "jax.grad", "jax.value_and_grad",
}


def _collect_traced_defs(mod: Module) -> dict:
    """name -> static argnames, for every local def whose body is traced:
    jit-decorated, jit-bound, or passed to lax control flow."""
    traced = dict(collect_jit_bindings(mod).traced_fns)
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            name = resolve(dotted_name(node.func), mod.aliases)
            if name in _LAX_CONTROL:
                for arg in node.args:
                    an = dotted_name(arg)
                    if an and "." not in an:
                        traced.setdefault(an, ())
    return traced


class _TaintChecker:
    """Flag host-sync sinks on values tainted by a traced function's
    (non-static) parameters."""

    def __init__(self, mod: Module, findings: list):
        self.mod = mod
        self.findings = findings

    def check(self, fn, static: tuple):
        tainted = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                   + fn.args.kwonlyargs)
                   if a.arg not in static and a.arg != "self"}
        self._walk_body(fn, tainted)

    # trace-time-static attributes: reading x.shape / x.ndim of a traced
    # array yields a python value, not a traced one — no sync involved
    _STATIC_ATTRS = ("shape", "ndim", "dtype", "size", "sharding")

    def _is_tainted(self, expr, tainted) -> bool:
        if isinstance(expr, ast.Attribute) \
                and expr.attr in self._STATIC_ATTRS:
            return False
        if isinstance(expr, ast.Call) and isinstance(expr.func, ast.Name) \
                and expr.func.id == "len":
            return False      # len(x) of a traced array is static too
        if isinstance(expr, ast.Name):
            return expr.id in tainted
        return any(self._is_tainted(sub, tainted)
                   for sub in ast.iter_child_nodes(expr))

    def _flag(self, node, what):
        self.findings.append(self.mod.finding(
            "host-sync", node,
            f"{what} on a traced value inside a jitted/scanned body — "
            f"this is a host synchronization point in the hot loop "
            f"(or a trace-time crash)"))

    def _walk_body(self, fn, tainted):
        sinks_builtin = {"bool", "int", "float"}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                if self._is_tainted(node.value, tainted):
                    for t in node.targets:
                        for sub in ast.walk(t):
                            if isinstance(sub, ast.Name):
                                tainted.add(sub.id)
            elif isinstance(node, (ast.For,)):
                if self._is_tainted(node.iter, tainted):
                    for sub in ast.walk(node.target):
                        if isinstance(sub, ast.Name):
                            tainted.add(sub.id)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                rname = resolve(dotted_name(node.func), self.mod.aliases)
                if rname in _NP_SINKS or rname == "jax.device_get":
                    if any(self._is_tainted(a, tainted) for a in node.args):
                        self._flag(node, rname)
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item" and not node.args:
                    if self._is_tainted(node.func.value, tainted):
                        self._flag(node, ".item()")
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in sinks_builtin \
                        and node.func.id not in tainted:
                    if any(self._is_tainted(a, tainted) for a in node.args):
                        self._flag(node, f"{node.func.id}()")
            elif isinstance(node, (ast.If, ast.While)):
                if self._is_tainted(node.test, tainted):
                    self._flag(node, "branching (implicit bool())")
            elif isinstance(node, ast.Assert):
                if self._is_tainted(node.test, tainted):
                    self._flag(node, "assert (implicit bool())")


@register_rule("host-sync")
def check_host_sync(mod: Module) -> list:
    findings: list = []
    # 1. block_until_ready outside the sanctioned sync sites
    if not _allowed(mod.relpath, _BLOCK_ALLOWED):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute) \
                    and node.attr == "block_until_ready":
                findings.append(mod.finding(
                    "host-sync", node,
                    "block_until_ready outside core/sync.py and "
                    "benchmarks/ — route the sync through core.sync "
                    "(e.g. fence()) so sync points stay auditable"))
    # 2. host pulls inside traced bodies
    traced = _collect_traced_defs(mod)
    checker = _TaintChecker(mod, findings)
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in traced:
            checker.check(node, tuple(traced[node.name]))
        elif isinstance(node, ast.Lambda):
            pass   # lambda bodies are expressions; sinks there are rare
    # jit-decorated defs not caught by name (decorator form records by name
    # too, so nothing extra to do)
    seen, out = set(), []
    for f in findings:
        key = (f.line, f.message)
        if key not in seen:
            seen.add(key)
            out.append(f)
    return out


# re-export for schema.py / tests
_allowed_paths = _allowed

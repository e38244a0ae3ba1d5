"""Distribution tests on an 8-host-device mesh: sharding rules, small-mesh
compiles, pipeline parallelism, gradient compression."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_smoke_config
from repro.distributed.compression import (compress_grads_with_feedback,
                                           compressed_psum, init_error)
from repro.distributed.sharding import (batch_sharding, cache_specs,
                                        param_specs, sanitize_spec)
from jax import shard_map
from jax.sharding import set_mesh
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_step_and_specs
from repro.models import build_model

RNG = jax.random.PRNGKey(0)


def small_mesh():
    return make_host_mesh(2, 4)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_specs_cover_all_leaves(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(RNG))
    mesh = small_mesh()
    specs = param_specs(shapes, mesh)
    flat_shapes = jax.tree.leaves(shapes)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    # every spec valid for its leaf (divisibility sanitized)
    for s, sp in zip(flat_shapes, flat_specs):
        for dim, ax in zip(s.shape, list(sp)):
            if ax is not None:
                n = np.prod([mesh.shape[a] for a in
                             ((ax,) if isinstance(ax, str) else ax)])
                assert dim % n == 0


def test_sanitize_spec():
    mesh = small_mesh()        # model axis = 4
    assert sanitize_spec(P("model"), (503,), mesh) == P()       # 503 % 4 != 0
    assert sanitize_spec(P("model"), (512,), mesh) == P("model")
    assert sanitize_spec(P(("data",), "model"), (1, 8), mesh) == P(None, "model")


def _compile_cells():
    """Supported (arch, shape) cells only — the support gate is static
    config knowledge (e.g. encoder-only hubert has no decode shapes), so
    unsupported combinations are excluded at collection instead of
    producing perpetual runtime skips. ``test_cell_support_gate`` pins the
    gate itself."""
    from repro.configs.base import cell_is_supported
    cells = []
    for arch in ("llama3-8b", "qwen2-moe-a2.7b", "zamba2-2.7b", "rwkv6-3b",
                 "hubert-xlarge"):
        for shape_name in ("train_4k", "decode_32k"):
            cfg = get_smoke_config(arch)
            shape = dataclasses.replace(SHAPES[shape_name], seq_len=64,
                                        global_batch=4)
            if cell_is_supported(cfg, shape)[0]:
                cells.append((arch, shape_name))
    return cells


def test_cell_support_gate():
    """The only gated-out compile cell is encoder-only hubert x decode
    (no autoregressive path to compile) — if the gate widens, the compile
    grid above must be revisited, so pin it."""
    from repro.configs.base import cell_is_supported
    cells = _compile_cells()
    assert ("hubert-xlarge", "decode_32k") not in cells
    assert len(cells) == 9
    ok, reason = cell_is_supported(
        get_smoke_config("hubert-xlarge"),
        dataclasses.replace(SHAPES["decode_32k"], seq_len=64, global_batch=4))
    assert not ok and reason


@pytest.mark.parametrize("arch,shape_name", _compile_cells())
def test_small_mesh_compile(arch, shape_name):
    """The dry-run pipeline end-to-end on a 2x4 host mesh, reduced shapes."""
    from repro.distributed.sharding import activation_sharding
    cfg = get_smoke_config(arch)
    shape = dataclasses.replace(SHAPES[shape_name], seq_len=64, global_batch=4)
    mesh = small_mesh()
    with set_mesh(mesh):
        jf, args, act_spec = make_step_and_specs(cfg, mesh, shape)
        with activation_sharding(act_spec):
            compiled = jf.lower(*args).compile()
    assert compiled.cost_analysis() is not None


def test_pipeline_parallel_matches_serial():
    from repro.distributed.pipeline import make_pipeline_forward
    mesh = jax.make_mesh((4,), ("stage",))
    n_stages, n_micro, mb, d = 4, 8, 2, 16
    ks = jax.random.split(RNG, 2)
    Ws = jax.random.normal(ks[0], (n_stages, 1, d, d)) / np.sqrt(d)
    x = jax.random.normal(ks[1], (n_micro, mb, d))

    def layer_fn(w, h):
        return jnp.tanh(h @ w[0])

    pipe = make_pipeline_forward(layer_fn, n_stages, n_micro, mesh)
    with set_mesh(mesh):
        y = pipe(Ws, x)
    ref = x
    for s in range(n_stages):
        ref = jnp.tanh(ref @ Ws[s, 0])
    assert float(jnp.max(jnp.abs(y - ref))) < 1e-5


def test_compression_error_feedback_unbiased():
    """EF carries the residual: sum of compressed grads -> sum of true grads."""
    g = jax.random.normal(RNG, (256,)) * 0.01
    err = jnp.zeros((256,))
    acc_c = jnp.zeros((256,))
    for i in range(50):
        comp, err = compress_grads_with_feedback({"g": g}, {"g": err["g"] if
                                                 isinstance(err, dict) else err})
        err = err["g"]
        acc_c = acc_c + comp["g"]
    acc_true = 50 * g
    rel = float(jnp.linalg.norm(acc_c - acc_true) / jnp.linalg.norm(acc_true))
    assert rel < 0.01        # residual bounded, not accumulating


def test_compressed_psum_close_to_exact():
    mesh = jax.make_mesh((8,), ("d",))
    x = jax.random.normal(RNG, (8, 128))

    @jax.jit
    def f(x):
        return shard_map(lambda xs: compressed_psum(xs, "d"),
                             mesh=mesh, in_specs=P("d"),
                             out_specs=P("d"))(x)
    with set_mesh(mesh):
        y = f(x)
    exact = jnp.broadcast_to(x.sum(0, keepdims=True), x.shape)
    rel = float(jnp.max(jnp.abs(y - exact)) / (jnp.max(jnp.abs(exact)) + 1e-9))
    assert rel < 0.05        # int8 quantized reduction


def test_split_kv_decode_matches_oracle():
    """Mesh split-KV flash-decoding == single-device decode oracle."""
    from repro.distributed.split_kv import split_kv_decode_update_attend
    from repro.kernels.decode_attention.ref import decode_attention_ref
    mesh = small_mesh()
    B, Smax, Hq, Hkv, D = 4, 64, 8, 2, 16
    ks = jax.random.split(RNG, 5)
    q = jax.random.normal(ks[0], (B, 1, Hq, D), jnp.float32)
    kn = jax.random.normal(ks[1], (B, 1, Hkv, D), jnp.float32)
    vn = jax.random.normal(ks[2], (B, 1, Hkv, D), jnp.float32)
    kc = jax.random.normal(ks[3], (B, Smax, Hkv, D), jnp.float32)
    vc = jax.random.normal(ks[4], (B, Smax, Hkv, D), jnp.float32)
    jf = jax.jit(split_kv_decode_update_attend)   # hoisted: one trace cache
    for pos in (0, 15, 16, 37, 63):      # includes shard boundaries
        idx = jnp.asarray(pos, jnp.int32)
        with set_mesh(mesh):
            out, ck, cv = jf(q, kn, vn, kc, vc, idx)
        kc2 = kc.at[:, pos].set(kn[:, 0])
        vc2 = vc.at[:, pos].set(vn[:, 0])
        ref = decode_attention_ref(q[:, 0], kc2, vc2, pos + 1)
        assert float(jnp.abs(out[:, 0] - ref).max()) < 1e-5, pos
        assert float(jnp.abs(np.asarray(ck) - np.asarray(kc2)).max()) == 0.0


def test_split_kv_indivisible_smax_raises():
    """Regression: Smax not divisible by the model-axis size used to
    silently floor-divide — the trailing ``Smax % n_shards`` slots were
    never attended over and writes to them were dropped. Must raise."""
    from repro.distributed.split_kv import split_kv_decode_update_attend
    mesh = small_mesh()                       # model axis = 4
    B, Smax, Hq, Hkv, D = 4, 66, 8, 2, 16    # 66 % 4 == 2
    ks = jax.random.split(RNG, 5)
    q = jax.random.normal(ks[0], (B, 1, Hq, D), jnp.float32)
    kn = jax.random.normal(ks[1], (B, 1, Hkv, D), jnp.float32)
    vn = jax.random.normal(ks[2], (B, 1, Hkv, D), jnp.float32)
    kc = jax.random.normal(ks[3], (B, Smax, Hkv, D), jnp.float32)
    vc = jax.random.normal(ks[4], (B, Smax, Hkv, D), jnp.float32)
    with set_mesh(mesh):
        with pytest.raises(ValueError, match="not divisible"):
            split_kv_decode_update_attend(q, kn, vn, kc, vc,
                                          jnp.asarray(65, jnp.int32))


def test_combine_split_softmax_matches_dense_on_ragged_lengths():
    """The split-softmax combine == one dense softmax-weighted sum, on
    RAGGED lengths: per-batch valid prefixes that straddle shard
    boundaries, including a length-1 row whose non-owner shards see
    all-NEG_INF scores (their partials must contribute exactly zero)."""
    from repro.distributed.split_kv import NEG_INF, combine_split_softmax
    B, K, Hkv, G, D = 3, 48, 2, 4, 16
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, K, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, K, Hkv, D), jnp.float32)
    lengths = jnp.asarray([1, 17, 48])       # ragged; 17 straddles K/4=12
    s = jnp.einsum("bhgd,bkhd->bhgk", q, k)
    mask = jnp.arange(K)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    ref = jnp.einsum("bhgk,bkhd->bhgd", jax.nn.softmax(s, axis=-1), v)

    # axis_name=None: the collectives degenerate to identity
    local = combine_split_softmax(s, v)
    assert float(jnp.abs(local - ref).max()) < 1e-5

    # K split over a 4-wide model axis: partials combine across shards
    mesh = jax.make_mesh((4,), ("model",))
    sharded = shard_map(
        lambda sl, vl: combine_split_softmax(sl, vl, "model"),
        mesh=mesh,
        in_specs=(P(None, None, None, "model"), P(None, "model")),
        out_specs=P(), check_vma=False)(s, v)
    assert float(jnp.abs(sharded - ref).max()) < 1e-5


def test_sanitize_spec_warns_and_reports_dropped_dims():
    """Regression: sanitize_spec silently replaced an intended shard with
    full replication (a capacity bug at scale — e.g. vocab=504 or
    n_kv_heads=8 on a 16-wide model axis). It must warn once per distinct
    drop and report the replicated dim indices through ``dropped``."""
    import types
    import warnings
    from repro.distributed.sharding import (ShardingDropWarning,
                                            _SANITIZE_WARNED)
    # sanitize_spec only reads mesh.shape[axis]; a 16-wide stub exercises
    # the axis widths the 8-device test pool cannot build
    mesh16 = types.SimpleNamespace(shape={"data": 1, "model": 16})
    _SANITIZE_WARNED.clear()
    dropped = []
    with pytest.warns(ShardingDropWarning, match="REPLICATE"):
        spec = sanitize_spec(P("model"), (504,), mesh16, dropped=dropped)
    assert spec == P() and dropped == [0]    # 504 % 16 != 0 -> replicated
    # one-time: the SAME drop does not warn again (no per-step log spam)
    with warnings.catch_warnings():
        warnings.simplefilter("error", ShardingDropWarning)
        assert sanitize_spec(P("model"), (504,), mesh16) == P()
    # mixed spec: only the indivisible KV-head dim (8 % 16) drops, and the
    # caller is told exactly which one
    _SANITIZE_WARNED.clear()
    dropped = []
    with pytest.warns(ShardingDropWarning):
        spec = sanitize_spec(P(None, None, None, "model", None),
                             (2, 4, 16, 8, 32), mesh16, dropped=dropped)
    assert dropped == [3] and spec == P()
    # divisible dims keep their sharding and report nothing
    dropped = []
    assert sanitize_spec(P("model"), (512,), mesh16,
                         dropped=dropped) == P("model")
    assert dropped == []


def test_sharding_contexts_isolated_across_interleaved_streams():
    """Regression: ``activation_sharding`` / ``split_kv_enabled`` are
    contextvar-backed, so two logically-concurrent streams (e.g. a TP
    serving thread next to a training trace) interleaved in any order each
    observe ONLY their own setting — a module-global flag would leak the
    last writer's value across both."""
    import contextvars
    from repro.distributed.sharding import (_ACT_SPEC, activation_sharding,
                                            split_kv_active, split_kv_enabled)
    spec_a, spec_b = P("data"), P("model")
    ctx_a, ctx_b = contextvars.copy_context(), contextvars.copy_context()

    def enter(cm):
        cm.__enter__()
        return cm

    # interleave: A enters, B enters different values, both re-checked
    a_act = ctx_a.run(enter, activation_sharding(spec_a))
    assert ctx_b.run(_ACT_SPEC.get) is None          # B unaffected by A
    ctx_b.run(enter, activation_sharding(spec_b))
    b_kv = ctx_b.run(enter, split_kv_enabled(True))
    assert ctx_a.run(_ACT_SPEC.get) == spec_a        # A keeps its own
    assert ctx_b.run(_ACT_SPEC.get) == spec_b
    assert ctx_a.run(split_kv_active) is False       # B's split-KV private
    assert ctx_b.run(split_kv_active) is True
    # A exits while B is still inside: B's values must survive
    ctx_a.run(a_act.__exit__, None, None, None)
    assert ctx_a.run(_ACT_SPEC.get) is None
    assert ctx_b.run(_ACT_SPEC.get) == spec_b
    assert ctx_b.run(split_kv_active) is True
    ctx_b.run(b_kv.__exit__, None, None, None)
    assert ctx_b.run(split_kv_active) is False
    # this test's contexts are copies: the suite's root context untouched
    assert _ACT_SPEC.get() is None and split_kv_active() is False

"""Compiles of the chip's main path for a described TPU v5e, without a chip.

The TPU compiler is installed with libtpu and compiles for a topology that
is described, not attached. These tests compile, at Qwen3-1.7B widths:

  * each MXU-path Pallas kernel (bf16, int8, W4A16)
    with ``interpret=False``, asserting the Mosaic kernel
    (``tpu_custom_call``) is in the program;
  * the full-width ``paged_decode_step`` and the solver-planned
    (``hetero-tensor``) ``paged_prefill`` from ``jax.eval_shape`` shapes,
    asserting the program fits one v5e's 16 GB;
  * the benchmark's two largest step programs at their deployments (the
    fused 8-step decode window of Qwen3-1.7B and a 256-token InternLM2
    prefill chunk), asserting the pool is carried through the layer scan:
    no whole-pool ``copy`` and a small bound on temporaries.

A compile that passes is not a chip run: it says nothing about results or
times. The topology is described inside a module fixture, never at import:
only one process at a time may load libtpu, and every test worker imports
this file.
"""
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.hetero_matmul.ops import (mxu_matmul, mxu_q4_matmul,
                                             mxu_quant_matmul)
from repro.models import build_model

V5E_HBM_BYTES = 16 * 10**9
M = 256                                     # a prefill chunk of tokens
NUM_BLOCKS, BLOCK_SIZE, LANES = 1024, 32, 8
MAX_BLOCKS_PER_SEQ = 35                     # 1,097 tokens of 32-token blocks
QWEN3_SITES = {"qkv_o": (2048, 6144), "down": (6144, 2048),
               "head": (2048, 151936)}


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # no libtpu: nothing to compile
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for the described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_call(kind, K, N, sh):
    """(jitted kernel wrapper, shape args)."""
    x = _sds((M, K), jnp.bfloat16, sh)
    if kind == "bf16":
        return mxu_matmul, (x, _sds((K, N), jnp.bfloat16, sh))
    scale = _sds((N,), jnp.float32, sh)
    if kind == "int8":
        return mxu_quant_matmul, (x, _sds((K, N), jnp.int8, sh), scale)
    return mxu_q4_matmul, (x, _sds((K // 2, N), jnp.int8, sh), scale)


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes
            + ma.generated_code_size_in_bytes)
    assert peak < V5E_HBM_BYTES, f"{peak / 1e9:.2f} GB does not fit"
    return peak


@pytest.mark.parametrize("site", sorted(QWEN3_SITES))
@pytest.mark.parametrize("kind", ["bf16", "int8", "w4a16"])
def test_mxu_kernel_compiles_for_v5e(one_chip, kind, site):
    K, N = QWEN3_SITES[site]
    fn, args = _kernel_call(kind, K, N, one_chip)
    compiled = fn.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _model_shapes(sh):
    cfg = get_config("qwen3-1.7b")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = jax.eval_shape(partial(model.init_paged_cache,
                                  num_blocks=NUM_BLOCKS,
                                  block_size=BLOCK_SIZE))
    place = lambda t: jax.tree.map(lambda a: _sds(a.shape, a.dtype, sh), t)
    return cfg, model, place(params), place(pool)


def test_paged_decode_step_fits_v5e(one_chip):
    cfg, model, params, pool = _model_shapes(one_chip)
    i32 = partial(_sds, dtype=jnp.int32, sharding=one_chip)
    step = jax.jit(model.paged_decode_step, donate_argnums=(2,))
    compiled = step.lower(params, i32((LANES, 1)), pool,
                          block_tables=i32((LANES, MAX_BLOCKS_PER_SEQ)),
                          lengths=i32((LANES,))).compile()
    _fits(compiled)


def test_hetero_tensor_paged_prefill_fits_v5e(one_chip, monkeypatch):
    import repro.kernels
    from repro.core.engine import build_hetero_ctx
    # the kernels work interpret out from the platform, which here is the
    # CPU: steer them to the chip the program is compiled for
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    cfg, model, params, pool = _model_shapes(one_chip)
    ctx = build_hetero_ctx(cfg, "hetero-tensor", sync_mode="fast")
    i32 = partial(_sds, dtype=jnp.int32, sharding=one_chip)
    prefill = jax.jit(partial(model.paged_prefill, hetero_ctx=ctx),
                      donate_argnums=(2,))
    compiled = prefill.lower(params, i32((1, 128)), pool,
                             block_table=i32((1, MAX_BLOCKS_PER_SEQ)),
                             start_index=i32(())).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def _pool_copies(compiled, pool) -> list:
    """HLO ``copy`` instructions whose result has the pool leaf's shape."""
    dims = ",".join(map(str, pool["k"].shape))
    pat = re.compile(rf"= \w+\[{dims}\]\{{[^}}]*\}} copy(?:-start)?\(")
    return pat.findall(compiled.as_text())


def _deployment(name, num_blocks, sh):
    cfg = get_config(name)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = jax.eval_shape(partial(model.init_paged_cache,
                                  num_blocks=num_blocks, block_size=32))
    place = lambda t: jax.tree.map(lambda a: _sds(a.shape, a.dtype, sh), t)
    return model, place(params), place(pool)


def test_decode_window_carries_pool_v5e(one_chip):
    """The chat cell's fused window: 16 lanes, an 80-block table, 1,281
    blocks, 8 steps. A pool scanned as the layer scan's xs/ys was copied
    whole every step and took 6.25 GB of temporaries."""
    from repro.core.sync import _paged_window
    model, params, pool = _deployment("qwen3-1.7b", 1281, one_chip)
    i32 = partial(_sds, dtype=jnp.int32, sharding=one_chip)
    lanes = i32((16,))
    compiled = _paged_window.lower(
        params, i32((16, 1)), pool, i32((16, 80)), lanes, lanes,
        _sds((8, 2), jnp.uint32, one_chip),
        decode_step=model.paged_decode_step, n_steps=8, sampler=None,
        eos_id=None).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.5e9, f"{temp / 1e9:.2f} GB of temporaries"
    assert not _pool_copies(compiled, pool)
    _fits(compiled)


def test_prefill_chunk_carries_pool_v5e(one_chip):
    """The code cell's 256-token prefill chunk: a 130-block table over
    1,344 blocks. Scanned as xs/ys, the pool took 4.45 GB of temporaries."""
    model, params, pool = _deployment("internlm-1.8b", 1344, one_chip)
    i32 = partial(_sds, dtype=jnp.int32, sharding=one_chip)
    prefill = jax.jit(model.paged_prefill, donate_argnums=(2,))
    compiled = prefill.lower(params, i32((1, 256)), pool,
                             block_table=i32((1, 130)),
                             start_index=i32(())).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.5e9, f"{temp / 1e9:.2f} GB of temporaries"
    assert not _pool_copies(compiled, pool)
    _fits(compiled)

"""MXU-path blocked matmul — the TPU analogue of the paper's NPU engine.

Pallas TPU kernels with explicit BlockSpec VMEM tiling, all output-stationary:
grid (m, n, k), k innermost, the fp32 accumulator lives in a VMEM scratch
and each output block is written once. (A weight-stationary order, grid
(n, k, m), revisits each output block once per k step with other blocks in
between; Pallas on TPU writes a block back when its index changes and never
reads it again, so its partial sums are lost on the chip. The paper's
order sensitivity lives in the analytic cost model, core/characteristics.py.)

Weight-only quantization (the paper's W4A16 stance): int8 weights + per-column
fp32 scales are dequantized tile-by-tile in VMEM; activations stay bf16/f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


DEFAULT_BM, DEFAULT_BK, DEFAULT_BN = 128, 128, 128


def _mm_kernel_output_stationary(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    """grid (m, n, k); acc scratch in VMEM; single output visit."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_kernel_quant(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk: int):
    """Output-stationary int8-weight matmul with in-VMEM dequant."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(x: jax.Array, w: jax.Array, *,
                  bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                  bn: int = DEFAULT_BN, out_dtype=None,
                  interpret: bool | None = None) -> jax.Array:
    """x [M,K] @ w [K,N]. Dims must be multiples of the block sizes — this is
    the 'static graph' constraint of the MXU path (the NPU analogue); the
    HeteroInfer engine routes misaligned remainders to the XLA path instead."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, \
        f"misaligned ({M},{K},{N}) for blocks ({bm},{bk},{bn})"
    out_dtype = out_dtype or x.dtype
    nk = K // bk
    return pl.pallas_call(
        functools.partial(_mm_kernel_output_stationary, nk=nk),
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, w)


def _mm_kernel_q4(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk: int):
    """Output-stationary W4A16 matmul: two int4 weights packed per int8
    byte along K (the paper's storage format); nibbles are unpacked and
    dequantized in VMEM, activations stay high-precision.

    Unpacking runs in int32 (Mosaic has no int8 shifts): the sign-extended
    byte shifted left 28 then arithmetically right 28 is the low nibble,
    shifted right 4 the high one. The nibbles are stacked [lo; hi] along
    K rather than interleaved, and ``q4_matmul_pallas`` permutes each
    activation block's columns to the same [even; odd] order."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    packed = w_ref[...].astype(jnp.int32)                # [bk//2, bn]
    lo = jnp.right_shift(jnp.left_shift(packed, 28), 28)  # sign-extended low
    hi = jnp.right_shift(packed, 4)                      # arithmetic high
    w = jnp.concatenate([lo, hi], axis=0).astype(jnp.float32)
    w = w * s_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def q4_matmul_pallas(x: jax.Array, wq4: jax.Array, scale: jax.Array, *,
                     bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                     bn: int = DEFAULT_BN, out_dtype=None,
                     interpret: bool | None = None) -> jax.Array:
    """x [M,K] @ dequant(wq4 int8-packed [K//2,N], scale f32 [N]).
    K-order inside wq4: row r holds original rows (2r, 2r+1) as (lo, hi)."""
    M, K = x.shape
    K2, N = wq4.shape
    assert K == 2 * K2
    assert M % bm == 0 and K % bk == 0 and N % bn == 0 and bk % 2 == 0
    out_dtype = out_dtype or x.dtype
    nk = K // bk
    # within each K block, even columns first then odd ones: the order in
    # which the kernel stacks the unpacked [lo; hi] weight rows
    xp = x.reshape(M, nk, bk // 2, 2).swapaxes(2, 3).reshape(M, K)
    return pl.pallas_call(
        functools.partial(_mm_kernel_q4, nk=nk),
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk // 2, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(xp, wq4, scale[None, :])


def quant_matmul_pallas(x: jax.Array, wq: jax.Array, scale: jax.Array, *,
                        bm: int = DEFAULT_BM, bk: int = DEFAULT_BK,
                        bn: int = DEFAULT_BN, out_dtype=None,
                        interpret: bool | None = None) -> jax.Array:
    """x [M,K] @ dequant(wq int8 [K,N], scale f32 [N])."""
    M, K = x.shape
    _, N = wq.shape
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    out_dtype = out_dtype or x.dtype
    nk = K // bk
    return pl.pallas_call(
        functools.partial(_mm_kernel_quant, nk=nk),
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, wq, scale[None, :])

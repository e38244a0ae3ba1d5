"""jit'd public wrappers for the MXU-path matmul (batch-flattening, dtype policy)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import matmul_pallas, quant_matmul_pallas


def _flatten_leading(x):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


@partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def mxu_matmul(x: jax.Array, w: jax.Array, *, bm=128, bk=128, bn=128,
               interpret: bool | None = None) -> jax.Array:
    """[..., K] @ [K, N] on the aligned MXU path. Shapes must be aligned."""
    x2, lead = _flatten_leading(x)
    y = matmul_pallas(x2, w, bm=bm, bk=bk, bn=bn, out_dtype=x.dtype,
                      interpret=interpret)
    return y.reshape(*lead, w.shape[-1])


@partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def mxu_quant_matmul(x: jax.Array, wq: jax.Array, scale: jax.Array, *,
                     bm=128, bk=128, bn=128,
                     interpret: bool | None = None) -> jax.Array:
    x2, lead = _flatten_leading(x)
    y = quant_matmul_pallas(x2, wq, scale, bm=bm, bk=bk, bn=bn,
                            out_dtype=x.dtype, interpret=interpret)
    return y.reshape(*lead, wq.shape[-1])


def quantize_weight(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-output-channel symmetric int8 weight quantization (W8A16-style)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    wq = jnp.clip(jnp.round(w.astype(jnp.float32) / scale[None, :]),
                  -127, 127).astype(jnp.int8)
    return wq, scale.astype(jnp.float32)


def quantize_weight_int4(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """W4A16 (the paper's deployment format): per-column symmetric int4,
    two weights packed per int8 byte along K (rows 2r, 2r+1 -> lo, hi).

    Odd K is zero-padded to K+1 before packing (the pad row quantizes to
    code 0, so dequant of the padded row is exactly zero); callers that
    need the logical K back pass it to :func:`dequant_int4_ref`.

    The int4 code range is asymmetric ([-8, 7]): when a column's
    max-magnitude entry is negative and no positive entry would clip at
    the wider step, amax/8 is the better scale — it maps the extreme to
    the -8 code exactly instead of clipping it at -7 with amax/7.
    """
    w = w.astype(jnp.float32)
    K, N = w.shape
    if K % 2:
        w = jnp.concatenate([w, jnp.zeros((1, N), jnp.float32)], axis=0)
    pos = jnp.max(jnp.maximum(w, 0.0), axis=0)
    neg = jnp.max(jnp.maximum(-w, 0.0), axis=0)
    amax = jnp.maximum(pos, neg)
    # amax/8 is usable iff the largest positive still rounds inside +7,
    # i.e. pos/(amax/8) < 7.5  <=>  pos < 0.9375 * amax (== neg here).
    scale = jnp.where(pos < 0.9375 * neg, amax / 8.0, amax / 7.0)
    scale = jnp.where(amax > 0, scale, 1.0)
    q = jnp.clip(jnp.round(w / scale[None, :]), -8, 7).astype(jnp.int8)
    lo = q[0::2] & 0x0F
    hi = q[1::2] & 0x0F
    packed = (lo | (hi << 4)).astype(jnp.int8)
    return packed, scale.astype(jnp.float32)


@partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def mxu_q4_matmul(x: jax.Array, wq4: jax.Array, scale: jax.Array, *,
                  bm=128, bk=128, bn=128,
                  interpret: bool | None = None) -> jax.Array:
    from .kernel import q4_matmul_pallas
    x2, lead = _flatten_leading(x)
    y = q4_matmul_pallas(x2, wq4, scale, bm=bm, bk=bk, bn=bn,
                         out_dtype=x.dtype, interpret=interpret)
    return y.reshape(*lead, wq4.shape[-1])


def dequant_int4_ref(wq4: jax.Array, scale: jax.Array,
                     k: int | None = None) -> jax.Array:
    """Unpack oracle for tests. ``k`` recovers the logical contraction dim
    when the original K was odd (the packer zero-pads to even)."""
    lo = (jnp.left_shift(wq4, 4) >> 4).astype(jnp.float32)
    hi = (wq4 >> 4).astype(jnp.float32)
    K2, N = wq4.shape
    q = jnp.stack([lo, hi], axis=1).reshape(2 * K2, N)
    if k is not None:
        q = q[:k]
    return q * scale[None, :]

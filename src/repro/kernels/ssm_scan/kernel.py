"""SSD (Mamba2) chunk-step kernel — Pallas TPU.

One grid cell = one (batch, head) pair; the whole chunk's working set lives
in VMEM: CB [L,L] via MXU, per-head scalar decay applied on the VPU, three
more MXU matmuls for the intra-chunk output, state read-out and state
update. L=256, N=64, hd=64 keeps every matmul dim 64/128-aligned and the
VMEM footprint ~1.2 MB/cell.

This is the compute hot spot of the zamba2 cells; the chunk scan itself
(state passing) stays in jax.lax.scan — recurrences don't cross the kernel
boundary, exactly like the paper's per-operator NPU offload.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _chunk_kernel(xb_ref, b_ref, c_ref, seg_ref, sprev_ref, y_ref, snew_ref,
                  *, L: int):
    xb = xb_ref[0, :, 0, :].astype(jnp.float32)        # [L, hd]
    B_ = b_ref[0].astype(jnp.float32)                  # [L, N]
    C_ = c_ref[0].astype(jnp.float32)                  # [L, N]
    seg = seg_ref[0, :, 0].astype(jnp.float32)         # [L]
    S_prev = sprev_ref[0, 0].astype(jnp.float32)       # [hd, N]

    CB = jnp.dot(C_, B_.T, preferred_element_type=jnp.float32)   # [L, L]
    dec = jnp.exp(seg[:, None] - seg[None, :])
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    att = jnp.where(jj <= ii, CB * dec, 0.0)
    y = jnp.dot(att, xb, preferred_element_type=jnp.float32)     # intra
    y = y + jnp.dot(C_, S_prev.T,
                    preferred_element_type=jnp.float32) * jnp.exp(seg)[:, None]

    tot = seg[L - 1]
    w_in = jnp.exp(tot - seg)                          # [L] (<=0 exponents)
    S_new = (jnp.exp(tot) * S_prev
             + jnp.dot((xb * w_in[:, None]).T, B_,
                       preferred_element_type=jnp.float32))      # [hd, N]

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)
    snew_ref[0, 0] = S_new.astype(snew_ref.dtype)


def ssd_chunk_pallas(xb, B_, C_, seg, S_prev, *,
                     interpret: bool | None = None):
    """xb [B,L,nh,hd]; B_,C_ [B,L,N]; seg [B,L,nh]; S_prev [B,nh,hd,N].
    Returns (y [B,L,nh,hd], S_new [B,nh,hd,N])."""
    Bb, L, nh, hd = xb.shape
    N = B_.shape[-1]
    kern = functools.partial(_chunk_kernel, L=L)
    y, S_new = pl.pallas_call(
        kern,
        grid=(Bb, nh),
        in_specs=[
            pl.BlockSpec((1, L, 1, hd), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, L, N), lambda b, h: (b, 0, 0)),
            pl.BlockSpec((1, L, N), lambda b, h: (b, 0, 0)),
            pl.BlockSpec((1, L, 1), lambda b, h: (b, 0, h)),
            pl.BlockSpec((1, 1, hd, N), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, 1, hd), lambda b, h: (b, 0, h, 0)),
            pl.BlockSpec((1, 1, hd, N), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, L, nh, hd), jnp.float32),
            jax.ShapeDtypeStruct((Bb, nh, hd, N), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(xb, B_, C_, seg, S_prev)
    return y, S_new

"""jit'd wrapper: full SSD scan = lax.scan of the Pallas chunk kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import ssd_chunk_pallas


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xh, dt, A, B_, C_, *, chunk: int = 256,
             interpret: bool | None = None):
    """Same contract as models.mamba2.ssd_chunked, Pallas chunk compute.
    xh [B,S,nh,hd]; dt [B,S,nh] (post-softplus); A [nh] (<0); B_,C_ [B,S,N].
    """
    Bb, S, nh, hd = xh.shape
    N = B_.shape[-1]
    L = min(chunk, S)
    assert S % L == 0
    nc = S // L

    da = (dt * A[None, None, :]).astype(jnp.float32)
    xb = (xh * dt[..., None]).astype(jnp.float32)
    rs = lambda a: a.reshape(Bb, nc, L, *a.shape[2:]).transpose(
        1, 0, 2, *range(3, a.ndim + 1))
    da_c, xb_c = rs(da), rs(xb)
    B_c, C_c = rs(B_.astype(jnp.float32)), rs(C_.astype(jnp.float32))
    seg = jnp.cumsum(da_c, axis=2)

    def step(S_prev, xs):
        xb_i, B_i, C_i, seg_i = xs
        y, S_new = ssd_chunk_pallas(xb_i, B_i, C_i, seg_i, S_prev,
                                    interpret=interpret)
        return S_new, y

    S0 = jnp.zeros((Bb, nh, hd, N), jnp.float32)
    S_fin, y = jax.lax.scan(step, S0, (xb_c, B_c, C_c, seg))
    return y.transpose(1, 0, 2, 3, 4).reshape(Bb, S, nh, hd), S_fin

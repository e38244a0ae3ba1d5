"""Distributed split-KV decode attention (flash-decoding over the mesh).

The KV cache shards along the SEQUENCE dim over the model axis (the
mesh-level form of activation-centric partitioning). Per decode step, inside
a shard_map over the whole mesh:

  * the shard owning slot ``idx`` writes the new K/V locally (no cross-shard
    cache movement — this kills the involuntary-full-rematerialization
    collectives GSPMD emits for a dynamic-update-slice on a sharded dim,
    §Perf decode/i3);
  * every shard computes attention over its local KV slice;
  * partial softmax stats combine with a global pmax + two psums of
    [B, Hq, D]-sized tensors (~100 KB — vs gigabytes of cache traffic).

All shards aggregate their HBM streams simultaneously — the paper's
Memory-1 bandwidth-aggregation insight, applied across chips.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def combine_split_softmax(s, v_local, axis_name=None):
    """Numerically-stable softmax combine of per-shard attention partials —
    the pmax + 2×psum pattern (one [B, Hkv, G] pmax, then psums of the
    [B, Hq, D]-sized numerator and the [B, Hkv, G] denominator).

    ``s``: local masked scores [B, Hkv, G, K_local] (NEG_INF outside range);
    ``v_local``: local values [B, K_local, Hkv, D]. With ``axis_name=None``
    (single shard / unit tests) the collectives degenerate to identity and
    this is exactly a blockwise-stable softmax-weighted sum.

    Returns fp32 [B, Hkv, G, D].
    """
    m_l = s.max(axis=-1)                                # [B, Hkv, G]
    m_g = jax.lax.pmax(m_l, axis_name) if axis_name else m_l
    p = jnp.exp(s - m_g[..., None])
    den = p.sum(axis=-1)
    num = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_local.dtype), v_local,
                     preferred_element_type=jnp.float32)
    if axis_name:
        den = jax.lax.psum(den, axis_name)
        num = jax.lax.psum(num, axis_name)
    return num / jnp.where(den == 0.0, 1.0, den)[..., None]


def _mesh_axes():
    mesh = jax.sharding.get_abstract_mesh()
    names = mesh.axis_names
    data = tuple(n for n in names if n != "model")
    return mesh, data


def split_kv_decode_update_attend(q, k_new, v_new, k_cache, v_cache, idx):
    """q,k_new,v_new: [B, 1, H*, D] (Hq for q, Hkv for kv); caches
    [B, Smax, Hkv, D] seq-sharded over 'model', batch over the data axes.
    idx: scalar int32 write slot (= query position).
    Returns (out [B, 1, Hq, D], new_k_cache, new_v_cache)."""
    mesh, data_axes = _mesh_axes()
    B, _, Hq, D = q.shape
    Hkv = k_new.shape[2]
    Smax = k_cache.shape[1]
    n_shards = mesh.shape["model"]
    if Smax % n_shards != 0:
        raise ValueError(
            f"split-KV cache length Smax={Smax} is not divisible by the "
            f"model-axis size {n_shards}: the trailing {Smax % n_shards} "
            "slots would never be attended over and writes to them would be "
            "silently dropped. Pad Smax to a multiple of the shard count.")
    chunk = Smax // n_shards
    scale = 1.0 / math.sqrt(D)
    G = Hq // Hkv

    qs = P(data_axes, None, None, None)
    cs = P(data_axes, "model", None, None)

    def local(qx, kn, vn, kc, vc, i):
        Bl = qx.shape[0]                 # local (per-data-shard) batch
        sid = jax.lax.axis_index("model")
        start = sid * chunk
        pos = i - start
        in_range = (pos >= 0) & (pos < chunk)

        def write(c, new):
            upd = jax.lax.dynamic_update_slice(
                c, new.astype(c.dtype),
                (0, jnp.clip(pos, 0, chunk - 1), 0, 0))
            return jnp.where(in_range, upd, c)

        kc = write(kc, kn)
        vc = write(vc, vn)

        # local attention over this shard's KV slice. NO .astype on the
        # cache operands: fp32 copies of K/V would dominate HBM traffic
        # (§Perf decode/i4) — accumulate in fp32 via preferred_element_type.
        qg = qx.reshape(Bl, Hkv, G, D)
        s = jnp.einsum("bhgd,bkhd->bhgk", qg, kc,
                       preferred_element_type=jnp.float32) * scale
        kv_pos = start + jnp.arange(chunk, dtype=jnp.int32)
        s = jnp.where(kv_pos[None, None, None, :] <= i, s, NEG_INF)
        out = combine_split_softmax(s, vc, "model")
        return out.reshape(Bl, 1, Hq, D).astype(qx.dtype), kc, vc

    return shard_map(
        local, mesh=mesh,
        in_specs=(qs, qs, qs, cs, cs, P()),
        out_specs=(qs, cs, cs),
        check_vma=False,
    )(q, k_new, v_new, k_cache, v_cache, idx)

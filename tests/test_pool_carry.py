"""The paged pool carried whole through the layer scan, checked bit for bit
against the layout it replaced.

The oracle below is the earlier form of the paged layer stack, kept here as
a reference: the pool entered the layer scan as ``xs`` (one ``[NB, BS, ...]``
slice per layer) and left it as stacked ``ys``, and the unrolled loop
stacked the per-layer pools back with ``jnp.stack``. The carried form must
give the same logits and the same pool leaves, bit for bit, for every paged
entry point, for a bf16 and an int8 pool, scanned and unrolled.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.distributed.sharding import hidden_constraint
from repro.models import build_model
from repro.models.layers import (_qkv_rope, blockwise_attention,
                                 dequant_kv_ref, quantize_kv_slot, rms_norm,
                                 swiglu)
from repro.models.transformer import _embed, _head_logits

NUM_BLOCKS, BLOCK_SIZE, NBMAX = 24, 4, 6


# ------------------------------------------------------------------ oracle --

def _oracle_attention(p, x, cfg, *, positions, pool, block_table, unroll):
    """Paged attention over ONE layer's pool slice (``[NB, BS, Hkv, D]``)."""
    B, S, _ = x.shape
    NB, BS, Hkv, D = pool["k"].shape
    quant = "k_scale" in pool
    q, k, v, mm = _qkv_rope(p, x, cfg, positions, None)
    pos = (positions if positions.ndim == 2
           else jnp.broadcast_to(positions[None, :], (B, S))).astype(jnp.int32)
    blk = jnp.take_along_axis(block_table, pos // BS, axis=1)
    flat_idx = (blk * BS + pos % BS).reshape(-1)
    fk = pool["k"].reshape(NB * BS, Hkv, D)
    fv = pool["v"].reshape(NB * BS, Hkv, D)
    new = {}
    if quant:
        k_codes, k_sc = quantize_kv_slot(k.reshape(B * S, Hkv, D),
                                         pool["k_scale"].dtype)
        v_codes, v_sc = quantize_kv_slot(v.reshape(B * S, Hkv, D),
                                         pool["v_scale"].dtype)
        fk = fk.at[flat_idx].set(k_codes)
        fv = fv.at[flat_idx].set(v_codes)
        new["k_scale"] = pool["k_scale"].reshape(
            NB * BS).at[flat_idx].set(k_sc).reshape(NB, BS)
        new["v_scale"] = pool["v_scale"].reshape(
            NB * BS).at[flat_idx].set(v_sc).reshape(NB, BS)
    else:
        fk = fk.at[flat_idx].set(k.reshape(B * S, Hkv, D).astype(fk.dtype))
        fv = fv.at[flat_idx].set(v.reshape(B * S, Hkv, D).astype(fv.dtype))
    new["k"] = fk.reshape(NB, BS, Hkv, D)
    new["v"] = fv.reshape(NB, BS, Hkv, D)
    NBmax = block_table.shape[1]
    ck = jnp.take(new["k"], block_table, axis=0).reshape(B, NBmax * BS, Hkv, D)
    cv = jnp.take(new["v"], block_table, axis=0).reshape(B, NBmax * BS, Hkv, D)
    if quant:
        ck = dequant_kv_ref(ck, jnp.take(new["k_scale"], block_table, axis=0)
                            .reshape(B, NBmax * BS), q.dtype)
        cv = dequant_kv_ref(cv, jnp.take(new["v_scale"], block_table, axis=0)
                            .reshape(B, NBmax * BS), q.dtype)
    o = blockwise_attention(q, ck, cv, q_pos=pos,
                            kv_pos=jnp.arange(NBmax * BS, dtype=jnp.int32),
                            causal=True, block_k=cfg.attn_block_k,
                            unroll=unroll)
    return mm(o.reshape(B, S, cfg.n_heads * cfg.head_dim), p["wo"],
              name="wo"), new


def _oracle_layer(lp, x, cfg, positions, pl, table, unroll):
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    a, pl = _oracle_attention(lp["attn"], h, cfg, positions=positions,
                              pool=pl, block_table=table, unroll=unroll)
    x = x + a
    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    return hidden_constraint(x + swiglu(lp["ffn"], h)), pl


def _oracle_layers(params, cfg, unroll, body, carry, pool):
    """The pool as the layer scan's ``xs``/``ys`` (or stacked, unrolled)."""
    if unroll:
        pools = []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            carry, pl = body(lp, carry, jax.tree.map(lambda a: a[i], pool))
            pools.append(pl)
        return carry, jax.tree.map(lambda *ls: jnp.stack(ls), *pools)
    return jax.lax.scan(lambda c, xs: body(xs[0], c, xs[1]), carry,
                        (params["layers"], pool))


def _positions(start, S):
    start = jnp.asarray(start, jnp.int32)
    steps = jnp.arange(S, dtype=jnp.int32)
    return start[:, None] + steps[None, :] if start.ndim == 1 else start + steps


def _oracle_run(params, tokens, pool, cfg, table, positions, unroll):
    x = _embed(params, tokens, cfg)
    x, pool = _oracle_layers(
        params, cfg, unroll,
        lambda lp, x, pl: _oracle_layer(lp, x, cfg, positions, pl, table,
                                        unroll), x, pool)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), pool


def _oracle_mixed(params, dec, pre, pool, cfg, *, decode_tables,
                  decode_lengths, prefill_table, prefill_start, unroll):
    dec_pos = decode_lengths[:, None].astype(jnp.int32)
    pre_pos = _positions(prefill_start, pre.shape[1])

    def body(lp, c, pl):
        xd, pl = _oracle_layer(lp, c[0], cfg, dec_pos, pl, decode_tables,
                               unroll)
        xp, pl = _oracle_layer(lp, c[1], cfg, pre_pos, pl, prefill_table,
                               unroll)
        return (xd, xp), pl

    (xd, xp), pool = _oracle_layers(
        params, cfg, unroll, body,
        (_embed(params, dec, cfg), _embed(params, pre, cfg)), pool)
    head = lambda x: _head_logits(
        params, rms_norm(x, params["final_norm"], cfg.norm_eps), cfg)
    return head(xd), head(xp[:, -1:, :]), pool


# ------------------------------------------------------------------ inputs --

@pytest.fixture(scope="module")
def smoke():
    cfg = get_smoke_config("qwen3-1.7b").with_(param_dtype="float32",
                                               compute_dtype="float32")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(3))


def _filled_pool(model, kv):
    """A pool whose every slot holds data, so each gather reads real K/V."""
    quant = "int8" if kv == "int8" else None
    pool = model.init_paged_cache(num_blocks=NUM_BLOCKS,
                                  block_size=BLOCK_SIZE, dtype=jnp.bfloat16,
                                  kv_quant=quant)
    keys = iter(jax.random.split(jax.random.PRNGKey(11), len(pool)))
    out = {}
    for name, leaf in sorted(pool.items()):
        key = next(keys)
        if leaf.dtype == jnp.int8:
            out[name] = jax.random.randint(key, leaf.shape, -127, 128,
                                           jnp.int32).astype(jnp.int8)
        elif name.endswith("_scale"):
            out[name] = jax.random.uniform(key, leaf.shape, jnp.float32,
                                           0.001, 0.02).astype(leaf.dtype)
        else:
            out[name] = jax.random.normal(key, leaf.shape).astype(leaf.dtype)
    return out


def _tables(rows):
    """Distinct, shuffled blocks per row; a row of zeros is a null table."""
    perm = np.random.default_rng(5).permutation(np.arange(1, NUM_BLOCKS))
    out = np.zeros((len(rows), NBMAX), np.int32)
    at = 0
    for r, n in enumerate(rows):
        out[r, :n] = perm[at:at + n]
        at += n
    return jnp.asarray(out)


def _tokens(cfg, shape, seed):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, shape), jnp.int32)


def _case(entry, cfg, model, params, pool, unroll):
    """(carried form's outputs, oracle's outputs) for one entry point."""
    if entry == "decode":
        tok = _tokens(cfg, (3, 1), 1)
        tables = _tables([5, 3, 0])                  # the last lane inactive
        lengths = jnp.asarray([17, 9, 0], jnp.int32)
        got = model.paged_decode_step(params, tok, pool, block_tables=tables,
                                      lengths=lengths, unroll=unroll)
        x, ref_pool = _oracle_run(params, tok, pool, cfg, tables,
                                  lengths[:, None], unroll)
        return got, (_head_logits(params, x, cfg), ref_pool)
    if entry in ("prefill", "prefill_per_lane", "verify"):
        if entry == "prefill":
            tok, tables, start = _tokens(cfg, (1, 7), 2), _tables([4]), 5
        else:
            S = 6 if entry == "prefill_per_lane" else 3
            tok, tables = _tokens(cfg, (3, S), 3), _tables([5, 4, 6])
            start = jnp.asarray([0, 7, 13], jnp.int32)
        fn = model.paged_verify if entry == "verify" else model.paged_prefill
        got = fn(params, tok, pool, block_table=tables, start_index=start,
                 unroll=unroll)
        x, ref_pool = _oracle_run(params, tok, pool, cfg, tables,
                                  _positions(start, tok.shape[1]), unroll)
        x = x if entry == "verify" else x[:, -1:, :]
        return got, (_head_logits(params, x, cfg), ref_pool)
    dec, pre = _tokens(cfg, (2, 1), 4), _tokens(cfg, (1, 9), 5)
    tables = _tables([5, 3, 4])
    kw = dict(decode_tables=tables[:2],
              decode_lengths=jnp.asarray([18, 10], jnp.int32),
              prefill_table=tables[2:], prefill_start=jnp.asarray(3),
              unroll=unroll)
    return (model.mixed_step(params, dec, pre, pool, **kw),
            _oracle_mixed(params, dec, pre, pool, cfg, **kw))


@pytest.mark.parametrize("unroll", [False, True])
@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("entry", ["decode", "prefill", "prefill_per_lane",
                                   "verify", "mixed"])
def test_carried_pool_matches_scanned_slices(smoke, entry, kv, unroll):
    cfg, model, params = smoke
    pool = _filled_pool(model, kv)
    got, want = _case(entry, cfg, model, params, pool, unroll)
    *got_logits, got_pool = got
    *want_logits, want_pool = want
    for g, w in zip(got_logits, want_logits):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert sorted(got_pool) == sorted(want_pool)
    for name in want_pool:
        assert got_pool[name].dtype == want_pool[name].dtype
        np.testing.assert_array_equal(np.asarray(got_pool[name]),
                                      np.asarray(want_pool[name]))

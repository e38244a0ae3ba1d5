"""Device layout objects: serving-state placement, decoupled from logic.

ROADMAP item 3a's load-bearing refactor: ``PagedBatcher`` / ``PagedKVCache``
host-side bookkeeping (block tables, refcounts, prefix-cache hash chains,
admission accounting) is device-agnostic — it reasons about *logical* block
ids. What varies across deployments is only where the arrays live. A layout
object owns exactly that:

  * ``DeviceLayout``    — the single-device identity layout (placement is a
    no-op, the step functions are the model's own paged entry points).
  * ``MeshLayout(mesh)`` — head-wise tensor parallelism over the mesh's
    ``model`` axis: weights and the paged KV pool shard, host bookkeeping
    stays replicated, and the four paged inference paths (``paged_prefill``,
    ``paged_decode_step`` — the fused-window scan body — ``mixed_step`` and
    ``paged_verify``) run under ``shard_map``.

Sharding plan (TP = model-axis size):

  shards over ``model``                          replicates
  ---------------------------------------------  -------------------------
  wq/wk/wv          output cols (heads local)    embed table
  wo                output cols (d_model/TP)     all norms
  w_gate/w_up       output cols (d_ff/TP)        int8 pool scale planes
  w_down            output cols (d_model/TP)     tied head (via embed)
  head (untied)     output cols (vocab/TP)       block tables / lengths
  KV pool k/v       axis 3 (KV heads local)      draft-lane params (spec)

Every sharded matrix splits on its OUTPUT axis, never the contraction axis:
each shard computes full-depth reductions for its slice of the output
columns and a tiled ``all_gather`` concatenates the slices in shard order.
That makes TP an execution schedule, never a numerics change — greedy token
streams are bit-identical to the single-device batcher in the f32 CPU
tests (a row-parallel psum-of-partials would reassociate the reduction and
drift at ULP level). On a TPU v5e in bf16 the compiler lowers the narrower
shard matmuls differently, so TP=4 and TP=1 logits differ by bf16 rounding
and long greedy streams can part at near-ties.
Quantized sites shard the same way: ``QuantWeight`` codes and their
per-output-channel scales both split along N, so w4a16's K-axis nibble
packing is never cut. Per-step collectives (decode, B lanes, d = d_model):

  * 2 all-gathers of [B, 1, d] per layer (head concat + wo output concat)
  * 2 all-gathers of [B, 1, d_ff] / [B, 1, d] per layer (FFN hidden/output)
  * 1 all-gather of [B, 1, vocab] for untied LM-head logits
  * int8 pool only: 1 pmax of [tokens] per layer (global amax for the slot
    scales — max-of-maxes is exact, so codes match the single-device pool
    bit for bit)
"""
from __future__ import annotations

import re
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.partition import QuantWeight
from repro.distributed.sharding import sanitize_spec
from repro.models import transformer

TP_AXIS = "model"

# param paths whose LAST axis is an output-channel axis sharded over TP
_COL_SHARDED = re.compile(r"(attn/(wq|wk|wv|wo)|ffn/(w_gate|w_up|w_down))$")


class DeviceLayout:
    """Single-device identity layout."""

    mesh = None
    tp = 1

    def place_params(self, params):
        return params

    def place_pool(self, pool):
        return pool

    def step_fns(self, model, params):
        """The model's own paged entry points, unchanged."""
        return {"paged_prefill": model.paged_prefill,
                "paged_decode_step": model.paged_decode_step,
                "mixed_step": model.mixed_step,
                "paged_verify": model.paged_verify}


class MeshLayout(DeviceLayout):
    """Head-wise tensor-parallel layout over ``mesh``'s ``model`` axis."""

    def __init__(self, cfg, mesh):
        if "model" not in mesh.axis_names:
            raise ValueError(f"mesh {mesh.axis_names} has no 'model' axis")
        tp = mesh.shape[TP_AXIS]
        if cfg.moe is not None or cfg.ssm is not None or cfg.rwkv is not None:
            raise ValueError("tensor-parallel serving supports the dense "
                             "transformer family only")
        for dim, name in ((cfg.n_heads, "n_heads"),
                          (cfg.n_kv_heads, "n_kv_heads"),
                          (cfg.d_model, "d_model"),
                          (cfg.d_ff, "d_ff")):
            if dim % tp:
                raise ValueError(
                    f"cfg.{name}={dim} is not divisible by the model-axis "
                    f"size {tp}; pick a TP width that divides it")
        if not cfg.tie_embeddings and cfg.vocab_size % tp:
            raise ValueError(
                f"untied head: vocab_size={cfg.vocab_size} is not divisible "
                f"by the model-axis size {tp}")
        self.cfg = cfg
        self.mesh = mesh
        self.tp = tp
        # the shard-local view: each shard runs the UNCHANGED transformer
        # code over its own heads. head_dim is derived from d_model/n_heads
        # when d_head is 0, so pin it before halving the head counts.
        self.cfg_local = cfg.with_(n_heads=cfg.n_heads // tp,
                                   n_kv_heads=cfg.n_kv_heads // tp,
                                   d_head=cfg.head_dim)

    # ------------------------------------------------------------- specs --

    def _last_axis(self, ndim: int) -> P:
        return P(*([None] * (ndim - 1)), TP_AXIS)

    def param_specs(self, params) -> Any:
        """Params-shaped pytree of PartitionSpec (QuantWeight leaves map to
        QuantWeight nodes holding their children's specs, so the spec tree
        flattens 1:1 with the params tree)."""
        def spec_for(path, leaf):
            parts = [p.key for p in path
                     if isinstance(p, jax.tree_util.DictKey)]
            s = "/".join(parts)
            col = (_COL_SHARDED.search(s) is not None
                   or (s == "head" and not self.cfg.tie_embeddings))
            if isinstance(leaf, QuantWeight):
                if not col:
                    return QuantWeight(P(), P(), leaf.fmt, leaf.k)
                return QuantWeight(self._last_axis(leaf.wq.ndim),
                                   self._last_axis(leaf.scale.ndim),
                                   leaf.fmt, leaf.k)
            return self._last_axis(leaf.ndim) if col else P()

        return jax.tree_util.tree_map_with_path(
            spec_for, params, is_leaf=lambda x: isinstance(x, QuantWeight))

    def pool_specs(self, pool) -> dict:
        """k/v: [L, NB, BS, Hkv, D] with KV heads (axis 3) over ``model``;
        int8 scale planes [L, NB, BS] replicate (one scalar per slot covers
        ALL heads, so every shard must hold it)."""
        specs = {}
        for key, leaf in pool.items():
            if key in ("k", "v"):
                spec = P(None, None, None, TP_AXIS, None)
                dropped: list = []
                sanitize_spec(spec, leaf.shape, self.mesh, dropped=dropped)
                if 3 in dropped:
                    raise ValueError(
                        f"KV-head dim of pool[{key!r}] (size {leaf.shape[3]})"
                        f" did not shard over the {self.tp}-wide model axis —"
                        " the pool would silently replicate")
                specs[key] = spec
            else:
                specs[key] = P()
        return specs

    # --------------------------------------------------------- placement --

    def place_params(self, params):
        specs = self.param_specs(params)
        return jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(self.mesh, s)),
            params, specs)

    def place_pool(self, pool):
        specs = self.pool_specs(pool)
        return {k: jax.device_put(v, NamedSharding(self.mesh, specs[k]))
                for k, v in pool.items()}

    # -------------------------------------------------------- step fns --

    def step_fns(self, model, params) -> dict:
        """shard_map-wrapped variants of the four paged inference paths,
        signature-compatible with the model's own (``hetero_ctx`` is
        accepted for interface parity but must be None — the hetero engine
        and the mesh are separate axes of the machine). The returned
        callables have stable identity: callers may bake them into jitted
        graphs as static arguments (core/sync.py fused windows)."""
        cfg_l = self.cfg_local
        pspecs = self.param_specs(params)
        rep = P()

        def _pool_specs(pool):
            return self.pool_specs(pool)

        def _sm(inner, in_specs, out_specs):
            return shard_map(inner, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

        # pool spec structure depends on kv_quant; build per-call-signature
        # wrappers lazily on first use and cache them (stable identity).
        cache: dict = {}

        def _cached(key, build):
            if key not in cache:
                cache[key] = build()
            return cache[key]

        def paged_prefill(params, tokens, pool, *, block_table,
                          start_index=0, unroll=False, hetero_ctx=None):
            _no_ctx(hetero_ctx)
            ps = _pool_specs(pool)

            def build():
                def inner(params, tokens, pool, block_table, start_index):
                    return transformer.paged_prefill(
                        params, tokens, pool, cfg_l, block_table=block_table,
                        start_index=start_index, tp_axis=TP_AXIS)
                return _sm(inner, (pspecs, rep, ps, rep, rep), (rep, ps))

            return _cached(("prefill", tuple(sorted(ps))), build)(
                params, tokens, pool, block_table,
                jnp.asarray(start_index, jnp.int32))

        def paged_decode_step(params, token, pool, *, block_tables, lengths,
                              unroll=False, hetero_ctx=None):
            _no_ctx(hetero_ctx)
            ps = _pool_specs(pool)

            def build():
                def inner(params, token, pool, block_tables, lengths):
                    return transformer.paged_decode_step(
                        params, token, pool, cfg_l,
                        block_tables=block_tables, lengths=lengths,
                        tp_axis=TP_AXIS)
                return _sm(inner, (pspecs, rep, ps, rep, rep), (rep, ps))

            return _cached(("decode", tuple(sorted(ps))), build)(
                params, token, pool, block_tables, lengths)

        def mixed_step(params, decode_tokens, prefill_tokens, pool, *,
                       decode_tables, decode_lengths, prefill_table,
                       prefill_start=0, unroll=False, hetero_ctx=None):
            _no_ctx(hetero_ctx)
            ps = _pool_specs(pool)

            def build():
                def inner(params, dt, pt, pool, dtab, dlen, ptab, pstart):
                    return transformer.mixed_step(
                        params, dt, pt, pool, cfg_l, decode_tables=dtab,
                        decode_lengths=dlen, prefill_table=ptab,
                        prefill_start=pstart, tp_axis=TP_AXIS)
                return _sm(inner, (pspecs, rep, rep, ps, rep, rep, rep, rep),
                           (rep, rep, ps))

            return _cached(("mixed", tuple(sorted(ps))), build)(
                params, decode_tokens, prefill_tokens, pool, decode_tables,
                decode_lengths, prefill_table,
                jnp.asarray(prefill_start, jnp.int32))

        def paged_verify(params, tokens, pool, *, block_table, start_index,
                         unroll=False, hetero_ctx=None):
            _no_ctx(hetero_ctx)
            ps = _pool_specs(pool)

            def build():
                def inner(params, tokens, pool, block_table, start_index):
                    return transformer.paged_verify(
                        params, tokens, pool, cfg_l, block_table=block_table,
                        start_index=start_index, tp_axis=TP_AXIS)
                return _sm(inner, (pspecs, rep, ps, rep, rep), (rep, ps))

            return _cached(("verify", tuple(sorted(ps))), build)(
                params, tokens, pool, block_table,
                jnp.asarray(start_index, jnp.int32))

        return {"paged_prefill": paged_prefill,
                "paged_decode_step": paged_decode_step,
                "mixed_step": mixed_step,
                "paged_verify": paged_verify}


def _no_ctx(hetero_ctx):
    if hetero_ctx is not None:
        raise ValueError("tensor-parallel serving does not compose with a "
                         "HeteroCtx engine mode (engine_mode must be None "
                         "when a mesh is given)")


def make_layout(cfg, mesh) -> DeviceLayout:
    return DeviceLayout() if mesh is None else MeshLayout(cfg, mesh)

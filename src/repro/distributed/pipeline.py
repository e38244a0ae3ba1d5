"""Pipeline parallelism: GPipe-style microbatch schedule over a "stage" axis,
expressed with shard_map + collective_permute (jax-native; no NCCL-style
point-to-point emulation).

Layers are split into ``n_stages`` contiguous groups. A shard_map over the
stage axis runs ``n_micro + n_stages - 1`` ticks; each tick every stage
processes one microbatch slice and ppermutes its activation to the next
stage. Bubble fraction = (S-1)/(M+S-1), surfaced by ``pipeline_stats`` so the
solver/roofline can weigh PP against TP for deep models. Used as an optional
config (``pp=N``) in the trainer; tested end-to-end in
tests/test_distributed.py on a host mesh.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P



def pipeline_stats(n_micro: int, n_stages: int) -> dict:
    ticks = n_micro + n_stages - 1
    return {"ticks": ticks,
            "bubble_fraction": (n_stages - 1) / ticks}


def make_pipeline_forward(layer_fn: Callable, n_stages: int, n_micro: int,
                          mesh, *, stage_axis: str = "stage"):
    """layer_fn(stage_params, x) -> x, applied per stage.

    stage_params: pytree stacked on a leading stage dim (sharded over
    ``stage_axis``); x: [n_micro, mb, ...] microbatched input living on
    stage 0. Returns outputs [n_micro, mb, ...] gathered on the last stage
    then broadcast (simple GPipe; interleaved 1F1B left as config).
    """

    def stage_prog(params_s, x_s):
        # params_s: this stage's params (leading dim 1); x_s: [n_micro, mb, ...]
        params_s = jax.tree.map(lambda a: a[0], params_s)
        sid = jax.lax.axis_index(stage_axis)
        ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(x_s[0])
        outs = jnp.zeros_like(x_s)

        def tick(c, t):
            buf, outs = c
            mb_in = t - sid                      # microbatch index at this stage
            feed = jnp.where(mb_in >= 0, jnp.clip(mb_in, 0, n_micro - 1), 0)
            x_in = jnp.where(sid == 0, x_s[feed], buf)
            active = (mb_in >= 0) & (mb_in < n_micro)
            y = layer_fn(params_s, x_in)
            y = jnp.where(active, y, buf)
            # last stage records its finished microbatch
            outs = jax.lax.cond(
                active & (sid == n_stages - 1),
                lambda o: o.at[feed].set(y), lambda o: o, outs)
            # everyone hands activations down the ring
            buf = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(ticks))
        # broadcast final outputs from the last stage to all stages
        outs = jax.lax.ppermute(
            outs, stage_axis,
            [((n_stages - 1 + i) % n_stages, i) for i in range(n_stages)]) \
            if n_stages > 1 else outs
        return outs

    return shard_map(
        stage_prog, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False)

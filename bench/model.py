"""A configuration file `bench/configs/<config>.json` and the random weights
made from the seed.

The file holds the model's published `config.json` keys as they are run,
and under ``deployment`` the sizes the serving batcher is given. The
weights are made on the device in one jitted call, in the layout the
program's dense GQA transformer takes and in the type it serves them in.
The reference (`reference.py`) makes the same weights from the same seed
with this same function, so nothing the program made reaches it.
"""
from __future__ import annotations

import json
import math
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BENCH = Path(__file__).resolve().parent

# published keys every dense GQA configuration must give
REQUIRED = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "vocab_size",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings")


def load_config(name: str) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    missing = [k for k in REQUIRED if k not in cfg]
    if missing:
        raise ValueError(f"config {name}: missing {missing}")
    return cfg


def head_dim(c: dict) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def model_config(c: dict):
    """The program's ModelConfig for configuration file ``c``."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], d_head=head_dim(c),
        qk_norm=bool(c.get("qk_norm", False)),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        param_dtype=c["torch_dtype"], compute_dtype=c["torch_dtype"])


def jax_key(seed: int):
    """A JAX key from any whole-number seed, 64-bit ones included."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


def param_shapes(c: dict) -> dict:
    """Leaf shapes of the program's parameter tree (layers stacked)."""
    d, f, v, L = (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
                  c["num_hidden_layers"])
    hq, hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    attn = {"wq": (L, d, hq * hd), "wk": (L, d, hkv * hd),
            "wv": (L, d, hkv * hd), "wo": (L, hq * hd, d)}
    if c.get("qk_norm"):
        attn.update(q_norm=(L, hd), k_norm=(L, hd))
    shapes = {"embed": (v, d), "final_norm": (d,),
              "layers": {"attn_norm": (L, d), "attn": attn,
                         "ffn_norm": (L, d),
                         "ffn": {"w_gate": (L, d, f), "w_up": (L, d, f),
                                 "w_down": (L, f, d)}}}
    if not c["tie_word_embeddings"]:
        shapes["head"] = (d, v)
    return shapes


def _fan_in(path: str, shape) -> int:
    if path in ("embed", "head"):
        return shape[-1] if path == "embed" else shape[0]
    return shape[-2]


@partial(jax.jit, static_argnames=("shapes_json", "dtype"))
def _make(key, shapes_json: str, dtype: str):
    shapes = json.loads(shapes_json)
    flat, tree = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, list))
    keys = jax.random.split(key, len(flat))
    out = []
    for k, (path, shape) in zip(keys, flat):
        name = path[-1].key
        if "norm" in name:
            # norm gains near 1, so that a gain left out shows
            w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            # unit-variance outputs at every width; logits of std ~1
            w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(
                _fan_in(name, shape))
        out.append(w.astype(dtype))
    return jax.tree.unflatten(tree, out)


def make_weights(c: dict, seed: int) -> dict:
    """All weights of configuration ``c`` from ``seed``, on the default
    device, in one jitted call."""
    shapes = json.dumps(param_shapes(c), sort_keys=True)
    return _make(jax_key(seed), shapes, c["torch_dtype"])


def param_counts(c: dict) -> dict:
    """Parameters read per step: the layers, and the output head (the
    embedding matrix when tied). The embedding lookup reads one row per
    token and is left out."""
    shapes = param_shapes(c)
    layers = sum(math.prod(s) for s in jax.tree.leaves(
        shapes["layers"], is_leaf=lambda x: isinstance(x, tuple)))
    d, v = c["hidden_size"], c["vocab_size"]
    return {"layers": layers + d, "head": d * v}

"""The dense GQA family: pre-norm transformer layers of grouped-query
attention and a SwiGLU MLP (Qwen3, InternLM2).

`layer` computes one layer in float32 at the highest matmul precision:
RMSNorm, the q/k/v projections, optional per-head q/k RMSNorm (Qwen3),
rotary embeddings on the two halves of each head, causal softmax attention
with grouped KV heads and the output projection, added to the residual;
then RMSNorm and the SwiGLU MLP, added to the residual. `attention` and
`mlp` are its two halves, for a family that shares one of them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.model import head_dim
from bench.reference import HIGHEST, _attention, _mm, _rms, _rope

# published keys every dense GQA configuration must give
REQUIRED = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "vocab_size",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings")


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


def attn_shapes(c: dict) -> dict:
    """Leaf shapes of the stacked attention parameters."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    hq, hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    attn = {"wq": (L, d, hq * hd), "wk": (L, d, hkv * hd),
            "wv": (L, d, hkv * hd), "wo": (L, hq * hd, d)}
    if c.get("qk_norm"):
        attn.update(q_norm=(L, hd), k_norm=(L, hd))
    return attn


def param_shapes(c: dict) -> dict:
    """Leaf shapes of the program's parameter tree (layers stacked)."""
    d, f, v, L = (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
                  c["num_hidden_layers"])
    shapes = {"embed": (v, d), "final_norm": (d,),
              "layers": {"attn_norm": (L, d), "attn": attn_shapes(c),
                         "ffn_norm": (L, d),
                         "ffn": {"w_gate": (L, d, f), "w_up": (L, d, f),
                                 "w_down": (L, f, d)}}}
    if not c["tie_word_embeddings"]:
        shapes["head"] = (d, v)
    return shapes


def attention(c: dict, lp: dict, x, pos):
    """``x`` [S, d] plus the attention block of layer parameters ``lp``."""
    hq, hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    S = x.shape[0]
    h = _rms(x, lp["attn_norm"], eps)
    q = _mm(h, lp["attn"]["wq"]).reshape(S, hq, hd)
    k = _mm(h, lp["attn"]["wk"]).reshape(S, hkv, hd)
    v = _mm(h, lp["attn"]["wv"]).reshape(S, hkv, hd)
    if c.get("qk_norm", False):
        q = _rms(q, lp["attn"]["q_norm"], eps)
        k = _rms(k, lp["attn"]["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    o = _attention(q, k, v, HIGHEST)
    return x + _mm(o, lp["attn"]["wo"])


def mlp(f: dict, h):
    """The SwiGLU MLP of parameters ``f`` on normed ``h`` [S, d]."""
    a = jax.nn.silu(_mm(h, f["w_gate"])) * _mm(h, f["w_up"])
    return _mm(a, f["w_down"])


def layer(c: dict, lp: dict, x, pos):
    """One layer: ``x`` [S, d] float32 at positions ``pos`` [S]."""
    x = attention(c, lp, x, pos)
    h = _rms(x, lp["ffn_norm"], float(c["rms_norm_eps"]))
    return x + mlp(lp["ffn"], h)


def flops_params(c: dict) -> dict:
    """Parameters computed per token: the layers (with the final norm), and
    the output head (the embedding matrix when tied). The embedding lookup
    reads one row per token and is left out."""
    layers = param_shapes(c)["layers"]
    n = sum(math.prod(s) for s in jax.tree.leaves(
        layers, is_leaf=lambda x: isinstance(x, tuple)))
    d, v = c["hidden_size"], c["vocab_size"]
    return {"layers": n + d, "head": d * v}


def weight_bytes(c: dict, n_tokens: int) -> int:
    """Every weight is read once per step, whatever its number of tokens."""
    p = flops_params(c)
    return (p["layers"] + p["head"]) * jnp.dtype(c["torch_dtype"]).itemsize

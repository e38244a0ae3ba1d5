"""The plain reference: a float32 dense GQA transformer in `jax.numpy`.

It imports nothing of the program. It reads the widths from the
configuration file and makes the weights from the seed with
`model.make_weights`, the same call that made the served weights, then
computes in float32 at the highest matmul precision: RMSNorm, optional
per-head q/k RMSNorm (Qwen3), rotary embeddings on the two halves of each
head, causal softmax attention with grouped KV heads, a SwiGLU MLP, and a
tied or untied output head.

Attention runs in blocks of query rows and the layers in a scan, so a
sequence of several thousand tokens fits on one chip beside nothing else.

`served_gaps` is the comparison that decides `correct`: for each served
token, how far its reference logit lies below the reference's best at that
position.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import head_dim, make_weights

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _dot(a, b, spec, precision):
    return jnp.einsum(spec, a, b, precision=precision,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return x32 * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [S, H, D]: rotate the two halves of each head by position."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, precision):
    """Causal GQA attention, q [S, Hq, D], k/v [S, Hkv, D], in blocks of
    query rows."""
    S, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    nb = S // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, hkv, g, d)
    kpos = jnp.arange(S)

    def block(args):
        i, qi = args
        s = _dot(qi, k, "qhgd,khd->hgqk", precision) / math.sqrt(d)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _dot(p, v, "hgqk,khd->qhgd", precision)

    o = jax.lax.map(block, (jnp.arange(nb), qb))
    return o.reshape(S, hq * d)


@partial(jax.jit, static_argnames=("c_items",))
def _forward(params, tokens, positions, *, c_items):
    """Logits [len(positions), V] of ``tokens`` [S] at ``positions``."""
    c = dict(c_items)
    hq, hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["hd"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    f32 = lambda t: t.astype(jnp.float32)
    mm = lambda a, b: _dot(f32(a), f32(b), "sd,df->sf", HIGHEST)
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, lp):
        h = _rms(x, lp["attn_norm"], eps)
        q = mm(h, lp["attn"]["wq"]).reshape(S, hq, hd)
        k = mm(h, lp["attn"]["wk"]).reshape(S, hkv, hd)
        v = mm(h, lp["attn"]["wv"]).reshape(S, hkv, hd)
        if c["qk_norm"]:
            q = _rms(q, lp["attn"]["q_norm"], eps)
            k = _rms(k, lp["attn"]["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        o = _attention(q, k, v, HIGHEST)
        x = x + mm(o, lp["attn"]["wo"])
        h = _rms(x, lp["ffn_norm"], eps)
        f = lp["ffn"]
        a = jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"])
        return x + mm(a, f["w_down"]), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    h = _rms(x[positions], params["final_norm"], eps)
    head = params["embed"].T if c["tied"] else params["head"]
    return mm(h, head)


def _c_items(c: dict) -> tuple:
    return tuple(sorted({
        "num_attention_heads": c["num_attention_heads"],
        "num_key_value_heads": c["num_key_value_heads"], "hd": head_dim(c),
        "rms_norm_eps": float(c["rms_norm_eps"]),
        "rope_theta": float(c["rope_theta"]),
        "qk_norm": bool(c.get("qk_norm", False)),
        "tied": bool(c["tie_word_embeddings"])}.items()))


class Reference:
    """The reference forward for configuration ``c`` and ``seed``, padded to
    ``seq_len`` tokens and ``n_pos`` positions so that one compiled program
    serves every request of a cell."""

    def __init__(self, c: dict, seed: int, *, seq_len: int, n_pos: int):
        self.c = c
        self.seq_len = Q_BLOCK * math.ceil(seq_len / Q_BLOCK)
        self.n_pos = n_pos
        self.params = make_weights(c, seed)

    def logits(self, prompt, served) -> np.ndarray:
        """Reference logits [n, V] at the positions where each of the ``n``
        served tokens was chosen: the last prompt position and each served
        token's but the last."""
        seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
        n, S = len(served), len(seq)
        if S > self.seq_len or n > self.n_pos:
            raise ValueError(f"request of {S} tokens / {n} served exceeds "
                             f"the reference's {self.seq_len} / {self.n_pos}")
        toks = np.zeros(self.seq_len, np.int32)
        toks[:S] = seq
        pos = np.zeros(self.n_pos, np.int32)
        pos[:n] = np.arange(len(prompt) - 1, S)
        out = _forward(self.params, jnp.asarray(toks), jnp.asarray(pos),
                       c_items=_c_items(self.c))
        return np.asarray(out[:n], np.float64)


def served_gaps(ref: Reference, prompt, served) -> np.ndarray:
    """Per served token: the reference's best logit minus its logit for the
    token that was served (0 where the served token is the reference's
    own first choice)."""
    lg = ref.logits(prompt, served)
    return lg.max(axis=1) - lg[np.arange(len(served)), np.asarray(served)]


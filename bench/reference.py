"""The plain reference: a float32 transformer in `jax.numpy`.

It imports nothing of the program. It reads the widths from the
configuration file and makes the weights from the seed with
`model.make_weights`, the same call that made the served weights, then
computes in float32 at the highest matmul precision: the embedding, the
layers of the configuration's family (`bench/families/<family>.py`'s
`layer`, which builds on the helpers here: `_rms`, `_rope`, `_attention`,
`_mm`), the final RMSNorm, and a tied or untied output head.

Attention runs in blocks of query rows and the layers in a scan, so a
sequence of several thousand tokens fits on one chip beside nothing else.

`served_gaps` is the comparison that decides `correct`: for each served
token, how far its reference logit lies below the reference's best at that
position.
"""
from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.model import family, make_weights

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _dot(a, b, spec, precision):
    return jnp.einsum(spec, a, b, precision=precision,
                      preferred_element_type=jnp.float32)


def _mm(a, b):
    """[S, d] @ [d, f] in float32 at the highest precision."""
    return _dot(a.astype(jnp.float32), b.astype(jnp.float32), "sd,df->sf",
                HIGHEST)


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


@partial(jax.jit, static_argnames=("layer", "c_json"))
def _forward(params, tokens, positions, *, layer, c_json):
    """Logits [len(positions), V] of ``tokens`` [S] at ``positions``, each
    layer computed by the family's ``layer``."""
    c = json.loads(c_json)
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(lambda x, lp: (layer(c, lp, x, pos), None), x,
                        params["layers"])
    h = _rms(x[positions], params["final_norm"], float(c["rms_norm_eps"]))
    head = (params["embed"].T if c["tie_word_embeddings"]
            else params["head"])
    return _mm(h, head)


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
                       layer=family(self.c).layer,
                       c_json=json.dumps(self.c, sort_keys=True))
        return np.asarray(out[:n], np.float64)


def served_gaps(ref: Reference, prompt, served) -> np.ndarray:
    """Per served token: the reference's best logit minus its logit for the
    token that was served (0 where the served token is the reference's
    own first choice)."""
    lg = ref.logits(prompt, served)
    return lg.max(axis=1) - lg[np.arange(len(served)), np.asarray(served)]


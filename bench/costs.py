"""Operations and bytes that the algorithm needs, worked out from shapes.

They count what serving needs, not what the current implementation does:
a decode step reads each weight it needs once and each lane's live keys
and values, not the padded block table it gathers today. A kernel that reads less
therefore raises its roofline share without making these counts stale.

What depends on the architecture comes from the configuration's family
(`bench/families/<family>.py`): the parameters computed per token
(``flops_params``) and the weight bytes a step of ``n`` tokens reads
(``weight_bytes``), which with routed experts grow with ``n``. The
attention and key/value terms are the same for every family.
"""
from __future__ import annotations

from bench.model import family, head_dim


class Costs:
    def __init__(self, c: dict, bytes_per_el: int = 2):
        self.c, self.family = c, family(c)
        p = self.family.flops_params(c)
        self.layer_params, self.head_params = p["layers"], p["head"]
        self.b = bytes_per_el
        self.L = c["num_hidden_layers"]
        self.hq = c["num_attention_heads"]
        self.hd = head_dim(c)
        # keys and values of one token, all layers
        self.kv_bytes = 2 * self.L * c["num_key_value_heads"] * self.hd * self.b

    def weight_bytes(self, n: int):
        """Weight bytes one step of ``n`` tokens reads: a decode step's
        lanes, or a prefill chunk's length."""
        return self.family.weight_bytes(self.c, n)

    def attn_flops(self, ctx: int) -> float:
        """Scores and weighted values of one query over ``ctx`` positions."""
        return 4.0 * self.L * self.hq * self.hd * ctx

    def decode_step(self, ctxs) -> tuple[float, float]:
        """(FLOPs, bytes) of one decode step whose lanes each produce one
        token attending ``ctx`` positions (the new one included)."""
        flops = sum(2.0 * (self.layer_params + self.head_params)
                    + self.attn_flops(c) for c in ctxs)
        nbytes = (self.weight_bytes(len(ctxs))
                  + sum((c - 1) * self.kv_bytes for c in ctxs)   # live K/V
                  + len(ctxs) * self.kv_bytes)                    # new K/V
        return flops, nbytes

    def prefill_chunk(self, start: int, n: int) -> tuple[float, float]:
        """(FLOPs, bytes) of a prompt chunk of ``n`` tokens after ``start``
        resident ones: the layers for every token, causal attention, the
        head for the last token; the weights its tokens need once, the
        resident K/V read and the chunk's K/V written."""
        attn = 4.0 * self.L * self.hq * self.hd * (n * start
                                                    + n * (n + 1) / 2)
        flops = 2.0 * n * self.layer_params + 2.0 * self.head_params + attn
        nbytes = self.weight_bytes(n) + (start + n) * self.kv_bytes
        return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's least seconds: the larger of the two bounds."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])

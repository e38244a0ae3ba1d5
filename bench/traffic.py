"""One general traffic generator, driven by a mix file `bench/traffic/<mix>.json`.

Every seed gets the same multiset of request sizes and inter-arrival gaps,
in another order: sizes are evenly spaced quantiles of the mix's length
distributions and gaps are quantiles of the arrival process, permuted by
the seed. The seed then only reorders the work, so runs of different seeds
do the same amount of it. Token ids are drawn from the seed.

A mix file holds:

- ``loop``: ``"open"`` (arrivals on a schedule, independent users) or
  ``"closed"`` (``outstanding_per_lane`` × decode lanes requests kept in
  flight, each client sending its next request when the last one ends);
- ``arrival`` (open loop): ``{"process": "poisson" | "burst", "rate_rps": r}``,
  and for ``burst`` also ``burst_size`` and ``duty``;
- ``prompt_tokens`` / ``output_tokens``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}``;
- ``shared_prefix`` (optional): ``{"tokens": n, "groups": g}``: every prompt
  starts with one of ``g`` shared prefixes of ``n`` tokens;
- ``lead_in_s``: seconds of the same traffic sent before the window opens,
  so that the window does not open on an empty server;
- ``drain_s``: how long after the window closes a window request may still
  finish before it counts as failed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

# quantile sets for a closed loop are cycled through in this many requests
CLOSED_CYCLE = 512


# --- fixed quantile sets, permuted by the seed

def _mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _normal_ppf(p: np.ndarray) -> np.ndarray:
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error < 1.2e-9): enough for quantiles of a length mix."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
               + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
                + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3])
                                * r + b[4]) * r + 1)
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -(((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4]) * q
                + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3]) * q + 1)
    return out


def length_set(spec: dict, n: int) -> np.ndarray:
    """``n`` token counts at evenly spaced quantiles of ``spec``'s clipped
    distribution (sorted ascending)."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = _normal_ppf(_mid_quantiles(n))
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def gap_set(arrival: dict, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps (seconds) whose order a seed permutes. For
    ``poisson`` they are exponential quantiles at ``rate_rps``; ``burst``
    gives bursts of ``burst_size`` at ``rate_rps / duty``, each followed by
    an off gap sized so that the long-run rate stays ``rate_rps`` (the
    on/off process of the program's ``serving/ingress.burst_arrivals``)."""
    rate = float(arrival["rate_rps"])
    if rate <= 0:
        raise ValueError(f"arrival rate must be > 0, got {rate}")
    e = -np.log1p(-_mid_quantiles(n))        # unit-mean exponential quantiles
    if arrival["process"] == "poisson":
        return e / rate
    if arrival["process"] == "burst":
        size, duty = int(arrival["burst_size"]), float(arrival["duty"])
        gaps = e * duty / rate                # gaps inside a burst
        off = -np.log1p(-_mid_quantiles(max(1, n // size)))
        gaps[::size][:len(off)] += off * size * (1.0 - duty) / rate
        return gaps
    raise ValueError(f"unknown arrival process {arrival['process']!r}")


@dataclass
class Request:
    """One request of the schedule: ``at`` is its scheduled arrival in
    seconds from the window's opening (negative in the lead-in; None in a
    closed loop, where it is sent when its client is free)."""
    idx: int
    prompt: np.ndarray
    max_new_tokens: int
    at: float | None


class Traffic:
    """A mix file bound to a seed: the open-loop schedule, or the closed
    loop's request source."""

    def __init__(self, mix: dict, *, seed: int, seconds: float,
                 vocab_size: int, first_id: int = 0):
        self.mix = mix
        self.seconds = float(seconds)
        self.vocab_size = vocab_size
        self.lead_in_s = float(mix["lead_in_s"])
        self.drain_s = float(mix["drain_s"])
        self.rng = np.random.default_rng(seed)
        pre = mix.get("shared_prefix")
        self.prefixes = ([self._tokens(int(pre["tokens"]))
                          for _ in range(int(pre["groups"]))] if pre else [])
        self.next_id = first_id      # request ids, unique in a server's life
        self._cycle = None
        self._closed_sent = 0

    # ------------------------------------------------------------- sizes --
    @property
    def max_prompt(self) -> int:
        pre = self.mix.get("shared_prefix")
        return int(self.mix["prompt_tokens"]["max"]) + (
            int(pre["tokens"]) if pre else 0)

    @property
    def min_prompt(self) -> int:
        pre = self.mix.get("shared_prefix")
        return int(self.mix["prompt_tokens"]["min"]) + (
            int(pre["tokens"]) if pre else 0)

    @property
    def max_context(self) -> int:
        """The longest request of the mix: prompt plus output tokens."""
        return self.max_prompt + int(self.mix["output_tokens"]["max"])

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab_size, size=n, dtype=np.int32)

    def _request(self, plen: int, olen: int, at) -> Request:
        body = self._tokens(int(plen))
        if self.prefixes:
            pre = self.prefixes[int(self.rng.integers(len(self.prefixes)))]
            body = np.concatenate([pre, body])
        req = Request(self.next_id, body, int(olen), at)
        self.next_id += 1
        return req

    def _sizes(self, n: int) -> tuple[np.ndarray, np.ndarray]:
        p = self.rng.permutation(length_set(self.mix["prompt_tokens"], n))
        o = self.rng.permutation(length_set(self.mix["output_tokens"], n))
        return p, o

    # --------------------------------------------------------- open loop --
    def schedule(self) -> list[Request]:
        """Lead-in and window arrivals, in time order. The window's gaps are
        scaled to span exactly ``seconds``, so every seed sends the same
        number of requests in it."""
        arrival = self.mix["arrival"]
        rate = float(arrival["rate_rps"])
        n_lead = int(round(rate * self.lead_in_s))
        n_win = max(1, int(round(rate * self.seconds)))
        out = []
        if n_lead:
            g = self.rng.permutation(gap_set(arrival, n_lead))
            g *= self.lead_in_s / g.sum()
            at = -np.cumsum(g)[::-1]
            for t, p, o in zip(at, *self._sizes(n_lead)):
                out.append(self._request(p, o, float(t)))
        g = self.rng.permutation(gap_set(arrival, n_win))
        g *= self.seconds / g.sum()
        at = np.concatenate([[0.0], np.cumsum(g)[:-1]])
        for t, p, o in zip(at, *self._sizes(n_win)):
            out.append(self._request(p, o, float(t)))
        return out

    # ------------------------------------------------------- closed loop --
    def outstanding(self, decode_width: int) -> int:
        return int(self.mix["outstanding_per_lane"]) * decode_width

    def next_closed(self) -> Request:
        """The next request a closed-loop client sends, from a quantile set
        of :data:`CLOSED_CYCLE` sizes in seed order, cycled."""
        k = self._closed_sent % CLOSED_CYCLE
        self._closed_sent += 1
        if k == 0:
            self._cycle = self._sizes(CLOSED_CYCLE)
        return self._request(self._cycle[0][k], self._cycle[1][k], None)


def warmup_lengths(min_prompt: int, max_prompt: int,
                   buckets=(64, 128, 256)) -> list[int]:
    """Prompt lengths that make the batcher dispatch every prefill chunk
    length the mix can: each bucket and every ragged tail 1..min-1 (the
    program splits a prompt greedily into the buckets plus a tail). Each
    length lies inside the mix's own range."""
    small, base = min(buckets), sum(buckets)
    out = []
    for tail in range(small):
        n = base + tail
        n += max(0, math.ceil((min_prompt - n) / max(buckets))) * max(buckets)
        if n > max_prompt:
            raise ValueError(f"no warm-up prompt for tail {tail} fits in "
                             f"[{min_prompt}, {max_prompt}]")
        out.append(n)
    return out

"""Partition execution: the HeteroCtx that models thread through every matmul.

``HeteroCtx.matmul(x, w, name=site)`` consults the PartitionPlan (or the
engine mode) and executes the chosen strategy:

  xla_only  : one flexible-path matmul
  mxu_only  : aligned Pallas MXU-path matmul (pad M/K/N to 128 = the NPU's
              internal stage padding); order-exchange applied when profitable
              (NPU-2: y = x@w  ->  y = (w^T @ x^T)^T when x is the smaller,
              better-stationary operand)
  pad       : mxu_only with M padded up to the decision's bucket
  weight    : weight-centric split — MXU path computes the 128-aligned major
              column block, XLA path the remainder columns; the two matmuls
              are data-independent so XLA schedules them concurrently (the
              GPU||NPU analogue)
  act       : activation-centric split — first ``m_bucket`` tokens on the MXU
              path, ragged tail on the XLA path
  hybrid    : act bucketing + weight split of the bucketed part

Everything happens at trace time (static shapes), so a jitted program bakes
in the plan — the paper's 'graphs generated in advance by the solver'.

Speculative-decoding verification dispatches use a context view from
``for_verify(k, lanes)``: same strategies, but sites resolve through the
plan's VERIFY decisions (solver.py ``solve_verify``) instead of the generic
nearest-M grid.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.hetero_matmul.ops import (mxu_matmul, mxu_q4_matmul,
                                             mxu_quant_matmul)

from .characteristics import V5E, mxu_matmul_time_us
from .solver import Decision, PartitionPlan

ALIGN = 128


def _pad_to(x, mult, axis):
    r = x.shape[axis] % mult
    if r == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, mult - r)
    return jnp.pad(x, pads)


@jax.tree_util.register_pytree_node_class
class QuantWeight:
    """A quantized weight that flows anywhere an fp weight array does.

    Per-output-channel symmetric quantization in one of two storage formats
    (the paper's deployment stances):

      * ``int8``  — ``wq`` int8 ``[..., K, N]``, ``scale`` f32 ``[..., N]``
      * ``w4a16`` — ``wq`` int8 ``[..., ceil(K/2), N]`` with two int4 codes
        packed per byte along K (rows 2r, 2r+1 -> lo, hi nibbles), same
        per-column scale

    Registered as a pytree node (arrays are children, ``fmt``/``k`` are
    static aux data) so stacked per-layer quantized weights thread through
    ``lax.scan``/``jit`` exactly like fp arrays: scan slices the leading
    layer axis of ``wq`` and ``scale`` and the model sees a per-layer
    ``QuantWeight``. ``k`` is the LOGICAL contraction dim — the int4 packer
    zero-pads odd K, so storage and logical K can differ.
    """

    def __init__(self, wq, scale, fmt: str, k: int):
        assert fmt in ("int8", "w4a16"), fmt
        self.wq = wq
        self.scale = scale
        self.fmt = fmt
        self.k = int(k)

    def tree_flatten(self):
        return (self.wq, self.scale), (self.fmt, self.k)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def shape(self):
        """Logical [..., K, N] shape (what the fp weight would report)."""
        return (*self.wq.shape[:-2], self.k, self.wq.shape[-1])

    @property
    def n(self) -> int:
        return self.wq.shape[-1]

    def dequant(self, dtype=jnp.float32):
        """Dequantize-then-cast reference expansion (the XLA-path execution
        and the conformance oracle)."""
        if self.fmt == "int8":
            q = self.wq.astype(jnp.float32)
        else:
            lo = jnp.left_shift(self.wq, 4) >> 4     # sign-extended low nibble
            hi = self.wq >> 4                        # arithmetic high nibble
            k2, n = self.wq.shape[-2], self.wq.shape[-1]
            q = jnp.stack([lo, hi], axis=-2).reshape(
                *self.wq.shape[:-2], 2 * k2, n)[..., :self.k, :]
            q = q.astype(jnp.float32)
        return (q * self.scale[..., None, :]).astype(dtype)

    def slice_n(self, a: int, b: int) -> "QuantWeight":
        """Column (output-channel) slice — packing is along K, so any N
        split point is representable; this is what makes the solver's
        weight/hybrid strategies legal on quantized sites."""
        return QuantWeight(self.wq[..., :, a:b], self.scale[..., a:b],
                           self.fmt, self.k)


def _weight_cols(w, a: int, b: int):
    return w.slice_n(a, b) if isinstance(w, QuantWeight) else w[:, a:b]


def matmul_any(x, w, name: Optional[str] = None):
    """Plan-free matmul over fp or quantized weights — the model code's
    fallback when no HeteroCtx is threaded (training, references)."""
    if isinstance(w, QuantWeight):
        return x @ w.dequant(x.dtype)
    return x @ w


@dataclass
class HeteroCtx:
    """mode: 'xla' | 'mxu' | 'hetero-layer' | 'hetero-tensor'."""
    mode: str = "hetero-tensor"
    plan: Optional[PartitionPlan] = None
    order_exchange: bool = True
    layer_mxu_threshold: int = 128       # hetero-layer: M >= this -> MXU path
    # VERIFY site class (speculative decoding): when set to (k, lanes), every
    # matmul consults plan.verify_decisions first — the solver's plan for the
    # M = lanes*(k+1) verification dispatch, not the generic nearest-M grid
    verify_key: Optional[tuple] = None

    def for_verify(self, k: int, lanes: int = 1) -> "HeteroCtx":
        """A view of this context for verification dispatches: same plan,
        same mode, but matmul sites resolve through the VERIFY decisions
        solved for (k, lanes). Callers bake the returned ctx into the jitted
        ``paged_verify`` graph (trace-time, like every other decision)."""
        return replace(self, verify_key=(k, lanes))

    # ---------------------------------------------------------- primitives --
    def _mxu(self, x2, w):
        """Aligned MXU-path matmul with internal stage padding + NPU-2
        order-exchange. Quantized weights dispatch the in-VMEM-dequant
        kernels (``mxu_quant_matmul`` / ``mxu_q4_matmul``); order-exchange
        is fp-only (a packed weight can't become the streamed operand)."""
        if isinstance(w, QuantWeight):
            return self._mxu_quant(x2, w)
        M, K = x2.shape
        N = w.shape[1]
        use_exchange = (self.order_exchange and
                        mxu_matmul_time_us(N, K, M) < mxu_matmul_time_us(M, K, N))
        xp = _pad_to(_pad_to(x2, ALIGN, 0), ALIGN, 1)
        wp = _pad_to(_pad_to(w.astype(x2.dtype), ALIGN, 0), ALIGN, 1)
        if use_exchange:
            y = mxu_matmul(wp.T, xp.T).T
        else:
            y = mxu_matmul(xp, wp)
        return y[:M, :N]

    def _mxu_quant(self, x2, w: QuantWeight):
        """Stage padding for the quantized MXU kernels: codes pad with 0
        (dequants to exactly 0 against any scale), scales pad with 0 — the
        padded columns are sliced off. x pads along K with zeros, so the
        code rows beyond the logical K contribute nothing either way."""
        M = x2.shape[0]
        N = w.n
        xp = _pad_to(_pad_to(x2, ALIGN, 0), ALIGN, 1)
        sp = _pad_to(w.scale, ALIGN, -1)
        if w.fmt == "int8":
            wqp = _pad_to(_pad_to(w.wq, ALIGN, 0), ALIGN, 1)
            y = mxu_quant_matmul(xp, wqp, sp)
        else:
            # packed rows count ceil(K/2) pads to Kp//2 (Kp = xp's padded K)
            wqp = _pad_to(_pad_to(w.wq, ALIGN // 2, 0), ALIGN, 1)
            y = mxu_q4_matmul(xp, wqp, sp)
        return y[:M, :N]

    def _xla(self, x2, w):
        if isinstance(w, QuantWeight):
            return x2 @ w.dequant(x2.dtype)
        return x2 @ w.astype(x2.dtype)

    # ------------------------------------------------------------ dispatch --
    def matmul(self, x, w, name: Optional[str] = None):
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        M, N = x2.shape[0], w.shape[1]

        if self.mode == "xla":
            y = self._xla(x2, w)
        elif self.mode == "mxu":
            y = self._mxu(x2, w)
        elif self.mode == "hetero-layer":
            y = self._mxu(x2, w) if M >= self.layer_mxu_threshold else \
                self._xla(x2, w)
        else:
            y = self._tensor_level(x2, w, name, M, N)
        return y.reshape(*lead, N)

    def _tensor_level(self, x2, w, name, M, N):
        dec = None
        if self.plan is not None and name is not None:
            if self.verify_key is not None:
                dec = self.plan.verify_decision(name, *self.verify_key)
            if dec is None:
                dec = self.plan.decision(name, M)
            if dec is None:       # nearest-M fallback (solver probes a grid)
                ms = sorted({m for (s, m) in self.plan.decisions if s == name})
                if ms:
                    nearest = min(ms, key=lambda m: abs(m - M))
                    dec = self.plan.decision(name, nearest)
        if dec is None:
            return self._mxu(x2, w) if M >= ALIGN else self._xla(x2, w)
        return self.execute(dec, x2, w)

    def execute(self, dec: Decision, x2, w):
        M, N = x2.shape[0], w.shape[1]
        s = dec.strategy
        if s == "xla_only":
            return self._xla(x2, w)
        if s in ("mxu_only", "pad"):
            return self._mxu(x2, w)     # _mxu pads M internally (stage padding)
        if s == "weight":
            n = min(dec.n_split, N - 1)
            y1 = self._mxu(x2, _weight_cols(w, 0, n))
            y2 = self._xla(x2, _weight_cols(w, n, N))
            return jnp.concatenate([y1, y2], axis=-1)
        if s == "act":
            b = min(dec.m_bucket, M - 1) if dec.m_bucket < M else M - ALIGN
            b = max(b, 1)
            y1 = self._mxu(x2[:b], w)
            y2 = self._xla(x2[b:], w)
            return jnp.concatenate([y1, y2], axis=0)
        if s == "hybrid":
            b = min(dec.m_bucket, M - 1)
            b = max(b, 1)
            n = min(dec.n_split, N - 1)
            y1a = self._mxu(x2[:b], _weight_cols(w, 0, n))
            y1b = self._xla(x2[:b], _weight_cols(w, n, N))
            y2 = self._xla(x2[b:], w)
            return jnp.concatenate(
                [jnp.concatenate([y1a, y1b], axis=-1), y2], axis=0)
        raise ValueError(f"unknown strategy {s}")

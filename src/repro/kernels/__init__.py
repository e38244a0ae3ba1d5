"""Pallas kernels for the MXU path and the attention/scan hot spots.

Every kernel entry point takes ``interpret: bool | None = None``. ``None``
means "work it out from the platform": compiled Mosaic on a TPU, the
Pallas interpreter anywhere else (the CPU test suite). Callers above the
kernels never choose; a compile test for a described TPU passes ``False``.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """Whether JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret exactly when the platform is not a TPU."""
    return (not on_tpu()) if interpret is None else interpret

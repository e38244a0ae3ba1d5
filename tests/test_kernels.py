"""Kernel-specific PROPERTY tests (tile counts, GQA ratios, block sizes,
model-path equivalence). Plain dtype/shape parity — including ragged-M and
odd-K edge cases — lives in the unified conformance harness
(test_kernel_conformance.py, one shared parameterization for all four
kernel packages against their ref.py oracles)."""
import jax
import jax.numpy as jnp
import pytest

from conftest import rel_err

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.hetero_matmul.ops import mxu_matmul
from repro.kernels.hetero_matmul.ref import matmul_ref

RNG = jax.random.PRNGKey(0)


@pytest.mark.parametrize("tm,tk,tn", [(1, 1, 1), (3, 1, 2), (2, 2, 2)])
def test_mxu_matmul_property(tm, tk, tn):
    """Any tile-aligned shape agrees with the oracle."""
    M, K, N = tm * 128, tk * 128, tn * 128
    k1, k2 = jax.random.split(RNG)
    x = jax.random.normal(k1, (M, K), jnp.float32)
    w = jax.random.normal(k2, (K, N), jnp.float32)
    assert rel_err(mxu_matmul(x, w),
                   matmul_ref(x, w)) < 2e-6


@pytest.mark.parametrize("sblocks,g,causal", [
    (1, 1, True), (2, 4, True), (3, 2, False), (4, 1, False), (2, 2, True)])
def test_flash_attention_property(sblocks, g, causal):
    """Any GQA group size / block count / causality matches the oracle."""
    S = sblocks * 64
    Hkv, D = 2, 64
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (1, S, Hkv * g, D), jnp.float32)
    k = jax.random.normal(ks[1], (1, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (1, S, Hkv, D), jnp.float32)
    o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    assert rel_err(o, attention_ref(q, k, v, causal=causal)) < 2e-6


@pytest.mark.parametrize("length,bk", [
    (1, 64), (63, 64), (64, 64), (65, 128), (200, 128), (256, 256),
    (129, 256)])
def test_decode_attention_property(length, bk):
    """Valid-prefix masking is exact for any length and block size."""
    B, S, Hq, Hkv, D = 1, 256, 4, 2, 64
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    o = decode_attention(q, kc, vc, length, block_k=bk)
    assert rel_err(o, decode_attention_ref(q, kc, vc, length)) < 2e-6


@pytest.mark.parametrize("chunk", [32, 64])
def test_ssd_scan_kernel_matches_model_path(chunk):
    """The full Pallas SSD scan equals the model's chunked-recurrence path
    (the integration contract the zamba2 cells rely on)."""
    from repro.kernels.ssm_scan.ops import ssd_scan
    from repro.models.mamba2 import ssd_chunked
    B, S, nh, hd, N = 2, 128, 4, 64, 64
    ks = jax.random.split(RNG, 5)
    xh = jax.random.normal(ks[0], (B, S, nh, hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.5)
    B_ = jax.random.normal(ks[3], (B, S, N)) * 0.5
    C_ = jax.random.normal(ks[4], (B, S, N)) * 0.5
    y1, s1 = ssd_scan(xh, dt, A, B_, C_, chunk=chunk)
    y2, s2 = ssd_chunked(xh, dt, A, B_, C_, chunk=chunk)
    assert float(jnp.abs(y1 - y2).max()) < 1e-4
    assert float(jnp.abs(s1 - s2).max()) < 1e-4

#!/usr/bin/env python3
"""Chip smoke test: paged serving of Qwen3-1.7B at full width on a TPU.

    python chip_smoke.py            # one chip: kernels, phases A, B, C
    python chip_smoke.py --tp 4     # four chips: TP=4 serving against TP=1

Everything runs in this one process, through the serving launcher
(``repro.launch.serve.main``) with random bf16 weights drawn from a seed:

  kernels  each MXU-path Pallas kernel (bf16, int8, W4A16) compiled at
           Qwen3-1.7B widths, against its ``ref.py`` oracle.
  A        plain paged serving: 16 requests, prompts of 128-1024 tokens,
           64 new tokens each, 8 decode lanes, fused 8-step decode windows,
           a pool of 1,024 blocks of 32 tokens.
  B        the same requests under ``--engine-mode hetero-tensor``: prefill
           matmuls follow the solver's plan through the compiled Pallas
           kernels. One request's first-step logits are compared with A's.
  C        the paged prefill logits of that request against the
           dense-cache ``model.prefill``.

``--tp 4`` runs only tensor-parallel paged serving of the same requests
and its TP=1 comparison on the first of the same four chips, and checks
that the layout put shards on all four.

Without a TPU the script exits nonzero before serving. Any failed phase
raises, so the exit is nonzero. The last line of stdout is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

SEED = 0
PROBE = 0                # the request whose logits phases B and C compare
SERVE = ["--arch", "qwen3-1.7b", "--seed", str(SEED), "--batched", "--paged",
         "--sync", "device", "--window", "8", "--decode-width", "8",
         "--requests", "16", "--min-prompt-len", "128", "--prompt-len", "1025",
         "--new-tokens", "64", "--block-size", "32", "--max-blocks", "1024"]
NEW_TOKENS = 64

# Relative L2 error allowed between two bf16 forward passes that differ only
# in how matmuls (B) or attention (C) are lowered. bf16 keeps 8 significant
# bits, so every rounded matmul output carries a relative error of up to
# 2^-9 (rms ~2^-10). The 28 layers round 7 matmul outputs each in both
# passes, at different points of different accumulation orders; as a random
# walk that is sqrt(2 * 196) * 2^-10 ~ 0.02 of the logits' norm, and the
# bound takes 2.5x that. It still fails on a wrong mask, position, block
# table or kernel, each of which moves the logits by O(1) of their norm.
LOGITS_RTOL = 0.05
# A kernel's output is rounded once to bf16 (2^-9 relative) after an f32
# accumulation, and its oracle accumulates in f32 at the highest precision:
# 2^-7 of the output's largest magnitude leaves 4x for accumulation order.
KERNEL_RTOL = 2.0 ** -7
QWEN3_SITES = {"qkv_o": (2048, 6144), "down": (6144, 2048),
               "head": (2048, 151936)}
KERNEL_M = 256


def rel_l2(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def common_prefix(a, b) -> int:
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return n


class CompileWatch:
    """Counts persistent-cache hits and writes and sums backend compile
    seconds (persistent-cache loads included) through jax.monitoring."""

    def __init__(self):
        import jax
        self.hits = self.writes = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def line(self) -> str:
        return (f"compile {self.seconds:.1f} s, persistent cache "
                f"{self.hits} hits, {self.writes} writes")


def peak_gb(device, stat="peak_bytes_in_use") -> float:
    return device.memory_stats()[stat] / 1e9


def phase_kernels():
    """Each MXU-path kernel, compiled, against its oracle."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.hetero_matmul.ops import (
        dequant_int4_ref, mxu_matmul, mxu_q4_matmul, mxu_quant_matmul,
        quantize_weight, quantize_weight_int4)
    from repro.kernels.hetero_matmul.ref import matmul_ref, quant_matmul_ref

    key = jax.random.PRNGKey(SEED)
    for site, (K, N) in QWEN3_SITES.items():
        kx, kw, key = jax.random.split(key, 3)
        x = jax.random.normal(kx, (KERNEL_M, K), jnp.bfloat16)
        w = (jax.random.normal(kw, (K, N)) / K ** 0.5).astype(jnp.bfloat16)
        wq, s = quantize_weight(w)
        wq4, s4 = quantize_weight_int4(w)
        # the oracles at full f32 precision; the kernels as they are built
        with jax.default_matmul_precision("highest"):
            refs = {"bf16": matmul_ref(x, w),
                    "int8": quant_matmul_ref(x, wq, s),
                    "w4a16": matmul_ref(x, dequant_int4_ref(wq4, s4))}
        cases = {"bf16": (mxu_matmul(x, w), refs["bf16"]),
                 "int8": (mxu_quant_matmul(x, wq, s), refs["int8"]),
                 "w4a16": (mxu_q4_matmul(x, wq4, s4), refs["w4a16"])}
        for kind, (out, ref) in cases.items():
            out = out.astype(jnp.float32)
            ref = ref.astype(jnp.float32)
            err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
            print(f"kernel {kind:7s} {site:5s} M={KERNEL_M} K={K} N={N}: "
                  f"max err {err:.2e} of max |ref| (limit {KERNEL_RTOL:.2e})",
                  flush=True)
            assert out.shape == (KERNEL_M, N), (kind, site, out.shape)
            assert bool(jnp.isfinite(out).all()), (kind, site)
            assert err <= KERNEL_RTOL, (kind, site, err)


def serve(extra):
    """One run of the serving launcher; checks what any run must satisfy
    and returns (result, token streams by request id)."""
    from repro.launch import serve as launcher
    res = launcher.main(SERVE + extra)
    cb = res.batcher
    vocab = cb.cfg.vocab_size
    assert len(res.handles) == 16
    streams = {}
    for h in res.handles:
        assert h.done, h.rid
        assert len(h.tokens) == NEW_TOKENS, (h.rid, len(h.tokens))
        assert all(0 <= t < vocab for t in h.tokens), h.rid
        streams[h.rid] = list(h.tokens)
    lens = [len(p) for p in res.prompts]
    assert 128 <= min(lens) and max(lens) <= 1024, lens
    cb.kv.assert_drained()
    return res, streams


def probe_logits(res, streams):
    """The probe request's first-step logits, recomputed through the
    batcher's own prefill; their argmax must be the token it served."""
    import numpy as np
    logits = np.asarray(res.batcher.prompt_logits(res.prompts[PROBE]),
                        np.float32)
    assert logits.shape == (res.batcher.cfg.vocab_size,), logits.shape
    assert np.isfinite(logits).all()
    assert int(np.argmax(logits)) == streams[PROBE][0], \
        (int(np.argmax(logits)), streams[PROBE][0])
    return logits


def dense_prefill_logits(res):
    """Phase C's reference: the dense-cache prefill of the probe prompt."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cb = res.batcher
    prompt = jnp.asarray(res.prompts[PROBE], jnp.int32)[None]
    cache = cb.model.init_cache(batch=1, max_len=prompt.shape[1],
                                dtype=jnp.dtype(cb.cfg.compute_dtype))
    logits, _ = jax.jit(cb.model.prefill)(cb.params, prompt, cache)
    return np.asarray(logits[0, -1], np.float32)


def agreement(a, b) -> str:
    same = sum(common_prefix(a[r], b[r]) for r in a)
    total = sum(len(a[r]) for r in a)
    full = sum(a[r] == b[r] for r in a)
    return (f"{same}/{total} greedy tokens agree before the first "
            f"divergence; {full}/{len(a)} streams identical")


def one_chip(dev):
    phase_kernels()
    print(f"peak after kernels: {peak_gb(dev):.2f} GB", flush=True)

    res, a = serve([])
    logits_a = probe_logits(res, a)
    dense = dense_prefill_logits(res)
    err_c = rel_l2(logits_a, dense)
    print(f"phase A: served 16 requests, prompt lengths "
          f"{sorted(map(len, res.prompts))}, peak {peak_gb(dev):.2f} GB",
          flush=True)
    print(f"phase C: paged vs dense prefill logits, request {PROBE} "
          f"({len(res.prompts[PROBE])} tokens): rel L2 {err_c:.2e} "
          f"(limit {LOGITS_RTOL}), argmax equal "
          f"{int(logits_a.argmax()) == int(dense.argmax())}", flush=True)
    assert err_c <= LOGITS_RTOL, err_c
    del res                 # A's weights and pool leave the device
    gc.collect()

    res, b = serve(["--engine-mode", "hetero-tensor"])
    logits_b = probe_logits(res, b)
    err_b = rel_l2(logits_b, logits_a)
    print(f"phase B: hetero-tensor vs A first-step logits, request {PROBE}: "
          f"rel L2 {err_b:.2e} (limit {LOGITS_RTOL}); {agreement(b, a)}; "
          f"peak {peak_gb(dev):.2f} GB", flush=True)
    assert err_b <= LOGITS_RTOL, err_b


def four_chips(devices):
    """TP=4 against TP=1 on the first of the same four chips."""
    assert len(devices) >= 4, f"--tp 4 needs four devices, found {devices}"
    res, one = serve([])
    logits_1 = probe_logits(res, one)
    print(f"TP=1 on {devices[0]}: served, peak {peak_gb(devices[0]):.2f} GB",
          flush=True)
    del res
    gc.collect()

    res, four = serve(["--tp", "4"])
    cb = res.batcher
    n_kv = cb.cfg.n_kv_heads
    for name, leaf in cb.kv.pool.items():
        shards = leaf.addressable_shards
        assert {s.device for s in shards} == set(devices[:4]), name
        assert all(s.data.shape[3] == n_kv // 4 for s in shards), name
    for leaf in (cb.params["layers"]["attn"]["wq"],
                 cb.params["layers"]["ffn"]["w_gate"]):
        assert {s.device for s in leaf.addressable_shards} == \
            set(devices[:4])
        assert all(s.data.shape[-1] == leaf.shape[-1] // 4
                   for s in leaf.addressable_shards)
    used = [peak_gb(d, "bytes_in_use") for d in devices[:4]]
    print("TP=4 bytes in use per device (GB): "
          + ", ".join(f"{u:.2f}" for u in used), flush=True)
    assert min(used) >= 0.5 * max(used), used
    logits_4 = probe_logits(res, four)
    err = rel_l2(logits_4, logits_1)
    print(f"TP=4 vs TP=1 first-step logits, request {PROBE}: rel L2 "
          f"{err:.2e} (limit {LOGITS_RTOL}), bit-identical "
          f"{bool((logits_4 == logits_1).all())}; {agreement(four, one)}",
          flush=True)
    assert err <= LOGITS_RTOL, err


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", type=int, default=1, choices=[1, 4],
                    help="4: run only TP=4 serving and its TP=1 comparison")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    watch = CompileWatch()
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache "
          f"{cache_dir}", flush=True)

    if args.tp == 4:
        four_chips(devices)
    else:
        one_chip(dev)

    print(f"{watch.line()}; peak {peak_gb(dev):.2f} GB on {dev}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

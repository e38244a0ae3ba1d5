"""Fast synchronization (paper §4.3), TPU-native.

The paper's problem: host-driver sync (clFinish ~400us) between every
GPU/NPU kernel dwarfs decode kernels. The JAX analogue is the host-stepped
decode loop: one dispatch + block_until_ready + host round-trip per token.
The fix is the same idea as the paper's shared-buffer flag polling — keep
the whole loop on device:

  * ``generate_on_device``  — a single jitted ``lax.scan`` over decode steps
    with donated cache buffers: zero host round-trips ("fast sync").
  * ``generate_host_loop``  — the baseline: one jitted decode_step per token,
    host-synced each step (the clFinish analogue). ``hard_sync=True`` adds a
    device->host token fetch per step (the worst case the paper measures).
  * ``paged_decode_window`` — the paged-serving analogue: one jitted scan
    running a fixed WINDOW of batched paged decode steps per dispatch
    (scatter cache writes inside the scan, donated pool buffers), so the
    scheduler pays one host round-trip per window instead of per token.
    Mid-window termination (per-lane token budget or EOS) is handled by
    masking: a finished lane's block table is swapped to the null table and
    its length to 0, so its writes sink into the pool's null block exactly
    like an inactive lane. A window can additionally CARRY an in-flight
    prefill chunk (stage-parallel mixed batching, §4.1/§4.2): the first
    step of the window runs ``model.mixed_step`` — every decode lane plus
    one aligned prefill chunk of an admitting request in the same graph —
    so admission rides along a decode dispatch instead of stalling it.

``measure_dispatch_overhead`` quantifies the per-dispatch cost on the current
backend — the number the solver uses as T_sync in 'host' mode.
"""
from __future__ import annotations

import time
from functools import lru_cache, partial

import jax
import jax.numpy as jnp


def fence(*values):
    """The repo's ONE sanctioned host-side synchronization point: block
    until ``values`` are resolved on device, and return them unchanged.

    Every library-side ``block_until_ready`` routes through here (repolint's
    host-sync rule enforces it), so grepping for ``fence(`` enumerates all
    planned sync sites — the discipline the paper's §4.3 argues for. Returns
    the single value un-tupled for the common one-arg case."""
    for v in values:
        jax.block_until_ready(v)
    return values[0] if len(values) == 1 else values


def _greedy(logits):
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]


@partial(jax.jit, static_argnames=("decode_step", "n_steps"), donate_argnums=(2,))
def _device_loop(params, first_token, cache, *, decode_step, n_steps: int):
    def step(carry, _):
        token, cache = carry
        logits, cache = decode_step(params, token, cache)
        nxt = _greedy(logits)
        return (nxt, cache), nxt[:, 0]

    (_, cache), toks = jax.lax.scan(step, (first_token, cache), None,
                                    length=n_steps)
    return toks.T, cache        # [B, n_steps]


def generate_on_device(model, params, first_token, cache, n_steps: int):
    """Fast-sync path: the entire decode loop is one device program."""
    return _device_loop(params, first_token, cache,
                        decode_step=model.decode_step, n_steps=n_steps)


def _masked_step(run, carry, key, *, block_tables, sampler, eos_id):
    """One masked batched decode step shared by the pure and mixed windows.

    ``run(token, eff_tables, eff_lengths, pool) -> (logits, extra, pool)``
    is the step body (plain paged decode, or a mixed decode+prefill step
    whose ``extra`` is the prefill-chunk logits). Finished/inactive lanes
    are masked: null block table + length 0 sinks their write into the null
    block and keeps the step fully batched.
    """
    token, pool, lengths, remaining = carry
    active = remaining > 0
    eff_tables = jnp.where(active[:, None], block_tables, 0)
    eff_lengths = jnp.where(active, lengths, 0)
    logits, extra, pool = run(token, eff_tables, eff_lengths, pool)
    if sampler is None or sampler.temperature <= 0.0:
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    else:
        # deferred: keeps core free of a top-level serving dependency
        from repro.serving.sampler import sample
        nxt = sample(logits[:, -1, :], key, sampler)
    nxt = jnp.where(active, nxt, token[:, 0])
    new_remaining = jnp.where(active, remaining - 1, 0)
    if eos_id is not None:
        new_remaining = jnp.where(active & (nxt == eos_id), 0,
                                  new_remaining)
    new_lengths = lengths + active.astype(jnp.int32)
    return ((nxt[:, None], pool, new_lengths, new_remaining),
            (nxt, active), extra)


@partial(jax.jit,
         static_argnames=("decode_step", "n_steps", "sampler", "eos_id"),
         donate_argnums=(2,))
def _paged_window(params, token, pool, block_tables, lengths, remaining,
                  step_keys, *, decode_step, n_steps: int, sampler, eos_id):
    def run(token, eff_tables, eff_lengths, pool):
        logits, pool = decode_step(params, token, pool,
                                   block_tables=eff_tables,
                                   lengths=eff_lengths)
        return logits, None, pool

    def step(carry, key):
        carry, out, _ = _masked_step(run, carry, key,
                                     block_tables=block_tables,
                                     sampler=sampler, eos_id=eos_id)
        return carry, out

    (token, pool, lengths, remaining), (toks, valid) = jax.lax.scan(
        step, (token, pool, lengths, remaining), step_keys, length=n_steps)
    return toks.T, valid.T, pool, lengths, remaining


@partial(jax.jit,
         static_argnames=("decode_step", "mixed_step", "n_steps", "sampler",
                          "eos_id"),
         donate_argnums=(2,))
def _paged_mixed_window(params, token, pool, block_tables, lengths, remaining,
                        step_keys, prefill_tokens, prefill_table,
                        prefill_start, *, decode_step, mixed_step,
                        n_steps: int, sampler, eos_id):
    """Window carrying an in-flight prefill chunk: step 0 is the fused
    ``mixed_step`` (decode lanes ⊕ prefill chunk, one pool write), the
    remaining ``n_steps - 1`` steps are pure batched decode — all ONE
    dispatch, so admission costs zero extra host round-trips."""
    def run_mixed(token, eff_tables, eff_lengths, pool):
        logits, pre_logits, pool = mixed_step(
            params, token, prefill_tokens, pool,
            decode_tables=eff_tables, decode_lengths=eff_lengths,
            prefill_table=prefill_table, prefill_start=prefill_start)
        return logits, pre_logits, pool

    def run_decode(token, eff_tables, eff_lengths, pool):
        logits, pool = decode_step(params, token, pool,
                                   block_tables=eff_tables,
                                   lengths=eff_lengths)
        return logits, None, pool

    carry = (token, pool, lengths, remaining)
    carry, (tok0, act0), pre_logits = _masked_step(
        run_mixed, carry, step_keys[0], block_tables=block_tables,
        sampler=sampler, eos_id=eos_id)

    def step(carry, key):
        carry, out, _ = _masked_step(run_decode, carry, key,
                                     block_tables=block_tables,
                                     sampler=sampler, eos_id=eos_id)
        return carry, out

    (token, pool, lengths, remaining), (toks, valid) = jax.lax.scan(
        step, carry, step_keys[1:], length=n_steps - 1)
    toks = jnp.concatenate([tok0[None], toks], axis=0)
    valid = jnp.concatenate([act0[None], valid], axis=0)
    return toks.T, valid.T, pre_logits, pool, lengths, remaining


def paged_decode_window(model, params, last_token, pool, block_tables,
                        lengths, remaining, rng, n_steps: int, *,
                        sampler=None, eos_id=None, prefill_tokens=None,
                        prefill_table=None, prefill_start=0,
                        mixed_step_fn=None, decode_step_fn=None):
    """Fused-window paged decode: ONE dispatch for ``n_steps`` batched steps.

    last_token: [W, 1] each lane's most recent token; block_tables: [W, NBmax]
    (pre-grown on the host to cover the whole window's writes); lengths: [W]
    write positions; remaining: [W] per-lane steps still to emit (0 = lane
    inactive for the whole window). Greedy when ``sampler`` is None or
    temperature 0; otherwise one fold of ``rng`` per step.

    Returns (tokens [W, n_steps], valid [W, n_steps] bool, pool,
    final lengths [W], final remaining [W]) — the host reconciles per-lane
    outputs/lengths/blocks from the valid mask after the window.

    With ``prefill_tokens`` ([1, C]) + ``prefill_table`` ([1, NBmax]) the
    window additionally carries one prefill chunk of an admitting request
    (stage-parallel mixed batching): the fused graph runs the chunk
    concurrently with the window's first decode step, and the return gains
    the chunk's last-token logits as a third element —
    (tokens, valid, prefill_logits, pool, lengths, remaining).
    ``mixed_step_fn`` / ``decode_step_fn`` must be STABLE callables (cached
    by the caller, e.g. ``partial(model.mixed_step, hetero_ctx=ctx)`` or a
    layout object's shard_map-wrapped step) so jit caching holds across
    windows; they default to the model's own step functions. The override is
    how tensor-parallel serving threads its sharded step into the fused
    window: the shard_map body simply becomes the scanned step.
    """
    keys = jax.random.split(rng, n_steps)
    decode_step = (decode_step_fn if decode_step_fn is not None
                   else model.paged_decode_step)
    if prefill_tokens is None:
        return _paged_window(params, last_token, pool, block_tables,
                             lengths, remaining, keys,
                             decode_step=decode_step, n_steps=n_steps,
                             sampler=sampler, eos_id=eos_id)
    return _paged_mixed_window(
        params, last_token, pool, block_tables, lengths, remaining, keys,
        prefill_tokens, prefill_table,
        jnp.asarray(prefill_start, jnp.int32),
        decode_step=decode_step,
        mixed_step=(mixed_step_fn if mixed_step_fn is not None
                    else model.mixed_step),
        n_steps=n_steps, sampler=sampler, eos_id=eos_id)


@lru_cache(maxsize=16)
def _host_loop_jit(decode_step):
    """Per-decode-step-callable jit cache: ``generate_host_loop`` is called
    per request, and re-wrapping decode_step each call would retrace."""
    return jax.jit(decode_step, donate_argnums=(2,))


def generate_host_loop(model, params, first_token, cache, n_steps: int,
                       *, hard_sync: bool = True):
    """Baseline: host dispatches each token step (GPU-2 cost per token)."""
    step = _host_loop_jit(model.decode_step)
    token = first_token
    out = []
    for _ in range(n_steps):
        logits, cache = step(params, token, cache)
        if hard_sync:
            jax.block_until_ready(logits)           # the clFinish analogue
            token = jnp.asarray(jax.device_get(_greedy(logits)))  # host trip
        else:
            token = _greedy(logits)
        out.append(token[:, 0])
    return jnp.stack(out, axis=1), cache


def measure_dispatch_overhead(n: int = 50) -> float:
    """Median microseconds per trivial-dispatch+sync on this backend."""
    x = jnp.zeros((8,), jnp.float32)
    f = jax.jit(lambda a: a + 1)
    f(x).block_until_ready()
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()  # repolint: disable=determinism -- measures real per-dispatch wall overhead (the solver's T_sync input); a virtual clock would measure nothing
        f(x).block_until_ready()
        ts.append(time.perf_counter() - t0)  # repolint: disable=determinism -- second half of the same real-wall-time measurement
    ts.sort()
    return ts[len(ts) // 2] * 1e6

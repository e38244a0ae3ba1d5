"""The float32 reference against the program's own paged prefill and decode
through the pool, at smoke size on the CPU, in float32: they agree to
rounding. In bfloat16 the served tokens still stay within the smoke cell's
limit of the reference's best."""
import jax
import jax.numpy as jnp
import numpy as np

from bench.model import make_weights, model_config
from bench.reference import Reference, served_gaps
from smoke import SMOKE_CONFIG

F32 = dict(SMOKE_CONFIG, torch_dtype="float32")


def _batcher(c, seed):
    from repro.serving.scheduler import PagedBatcher
    dep = {k: v for k, v in c["deployment"].items()}
    return PagedBatcher(model_config(c), make_weights(c, seed),
                        max_blocks_per_seq=16, **dep)


def test_reference_matches_paged_prefill_and_decode():
    from repro.serving.scheduler import Request
    c, seed = F32, 4
    b = _batcher(c, seed)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, c["vocab_size"], n).astype(np.int32)
               for n in (5, 19, 37)]
    ref = Reference(c, seed, seq_len=128, n_pos=16)
    with jax.default_matmul_precision("highest"):
        for p in prompts:
            got = np.asarray(b.prompt_logits(p), np.float64)
            want = ref.logits(p, [0])[0]
            assert np.max(np.abs(got - want)) < 1e-4 * np.max(np.abs(want))
        reqs = [Request(i, p, max_new_tokens=9) for i, p in enumerate(prompts)]
        b.run(reqs)
    for r in reqs:
        assert len(r.output) == 9
        # each greedy token is the reference's own first choice
        assert served_gaps(ref, r.prompt, r.output).max() < 1e-4


def test_reference_widths_follow_the_file():
    c = dict(SMOKE_CONFIG, tie_word_embeddings=False, qk_norm=False)
    p = make_weights(c, 1)
    assert p["head"].shape == (64, 256) and "q_norm" not in p["layers"]["attn"]
    assert p["embed"].dtype == jnp.bfloat16
    lg = Reference(c, 1, seq_len=64, n_pos=4).logits(np.arange(10), [3, 4])
    assert lg.shape == (2, 256) and np.isfinite(lg).all()

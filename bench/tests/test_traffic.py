"""The traffic generator: every seed gets the same work in another order."""
import numpy as np
import pytest

from bench.traffic import Traffic, gap_set, length_set, warmup_lengths
from bench.run import cell_spec

CHAT = cell_spec("qwen3-1.7b.chat")["mix"]


def test_same_sizes_and_arrival_count_for_every_seed():
    runs = [Traffic(CHAT, seed=s, seconds=30, vocab_size=1000).schedule()
            for s in (1, 2, 2**33 + 7)]
    window = [[r for r in run if r.at >= 0] for run in runs]
    assert len({len(w) for w in window}) == 1
    assert len(window[0]) == round(CHAT["arrival"]["rate_rps"] * 30)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert len({tuple(sorted(map(key, w))) for w in window}) == 1
        assert len({tuple(map(key, w)) for w in window}) == 3
    for w in window:
        at = [r.at for r in w]
        assert at == sorted(at) and at[0] == 0.0 and at[-1] < 30


def test_same_seed_same_requests():
    a = Traffic(CHAT, seed=5, seconds=10, vocab_size=1000).schedule()
    b = Traffic(CHAT, seed=5, seconds=10, vocab_size=1000).schedule()
    assert all(np.array_equal(x.prompt, y.prompt) and x.at == y.at
               for x, y in zip(a, b))


def test_lengths_follow_the_mix():
    spec = CHAT["prompt_tokens"]
    xs = length_set(spec, 1001)
    assert xs.min() >= spec["min"] and xs.max() <= spec["max"]
    assert np.median(xs) == spec["median"]


@pytest.mark.parametrize("arrival", [
    {"process": "poisson", "rate_rps": 4.0},
    {"process": "burst", "rate_rps": 4.0, "burst_size": 4, "duty": 0.2}])
def test_gap_sets_keep_the_rate(arrival):
    g = gap_set(arrival, 4000)
    assert abs(len(g) / g.sum() - 4.0) < 0.1


def test_closed_loop_cycles_one_size_set():
    mix = {k: v for k, v in CHAT.items() if k != "arrival"}
    t = Traffic(dict(mix, loop="closed", outstanding_per_lane=2), seed=3,
                seconds=10, vocab_size=1000)
    a = [t.next_closed() for _ in range(1024)]
    first = sorted(len(r.prompt) for r in a[:512])
    assert first == sorted(len(r.prompt) for r in a[512:])
    assert len({r.idx for r in a}) == 1024


def test_shared_prefix():
    mix = dict(CHAT, shared_prefix={"tokens": 64, "groups": 2})
    reqs = Traffic(mix, seed=1, seconds=5, vocab_size=1000).schedule()
    heads = {tuple(r.prompt[:64]) for r in reqs}
    assert len(heads) == 2
    assert all(len(r.prompt) >= 64 + CHAT["prompt_tokens"]["min"]
               for r in reqs)


@pytest.mark.parametrize("lo,hi", [(64, 2048), (512, 4096)])
def test_warmup_covers_every_chunk_length(lo, hi):
    from repro.serving.scheduler import bucket_chunks
    buckets = (64, 128, 256)
    lengths = warmup_lengths(lo, hi, buckets)
    assert all(lo <= n <= hi for n in lengths)
    chunks = {c for n in lengths for c in bucket_chunks(n, buckets)}
    assert chunks == set(buckets) | set(range(1, 64))

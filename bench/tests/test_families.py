"""The family seam: a configuration names its module in `bench/families/`,
and the harness takes everything particular to an architecture from it.

The constants below were recorded from the harness as it was before the
family modules existed, at smoke size and for both real configuration
files; the dense family must give them exactly. The last tests add a
second family as new files only and run a smoke cell of it end to end."""
import hashlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench.run as run
from bench import model
from bench.costs import Costs
from bench.reference import Reference
from smoke import DEVICE, PEAK, SMOKE_CONFIG, SMOKE_MIX, smoke_spec

WEIGHTS_SHA = {
    ("bfloat16", 7):
        "0e7a414ff86902248f9b8655a88a4e79bccc472a5fbf16a39592be89df718dd9",
    ("bfloat16", 2**33 + 5):
        "9f80f439711982e54b9d1ae241225ea60aae229044fc63fe6d0b86c294909a5a",
    ("float32", 7):
        "dcd00ddcebc20a50fc055eb1b23307cfd3d2c1614b9c65b2bb3b070d2dc7efd1",
    ("float32", 2**33 + 5):
        "dbd3b85860574507c7f3cee0700b9dc4fea9d99a4debc5dd88be82c0a4a82a11",
}
LOGITS_SHA = {
    "tied_qknorm_bf16":
        "5019cf225f05698b965fe905c9807b3ed4093e4fe531cddd5751ca6cc07dc64d",
    "untied_f32":
        "f858713e6683c74b3bec9f1320637a1084297742968aaad8905c68131e39a495",
}
LOGITS_CASES = {
    "tied_qknorm_bf16": (dict(SMOKE_CONFIG), 7),
    "untied_f32": (dict(SMOKE_CONFIG, tie_word_embeddings=False,
                        qk_norm=False, torch_dtype="float32"), 8),
}
DECODE_CTXS = ([1, 17, 512, 2560], [100] * 16)
CHUNKS = ((0, 256), (1024, 256), (3840, 256), (4000, 96))
COSTS = {
    "qwen3-1.7b": {
        "params": (1409410048, 311164928),
        "decode": ((14473371648.0, 3795535872), (55425400832.0, 3624650752)),
        "prefill": ((729785827328.0, 3470510080), (789915369472.0, 3587950592),
                    (955271610368.0, 3910912000), (360377417728.0, 3910912000)),
    },
    "internlm-1.8b": {
        "params": (1510049792, 189530112),
        "decode": ((14204157952.0, 3702919168), (54701129728.0, 3556446208)),
        "prefill": ((779992170496.0, 3424325632), (831531778048.0, 3524988928),
                    (973265698816.0, 3801812992), (366721499136.0, 3801812992)),
    },
}


def _dense_shapes(L, d, f, v, qk_norm, tied):
    attn = {"wk": [L, d, 1024], "wo": [L, 2048, d], "wq": [L, d, 2048],
            "wv": [L, d, 1024]}
    if qk_norm:
        attn.update(k_norm=[L, 128], q_norm=[L, 128])
    shapes = {"embed": [v, d], "final_norm": [d],
              "layers": {"attn": attn, "attn_norm": [L, d],
                         "ffn": {"w_down": [L, f, d], "w_gate": [L, d, f],
                                 "w_up": [L, d, f]},
                         "ffn_norm": [L, d]}}
    if not tied:
        shapes["head"] = [d, v]
    return shapes


SHAPES = {"qwen3-1.7b": _dense_shapes(28, 2048, 6144, 151936, True, True),
          "internlm-1.8b": _dense_shapes(24, 2048, 8192, 92544, False, False)}
_MC = {"attn_block_k": 1024, "attn_block_q": 512, "compute_dtype": "bfloat16",
       "d_head": 128, "d_model": 2048, "encoder_only": False,
       "family": "dense", "loss_chunk": 512, "moe": None, "n_heads": 16,
       "n_kv_heads": 8, "param_dtype": "bfloat16", "remat": True,
       "remat_policy": "nothing", "rope_theta": 1000000.0, "rwkv": None,
       "ssm": None}
MODEL_CONFIG = {
    "qwen3-1.7b": dict(_MC, name="qwen3-1.7b", d_ff=6144, n_layers=28,
                       norm_eps=1e-06, qk_norm=True, tie_embeddings=True,
                       vocab_size=151936),
    "internlm-1.8b": dict(_MC, name="internlm-1.8b", d_ff=8192, n_layers=24,
                          norm_eps=1e-05, qk_norm=False, tie_embeddings=False,
                          vocab_size=92544),
}


def _weights_sha(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _real(name):
    c = model.load_config(name)
    c["name"] = name
    return c


@pytest.mark.parametrize("dtype,seed", sorted(WEIGHTS_SHA))
def test_weights_are_unchanged(dtype, seed):
    c = dict(SMOKE_CONFIG, torch_dtype=dtype)
    assert _weights_sha(model.make_weights(c, seed)) == WEIGHTS_SHA[
        (dtype, seed)]


@pytest.mark.parametrize("case", sorted(LOGITS_SHA))
def test_reference_logits_are_unchanged(case):
    c, seed = LOGITS_CASES[case]
    lg = Reference(c, seed, seq_len=64, n_pos=4).logits(
        (np.arange(40) * 7) % 256, [3, 4, 5])
    assert hashlib.sha256(np.ascontiguousarray(lg).tobytes()).hexdigest() \
        == LOGITS_SHA[case]


@pytest.mark.parametrize("name", sorted(COSTS))
def test_costs_are_unchanged(name):
    k, want = Costs(_real(name)), COSTS[name]
    assert (k.layer_params, k.head_params) == want["params"]
    assert tuple(k.decode_step(x) for x in DECODE_CTXS) == want["decode"]
    assert tuple(k.prefill_chunk(s, n) for s, n in CHUNKS) == want["prefill"]


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_shapes_and_model_config_are_unchanged(name):
    import dataclasses
    c = _real(name)
    shapes = json.loads(json.dumps(model.family(c).param_shapes(c)))
    assert shapes == SHAPES[name]
    assert dataclasses.asdict(model.model_config(c)) == MODEL_CONFIG[name]


# ------------------------------------------------ a family as new files --

TOY_FAMILY = '''"""A family for the tests: the dense block, priced as routed experts.

Its parameter tree and layer are the dense family's; it requires the
routing keys of a mixture of experts, and a step of n tokens reads the
weights outside the MLP and the share of the MLP that n tokens, each
routed to num_experts_per_tok of num_experts, reach."""
from pathlib import Path

import bench.model
from bench.model import load_family

_dense = load_family("dense", Path(bench.model.__file__).parent / "families")
REQUIRED = _dense.REQUIRED + ("num_experts", "num_experts_per_tok")
model_config = _dense.model_config
param_shapes = _dense.param_shapes
layer = _dense.layer
flops_params = _dense.flops_params
CALLS = []


def weight_bytes(c, n_tokens):
    CALLS.append(n_tokens)
    e, k = c["num_experts"], c["num_experts_per_tok"]
    mlp = 3 * c["num_hidden_layers"] * c["hidden_size"] * c["intermediate_size"]
    unread = mlp * 2 * (1 - 1 / e) ** (k * n_tokens)
    return _dense.weight_bytes(c, n_tokens) - unread
'''

ROUTED_FAMILY = '''"""A family for the tests: dense shapes plus a float32 router and a
float32 gain whose name does not say so."""
from pathlib import Path

import bench.model
from bench.model import load_family

_dense = load_family("dense", Path(bench.model.__file__).parent / "families")
REQUIRED = _dense.REQUIRED


def param_shapes(c):
    shapes = _dense.param_shapes(c)
    L, d = c["num_hidden_layers"], c["hidden_size"]
    shapes["layers"]["router"] = {"shape": (L, d, 8), "dtype": "float32"}
    shapes["layers"]["scale"] = {"shape": (L, d), "dtype": "float32",
                                 "init": "gain"}
    return shapes
'''


@pytest.fixture
def bench_dir(tmp_path, monkeypatch):
    """A benchmark directory with nothing but new files in it, which the
    family loader and `load_config` read in place of `bench/`."""
    for sub in ("families", "configs", "traffic", "checks"):
        (tmp_path / sub).mkdir()
    monkeypatch.setattr(model, "BENCH", tmp_path)
    monkeypatch.setattr(model, "FAMILIES", tmp_path / "families")
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    return tmp_path


def _write(path, obj):
    path.write_text(obj if isinstance(obj, str) else json.dumps(obj))


def test_second_family_runs_end_to_end_from_new_files(bench_dir):
    _write(bench_dir / "families" / "toymoe.py", TOY_FAMILY)
    _write(bench_dir / "configs" / "toy.json",
           dict(SMOKE_CONFIG, family="toymoe", num_experts=8,
                num_experts_per_tok=2))
    _write(bench_dir / "traffic" / "toy.json", SMOKE_MIX)
    _write(bench_dir / "checks" / "toy.chat.json", {"mean_logit_gap": 2e-3})
    _write(bench_dir / "configs" / "toy-bare.json",
           dict(SMOKE_CONFIG, family="toymoe"))
    with pytest.raises(ValueError, match="num_experts"):
        model.load_config("toy-bare")
    c = model.load_config("toy")
    spec = smoke_spec(
        config=c, mix=json.loads((bench_dir / "traffic" / "toy.json")
                                 .read_text()),
        limit=json.loads((bench_dir / "checks" / "toy.chat.json")
                         .read_text())["mean_logit_gap"])
    out = run.run_cell(spec, seed=11, seconds=1.5, trace=False,
                       device=DEVICE, peak=PEAK, t_start=time.monotonic())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0

    fam = model.family(c)
    assert fam.__file__ == str(bench_dir / "families" / "toymoe.py")
    k = Costs(c)
    fam.CALLS.clear()
    one, four = k.decode_step([5]), k.decode_step([5, 6, 7, 8])
    chunk = k.prefill_chunk(0, 16)
    assert fam.CALLS == [1, 4, 16]
    assert one[1] - k.kv_bytes * 5 == fam.weight_bytes(c, 1)
    assert chunk[1] - 16 * k.kv_bytes == fam.weight_bytes(c, 16)
    # the weights of one token are fewer than those of four
    assert one[1] - 5 * k.kv_bytes < four[1] - 26 * k.kv_bytes


def test_a_leaf_keeps_its_own_dtype_and_init(bench_dir):
    _write(bench_dir / "families" / "routed.py", ROUTED_FAMILY)
    c = dict(SMOKE_CONFIG, family="routed")
    p = model.make_weights(c, 3)["layers"]
    assert p["router"].dtype == jnp.float32 and p["router"].shape == (2, 64, 8)
    assert abs(float(p["router"].std()) - 1 / 8) < 0.02
    assert p["scale"].dtype == jnp.float32
    assert abs(float(p["scale"].mean()) - 1.0) < 0.05
    assert p["ffn"]["w_up"].dtype == jnp.bfloat16


@pytest.mark.parametrize("cfg,words", [
    ({k: v for k, v in SMOKE_CONFIG.items() if k != "family"}, ['"family"']),
    (dict(SMOKE_CONFIG, family="nosuch"), ["'nosuch'", "'dense'"]),
    (dict(SMOKE_CONFIG, family="../dense"), ["'../dense'", "'dense'"]),
])
def test_config_without_a_known_family_is_refused(cfg, words):
    with pytest.raises(ValueError) as e:
        model.make_weights(cfg, 1)
    assert all(w in str(e.value) for w in words), str(e.value)


def test_config_file_without_family_is_refused(bench_dir):
    _write(bench_dir / "configs" / "bare.json",
           {k: v for k, v in SMOKE_CONFIG.items() if k != "family"})
    with pytest.raises(ValueError, match='"family"'):
        model.load_config("bare")

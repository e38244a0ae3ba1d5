"""A cell at smoke size: the harness's whole run on the CPU."""
import copy

SMOKE_CONFIG = {
    "name": "smoke", "family": "dense", "hidden_size": 64,
    "intermediate_size": 160, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000.0,
    "tie_word_embeddings": True, "qk_norm": True, "torch_dtype": "bfloat16",
    "deployment": {"num_blocks": 64, "block_size": 8, "decode_width": 4,
                   "window": 4, "sync": "device", "buckets": [8, 16]},
}

SMOKE_MIX = {
    "loop": "open", "arrival": {"process": "poisson", "rate_rps": 8.0},
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "min": 8, "max": 48},
    "output_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                      "min": 2, "max": 12},
    "lead_in_s": 0.5, "drain_s": 60,
}

E2E = [{"name": n, "unit": u} for n, u in (
    ("ttft_p50_ms", "ms"), ("ttft_p70_ms", "ms"), ("tpot_p70_ms", "ms"),
    ("setup_s", "s"))]

PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def smoke_spec(config=None, mix=None, limit=2e-3):
    return {"workload": {"name": "smoke.chat", "chips": 1},
            "config": copy.deepcopy(config or SMOKE_CONFIG),
            "mix": copy.deepcopy(mix or SMOKE_MIX),
            "check": {"mean_logit_gap": limit},
            "end_to_end": E2E, "per_layer": []}

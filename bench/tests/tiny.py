"""A tiny configuration and mixes that drive the harness on the CPU,
and where the benchmark lives."""
import copy
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CONFIG = {
    "name": "tiny",
    "family": "gqa_decoder",
    "program_arch": "qwen1.5-4b",
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_hidden_layers": 2,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "vocab_size": 200,
    "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0,
    "attention_bias": True,
    "tie_word_embeddings": False,
    "tp": 1,
    "max_batch": 2,
    "capacity": 640,
    "pool_tokens": 1280,
    "kv_dtype": "bf16",
    "prefix_cache_tokens": 640,
    "check": {"logit_gap_limit": 0.5},
}

OPEN = {
    "kind": "open_loop", "rate_per_s": 4.0, "drain_cap_s": 20,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.7,
               "min": 8, "max": 520},
    "output": {"dist": "lognormal", "median": 6, "sigma": 0.5,
               "min": 2, "max": 12},
    "check": {"requests": 4, "tokens": 30, "min_tokens": 10},
}

CLOSED = {
    "kind": "closed", "backlog_per_slot": 2,
    "prompt": {"dist": "uniform", "min": 8, "max": 40},
    "output": {"dist": "uniform", "min": 4, "max": 12},
    "check": {"requests": 2, "tokens": 16, "min_tokens": 8},
}


def config(**kw):
    c = copy.deepcopy(CONFIG)
    c.update(kw)
    return c

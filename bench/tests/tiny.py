"""A tiny configuration and mixes that drive the harness on the CPU,
where the benchmark lives, and a checkout that adds a cell of its own."""
import copy
import glob
import json
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


CELL = "tiny-closed"


def checkout(tmp_path, config, families=None):
    """A checkout that holds a copy of BENCHMARK.json with one more cell,
    :data:`CELL`, of ``config`` (written as ``bench/configs/tiny.json``)
    under the offline mix, which ``decode_roofline`` and ``step_mfu``
    read; the benchmark's own families beside ``families``
    (``{name: source}``), and its own mixes and readers."""
    root = tmp_path / "checkout"
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "families").mkdir()
    for d in ("metrics", "mixes"):
        os.symlink(os.path.join(BENCH, d), root / "bench" / d)
    for path in glob.glob(os.path.join(BENCH, "families", "*.py")):
        os.symlink(path, root / "bench" / "families" /
                   os.path.basename(path))
    for name, source in (families or {}).items():
        (root / "bench" / "families" / (name + ".py")).write_text(source)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny", "source": "tests",
                             "file": "bench/configs/tiny.json",
                             "reduced": config.get("reduced", []),
                             "why": "a configuration in new files"})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "offline", "chips": 1,
                               "why": "the tiny configuration"})
    for m in bench["per_layer"]:
        if m["name"] in ("decode_roofline", "step_mfu"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    return str(root)

"""A model family is found by the name its configuration gives: a
family written into a checkout of its own, in new files only, is taken
by every step of a run; a name with no file is an error; and the
generic modules leave every architecture to the family."""
import hashlib
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import tiny
import traffic
import weights as W
from peaks import PEAKS
from tiny import BENCH, ROOT

G = harness.load_family(tiny.CONFIG)
SEED = 2 ** 33 + 5
CELL = "tiny-closed"

# A family that computes as gqa_decoder does and draws its biases by a
# kind of its own, as a family in new files would.
FAMILY = '''"""gqa_decoder with biases of a smaller draw."""
import jax

import harness

_G = harness.load_family({"family": "gqa_decoder"})
program_config = _G.program_config
position_stats = _G.position_stats
decode_flops = _G.decode_flops
prefill_flops = _G.prefill_flops
decode_step_work = _G.decode_step_work
DRAWS = {"small_bias": lambda key, shape, dtype:
         0.05 * jax.random.normal(key, shape, dtype)}


def layout(c):
    return {p: (s, "small_bias" if k == "bias" else k)
            for p, (s, k) in _G.layout(c).items()}
'''


def _checkout(tmp_path, family="tmp_family", source=FAMILY, config=None):
    """A checkout that holds a copy of BENCHMARK.json with one more cell,
    the tiny configuration naming ``family``, and that family's file;
    the mixes and readers are the benchmark's own."""
    root = tmp_path / "checkout"
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "families").mkdir()
    for d in ("metrics", "mixes"):
        os.symlink(os.path.join(BENCH, d), root / "bench" / d)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny", "source": "tests",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "a family in new files"})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "offline", "chips": 1,
                               "why": "the tiny configuration"})
    for m in bench["per_layer"]:
        if m["name"] in ("decode_roofline", "step_mfu"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    c = config or tiny.config(family=family)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(c))
    if source is not None:
        (root / "bench" / "families" / (family + ".py")).write_text(source)
    return str(root)


def test_a_family_in_new_files_drives_a_whole_run(tmp_path, monkeypatch):
    root = _checkout(tmp_path)
    cell = harness.resolve(CELL, root)
    F = cell.family
    assert F.__file__ == os.path.join(root, "bench", "families",
                                      "tmp_family.py")
    assert set(cell.readers) == {"decode_roofline", "step_mfu"}
    c, mix = cell.config, dict(tiny.CLOSED)
    sys_ = harness.build(F, c, SEED, jax.devices())
    bias = sys_.params["stack"]["mixer"]["bq"].astype(jnp.float32)
    assert 0.02 < float(jnp.std(bias)) < 0.1        # the family's own draw
    harness.warm_up(sys_, mix, c["vocab_size"])
    reqs = traffic.generate(mix, seconds=2.0, seed=SEED,
                            vocab_size=c["vocab_size"],
                            slots=c["max_batch"])
    out = harness.drive(sys_, mix, reqs, 2.0, None)
    eng = sys_.engine
    run = harness.Run(CELL, c, F, mix, 1, PEAKS["TPU v5 lite"], out["t0"],
                      out["t1"], out["records"], out["ticks"],
                      c["max_batch"], eng.kv_stats()["kv_blocks_total"],
                      setup_s=0.0, t_end=time.monotonic())
    ticks = len([k for k in run.ticks if run.t0 <= k[0] <= run.t1])
    assert ticks
    run.trace = {"programs": {"decode": {"calls": ticks,
                                         "device_s": 0.05 * ticks}}}
    calls = []
    for name in ("decode_step_work", "decode_flops", "prefill_flops"):
        fn = getattr(F, name)
        monkeypatch.setattr(F, name, lambda *a, _f=fn, _n=name, **k: (
            calls.append(_n), _f(*a, **k))[1])
    got = {m: cell.readers[m](run) for m in cell.readers}
    assert {"decode_step_work", "decode_flops"} <= set(calls)
    monkeypatch.undo()
    # the same readings as the family it wraps
    run.family = G
    assert got == {m: cell.readers[m](run) for m in cell.readers}
    assert 0 < got["decode_roofline"] <= 100 and 0 < got["step_mfu"] <= 100
    chosen = harness.sample(run.records, mix["check"], SEED)
    cmp_ = harness.compare(F, c, sys_.params, chosen,
                           mix["check"]["requests"], control=True)
    # the int8 control runs through the family too; test_bench_control
    # shows at a larger size that it fails the limit
    assert cmp_["served_tokens"] >= mix["check"]["min_tokens"]
    assert cmp_["logit_gap_max"] <= c["check"]["logit_gap_limit"]
    assert cmp_["control_gap_max"] >= 0


@pytest.mark.parametrize("case", ["no_file", "no_key"])
def test_a_family_that_cannot_be_found_is_an_error(tmp_path, case):
    if case == "no_file":
        root = _checkout(tmp_path, family="no_such_family", source=None)
        want = os.path.join(root, "bench", "families", "no_such_family.py")
        with pytest.raises(FileNotFoundError, match=re.escape(want)):
            harness.resolve(CELL, root)
    else:
        c = tiny.config()
        del c["family"]
        root = _checkout(tmp_path, config=c)
        want = os.path.join(root, "bench", "families", "<family>.py")
        with pytest.raises(KeyError, match=re.escape(want)):
            harness.resolve(CELL, root)


# sha256 over the tiny configuration's bf16 leaves from seed 2**33 + 5, in
# the order of their sorted paths: a changed draw, key split or leaf
# order changes it
WEIGHTS_SHA256 = ("533260ecaff50de6923212cc1ea734efe9549d74f9c86d17e9e391c2"
                  "ce42119e")


def test_weights_from_a_fixed_seed_match_the_pinned_checksum():
    lay = G.layout(tiny.CONFIG)
    tree = W.make(lay, SEED)
    h = hashlib.sha256()
    for path in sorted(lay):
        leaf = tree
        for k in path:
            leaf = leaf[k]
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256


GENERIC = ["harness.py", "weights.py", "reference.py", "stats.py",
           "work.py"] + [os.path.join("metrics", n) for n in sorted(
               os.listdir(os.path.join(BENCH, "metrics")))
               if n.endswith(".py")]


@pytest.mark.parametrize("name", GENERIC)
def test_generic_modules_name_no_architecture(name):
    with open(os.path.join(BENCH, name)) as f:
        text = f.read()
    assert not re.search(r"""["'](wq|wk|wv|wo|gate|up|down)["']""", text)
    assert not re.search(r"\b(layer|body|head|total)_params\b|"
                         r"kv_bytes_per_token|attn_flops", text)
    assert "gqa" not in text.lower()

"""A model family is found by the name its configuration gives: a
family written into a checkout of its own, in new files only, is taken
by every step of a run, with a configuration cut as the family allows
and the scopes the family names; a name with no file is an error; and
the generic modules leave every architecture to the family."""
import hashlib
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import spans
import tiny
import traffic
import weights as W
from peaks import PEAKS
from tiny import BENCH, CELL

G = harness.load_family(tiny.CONFIG)
SEED = 2 ** 33 + 5

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

# The same, with a program that names an experts scope and configurations
# that may be cut as a family with routed experts allows.
CUT_FAMILY = FAMILY + '''

SCOPES = ("experts",)
CUTS = dict(_G.CUTS, experts="n_routed_experts")
min_layers = _G.min_layers
'''


def test_a_family_in_new_files_drives_a_whole_run(tmp_path, monkeypatch):
    _whole_run(tmp_path, monkeypatch, FAMILY,
               tiny.config(family="tmp_family"))


def test_a_cut_configuration_and_family_scopes_drive_a_whole_run(
        tmp_path, monkeypatch):
    _whole_run(tmp_path, monkeypatch, CUT_FAMILY, tiny.config(
        family="tmp_family", num_hidden_layers=4,
        reduced=["num_hidden_layers"], published={"num_hidden_layers": 8},
        deployment="two chips as two pipeline stages of four layers"))


def _whole_run(tmp_path, monkeypatch, source, config):
    """``resolve``, ``build``, a CPU drive, both work readers and
    ``compare`` on a checkout that adds the family ``source`` and
    ``config`` in new files only."""
    root = tiny.checkout(tmp_path, config, {"tmp_family": source})
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
    traces = tmp_path / "traces"
    out = harness.drive(sys_, mix, reqs, 2.0, str(traces / CELL))
    eng = sys_.engine
    run = harness.Run(CELL, c, F, mix, 1, PEAKS["TPU v5 lite"], out["t0"],
                      out["t1"], out["records"], out["ticks"],
                      c["max_batch"], eng.kv_stats()["kv_blocks_total"],
                      setup_s=0.0, t_end=time.monotonic())
    ticks = len([k for k in run.ticks if run.t0 <= k[0] <= run.t1])
    assert ticks
    run.trace = {"programs": {"decode": {"calls": ticks,
                                         "device_s": 0.05 * ticks}}}
    # the trace's ops are booked to the scopes the family names too
    loaded = []
    monkeypatch.setattr(spans, "TRACES", str(traces))
    monkeypatch.setattr(spans, "load", lambda path, scopes: (
        loaded.append(scopes), spans.Spans([], []))[1])
    assert spans.of(run) is not None
    assert loaded == [spans.SCOPES + getattr(F, "SCOPES", ())]
    monkeypatch.undo()
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
        root = tiny.checkout(tmp_path, tiny.config(family="no_such_family"))
        want = os.path.join(root, "bench", "families", "no_such_family.py")
        with pytest.raises(FileNotFoundError, match=re.escape(want)):
            harness.resolve(CELL, root)
    else:
        c = tiny.config()
        del c["family"]
        root = tiny.checkout(tmp_path, c)
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

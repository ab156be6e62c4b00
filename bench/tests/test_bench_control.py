"""The comparison that decides ``correct`` fails what it must: the int8
control in the program's place, and the timed path broken underneath in
each way a serving cell can break.  The harness runs whole (weights,
gateway, engine, window, reference) at a tiny size on the CPU; only its
look for a chip is skipped."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import harness
import tiny
from tiny import ROOT
from peaks import PEAKS

SEED = 3
# the tiny configuration's own limit, between its two readings at SEED:
# the program (bf16) reads 0.0026, the int8 control 0.030
LIMIT = 0.01
MIX = dict(tiny.CLOSED, output={"dist": "uniform", "min": 20, "max": 40},
           check={"requests": 2, "tokens": 60, "min_tokens": 20})


def _run(fault=None, control=False):
    c = tiny.config(vocab_size=8192, hidden_size=128, num_hidden_layers=4,
                    check={"logit_gap_limit": LIMIT})
    cell = harness.Cell("tiny", 1, c, MIX, [], [], {},
                        harness.load_family(c))
    return harness.run_cell(cell, seed=SEED, seconds=2.0, trace=False,
                            devices=jax.devices(),
                            peaks=PEAKS["TPU v5 lite"],
                            t_start=time.monotonic(), control=control,
                            fault=fault, log=lambda s: None)


def test_program_is_correct_and_int8_control_is_not():
    r = _run(control=True)
    assert r["correct"], r["checks"]
    ctl = dict(r["checks"], logit_gap_max=r["checks"]["control_gap_max"])
    assert not harness.judge(ctl), ctl


def _alter_tokens(sys_):
    sched = sys_.engine.scheduler
    emit = sched._emit

    def altered(slot, req, token):
        if len(req.generated) == 5:
            token = (token + 1) % 8192
        emit(slot, req, token)
    sched._emit = altered


def _state_unchanged(sys_):
    eng = sys_.engine
    step = eng._decode_sample_paged

    def stale(p, t, pool, *a):
        out, _ = step(p, t, jax.tree.map(jnp.copy, pool), *a)
        return out, pool
    eng._decode_sample_paged = stale


def _exchange_left_out(sys_):
    """What chip 0 of four computes when the all-reduce after the
    row-parallel products is left out: its own heads' and its own
    quarter of the MLP's partial sums."""
    p = sys_.engine.params
    st = p["stack"]
    wo, down = st["mixer"]["wo"], st["mlp"]["down"]
    st = dict(st, mixer=dict(st["mixer"],
                             wo=wo.at[:, wo.shape[1] // 4:].set(0)),
              mlp=dict(st["mlp"],
                       down=down.at[:, down.shape[1] // 4:].set(0)))
    sys_.engine.params = dict(p, stack=st)


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _exchange_left_out],
                         ids=["token_altered", "state_unchanged",
                              "exchange_left_out"])
def test_broken_timed_path_is_not_correct(fault):
    r = _run(fault=fault)
    assert not r["correct"], r["checks"]
    assert r["checks"]["logit_gap_max"]["value"] > LIMIT


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout

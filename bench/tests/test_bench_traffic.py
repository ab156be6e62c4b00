"""The traffic generator: deterministic by seed, draws inside the clips,
the same work for every seed."""
import collections
import os

import pytest

import traffic
from tiny import BENCH

MIXES = {n: traffic.load_mix(os.path.join(BENCH, "mixes", n + ".json"))
         for n in ("chat", "offline", "longform")}
BIG_SEED = 2 ** 40 + 12345


def _gen(mix, seed, seconds=51.0):
    return traffic.generate(mix, seconds=seconds, seed=seed,
                            vocab_size=1000, slots=8)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_requests(name):
    a, b = _gen(MIXES[name], BIG_SEED), _gen(MIXES[name], BIG_SEED)
    assert [(r.due, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due, r.prompt, r.max_new_tokens) for r in b]
    c = _gen(MIXES[name], BIG_SEED + 2 ** 33)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", sorted(MIXES))
def test_draws_inside_clips_and_same_work_for_every_seed(name):
    mix = MIXES[name]
    runs = [_gen(mix, s) for s in (1, 7, BIG_SEED)]
    for reqs in runs:
        for r in reqs:
            assert mix["prompt"]["min"] <= len(r.prompt) \
                <= mix["prompt"]["max"]
            assert mix["output"]["min"] <= r.max_new_tokens \
                <= mix["output"]["max"]
            assert all(0 < t < 999 for t in r.prompt)
    work = [collections.Counter((len(r.prompt), r.max_new_tokens > 0)
                                for r in reqs if r.measured)
            for reqs in runs]
    assert work[0] == work[1] == work[2]
    outs = [sorted(r.max_new_tokens for r in reqs if r.measured)
            for reqs in runs]
    assert outs[0] == outs[1] == outs[2]


def test_open_loop_window_and_rate():
    mix = MIXES["chat"]
    for seed in (3, BIG_SEED):
        reqs = _gen(mix, seed)
        inside = [r for r in reqs if r.measured]
        assert len(inside) == round(mix["rate_per_s"] * 51)
        assert inside[0].due == 0
        assert all(a.due <= b.due for a, b in zip(reqs, reqs[1:]))
        assert all(r.due < 51 for r in inside)
        assert all(r.due >= 51 for r in reqs if not r.measured)


def test_closed_backlog_is_due_at_once():
    reqs = _gen(MIXES["offline"], 5)
    assert len(reqs) == MIXES["offline"]["backlog_per_slot"] * 8
    assert all(r.due == 0 and r.measured for r in reqs)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_warm_lengths_cover_every_prefill_shape(name):
    """Every (padding bucket, KV blocks) pair a prompt of the mix can
    produce is produced by some warm-up length."""
    from repro.serving.scheduler import _bucket
    mix = MIXES[name]
    lens = traffic.warm_lengths(mix, 512, 16)
    shape = lambda n: (_bucket(min(n, 512)), -(-min(n, 512) // 16))  # noqa
    need = {shape(n) for n in range(mix["prompt"]["min"],
                                    mix["prompt"]["max"] + 1)}
    assert need <= {shape(n) for n in lens}

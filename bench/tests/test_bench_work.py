"""Required work against hand counts for both configurations, as their
family counts it."""
import json
import os

import pytest

import harness
import work
from tiny import BENCH


def _cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        c = json.load(f)
    return c, harness.load_family(c)


def test_qwen_hand_counts():
    c, F = _cfg("qwen1.5-4b")
    # per layer: q,k,v,o 4 x 2560^2, QKV bias 3 x 2560, gated MLP
    # 3 x 2560 x 6912, two norms 2 x 2560
    layer = 4 * 2560 ** 2 + 3 * 2560 + 3 * 2560 * 6912 + 2 * 2560
    assert F.layer_params(c) == layer == 79_311_360
    assert F.total_params(c) == 40 * layer + 2560 + 2 * 151936 * 2560
    assert F.total_params(c) == 3_950_369_280
    assert F.kv_bytes_per_token(c) == 40 * 2 * 20 * 128 * 2 == 409_600


def test_apertus_hand_counts():
    c, F = _cfg("apertus-8b-tp4")
    layer = (4096 * 128 * (32 + 2 * 8) + 32 * 128 * 4096
             + 3 * 4096 * 14336 + 2 * 4096)
    assert F.layer_params(c) == layer == 218_112_000
    assert F.total_params(c) == 8_053_329_920
    assert F.kv_bytes_per_token(c) == 32 * 2 * 8 * 128 * 2 == 131_072


def test_decode_and_prefill_flops_and_bytes():
    c, F = _cfg("qwen1.5-4b")
    body = F.body_params(c)
    head = 2560 * 151936
    assert F.decode_flops(c, 100) == 2 * (body + head) \
        + 4 * 40 * 20 * 128 * 100
    # causal: position i attends to i+1 keys, sum over i = n(n+1)/2
    n = 64
    assert F.prefill_flops(c, n) == 2 * body * n \
        + 4 * 40 * 20 * 128 * n * (n + 1) // 2 + 2 * head
    # two sequences of 10 and 20 tokens, on one chip of two
    f, b = F.decode_step_work(c, 2, 30, tp=2)
    assert b == ((body + head) * 2 + 409_600 * 30) / 2
    assert f == (F.decode_flops(c, 10) + F.decode_flops(c, 20)) / 2
    f, _ = F.decode_step_work(c, 8, 8 * 512)
    assert f == 8 * F.decode_flops(c, 512)
    t, bound = work.roofline_seconds(
        f, b, {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and t == pytest.approx(b / 819e9)

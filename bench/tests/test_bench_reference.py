"""The plain reference against the engine at a tiny size: one-shot
prefill, the prompt tokens past the prefill chunk streamed one per
launch, and decode through the paged pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference
import tiny
import weights as W

G = harness.load_family(tiny.CONFIG)


@pytest.fixture(scope="module")
def served():
    from repro.models import model as M
    from repro.serving.engine import InferenceEngine, Request
    from repro.serving.scheduler import SchedulerConfig
    c = tiny.config(vocab_size=300)
    pcfg = G.program_config(c)
    W.check_layout(G.layout(c), jax.eval_shape(lambda k: M.init(pcfg, k),
                                               jax.random.PRNGKey(0)))
    params = W.make(G.layout(c), 2 ** 35 + 11, dtype=jnp.float32)
    eng = InferenceEngine(pcfg, params, max_batch=2, capacity=128,
                          sched=SchedulerConfig(prefill_chunk=16))
    rng = np.random.default_rng(0)
    # 12 tokens: one-shot prefill; 40: a 16-token chunk, then 24 streamed
    reqs = [Request(prompt=[int(t) for t in rng.integers(1, 299, n)],
                    max_new_tokens=10) for n in (12, 40)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    assert eng.paged and all(len(r.generated) == 10 for r in reqs)
    return c, params, [(r.prompt, r.generated) for r in reqs]


def test_served_tokens_are_the_reference_argmax(served):
    c, params, seqs = served
    tokens, target, mask = reference.pack(seqs)
    mx, at, _, arg = G.position_stats(c, params, tokens, target)
    gaps = (mx - at)[mask]
    # f32 weights on both sides: only summation order differs
    assert gaps.max() < 1e-4, gaps.max()
    assert (arg[mask] == target[mask]).mean() > 0.9


def test_int8_control_departs_from_the_reference(served):
    c, params, seqs = served
    tokens, target, mask = reference.pack(seqs)
    r32 = G.position_stats(c, params, tokens, target)
    r8 = G.position_stats(c, params, tokens, target, precision="int8")
    assert np.abs(r8[0] - r32[0])[mask].max() > 1e-3


def test_pack_marks_the_position_before_each_served_token():
    tokens, target, mask = reference.pack([([5, 6, 7], [8, 9]), ([1], [2])])
    assert tokens.shape[1] == reference.Q_BLOCK
    assert list(tokens[0, :4]) == [5, 6, 7, 8]
    assert list(target[0, :5]) == [-1, -1, 8, 9, -1]
    assert list(target[1, :2]) == [2, -1]
    assert mask.sum() == 3

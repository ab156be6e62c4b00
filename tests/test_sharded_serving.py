"""Differential tests: a tensor-parallel (mesh-aware) engine must be
token-identical to the single-device engine at temperature 0.

The suite needs several host devices, which XLA only provides when
``--xla_force_host_platform_device_count`` is set *before jax imports*.
conftest.py appends that flag when ``REPRO_FORCE_DEVICES`` is exported,
so there are two ways in:

- the CI multi-device job (and any dev run) launches
  ``REPRO_FORCE_DEVICES=4 pytest tests/test_sharded_serving.py``;
- inside a plain single-device tier-1 run, the differential tests skip
  and :func:`test_sharded_suite_in_subprocess` re-runs this file in a
  subprocess with the flag set — so the tier-1 gate still proves TP
  token-identity without perturbing every other test's device world.

Token-identity caveat pinned here on purpose: TP shards contracting
dimensions (wo, mlp down), so partial sums reduce in a different order
than the single-device matmul.  On the tiny fp32 test models the logit
gaps are orders of magnitude above that reassociation noise, so greedy
argmax — and therefore every emitted token — is exactly identical; these
tests are the regression net that keeps it that way.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, scaled_down
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.parallel import sharding
from repro.serving.engine import InferenceEngine, Request

REPO = Path(__file__).resolve().parent.parent
MULTI = jax.device_count() >= 2
needs_multi = pytest.mark.skipif(
    not MULTI, reason="needs forced host devices (REPRO_FORCE_DEVICES)")


def _gqa_cfg(**over):
    kw = dict(num_layers=2, d_model=64, d_ff=128, vocab_size=128,
              num_heads=4, num_kv_heads=2, head_dim=16)
    kw.update(over)
    return scaled_down(get_config("qwen1.5-4b"), **kw)


def _mla_cfg():
    return scaled_down(get_config("deepseek-v2-lite-16b"), num_layers=2,
                       d_model=64, d_ff=128, vocab_size=128, num_heads=2)


def _tp_mesh(n: int):
    return make_mesh((n,), ("model",))


def _prompts(vocab: int, n: int = 5, seed: int = 0):
    """Mixed-length prompts; the last two share a 20-token prefix so the
    sharded radix/prefix-cache adoption path is exercised too."""
    rng = np.random.default_rng(seed)
    ps = [[int(x) for x in rng.integers(1, vocab - 1, 5 + 3 * i)]
          for i in range(n - 2)]
    shared = [int(x) for x in rng.integers(1, vocab - 1, 20)]
    ps.append(shared + [3, 5])
    ps.append(shared + [7, 9])
    return ps


def _run(cfg, params, mesh, prompts, max_new=8, **eng_kw):
    eng = InferenceEngine(cfg, params, max_batch=4, capacity=128,
                          mesh=mesh, **eng_kw)
    reqs = [Request(prompt=list(p), max_new_tokens=max_new)
            for p in prompts]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    assert all(r.done for r in reqs)
    return [r.generated for r in reqs], eng


# ------------------------------------------------------------- differential
@needs_multi
def test_tp2_paged_gqa_token_identity():
    cfg = _gqa_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    prompts = _prompts(cfg.vocab_size)
    base, b_eng = _run(cfg, params, None, prompts)
    tp, t_eng = _run(cfg, params, _tp_mesh(2), prompts)
    assert b_eng.paged and t_eng.paged
    assert base == tp
    # the pool actually sharded: one device holds half the head axis
    leaf = jax.tree.leaves(t_eng.slots.pool)[0]
    assert leaf.addressable_shards[0].data.nbytes * 2 == leaf.nbytes


@needs_multi
def test_tp2_paged_mla_token_identity():
    cfg = _mla_cfg()
    params = M.init(cfg, jax.random.PRNGKey(1), jnp.float32)
    prompts = _prompts(cfg.vocab_size, seed=1)
    base, _ = _run(cfg, params, None, prompts)
    tp, t_eng = _run(cfg, params, _tp_mesh(2), prompts)
    assert t_eng.paged
    assert base == tp
    # MLA's latent pool has no head axis -> replicated on every device
    leaf = jax.tree.leaves(t_eng.slots.pool)[0]
    assert leaf.addressable_shards[0].data.nbytes == leaf.nbytes


@needs_multi
def test_tp2_dense_fallback_token_identity():
    cfg = _gqa_cfg()
    params = M.init(cfg, jax.random.PRNGKey(2), jnp.float32)
    prompts = _prompts(cfg.vocab_size, n=3, seed=2)
    base, _ = _run(cfg, params, None, prompts, paged=False)
    tp, t_eng = _run(cfg, params, _tp_mesh(2), prompts, paged=False)
    assert not t_eng.paged
    assert base == tp


@needs_multi
@pytest.mark.skipif(jax.device_count() < 4, reason="needs 4 devices")
def test_tp4_paged_gqa_token_identity():
    # TP=4 needs num_kv_heads % 4 == 0 (a sharded dim must divide the
    # mesh axis — the engine surfaces jax's divisibility error otherwise)
    cfg = _gqa_cfg(num_kv_heads=4)
    params = M.init(cfg, jax.random.PRNGKey(3), jnp.float32)
    prompts = _prompts(cfg.vocab_size, n=3, seed=3)
    base, _ = _run(cfg, params, None, prompts)
    tp, _ = _run(cfg, params, _tp_mesh(4), prompts)
    assert base == tp


@needs_multi
def test_tp2_multi_lora_token_identity():
    from repro.finetune.lora import LoraConfig, lora_init, lora_randomize

    cfg = _gqa_cfg()
    params = M.init(cfg, jax.random.PRNGKey(4), jnp.float32)
    lcfg = LoraConfig(rank=4)
    ads = [lora_randomize(lora_init(params, lcfg, jax.random.PRNGKey(10 + i)),
                          jax.random.PRNGKey(20 + i)) for i in range(2)]

    def go(mesh):
        eng = InferenceEngine(cfg, params, max_batch=4, capacity=128,
                              mesh=mesh, adapter_slots=2)
        for i, ad in enumerate(ads):
            eng.register_adapter(f"t{i}", ad, lcfg)
        prompts = _prompts(cfg.vocab_size, n=4, seed=4)
        names = ["", "t0", "t1", "t0"]
        reqs = [Request(prompt=list(p), max_new_tokens=8, adapter=a)
                for p, a in zip(prompts, names)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_idle()
        return [r.generated for r in reqs]

    assert go(None) == go(_tp_mesh(2))


@needs_multi
def test_tp2_speculative_ngram_token_identity():
    cfg = _gqa_cfg()
    params = M.init(cfg, jax.random.PRNGKey(5), jnp.float32)
    # repetitive prompts give the n-gram drafter real matches
    prompts = [[7, 8, 9, 7, 8, 9, 7, 8] for _ in range(3)]
    base, _ = _run(cfg, params, None, prompts, max_new=12)
    spec, s_eng = _run(cfg, params, _tp_mesh(2), prompts, max_new=12,
                       speculative="ngram", spec_k=3)
    assert base == spec
    assert s_eng.metrics.spec_rows > 0   # the drafter actually drafted


@needs_multi
def test_tp2_crash_recover_evacuation_token_identity():
    cfg = _gqa_cfg()
    params = M.init(cfg, jax.random.PRNGKey(6), jnp.float32)
    prompts = _prompts(cfg.vocab_size, n=3, seed=6)
    base, _ = _run(cfg, params, None, prompts, max_new=10)

    mesh = _tp_mesh(2)
    a = InferenceEngine(cfg, params, max_batch=4, capacity=128, mesh=mesh,
                        name="tpA")
    b = InferenceEngine(cfg, params, max_batch=4, capacity=128, mesh=mesh,
                        name="tpB")
    reqs = [Request(prompt=list(p), max_new_tokens=10) for p in prompts]
    for r in reqs:
        a.submit(r)
    for _ in range(4):           # a few committed tokens, then the crash
        a.step()
    evacuated = a.crash()
    assert a.health() == "down"
    for r in evacuated:          # preemption fold keeps them token-exact
        b.submit(r)
    b.run_until_idle()
    assert [r.generated for r in reqs] == base
    a.recover()
    assert a.health() == "ok"


@needs_multi
def test_tp2_gateway_sharded_replica_is_one_endpoint():
    from repro.core.gateway import Gateway, ModelEntry

    cfg = _gqa_cfg()
    params = M.init(cfg, jax.random.PRNGKey(7), jnp.float32)
    prompts = _prompts(cfg.vocab_size, n=3, seed=7)
    base, _ = _run(cfg, params, None, prompts, max_new=8)

    eng = InferenceEngine(cfg, params, max_batch=4, capacity=128,
                          mesh=_tp_mesh(2), name="tp2")
    gw = Gateway()
    gw.vet_model(ModelEntry(cfg.name, cfg.name, 0.5, 1.5), cfg)
    gw.bind_endpoints(cfg.name, [eng])     # sharded replica == 1 endpoint
    key = gw.mint_key("test", budget_usd=10.0)
    outs = [gw.completion(api_key=key.key, model=cfg.name, prompt=list(p),
                          max_tokens=8, temperature=0.0)["tokens"]
            for p in prompts]
    assert outs == base


# ------------------------------------------------------------------- HLO
@needs_multi
def test_tp2_decode_hlo_collectives():
    """The per-token collective budget (serving/README.md): the fused
    paged decode step lowers to all-reduce/all-gather only — the two
    partial-sum reductions per layer plus the logits gather — and never
    an all-to-all or a host transfer."""
    from repro.launch import hlo_analysis as H

    cfg = _gqa_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = InferenceEngine(cfg, params, max_batch=4, capacity=128,
                          mesh=_tp_mesh(2))
    B = eng.slots.B
    toks = jnp.zeros((B, 1), jnp.int32)
    lengths = jnp.ones((B,), jnp.int32)
    key = jax.random.PRNGKey(0)
    temps = jnp.zeros((B,), jnp.float32)
    tks = jnp.zeros((B,), jnp.int32)
    tps = jnp.ones((B,), jnp.float32)
    lowered = eng._decode_sample_paged.lower(
        eng.params, toks, eng.slots.pool, eng.slots.tables_device(),
        lengths, key, temps, tks, tps, None, None, True)
    txt = lowered.compile().as_text()
    n_ar = txt.count("all-reduce(") + txt.count("all-reduce-start(")
    n_ag = txt.count("all-gather(") + txt.count("all-gather-start(")
    assert n_ar >= 1, "TP decode must reduce partial sums"
    # static instruction budget: 2 reductions per layer (attn wo + mlp
    # down) plus a small constant for logits/embed — the scan body
    # appears once in the module text
    assert n_ar + n_ag <= 2 * cfg.num_layers + 6, txt[:2000]
    assert "all-to-all" not in txt
    res = H.analyze(txt, 2)
    active = {k for k, v in res["by_collective"].items() if v > 0}
    assert active <= {"all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute"}, active


@needs_multi
def test_tp1_decode_hlo_has_no_collectives():
    """mesh=None engines compile collective-free single-device modules —
    the 'bit-for-bit untouched' acceptance criterion at the HLO level."""
    cfg = _gqa_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = InferenceEngine(cfg, params, max_batch=4, capacity=128)
    assert eng.mesh is None and eng.rules is None and eng.tp == 1
    B = eng.slots.B
    lowered = eng._decode_sample_paged.lower(
        eng.params, jnp.zeros((B, 1), jnp.int32), eng.slots.pool,
        eng.slots.tables_device(), jnp.ones((B,), jnp.int32),
        jax.random.PRNGKey(0), jnp.zeros((B,), jnp.float32),
        jnp.zeros((B,), jnp.int32), jnp.ones((B,), jnp.float32),
        None, None, True)
    txt = lowered.compile().as_text()
    for coll in ("all-reduce(", "all-gather(", "all-to-all",
                 "collective-permute("):
        assert coll not in txt


def test_serving_mesh_requires_model_axis():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    cfg = _gqa_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(ValueError, match="model"):
        InferenceEngine(cfg, params, mesh=mesh)


def test_serving_tp_rules_resolve():
    """Pure rule-table checks (no devices): the serving_tp layout."""
    r = sharding.make_rules("serving_tp")
    # params: pure TP, no fsdp
    assert tuple(r.spec(("fsdp", "tensor"))) == (None, "model")
    assert tuple(r.spec(("tensor", "fsdp"))) == ("model", None)
    # GQA pool leaf (num_blocks, block_size, KV, hd): head-sharded only
    assert tuple(r.spec(("act_batch", "act_kvseq", "act_heads", None))) \
        == (None, None, "model", None)
    # MLA latent pool leaf: fully replicated
    assert tuple(r.spec(("act_batch", "act_kvseq", None))) \
        == (None, None, None)
    # embeddings + logits replicated
    assert tuple(r.spec((None, "fsdp"))) == (None, None)
    assert tuple(r.spec(("act_batch", None, "act_vocab"))) \
        == (None, None, None)
    # MoE: dense-impl (no expert axis) with TP-sharded shared experts
    assert r.resolve("expert") is None
    assert r.resolve("act_ff") == "model"


# ------------------------------------------------------- tier-1 entrypoint
def test_sharded_suite_in_subprocess():
    """Single-device tier-1 runs still gate on the sharded suite: re-run
    this file with 4 forced host devices in a fresh interpreter (the
    flag must precede jax's import, so it cannot be set in-process)."""
    if MULTI:
        pytest.skip("already multi-device: the suite ran natively")
    env = dict(os.environ)
    env["REPRO_FORCE_DEVICES"] = "4"
    env["PYTHONPATH"] = (str(REPO / "src") + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    r = subprocess.run(
        [sys.executable, "-m", "pytest", str(Path(__file__).resolve()),
         "-q", "-p", "no:randomly", "-x"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=3000)
    assert r.returncode == 0, (r.stdout[-5000:] + "\n" + r.stderr[-2000:])
    assert "passed" in r.stdout

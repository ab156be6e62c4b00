"""Paper §5.2: serving metrics (QPS, TTFT, ITL, E2EL) + paged-KV A/B.

Reproductions:
1. measured: the continuous-batching engine on a tiny model on CPU, with
   the paper's two workload mixes (70B-style: medium prompts / moderate
   responses; 8B-style: short prompts / long-form generation) scaled down.
   Reproduces the paper's qualitative finding: the long-generation mix has
   far higher E2EL despite lower per-token latency pressure.  Rows include
   decode tokens/sec and peak KV blocks in use.
2. analytic: ITL for Apertus-8B/70B-class configs on the v5e target from
   the decode roofline (paper reference points: ~11 ms and ~42 ms).
3. shared-system-prompt mix: the multi-tenant gateway pattern (every
   request of a project carries the same long system prefix) with the
   radix prefix cache on vs. off — reports TTFT, prefill tokens saved,
   and hit rate, and checks decoded outputs are identical
   token-for-token across cache on/off AND across the paged/dense KV
   layouts (see src/repro/serving/README.md).
4. paged-vs-dense: same total KV budget, same per-request capacity — the
   paged engine allocates blocks on demand, so it sustains a larger
   concurrent decode batch than the dense engine (which pins
   max_batch x capacity up front) and reports decode tokens/sec for both.
5. multi-adapter mix: 4 tenants' LoRA adapters + base-model requests in
   ONE continuous decode batch (the S-LoRA pattern behind the paper's
   shared fine-tune/serve platform).  Acceptance: every request's output
   is token-identical to a single-tenant run on that adapter's
   ``lora_merge``d weights; also reports A/B decode tokens/sec vs the
   merge-and-redeploy alternative and the pool's load/evict counters
   under slot pressure.
6. speculative decoding A/B: the same repetitive-prompt mix (the
   code/RAG shape prompt-lookup thrives on) through the baseline
   engine, the n-gram drafter, and a draft-model drafter, on paged GQA
   *and* paged MLA.  Acceptance: temperature-0 outputs token-identical
   to the baseline for every drafter/architecture pair; rows report
   acceptance rate, tokens-per-launch, and decode tokens/sec vs
   baseline.

7. chaos mix: two replicas behind the resilient gateway on a virtual
   clock; a deterministic fault injector kills one mid-decode.  The
   gateway's breaker opens, the evacuated request retries onto the
   survivor token-exactly, and after recovery a half-open probe
   re-closes the circuit.  Acceptance: 100% completion, temp-0 token
   identity to a fault-free run, breaker open AND re-close observed in
   the metrics snapshot, zero real sleeps (docs/robustness.md).
8. sharded serving (tensor parallelism): the same greedy mix through a
   TP=2 mesh-aware engine and the single-device engine.  Acceptance: a
   HARD token-identity assert (serving/README.md "Sharded serving"),
   plus decode tokens/s and per-device KV bytes rows (the head-sharded
   paged pool halves per-device KV at TP=2).  Needs two devices; run as
   a CLI with JAX_PLATFORMS=cpu the module forces two XLA host devices
   before jax loads, so the rows are live even on a one-CPU CI runner.

9. kv-quant A/B: the same greedy mix through a bf16-KV and an int8-KV
   paged engine at a fixed pool_tokens budget.  The int8 pool carries
   2x the blocks in the same device bytes, so it sustains >= 1.8x the
   concurrent decode batch; greedy tokens must agree with the bf16 run
   at >= 90% (the accuracy-guard floor; see serving/README.md
   "Quantized serving").

CLI: ``--paged`` (default) / ``--dense`` select the KV layout for the
measured mixes; ``--smoke`` runs the fast subset (3 + 4 + 5 + 6 + 7 +
8 + 9) for CI; ``--chaos-smoke`` runs only mix 7 (the CI chaos job);
``--kv-quant-smoke`` runs only mix 9 (the CI kv-quant job);
``--json PATH`` additionally writes the rows as a machine-readable
artifact (uploaded by the CI workflow).
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
from typing import List, Optional, Tuple

# mix 8 needs >= 2 devices; on a CPU run (JAX_PLATFORMS=cpu set
# explicitly) force two XLA host devices — must happen before jax's first
# import (harmless for every other mix: their engines are mesh-free and
# compile single-device modules on device 0).  When another module
# imported jax first (e.g. a test importing this file) the flag is too
# late; sharded_rows then degrades to an explicit skip row instead of
# asserting.  Elsewhere the mix runs on the real devices or skips.
if os.environ.get("JAX_PLATFORMS") == "cpu" and "jax" not in sys.modules \
        and "xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2").strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, scaled_down
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.obs.peaks import PEAKS
from repro.serving.engine import InferenceEngine, Request
from repro.serving.scheduler import SchedulerConfig

# v5e-per-chip constants (same as launch.dryrun)
HBM_BW = PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
PEAK = PEAKS["TPU v5 lite"]["bf16_flops"]

_STATE = {}


def _tiny():
    if "cfg" not in _STATE:
        cfg = scaled_down(get_config("apertus-8b"), num_layers=2, d_model=64,
                          d_ff=128, vocab_size=256, num_heads=2,
                          num_kv_heads=2, head_dim=32)
        _STATE["cfg"] = cfg
        _STATE["params"] = M.init(cfg, jax.random.PRNGKey(0))
    return _STATE["cfg"], _STATE["params"]


def _mk_engine(max_batch=4, capacity=160, sched=None, paged=None,
               pool_tokens=None):
    cfg, params = _tiny()
    return InferenceEngine(cfg, params, max_batch=max_batch,
                           capacity=capacity, sched=sched, paged=paged,
                           pool_tokens=pool_tokens)


def _mix(engine, rng, n_req, prompt_rng, gen_rng):
    reqs = []
    for _ in range(n_req):
        p = int(rng.integers(*prompt_rng))
        g = int(rng.integers(*gen_rng))
        r = Request(prompt=list(rng.integers(1, 255, p)), max_new_tokens=g)
        reqs.append(r)
        engine.submit(r)
    return engine.run_until_idle()


def measured_rows(paged: Optional[bool] = None) -> List[str]:
    rng = np.random.default_rng(0)
    tag_kv = "paged" if (paged or paged is None) else "dense"
    # 70B-style mix: prompts 100-800 -> 10-80; responses 200-500 -> 20-50
    e1 = _mk_engine(paged=paged)
    s1 = _mix(e1, rng, 12, (10, 80), (20, 50))
    # 8B-style mix: prompts <200 -> <20; long-form 3000+ -> 100+
    e2 = _mk_engine(capacity=192, paged=paged)
    s2 = _mix(e2, rng, 12, (4, 20), (100, 128))
    rows = []
    for tag, s, e in (("mix70b", s1, e1), ("mix8b_longform", s2, e2)):
        kv = e.kv_stats()
        rows.append(f"serve_{tag}_ttft_p50,{s['ttft_p50_s'] * 1e6:.0f},"
                    f"p99_s={s['ttft_p99_s']:.3f}")
        rows.append(f"serve_{tag}_itl_mean,{s['itl_mean_s'] * 1e6:.0f},"
                    f"tokens={s['generated_tokens']}")
        rows.append(f"serve_{tag}_e2el_mean,{s['e2el_mean_s'] * 1e6:.0f},"
                    f"qps={s['qps']:.3f}")
        rows.append(f"serve_{tag}_decode_tokens_per_s,"
                    f"{s['tokens_per_s']:.1f},kv={tag_kv}")
        rows.append(f"serve_{tag}_kv_blocks_peak,{kv['kv_blocks_peak']},"
                    f"of_total={kv['kv_blocks_total']}"
                    f" block_tokens={kv['kv_block_size']}")
    # paper's qualitative claim: long-form mix E2EL >> medium mix E2EL
    ratio = s2["e2el_mean_s"] / s1["e2el_mean_s"]
    rows.append(f"serve_longform_e2el_ratio,{ratio * 1e6:.0f},"
                f"paper=31.4s_vs_5.84s (5.4x)")
    return rows


def shared_prefix_rows() -> List[str]:
    """Multi-tenant shared-system-prompt mix: prefix cache on vs. off and
    paged vs. dense KV.

    Every request of the project carries the same 48-token system prompt
    plus a short unique user turn — the dominant pattern behind the
    paper's shared gateway.  Acceptance: >= 30% of prefill tokens served
    from cache, outputs token-identical across cache on/off AND across
    the paged/dense layouts (the paged hit is copy-free: physical blocks
    are refcount-spliced into the request's block table)."""
    rng = np.random.default_rng(7)
    system = list(map(int, rng.integers(1, 255, 48)))
    prompts = [system + list(map(int, rng.integers(1, 255,
                                                   int(rng.integers(8, 24)))))
               for _ in range(12)]
    outs, sums, engines = {}, {}, {}
    cases = [("paged_on", True, True), ("paged_off", True, False),
             ("dense_on", False, True)]
    for name, paged, cache_on in cases:
        eng = _mk_engine(capacity=192, paged=paged, sched=SchedulerConfig(
            enable_prefix_cache=cache_on, prefix_block=8, prefill_chunk=32))
        reqs = [Request(prompt=list(p), max_new_tokens=24,
                        namespace="proj") for p in prompts]
        for r in reqs:
            eng.submit(r)
        sums[name] = eng.run_until_idle()
        outs[name] = [r.generated for r in reqs]
        engines[name] = eng
    identical = int(outs["paged_on"] == outs["paged_off"])
    paged_eq_dense = int(outs["paged_on"] == outs["dense_on"])
    s_on, s_off = sums["paged_on"], sums["paged_off"]
    kv_on = engines["paged_on"].kv_stats()
    rows = [
        f"serve_sharedprefix_cache_on_ttft_p50,{s_on['ttft_p50_s'] * 1e6:.0f},"
        f"cached_p50_s={s_on['ttft_cached_p50_s']:.4f}"
        f" uncached_p50_s={s_on['ttft_uncached_p50_s']:.4f}",
        f"serve_sharedprefix_cache_off_ttft_p50,"
        f"{s_off['ttft_p50_s'] * 1e6:.0f},baseline",
        f"serve_sharedprefix_prefill_tokens_saved,"
        f"{s_on['prefill_tokens_saved']},"
        f"of_total={s_on['prompt_tokens']}",
        f"serve_sharedprefix_hit_rate_pct,"
        f"{s_on['prefix_hit_rate'] * 100:.1f},target>=30",
        f"serve_sharedprefix_decode_tokens_per_s,"
        f"{s_on['tokens_per_s']:.1f},kv=paged",
        f"serve_sharedprefix_kv_blocks_peak,{kv_on['kv_blocks_peak']},"
        f"shared_blocks_counted_once block_tokens={kv_on['kv_block_size']}",
        f"serve_sharedprefix_outputs_identical,{identical},"
        f"token-for-token vs cache-off",
        f"serve_sharedprefix_paged_equals_dense,{paged_eq_dense},"
        f"token-for-token vs dense KV",
    ]
    assert identical, "prefix cache changed decoded tokens"
    assert paged_eq_dense, "paged KV changed decoded tokens"
    assert s_on["prefix_hit_rate"] >= 0.30, s_on["prefix_hit_rate"]
    return rows


def paged_vs_dense_rows(smoke: bool = False) -> List[str]:
    """Same KV budget (1024 cache tokens), same per-request capacity
    (256): the dense layout can only preallocate 4 slots; the paged
    layout runs 8 slots over an on-demand pool and serves short requests
    at twice the concurrency."""
    budget, capacity = 1024, 256
    gen = 12 if smoke else 24
    n_req = 8
    sched = SchedulerConfig(enable_prefix_cache=False, admit_per_tick=8,
                            prefill_chunk=32)
    rng = np.random.default_rng(11)
    prompts = [list(map(int, rng.integers(1, 255, 12))) for _ in range(n_req)]
    res = {}
    for mode, paged, mb in (("dense", False, budget // capacity),
                            ("paged", True, 8)):
        eng = _mk_engine(max_batch=mb, capacity=capacity, sched=sched,
                         paged=paged,
                         pool_tokens=budget if paged else None)
        reqs = [Request(prompt=list(p), max_new_tokens=gen) for p in prompts]
        for r in reqs:
            eng.submit(r)
        peak = 0
        while eng.num_active:
            eng.step()
            peak = max(peak, len(eng.running))
        s = eng.metrics.summary()
        kv = eng.kv_stats()
        res[mode] = (peak, s, kv, [r.generated for r in reqs])
    rows = []
    for mode in ("dense", "paged"):
        peak, s, kv, _ = res[mode]
        rows.append(
            f"serve_{mode}_concurrent_batch_peak,{peak},"
            f"budget_tokens={budget} capacity={capacity}")
        rows.append(
            f"serve_{mode}_decode_tokens_per_s,{s['tokens_per_s']:.1f},"
            f"generated={s['generated_tokens']}")
        rows.append(
            f"serve_{mode}_kv_blocks_peak,{kv['kv_blocks_peak']},"
            f"block_tokens={kv['kv_block_size']}"
            f" peak_kv_tokens={kv['kv_blocks_peak'] * kv['kv_block_size']}")
    assert res["paged"][3] == res["dense"][3], \
        "paged KV changed decoded tokens"
    assert res["paged"][0] > res["dense"][0], (
        f"paged sustained {res['paged'][0]} concurrent <= "
        f"dense {res['dense'][0]} under the same budget")
    rows.append(f"serve_paged_batch_gain,"
                f"{res['paged'][0] / res['dense'][0]:.2f},"
                f"paged_peak/dense_peak under equal KV budget")
    return rows


def multi_adapter_rows(smoke: bool = False) -> List[str]:
    """Multi-tenant LoRA mix: 4 distinct adapters + base requests in one
    decode batch, validated token-for-token against per-adapter
    ``lora_merge``d single-tenant runs (same engine machinery, merged
    weights — the A/B the merge-and-redeploy alternative would serve)."""
    from repro.finetune.lora import (LoraConfig, lora_init, lora_merge,
                                     lora_randomize)
    cfg, params = _tiny()
    lcfg = LoraConfig(rank=4)
    n_adapters, gen = 4, (10 if smoke else 20)
    ads = {f"tenant{i}": lora_randomize(
        lora_init(params, lcfg, jax.random.PRNGKey(50 + i)),
        jax.random.PRNGKey(150 + i)) for i in range(n_adapters)}
    # slot pressure: fewer device slots than adapters, so the mix also
    # exercises load + LRU eviction mid-run
    eng_ml = InferenceEngine(cfg, params, max_batch=4, capacity=160,
                             adapter_slots=3)
    for name, ad in ads.items():
        eng_ml.register_adapter(name, ad, lcfg)
    rng = np.random.default_rng(23)
    names = (list(ads) + [""]) * 2          # 8 adapter'd + 2 base
    prompts = [list(map(int, rng.integers(1, 255,
                                          int(rng.integers(8, 20)))))
               for _ in names]
    reqs = [Request(prompt=list(p), max_new_tokens=gen, adapter=nm)
            for p, nm in zip(prompts, names)]
    for r in reqs:
        eng_ml.submit(r)
    s = eng_ml.run_until_idle()
    merged = {nm: lora_merge(params, ad, lcfg) for nm, ad in ads.items()}
    merged[""] = params
    # A/B: the merge-and-redeploy alternative serves each variant's
    # requests on its own merged-weights engine (same total work, no
    # sharing) — and is the token-identity baseline for the mixed batch
    identical, t_nonshared = True, 0.0
    for nm in [""] + list(ads):
        e = InferenceEngine(cfg, merged[nm], max_batch=4, capacity=160)
        pairs = [(p, r) for p, n2, r in zip(prompts, names, reqs)
                 if n2 == nm]
        sub = [Request(prompt=list(p), max_new_tokens=gen)
               for p, _ in pairs]
        for r in sub:
            e.submit(r)
        t_nonshared += e.run_until_idle()["e2el_mean_s"] * len(sub)
        identical &= all(r.generated == mixed.generated
                         for r, (_, mixed) in zip(sub, pairs))
    st = eng_ml.adapter_stats()
    rows = [
        f"serve_multilora_outputs_identical,{int(identical)},"
        f"token-for-token vs per-adapter lora_merge",
        f"serve_multilora_decode_tokens_per_s,{s['tokens_per_s']:.1f},"
        f"adapters={n_adapters}+base in one batch",
        f"serve_multilora_e2el_mean,{s['e2el_mean_s'] * 1e6:.0f},"
        f"merged_per_tenant_sum={t_nonshared * 1e6:.0f}",
        f"serve_multilora_pool,{st['loads']},loads "
        f"evictions={st['evictions']} slots={st['slots']}"
        f" registered={st['registered']}",
    ]
    assert identical, "multi-LoRA decode diverged from merged baselines"
    assert st["evictions"] > 0, "slot pressure never exercised eviction"
    return rows


def _tiny_mla():
    if "mla_cfg" not in _STATE:
        cfg = scaled_down(get_config("deepseek-v2-lite-16b"), num_layers=2,
                          d_model=64, d_ff=128, vocab_size=256, num_heads=2)
        _STATE["mla_cfg"] = cfg
        _STATE["mla_params"] = M.init(cfg, jax.random.PRNGKey(2))
    return _STATE["mla_cfg"], _STATE["mla_params"]


def speculative_rows(smoke: bool = False) -> List[str]:
    """Speculative decoding A/B (ISSUE 4 acceptance bar).

    A repetitive-prompt workload (a shared boilerplate block + short
    unique tail — the shape of code-edit/RAG/summarisation traffic)
    decoded greedily through (a) the baseline engine, (b) the n-gram /
    prompt-lookup drafter, (c) a draft-model drafter — on paged GQA, and
    (a)+(b) again on paged MLA.  Every speculative run must be
    token-identical to its baseline (temperature 0 makes accept/reject
    an exact argmax match, so this is a hard assert, not a tolerance).
    The draft model here is the target itself ("self-draft"): it bounds
    the machinery's best case (acceptance ~1, tokens/launch -> k+1) with
    zero training dependencies; realistic draft pairs plug in via
    ``launch/serve.py --speculative draft --draft-config ...``.
    """
    gen = 16 if smoke else 32
    spec_k = 4
    rng = np.random.default_rng(17)

    def mk_prompts(vocab):
        pat = list(map(int, rng.integers(1, vocab - 1, 8)))
        return [pat * 4 + list(map(int, rng.integers(1, vocab - 1, 3)))
                for _ in range(6)]

    def run(cfg, params, prompts, **kw):
        eng = InferenceEngine(cfg, params, max_batch=4, capacity=192,
                              sched=SchedulerConfig(prefill_chunk=32,
                                                    prefix_block=8), **kw)
        reqs = [Request(prompt=list(p), max_new_tokens=gen)
                for p in prompts]
        for r in reqs:
            eng.submit(r)
        s = eng.run_until_idle()
        return [r.generated for r in reqs], s

    rows = []
    for tag, (cfg, params) in (("gqa", _tiny()), ("mla", _tiny_mla())):
        prompts = mk_prompts(cfg.vocab_size)
        base, sb = run(cfg, params, prompts)
        cases = [("ngram", dict(speculative="ngram", spec_k=spec_k))]
        if tag == "gqa":
            cases.append(("draft", dict(speculative="draft", spec_k=spec_k,
                                        draft_cfg=cfg,
                                        draft_params=params)))
        for name, kw in cases:
            out, s = run(cfg, params, prompts, **kw)
            ident = int(out == base)
            rows.append(
                f"serve_spec_{tag}_{name}_acceptance_rate,"
                f"{s['spec_acceptance_rate'] * 100:.1f},"
                f"pct k={spec_k}")
            rows.append(
                f"serve_spec_{tag}_{name}_tokens_per_launch,"
                f"{s['spec_tokens_per_launch']:.2f},baseline=1.0")
            rows.append(
                f"serve_spec_{tag}_{name}_decode_tokens_per_s,"
                f"{s['tokens_per_s']:.1f},"
                f"baseline={sb['tokens_per_s']:.1f}")
            rows.append(
                f"serve_spec_{tag}_{name}_outputs_identical,{ident},"
                f"token-for-token vs non-speculative at temperature 0")
            assert ident, (
                f"speculative ({tag}/{name}) changed greedy tokens")
            assert s["spec_tokens_per_launch"] >= 1.0
    return rows


def observability_rows(smoke: bool = False) -> List[str]:
    """ISSUE 6 acceptance: lifecycle observability through the full
    gateway -> engine -> scheduler stack, plus an instrumentation
    overhead A/B.

    (a) a multi-tenant mix runs through a ``Gateway(obs=...)``; one
        ``collect_metrics`` snapshot must carry scheduler, KV-pool,
        prefix-cache, serving-latency, and per-tenant gateway series in
        Prometheus text form;
    (b) the Perfetto trace must round-trip ``json.loads`` and
        reconstruct at least one request's full lifecycle
        (queued -> prefill -> decode -> finish, in order, on one track);
    (c) instrumentation must cost < 2% of the uninstrumented decode
        tokens/s — measured by attribution (exact instrument-op counts
        from an obs-on run x tight-loop per-op costs, over the obs-off
        run time), because direct run-vs-run wall-clock deltas have a
        +-5% null spread on a contended CI core.
    The snapshot + trace are kept in ``_STATE`` so ``--json`` can write
    them as sibling CI artifacts."""
    import time

    from repro.core.gateway import Gateway, ModelEntry
    from repro.obs import Observability

    cfg, params = _tiny()
    sched = SchedulerConfig(prefill_chunk=32, prefix_block=8)
    gen = 12 if smoke else 24
    rng = np.random.default_rng(41)
    system = list(map(int, rng.integers(1, 255, 24)))
    prompts = [system + list(map(int, rng.integers(1, 255, 6)))
               for _ in range(8)]

    # (a)+(b): governed mix with obs attached
    obs = Observability()
    eng = InferenceEngine(cfg, params, max_batch=4, capacity=192,
                          sched=sched, obs=obs)
    gw = Gateway(obs=obs)
    gw.vet_model(ModelEntry(cfg.name, cfg.name, 0.5, 1.5), cfg)
    gw.bind_endpoints(cfg.name, [eng])
    keys = {p: gw.mint_key(p) for p in ("tenant-a", "tenant-b")}
    rids = []
    for i, p in enumerate(prompts):
        proj = "tenant-a" if i % 2 == 0 else "tenant-b"
        out = gw.completion(api_key=keys[proj].key, model=cfg.name,
                            prompt=list(p), max_tokens=gen)
        rids.append(out["id"])
    gw.collect_metrics()
    prom = obs.registry.to_prometheus()
    lines = prom.splitlines()
    subsystems = ("repro_sched_", "repro_kv_", "repro_prefix_",
                  "repro_serving_", "repro_gateway_")
    n_series = {}
    for pre in subsystems:
        # sample lines only (HELP/TYPE lines start with '#')
        n_series[pre] = sum(1 for ln in lines if ln.startswith(pre))
        assert n_series[pre] > 0, f"snapshot missing {pre}* series"
    assert 'project="tenant-a"' in prom and 'project="tenant-b"' in prom, \
        "per-tenant gateway accounting missing from snapshot"

    trace_text = obs.tracer.to_json()
    trace = json.loads(trace_text)            # must round-trip
    ev = trace["traceEvents"]
    tid_name = {e["tid"]: e["args"]["name"] for e in ev
                if e.get("ph") == "M" and e.get("name") == "thread_name"}
    lifecycle_ok = 0
    for rid in rids:
        tids = [t for t, nm in tid_name.items() if nm == f"req {rid}"]
        if not tids:
            continue
        spans = sorted((e["ts"], e["name"]) for e in ev
                       if e.get("ph") == "X" and e["tid"] == tids[0])
        names = [n for _, n in spans]
        insts = [e["name"] for e in ev
                 if e.get("ph") == "i" and e["tid"] == tids[0]]
        if (names and names[0] == "queued" and "prefill" in names
                and "decode" in names
                and names.index("prefill") < names.index("decode")
                and "finish" in insts):
            lifecycle_ok += 1
    assert lifecycle_ok == len(rids), (
        f"only {lifecycle_ok}/{len(rids)} request lifecycles "
        f"reconstructed from the trace")
    _STATE["obs_artifacts"] = (prom, trace_text)

    # (c): instrumentation overhead, obs on vs off.  Direct wall-clock
    # A/B between two separate engine runs cannot resolve 2% on a
    # contended CI core: a null experiment (off vs off, alternating
    # order, median/min of 12 process_time runs each) still shows a
    # +-5% spread, so any direct-delta assert at 2% is a coin flip.
    # The overhead is therefore measured by ATTRIBUTION, which is exact
    # and noise-robust:
    #   1. run the instrumented engine once and count the instrument
    #      ops it actually performed (span X-events + instants from the
    #      trace; histogram observes, gauge sets, counter incs from
    #      registry snapshot diffs — every push op is one of these);
    #   2. microbenchmark each op in a tight loop (min of several
    #      passes of process_time: contention noise is one-sided);
    #   3. overhead = sum(count * cost) / uninstrumented run time.
    # Noise enters only multiplicatively on an already-small ratio
    # (+-10% on ~0.6% stays ~0.6%), instead of additively on a delta of
    # two large numbers.  Both arms' measured tokens/s are reported
    # alongside for reference, with a loose 25% sanity band.
    import gc

    from repro.obs.registry import MetricsRegistry
    from repro.obs.tracer import Tracer
    gen_ab = 48

    def mk(obs_on: bool):
        return InferenceEngine(cfg, params, max_batch=4, capacity=192,
                               sched=SchedulerConfig(prefill_chunk=32,
                                                     prefix_block=8),
                               obs=Observability() if obs_on else None)

    def run_once(e) -> Tuple[int, float]:
        reqs = [Request(prompt=list(p), max_new_tokens=gen_ab)
                for p in prompts]
        for r in reqs:
            e.submit(r)
        gc.collect()
        t0 = time.process_time()
        e.run_until_idle()
        dt = time.process_time() - t0
        return sum(len(r.generated) for r in reqs), dt

    e_off, e_on = mk(False), mk(True)
    run_once(e_off), run_once(e_on)           # compile + cache warmup

    # 1. op counts from one instrumented run (trace + snapshot diffs)
    def ph_counts(tr):
        evs = tr.to_perfetto()["traceEvents"]
        return (sum(1 for e in evs if e.get("ph") == "X"),
                sum(1 for e in evs if e.get("ph") in ("i", "C")))

    o = e_on.obs
    kinds = o.registry.kinds()
    x0, i0 = ph_counts(o.tracer)
    snap0 = o.registry.snapshot()
    ntok, _ = run_once(e_on)
    x1, i1 = ph_counts(o.tracer)
    snap1 = o.registry.snapshot()

    def series_kind(key):
        return kinds.get(key.split("{", 1)[0], "gauge")

    n_observe = n_inc = 0
    tick_key = "repro_sched_tick_seconds"
    for key, v1 in snap1.items():
        v0 = snap0.get(key, {"count": 0} if isinstance(v1, dict) else 0.0)
        if isinstance(v1, dict):
            n_observe += v1["count"] - v0["count"]
        elif series_kind(key) == "counter":
            # every push-side counter inc is +1, so the value delta IS
            # the call count (pull-side .set()s only happen at
            # collect_metrics, which this run never calls)
            n_inc += int(v1 - v0)
    # gauges are set absolutely so snapshots can't be diffed for call
    # counts; the only per-run gauge sets are queue+running, twice per
    # tick
    n_ticks = (snap1[tick_key]["count"] - snap0[tick_key]["count"])
    counts = {"span": x1 - x0, "instant": i1 - i0,
              "observe": n_observe, "set": 2 * n_ticks, "inc": n_inc}

    # 2. per-op tight-loop costs (scratch tracer/registry, min-of-k)
    def bench(fn, n=5000 if smoke else 20000, passes=3 if smoke else 5):
        best = float("inf")
        for _ in range(passes):
            t0 = time.process_time()
            for _ in range(n):
                fn()
            best = min(best, (time.process_time() - t0) / n)
        return best

    st = Tracer(max_events=10_000_000)

    def op_span():
        sp = st.begin("scheduler", "micro_step", cat="sched",
                      decoding=3, prefilling=1)
        st.end(sp)

    sreg = MetricsRegistry()
    sh = sreg.histogram("repro_sched_tick_seconds", "bench")
    sg = sreg.gauge("repro_sched_queue_depth_requests", "bench")
    sc = sreg.counter("repro_sched_admitted_requests_total", "bench")
    costs = {
        "span": bench(op_span),
        "instant": bench(lambda: st.instant("req 0", "finish",
                                            cat="request", n_generated=1)),
        "observe": bench(lambda: sh.observe(0.013)),
        "set": bench(lambda: sg.set(5)),
        "inc": bench(lambda: sc.inc()),
    }

    # 3. attribute against the uninstrumented run (min: noise only adds)
    n_runs = 3 if smoke else 5
    t_off = min(run_once(e_off)[1] for _ in range(n_runs))
    t_on = min(run_once(e_on)[1] for _ in range(n_runs))
    extra = sum(counts[k] * costs[k] for k in counts)
    delta = extra / t_off
    tps_off, tps_on = ntok / t_off, ntok / t_on
    rows = [
        f"serve_obs_snapshot_series,{sum(n_series.values())},"
        + " ".join(f"{p.rstrip('_')}={n}" for p, n in n_series.items()),
        f"serve_obs_trace_events,{len(ev)},"
        f"lifecycles_reconstructed={lifecycle_ok}/{len(rids)}",
        f"serve_obs_overhead_pct,{delta * 100:.2f},"
        f"{sum(counts.values())} instrument ops (span={counts['span']}"
        f" observe={counts['observe']}) x tight-loop cost"
        f" / {t_off * 1e3:.0f}ms uninstrumented run; target<2",
        f"serve_obs_tps,{tps_on:.0f},on vs {tps_off:.0f} off"
        f" tokens_per_s (cpu-time, best of {n_runs}; reference only)",
    ]
    assert delta < 0.02, (
        f"observability overhead {delta * 100:.2f}% >= 2% "
        f"(counts={counts}, costs(us)="
        f"{ {k: round(v * 1e6, 2) for k, v in costs.items()} }, "
        f"t_off={t_off * 1e3:.1f}ms)")
    assert tps_on > 0.75 * tps_off, (
        f"instrumented engine tokens/s sanity band blown: "
        f"on={tps_on:.0f} off={tps_off:.0f}")
    return rows


def chaos_rows(smoke: bool = False) -> List[str]:
    """ISSUE 7 acceptance: serving-plane fault tolerance, end to end.

    Two engine replicas behind the resilient gateway on a VIRTUAL
    clock; a deterministic injector kills replica e0 mid-decode of one
    request.  The gateway must ride through it — breaker opens, the
    evacuated request (committed tokens folded into its prompt) retries
    onto e1 and resumes token-exactly — and, after e0 recovers and the
    breaker cooldown elapses, a half-open probe must re-close the
    circuit and return traffic to e0.  Hard asserts: 100% of requests
    complete, temp-0 outputs token-identical to a fault-free run, the
    breaker is seen opening AND re-closing in the metrics snapshot, and
    ``time.sleep`` is patched to raise for the whole run (retry backoff
    must use the injected clock only)."""
    import time

    from repro.core.gateway import Gateway, ModelEntry
    from repro.obs import Observability
    from repro.serving.faults import FaultInjector, FaultSpec, VirtualClock

    cfg, params = _tiny()
    gen = 8 if smoke else 12
    n_req = 8 if smoke else 12
    rng = np.random.default_rng(29)
    prompts = [list(map(int, rng.integers(1, 255,
                                          int(rng.integers(6, 12)))))
               for _ in range(n_req)]

    def serve(gw, key):
        outs = []
        for p in prompts:
            out = gw.completion(api_key=key.key, model=cfg.name,
                                prompt=list(p), max_tokens=gen)
            outs.append((out["tokens"], out["usage"]["engine"]))
        return outs

    # fault-free reference (token-identity baseline; routing is
    # irrelevant to greedy outputs — every replica holds the same
    # weights)
    e_ref = _mk_engine(capacity=192)
    gw_ref = Gateway()
    gw_ref.vet_model(ModelEntry(cfg.name, cfg.name, 0.5, 1.5), cfg)
    gw_ref.bind_endpoints(cfg.name, [e_ref])
    ref = [t for t, _ in serve(gw_ref, gw_ref.mint_key("chaos"))]

    # chaos run: crash e0 mid-decode of its 3rd request (each request
    # costs gen-1 micro-step fault checks after its one-shot prefill)
    at_call = 2 * (gen - 1) + 4
    vc = VirtualClock()
    obs = Observability(clock=vc.now)
    inj = FaultInjector(
        [FaultSpec(point="micro_step", kind="crash", at_call=at_call)],
        clock_advance=vc.advance)
    cfg_, params_ = _tiny()
    e0 = InferenceEngine(cfg_, params_, max_batch=4, capacity=192,
                         clock=vc, name="chaos-e0", faults=inj)
    e1 = InferenceEngine(cfg_, params_, max_batch=4, capacity=192,
                         clock=vc, name="chaos-e1")
    gw = Gateway(clock=vc, obs=obs, retry_budget=3, breaker_threshold=1,
                 breaker_cooldown_s=5.0, sleep=vc.sleep)
    gw.vet_model(ModelEntry(cfg.name, cfg.name, 0.5, 1.5), cfg)
    gw.bind_endpoints(cfg.name, [e0, e1])
    key = gw.mint_key("chaos")

    def no_real_sleep(_dt):
        raise AssertionError("real time.sleep in the retry/backoff path")

    outs, engines, recovered_after = [], [], None
    orig_sleep, time.sleep = time.sleep, no_real_sleep
    try:
        for i, p in enumerate(prompts):
            out = gw.completion(api_key=key.key, model=cfg.name,
                                prompt=list(p), max_tokens=gen)
            outs.append(out["tokens"])
            engines.append(out["usage"]["engine"])
            if e0.health() == "down" and recovered_after is None:
                # the "operator" restarts the dead replica; advancing
                # past the breaker cooldown arms the half-open probe
                e0.recover()
                vc.advance(gw.breaker_cooldown_s + 1.0)
                recovered_after = i
    finally:
        time.sleep = orig_sleep

    snap = obs.registry.snapshot()
    tr = {s: snap[s] for s in snap
          if s.startswith("repro_gateway_breaker_transitions_total")}
    n_open = sum(v for s, v in tr.items() if 'state="open"' in s)
    n_closed = sum(v for s, v in tr.items() if 'state="closed"' in s)
    final_state = snap.get(
        'repro_gateway_breaker_state{engine="chaos-e0"}', -1)
    n_retries = sum(v for s, v in snap.items()
                    if s.startswith("repro_serving_retries_total"))
    n_preempted = e0.metrics.summary()["preempted"]
    identical = int(outs == ref)
    failed_over = int("chaos-e1" in engines)
    returned = int(recovered_after is not None
                   and "chaos-e0" in engines[recovered_after + 1:])
    rows = [
        f"serve_chaos_completed,{len(outs)}/{n_req},"
        f"one of two engines crashed at micro-step {at_call}",
        f"serve_chaos_outputs_identical,{identical},"
        f"token-for-token vs fault-free run at temperature 0",
        f"serve_chaos_retries,{n_retries:.0f},"
        f"failed_over_to_e1={failed_over} budget=3",
        f"serve_chaos_preempted,{n_preempted:.0f},"
        f"committed tokens folded into the prompt on evacuation",
        f"serve_chaos_breaker_reclosed,{int(n_closed >= 1)},"
        f"open={n_open:.0f} closed={n_closed:.0f}"
        f" final_state={final_state:.0f}"
        f" traffic_returned_to_e0={returned}",
    ]
    assert len(outs) == n_req, f"only {len(outs)}/{n_req} completed"
    assert identical, "chaos run changed temp-0 tokens"
    assert inj.fired, "the injected crash never fired"
    assert n_retries >= 1 and failed_over, "gateway never retried"
    assert n_preempted >= 1, "crash evacuation never folded tokens"
    assert n_open >= 1 and n_closed >= 1, (
        f"breaker not seen opening AND re-closing: {tr}")
    assert final_state == 0 and returned, (
        "recovered engine never re-earned traffic")
    return rows


def sharded_rows(smoke: bool = False) -> List[str]:
    """ISSUE 8 acceptance: tensor-parallel serving token identity.

    The same greedy mix through a TP=2 engine (``("model",)`` mesh,
    serving_tp rules) and the plain single-device engine.  Token
    identity is a hard assert — TP reshards contractions, so this is
    the row that catches a rules/constraint regression; tokens/s is
    reported for parity (two forced host devices share one CPU, so no
    speedup is claimed), and per-device KV bytes shows the head-sharded
    pool halving each device's KV footprint."""
    if jax.device_count() < 2:
        return ["serve_tp_skipped,1,needs >=2 devices (CLI runs force "
                "2 host devices; in-process imports may be too late)"]
    cfg, params = _tiny()
    gen = 10 if smoke else 20
    rng = np.random.default_rng(31)
    prompts = [list(map(int, rng.integers(1, 255,
                                          int(rng.integers(6, 16)))))
               for _ in range(6)]

    def go(mesh):
        eng = InferenceEngine(cfg, params, max_batch=4, capacity=192,
                              mesh=mesh)
        reqs = [Request(prompt=list(p), max_new_tokens=gen)
                for p in prompts]
        for r in reqs:
            eng.submit(r)
        s = eng.run_until_idle()
        return [r.generated for r in reqs], s, eng.kv_stats()

    base, s1, kv1 = go(None)
    tp, s2, kv2 = go(make_mesh((2,), ("model",)))
    identical = int(base == tp)
    rows = [
        f"serve_tp2_outputs_identical,{identical},"
        f"token-for-token vs TP=1 at temperature 0 (hard assert)",
        f"serve_tp2_decode_tokens_per_s,{s2['tokens_per_s']:.1f},"
        f"tp1={s1['tokens_per_s']:.1f} (2 host devices on one CPU: "
        f"parity, not speedup)",
        f"serve_tp2_kv_peak_bytes_per_device,"
        f"{kv2['kv_peak_bytes_per_device']},"
        f"tp1={kv1['kv_peak_bytes_per_device']}"
        f" block_bytes_per_device={kv2['kv_block_bytes_per_device']}"
        f" (KV-head-sharded pool)",
    ]
    assert identical, "TP=2 engine diverged from TP=1 greedy tokens"
    assert kv2["kv_tp_degree"] == 2 and kv1["kv_tp_degree"] == 1
    assert kv2["kv_block_bytes_per_device"] * 2 \
        == kv1["kv_block_bytes_per_device"], (kv1, kv2)
    return rows


def disagg_rows(smoke: bool = False) -> List[str]:
    """ISSUE 9 acceptance: disaggregated prefill/decode serving.

    The same greedy mix through (a) one unified paged engine and (b) a
    prefill-pool -> KV-handoff -> decode-pool pair driven by the
    gateway's :class:`DisaggRouter` pipelined batch driver — on paged
    GQA AND paged MLA.  Hard asserts: outputs token-identical at
    temperature 0, every prompt exported exactly one handoff and every
    handoff imported, and the ``repro_serving_handoff_*`` counters plus
    the handoff-latency histogram all land in ONE gateway metrics
    snapshot.  TTFT/ITL/tokens-per-s rows are reported for both sides;
    both pools share one CPU here, so the rows demonstrate phase
    separation and token-exactness, not acceleration (the paper's
    point is that the phases want *different* hardware)."""
    from repro.core.gateway import Gateway
    from repro.obs import Observability

    gen = 10 if smoke else 24
    n_req = 6 if smoke else 10
    rows = []
    for tag, (cfg, params) in (("gqa", _tiny()), ("mla", _tiny_mla())):
        rng = np.random.default_rng(41)
        prompts = [list(map(int, rng.integers(1, cfg.vocab_size - 1,
                                              int(rng.integers(8, 24)))))
                   for _ in range(n_req)]
        uni = InferenceEngine(cfg, params, max_batch=4, capacity=192,
                              paged=True)
        ureqs = [Request(prompt=list(p), max_new_tokens=gen)
                 for p in prompts]
        for r in ureqs:
            uni.submit(r)
        su = uni.run_until_idle()
        base = [r.generated for r in ureqs]

        obs = Observability()
        pre = InferenceEngine(cfg, params, max_batch=4, capacity=192,
                              paged=True, role="prefill", obs=obs,
                              name=f"{tag}-prefill0")
        dec = InferenceEngine(cfg, params, max_batch=4, capacity=192,
                              paged=True, role="decode", obs=obs,
                              name=f"{tag}-decode0")
        gw = Gateway(obs=obs)
        router = gw.bind_disagg(cfg.name, [pre], [dec])
        dreqs = [Request(prompt=list(p), max_new_tokens=gen)
                 for p in prompts]
        outs = router.run_pipelined(dreqs)
        sd = dec.metrics.summary()
        sp = pre.metrics.summary()
        identical = int(outs == base)
        snap = gw.collect_metrics().snapshot()
        n_out = snap.get("repro_serving_handoff_exported_total", 0)
        n_in = snap.get("repro_serving_handoff_imported_total", 0)
        n_bytes = snap.get("repro_serving_handoff_bytes_total", 0)
        n_lat = snap.get("repro_serving_handoff_seconds",
                         {}).get("count", 0)
        rows += [
            f"serve_disagg_{tag}_outputs_identical,{identical},"
            f"token-for-token vs unified paged engine at temperature 0 "
            f"(hard assert)",
            f"serve_disagg_{tag}_ttft_p50,{sd['ttft_p50_s'] * 1e6:.0f},"
            f"unified={su['ttft_p50_s'] * 1e6:.0f} (us; disagg TTFT "
            f"includes the handoff import)",
            f"serve_disagg_{tag}_itl_mean,{sd['itl_mean_s'] * 1e6:.0f},"
            f"unified={su['itl_mean_s'] * 1e6:.0f} (us)",
            f"serve_disagg_{tag}_decode_tokens_per_s,"
            f"{sd['tokens_per_s']:.1f},unified={su['tokens_per_s']:.1f}"
            f" (both pools share one CPU: parity, not speedup)",
            f"serve_disagg_{tag}_handoffs,{n_out:.0f},"
            f"imported={n_in:.0f} payload_bytes={n_bytes:.0f}"
            f" latency_samples={n_lat:.0f}",
        ]
        assert identical, f"disagg ({tag}) diverged from unified tokens"
        assert sp["handed_off"] == n_req and sd["completed"] == n_req, (
            sp["handed_off"], sd["completed"])
        assert n_out == n_req and n_in >= n_req, (n_out, n_in)
        assert n_bytes > 0 and n_lat >= n_req, (n_bytes, n_lat)
    return rows


def kv_quant_rows(smoke: bool = False) -> List[str]:
    """ISSUE 10 acceptance: int8 quantized KV serving, same-budget A/B.

    The same greedy mix through a bf16-KV and an int8-KV paged engine
    at a FIXED ``pool_tokens`` budget (bf16-byte-equivalent, so the
    int8 pool carries 2x the blocks in the same device bytes).  Hard
    asserts: the int8 engine sustains >= 1.8x the bf16 engine's peak
    concurrent decode batch, its per-block device bytes land at ~1/2
    (int8 payload + f32 scale sliver), and its greedy tokens match the
    bf16 run at >= 90% per-token agreement (the accuracy-guard floor —
    on these tiny models agreement is typically exact)."""
    budget, capacity = 96, 64
    gen = 10 if smoke else 16
    n_req = 8
    sched = SchedulerConfig(enable_prefix_cache=False, admit_per_tick=8,
                            prefill_chunk=32, prefix_block=8)
    rng = np.random.default_rng(47)
    # 20-token prompts = 3 blocks each at admission: the 12-block bf16
    # pool admits 4, the 24-block int8 pool the full max_batch of 8
    prompts = [list(map(int, rng.integers(1, 255, 20)))
               for _ in range(n_req)]
    cfg, params = _tiny()
    res = {}
    for dt in ("bf16", "int8"):
        eng = InferenceEngine(cfg, params, max_batch=8, capacity=capacity,
                              sched=sched, paged=True,
                              pool_tokens=budget, kv_dtype=dt)
        reqs = [Request(prompt=list(p), max_new_tokens=gen)
                for p in prompts]
        for r in reqs:
            eng.submit(r)
        peak = 0
        while eng.num_active:
            eng.step()
            peak = max(peak, len(eng.running))
        res[dt] = (peak, eng.metrics.summary(), eng.kv_stats(),
                   [r.generated for r in reqs])
    hit = tot = 0
    for a, b in zip(res["int8"][3], res["bf16"][3]):
        tot += len(b)
        hit += sum(1 for x, y in zip(a, b) if x == y)
    match = hit / max(tot, 1)
    gain = res["int8"][0] / max(res["bf16"][0], 1)
    bratio = (res["int8"][2]["kv_block_bytes_per_device"]
              / res["bf16"][2]["kv_block_bytes_per_device"])
    rows = []
    for dt in ("bf16", "int8"):
        peak, s, kv, _ = res[dt]
        rows.append(
            f"serve_kv_{dt}_concurrent_batch_peak,{peak},"
            f"pool_tokens={budget} blocks_total={kv['kv_blocks_total']}"
            f" block_tokens={kv['kv_block_size']}")
        rows.append(
            f"serve_kv_{dt}_block_bytes_per_device,"
            f"{kv['kv_block_bytes_per_device']},"
            f"peak_bytes={kv['kv_peak_bytes_per_device']}")
        rows.append(
            f"serve_kv_{dt}_decode_tokens_per_s,{s['tokens_per_s']:.1f},"
            f"generated={s['generated_tokens']}")
    rows.append(f"serve_kv_int8_batch_gain,{gain:.2f},"
                f"int8_peak/bf16_peak at equal pool_tokens; target>=1.8")
    rows.append(f"serve_kv_int8_block_bytes_ratio,{bratio:.2f},"
                f"int8/bf16 per-block device bytes; target~0.5")
    rows.append(f"serve_kv_int8_match_rate_pct,{match * 100:.1f},"
                f"greedy per-token agreement vs bf16; floor=90")
    assert gain >= 1.8, (
        f"int8 sustained only {gain:.2f}x the bf16 concurrent batch "
        f"({res['int8'][0]} vs {res['bf16'][0]}) at pool_tokens={budget}")
    assert 0.45 < bratio < 0.6, bratio
    assert match >= 0.90, f"int8 KV match rate {match:.2f} below floor"
    return rows


def analytic_itl(arch: str, tp: int, batch: int, ctx: int) -> float:
    """Decode step latency (s) on v5e: max(weights+KV reads / HBM, flops)."""
    cfg = get_config(arch)
    w_bytes = cfg.param_count() * 2 / tp
    kv_per_tok = (cfg.kv_cache_bytes_per_token_per_layer
                  * len(cfg.attn_layer_ids()))
    kv_bytes = kv_per_tok * ctx * batch / tp
    t_mem = (w_bytes + kv_bytes) / HBM_BW
    t_flops = 2 * cfg.param_count(active_only=True) * batch / (tp * PEAK)
    return max(t_mem, t_flops)


def analytic_rows() -> List[str]:
    rows = []
    for arch, tp, paper_ms in (("apertus-8b", 4, 11.0),
                               ("apertus-70b", 8, 42.0)):
        itl = analytic_itl(arch, tp, batch=8, ctx=1024)
        rows.append(f"serve_analytic_itl_{arch},{itl * 1e6:.0f},"
                    f"paper_ms={paper_ms} (GH200; v5e-chips={tp})")
    return rows


def run(paged: Optional[bool] = None, smoke: bool = False) -> List[str]:
    if smoke:
        return (shared_prefix_rows() + paged_vs_dense_rows(smoke=True)
                + multi_adapter_rows(smoke=True)
                + speculative_rows(smoke=True)
                + observability_rows(smoke=True)
                + chaos_rows(smoke=True)
                + sharded_rows(smoke=True)
                + disagg_rows(smoke=True)
                + kv_quant_rows(smoke=True))
    return (measured_rows(paged) + shared_prefix_rows()
            + paged_vs_dense_rows() + multi_adapter_rows()
            + speculative_rows() + observability_rows()
            + chaos_rows() + sharded_rows() + disagg_rows()
            + kv_quant_rows() + analytic_rows())


def rows_to_json(rows: List[str]) -> List[dict]:
    """``name,value,note`` row strings -> structured records (the CI
    build artifact; value stays a string — some rows carry composites)."""
    out = []
    for r in rows:
        parts = r.split(",", 2)
        out.append({"name": parts[0],
                    "value": parts[1] if len(parts) > 1 else "",
                    "note": parts[2] if len(parts) > 2 else ""})
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--paged", action="store_true",
                   help="paged KV for the measured mixes (default)")
    g.add_argument("--dense", action="store_true",
                   help="dense KV for the measured mixes (A/B baseline)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset: shared-prefix + paged-vs-dense "
                         "+ multi-LoRA + speculative + obs + chaos + "
                         "sharded TP=2")
    ap.add_argument("--chaos-smoke", action="store_true",
                    help="run ONLY the fault-tolerance chaos mix (the "
                         "CI chaos job)")
    ap.add_argument("--disagg-smoke", action="store_true",
                    help="run ONLY the disaggregated prefill/decode mix "
                         "(the CI disagg job)")
    ap.add_argument("--kv-quant-smoke", action="store_true",
                    help="run ONLY the int8-vs-bf16 quantized-KV A/B "
                         "(the CI kv-quant job)")
    ap.add_argument("--json", default="",
                    help="also write rows as JSON to this path (CI "
                         "uploads it as a build artifact)")
    args = ap.parse_args()
    paged = False if args.dense else True
    if args.chaos_smoke:
        rows = chaos_rows(smoke=True)
    elif args.disagg_smoke:
        rows = disagg_rows(smoke=True)
    elif args.kv_quant_smoke:
        rows = kv_quant_rows(smoke=True)
    else:
        rows = run(paged=paged, smoke=args.smoke)
    print("\n".join(rows))
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump({"smoke": args.smoke, "kv": "paged" if paged
                       else "dense", "rows": rows_to_json(rows)}, f,
                      indent=2)
        print(f"wrote {args.json}")
        if "obs_artifacts" in _STATE:
            # sibling CI artifacts: the observability run's registry
            # snapshot (Prometheus text) and Perfetto trace
            prom, trace_text = _STATE["obs_artifacts"]
            stem = args.json.rsplit(".json", 1)[0]
            for path, text in ((stem + ".metrics.txt", prom),
                               (stem + ".trace.json", trace_text)):
                with open(path, "w", encoding="utf-8") as f:
                    f.write(text)
                print(f"wrote {path}")

"""Serving driver: one engine replica behind the governed gateway.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b \
        --requests 8 --max-tokens 16

``--scale full`` serves the architecture at its published widths with
bf16 weights (qwen1.5-4b is 15.8 GB in f32, more than one 16 GB TPU v5e
chip holds); ``--scale tiny`` serves the scaled-down config in f32.

``--tp N`` serves one *sharded* replica (tensor parallelism over a
``("model",)`` mesh, serving/README.md "Sharded serving"); the gateway
still sees exactly one endpoint.  With ``JAX_PLATFORMS=cpu`` set, serve
forces N XLA host devices so the flag runs on a CPU host; on an
accelerator it needs N real devices.

Restores weights from ``--ckpt-dir`` when given (e.g. from
``repro.launch.train``) and fails if it cannot; otherwise serves random
weights drawn from a fixed seed.

``main(argv)`` returns the run's summary (per-request tokens and TTFT,
engine metrics, KV stats), so a caller in the same process can check it.
"""
from __future__ import annotations

import argparse
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, scaled_down
from repro.core.gateway import Gateway, GatewayError, ModelEntry
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.parallel import sharding
from repro.serving.engine import InferenceEngine, Request


def load_params(cfg, scale: str, key, mesh=None, ckpt_dir: str = ""):
    """Serving weights: ``--scale tiny`` in f32, ``--scale full`` in
    bf16.  Full-width weights are generated (or restored) already
    sharded over ``mesh`` under the serving rules, so no device ever
    holds the whole model (apertus-8b in bf16 is 16.1 GB).  An explicit
    ``ckpt_dir`` that cannot be restored raises."""
    dtype = jnp.float32 if scale == "tiny" else jnp.bfloat16
    init = functools.partial(M.init, cfg, dtype=dtype)
    shardings = None if mesh is None else sharding.tree_shardings(
        M.model_param_axes(cfg), mesh, sharding.make_rules("serving_tp"))
    if ckpt_dir:
        from repro.checkpoint import ckpt as C
        state, manifest = C.restore(
            ckpt_dir, {"params": jax.eval_shape(init, key)},
            shardings=None if shardings is None else {"params": shardings})
        print(f"restored weights from {ckpt_dir} step {manifest['step']}")
        return jax.tree.map(lambda x: x.astype(dtype), state["params"])
    if scale == "tiny":
        return init(key)
    return jax.jit(init, out_shardings=shardings)(key)


def make_prompt(vocab_size: int, n: int):
    """A prompt of ``n`` token ids drawn from a generator seeded by
    ``n``: prompts of equal length are equal, so a repeated length in
    ``--prompt-lens`` repeats a prompt."""
    rng = np.random.default_rng(n)
    return [int(x) for x in rng.integers(1, vocab_size - 1, n)]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--scale", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-lens", default="4,5,6,7,8",
                    help="comma-separated prompt lengths, cycled over "
                         "the requests; equal lengths give equal prompts")
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=256)
    ap.add_argument("--dense", action="store_true",
                    help="force the dense per-slot KV layout (default: "
                         "paged on supported architectures)")
    ap.add_argument("--pool-tokens", type=int, default=None,
                    help="paged KV pool size in tokens (default: "
                         "max_batch * capacity)")
    ap.add_argument("--kv-dtype", choices=["bf16", "int8"],
                    default="bf16",
                    help="paged KV pool storage precision: 'int8' "
                         "quantizes blocks symmetrically with per-block "
                         "f32 scales — the same --pool-tokens budget "
                         "buys ~2x the blocks (accuracy-guarded; see "
                         "serving/README.md 'Quantized serving')")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree of this replica (one "
                         "sharded engine = one gateway endpoint); KV "
                         "heads must divide N; with JAX_PLATFORMS=cpu "
                         "forces N XLA host devices")
    ap.add_argument("--adapters", type=int, default=0,
                    help="serve N demo LoRA adapters (tenant0..N-1) from "
                         "one adapter pool; requests round-robin across "
                         "base and model@tenantI")
    ap.add_argument("--adapter-slots", type=int, default=None,
                    help="device-resident adapter slots (default: "
                         "min(--adapters, 4); fewer than --adapters "
                         "exercises LRU eviction)")
    ap.add_argument("--speculative", choices=["ngram", "draft"],
                    default=None,
                    help="speculative decoding: 'ngram' (prompt-lookup, "
                         "model-free) or 'draft' (small draft model via "
                         "--draft-config); greedy outputs stay "
                         "token-identical to the plain engine")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens verified per launch")
    ap.add_argument("--draft-config", default="",
                    help="architecture name of the draft model (e.g. "
                         "qwen1.5-4b drafting for qwen2.5-32b); same "
                         "--scale treatment as the target; random-init "
                         "weights unless --draft-ckpt-dir is given")
    ap.add_argument("--draft-ckpt-dir", default="",
                    help="checkpoint dir for the draft model's weights")
    ap.add_argument("--role", choices=["unified", "prefill", "decode"],
                    default="unified",
                    help="engine role (disaggregated serving): "
                         "'prefill' runs prompts to KV-handoff export "
                         "and reports the outbox (one side of a "
                         "disaggregated deployment); 'decode' alone is "
                         "an error (nothing feeds it handoffs) — use "
                         "--disagg for the full pair in one process")
    ap.add_argument("--disagg", action="store_true",
                    help="serve a disaggregated prefill/decode engine "
                         "pair behind the gateway's DisaggRouter "
                         "(prefill pool -> KV handoff -> decode pool; "
                         "token-identical to unified at temperature 0)")
    ap.add_argument("--chaos", nargs="?", const="crash@micro_step:8",
                    default=None, metavar="KIND@POINT[:AT_CALL]",
                    help="arm fault injection on the engine (e.g. "
                         "crash@micro_step:8, reject@admission:2, "
                         "hang@micro_step:5:0.25); a crashed engine "
                         "auto-recovers after two health probes — pair "
                         "with --retry-budget to watch the gateway "
                         "ride through it (docs/robustness.md)")
    ap.add_argument("--retry-budget", type=int, default=0,
                    help="gateway retries per completion after an "
                         "engine failure (exponential backoff + full "
                         "jitter)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall budget; past it the request "
                         "is evacuated and DeadlineExceeded raised")
    ap.add_argument("--metrics-out", default="",
                    help="write a Prometheus text snapshot of the "
                         "metrics registry here (enables observability)")
    ap.add_argument("--trace-out", default="",
                    help="write a Perfetto trace_event JSON here "
                         "(enables observability)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="with --metrics-out: also re-dump the snapshot "
                         "every N requests (a cheap stand-in for a "
                         "scrape endpoint)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    flags = os.environ.get("XLA_FLAGS", "")
    if (args.tp > 1 and os.environ.get("JAX_PLATFORMS") == "cpu"
            and "xla_force_host_platform_device_count" not in flags):
        # a CPU run stands N host devices in for the mesh; XLA reads the
        # flag when the backend starts, so a process that has queried
        # its devices already gets the device-count error below
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={args.tp}"
        ).strip()
    mesh = None
    if args.tp > 1:
        if jax.device_count() < args.tp:
            ap.error(f"--tp {args.tp} needs {args.tp} devices, have "
                     f"{jax.device_count()}")
        mesh = make_mesh((args.tp,), ("model",),
                         devices=jax.devices()[:args.tp])
        print(f"tensor parallel: TP={args.tp} over "
              f"{[d.platform + str(d.id) for d in mesh.devices.flat]}")

    cfg = get_config(args.arch)
    if args.scale == "tiny":
        cfg = scaled_down(cfg)
    params = load_params(cfg, args.scale, jax.random.PRNGKey(0), mesh,
                         args.ckpt_dir)

    if args.role == "decode" and not args.disagg:
        ap.error("--role decode has no handoff source in a single-engine "
                 "process; use --disagg for the prefill/decode pair")
    if (args.disagg or args.role != "unified") and args.dense:
        ap.error("disaggregated roles need the paged KV layout "
                 "(KV handoffs are block-granular); drop --dense")
    if args.kv_dtype == "int8" and args.dense:
        ap.error("--kv-dtype int8 needs the paged KV layout (per-block "
                 "scales live in the block pool); drop --dense")
    adapter_slots = (min(args.adapters, 4) if args.adapter_slots is None
                     else args.adapter_slots)
    if args.adapters and adapter_slots < 1:
        ap.error("--adapters requires --adapter-slots >= 1")
    draft_cfg = draft_params = None
    if args.speculative == "draft":
        if not args.draft_config:
            ap.error("--speculative draft requires --draft-config")
        draft_cfg = get_config(args.draft_config)
        if args.scale == "tiny":
            draft_cfg = scaled_down(draft_cfg)
        draft_params = load_params(draft_cfg, args.scale,
                                   jax.random.PRNGKey(1), None,
                                   args.draft_ckpt_dir)
    obs = None
    if args.metrics_out or args.trace_out:
        from repro.obs import Observability
        obs = Observability()
    def mk_engine(name="engine", role="unified", spec=True):
        # speculative decoding only makes sense where tokens are
        # emitted, so a prefill-role engine never carries a drafter
        return InferenceEngine(
            cfg, params, max_batch=args.max_batch,
            capacity=args.capacity,
            paged=False if args.dense else None,
            pool_tokens=args.pool_tokens,
            adapter_slots=adapter_slots,
            speculative=args.speculative if spec else None,
            spec_k=args.spec_k,
            draft_cfg=draft_cfg if spec else None,
            draft_params=draft_params if spec else None,
            obs=obs, mesh=mesh, name=name, role=role,
            kv_dtype=args.kv_dtype)

    pre = None
    if args.disagg:
        pre = mk_engine("prefill0", "prefill", spec=False)
        eng = mk_engine("decode0", "decode")
        print("disaggregated pair: prefill0 -> KV handoff -> decode0")
    else:
        eng = mk_engine(role=args.role)
    names = [cfg.name]
    if args.adapters:
        from repro.finetune.lora import (LoraConfig, lora_init,
                                         lora_randomize)
        from repro.finetune.sft import publish_adapter
        lcfg = LoraConfig(rank=4)
        for i in range(args.adapters):
            ad = lora_randomize(
                lora_init(params, lcfg, jax.random.PRNGKey(100 + i)),
                jax.random.PRNGKey(200 + i))
            publish_adapter(eng, f"tenant{i}", ad, lcfg)
            if pre is not None:
                # the adapter pin transfers with the handoff, so the
                # prefill pool must hold the same adapters
                publish_adapter(pre, f"tenant{i}", ad, lcfg)
            names.append(f"{cfg.name}@tenant{i}")

    lens = [int(x) for x in args.prompt_lens.split(",")]
    prompts = [make_prompt(cfg.vocab_size, lens[i % len(lens)])
               for i in range(args.requests)]

    if args.role == "prefill" and not args.disagg:
        # one side of a disaggregated deployment: run the prompts to
        # handoff export and report the outbox (no decode peer in this
        # process — --disagg serves the full pair)
        for prompt in prompts:
            eng.submit(Request(prompt=prompt,
                               max_new_tokens=args.max_tokens,
                               temperature=args.temperature))
        eng.run_until_idle()
        for req, ho in eng.outbox:
            print(f"handoff: rid={ho.request_id} tokens={ho.length} "
                  f"blocks={ho.n_blocks} bytes={ho.payload_bytes}")
        s = eng.metrics.summary()
        print("metrics:", {k: round(v, 4) for k, v in s.items()})
        if obs is not None:
            eng.collect_metrics()
            if args.metrics_out:
                obs.write_metrics(args.metrics_out)
                print(f"metrics snapshot -> {args.metrics_out}")
            if args.trace_out:
                obs.write_trace(args.trace_out)
        return {"metrics": s, "requests": [], "kv": eng.kv_stats()}
    endpoint = eng
    if args.chaos:
        from repro.serving.faults import (ChaosEngine, FaultInjector,
                                          parse_fault_spec)
        injector = FaultInjector([parse_fault_spec(args.chaos)])
        endpoint = ChaosEngine(eng, injector, auto_recover_probes=2)
        print(f"chaos armed: {args.chaos}")
    # short breaker cooldown so a recovered engine re-earns traffic
    # within a CLI demo run, not after 30 wall seconds
    gw = Gateway(obs=obs, retry_budget=args.retry_budget,
                 deadline_s=args.deadline_s,
                 breaker_threshold=1, breaker_cooldown_s=0.05)
    gw.vet_model(ModelEntry(cfg.name, cfg.name, 0.5, 1.5), cfg)
    if args.disagg:
        gw.bind_disagg(cfg.name, [pre], [endpoint])
    else:
        gw.bind_endpoints(cfg.name, [endpoint])
    key = gw.mint_key("cli", budget_usd=10.0)

    def dump_snapshot():
        if obs is None or not args.metrics_out:
            return
        gw.collect_metrics()          # pull engine/pool/cache state
        obs.write_metrics(args.metrics_out)

    results = []
    for i, prompt in enumerate(prompts):
        model = names[i % len(names)]
        try:
            out = gw.completion(api_key=key.key, model=model,
                                prompt=prompt,
                                max_tokens=args.max_tokens,
                                temperature=args.temperature)
        except GatewayError as e:
            # chaos demo: a failed request is an outcome to show, not a
            # crash of the driver
            print(f"req{i}: model={model} FAILED "
                  f"{type(e).__name__}: {e}")
            results.append({"model": model, "prompt": prompt,
                            "error": f"{type(e).__name__}: {e}"})
            continue
        rec = eng.metrics.requests.get(out["id"])
        ttft = rec.ttft if rec is not None else None
        results.append({"model": model, "prompt": prompt,
                        "tokens": list(out["tokens"]), "ttft_s": ttft})
        print(f"req{i}: model={model} prompt_len={len(prompt)} "
              f"ttft_s={ttft} -> {out['tokens']}")
        if args.snapshot_every and (i + 1) % args.snapshot_every == 0:
            dump_snapshot()
    s = eng.metrics.summary()
    print("metrics:", {k: round(v, 4) for k, v in s.items()})
    if args.disagg:
        ps = pre.metrics.summary()
        print(f"disagg: handoffs={ps['handed_off']} "
              f"(prefill0 -> decode0)")
    if args.kv_dtype == "int8":
        kv = eng.kv_stats()
        print(f"quantized KV: dtype=int8 "
              f"blocks_total={kv['kv_blocks_total']} "
              f"block_bytes_per_device="
              f"{kv.get('kv_block_bytes_per_device', 0)} B "
              f"(~2x blocks at the same --pool-tokens budget)")
    if args.tp > 1:
        kv = eng.kv_stats()
        line = f"sharded replica: tp={kv.get('kv_tp_degree', args.tp)}"
        if "kv_peak_bytes_per_device" in kv:
            line += (f" peak_kv_per_device="
                     f"{kv['kv_peak_bytes_per_device']} B")
        print(line)
    if args.speculative:
        print(f"speculative[{args.speculative}] k={args.spec_k}: "
              f"acceptance={s['spec_acceptance_rate']:.3f} "
              f"tokens/launch={s['spec_tokens_per_launch']:.2f}")
    if args.adapters:
        print("adapter pool:", eng.adapter_stats())
        print("usage by adapter:", gw.usage_by_adapter())
    print("usage:", gw.usage_by_project())
    if obs is not None:
        dump_snapshot()
        if args.metrics_out:
            print(f"metrics snapshot -> {args.metrics_out}")
        if args.trace_out:
            obs.write_trace(args.trace_out)
            print(f"perfetto trace -> {args.trace_out} "
                  f"(open at https://ui.perfetto.dev)")
    return {"metrics": s, "requests": results, "kv": eng.kv_stats()}


if __name__ == "__main__":
    main()

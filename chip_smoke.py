"""Smoke run of the serving path on a TPU chip.

    python chip_smoke.py               # one chip: qwen1.5-4b via the gateway
    python chip_smoke.py --four-chips  # four chips: apertus-8b, TP=4 vs TP=2

The default mode serves qwen1.5-4b at its published widths (40 layers,
d_model 2560, 20/20 heads of 128, vocab 151936) with random bf16 weights
from a fixed seed, through ``repro.launch.serve.main``: gateway ->
scheduler -> paged bf16 KV pool -> fused decode jits, in this process.
One prompt is longer than the scheduler's 512-token prefill chunk, so
chunked prefill runs, and one prompt is sent twice.  It checks that every
request returns its full token count, every token lies in the
vocabulary, and the repeated prompt gives identical greedy tokens.

``--four-chips`` runs only the tensor-parallel path: apertus-8b at its
published widths (bf16 weights, 16.1 GB) served at ``--tp 4`` and, in
the same process with the same prompts, at ``--tp 2``.  It checks that
the two meshes' prefill logits agree within LOGIT_RTOL and that the
first greedy token of every request is identical.

Everything printed before the last line is smoke output, not a
benchmark metric.  The last line is one JSON object naming the device.
Without a TPU the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import jax

ROOT = os.path.dirname(os.path.abspath(__file__))

# Pool of the one-chip run: a compile for v5e puts the full-width decode
# step at ~9.6 GB of arguments (7.9 GB weights + 1.7 GB pool) and ~1.8 GB
# of temporaries here; max_batch 8 at capacity 2048 does not fit 15.75 GB.
MAX_BATCH, CAPACITY = 4, 1024
# 600 > prefill_chunk (512): chunked prefill.  Prompts of equal length
# are equal (serve.make_prompt), so the two 12s are one repeated prompt;
# 12 tokens is less than one 16-token KV block, so the repeat cannot hit
# the prefix cache and takes the same path as the first.
PROMPT_LENS = (600, 12, 24, 12)
MAX_TOKENS = 24

# TP=4 and TP=2 cut every row-parallel matmul into different partial
# sums, whose bf16 rounding differs: each of apertus-8b's 32 layers can
# move its activations by a few bf16 ulps (2**-8 relative).  Compared
# relative to the largest logit, 5e-2 bounds that drift with room, and
# is far below the change a wrong sharding rule makes (logits of
# unrelated weights differ at ~1).
LOGIT_RTOL = 5e-2


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def device_line(devices) -> str:
    d = devices[0]
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}})


class CompileTimes:
    """Sums JAX's compile-duration events, keyed by event name."""

    def __init__(self):
        self.secs = {}
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if "compil" in event:
            self.secs[event] = self.secs.get(event, 0.0) + duration


def one_chip(serve) -> None:
    cfg_name = "qwen1.5-4b"
    argv = ["--arch", cfg_name, "--scale", "full",
            "--max-batch", str(MAX_BATCH), "--capacity", str(CAPACITY),
            "--requests", str(len(PROMPT_LENS)),
            "--prompt-lens", ",".join(map(str, PROMPT_LENS)),
            "--max-tokens", str(MAX_TOKENS)]
    t0 = time.perf_counter()
    summary = serve.main(argv)
    wall = time.perf_counter() - t0
    from repro.configs import get_config
    cfg = get_config(cfg_name)
    print(f"smoke: {cfg_name} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads} head_dim={cfg.head_dim} "
          f"vocab={cfg.vocab_size} weight_bytes_bf16="
          f"{cfg.param_count() * 2}")
    print(f"smoke: serve.main wall_s={wall:.3f} "
          f"kv_blocks_total={summary['kv'].get('kv_blocks_total')}")
    reqs = summary["requests"]
    if len(reqs) != len(PROMPT_LENS):
        fail(f"{len(reqs)} results for {len(PROMPT_LENS)} requests")
    for i, r in enumerate(reqs):
        if "error" in r:
            fail(f"request {i} failed: {r['error']}")
        toks = r["tokens"]
        print(f"smoke: request {i} prompt_len={len(r['prompt'])} "
              f"ttft_s={r['ttft_s']} tokens={toks}")
        if len(toks) != MAX_TOKENS:
            fail(f"request {i} returned {len(toks)} of {MAX_TOKENS} tokens")
        if not all(0 <= t < cfg.vocab_size for t in toks):
            fail(f"request {i} has a token outside the vocabulary")
    first, again = [i for i, n in enumerate(PROMPT_LENS) if n == 12]
    if reqs[first]["prompt"] != reqs[again]["prompt"]:
        fail("the repeated prompt was not repeated")
    if reqs[first]["tokens"] != reqs[again]["tokens"]:
        fail(f"repeated prompt gave {reqs[first]['tokens']} then "
             f"{reqs[again]['tokens']}")


def four_chips(serve) -> None:
    import numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.parallel import sharding

    if jax.device_count() < 4:
        fail(f"--four-chips needs 4 devices, have {jax.device_count()}")
    arch, lens, max_tokens = "apertus-8b", (40, 12), 8
    cfg = get_config(arch)
    prompts = [serve.make_prompt(cfg.vocab_size, n) for n in lens]
    pad = 64
    toks = np.zeros((len(prompts), pad), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    batch = {"tokens": jax.numpy.asarray(toks),
             "prompt_lengths": jax.numpy.asarray(lens, jax.numpy.int32)}
    logits, served = {}, {}
    for tp in (4, 2):
        mesh = make_mesh((tp,), ("model",), devices=jax.devices()[:tp])
        params = serve.load_params(cfg, "full", jax.random.PRNGKey(0),
                                   mesh)
        prefill = sharding.sharded_jit(
            lambda p, b: M.prefill(cfg, p, b)[0], mesh,
            sharding.make_rules("serving_tp"))
        logits[tp] = np.asarray(prefill(params, batch))
        del params, prefill
        gc.collect()
        served[tp] = serve.main([
            "--arch", arch, "--scale", "full", "--tp", str(tp),
            "--max-batch", "2", "--capacity", "256",
            "--requests", str(len(lens)),
            "--prompt-lens", ",".join(map(str, lens)),
            "--max-tokens", str(max_tokens)])["requests"]
        gc.collect()
    largest = float(np.max(np.abs(logits[2])))
    err = float(np.max(np.abs(logits[4] - logits[2]))) / largest
    top2 = np.sort(logits[2], -1)[:, -2:]
    print(f"smoke: {arch} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"weight_bytes_bf16={cfg.param_count() * 2}")
    print(f"smoke: prefill logits tp4 vs tp2 max_abs_diff/max_abs="
          f"{err:.3e} (limit {LOGIT_RTOL}) top1-top2 gap="
          f"{(top2[:, 1] - top2[:, 0]).tolist()}")
    if not err <= LOGIT_RTOL:
        fail(f"tp4 and tp2 prefill logits differ by {err:.3e} relative")
    for i in range(len(lens)):
        a, b = served[4][i], served[2][i]
        for r in (a, b):
            if "error" in r or len(r["tokens"]) != max_tokens:
                fail(f"request {i}: {r}")
        same = sum(x == y for x, y in zip(a["tokens"], b["tokens"]))
        argmax = [int(np.argmax(logits[tp][i])) for tp in (4, 2)]
        print(f"smoke: request {i} tp4={a['tokens']} tp2={b['tokens']} "
              f"same={same}/{max_tokens} prefill_argmax tp4/tp2={argmax}")
        if a["tokens"][0] != b["tokens"][0]:
            fail(f"request {i}: first greedy token differs between "
                 f"tp4 and tp2")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel path on 4 chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX sees {devices[0].platform} devices")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import serve
    from repro.launch.compile_cache import enable_compile_cache

    times = CompileTimes()
    print(f"smoke: jax={jax.__version__} devices={len(devices)} "
          f"kind={devices[0].device_kind} "
          f"compile_cache={enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(serve)
        used = devices[:4]
    else:
        one_chip(serve)
        used = devices[:1]
    print(f"smoke: phase_wall_s={time.perf_counter() - t0:.3f} "
          f"compile_s={json.dumps(times.secs)}")
    for d in used:
        stats = d.memory_stats() or {}
        print(f"smoke: {d} peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use')}")
    print(device_line(used))


if __name__ == "__main__":
    main()

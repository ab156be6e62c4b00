"""The serving cells: build the system under test, drive it, read it.

One run of one cell: weights from the seed, the engine behind the
gateway, a warm-up of exactly the shapes the mix can produce, then a
measured window of ``--seconds`` in which every due request enters
through ``Gateway.completion(..., run=False)`` and the loop between
arrivals is ``InferenceEngine.step()``.  After the window the served
tokens of a sample of requests are compared with the plain reference.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name in ``BENCHMARK.json``:
``bench/configs/<config>.json``, ``bench/mixes/<traffic>.json`` and
``bench/metrics/<metric>.py``.  Everything that depends on the model's
architecture is in the module of the family that the configuration
names, ``bench/families/<family>.py`` (:func:`load_family`): the
program's model configuration, the weights' layout, the plain
reference's forward pass and the required work.  This module knows no
architecture.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import reference  # noqa: E402
import traffic  # noqa: E402
import weights as W  # noqa: E402

PREFILL_CHUNK = 512          # the scheduler's default one-shot chunk


# ------------------------------------------------------------- resolve
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Callable]
    family: object


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_module(name: str):
    """A module of ``bench/`` by file, under a name of its own (so that
    ``bench/trace.py`` never meets the standard library's ``trace``)."""
    key = "bench_" + name
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, os.path.join(BENCH, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def load_reader(name: str, root: str = ROOT) -> Callable:
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_family(c: dict, root: str = ROOT):
    """The module of the model family that configuration ``c`` names
    under ``"family"``: ``bench/families/<family>.py`` under ``root``.
    It gives ``program_config``, ``layout`` (and ``DRAWS`` where it has
    kinds of weight of its own), ``position_stats``, ``decode_flops``,
    ``prefill_flops`` and ``decode_step_work``; ``CUTS`` and
    ``min_layers`` where its configurations may be cut (:func:`check_cut`),
    and ``SCOPES`` where its program names scopes of its own
    (``bench/spans.py``).  There is no default."""
    where = os.path.join(root, "bench", "families")
    if "family" not in c:
        raise KeyError(f"configuration {c.get('name')!r} has no "
                       f"\"family\": it names the module "
                       f"{os.path.join(where, '<family>.py')}")
    path = os.path.join(where, c["family"] + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"configuration {c.get('name')!r} names "
                                f"family {c['family']!r}: no file {path}")
    key = "bench_family_" + c["family"]
    mod = sys.modules.get(key)
    if mod is None or mod.__file__ != path:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return mod


CUT_KINDS = ("depth", "experts", "vocab")


def check_cut(c: dict, family) -> None:
    """Raise ValueError, naming the key, unless configuration ``c`` is cut
    only as the model-configs guide (section 4) allows.

    Only the keys that ``family.CUTS`` names may be in ``reduced``: the
    layers (``"depth"``), the routed experts held in each layer
    (``"experts"``) and the vocabulary rows (``"vocab"``); widths are never
    cut.  Each reduced key states its published value under
    ``published``, above the value held, and a cut file states its
    ``deployment``.  What is held keeps to the floors: at least
    ``family.min_layers(c)`` layers, 8 experts (all where fewer are
    published), an eighth of the vocabulary."""
    reduced = c.get("reduced", [])
    if not reduced:
        return
    name = c.get("name")
    cuts = getattr(family, "CUTS", {})
    unknown = {k: key for k, key in cuts.items() if k not in CUT_KINDS}
    if unknown:
        raise ValueError(f"family {c.get('family')!r} names cuts "
                         f"{unknown}: a cut is one of {CUT_KINDS}; widths "
                         f"are never cut")
    kind = {key: k for k, key in cuts.items()}
    if not c.get("deployment"):
        raise ValueError(f"configuration {name!r} reduces {reduced} and "
                         f"states no \"deployment\"")
    published = c.get("published", {})
    for key in reduced:
        if key not in kind:
            raise ValueError(
                f"configuration {name!r} reduces {key!r}, which its "
                f"family {c.get('family')!r} does not name in CUTS "
                f"({cuts}): only the depth, the experts held and the "
                f"vocabulary may be cut; widths are never cut")
        if key not in published:
            raise ValueError(f"configuration {name!r} reduces {key!r} and "
                             f"states no published value for it under "
                             f"\"published\"")
        held, pub = c.get(key), published[key]
        if not (isinstance(held, int) and isinstance(pub, int)
                and held < pub):
            raise ValueError(f"configuration {name!r} holds {key!r} = "
                             f"{held!r} of a published {pub!r}: a cut "
                             f"holds a whole number below the published one")
        floor = (family.min_layers(c) if kind[key] == "depth"
                 else min(8, pub) if kind[key] == "experts"
                 else -(-pub // 8))
        if held < floor:
            raise ValueError(f"configuration {name!r} holds {key!r} = "
                             f"{held} of a published {pub}: its "
                             f"{kind[key]} cut keeps at least {floor}")


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload`` with its configuration, mix, readers
    and model family, found by name; the configuration's cuts are
    checked (:func:`check_cut`)."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    if entry["reduced"] != config.get("reduced", []):
        raise ValueError(f"configuration {entry['name']!r}: BENCHMARK.json "
                         f"reduces {entry['reduced']}, {entry['file']} "
                         f"{config.get('reduced', [])}")
    family = load_family(config, root)
    check_cut(config, family)
    mix = traffic.load_mix(os.path.join(root, "bench", "mixes",
                                        w["traffic"] + ".json"))
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    return Cell(workload, w["chips"], config, mix,
                [m for m in bench["end_to_end"] if _applies(m, workload)],
                per_layer,
                {m["name"]: load_reader(m["name"], root) for m in per_layer},
                family)


# -------------------------------------------------------- the program
@dataclasses.dataclass
class System:
    cfg: object
    params: object
    engine: object
    gateway: object
    key: str
    model: str
    mesh: object


def build(family, c: dict, seed: int, devices) -> System:
    """Weights from ``seed`` in the layout of ``family`` (sharded as made
    where ``tp`` > 1), the engine, and the gateway in front of it."""
    import jax
    from repro.core.gateway import Gateway, ModelEntry
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.parallel import sharding
    from repro.serving.engine import InferenceEngine
    from repro.serving.scheduler import SchedulerConfig

    pcfg = family.program_config(c)
    lay = family.layout(c)
    W.check_layout(lay, jax.eval_shape(
        lambda k: M.init(pcfg, k), jax.random.PRNGKey(0)))
    mesh = shardings = None
    if c["tp"] > 1:
        mesh = make_mesh((c["tp"],), ("model",), devices=devices[:c["tp"]])
        shardings = sharding.tree_shardings(
            M.model_param_axes(pcfg), mesh,
            sharding.make_rules("serving_tp"))
    params = W.make(lay, seed, shardings,
                    draws=getattr(family, "DRAWS", None))
    eng = InferenceEngine(pcfg, params, max_batch=c["max_batch"],
                          capacity=c["capacity"],
                          pool_tokens=c["pool_tokens"], mesh=mesh,
                          kv_dtype=c["kv_dtype"], name="bench0",
                          sched=SchedulerConfig(
                              cache_capacity_tokens=c["prefix_cache_tokens"]))
    gw = Gateway()
    gw.vet_model(ModelEntry(pcfg.name, pcfg.name, 0.5, 1.5), pcfg)
    gw.bind_endpoints(pcfg.name, [eng])
    key = gw.mint_key("bench", budget_usd=1e18, rate_limit_per_min=1 << 40)
    return System(pcfg, params, eng, gw, key.key, pcfg.name, mesh)


def submit(sys_: System, prompt, max_new_tokens: int):
    """One request through the gateway, left for the engine loop to
    serve.  Returns its id and the list its tokens are served into."""
    out = sys_.gateway.completion(api_key=sys_.key, model=sys_.model,
                                  prompt=list(prompt),
                                  max_tokens=max_new_tokens,
                                  temperature=0.0, run=False)
    return out["id"], out["tokens"]


def warm_up(sys_: System, mix: dict, vocab: int) -> None:
    """Compile every shape the mix can produce: the prefill and KV-write
    programs of each prompt size (and a prompt past the chunk where the
    mix has such prompts), the greedy paged decode step and the
    first-token sampling; then drop the warm-up prompts from the prefix
    cache."""
    lens = traffic.warm_lengths(mix, PREFILL_CHUNK,
                                sys_.engine.slots.block_size)
    if mix["prompt"]["max"] > PREFILL_CHUNK:
        lens.append(PREFILL_CHUNK + 2)
    rng = np.random.default_rng(12345)
    for n in lens:
        submit(sys_, rng.integers(1, vocab - 1, n), 2)
    sys_.engine.run_until_idle()
    sys_.engine.scheduler.reset_cache()


class CompileCounter:
    """Counts JAX compile events while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if self.on and ("compile" in event or "trace_duration" in event):
            self.count += 1


# ----------------------------------------------------------- the run
@dataclasses.dataclass
class Record:
    index: int
    measured: bool
    due: float                 # absolute, host monotonic clock
    n_prompt: int
    max_new: int
    prompt: List[int]
    submit_start: float = 0.0
    submit_end: float = 0.0
    rid: str = ""
    prefill_start: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    status: str = "unsubmitted"


@dataclasses.dataclass
class Run:
    """What one run saw; the per-layer readers read it."""
    cell: str
    config: dict
    family: object
    mix: dict
    chips: int
    peaks: dict
    t0: float
    t1: float
    records: List[Record]
    ticks: List[tuple]   # (t_end, running, kv_used, live_tokens, queued)
    max_batch: int
    kv_total: int
    setup_s: float
    t_end: float                # when the run stopped waiting
    prefill_chunk: int = PREFILL_CHUNK
    trace: Optional[dict] = None
    notes: dict = dataclasses.field(default_factory=dict)


def _span(tracing: bool, name: str):
    if not tracing:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation("bench." + name)


def drive(sys_: System, mix: dict, reqs: List[traffic.Request],
          seconds: float, trace_dir: Optional[str],
          clock=time.monotonic) -> dict:
    """The measured window (and, for an open loop, the drain after it),
    traced into ``trace_dir`` when one is given.  Returns the records,
    per-tick samples and window bounds."""
    import jax
    eng = sys_.engine
    recs = [Record(r.index, r.measured, 0.0, len(r.prompt),
                   r.max_new_tokens, r.prompt) for r in reqs]
    ticks: List[tuple] = []
    tracing = False

    def do_submit(rec: Record, t_base: float, req):
        rec.due = t_base + req.due
        with _span(tracing, "gateway_submit"):
            rec.submit_start = clock()
            rec.rid, rec.tokens = submit(sys_, req.prompt,
                                         req.max_new_tokens)
            rec.submit_end = clock()
        rec.status = "submitted"

    if mix["kind"] == "closed":
        # every slot full before the window opens; the rest waits queued
        B = eng.slots.B
        t_base = clock()
        for rec, req in zip(recs[:B], reqs[:B]):
            do_submit(rec, t_base, req)
        while any(not eng.metrics.requests[r.rid].token_times
                  for r in recs[:B]):
            eng.step()
        for rec, req in zip(recs[B:], reqs[B:]):
            do_submit(rec, t_base, req)
        nxt = len(recs)
    else:
        nxt = 0
    window = None
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window = jax.profiler.TraceAnnotation("bench.window")
        window.__enter__()
        tracing = True
    t0 = clock()
    end = t0 + seconds
    drain_end = end + mix.get("drain_cap_s", 0.0)

    def pending_measured():
        return any(r.measured and (not r.rid or eng.metrics.requests[
            r.rid].status == "active") for r in recs)

    t1 = None
    while True:
        now = clock()
        if t1 is None and now >= end:
            t1 = now
            if tracing:
                window.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
            if mix["kind"] == "closed":
                break
        if t1 is not None and (now >= drain_end or not pending_measured()):
            break
        while nxt < len(reqs) and t0 + reqs[nxt].due <= now:
            do_submit(recs[nxt], t0, reqs[nxt])
            nxt += 1
        if eng.num_active:
            with _span(tracing, "engine_step"):
                eng.step()
            lens = eng.slots.lengths
            ticks.append((clock(), len(eng.running),
                          eng.kv_stats()["kv_blocks_used"],
                          int(sum(int(lens[s]) for s in eng.running)),
                          len(eng.queue)))
        else:
            wake = t0 + reqs[nxt].due if nxt < len(reqs) else end
            with _span(tracing, "generator"):
                time.sleep(max(0.0, min(wake, end if t1 is None
                                        else drain_end) - clock()))
    for rec in recs:
        if rec.rid:
            m = eng.metrics.requests[rec.rid]
            rec.prefill_start = m.prefill_start
            rec.token_times = list(m.token_times)
            rec.tokens = list(rec.tokens)
            rec.status = m.status
    return {"records": recs, "ticks": ticks, "t0": t0, "t1": t1}


# ------------------------------------------------------------ correct
def sample(records: List[Record], check: dict, seed: int) -> List[Record]:
    """Requests whose served tokens are compared: the longest answer,
    the longest prompt, then others drawn from the seed until the
    sample holds ``check["tokens"]`` served tokens or
    ``check["requests"]`` requests.  Finished requests where there are
    any, else those still in flight."""
    served = [r for r in records if r.measured and r.tokens]
    pool = [r for r in served if r.status == "finished"] or served
    if not pool:
        return []
    chosen = [max(pool, key=lambda r: (len(r.tokens), r.index)),
              max(pool, key=lambda r: (r.n_prompt, r.index))]
    chosen = list({r.index: r for r in chosen}.values())
    rng = np.random.default_rng([int(seed) % (1 << 63), 7])
    for i in rng.permutation(len(pool)):
        if (sum(len(r.tokens) for r in chosen) >= check["tokens"]
                or len(chosen) >= check["requests"]):
            break
        if pool[i] not in chosen:
            chosen.append(pool[i])
    return chosen


def compare(family, c: dict, params, chosen: List[Record], rows: int,
            control: bool = False) -> dict:
    """Widest gap by which a served token's reference logit (``family``'s
    ``position_stats``) lies below the reference's best, over the
    sample; with ``control``, also the same gap for the tokens the int8
    control puts first."""
    seqs = [(r.prompt, r.tokens) for r in chosen]
    seqs += [([0], [0])] * (rows - len(seqs))
    tokens, target, mask = reference.pack(seqs)
    mask[len(chosen):] = False
    out = {"served_tokens": int(mask.sum())}
    alt = None
    if control:
        _, _, _, alt = family.position_stats(c, params, tokens, target,
                                             precision="int8")
    mx, at, at_alt, _ = family.position_stats(c, params, tokens, target,
                                              alt=alt)
    # with nothing compared the served-token count fails the check
    out["logit_gap_max"] = float(np.max((mx - at)[mask])) if mask.any() \
        else 0.0
    if control:
        out["control_gap_max"] = float(np.max((mx - at_alt)[mask]))
    return out


def judge(checks: dict) -> bool:
    """``correct``: the widest logit gap within its limit, at least the
    stated number of served tokens compared, and no request rejected."""
    return (checks["logit_gap_max"]["value"]
            <= checks["logit_gap_max"]["limit"]
            and checks["served_tokens_compared"]["value"]
            >= checks["served_tokens_compared"]["limit"]
            and checks["rejected_requests"]["value"] == 0)


# ----------------------------------------------------------- one cell
def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             devices, peaks: dict, t_start: float,
             trace_dir: Optional[str] = None, control: bool = False,
             fault: Optional[Callable] = None, log=print) -> dict:
    """Build, warm, drive and check one cell; return the result line's
    object (without the ``device`` entry's platform fields).  ``fault``
    (tests only) is called with the built system before the window."""
    c, mix = cell.config, cell.mix
    counter = CompileCounter()
    sys_ = build(cell.family, c, seed, devices)
    warm_up(sys_, mix, c["vocab_size"])
    reqs = traffic.generate(mix, seconds=seconds, seed=seed,
                            vocab_size=c["vocab_size"],
                            slots=c["max_batch"])
    if fault is not None:
        fault(sys_)
    counter.on = True
    out = drive(sys_, mix, reqs, seconds, trace_dir if trace else None)
    counter.on = False
    t_end = time.monotonic()
    used = devices[:cell.chips]
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in used)
    eng = sys_.engine
    run = Run(cell.name, c, cell.family, mix, cell.chips, peaks, out["t0"],
              out["t1"], out["records"], out["ticks"], c["max_batch"],
              eng.kv_stats()["kv_blocks_total"],
              setup_s=out["t0"] - t_start, t_end=t_end)
    log(f"bench: compiles_in_window={counter.count} "
        f"ticks={len(run.ticks)} submitted="
        f"{sum(1 for r in run.records if r.rid)}")
    device = {"memory_peak_bytes": int(peak_bytes)}
    breakdown = None
    if trace:
        T = load_module("trace")
        t_read = time.monotonic()
        run.trace = T.reduce(T.load(T.find_xplane(trace_dir)))
        log(f"bench: trace read in {time.monotonic() - t_read:.1f}s "
            f"programs={run.trace['programs']}")
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
        breakdown = {"device_ops": [list(x) for x in
                                    run.trace["top_ops"][:10]],
                     "idle_gaps": [list(x) for x in
                                   run.trace["idle_by_label"][:10]]}
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        reader = cell.readers.get(m["name"]) or load_reader(m["name"])
        v = reader(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if run.notes:
        log(f"bench: notes {json.dumps(run.notes)}")
    meas = [r for r in run.records if r.measured]
    rejected = sum(1 for r in meas if r.status == "rejected")
    late = sum(1 for r in meas if mix["kind"] == "open_loop"
               and r.status != "finished")
    # free the program's state before the reference runs
    del sys_.engine, sys_.gateway, eng
    gc.collect()
    chosen = sample(run.records, mix["check"], seed)
    t_ref = time.monotonic()
    cmp_ = compare(cell.family, c, sys_.params, chosen,
                   mix["check"]["requests"], control=control)
    log(f"bench: reference over {len(chosen)} requests, "
        f"{cmp_['served_tokens']} served tokens, in "
        f"{time.monotonic() - t_ref:.1f}s")
    checks = {
        "logit_gap_max": {"value": cmp_["logit_gap_max"],
                          "limit": c["check"]["logit_gap_limit"]},
        "served_tokens_compared": {"value": cmp_["served_tokens"],
                                   "limit": mix["check"]["min_tokens"]},
        "rejected_requests": {"value": rejected, "limit": 0},
    }
    correct = judge(checks)
    if control:
        checks["control_gap_max"] = {"value": cmp_["control_gap_max"],
                                     "limit": c["check"]["logit_gap_limit"]}
    result = {"correct": bool(correct), "attempted": len(meas),
              "failed": rejected + late, "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result

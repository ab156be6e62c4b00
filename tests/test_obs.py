"""Observability: metrics registry, tracer, collector edge cases, and
end-to-end instrumentation through the engine, gateway, and trainer."""
import itertools
import json
import math
import time

import pytest

from repro.obs import Observability, MetricsRegistry, Tracer
from repro.obs.registry import validate_metric_name
from repro.serving.metrics import MetricsCollector, TracingMetricsCollector


def _vclock(step=1.0):
    t = itertools.count()
    return lambda: next(t) * step


# --------------------------------------------------------------- registry
def test_counter_inc_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("repro_kv_hits_total", "h", labelnames=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    snap = reg.snapshot()
    assert snap['repro_kv_hits_total{kind="a"}'] == 3
    assert snap['repro_kv_hits_total{kind="b"}'] == 1
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)          # counters only go up
    with pytest.raises(ValueError):
        c.labels(wrong="a")                 # label names must match


def test_gauge_set_dec():
    reg = MetricsRegistry()
    g = reg.gauge("repro_kv_used_blocks")
    g.set(7)
    g.dec(2)
    assert reg.snapshot()["repro_kv_used_blocks"] == 5


def test_histogram_bucket_boundaries():
    reg = MetricsRegistry()
    h = reg.histogram("repro_sched_tick_seconds", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 2.0, 7.0):
        h.observe(v)
    snap = reg.snapshot()["repro_sched_tick_seconds"]
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(12.0)
    # Prometheus le semantics: a value exactly on a boundary counts in
    # that le bucket (le = less-or-equal), buckets are cumulative
    assert snap["buckets"] == [(1.0, 2), (2.0, 4), (5.0, 4), ("+Inf", 5)]


def test_histogram_buckets_must_increase():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("repro_sched_bad_seconds", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        reg.histogram("repro_sched_dup_seconds", buckets=(1.0, 1.0))


def test_name_validation():
    assert validate_metric_name("repro_kv_used_blocks") is None
    assert validate_metric_name("repro_sched_preemptions_total",
                                "counter") is None
    # not our prefix / wrong case / missing unit suffix
    assert validate_metric_name("kv_used_blocks") is not None
    assert validate_metric_name("repro_KV_used_blocks") is not None
    assert validate_metric_name("repro_kv_used") is not None
    # kind rules: counters end _total, gauges/histograms must not
    assert validate_metric_name("repro_kv_used_blocks",
                                "counter") is not None
    assert validate_metric_name("repro_kv_hits_total",
                                "gauge") is not None
    assert validate_metric_name("repro_kv_hits_total",
                                "histogram") is not None
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("repro_kv_used_blocks")


def test_reregistration_is_get_or_create():
    reg = MetricsRegistry()
    a = reg.counter("repro_kv_hits_total")
    a.inc(3)
    b = reg.counter("repro_kv_hits_total")   # same family back
    assert b.value == 3
    with pytest.raises(ValueError):
        reg.gauge("repro_kv_hits_total")     # kind changed
    with pytest.raises(ValueError):
        reg.counter("repro_kv_hits_total", labelnames=("x",))


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("repro_kv_hits_total", "cache hits",
                labelnames=("kind",)).labels(kind="radix").inc(4)
    reg.gauge("repro_kv_used_blocks", "blocks in use").set(float("nan"))
    reg.histogram("repro_sched_tick_seconds",
                  buckets=(0.5, 1.0)).observe(0.25)
    text = reg.to_prometheus()
    assert "# HELP repro_kv_hits_total cache hits" in text
    assert "# TYPE repro_kv_hits_total counter" in text
    assert 'repro_kv_hits_total{kind="radix"} 4' in text
    assert "repro_kv_used_blocks NaN" in text
    assert "# TYPE repro_sched_tick_seconds histogram" in text
    assert 'repro_sched_tick_seconds_bucket{le="0.5"} 1' in text
    assert 'repro_sched_tick_seconds_bucket{le="+Inf"} 1' in text
    assert "repro_sched_tick_seconds_sum 0.25" in text
    assert "repro_sched_tick_seconds_count 1" in text
    # JSON surface parses and carries the same families
    doc = json.loads(reg.to_json())
    assert {m["name"] for m in doc["metrics"]} == {
        "repro_kv_hits_total", "repro_kv_used_blocks",
        "repro_sched_tick_seconds"}


# ----------------------------------------------------------------- tracer
def test_tracer_spans_nest_by_containment():
    tr = Tracer(clock=_vclock())
    with tr.span("scheduler", "tick", cat="sched", queued=2):
        with tr.span("scheduler", "micro_step"):
            pass
    evs = tr.events_for("scheduler")
    inner = next(e for e in evs if e["name"] == "micro_step")
    outer = next(e for e in evs if e["name"] == "tick")
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert outer["args"] == {"queued": 2} and outer["cat"] == "sched"


def test_tracer_end_idempotent_and_instants():
    tr = Tracer(clock=_vclock())
    s = tr.begin("req", "decode")
    tr.end(s, n=3)
    tr.end(s, n=99)                          # double-end ignored
    tr.instant("req", "finish", cat="request")
    evs = tr.events_for("req")
    assert [e["ph"] for e in evs] == ["X", "i"]
    assert evs[0]["args"] == {"n": 3}
    assert evs[1]["s"] == "t"


def test_tracer_event_cap_counts_drops():
    tr = Tracer(clock=_vclock(), max_events=2)
    for _ in range(4):
        tr.instant("t", "e")
    assert tr.n_events == 2 and tr.dropped == 2
    assert tr.to_perfetto()["otherData"]["dropped_events"] == 2


def test_perfetto_round_trip():
    tr = Tracer(clock=_vclock(), process="test-proc")
    with tr.span("scheduler", "tick"):
        pass
    doc = json.loads(tr.to_json())
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    procs = [e for e in evs if e["ph"] == "M"
             and e["name"] == "process_name"]
    assert procs[0]["args"]["name"] == "test-proc"
    threads = {e["args"]["name"]: e["tid"] for e in evs
               if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "scheduler" in threads
    x = next(e for e in evs if e["ph"] == "X")
    assert x["tid"] == threads["scheduler"] and x["dur"] >= 0


# ----------------------------------------------- collector edge cases
def test_summary_empty_collector():
    s = MetricsCollector().summary()
    assert s["completed"] == 0 and s["rejected"] == 0
    assert s["preempted"] == 0
    assert math.isnan(s["qps"]) and math.isnan(s["ttft_p50_s"])
    assert math.isnan(s["preempt_to_resume_mean_s"])
    assert s["prefix_hit_rate"] == 0.0
    assert s["generated_tokens"] == 0


def test_summary_rejected_only():
    mc = MetricsCollector()
    mc.arrival("r1", 0.0, 10)
    mc.reject("r1", 1.0)
    s = mc.summary()
    assert s["rejected"] == 1 and s["completed"] == 0
    # rejections must not pollute latency quantiles / token accounting
    assert math.isnan(s["e2el_mean_s"]) and math.isnan(s["ttft_p50_s"])
    assert s["prompt_tokens"] == 0


def test_summary_all_preempted_never_resumed():
    mc = MetricsCollector()
    mc.arrival("r1", 0.0, 4)
    mc.prefill_start("r1", 1.0)
    mc.preempt("r1", 3.0)
    s = mc.summary()
    assert s["preempted"] == 1 and s["completed"] == 0
    # the preempt interval never closed: no resume delay to average
    assert math.isnan(s["preempt_to_resume_mean_s"])


def test_preempt_timestamps_surface_time_to_resume():
    """The old ``preempt(rid, t)`` dropped ``t`` on the floor; it must
    now pair with the next ``prefill_start`` into a resume delay."""
    mc = MetricsCollector()
    mc.arrival("r1", 0.0, 4)
    mc.prefill_start("r1", 1.0)
    mc.preempt("r1", 3.0)
    mc.prefill_start("r1", 8.0)      # re-admitted 5s later
    mc.preempt("r1", 10.0)
    mc.prefill_start("r1", 11.0)     # and again, 1s later
    mc.token("r1", 12.0)
    mc.finish("r1", 12.0)
    r = mc.requests["r1"]
    assert r.preempt_times == [3.0, 10.0]
    assert r.resume_times == [8.0, 11.0]
    assert r.resume_delays == [5.0, 1.0]
    assert mc.summary()["preempt_to_resume_mean_s"] == pytest.approx(3.0)


def test_tracing_collector_lifecycle_and_resume_histogram():
    obs = Observability(clock=_vclock())
    mc = TracingMetricsCollector(obs)
    mc.arrival("r1", 0.0, 4)
    mc.prefill_start("r1", 1.0)
    mc.preempt("r1", 2.0)
    mc.prefill_start("r1", 6.0)
    mc.token("r1", 7.0)
    mc.token("r1", 8.0)
    mc.finish("r1", 8.5)
    names = [e["name"] for e in obs.tracer.events_for("req r1")]
    # spans close in lifecycle order; finish instant last
    assert names == ["queued", "prefill", "preempted", "prefill",
                     "decode", "finish"]
    snap = obs.registry.snapshot()
    assert snap["repro_sched_admitted_requests_total"] == 2
    assert snap["repro_sched_preemptions_total"] == 1
    assert snap["repro_serving_preempt_resume_seconds"]["count"] == 1
    assert snap["repro_serving_preempt_resume_seconds"]["sum"] == 4.0
    assert snap["repro_serving_ttft_seconds"]["count"] == 1
    assert snap["repro_serving_itl_seconds"]["count"] == 1
    # summary behaviour identical to the plain collector
    assert mc.summary()["completed"] == 1


# ------------------------------------------------------------ integration
def test_engine_instrumented_end_to_end(tiny_cfg, tiny_params):
    from repro.serving.engine import InferenceEngine, Request
    t = itertools.count()
    obs = Observability(clock=lambda: float(next(t)))
    eng = InferenceEngine(tiny_cfg, tiny_params, max_batch=2, capacity=64,
                          clock=obs.clock, obs=obs)
    for p in ([1, 2, 3], [4, 5, 6, 7]):
        eng.submit(Request(prompt=p, max_new_tokens=4))
    s = eng.run_until_idle()
    assert s["completed"] == 2
    eng.collect_metrics()
    snap = obs.registry.snapshot()
    assert snap["repro_serving_finished_requests_total"] == 2
    assert snap["repro_serving_generated_tokens_total"] == 8
    assert snap["repro_sched_admitted_requests_total"] == 2
    assert snap["repro_sched_tick_seconds"]["count"] > 0
    assert snap["repro_sched_batch_occupancy_ratio"]["count"] > 0
    assert snap["repro_kv_capacity_blocks"] > 0
    # every request's lifecycle reconstructs on its own track
    doc = json.loads(obs.tracer.to_json())
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e.get("ph") == "M" and e["name"] == "thread_name"}
    req_tracks = {n for n in tracks if n.startswith("req ")}
    assert len(req_tracks) == 2 and "scheduler" in tracks
    for rt in req_tracks:
        names = [e["name"] for e in obs.tracer.events_for(rt)
                 if e["ph"] == "X"]
        assert names[0] == "queued" and "prefill" in names \
            and "decode" in names


def _served(cfg, params, obs=None, prompts=([1, 2], [3, 4, 5, 6, 7])):
    from repro.serving.engine import InferenceEngine, Request
    eng = InferenceEngine(cfg, params, max_batch=2, capacity=64,
                          clock=obs.clock if obs else time.monotonic,
                          obs=obs)
    reqs = [Request(prompt=list(p), max_new_tokens=5) for p in prompts]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    return eng, [list(r.generated) for r in reqs]


def test_engine_without_obs_unchanged(tiny_cfg, tiny_params):
    from repro.serving.metrics import MetricsCollector
    eng, toks = _served(tiny_cfg, tiny_params)
    assert eng.obs is None and eng.scheduler._tracer is None
    assert type(eng.metrics) is MetricsCollector
    assert eng.metrics.summary()["completed"] == 2
    with pytest.raises(ValueError):
        eng.collect_metrics()            # no registry anywhere
    # the phases still open (as profiler annotations) and change nothing
    # the engine serves: the same tokens as with obs attached
    obs = Observability(clock=_vclock())
    _, toks_obs = _served(tiny_cfg, tiny_params, obs)
    assert toks == toks_obs and all(len(t) == 5 for t in toks)
    assert obs.tracer.n_events > 0


# the scheduler track's spans before the profiler phases, and the phases
SCHED_SPANS = {"tick", "admit", "decode", "micro_step"}
PHASES = {"grow", "inputs", "dispatch", "device_wait", "emit", "prefill",
          "kv_insert", "first_token"}


def test_scheduler_track_keeps_its_spans_and_adds_the_phases(
        tiny_cfg, tiny_params):
    obs = Observability(clock=_vclock())
    eng, _ = _served(tiny_cfg, tiny_params, obs)
    assert eng.paged
    evs = [e for e in obs.tracer.events_for("scheduler") if e["ph"] == "X"]
    names = {e["name"] for e in evs}
    assert names == SCHED_SPANS | PHASES
    ticks = [e for e in evs if e["name"] == "tick"]
    assert [set(e["args"]) for e in ticks[:1]] == [{"queued", "running"}]
    rids = set(eng.metrics.requests)
    for e in evs:
        if e["name"] in ("prefill", "kv_insert", "first_token"):
            assert e["args"]["rid"] in rids
    # every phase of a micro-step nests inside it
    steps = [e for e in evs if e["name"] == "micro_step"]
    for e in evs:
        if e["name"] in ("inputs", "dispatch", "device_wait", "emit"):
            assert any(m["ts"] <= e["ts"] and e["ts"] + e["dur"]
                       <= m["ts"] + m["dur"] for m in steps), e


def test_micro_step_counts_live_and_read_kv(tiny_cfg, tiny_params):
    from repro.models.attention import paged_kv_read_tokens
    from repro.serving.engine import InferenceEngine, Request
    obs = Observability(clock=_vclock())
    eng = InferenceEngine(tiny_cfg, tiny_params, max_batch=2, capacity=64,
                          clock=obs.clock, obs=obs)
    for p in ([1, 2, 3], [4, 5, 6, 7, 8]):
        eng.submit(Request(prompt=p, max_new_tokens=6))
    live_seen = []
    while eng.num_active:
        before = len([e for e in obs.tracer.events_for("scheduler")
                      if e["name"] == "micro_step"])
        running = set(eng.running)
        eng.step()
        steps = [e for e in obs.tracer.events_for("scheduler")
                 if e["name"] == "micro_step"][before:]
        for e in steps:
            live_seen.append(e["args"]["live_tokens"])
            assert e["args"]["kv_read_tokens"] == paged_kv_read_tokens(
                2, 64 // 16, 16) == 2 * eng.slots.tables.shape[1] * 16
        if steps and running == set(eng.running):
            # nothing finished: the last step's live tokens are the
            # running slots' lengths now
            assert live_seen[-1] == sum(int(eng.slots.lengths[s])
                                        for s in eng.running)
    assert live_seen and all(0 < n <= 2 * 64 for n in live_seen)
    snap = obs.registry.snapshot()
    per = eng.kv_stats()["kv_block_bytes_per_device"] // 16
    assert snap["repro_kv_live_bytes_total"] == sum(live_seen) * per
    assert snap["repro_kv_read_bytes_total"] == (
        len(live_seen) * 2 * 4 * 16 * per)


def test_gateway_rejections_counted():
    from repro.core.gateway import Gateway, Unauthorized
    obs = Observability(clock=_vclock())
    gw = Gateway(clock=obs.clock, obs=obs)
    k = gw.mint_key("acme")
    with pytest.raises(Unauthorized):
        gw.completion(api_key=k.key, model="no-such-model", prompt=[1])
    with pytest.raises(Unauthorized):
        gw.completion(api_key="sk-bogus", model="no-such-model",
                      prompt=[1])
    snap = obs.registry.snapshot()
    assert snap[
        'repro_gateway_rejected_requests_total{kind="Unauthorized"}'] == 2


def test_trainer_emits_step_and_mfu_series(tiny_cfg, tmp_path):
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.training.optimizer import OptConfig
    from repro.training.trainer import Trainer, TrainerConfig
    obs = Observability()
    data = SyntheticLM(DataConfig(vocab_size=tiny_cfg.vocab_size,
                                  seq_len=16, global_batch=2))
    tr = Trainer(tiny_cfg, OptConfig(lr=1e-3), data,
                 TrainerConfig(num_steps=3, ckpt_every=100,
                               ckpt_dir=str(tmp_path), log_every=1),
                 obs=obs, peak_flops=1e12)
    tr.run()
    snap = obs.registry.snapshot()
    assert snap["repro_train_steps_total"] == 3
    assert snap["repro_train_tokens_total"] == 3 * 2 * 16
    assert snap["repro_train_step_seconds"]["count"] == 3
    assert snap["repro_train_tokens_per_s"] > 0
    assert 0 < snap["repro_train_mfu_ratio"] < 1
    steps = [e for e in obs.tracer.events_for("train")
             if e["ph"] == "X"]
    assert len(steps) == 3
    text = obs.registry.to_prometheus()
    assert "repro_train_mfu_ratio" in text


def test_mfu_only_for_a_device_kind_with_a_published_peak():
    """The peak table is keyed by device kind; a kind it lacks (the CPU
    here) gets no MFU gauge rather than another chip's figure."""
    import jax
    from repro.finetune.sft import instrument_sft_step
    from repro.obs.peaks import chip_peak
    assert chip_peak("TPU v5 lite", "bf16_flops") == 197e12
    assert chip_peak("cpu", "bf16_flops") is None
    kind = jax.devices()[0].device_kind
    obs = Observability()
    step = instrument_sft_step(lambda p, o, b: (p, o, {}),
                               _TinyCfg(), obs)
    step(None, None, {"tokens": jax.numpy.zeros((2, 8))})
    snap = obs.registry.snapshot()
    assert snap["repro_finetune_tokens_total"] == 16
    assert ("repro_finetune_mfu_ratio" in snap) == (
        chip_peak(kind, "bf16_flops") is not None)


class _TinyCfg:
    def param_count(self, active_only=False):
        return 1000

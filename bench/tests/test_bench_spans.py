"""The program's spans and scopes read from traces: idle gaps labelled
by ``repro.*`` phases, leaf-op time per named scope, the three readers
on synthetic traces, and a recorded CPU trace of the engine."""
import dataclasses
import re
import types

import pytest

import harness
import spans

T = harness.load_module("trace")
E = T.Event
DEV0, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6


def _mod(name, start, dur, pid=1):
    return E(DEV0, T.MODULES_LINE, name, start * MS, dur * MS,
             {"program_id": pid})


def _op(name, start, dur, scope=None):
    return E(DEV0, T.OPS_LINE, name, start * MS, dur * MS,
             {"scope": scope} if scope else {})


def _ann(name, start, dur):
    return E(HOST, "python", "bench." + name, start * MS, dur * MS, {})


def _span(name, start, dur, **args):
    return E(HOST, "python", "repro.scheduler." + name, start * MS,
             dur * MS, dict(args))


def device_events():
    """Two engine steps, each a decode call holding a scan (``while``)
    whose body ops carry scopes, and a prefill."""
    ev = [_ann("window", 0, 100),
          _ann("engine_step", 0, 45), _ann("engine_step", 50, 45)]
    for t0 in (5, 55):
        ev += [_mod("jit__fused_paged(7)", t0, 30),
               _op("while.4", t0, 24),
               _op("convert.63", t0, 6, "attn"),
               _op("fusion.130", t0 + 6, 8, "attn"),
               _op("copy.90", t0 + 14, 4),
               _op("fusion.2", t0 + 18, 6, "mlp"),
               _op("fusion.9", t0 + 24, 6, "head")]
    ev += [_mod("jit__prefill_fn(9)", 90, 4, pid=2),
           _op("fusion.77", 90, 4, "attn")]
    return ev


def program_events():
    """The scheduler's phases of the two ticks: host work before each
    decode call, the wait on it, and emission after it."""
    ev = []
    for n, t0 in enumerate((0, 50)):
        ev += [_span("tick", t0, 44, step_num=n),
               _span("decode", t0 + 1, 42),
               _span("micro_step", t0 + 1, 42, live_tokens=300,
                     kv_read_tokens=1024),
               _span("inputs", t0 + 2, 2),
               _span("dispatch", t0 + 4, 1),
               _span("device_wait", t0 + 5, 30),
               _span("emit", t0 + 36, 6)]
    return ev


def test_idle_gaps_take_the_innermost_program_span():
    sp = spans.Spans(device_events(), program_events())
    idle = dict(spans.idle_by_label(sp))
    # the device idles 0-5, 35-55, 85-90 and 94-100; each gap goes to the
    # innermost span over its middle: 2.5 lies in the first tick's
    # inputs, 87.5 in the second tick's emit, 45 in no program span (the
    # first engine step, 0-45, keeps it) and 97 in the window alone
    assert idle == {"repro.scheduler.inputs": pytest.approx(0.005),
                    "repro.scheduler.emit": pytest.approx(0.005),
                    "engine_step": pytest.approx(0.020),
                    "window": pytest.approx(0.006)}
    # without the first tick's spans its gap falls back to the engine step
    bare = [e for e in program_events() if e.start_ns >= 50 * MS]
    idle = dict(spans.idle_by_label(spans.Spans(device_events(), bare)))
    assert idle["engine_step"] == pytest.approx(0.025)
    assert "repro.scheduler.inputs" not in idle


def test_program_spans_move_no_existing_reading():
    """busy, programs, collectives, top operations and the idle total
    read the same with and without the program's spans."""
    dev = device_events()
    host = [dataclasses.replace(e, name="bench." + e.name)
            for e in program_events()]
    before, after = T.reduce(dev), T.reduce(dev + host)
    for k in ("chips", "window_s", "busy_s", "programs", "collective_s",
              "top_ops"):
        assert before[k] == after[k], k
    total = lambda r: sum(s for _, s in r["idle_by_label"])  # noqa: E731
    assert total(before) == pytest.approx(total(after))
    # and the loader never hands the program's spans to reduce
    assert not any(e.name.startswith("repro.") for e in dev)


def test_leaf_ops_per_scope_skip_the_containing_while():
    sp = spans.Spans(device_events(), [])
    per = spans.scope_ms_per_decode(sp)
    # two decode calls: attn 6 + 8 ms each, mlp 6, head 6; the while
    # (24 ms, no scope of its own) and the prefill's attn op do not count
    assert per == {"attn": pytest.approx(14.0), "mlp": pytest.approx(6.0),
                   "head": pytest.approx(6.0)}
    scoped = [_op("while.4", 5, 24, "attn"), _op("fusion.1", 5, 10, "attn")]
    leaves = spans._leaves(scoped)
    assert [o.name for o in leaves] == ["fusion.1"]


def test_host_ms_and_kv_share():
    sp = spans.Spans(device_events(), program_events())
    # each tick is 44 ms, of which 30 ms waited on the device
    assert spans.tick_host_ms(sp) == [pytest.approx(14.0)] * 2
    assert spans.kv_read_useful_share(sp) == pytest.approx(
        100.0 * 600 / 2048)
    # arguments a TraceMe left in the event's name read the same
    assert spans.parse_name(
        "repro.scheduler.micro_step#live_tokens=300,kv_read_tokens=1024#"
    ) == ("repro.scheduler.micro_step",
          {"live_tokens": "300", "kv_read_tokens": "1024"})
    # a parent without program spans reads nothing, and raises nothing
    bare = spans.Spans(device_events(), [])
    assert spans.tick_host_ms(bare) == []
    assert spans.kv_read_useful_share(bare) is None


@dataclasses.dataclass
class FakeRun:
    cell: str = "tiny"
    trace: dict = dataclasses.field(default_factory=dict)
    notes: dict = dataclasses.field(default_factory=dict)
    family: object = None


@pytest.mark.parametrize("name,value", [
    ("host_ms_per_tick", 14.0),
    ("decode_attn_ms", 14.0),
    ("kv_read_useful_share", 100.0 * 600 / 2048),
])
def test_readers_on_a_synthetic_trace(monkeypatch, name, value):
    read = harness.load_reader(name)
    run = FakeRun()
    sp = spans.Spans(device_events(), program_events())
    monkeypatch.setattr(spans, "of", lambda r: sp)
    assert read(run) == pytest.approx(value)
    if name == "host_ms_per_tick":
        assert run.notes["host_ticks"] == 2
        assert run.notes["idle_program_share"] == pytest.approx(
            100.0 * 10 / 36)
    # the parent program writes no spans or scopes: nothing is read
    monkeypatch.setattr(spans, "of", lambda r: spans.Spans(
        device_events() if name != "decode_attn_ms" else [
            dataclasses.replace(e, stats={k: v for k, v in e.stats.items()
                                          if k != "scope"})
            for e in device_events()], []))
    run = FakeRun()
    assert read(run) is None
    if name == "decode_attn_ms":
        assert run.notes["decode_scope_ms"] == "no scoped operation"


def test_untraced_run_reads_nothing():
    run = FakeRun(trace=None)
    assert spans.of(run) is None
    for name in ("host_ms_per_tick", "decode_attn_ms",
                 "kv_read_useful_share"):
        assert harness.load_reader(name)(run) is None


def test_a_recorded_cpu_trace_of_one_engine_step(tmp_path):
    """The engine's phases land on the profiler's host plane with their
    counts; the bench's loader keeps them apart from its own events."""
    import jax
    from tiny import CONFIG
    from repro.serving.engine import InferenceEngine, Request
    from repro.models import model as M
    cfg = harness.load_family(CONFIG).program_config(CONFIG)
    params = M.init(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, max_batch=2, capacity=64)
    assert eng.paged
    for p in ([1, 2, 3], [4, 5, 6, 7]):
        eng.submit(Request(prompt=p, max_new_tokens=8))
    eng.step()                  # admits and compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            eng.step()
    jax.profiler.stop_trace()
    path = T.find_xplane(str(tmp_path))
    sp = spans.load(path)
    names = {e.name for e in sp.program}
    assert {"repro.scheduler." + n for n in (
        "tick", "decode", "micro_step", "grow", "inputs", "dispatch",
        "device_wait", "emit")} <= names
    assert all(e.plane == HOST for e in sp.program)
    step = next(e for e in sp.program
                if e.name == "repro.scheduler.micro_step")
    assert int(step.stats["kv_read_tokens"]) == 2 * 4 * 16
    assert int(step.stats["live_tokens"]) == sum(
        int(eng.slots.lengths[s]) for s in eng.running)
    tick = next(e for e in sp.program if e.name == "repro.scheduler.tick")
    assert int(tick.stats["step_num"]) == 1
    assert spans.kv_read_useful_share(sp) == pytest.approx(
        100.0 * int(step.stats["live_tokens"]) / 128)
    assert len(spans.tick_host_ms(sp)) == 1
    # the existing loader reads none of them
    assert not any(e.name.startswith("repro.") for e in T.load(path))


def test_scopes_in_the_compiled_decode_program():
    """The compiled decode program names the scopes in its ops' op_name
    metadata, and the scope map finds each of them there: attention's
    gather among the attn ops."""
    import jax
    import jax.numpy as jnp
    from tiny import CONFIG
    from repro.serving.engine import InferenceEngine
    from repro.models import model as M
    cfg = harness.load_family(CONFIG).program_config(CONFIG)
    params = M.init(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, max_batch=2, capacity=64)
    B = eng.slots.B
    z = jnp.zeros((B,), jnp.float32)
    text = eng._decode_sample_paged.lower(
        eng.params, jnp.zeros((B, 1), jnp.int32), eng.slots.pool,
        eng.slots.tables_device(), jnp.ones((B,), jnp.int32),
        jax.random.PRNGKey(0), z, jnp.zeros((B,), jnp.int32), z + 1,
        None, None, True).compile().as_text()
    found = {}
    for name, op_name in re.findall(r'^\s*(?:ROOT )?(%\S+) = .*op_name="'
                                    r'([^"]*)"', text, re.M):
        scope = spans.scope_of(op_name)
        if scope:
            found.setdefault(scope, []).append(op_name)
    assert set(found) == set(spans.SCOPES)
    assert any(n.endswith("/gather") for n in found["attn"])
    assert spans.scope_of("jit(f)/while/body/closed_call/attn/"
                          "flash_decode_kernel_scope/sub:") == "attn"
    assert spans.scope_of("jit(f)/while/body/add:") is None
    # a scope a family names wins inside the program's own
    experts = "jit(f)/while/body/closed_call/mlp/experts/dot_general:"
    assert spans.scope_of(experts) == "mlp"
    assert spans.scope_of(experts, spans.SCOPES + ("experts",)) == "experts"


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _msg(*fields):
    """Protobuf wire bytes of ``(field, value)`` pairs: ints as varints,
    bytes and strings length-delimited."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_names_read_from_the_event_metadata():
    """``tf_op`` of each device op's event metadata, read from the wire
    format as a TPU's xplane holds it: as a string, or as a reference
    to a string kept once; host planes and other stats are skipped."""
    stat_md = [(5, _msg((1, n), (2, _msg((1, n), (2, name)))))
               for n, name in ((7, "tf_op"), (8, "flops"),
                               (9, "jit(f)/while/body/attn/gather:"))]
    ev_md = [(4, _msg((1, 1), (2, _msg(
                 (1, 1), (2, "%fusion.1 = f32[2] fusion()"),
                 (5, _msg((1, 8), (4, 12))),
                 (5, _msg((1, 7), (5, "jit(f)/head/argmax:"))))))),
             (4, _msg((1, 2), (2, _msg(
                 (1, 2), (2, "%copy.3 = f32[2] copy()"),
                 (5, _msg((1, 7), (7, 9))))))),
             (4, _msg((1, 3), (2, _msg((1, 3), (2, "%add.4 = add()")))))]
    line = (3, _msg((2, "XLA Ops"), (4, _msg((1, 1), (2, 5)))))
    device = _msg((1, 1), (2, "/device:TPU:0"), line, *ev_md, *stat_md)
    host = _msg((1, 2), (2, "/host:CPU"), *ev_md, *stat_md)
    space = _msg((1, device), (1, host))
    assert spans.op_names(space) == {"/device:TPU:0": {
        "%fusion.1 = f32[2] fusion()": "jit(f)/head/argmax:",
        "%copy.3 = f32[2] copy()": "jit(f)/while/body/attn/gather:"}}


def _decode_xspace():
    """A serialized XSpace of one chip: one decode call of 30 ms holding
    an attention gather (10 ms), a routed-expert matmul inside the MLP
    (6 ms) and a shared MLP matmul (4 ms), each named by its ``tf_op``."""
    ops = {1: ("jit__fused_paged(7)", None),
           2: ("%fusion.1 = bf16[2] fusion()", "attn/gather:"),
           3: ("%fusion.2 = bf16[2] fusion()", "mlp/experts/dot_general:"),
           4: ("%fusion.3 = bf16[2] fusion()", "mlp/dot_general:")}
    ev_md = [(4, _msg((1, i), (2, _msg((1, i), (2, name), *(
        [(5, _msg((1, 7), (5, "jit(_fused_paged)/while/body/" + op)))]
        if op else [])))))
        for i, (name, op) in ops.items()]
    stat_md = (5, _msg((1, 7), (2, _msg((1, 7), (2, "tf_op")))))

    def ev(md, start_ms, dur_ms):
        return (4, _msg((1, md), (2, int(start_ms * 1e9)),
                        (3, int(dur_ms * 1e9))))

    modules = (3, _msg((1, 1), (2, T.MODULES_LINE), (3, 1000), ev(1, 0, 30)))
    op_line = (3, _msg((1, 2), (2, T.OPS_LINE), (3, 1000), ev(2, 0, 10),
                       ev(3, 10, 6), ev(4, 16, 4)))
    return _msg((1, _msg((1, 1), (2, DEV0), modules, op_line, *ev_md,
                         stat_md)))


def test_a_family_s_scopes_book_its_ops_in_decode_scope_ms(tmp_path,
                                                            monkeypatch):
    """One trace read for the GQA family (no scopes of its own) and for a
    family that names ``experts``: the expert matmul leaves ``mlp`` for
    ``experts`` only in the second, and the GQA family's readings are
    those of the program's scopes alone, before and after."""
    d = tmp_path / "tiny" / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "chip.xplane.pb").write_bytes(_decode_xspace())
    monkeypatch.setattr(spans, "TRACES", str(tmp_path))
    read = harness.load_reader("decode_attn_ms")
    gqa = harness.load_family({"family": "gqa_decoder"})
    moe = types.SimpleNamespace(SCOPES=("experts",))
    for family, want in ((gqa, {"attn": 10.0, "mlp": 10.0}),
                         (moe, {"attn": 10.0, "experts": 6.0, "mlp": 4.0}),
                         (gqa, {"attn": 10.0, "mlp": 10.0})):
        run = FakeRun(family=family)
        assert read(run) == pytest.approx(10.0)
        assert run.notes["decode_scope_ms"] == pytest.approx(want)

"""The trace reduction on small synthetic traces."""
import pytest

import harness

T = harness.load_module("trace")
E = T.Event
DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
MS = 1e6


def _mod(plane, name, start, dur, pid=1):
    return E(plane, T.MODULES_LINE, name, start * MS, dur * MS,
             {"program_id": pid})


def _op(plane, name, start, dur):
    return E(plane, T.OPS_LINE, name, start * MS, dur * MS, {})


def _ann(name, start, dur):
    return E(HOST, "python", "bench." + name, start * MS, dur * MS, {})


def one_chip():
    ev = [_ann("window", 0, 100),
          _ann("engine_step", 0, 40), _ann("engine_step", 50, 40),
          _ann("gateway_submit", 92, 6)]
    # two decode calls and one prefill, with ops inside them
    ev += [_mod(DEV0, "jit__fused_paged(7)", 5, 30),
           _mod(DEV0, "jit__fused_paged(7)", 55, 30),
           _mod(DEV0, "jit__prefill_fn(9)", 90, 4, pid=2)]
    ev += [_op(DEV0, "fusion.1", 5, 20), _op(DEV0, "fusion.2", 20, 15),
           _op(DEV0, "fusion.1", 55, 30), _op(DEV0, "dot.3", 90, 4)]
    return ev


def test_busy_union_programs_and_idle_labels():
    r = T.reduce(one_chip())
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.1)
    # ops 5-35 (overlapping pair merged), 55-85, 90-94: 64 ms busy
    assert r["busy_s"] == pytest.approx(0.064)
    assert r["programs"]["decode"]["calls"] == 2
    assert r["programs"]["decode"]["device_s"] == pytest.approx(0.060)
    assert r["programs"]["prefill"]["device_s"] == pytest.approx(0.004)
    assert r["collective_s"] == 0
    idle = dict(r["idle_by_label"])
    # gaps 0-5 and 85-90 lie in engine steps; 35-55 has its middle in
    # no step (window); 94-100 has its middle in a submit
    assert idle["engine_step"] == pytest.approx(0.010)
    assert idle["window"] == pytest.approx(0.020)
    assert idle["gateway_submit"] == pytest.approx(0.006)
    assert r["top_ops"][0] == ("fusion.1", pytest.approx(0.050))


def test_collectives_count_only_inside_decode_and_average_over_chips():
    ev = [_ann("window", 0, 100)]
    for dev in (DEV0, DEV1):
        ev += [_mod(dev, "jit_wrapped(3)", 10, 40, pid=3),
               _mod(dev, "jit_wrapped(3)", 60, 20, pid=3),
               _mod(dev, "jit_wrapped(4)", 85, 10, pid=4)]
        ev += [_op(dev, "all-reduce.1", 20, 5),
               _op(dev, "%fusion.4 = bf16[8] fusion(%all-reduce.1)", 25, 25),
               _op(dev, "all-gather-start.2", 60, 4),
               _op(dev, "fusion.5", 64, 16),
               _op(dev, "all-reduce.7", 85, 10)]     # in the prefill
    r = T.reduce(ev)
    assert r["chips"] == 2
    # the wrapped program that ran most often is the decode step
    assert r["programs"]["decode"]["calls"] == pytest.approx(2)
    assert r["programs"]["prefill"]["calls"] == pytest.approx(1)
    assert r["collective_s"] == pytest.approx(0.009)
    assert r["busy_s"] == pytest.approx(0.060)


def test_no_decode_program_raises_never_zero():
    ev = [_ann("window", 0, 10), _mod(DEV0, "jit_other", 1, 2),
          _op(DEV0, "fusion.1", 1, 2)]
    with pytest.raises(ValueError, match="no decode program"):
        T.reduce(ev)
    with pytest.raises(ValueError, match="no TPU device plane"):
        T.reduce([_ann("window", 0, 10)])


def test_load_reads_a_recorded_host_trace(tmp_path):
    """A real (CPU) trace: the loader keeps the bench annotations."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = T.load(T.find_xplane(str(tmp_path)))
    names = {e.name for e in ev}
    assert {"bench.window", "bench.engine_step"} <= names
    with pytest.raises(ValueError, match="no TPU device plane"):
        T.reduce(ev)

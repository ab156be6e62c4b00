"""Gradient compression + error feedback properties."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.parallel.compression import (compress_with_feedback, ef_init,
                                        wire_bytes)


def test_int8_roundtrip_bounded_error():
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (256, 64))}
    ef = ef_init(g)
    r, ef = compress_with_feedback(g, ef, bits=8)
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    err = float(jnp.max(jnp.abs(r["w"] - g["w"])))
    assert err <= scale * 0.5 + 1e-6


def test_error_feedback_unbiased_over_steps():
    """With a CONSTANT gradient, error feedback makes the mean of the
    reconstructed gradients converge to the true gradient."""
    g = {"w": jnp.asarray([0.004, -0.3, 1.7, 0.011])}
    ef = ef_init(g)
    acc = jnp.zeros_like(g["w"])
    steps = 64
    for _ in range(steps):
        r, ef = compress_with_feedback(g, ef, bits=8)
        acc = acc + r["w"]
    mean = acc / steps
    np.testing.assert_allclose(np.asarray(mean), np.asarray(g["w"]),
                               rtol=0.02, atol=1e-4)


@settings(max_examples=8, deadline=None)
@given(bits=st.sampled_from([8, 16]), n=st.integers(8, 300))
def test_wire_bytes_shrink(bits, n):
    g = {"w": jnp.ones((n,), jnp.float32)}
    assert wire_bytes(g, bits) < n * 4 + 8


def test_reduction_schedules_agree():
    """All three schedules produce the same reduced gradients."""
    import os, subprocess, sys
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel import collectives as C

mesh = jax.make_mesh((8,), ("dp",))
gs = [jax.random.normal(jax.random.PRNGKey(i), (64 * 8,)) for i in range(3)]
outs = []
for fn in (lambda g: C.per_tensor_psum(g, "dp"),
           lambda g: C.bucketed_psum(g, "dp"),
           lambda g: C.rs_ag(g, "dp", pad_to=64)):
    f = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P("dp"),),
                              out_specs=P("dp")))
    outs.append(f(gs))
for o in outs[1:]:
    for a, b in zip(outs[0], o):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
print("SCHEDULES-AGREE")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "SCHEDULES-AGREE" in out.stdout, out.stderr[-2000:]

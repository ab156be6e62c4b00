"""Paper Table 1: DDP step time vs network path (ResNet-18/CIFAR scale,
~45 MB of gradients, world size 8).

TPU adaptation (DESIGN.md §2): the *insight* — the collective path, not
compute, dominates small-model DDP — transfers as the choice of gradient
reduction schedule.  Two reproductions:

1. analytic: the paper's four network paths under a (bandwidth,
   per-message overhead) model; reproduces the eth0/hsn0/multi-NIC
   ordering including the multi-NIC *regression* for small payloads.
2. measured: three JAX-native reduction schedules (per-tensor all-reduce,
   bucketed all-reduce, reduce-scatter+all-gather) wall-clocked on an
   8-fake-device host mesh in a subprocess.
"""
from __future__ import annotations

import os
import subprocess
import sys
from typing import Dict, List, Tuple

GRAD_BYTES = 45e6          # paper: ResNet-18 allreduce payload ~45 MB
WORLD = 8                  # 2 nodes x 4 GPUs
COMPUTE_MS = 3.0           # fwd/bwd of ResNet-18 on H100 at bs 256, approx

# (name, per-link bandwidth B/s, links, per-message overhead s, messages)
# eth0: management overlay, high stack overhead; hsn0 TCP: one 200 Gb NIC;
# hsn0-3 TCP: 4 sockets but per-message overhead x4 on a 45 MB payload;
# CXI RDMA: kernel-bypass tiny overhead. Ring all-reduce: 2(n-1)/n * bytes.
PATHS = [
    ("eth0_tcp", 25e9 / 8, 1, 6e-3, 25),
    ("hsn0_tcp", 200e9 / 8, 1, 1.2e-3, 25),
    ("hsn0-3_tcp", 200e9 / 8, 4, 1.2e-3, 50),  # 4 streams ~ 2x messages
    ("cxi_rdma", 200e9 / 8, 4, 15e-6, 25),
]
PAPER_MS = {"eth0_tcp": 190.0, "hsn0_tcp": 58.0, "hsn0-3_tcp": 79.0,
            "cxi_rdma": 4.0}


def analytic_rows() -> List[Tuple[str, float, float]]:
    out = []
    wire = 2 * (WORLD - 1) / WORLD * GRAD_BYTES
    for name, bw, links, overhead, msgs in PATHS:
        t = wire / (bw * links) + overhead * msgs + COMPUTE_MS / 1e3
        out.append((name, t * 1e3, PAPER_MS[name]))
    return out


_MEASURE_SRC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import time, jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

mesh = jax.make_mesh((8,), ("dp",))
repl = NamedSharding(mesh, P())
shard = NamedSharding(mesh, P("dp"))
# ~45 MB of "gradients" in 25 tensors (sizes divisible by 64 so the
# tiled reduce-scatter shards evenly on the 8-way mesh)
sizes = [450_048] * 25
grads = [jax.device_put(jnp.ones((s,), jnp.float32), shard)
         for s in sizes]

def timeit(fn, *args, iters=5):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3

def make(fn):
    return jax.jit(fn)

def psum_shardmap_per_tensor(gs):
    f = jax.shard_map(lambda *xs: tuple(jax.lax.psum(x, "dp") for x in xs),
                      mesh=mesh, in_specs=(P("dp"),) * len(gs),
                      out_specs=(P("dp"),) * len(gs))
    return f(*gs)

def psum_bucketed(gs):
    flat = jnp.concatenate(gs)
    f = jax.shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
                      in_specs=P("dp"), out_specs=P("dp"))
    return f(flat)

def rs_ag(gs):
    flat = jnp.concatenate(gs)
    def inner(x):
        r = jax.lax.psum_scatter(x, "dp", scatter_dimension=0, tiled=True)
        return jax.lax.all_gather(r, "dp", tiled=True)
    f = jax.shard_map(inner, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    return f(flat)

rows = [
    ("measured_per_tensor_psum", timeit(make(psum_shardmap_per_tensor), grads)),
    ("measured_bucketed_psum", timeit(make(psum_bucketed), grads)),
    ("measured_rs_ag", timeit(make(rs_ag), grads)),
]
for n, ms in rows:
    print(f"ROW,{n},{ms:.3f}")
"""


def measured_rows() -> List[Tuple[str, float]]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    # a host-device simulation by design: the child must not take an
    # accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _MEASURE_SRC], env=env,
                         capture_output=True, text=True, timeout=900)
    rows = []
    for line in out.stdout.splitlines():
        if line.startswith("ROW,"):
            _, name, ms = line.split(",")
            rows.append((name, float(ms)))
    if not rows:
        raise RuntimeError(out.stderr[-2000:])
    return rows


def run() -> List[str]:
    lines = []
    for name, ms, paper in analytic_rows():
        lines.append(f"table1_analytic_{name},{ms * 1e3:.1f},"
                     f"paper_ms={paper}")
    for name, ms in measured_rows():
        lines.append(f"table1_{name},{ms * 1e3:.1f},host_mesh_8dev")
    return lines


if __name__ == "__main__":
    print("\n".join(run()))

"""Size a configuration's KV pool from an ahead-of-time compile of its
decode step for a TPU v5e that is described, not attached.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/size_pool.py \\
        bench/configs/qwen1.5-4b.json 8 9 10

For each ``max_batch`` given, at the file's ``capacity`` (pool of
``max_batch * capacity`` tokens in 16-token blocks, as the engine makes
it), compiles the engine's greedy paged decode step at the file's
widths and ``tp`` (the pool sharded as the engine shards it, on
``M.paged_pool_axes``) and prints the bytes of arguments, temporaries
and outputs on one chip.  The largest ``max_batch`` whose arguments plus
temporaries stay under the compiler's grant is the one a deployment
would serve.  Nothing runs; each compile of apertus-8b at TP=4 takes a
few seconds on the host.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

V5E_GRANT = 15.75e9          # what the v5e compiler grants one program
BLOCK = 16                   # the scheduler's KV block


def compile_step(c: dict, max_batch: int):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, \
        SingleDeviceSharding

    import harness
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.models.param import abstract_params
    from repro.parallel import sharding
    from repro.serving.kvcache import constrain_cache

    cfg = harness.load_family(c).program_config(c)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    tp, cap = c["tp"], c["capacity"]
    nb = 1 + max_batch * cap // BLOCK
    params = abstract_params(M.model_specs(cfg), jnp.bfloat16)
    pool = M.make_paged_pool(cfg, nb, BLOCK, abstract=True,
                             kv_dtype=c["kv_dtype"])
    axes = M.paged_pool_axes(cfg, c["kv_dtype"])
    if tp == 1:
        mesh = rules = None
        one = SingleDeviceSharding(topo.devices[0])
        p_sh = jax.tree.map(lambda _: one, params)
        pool_sh = jax.tree.map(lambda _: one, pool)
        rep = one
    else:
        mesh = make_mesh((tp,), ("model",), devices=topo.devices[:tp])
        rules = sharding.make_rules("serving_tp")
        p_sh = sharding.tree_shardings(M.model_param_axes(cfg), mesh, rules)
        pool_sh = sharding.tree_shardings(axes, mesh, rules)
        rep = NamedSharding(mesh, PartitionSpec())

    def step(p, tokens, pool, block_tables, lengths):
        logits, pool = M.decode_step_paged(cfg, p, tokens, pool,
                                           block_tables, lengths)
        return (jnp.argmax(logits, -1).astype(jnp.int32),
                constrain_cache(pool, axes))

    put = lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa
                                            sharding=s)
    args = (jax.tree.map(put, params, p_sh),
            put(jax.ShapeDtypeStruct((max_batch, 1), jnp.int32), rep),
            jax.tree.map(put, pool, pool_sh),
            put(jax.ShapeDtypeStruct((max_batch, cap // BLOCK), jnp.int32),
                rep),
            put(jax.ShapeDtypeStruct((max_batch,), jnp.int32), rep))
    fn = sharding.sharded_jit(step, mesh, rules, donate_argnums=(2,))
    compiled = fn.lower(*args).compile()
    return compiled.memory_analysis(), compiled.as_text()


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    with open(argv[0]) as f:
        c = json.load(f)
    for b in map(int, argv[1:]):
        mem, hlo = compile_step(c, b)
        used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        print(json.dumps({
            "config": os.path.basename(argv[0]), "tp": c["tp"],
            "capacity": c["capacity"], "max_batch": b,
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "fits": used < V5E_GRANT,
            "all_reduce": hlo.count(" all-reduce("),
            "all_gather": hlo.count(" all-gather(")}), flush=True)


if __name__ == "__main__":
    main()

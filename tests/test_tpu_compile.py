"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

The chip's compiler refuses what interpret mode accepts: a tile that
needs more VMEM than a kernel may use, a reshape Mosaic cannot lower, a
vector load from SMEM, a program larger than the chip's HBM.  These
tests compile the paged attention kernels and the full-width decode
step of the served model at real widths, so such a refusal fails here
and costs no chip time.  Nothing runs; a pass says nothing of results
or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and pytest
workers import every test file.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.paged_attention import kernel as K
from repro.models import model as M
from repro.models.param import abstract_params

# the pool chip_smoke.py serves: --max-batch 4 --capacity 1024 with the
# scheduler's 16-token blocks, pool_tokens = max_batch * capacity
MAX_BATCH, CAPACITY, BLOCK = 4, 1024, 16
V5E_HBM_LIMIT = 15.75e9      # what the v5e compiler grants one program

# (heads, kv_heads) at head_dim 128: qwen1.5-4b is MHA, apertus-8b GQA
WIDTHS = {"qwen1.5-4b": (20, 20), "apertus-8b": (32, 8)}
KERNELS = {
    "decode-bf16": (K.paged_decode_attention, False, 1),
    "verify-bf16": (K.paged_verify_attention, False, 5),
    "decode-int8": (K.paged_decode_attention_int8, True, 1),
    "verify-int8": (K.paged_verify_attention_int8, True, 5),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("arch", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_paged_kernel_compiles_for_v5e(one_chip, kernel, arch):
    fn, int8, T = KERNELS[kernel]
    H, KV = WIDTHS[arch]
    D, B = 128, 8
    nb, W = 1 + B * CAPACITY // BLOCK, CAPACITY // BLOCK
    q = (B, H, D) if T == 1 else (B, T, H, D)
    pool = jax.ShapeDtypeStruct((nb, BLOCK, KV, D),
                                jnp.int8 if int8 else jnp.bfloat16)
    args = [jax.ShapeDtypeStruct(q, jnp.bfloat16), pool, pool]
    if int8:
        args += [jax.ShapeDtypeStruct((nb, KV), jnp.float32)] * 2
    args += [jax.ShapeDtypeStruct((B, W), jnp.int32),
             jax.ShapeDtypeStruct((B,), jnp.int32)]
    compiled = jax.jit(fn).lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _full_width_step(one_chip, max_batch, tail):
    """Compile the engine's greedy paged step for qwen1.5-4b at its
    published widths, bf16 weights, a pool of ``max_batch`` slots of
    ``CAPACITY``: the decode step for a 1-token tail, the speculative
    verify step otherwise.  Returns (config, pool, compiled)."""
    cfg = get_config("qwen1.5-4b")
    nb = 1 + max_batch * CAPACITY // BLOCK
    params = abstract_params(M.model_specs(cfg), jnp.bfloat16)
    pool = M.make_paged_pool(cfg, nb, BLOCK, abstract=True)
    fn = M.decode_step_paged if tail == 1 else M.verify_step_paged

    def step(p, tokens, pool, block_tables, lengths):
        logits, pool = fn(cfg, p, tokens, pool, block_tables, lengths)
        return jnp.argmax(logits, -1).astype(jnp.int32), pool

    args = _on(one_chip, (
        params, jax.ShapeDtypeStruct((max_batch, tail), jnp.int32), pool,
        jax.ShapeDtypeStruct((max_batch, CAPACITY // BLOCK), jnp.int32),
        jax.ShapeDtypeStruct((max_batch,), jnp.int32)))
    compiled = jax.jit(step, donate_argnums=(2,)).lower(*args).compile()
    return cfg, pool, compiled


def _results(hlo):
    """(dtype, element count, layout, op, fused) of every array
    instruction in compiled HLO text.  ``fused`` marks an instruction of
    a fusion's body, which is not materialized on its own; the layout
    carries the memory space: ``S(1)`` is the chip's on-core VMEM, none
    is HBM."""
    bodies = set(re.findall(r"calls=(%[\w.-]+)", hlo))
    fused = False
    for line in hlo.splitlines():
        if not line.startswith(" "):
            fused = line.split(" ")[0] in bodies
            continue
        m = re.search(r"= (\w+)\[([\d,]*)\](\{[^}]*\})? ([\w-]+)\(", line)
        if m:
            dims = [int(d) for d in m.group(2).split(",") if d]
            yield (m.group(1), math.prod(dims), m.group(3) or "",
                   m.group(4), fused)


def test_full_width_paged_decode_step_fits_one_v5e(one_chip):
    """qwen1.5-4b at its published widths, bf16 weights, the smoke's
    pool: the fused decode step the engine runs must fit one chip."""
    _, _, compiled = _full_width_step(one_chip, MAX_BATCH, 1)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 7.8e9       # the bf16 weights
    assert used < V5E_HBM_LIMIT, used


@pytest.mark.parametrize("tail", [1, 5], ids=["decode", "verify"])
def test_full_width_paged_step_updates_pool_in_place(one_chip, tail):
    """At the benchmark's 8 slots the decode step (and the speculative
    verify step with a 5-token tail) writes the donated pool in place:
    no second pool among the temporaries, no copy or
    dynamic-update-slice of the whole stacked pool, and no f32 copy of
    the gathered KV in HBM.  (The compiler still upcasts the gathered
    KV for attention's multiply-reduce, into VMEM.)"""
    B = 8
    cfg, pool, compiled = _full_width_step(one_chip, B, tail)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.25e9, mem.temp_size_in_bytes
    pool_elems = {math.prod(leaf.shape) for leaf in jax.tree.leaves(pool)}
    gathered = B * CAPACITY * cfg.num_kv_heads * cfg.head_dim
    for dtype, n, layout, op, fused in _results(compiled.as_text()):
        assert not (n in pool_elems
                    and op in ("copy", "dynamic-update-slice")), op
        assert fused or not (dtype == "f32" and n == gathered
                             and "S(1)" not in layout), op

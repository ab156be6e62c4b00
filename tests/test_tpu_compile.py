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


def test_full_width_paged_decode_step_fits_one_v5e(one_chip):
    """qwen1.5-4b at its published widths, bf16 weights, the smoke's
    pool: the fused decode step the engine runs must fit one chip."""
    cfg = get_config("qwen1.5-4b")
    nb = 1 + MAX_BATCH * CAPACITY // BLOCK
    params = abstract_params(M.model_specs(cfg), jnp.bfloat16)
    pool = M.make_paged_pool(cfg, nb, BLOCK, abstract=True)

    def step(p, tokens, pool, block_tables, lengths):
        logits, pool = M.decode_step_paged(cfg, p, tokens, pool,
                                           block_tables, lengths)
        return jnp.argmax(logits, -1).astype(jnp.int32), pool

    args = _on(one_chip, (
        params, jax.ShapeDtypeStruct((MAX_BATCH, 1), jnp.int32), pool,
        jax.ShapeDtypeStruct((MAX_BATCH, CAPACITY // BLOCK), jnp.int32),
        jax.ShapeDtypeStruct((MAX_BATCH,), jnp.int32)))
    compiled = jax.jit(step, donate_argnums=(2,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 7.8e9       # the bf16 weights
    assert used < V5E_HBM_LIMIT, used

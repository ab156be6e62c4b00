"""Pallas TPU paged flash-decode: T query tokens per sequence against a
*paged* KV cache, GQA.  ``T == 1`` is plain decode; ``T > 1`` is the
speculative-verify tail, query ``t`` at position ``length - T + t``.

The KV cache is a shared physical pool of fixed-size blocks —
``(num_blocks, block_size, KV, D)`` — and each sequence names its blocks
through a block table ``(B, max_blocks)`` of physical ids.  The grid is
(batch, table_column) with the table dimension sequential; both the block
table and the per-sequence valid lengths arrive via scalar prefetch
(SMEM), so the *index map itself* walks the table: the BlockSpec for K/V
resolves ``bt[b, j]`` before the kernel body runs and DMAs exactly that
physical block into VMEM.  No gathered per-sequence copy of the cache is
ever materialized in HBM — that gather is what the jnp oracle
(``ref.py``) pays for.

Queries arrive grouped by KV head, ``(B, KV, T*G, D)`` with row
``t*G + g``, so the body is one 2-D ``(T*G, D) x (D, blk)`` product per
KV head on a lane-aligned ``D``-wide slice of the block: no reshape of a
tile inside the kernel, which the TPU compiler does not lower for every
GQA width.  The online-softmax state is per KV head in VMEM scratch.

Physical block 0 is reserved as a null block: table entries past a
sequence's length point at it, the ``k_start < length`` guard skips their
compute, and the tail-block mask covers a partially-filled last block.

The ``*_int8`` variants read an int8 pool with per-block-per-head f32
scales (symmetric: ``x ≈ q * scale``).  The block's ``(1, KV)`` scale row
is DMA'd into SMEM through the same table walk as K/V, and each head's
scale multiplies its score row and its ``p @ v`` product — the scale is
uniform over the block, so that equals dequantizing the tile — so the
pool's HBM traffic stays int8 end to end (paged decode is
bandwidth-bound on the KV read).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(len_ref, bt_ref, q_ref, k_ref, v_ref, *rest, blk: int,
                  T: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)          # logical block index within the sequence
    _, KV, TG, D = q_ref.shape
    G = TG // T

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    k_start = j * blk

    @pl.when(k_start < length)
    def _compute():
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (TG, blk), 1)
        qpos = length - T + jax.lax.broadcasted_iota(
            jnp.int32, (TG, blk), 0) // G
        visible = (kpos <= qpos) & (kpos < length)
        scale = 1.0 / (D ** 0.5)
        for h in range(KV):
            q = q_ref[0, h].astype(jnp.float32) * scale        # (TG, D)
            k = k_ref[0, :, h * D:(h + 1) * D].astype(jnp.float32)
            v = v_ref[0, :, h * D:(h + 1) * D].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if quantized:
                s = s * ks_ref[0, 0, h]
            s = jnp.where(visible, s, NEG_INF)                  # (TG, blk)
            m_prev = m_scr[h]                                   # (TG, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, -1, keepdims=True)
            pv = jnp.dot(p, v, preferred_element_type=jnp.float32)
            if quantized:
                pv = pv * vs_ref[0, 0, h]
            acc_scr[h] = acc_scr[h] * corr + pv
            m_scr[h] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_call(q, k_pool, v_pool, scales, block_tables, lengths,
                interpret):
    """q (B, T, H, D) -> (B, T, H, D); ``scales`` is ``(k_scale,
    v_scale)`` for an int8 pool, else None."""
    B, T, H, D = q.shape
    nb, blk, KV, _ = k_pool.shape
    G = H // KV
    W = block_tables.shape[1]
    qg = q.reshape(B, T, KV, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, KV, T * G, D)
    q_spec = pl.BlockSpec((1, KV, T * G, D),
                          lambda b, j, lens, bt: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, blk, KV * D),
                           lambda b, j, lens, bt: (bt[b, j], 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [qg, k_pool.reshape(nb, blk, KV * D),
            v_pool.reshape(nb, blk, KV * D)]
    if scales is not None:
        in_specs += [pl.BlockSpec((1, 1, KV),
                                  lambda b, j, lens, bt: (bt[b, j], 0, 0),
                                  memory_space=pltpu.SMEM)] * 2
        args += [s.astype(jnp.float32).reshape(nb, 1, KV) for s in scales]
    kernel = functools.partial(_paged_kernel, blk=blk, T=T,
                               quantized=scales is not None)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, W),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((KV, T * G, 1), jnp.float32),
                pltpu.VMEM((KV, T * G, 1), jnp.float32),
                pltpu.VMEM((KV, T * G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32), *args)
    return out.reshape(B, KV, T, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, T, H, D)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k_pool/v_pool: (num_blocks, block_size, KV, D);
    block_tables: (B, max_blocks) int32 physical block ids; lengths: (B,)
    valid tokens per sequence.  Returns (B, H, D).

    Table entries at or past ``ceil(length / block_size)`` are never read
    (their grid steps are skipped), so callers may pad rows with any valid
    id — the serving layer uses the reserved null block 0.
    """
    return _paged_call(q[:, None], k_pool, v_pool, None, block_tables,
                       lengths, interpret)[:, 0]


def paged_verify_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    """Multi-query paged flash-decode for speculative verify.

    q: (B, T, H, D) — the T newest tokens of each sequence (their KV
    already scattered into the pool); k_pool/v_pool: (num_blocks,
    block_size, KV, D); block_tables: (B, max_blocks) int32; lengths:
    (B,) valid tokens including the T tail tokens.  Query t of row b
    sits at position ``lengths[b] - T + t`` and attends causally.
    Returns (B, T, H, D).  T == 1 is :func:`paged_decode_attention`."""
    return _paged_call(q, k_pool, v_pool, None, block_tables, lengths,
                       interpret)


def paged_decode_attention_int8(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, k_scale: jax.Array,
                                v_scale: jax.Array,
                                block_tables: jax.Array,
                                lengths: jax.Array, *,
                                interpret: bool = False) -> jax.Array:
    """Int8-pool variant of :func:`paged_decode_attention`.

    k_pool/v_pool: (num_blocks, block_size, KV, D) int8; k_scale/v_scale:
    (num_blocks, KV) f32 symmetric per-block-per-head scales (``x ≈ q *
    scale``).  Returns (B, H, D) in q.dtype.
    """
    return _paged_call(q[:, None], k_pool, v_pool, (k_scale, v_scale),
                       block_tables, lengths, interpret)[:, 0]


def paged_verify_attention_int8(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, k_scale: jax.Array,
                                v_scale: jax.Array,
                                block_tables: jax.Array,
                                lengths: jax.Array, *,
                                interpret: bool = False) -> jax.Array:
    """Int8-pool variant of :func:`paged_verify_attention`: q is
    (B, T, H, D), pools are int8 with (num_blocks, KV) f32 scales, and
    the causal-tail verify semantics match the bf16 kernel exactly."""
    return _paged_call(q, k_pool, v_pool, (k_scale, v_scale),
                       block_tables, lengths, interpret)

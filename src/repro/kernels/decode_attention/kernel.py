"""Pallas TPU flash-decode: one query token per sequence against a long
KV cache, GQA.

Grid: (batch, kv_blocks) with the kv dimension sequential; online-softmax
state for ALL H heads of the sequence is carried in VMEM scratch (H x D
fits comfortably: 64 heads x 128 = 32 KB fp32).  Per-sequence valid
length arrives via scalar prefetch (SMEM), masking both the tail block
and recovering variable-length batches without recompilation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, blk_k: int, G: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    k_start = ki * blk_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                  # (H, D)
        k = k_ref[0].astype(jnp.float32)                  # (blk_k, KV*D)
        H, D = q.shape
        KV = k.shape[-1] // D
        k = k.reshape(blk_k, KV, D)
        v = v_ref[0].astype(jnp.float32).reshape(blk_k, KV, D)
        scale = 1.0 / (D ** 0.5)
        # scores for all H heads: head h reads kv head h // G
        qg = q.reshape(KV, G, D)
        s = jnp.einsum("kgd,skd->kgs", qg * scale, k,
                       preferred_element_type=jnp.float32)  # (KV,G,blk)
        s = s.reshape(H, blk_k)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_scr[...]                               # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)                            # (H, blk)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, -1, keepdims=True)
        pv = jnp.einsum("kgs,skd->kgd", p.reshape(KV, G, blk_k), v,
                        preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv.reshape(H, D)
        m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, blk_k: int = 256,
                     interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k/v: (B, S, KV, D); lengths: (B,) valid entries.
    Returns (B, H, D)."""
    B, H, D = q.shape
    _, S, KV, _ = k.shape
    G = H // KV
    blk_k = min(blk_k, S)
    pad = (-S) % blk_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    kr = k.reshape(B, Sp, KV * D)
    vr = v.reshape(B, Sp, KV * D)

    grid = (B, Sp // blk_k)
    kernel = functools.partial(_decode_kernel, blk_k=blk_k, G=G)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, D), lambda b, ki, lens: (b, 0, 0)),
                pl.BlockSpec((1, blk_k, KV * D),
                             lambda b, ki, lens: (b, ki, 0)),
                pl.BlockSpec((1, blk_k, KV * D),
                             lambda b, ki, lens: (b, ki, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, D), lambda b, ki, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, kr, vr)
    return out

"""Pallas TPU flash attention (causal/full, GQA).

Grid: (batch*heads, q_blocks, kv_blocks) — kv innermost and sequential so
the online-softmax state (m, l, acc) lives in VMEM scratch across kv
iterations.  BlockSpecs tile Q/K/V into (blk_q x D) / (blk_k x D) VMEM
windows; D is the full head dim (hardware-aligned 64/128 for every
assigned arch).  Causal masking skips whole KV blocks above the diagonal
(`pl.when`), recovering the ~2x the XLA blockwise path wastes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, blk_q: int, blk_k: int, causal: bool,
                  kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * blk_q
    k_start = ki * blk_k
    # causal block skip: block strictly above the diagonal contributes 0
    run = (not causal) or (k_start <= q_start + blk_q - 1)
    if causal:
        run = k_start <= q_start + blk_q - 1  # traced predicate

    @pl.when(run if causal else True)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (blk_q, D)
        k = k_ref[0].astype(jnp.float32)                  # (blk_k, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (blk_q, blk_k)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_len
        if causal:
            mask &= qpos >= kpos
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                               # (blk_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, blk_q: int = 128,
                    blk_k: int = 128, interpret: bool = False) -> jax.Array:
    """q: (B, H, Sq, D); k/v: (B, KV, Skv, D); H % KV == 0.
    Returns (B, H, Sq, D) in q.dtype."""
    B, H, Sq, D = q.shape
    _, KV, Skv, _ = k.shape
    assert H % KV == 0
    G = H // KV
    scale = 1.0 / (D ** 0.5)

    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Skv)
    pad_q = (-Sq) % blk_q
    pad_k = (-Skv) % blk_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sq_p, Skv_p = Sq + pad_q, Skv + pad_k

    qr = q.reshape(B * H, Sq_p, D)
    kr = k.reshape(B * KV, Skv_p, D)
    vr = v.reshape(B * KV, Skv_p, D)

    grid = (B * H, Sq_p // blk_q, Skv_p // blk_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k,
        causal=causal, kv_len=Skv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, blk_k, D),
                         lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
            pl.BlockSpec((1, blk_k, D),
                         lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    out = out.reshape(B, H, Sq_p, D)
    return out[:, :, :Sq]

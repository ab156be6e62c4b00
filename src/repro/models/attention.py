"""GQA attention: blockwise (flash-style, scan over KV chunks) for
train/prefill, single-token cached attention for decode.

The blockwise path is the XLA-lowerable oracle used by the dry-run; on TPU
the Pallas kernels in ``repro.kernels.flash_attention`` /
``repro.kernels.decode_attention`` implement the same math (tests assert
allclose between the two).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope
from repro.models.param import ParamSpec
from repro.parallel import sharding

NEG_INF = -1e30


def lora_shift(x, ab, adapter_ids):
    """Batched multi-LoRA delta (the Punica/S-LoRA BGMV oracle).

    x: (B,S,din); ab: stacked adapter pair {"a": (K, din, r),
    "b": (K, r, dout)} with slot 0 all-zero (= base model); adapter_ids:
    (B,) int32 per-sequence adapter indices.  Each row adds its *own*
    adapter's low-rank shift ``x @ A[id] @ B[id]`` (any alpha/rank scale
    is folded into B at registration), so one fused step serves a batch
    mixing several adapters with base-model rows.  Accumulates in fp32
    and casts back so base-row results keep the base dtype.
    """
    a = jnp.take(ab["a"], adapter_ids, axis=0).astype(jnp.float32)
    b = jnp.take(ab["b"], adapter_ids, axis=0).astype(jnp.float32)
    t = jnp.einsum("bsd,bdr->bsr", x.astype(jnp.float32), a)
    return jnp.einsum("bsr,bro->bso", t, b).astype(x.dtype)


def attn_specs(cfg: ModelConfig):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = {
        "wq": ParamSpec((d, h * hd), ("fsdp", "tensor"), "fan_in"),
        "wk": ParamSpec((d, kv * hd), ("fsdp", "tensor"), "fan_in"),
        "wv": ParamSpec((d, kv * hd), ("fsdp", "tensor"), "fan_in"),
        "wo": ParamSpec((h * hd, d), ("tensor", "fsdp"), "fan_in"),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((h * hd,), ("tensor",), "zeros")
        s["bk"] = ParamSpec((kv * hd,), ("tensor",), "zeros")
        s["bv"] = ParamSpec((kv * hd,), ("tensor",), "zeros")
    return s


def project_qkv(cfg: ModelConfig, p, x, positions, rope: bool = True,
                lora=None, adapter_ids=None):
    """x: (B,S,d) -> q (B,S,H,hd), k/v (B,S,KV,hd).  ``lora`` holds
    per-target stacked adapter pairs (see :func:`lora_shift`); deltas are
    added to the flat projections, before RoPE — exactly where a merged
    ``W + scale*A@B`` weight would land them."""
    B, S, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    k = jnp.einsum("bsd,dq->bsq", x, p["wk"])
    v = jnp.einsum("bsd,dq->bsq", x, p["wv"])
    if lora:
        if "wq" in lora:
            q = q + lora_shift(x, lora["wq"], adapter_ids)
        if "wk" in lora:
            k = k + lora_shift(x, lora["wk"], adapter_ids)
        if "wv" in lora:
            v = v + lora_shift(x, lora["wv"], adapter_ids)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, h, hd)
    k = k.reshape(B, S, kv, hd)
    v = v.reshape(B, S, kv, hd)
    if rope and cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg, hd)
        k = apply_rope(k, positions, cfg, hd)
    q = sharding.constrain(q, ("act_batch", "act_qseq", "act_heads", None))
    k = sharding.constrain(k, ("act_batch", "act_kvseq", "act_heads", None))
    v = sharding.constrain(v, ("act_batch", "act_kvseq", "act_heads", None))
    return q, k, v


def _chunked(x, chunk, axis):
    n = x.shape[axis]
    chunk = min(chunk, n)
    if n % chunk:
        chunk = n  # fall back to a single chunk for ragged sizes
    shape = x.shape[:axis] + (n // chunk, chunk) + x.shape[axis + 1:]
    return x.reshape(shape), chunk


def blockwise_attention(q, k, v, *, causal: bool, q_offset=0,
                        kv_chunk: int = 1024,
                        kv_valid_len: Optional[jax.Array] = None):
    """Flash-style attention via lax.scan over KV chunks (fp32 softmax).

    q: (B,Sq,H,hd); k,v: (B,Skv,KV,hd) with H % KV == 0.
    ``q_offset``: global position of q[0] (for causal masking of a sharded
    or cached query block).  ``kv_valid_len``: optional (B,) valid KV
    prefix (padded prefill).
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    # operands stay in model dtype (bf16 on the TPU path); accumulation is
    # f32 via preferred_element_type — no materialized f32 cache copies.
    qg = q.reshape(B, Sq, KV, G, hd)
    kc, chunk = _chunked(k, kv_chunk, 1)                       # (B,N,C,KV,hd)
    vc, _ = _chunked(v, kv_chunk, 1)
    nchunks = kc.shape[1]
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qpos = q_offset + jnp.arange(Sq)

    def body(carry, inp):
        m, l, acc = carry
        kch, vch, ci = inp
        kvpos = ci * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqkgd,bckd->bkgqc", qg, kch,
                       preferred_element_type=jnp.float32) * scale
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask &= qpos[:, None] >= kvpos[None, :]
        if kv_valid_len is not None:
            mask &= kvpos[None, None, :] < kv_valid_len[:, None, None]
            s = jnp.where(mask[:, None, None] if mask.ndim == 3 else mask,
                          s, NEG_INF)
        else:
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bckd->bkgqd", p.astype(vch.dtype), vch,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    ks = jnp.moveaxis(kc, 1, 0)  # (N,B,C,KV,hd)
    vs = jnp.moveaxis(vc, 1, 0)
    # flash-style backward: recompute chunk scores instead of saving the
    # (B,KV,G,Sq,chunk) probability tensors per chunk.  The named scope
    # marks the kernel interior for the kernel-aware roofline (the Pallas
    # flash kernel keeps these tensors in VMEM on TPU).
    with jax.named_scope("flash_kernel_scope"):
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(body), (m0, l0, a0),
            (ks, vs, jnp.arange(nchunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, hd)
    return out


def naive_attention(q, k, v, *, causal: bool, q_offset=0,
                    kv_valid_len=None):
    """Materialized-scores oracle (tests/tiny models only)."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32))
    s = s / jnp.sqrt(hd)
    qpos = q_offset + jnp.arange(Sq)
    kvpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= qpos[:, None] >= kvpos[None, :]
    s = jnp.where(mask, s, NEG_INF)
    if kv_valid_len is not None:
        vm = kvpos[None, :] < kv_valid_len[:, None]          # (B,Skv)
        s = jnp.where(vm[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, hd)


def decode_attention(q, k_cache, v_cache, lengths):
    """One-token attention against a cache.

    q: (B,1,H,hd); k_cache/v_cache: (B,Smax,KV,hd); lengths: (B,) number of
    valid cache entries (the new token's KV must already be written).
    """
    B, _, H, hd = q.shape
    _, Smax, KV, _ = k_cache.shape
    G = H // KV
    # cache operands stay bf16 (no full-cache f32 materialization); f32
    # accumulation via preferred_element_type (the Pallas decode kernel
    # implements the same contract in VMEM)
    qg = q.reshape(B, KV, G, hd).astype(k_cache.dtype)
    # interior marked for the kernel-aware roofline: the Pallas
    # flash-decode kernel keeps scores/probabilities in VMEM
    with jax.named_scope("flash_decode_kernel_scope"):
        s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(hd)
        s = sharding.constrain(
            s, ("act_batch", "act_heads", None, "act_kvseq"))
        valid = jnp.arange(Smax)[None, :] < lengths[:, None]  # (B,Smax)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bkgs,bskd->bkgd",
                       (p / jnp.maximum(l, 1e-30)).astype(v_cache.dtype),
                       v_cache, preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, hd)


def verify_attention(q, k_cache, v_cache, lengths):
    """Multi-token tail attention against a cache (speculative verify).

    q: (B,S,H,hd) — the S newest tokens of each sequence, whose KV must
    already be written; lengths: (B,) valid cache entries *including* all
    S tail tokens, so query t of row b sits at absolute position
    ``lengths[b] - S + t`` and attends causally to positions ``<=`` its
    own.  For S == 1 this is exactly :func:`decode_attention`; the
    single-token path is kept separate so its jit signature (and the
    engine's step-for-step numerics) are untouched.
    """
    B, S, H, hd = q.shape
    _, Smax, KV, _ = k_cache.shape
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd).astype(k_cache.dtype)
    with jax.named_scope("flash_verify_kernel_scope"):
        s = jnp.einsum("bskgd,bmkd->bkgsm", qg, k_cache,
                       preferred_element_type=jnp.float32)
        s = s / jnp.sqrt(hd)
        qpos = lengths[:, None] - S + jnp.arange(S)[None, :]      # (B,S)
        valid = jnp.arange(Smax)[None, None, :] <= qpos[:, :, None]
        s = jnp.where(valid[:, None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bkgsm,bmkd->bkgsd",
                       (p / jnp.maximum(l, 1e-30)).astype(v_cache.dtype),
                       v_cache, preferred_element_type=jnp.float32)
    return jnp.moveaxis(o, 3, 1).reshape(B, S, H, hd)


def pool_index(layer, *idx):
    """Index tuple into a paged pool leaf: a stacked ``(layers,
    num_blocks, ...)`` leaf takes the layer first, a lone layer's
    ``(num_blocks, ...)`` leaf (``layer`` None) does not."""
    return idx if layer is None else (layer,) + idx


def pool_axes(layer, *axes):
    """Logical axes of a paged pool leaf, ``"layers"`` first when the
    leaf is stacked (see :func:`pool_index`)."""
    return axes if layer is None else ("layers",) + axes


def _gather_heads(pool, scale, block_tables, layer, hd):
    """Each row's blocks of a flat GQA pool leaf ``(…, num_blocks,
    block_size, KV·hd)`` in logical order, ``(B, W·block_size, KV, hd)``.
    An int8 leaf comes with its ``(…, num_blocks, KV)`` scale and is
    dequantized to f32; otherwise ``scale`` is None."""
    rows = pool[pool_index(layer, block_tables)]        # (B, W, blk, KV·hd)
    B, W, blk, F = rows.shape
    rows = rows.reshape(B, W, blk, F // hd, hd)
    if scale is not None:
        rows = (rows.astype(jnp.float32)
                * scale[pool_index(layer, block_tables)][:, :, None, :, None])
    # the gather moves block/position dims only; pin the head axis so a
    # TP partitioner keeps the gathered sequence head-sharded like the
    # pool (no-op without active sharding rules)
    return sharding.constrain(rows.reshape(B, W * blk, F // hd, hd),
                              ("act_batch", "act_kvseq", "act_heads", None))


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           layer=None, k_scale=None, v_scale=None):
    """One-token attention against a *paged* cache (jnp oracle).

    q: (B,1,H,hd); k_pool/v_pool: the shared physical pool's flat leaves,
    ``(num_blocks, block_size, KV·hd)``, or stacked ``(layers, ...)`` and
    read at ``layer``; block_tables: (B, max_blocks) int32 physical block
    ids; lengths: (B,) valid tokens (the new token's KV must already be
    written).  An int8 pool passes its ``(…, num_blocks, KV)`` f32
    scales and is dequantized as it is gathered.  Gathers each
    sequence's blocks into logical order and runs the dense decode math
    — the Pallas kernel (``repro.kernels.paged_attention``) implements
    the same contract on TPU by walking the table in SMEM instead of
    materializing the gather.
    """
    hd = q.shape[-1]
    return decode_attention(
        q, _gather_heads(k_pool, k_scale, block_tables, layer, hd),
        _gather_heads(v_pool, v_scale, block_tables, layer, hd), lengths)


def paged_kv_read_tokens(batch: int, table_width: int,
                         block_size: int) -> int:
    """KV positions one layer's paged attention reads per step:
    :func:`paged_decode_attention` (and the verify and latent variants)
    gathers every row's whole block table, ``batch x table_width x
    block_size`` positions, whatever the live lengths are.  Keep it
    beside the gather so that it changes when the gather does."""
    return batch * table_width * block_size


def paged_verify_attention(q, k_pool, v_pool, block_tables, lengths,
                           layer=None, k_scale=None, v_scale=None):
    """Multi-token tail attention against a *paged* cache (jnp oracle).

    q: (B,S,H,hd) — the S newest tokens, KV already scattered into the
    pool; lengths: (B,) valid tokens including all S; the pool as in
    :func:`paged_decode_attention`.  Gathers each sequence's blocks into
    logical order and runs :func:`verify_attention` — the Pallas kernel
    (``repro.kernels.paged_attention.paged_verify``) implements the same
    contract on TPU by walking the table in SMEM.
    """
    hd = q.shape[-1]
    return verify_attention(
        q, _gather_heads(k_pool, k_scale, block_tables, layer, hd),
        _gather_heads(v_pool, v_scale, block_tables, layer, hd), lengths)


def quantized_scatter_token(pool, scales, x_t, pb, off, layer=None):
    """Scatter one token's values into an int8 paged pool leaf.

    pool: (…, num_blocks, blk, F) int8, stacked leaves read and written at
    ``layer`` (see :func:`pool_index`); scales: (…, num_blocks) or (…,
    num_blocks, heads) f32 — one symmetric scale per block (per head when
    the leaf has a head axis, reducing over everything else); x_t:
    (B, *inner) new values, whose ``inner`` holds F elements (a GQA
    leaf stores a position's heads flat); pb/off: (B,) physical block and
    in-block slot.

    The block scale is a running max: if the new token raises it, the
    block's resident rows are requantized under the wider scale (gather
    one block per row, rescale, scatter back).  When the scale is
    unchanged the requant ratio is exactly 1.0, integers round to
    themselves, and resident codes are bit-identical — so appends within
    a block's existing dynamic range never disturb earlier tokens.
    Duplicate ``pb`` rows only occur for inert slots parked on the
    reserved null block 0, which is never read.
    """
    at = pool_index(layer, pb)
    blk = pool.shape[-2]
    x = x_t.astype(jnp.float32)
    B = x.shape[0]
    q_old = pool[at].astype(jnp.float32).reshape(
        (B, blk) + x.shape[1:])                      # (B, blk, *inner)
    s_old = scales[at]                              # (B,) or (B, heads)
    if s_old.ndim == 2:
        bcast = (slice(None), None, slice(None), None)    # -> (B,1,h,1)
        tokb = (slice(None), slice(None), None)           # -> (B,h,1)
    else:
        bcast = (slice(None), None, None)                 # -> (B,1,1)
        tokb = (slice(None), None)                        # -> (B,1)
    s_tok = jnp.max(jnp.abs(x), axis=-1) / 127.0          # (B,) or (B,h)
    s_new = jnp.maximum(s_old, s_tok)
    denom = jnp.maximum(s_new, 1e-12)
    ratio = jnp.where(s_new > 0, s_old / denom, 0.0)
    q_res = jnp.round(q_old * ratio[bcast])
    q_tok = jnp.clip(jnp.round(x / denom[tokb]), -127, 127)
    sel = jnp.arange(blk) == off[:, None]                 # (B, blk)
    sel = sel.reshape(sel.shape + (1,) * (x.ndim - 1))
    blk_new = jnp.where(sel, q_tok[:, None], q_res)
    pool = pool.at[at].set(
        blk_new.reshape(B, blk, -1).astype(pool.dtype))
    scales = scales.at[at].set(s_new)
    return pool, scales


def attention_block(cfg: ModelConfig, p, x, positions, *,
                    mode: str, cache=None, lengths=None,
                    kv_valid_len=None, causal: bool = True,
                    block_tables=None, layer=None, lora=None,
                    adapter_ids=None):
    """Full attention sublayer.  Returns (out (B,S,d), new_cache or None).

    mode: "train" | "prefill" | "decode".
    cache (decode): dict(k=(B,Smax,KV,hd), v=...); ``lengths`` (B,) counts
    valid entries *including* the token being decoded.  With
    ``block_tables`` (B, max_blocks), cache leaves are instead the paged
    pool's flat (num_blocks, block_size, KV·hd) leaves — stacked
    (layers, ...) and read and written at ``layer`` when the layer scan
    carries the whole pool — and the new token's KV is scattered into its
    sequence's current block in place.  ``lora`` + ``adapter_ids`` apply
    per-row multi-LoRA shifts to the targeted projections (see
    :func:`lora_shift`); adapter id 0 is the base model.
    """
    B = x.shape[0]
    dt = x.dtype
    if mode in ("train", "prefill"):
        q, k, v = project_qkv(cfg, p, x, positions,
                              lora=lora, adapter_ids=adapter_ids)
        with jax.named_scope("attn"):
            if cfg.attn_impl == "naive":
                o = naive_attention(q, k, v, causal=causal,
                                    kv_valid_len=kv_valid_len)
            else:
                o = blockwise_attention(q, k, v, causal=causal,
                                        kv_valid_len=kv_valid_len)
        o = sharding.constrain(o, ("act_batch", "act_qseq", "act_heads", None))
        new_cache = None
        if mode == "prefill":
            with jax.named_scope("kv_write"):
                new_cache = {"k": k.astype(dt), "v": v.astype(dt)}
    elif block_tables is not None:
        q, k, v = project_qkv(cfg, p, x, positions,
                              lora=lora, adapter_ids=adapter_ids)
        S = q.shape[1]
        blk = cache["k"].shape[-2]
        quant = "k_scale" in cache
        new_cache = dict(cache)
        # scatter the S tail tokens' KV (S > 1 = speculative verify; a
        # tail may straddle a block boundary, so resolve each position's
        # physical block separately — S is a static jit constant).  Inert
        # rows have lengths == 1, so their (clamped-negative) positions
        # resolve to table column 0 == the reserved null block.  An int8
        # pool quantizes at write time (running-max block scale, see
        # :func:`quantized_scatter_token`) and dequantizes on gather (the
        # fused Pallas kernel dequantizes in-register)
        with jax.named_scope("kv_write"):
            for t in range(S):
                idx = lengths - S + t
                pb = jnp.take_along_axis(block_tables,
                                         (idx // blk)[:, None], axis=1)[:, 0]
                off = idx % blk
                for name, x_t in (("k", k[:, t]), ("v", v[:, t])):
                    if quant:
                        new_cache[name], new_cache[name + "_scale"] = (
                            quantized_scatter_token(
                                new_cache[name], new_cache[name + "_scale"],
                                x_t, pb, off, layer))
                    else:
                        leaf = new_cache[name]
                        new_cache[name] = leaf.at[
                            pool_index(layer, pb, off)].set(
                            x_t.reshape(B, -1).astype(leaf.dtype))
            # the block and in-block dims sit in the (act_batch,
            # act_kvseq) slots, which a serving rule set maps to None —
            # so this pins exactly the head axis and the updated pool
            # keeps the input pool's sharding
            for name in new_cache:
                new_cache[name] = sharding.constrain(
                    new_cache[name],
                    pool_axes(layer, "act_batch", "act_heads")
                    if name.endswith("_scale") else
                    pool_axes(layer, "act_batch", "act_kvseq", "act_heads"))
        scales = ((new_cache["k_scale"], new_cache["v_scale"]) if quant
                  else (None, None))
        with jax.named_scope("attn"):
            attend = (paged_decode_attention if S == 1
                      else paged_verify_attention)
            o = attend(q, new_cache["k"], new_cache["v"], block_tables,
                       lengths, layer, *scales)
    else:
        q, k, v = project_qkv(cfg, p, x, positions,
                              lora=lora, adapter_ids=adapter_ids)
        S = q.shape[1]
        idx = lengths - S  # slot of the first (oldest) tail token
        with jax.named_scope("kv_write"):
            k_cache = jax.vmap(
                lambda c, kk, i: jax.lax.dynamic_update_slice(
                    c, kk, (i, 0, 0)))(cache["k"],
                                       k.astype(cache["k"].dtype), idx)
            v_cache = jax.vmap(
                lambda c, vv, i: jax.lax.dynamic_update_slice(
                    c, vv, (i, 0, 0)))(cache["v"],
                                       v.astype(cache["v"].dtype), idx)
            k_cache = sharding.constrain(
                k_cache, ("act_batch", "act_kvseq", "act_heads", None))
            v_cache = sharding.constrain(
                v_cache, ("act_batch", "act_kvseq", "act_heads", None))
        with jax.named_scope("attn"):
            if S == 1:
                o = decode_attention(q, k_cache, v_cache, lengths)
            else:
                o = verify_attention(q, k_cache, v_cache, lengths)
        new_cache = {"k": k_cache, "v": v_cache}
    o2 = o.reshape(B, o.shape[1], -1).astype(dt)
    out = jnp.einsum("bsq,qd->bsd", o2, p["wo"])
    if lora and "wo" in lora:
        out = out + lora_shift(o2, lora["wo"], adapter_ids)
    out = sharding.constrain(out, ("act_batch", "act_qseq", None))
    return out, new_cache


def cross_attention_block(cfg: ModelConfig, p, x, kv_cache):
    """Decoder cross-attention against precomputed encoder K/V."""
    B, S, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(B, S, h, hd)
    if S == 1:
        lengths = jnp.full((B,), kv_cache["k"].shape[1], jnp.int32)
        o = decode_attention(q, kv_cache["k"], kv_cache["v"], lengths)
    else:
        o = blockwise_attention(q, kv_cache["k"], kv_cache["v"], causal=False)
    o = o.reshape(B, S, -1).astype(x.dtype)
    return jnp.einsum("bsq,qd->bsd", o, p["wo"])


def cross_kv(cfg: ModelConfig, p, enc_out):
    """Precompute encoder K/V for cross attention."""
    B, S, _ = enc_out.shape
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    k = jnp.einsum("bsd,dq->bsq", enc_out, p["wk"])
    v = jnp.einsum("bsd,dq->bsq", enc_out, p["wv"])
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    return {"k": k.reshape(B, S, kv, hd), "v": v.reshape(B, S, kv, hd)}

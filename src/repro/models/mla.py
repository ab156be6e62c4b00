"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Train/prefill decompress the 512-d latent into per-head K/V and run
standard attention; decode uses the *absorbed-weight* formulation so the
KV cache stores only (kv_lora_rank + qk_rope_head_dim) floats per token —
the feature that makes 32k-decode caches ~9x smaller than GQA here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import (NEG_INF, blockwise_attention, lora_shift,
                                    naive_attention, pool_axes, pool_index,
                                    quantized_scatter_token)
from repro.models.layers import apply_rope
from repro.models.param import ParamSpec
from repro.parallel import sharding


def mla_specs(cfg: ModelConfig):
    d, h = cfg.d_model, cfg.num_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvl = cfg.kv_lora_rank
    s = {
        "wdkv": ParamSpec((d, kvl + rope), ("fsdp", None), "fan_in"),
        "kv_norm": ParamSpec((kvl,), (None,), "ones"),
        "wuk": ParamSpec((kvl, h * nope), ("fsdp", "tensor"), "fan_in"),
        "wuv": ParamSpec((kvl, h * vd), ("fsdp", "tensor"), "fan_in"),
        "wo": ParamSpec((h * vd, d), ("tensor", "fsdp"), "fan_in"),
    }
    if cfg.q_lora_rank:
        s["wdq"] = ParamSpec((d, cfg.q_lora_rank), ("fsdp", None), "fan_in")
        s["q_norm"] = ParamSpec((cfg.q_lora_rank,), (None,), "ones")
        s["wuq"] = ParamSpec((cfg.q_lora_rank, h * (nope + rope)),
                             ("fsdp", "tensor"), "fan_in")
    else:
        s["wq"] = ParamSpec((d, h * (nope + rope)), ("fsdp", "tensor"),
                            "fan_in")
    return s


def _rmsnorm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _project_q(cfg, p, x, positions, lora=None, adapter_ids=None):
    B, S, _ = x.shape
    h = cfg.num_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        cq = _rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["wdq"]),
                      p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rq->bsq", cq, p["wuq"])
        if lora and "wuq" in lora:
            q = q + lora_shift(cq, lora["wuq"], adapter_ids)
    else:
        q = jnp.einsum("bsd,dq->bsq", x, p["wq"])
        if lora and "wq" in lora:
            q = q + lora_shift(x, lora["wq"], adapter_ids)
    q = q.reshape(B, S, h, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = apply_rope(q_pe, positions, cfg, rope)
    return q_nope, q_pe


def _latent_kv(cfg, p, x, positions):
    """Compressed c_kv (B,S,kvl) + rope key k_pe (B,S,rope)."""
    kvl, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dkv = jnp.einsum("bsd,dr->bsr", x, p["wdkv"])
    c_kv = _rmsnorm(dkv[..., :kvl], p["kv_norm"], cfg.norm_eps)
    k_pe = dkv[..., kvl:][:, :, None, :]  # (B,S,1,rope)
    k_pe = apply_rope(k_pe, positions, cfg, rope)[:, :, 0, :]
    c_kv = sharding.constrain(c_kv, ("act_batch", "act_kvseq", None))
    k_pe = sharding.constrain(k_pe, ("act_batch", "act_kvseq", None))
    return c_kv, k_pe


def mla_block(cfg: ModelConfig, p, x, positions, *, mode: str,
              cache=None, lengths=None, block_tables=None, layer=None,
              lora=None, adapter_ids=None):
    """Returns (out, new_cache).  cache: {"ckv": (B,Smax,kvl),
    "kpe": (B,Smax,rope)} — or, with ``block_tables`` (B, max_blocks),
    pool-shaped {"ckv": (num_blocks, block_size, kvl), ...}, stacked
    (layers, ...) and indexed at ``layer`` when the layer scan carries
    the whole pool, with the new latent scattered into the sequence's
    current block in place.

    ``lora`` + ``adapter_ids`` add per-row multi-LoRA shifts.  Train/
    prefill applies them to the decompressed projections directly; decode
    folds them into the *absorbed-weight* formulation: a ``wuk`` adapter
    shifts the latent query (``q_lat += (q_nope @ B_k^T) @ A_k^T``) and a
    ``wuv`` adapter shifts the output (``o += (ctx @ A_v) @ B_v``), which
    is algebraically identical to decoding with merged weights.
    """
    B, S, _ = x.shape
    h = cfg.num_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvl = cfg.kv_lora_rank
    dt = x.dtype
    scale_dim = nope + rope

    q_nope, q_pe = _project_q(cfg, p, x, positions, lora, adapter_ids)
    c_kv, k_pe = _latent_kv(cfg, p, x, positions)

    if mode in ("train", "prefill"):
        # Decompress and run standard MHA (G=1) with concatenated heads.
        k_nope = jnp.einsum("bsr,rq->bsq", c_kv, p["wuk"])
        v = jnp.einsum("bsr,rq->bsq", c_kv, p["wuv"])
        if lora and "wuk" in lora:
            k_nope = k_nope + lora_shift(c_kv, lora["wuk"], adapter_ids)
        if lora and "wuv" in lora:
            v = v + lora_shift(c_kv, lora["wuv"], adapter_ids)
        k_nope = k_nope.reshape(B, S, h, nope)
        v = v.reshape(B, S, h, vd)
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, S, h, rope))],
            -1)
        # pad v to qk head size so one attention call handles both
        vpad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, scale_dim - vd)))
        attn = (blockwise_attention if cfg.attn_impl == "blockwise"
                else naive_attention)
        o = attn(q, k, vpad, causal=True)[..., :vd]
        o = o.reshape(B, S, h * vd).astype(dt)
        out = jnp.einsum("bsq,qd->bsd", o, p["wo"])
        if lora and "wo" in lora:
            out = out + lora_shift(o, lora["wo"], adapter_ids)
        new_cache = None
        if mode == "prefill":
            new_cache = {"ckv": c_kv.astype(dt), "kpe": k_pe.astype(dt)}
        return out, new_cache

    # ---- decode: absorbed-weight attention in latent space ----
    # S == 1 is the decode micro-step; S > 1 is the speculative verify
    # tail (the S newest tokens, written then causally attended — each
    # query t sits at position ``lengths - S + t``).
    if block_tables is not None:
        # paged latent pool: (…, num_blocks, block_size, kvl|rope) leaves,
        # stacked ones read and written at ``layer``.  A multi-token tail
        # may straddle a block boundary: resolve each position's physical
        # block separately (S is a static constant).  An int8 pool's
        # leaves have no head axis, so each block carries one scalar f32
        # scale (running-max, requant-on-widen — same write discipline as
        # the GQA int8 pool)
        blk = cache["ckv"].shape[-2]
        quant = "ckv_scale" in cache
        new_cache = dict(cache)
        for t in range(S):
            idx = lengths - S + t
            pb = jnp.take_along_axis(block_tables, (idx // blk)[:, None],
                                     axis=1)[:, 0]
            off = idx % blk
            for name, x_t in (("ckv", c_kv[:, t]), ("kpe", k_pe[:, t])):
                if quant:
                    new_cache[name], new_cache[name + "_scale"] = (
                        quantized_scatter_token(
                            new_cache[name], new_cache[name + "_scale"],
                            x_t, pb, off, layer))
                else:
                    leaf = new_cache[name]
                    new_cache[name] = leaf.at[
                        pool_index(layer, pb, off)].set(
                        x_t.astype(leaf.dtype))
        # latent pool leaves have no head axis — under serving_tp these
        # resolve to fully-replicated specs (the MLA cache is small
        # enough to replicate; scores still shard on act_heads below)
        for name in new_cache:
            new_cache[name] = sharding.constrain(
                new_cache[name],
                pool_axes(layer, "act_batch") if name.endswith("_scale")
                else pool_axes(layer, "act_batch", "act_kvseq", None))

        # gather (and dequantize) each sequence's blocks into logical
        # order (jnp oracle; a paged-MLA Pallas kernel would walk the
        # table in SMEM instead)
        def gather(name):
            rows = new_cache[name][pool_index(layer, block_tables)]
            if quant:
                rows = rows.astype(jnp.float32) * new_cache[
                    name + "_scale"][pool_index(layer, block_tables)][
                    :, :, None, None]
            return rows.reshape(B, -1, rows.shape[-1])

        ckv_c, kpe_c = gather("ckv"), gather("kpe")
    else:
        idx = lengths - S
        ckv_c = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u, (i, 0)))(cache["ckv"], c_kv.astype(cache["ckv"].dtype), idx)
        kpe_c = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
            c, u, (i, 0)))(cache["kpe"], k_pe.astype(cache["kpe"].dtype), idx)
        ckv_c = sharding.constrain(ckv_c, ("act_batch", "act_kvseq", None))
        kpe_c = sharding.constrain(kpe_c, ("act_batch", "act_kvseq", None))
        new_cache = {"ckv": ckv_c, "kpe": kpe_c}

    if S > 1:
        return _mla_verify(cfg, p, x, q_nope, q_pe, ckv_c, kpe_c, lengths,
                           lora, adapter_ids), new_cache

    wuk = p["wuk"].reshape(kvl, h, nope)
    # absorb W_UK into q:  q_lat (B,h,kvl); cache operands stay bf16 with
    # f32 accumulation (no full-cache f32 copies)
    q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], wuk,
                       preferred_element_type=jnp.float32)
    if lora and "wuk" in lora:
        # absorbed wuk adapter: contract the per-row B then A factor so
        # the (kvl, h*nope) weight delta is never materialized
        bk = jnp.take(lora["wuk"]["b"], adapter_ids, axis=0).reshape(
            B, -1, h, nope).astype(jnp.float32)
        ak = jnp.take(lora["wuk"]["a"], adapter_ids, axis=0).astype(
            jnp.float32)
        t = jnp.einsum("bhn,brhn->bhr", q_nope[:, 0].astype(jnp.float32),
                       bk)
        q_lat = q_lat + jnp.einsum("bhr,bkr->bhk", t, ak)
    s_lat = jnp.einsum("bhr,bsr->bhs", q_lat.astype(ckv_c.dtype), ckv_c,
                       preferred_element_type=jnp.float32)
    s_pe = jnp.einsum("bhp,bsp->bhs", q_pe[:, 0].astype(kpe_c.dtype),
                      kpe_c, preferred_element_type=jnp.float32)
    s = (s_lat + s_pe) / jnp.sqrt(scale_dim)
    s = sharding.constrain(s, ("act_batch", "act_heads", "act_kvseq"))
    Smax = ckv_c.shape[1]
    valid = jnp.arange(Smax)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", pr.astype(ckv_c.dtype), ckv_c,
                     preferred_element_type=jnp.float32)
    wuv = p["wuv"].reshape(kvl, h, vd)
    o = jnp.einsum("bhr,rhv->bhv", ctx.astype(wuv.dtype), wuv,
                   preferred_element_type=jnp.float32)
    if lora and "wuv" in lora:
        av = jnp.take(lora["wuv"]["a"], adapter_ids, axis=0).astype(
            jnp.float32)
        bv = jnp.take(lora["wuv"]["b"], adapter_ids, axis=0).reshape(
            B, -1, h, vd).astype(jnp.float32)
        t = jnp.einsum("bhk,bkr->bhr", ctx.astype(jnp.float32), av)
        o = o + jnp.einsum("bhr,brhv->bhv", t, bv)
    o = o.reshape(B, 1, h * vd).astype(dt)
    out = jnp.einsum("bsq,qd->bsd", o, p["wo"])
    if lora and "wo" in lora:
        out = out + lora_shift(o, lora["wo"], adapter_ids)
    return out, new_cache


def _mla_verify(cfg: ModelConfig, p, x, q_nope, q_pe, ckv_c, kpe_c,
                lengths, lora, adapter_ids):
    """Absorbed-weight attention for an S-token speculative tail.

    Same math as the S == 1 decode path with a query axis added: query t
    of row b sits at position ``lengths[b] - S + t`` and attends causally
    through the (already updated) latent cache.  Multi-LoRA shifts fold
    into the absorbed ``wuk``/``wuv`` contractions exactly as in decode.
    """
    B, S, _ = x.shape
    h = cfg.num_heads
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    kvl = cfg.kv_lora_rank
    dt = x.dtype
    scale_dim = nope + rope

    wuk = p["wuk"].reshape(kvl, h, nope)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wuk,
                       preferred_element_type=jnp.float32)
    if lora and "wuk" in lora:
        bk = jnp.take(lora["wuk"]["b"], adapter_ids, axis=0).reshape(
            B, -1, h, nope).astype(jnp.float32)
        ak = jnp.take(lora["wuk"]["a"], adapter_ids, axis=0).astype(
            jnp.float32)
        t = jnp.einsum("bshn,brhn->bshr", q_nope.astype(jnp.float32), bk)
        q_lat = q_lat + jnp.einsum("bshr,bkr->bshk", t, ak)
    s_lat = jnp.einsum("bshr,bmr->bhsm", q_lat.astype(ckv_c.dtype), ckv_c,
                       preferred_element_type=jnp.float32)
    s_pe = jnp.einsum("bshp,bmp->bhsm", q_pe.astype(kpe_c.dtype), kpe_c,
                      preferred_element_type=jnp.float32)
    s = (s_lat + s_pe) / jnp.sqrt(scale_dim)
    Smax = ckv_c.shape[1]
    qpos = lengths[:, None] - S + jnp.arange(S)[None, :]          # (B,S)
    valid = jnp.arange(Smax)[None, None, :] <= qpos[:, :, None]   # (B,S,M)
    s = jnp.where(valid[:, None], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhsm,bmr->bshr", pr.astype(ckv_c.dtype), ckv_c,
                     preferred_element_type=jnp.float32)
    wuv = p["wuv"].reshape(kvl, h, vd)
    o = jnp.einsum("bshr,rhv->bshv", ctx.astype(wuv.dtype), wuv,
                   preferred_element_type=jnp.float32)
    if lora and "wuv" in lora:
        av = jnp.take(lora["wuv"]["a"], adapter_ids, axis=0).astype(
            jnp.float32)
        bv = jnp.take(lora["wuv"]["b"], adapter_ids, axis=0).reshape(
            B, -1, h, vd).astype(jnp.float32)
        t = jnp.einsum("bshk,bkr->bshr", ctx.astype(jnp.float32), av)
        o = o + jnp.einsum("bshr,brhv->bshv", t, bv)
    o = o.reshape(B, S, h * vd).astype(dt)
    out = jnp.einsum("bsq,qd->bsd", o, p["wo"])
    if lora and "wo" in lora:
        out = out + lora_shift(o, lora["wo"], adapter_ids)
    return out

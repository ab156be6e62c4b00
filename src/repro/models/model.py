"""Composable LM covering all assigned architectures.

Pure-functional API:
  model_specs(cfg)          -> ParamSpec tree (shapes + logical axes)
  init(cfg, key, dtype)     -> params
  train_loss(cfg, params, batch)            -> (loss, metrics)
  prefill(cfg, params, batch)               -> (logits_last, cache, aux)
  decode_step(cfg, params, tokens, cache, lengths) -> (logits, cache)
  make_cache(cfg, batch, capacity, ...)     -> cache pytree (zeros/abstract)
  input_specs(cfg, shape)   -> ShapeDtypeStruct stand-ins for the dry-run

Layer stacks run under ``lax.scan`` over stacked parameters (compact HLO —
mandatory for compiling 80+ dry-run cells on one CPU core); Jamba scans
over period-8 super-blocks.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.shapes import ShapeSpec, WHISPER_ENCODER_FRAMES
from repro.models import moe as moe_mod
from repro.models.blocks import apply_layer, dec_layer_specs, layer_specs
from repro.models.layers import (apply_norm, embed_tokens, embedding_specs,
                                 norm_specs, unembed)
from repro.models.param import (ParamSpec, abstract_params, init_params,
                                param_axes, stack_specs)
from repro.parallel import sharding

# --------------------------------------------------------------- plans
JAMBA_FFN = ("mlp", "moe")  # even positions dense, odd positions MoE


def _ep_degree(multi_pod_hint: int = 16) -> int:
    """Experts are padded to a multiple of 16 at spec time; every divisor
    of 16 is then a valid EP degree (the 16-way production "model" axis
    and the 2/4/8-way test meshes alike)."""
    return 16


def stack_plan(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.is_encoder_decoder:
        return {"kind": "encdec"}
    if cfg.block_period > 1:
        return {"kind": "hybrid",
                "groups": cfg.num_layers // cfg.block_period}
    mixer = {"gqa": "gqa", "mla": "mla", "none": "mamba"}[cfg.attention]
    ffn = "none" if cfg.family == "ssm" else (
        "moe" if cfg.has_moe else "mlp")
    first = []
    n = cfg.num_layers
    if cfg.has_moe and cfg.first_k_dense:
        first = [(mixer, "mlp")] * cfg.first_k_dense
        n -= cfg.first_k_dense
    return {"kind": "uniform", "mixer": mixer, "ffn": ffn,
            "first": first, "n": n}


def _E_pad(cfg: ModelConfig) -> int:
    return moe_mod.padded_experts(cfg, _ep_degree())


def model_specs(cfg: ModelConfig):
    plan = stack_plan(cfg)
    s: Dict[str, Any] = {"embed": embedding_specs(cfg),
                         "ln_f": norm_specs(cfg)}
    if plan["kind"] == "uniform":
        if plan["first"]:
            s["first"] = [layer_specs(cfg, m, f, _E_pad(cfg))
                          for m, f in plan["first"]]
        s["stack"] = stack_specs(
            layer_specs(cfg, plan["mixer"], plan["ffn"], _E_pad(cfg)),
            plan["n"])
    elif plan["kind"] == "hybrid":
        sub = {}
        for i in range(cfg.block_period):
            mixer = "gqa" if i in cfg.attn_positions else "mamba"
            ffn = JAMBA_FFN[i % cfg.moe_layer_period == cfg.moe_layer_offset]
            sub[f"sub{i}"] = layer_specs(cfg, mixer, ffn, _E_pad(cfg))
        s["stack"] = stack_specs(sub, plan["groups"])
    else:  # encdec
        s["enc_stack"] = stack_specs(
            layer_specs(cfg, "gqa", "mlp"), cfg.num_encoder_layers)
        s["ln_enc"] = norm_specs(cfg)
        s["dec_stack"] = stack_specs(dec_layer_specs(cfg),
                                     cfg.num_layers)
    return s


def init(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32):
    return init_params(model_specs(cfg), key, dtype)


def model_param_axes(cfg: ModelConfig):
    return param_axes(model_specs(cfg))


# --------------------------------------------------------------- caches
def _layer_cache_struct(cfg: ModelConfig, mixer: str, B: int, cap: int,
                        dtype):
    if mixer == "gqa":
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        return {"k": ((B, cap, kv, hd), dtype),
                "v": ((B, cap, kv, hd), dtype)}
    if mixer == "mla":
        return {"ckv": ((B, cap, cfg.kv_lora_rank), dtype),
                "kpe": ((B, cap, cfg.qk_rope_head_dim), dtype)}
    if mixer == "mamba":
        convdim = cfg.ssm_d_inner + 2 * cfg.ssm_state
        return {"conv": ((B, cfg.ssm_conv_width - 1, convdim), dtype),
                "ssd": ((B, cfg.ssm_heads, cfg.ssm_head_dim,
                         cfg.ssm_state), jnp.float32)}
    raise ValueError(mixer)


def _cache_axes_one(cfg: ModelConfig, mixer: str):
    if mixer == "gqa":
        ax = ("act_batch", "act_kvseq", "act_heads", None)
        return {"k": ax, "v": ax}
    if mixer == "mla":
        return {"ckv": ("act_batch", "act_kvseq", None),
                "kpe": ("act_batch", "act_kvseq", None)}
    if mixer == "mamba":
        return {"conv": ("act_batch", None, "act_ff"),
                "ssd": ("act_batch", "act_ssm_heads", None, None)}
    raise ValueError(mixer)


def _materialize(tree, abstract: bool):
    def one(leaf):
        shape, dt = leaf
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.zeros(shape, dt)
    return jax.tree.map(one, tree,
                        is_leaf=lambda x: isinstance(x, tuple)
                        and len(x) == 2 and isinstance(x[0], tuple))


def _stackc(tree, n):
    return jax.tree.map(
        lambda leaf: ((n,) + leaf[0], leaf[1]), tree,
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))


def cache_struct(cfg: ModelConfig, B: int, cap: int, dtype=jnp.bfloat16):
    plan = stack_plan(cfg)
    if plan["kind"] == "uniform":
        c: Dict[str, Any] = {}
        if plan["first"]:
            c["first"] = [_layer_cache_struct(cfg, m, B, cap, dtype)
                          for m, _ in plan["first"]]
        c["stack"] = _stackc(
            _layer_cache_struct(cfg, plan["mixer"], B, cap, dtype),
            plan["n"])
        return c
    if plan["kind"] == "hybrid":
        sub = {}
        for i in range(cfg.block_period):
            mixer = "gqa" if i in cfg.attn_positions else "mamba"
            sub[f"sub{i}"] = _layer_cache_struct(cfg, mixer, B, cap, dtype)
        return {"stack": _stackc(sub, plan["groups"])}
    # encdec: decoder self cache + cross kv cache
    enc_len = min(WHISPER_ENCODER_FRAMES, cap)
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "dec": _stackc(_layer_cache_struct(cfg, "gqa", B, cap, dtype),
                       cfg.num_layers),
        "cross": _stackc({"k": ((B, enc_len, kv, hd), dtype),
                          "v": ((B, enc_len, kv, hd), dtype)},
                         cfg.num_layers),
    }


def cache_axes(cfg: ModelConfig):
    plan = stack_plan(cfg)
    pre = ("layers",)
    if plan["kind"] == "uniform":
        ax1 = _cache_axes_one(cfg, plan["mixer"])
        c: Dict[str, Any] = {"stack": jax.tree.map(
            lambda a: pre + a, ax1,
            is_leaf=lambda x: isinstance(x, tuple))}
        if plan["first"]:
            c["first"] = [_cache_axes_one(cfg, m) for m, _ in plan["first"]]
        return c
    if plan["kind"] == "hybrid":
        sub = {}
        for i in range(cfg.block_period):
            mixer = "gqa" if i in cfg.attn_positions else "mamba"
            sub[f"sub{i}"] = jax.tree.map(
                lambda a: pre + a, _cache_axes_one(cfg, mixer),
                is_leaf=lambda x: isinstance(x, tuple))
        return {"stack": sub}
    ax = pre + ("act_batch", "act_kvseq", "act_heads", None)
    return {"dec": {"k": ax, "v": ax}, "cross": {"k": ax, "v": ax}}


def make_cache(cfg: ModelConfig, B: int, capacity: int,
               dtype=jnp.bfloat16, abstract: bool = False):
    return _materialize(cache_struct(cfg, B, capacity, dtype), abstract)


def supports_paged_cache(cfg: ModelConfig) -> bool:
    """True iff decode can run over a paged block pool: every cache leaf
    carries adjacent (act_batch, act_kvseq) axes — pure-attention GQA/MLA
    stacks.  SSM/hybrid state caches have no per-position KV; encoder-
    decoder carries a fixed cross cache; vision-prefixed models key their
    cache on non-token inputs.  All of those keep the dense per-slot path.
    """
    if getattr(cfg, "is_encoder_decoder", False):
        return False
    if getattr(cfg, "frontend", "text") == "vision":
        return False
    if stack_plan(cfg)["kind"] != "uniform":
        return False
    leaves = jax.tree.leaves(cache_axes(cfg),
                             is_leaf=lambda x: isinstance(x, tuple))
    for ax in leaves:
        if "act_kvseq" not in ax or "act_batch" not in ax:
            return False
        if ax.index("act_kvseq") != ax.index("act_batch") + 1:
            return False
    return True


def _layer_pool_struct(cfg: ModelConfig, mixer: str, nb: int, bs: int,
                       dtype, quantized: bool):
    """One layer of a paged pool: ``(num_blocks, block_size, ...)`` leaves.
    A GQA leaf stores a position's heads flat, ``(nb, bs, KV·head_dim)``:
    the head count is no tile's minor dimension, so no head is padded and
    the stacked pool can be updated in place (see :func:`_scan_stack`).
    An int8 pool adds f32 ``<name>_scale`` siblings (symmetric, per block,
    and per KV head where the leaf carries heads; MLA's latent leaves get
    one scalar scale per block)."""
    out: Dict[str, Any] = {}
    for name, (shape, dt) in _layer_cache_struct(cfg, mixer, nb, bs,
                                                 dtype).items():
        if mixer == "gqa":
            shape = (nb, bs, cfg.num_kv_heads * cfg.head_dim)
        out[name] = (shape, dt)
        if quantized:
            out[name + "_scale"] = (
                ((nb, cfg.num_kv_heads) if mixer == "gqa" else (nb,)),
                jnp.float32)
    return out


def _pool_axes_one(cfg: ModelConfig, mixer: str, quantized: bool):
    out: Dict[str, Any] = {}
    for name, a in _cache_axes_one(cfg, mixer).items():
        # the flat GQA leaf keeps "act_heads" on its last axis: under TP
        # it shards in contiguous whole-head chunks when KV % tp == 0
        out[name] = a[:3] if mixer == "gqa" else a
        if quantized:
            # scales shard with the KV-head axis under TP (or replicate
            # when the leaf has no head axis, e.g. MLA latents)
            out[name + "_scale"] = (("act_batch", "act_heads")
                                    if "act_heads" in a else ("act_batch",))
    return out


def _pool_tree(cfg: ModelConfig, layer, stack):
    """``{"stack": stacked layers, "first": [lone layers]}`` of a paged
    pool, from ``layer(mixer)`` and ``stack(tree, n)``."""
    plan = stack_plan(cfg)
    c: Dict[str, Any] = {"stack": stack(layer(plan["mixer"]), plan["n"])}
    if plan["first"]:
        c["first"] = [layer(m) for m, _ in plan["first"]]
    return c


def make_paged_pool(cfg: ModelConfig, num_blocks: int, block_size: int,
                    dtype=jnp.bfloat16, abstract: bool = False,
                    kv_dtype: str = "bf16"):
    """Shared physical KV pool for paged decode.

    Each layer leaf is ``(num_blocks, block_size, ...)``: one row per
    physical block, which block tables map (sequence, logical block) to.
    The scanned layers' leaves are stacked ``(layers, num_blocks, ...)``;
    a GQA position is stored flat, ``(…, block_size, KV·head_dim)``.
    Every layer leaf indexes rows identically, so one block id spans the
    whole stack and allocation is accounted in token blocks, not
    per-layer bytes.  Decode carries the stacked pool through the layer
    scan and updates it in place.  ``kv_dtype="int8"`` stores int8 block
    data with f32 per-block scale leaves inside each layer dict, so scan
    threading, donation, export/import and byte accounting treat scales
    as ordinary pool leaves.
    """
    if not supports_paged_cache(cfg):
        raise ValueError("architecture has no position-sliceable KV cache")
    q = kv_dtype == "int8"
    dt = jnp.int8 if q else dtype
    return _materialize(_pool_tree(
        cfg, lambda m: _layer_pool_struct(cfg, m, num_blocks, block_size,
                                          dt, q),
        _stackc), abstract)


def paged_pool_axes(cfg: ModelConfig, kv_dtype: str = "bf16"):
    """Logical sharding axes for a paged pool: the GQA leaves' three
    ``("act_batch", "act_kvseq", "act_heads")`` after ``"layers"`` on the
    stack; ``int8`` pools add the ``*_scale`` leaves."""
    q = kv_dtype == "int8"
    return _pool_tree(
        cfg, lambda m: _pool_axes_one(cfg, m, q),
        lambda t, n: jax.tree.map(lambda a: ("layers",) + a, t,
                                  is_leaf=lambda x: isinstance(x, tuple)))


def pad_cache(cfg: ModelConfig, cache, capacity: int):
    """Pad the KV-sequence dim of every cache entry up to ``capacity``
    (prefill returns caches sized to the prompt; the engine/serve loop
    re-pads them to generation capacity)."""
    axes = cache_axes(cfg)

    def one(arr, ax):
        if "act_kvseq" not in ax:
            return arr
        i = ax.index("act_kvseq")
        if arr.shape[i] >= capacity:
            return arr
        pad = [(0, 0)] * arr.ndim
        pad[i] = (0, capacity - arr.shape[i])
        return jnp.pad(arr, pad)

    # cross-attention caches keep their (fixed) encoder length
    def walk(c, a, path=()):
        if isinstance(c, dict):
            return {k: walk(c[k], a[k], path + (k,)) for k in c}
        if isinstance(c, list):
            return [walk(x, y, path) for x, y in zip(c, a)]
        if path and path[0] == "cross":
            return c
        return one(c, a)

    return walk(cache, axes)


# --------------------------------------------------------------- stacks
def _maybe_remat(cfg, fn, mode):
    if cfg.remat != "none" and mode == "train":
        return jax.checkpoint(fn)
    return fn


def _scan_stack(cfg: ModelConfig, stack_p, x, positions, *, mixer, ffn,
                mode, cache=None, lengths=None, causal=True, enc_out=None,
                cross_cache=None, block_tables=None, lora=None,
                adapter_ids=None):
    """Scan a homogeneous stacked layer group.  ``lora`` leaves carry the
    same leading layer axis as the stacked params, so the scan slices a
    per-layer adapter stack alongside each layer's weights.

    A paged pool (``block_tables`` given) rides in the carry, whole and
    stacked, and each layer reads and writes it at its own layer index:
    the step updates the donated pool in place.  As scan ``xs``/``ys`` it
    would be sliced per layer and written back into a second stacked
    pool, which then has to be copied into the donated buffer."""
    paged = cache is not None and block_tables is not None
    xs: Dict[str, Any] = {"p": stack_p}
    if paged:
        xs["layer"] = jnp.arange(jax.tree.leaves(stack_p)[0].shape[0])
    elif cache is not None:
        xs["cache"] = cache
    if cross_cache is not None:
        xs["cross"] = cross_cache
    if lora is not None:
        xs["lora"] = lora

    def body(carry, layer_in):
        h, aux, pool = carry
        h, nc, ncross, a = apply_layer(
            cfg, layer_in["p"], h, positions, mixer=mixer, ffn=ffn,
            mode=mode, cache=pool if paged else layer_in.get("cache"),
            lengths=lengths, causal=causal, enc_out=enc_out,
            cross_cache=layer_in.get("cross"), block_tables=block_tables,
            layer=layer_in.get("layer"), lora=layer_in.get("lora"),
            adapter_ids=adapter_ids)
        ys = {}
        if nc is not None and not paged:
            ys["cache"] = nc
        if ncross is not None:
            ys["cross"] = ncross
        return (h, aux + a, nc if paged else None), ys

    body = _maybe_remat(cfg, body, mode)
    (x, aux, pool), ys = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), cache if paged else None), xs)
    if paged:
        ys["cache"] = pool
    return x, aux, ys


def _scan_hybrid(cfg: ModelConfig, stack_p, x, positions, *, mode,
                 cache=None, lengths=None):
    xs: Dict[str, Any] = {"p": stack_p}
    if cache is not None:
        xs["cache"] = cache

    def body(carry, blk):
        h, aux = carry
        ys_cache = {}
        for i in range(cfg.block_period):
            key = f"sub{i}"
            mixer = "gqa" if i in cfg.attn_positions else "mamba"
            ffn = JAMBA_FFN[i % cfg.moe_layer_period == cfg.moe_layer_offset]
            cl = blk["cache"][key] if "cache" in blk else None
            h, nc, _, a = apply_layer(
                cfg, blk["p"][key], h, positions, mixer=mixer, ffn=ffn,
                mode=mode, cache=cl, lengths=lengths)
            aux = aux + a
            if nc is not None:
                ys_cache[key] = nc
        return (h, aux), {"cache": ys_cache} if ys_cache else {}

    body = _maybe_remat(cfg, body, mode)
    (x, aux), ys = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux, ys


# --------------------------------------------------------------- inputs
def _embed_lm(cfg: ModelConfig, params, batch):
    """Token (+frontend) embedding for train/prefill.  Returns (x, positions)."""
    tokens = batch["tokens"]
    B = tokens.shape[0]
    parts = []
    if cfg.frontend == "vision":
        ve = batch["vision_embeds"].astype(tokens_dtype(params))
        parts.append(jnp.einsum(
            "btf,fd->btd", ve, params["embed"]["frontend_proj"]))
    S_txt = tokens.shape[1]
    positions = None
    S_total = S_txt + (parts[0].shape[1] if parts else 0)
    pos = jnp.arange(S_total)[None, :].repeat(B, 0)
    tok_pos = pos[:, S_total - S_txt:]
    parts.append(embed_tokens(cfg, params["embed"], tokens, tok_pos))
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    return x, pos


def tokens_dtype(params):
    return params["embed"]["embed"].dtype


# --------------------------------------------------------------- forward
def _backbone(cfg: ModelConfig, params, x, positions, *, mode,
              cache=None, lengths=None, enc_out=None, block_tables=None,
              lora=None, adapter_ids=None):
    """Run all decoder layers.  Returns (hidden, aux, new_cache).

    ``lora`` is a stacked multi-LoRA adapter tree mirroring the params
    layout (``{"stack": ..., "first": [...]}``, see
    ``serving.adapters.AdapterPool``) and ``adapter_ids`` (B,) selects
    each row's adapter (0 = base).  Only uniform attention stacks support
    it — the same gating as the paged KV path."""
    plan = stack_plan(cfg)
    new_cache: Dict[str, Any] = {}
    aux = jnp.zeros((), jnp.float32)
    if block_tables is not None and plan["kind"] != "uniform":
        raise ValueError("paged decode requires a uniform attention stack")
    if lora is not None and plan["kind"] != "uniform":
        raise ValueError("multi-LoRA requires a uniform attention stack")

    if plan["kind"] == "uniform":
        if plan["first"]:
            firsts = []
            for i, (m, f) in enumerate(plan["first"]):
                cl = cache["first"][i] if cache is not None else None
                lf = (lora["first"][i]
                      if lora is not None and "first" in lora else None)
                x, nc, _, a = apply_layer(
                    cfg, params["first"][i], x, positions, mixer=m, ffn=f,
                    mode=mode, cache=cl, lengths=lengths,
                    block_tables=block_tables, lora=lf,
                    adapter_ids=adapter_ids)
                aux += a
                firsts.append(nc)
            if firsts and firsts[0] is not None:
                new_cache["first"] = firsts
        x, a, ys = _scan_stack(
            cfg, params["stack"], x, positions, mixer=plan["mixer"],
            ffn=plan["ffn"], mode=mode,
            cache=cache["stack"] if cache is not None else None,
            lengths=lengths, block_tables=block_tables,
            lora=lora.get("stack") if lora is not None else None,
            adapter_ids=adapter_ids)
        aux += a
        if ys and "cache" in ys:
            new_cache["stack"] = ys["cache"]
    elif plan["kind"] == "hybrid":
        x, a, ys = _scan_hybrid(
            cfg, params["stack"], x, positions, mode=mode,
            cache=cache["stack"] if cache is not None else None,
            lengths=lengths)
        aux += a
        if ys and "cache" in ys:
            new_cache["stack"] = ys["cache"]
    else:  # encdec decoder
        dec_cache = cache["dec"] if cache is not None else None
        cross = cache["cross"] if (cache is not None and mode == "decode") \
            else None
        x, a, ys = _scan_stack(
            cfg, params["dec_stack"], x, positions, mixer="gqa", ffn="mlp",
            mode=mode, cache=dec_cache, lengths=lengths, causal=True,
            enc_out=enc_out, cross_cache=cross)
        aux += a
        if ys and "cache" in ys:
            new_cache["dec"] = ys["cache"]
        if ys and "cross" in ys:
            new_cache["cross"] = ys["cross"]
        elif mode == "decode":
            new_cache["cross"] = cache["cross"]  # carry through unchanged

    with jax.named_scope("head"):
        x = apply_norm(cfg, params["ln_f"], x)
    return x, aux, (new_cache or None)


def _encode(cfg: ModelConfig, params, frames):
    """Whisper encoder over stub frame embeddings (B,F,frontend_dim)."""
    x = jnp.einsum("btf,fd->btd", frames.astype(tokens_dtype(params)),
                   params["embed"]["frontend_proj"])
    F = x.shape[1]
    pos = jnp.arange(F)[None, :]
    x = x + jnp.take(params["embed"]["pos_embed"], pos[0], axis=0)[None]
    x = x.astype(tokens_dtype(params))
    x, _, _ = _scan_stack(cfg, params["enc_stack"], x, pos, mixer="gqa",
                          ffn="mlp", mode="train", causal=False)
    return apply_norm(cfg, params["ln_enc"], x)


# --------------------------------------------------------------- losses
def _chunked_ce(cfg: ModelConfig, params, x, targets, mask,
                chunk: int = 512):
    """Cross-entropy with z-loss, scanning over sequence chunks so the
    (B,S,V) logits are never materialized."""
    B, S, d = x.shape
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S
    nc = S // chunk
    xs = (jnp.moveaxis(x.reshape(B, nc, chunk, d), 1, 0),
          jnp.moveaxis(targets.reshape(B, nc, chunk), 1, 0),
          jnp.moveaxis(mask.reshape(B, nc, chunk), 1, 0))

    def body(carry, inp):
        nll_s, z_s, n_s, correct = carry
        xc, tc, mc = inp
        logits = unembed(cfg, params["embed"], xc).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        mcf = mc.astype(jnp.float32)
        nll_s += jnp.sum((lse - ll) * mcf)
        z_s += jnp.sum(jnp.square(lse) * mcf)
        n_s += jnp.sum(mcf)
        correct += jnp.sum((jnp.argmax(logits, -1) == tc) * mcf)
        return (nll_s, z_s, n_s, correct), ()

    body = jax.checkpoint(body)
    zero = jnp.zeros((), jnp.float32)
    (nll, z, n, correct), _ = jax.lax.scan(
        body, (zero, zero, zero, zero), xs)
    return nll, z, n, correct


def train_loss(cfg: ModelConfig, params, batch,
               z_coef: float = 1e-4) -> Tuple[jax.Array, Dict[str, Any]]:
    if cfg.is_encoder_decoder:
        enc_out = _encode(cfg, params, batch["frames"])
        tokens = batch["tokens"]
        pos = jnp.arange(tokens.shape[1])[None, :].repeat(tokens.shape[0], 0)
        x = embed_tokens(cfg, params["embed"], tokens, pos)
        x, aux, _ = _backbone(cfg, params, x, pos, mode="train",
                              enc_out=enc_out)
    else:
        x, pos = _embed_lm(cfg, params, batch)
        x, aux, _ = _backbone(cfg, params, x, pos, mode="train")
    nll, z, n, correct = _chunked_ce(
        cfg, params, x, batch["targets"], batch["mask"])
    n = jnp.maximum(n, 1.0)
    n_moe = max(len(cfg.moe_layer_ids()), 1)
    loss = nll / n + z_coef * z / n + cfg.router_aux_coef * aux / n_moe
    metrics = {"loss": nll / n, "z_loss": z / n, "aux_loss": aux / n_moe,
               "accuracy": correct / n, "tokens": n}
    return loss, metrics


def sequence_logprob(cfg: ModelConfig, params, batch) -> jax.Array:
    """Summed log p(target) per sequence under the mask — used by DPO."""
    if cfg.is_encoder_decoder:
        enc_out = _encode(cfg, params, batch["frames"])
        tokens = batch["tokens"]
        pos = jnp.arange(tokens.shape[1])[None, :].repeat(tokens.shape[0], 0)
        x = embed_tokens(cfg, params["embed"], tokens, pos)
        x, _, _ = _backbone(cfg, params, x, pos, mode="train",
                            enc_out=enc_out)
    else:
        x, pos = _embed_lm(cfg, params, batch)
        x, _, _ = _backbone(cfg, params, x, pos, mode="train")
    B, S, d = x.shape
    chunk = min(512, S)
    if S % chunk:
        chunk = S
    nc = S // chunk
    xs = (jnp.moveaxis(x.reshape(B, nc, chunk, d), 1, 0),
          jnp.moveaxis(batch["targets"].reshape(B, nc, chunk), 1, 0),
          jnp.moveaxis(batch["mask"].reshape(B, nc, chunk), 1, 0))

    def body(acc, inp):
        xc, tc, mc = inp
        logits = unembed(cfg, params["embed"], xc).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum((ll - lse) * mc, axis=1), ()

    body = jax.checkpoint(body)
    acc, _ = jax.lax.scan(body, jnp.zeros((B,), jnp.float32), xs)
    return acc


# --------------------------------------------------------------- serving
def prefill(cfg: ModelConfig, params, batch, *, lora=None,
            adapter_ids=None):
    """Returns (next-token logits (B,V), cache, lengths).

    batch: tokens (B,S) (+ vision_embeds / frames), prompt_lengths (B,).
    Cache entries are sized to S (the engine re-pads to capacity).
    ``lora`` + ``adapter_ids`` (B,) apply per-row multi-LoRA adapters
    (id 0 = base) — see :func:`_backbone`.
    """
    lengths = batch["prompt_lengths"]
    if cfg.is_encoder_decoder:
        enc_out = _encode(cfg, params, batch["frames"])
        tokens = batch["tokens"]
        pos = jnp.arange(tokens.shape[1])[None, :].repeat(tokens.shape[0], 0)
        x = embed_tokens(cfg, params["embed"], tokens, pos)
        x, aux, cache = _backbone(cfg, params, x, pos, mode="prefill",
                                  enc_out=enc_out)
    else:
        x, pos = _embed_lm(cfg, params, batch)
        x, aux, cache = _backbone(cfg, params, x, pos, mode="prefill",
                                  lora=lora, adapter_ids=adapter_ids)
    # next-token logits at the last valid position of each sequence
    with jax.named_scope("head"):
        idx = jnp.clip(lengths - 1, 0, x.shape[1] - 1)
        x_last = jnp.take_along_axis(
            x, idx[:, None, None].astype(jnp.int32).repeat(x.shape[-1], -1),
            axis=1)[:, 0]
        logits = unembed(cfg, params["embed"], x_last).astype(jnp.float32)
    return logits, cache, aux


def decode_step(cfg: ModelConfig, params, tokens, cache, lengths, *,
                lora=None, adapter_ids=None):
    """One decode step.  tokens (B,1) int32; lengths (B,) counts valid
    entries including this token.  Returns (logits (B,V), new_cache).
    ``lora`` + ``adapter_ids`` (B,) select a per-row LoRA adapter (0 =
    base), so one fused step serves a batch mixing tenants."""
    pos = (lengths - 1)[:, None]
    x = embed_tokens(cfg, params["embed"], tokens, pos)
    x, _, new_cache = _backbone(cfg, params, x, pos, mode="decode",
                                cache=cache, lengths=lengths,
                                lora=lora, adapter_ids=adapter_ids)
    with jax.named_scope("head"):
        logits = unembed(cfg, params["embed"], x[:, 0]).astype(jnp.float32)
    return logits, new_cache


def decode_step_paged(cfg: ModelConfig, params, tokens, pool, block_tables,
                      lengths, *, lora=None, adapter_ids=None):
    """One decode step over a paged KV pool (see :func:`make_paged_pool`).

    tokens (B,1) int32; block_tables (B, max_blocks) int32 physical block
    ids; lengths (B,) valid tokens including this one.  The new token's KV
    is scattered into block ``block_tables[b, (len-1) // block_size]`` at
    offset ``(len-1) % block_size``; attention reads through the table.
    ``lora`` + ``adapter_ids`` (B,) select a per-row LoRA adapter (0 =
    base).  Returns (logits (B,V), new_pool).
    """
    pos = (lengths - 1)[:, None]
    x = embed_tokens(cfg, params["embed"], tokens, pos)
    x, _, new_pool = _backbone(cfg, params, x, pos, mode="decode",
                               cache=pool, lengths=lengths,
                               block_tables=block_tables,
                               lora=lora, adapter_ids=adapter_ids)
    with jax.named_scope("head"):
        logits = unembed(cfg, params["embed"], x[:, 0]).astype(jnp.float32)
    return logits, new_pool


def verify_step(cfg: ModelConfig, params, tokens, cache, lengths, *,
                lora=None, adapter_ids=None):
    """Multi-token speculative verify: score a T-token tail in ONE launch.

    tokens (B,T) int32 — ``tokens[:, 0]`` is the last emitted token
    (whose KV is not yet written), ``tokens[:, 1:]`` are drafted
    continuations; lengths (B,) counts valid cache entries *including*
    all T tail tokens, so token t sits at position ``lengths - T + t``.
    Writes KV for all T positions and returns (logits (B,T,V),
    new_cache): ``logits[:, t]`` is the target distribution for the
    token *after* position t — exactly what speculative accept/reject
    compares draft t+1 against (and ``logits[:, -1]`` samples the bonus
    token).  The engine rolls back rejected positions by shrinking
    ``lengths``; stale KV past a row's length is never read (attention
    masks by position) and is overwritten when decoding resumes there.
    For T == 1 this is :func:`decode_step` with a (B,1,V) logit shape.
    """
    T = tokens.shape[1]
    pos = lengths[:, None] - T + jnp.arange(T)[None, :]
    x = embed_tokens(cfg, params["embed"], tokens, pos)
    x, _, new_cache = _backbone(cfg, params, x, pos, mode="decode",
                                cache=cache, lengths=lengths,
                                lora=lora, adapter_ids=adapter_ids)
    with jax.named_scope("head"):
        logits = unembed(cfg, params["embed"], x).astype(jnp.float32)
    return logits, new_cache


def verify_step_paged(cfg: ModelConfig, params, tokens, pool, block_tables,
                      lengths, *, lora=None, adapter_ids=None):
    """:func:`verify_step` over a paged KV pool: each tail token's KV is
    scattered into its sequence's block (``block_tables[b, pos // bs]``
    at offset ``pos % bs`` — a tail may straddle a block boundary) and
    the T queries attend causally through the table.  Returns
    (logits (B,T,V), new_pool)."""
    T = tokens.shape[1]
    pos = lengths[:, None] - T + jnp.arange(T)[None, :]
    x = embed_tokens(cfg, params["embed"], tokens, pos)
    x, _, new_pool = _backbone(cfg, params, x, pos, mode="decode",
                               cache=pool, lengths=lengths,
                               block_tables=block_tables,
                               lora=lora, adapter_ids=adapter_ids)
    with jax.named_scope("head"):
        logits = unembed(cfg, params["embed"], x).astype(jnp.float32)
    return logits, new_pool


def supports_speculative(cfg: ModelConfig) -> bool:
    """True iff the engine can run speculative decoding: rollback of
    rejected tokens requires per-position KV that can simply be
    length-masked and overwritten — the same position-sliceable caches
    the paged path needs (uniform GQA/MLA stacks).  SSM/hybrid recurrent
    state cannot be rolled back without checkpointing it per token;
    encoder-decoder and vision-prefixed models keep the plain engine."""
    return supports_paged_cache(cfg)


# --------------------------------------------------------------- specs
def input_specs(cfg: ModelConfig, shape: ShapeSpec,
                cache_dtype=jnp.bfloat16) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a dry-run cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32

    def tok_batch(S_txt):
        d: Dict[str, Any] = {
            "tokens": jax.ShapeDtypeStruct((B, S_txt), i32)}
        if cfg.frontend == "vision":
            d["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.frontend_tokens, cfg.frontend_dim), jnp.bfloat16)
        if cfg.is_encoder_decoder:
            d["frames"] = jax.ShapeDtypeStruct(
                (B, WHISPER_ENCODER_FRAMES, cfg.frontend_dim), jnp.bfloat16)
        return d

    if shape.kind == "train":
        S_txt = S - cfg.frontend_tokens if cfg.frontend == "vision" else S
        d = tok_batch(S_txt)
        d["targets"] = jax.ShapeDtypeStruct((B, S), i32)
        d["mask"] = jax.ShapeDtypeStruct((B, S), jnp.float32)
        return d
    if shape.kind == "prefill":
        S_txt = S - cfg.frontend_tokens if cfg.frontend == "vision" else S
        d = tok_batch(S_txt)
        d["prompt_lengths"] = jax.ShapeDtypeStruct((B,), i32)
        return d
    # decode
    return {
        "tokens": jax.ShapeDtypeStruct((B, 1), i32),
        "lengths": jax.ShapeDtypeStruct((B,), i32),
        "cache": make_cache(cfg, B, S, cache_dtype, abstract=True),
    }


def input_axes(cfg: ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """Logical axes mirroring input_specs (for in_shardings)."""
    base = {
        "tokens": ("act_batch", None),
        "targets": ("act_batch", None),
        "mask": ("act_batch", None),
        "vision_embeds": ("act_batch", None, None),
        "frames": ("act_batch", None, None),
        "prompt_lengths": ("act_batch",),
        "lengths": ("act_batch",),
    }
    specs = input_specs(cfg, shape)
    out: Dict[str, Any] = {}
    for k in specs:
        if k == "cache":
            out[k] = cache_axes(cfg)
        else:
            out[k] = base[k]
    return out

"""Layer kinds and their param specs / application.

A "layer" is one residual block: (norm → mixer → +res) [→ norm → ffn → +res].
Kinds compose the mixer (gqa attention / MLA / mamba / cross-attn) with the
ffn (dense MLP / MoE / none) to cover every assigned architecture.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_mlp, apply_norm, mlp_specs, norm_specs
from repro.parallel import sharding


def mixer_specs(cfg: ModelConfig, mixer: str):
    if mixer == "gqa":
        return attn_mod.attn_specs(cfg)
    if mixer == "mla":
        return mla_mod.mla_specs(cfg)
    if mixer == "mamba":
        return ssm_mod.mamba_specs(cfg)
    raise ValueError(mixer)


def layer_specs(cfg: ModelConfig, mixer: str, ffn: str,
                num_experts_padded: Optional[int] = None):
    """mixer: gqa|mla|mamba|none ; ffn: mlp|moe|none ; (+cross for enc-dec)."""
    s = {}
    if mixer != "none":
        s["mixer"] = mixer_specs(cfg, mixer)
        s["ln1"] = norm_specs(cfg)
    if ffn == "mlp":
        s["mlp"] = mlp_specs(cfg)
        s["ln2"] = norm_specs(cfg)
    elif ffn == "moe":
        s["moe"] = moe_mod.moe_specs(cfg, num_experts_padded)
        s["ln2"] = norm_specs(cfg)
    return s


def dec_layer_specs(cfg: ModelConfig):
    """Whisper decoder layer: self-attn + cross-attn + mlp."""
    return {
        "mixer": attn_mod.attn_specs(cfg),
        "ln1": norm_specs(cfg),
        "cross": attn_mod.attn_specs(cfg),
        "ln_cross": norm_specs(cfg),
        "mlp": mlp_specs(cfg),
        "ln2": norm_specs(cfg),
    }


def _gather_fsdp(p, specs):
    """ZeRO-3 weight gather: constrain each param with its 'fsdp' axis
    dropped, so SPMD all-gathers the (small) weight shards over "data"
    instead of batch-gathering the (huge) activations.  No-op outside an
    active mesh or when fsdp is unmapped (decode rules)."""
    from repro.models.param import ParamSpec

    def walk(pp, ss):
        if isinstance(ss, ParamSpec):
            if "fsdp" not in ss.axes:
                return pp
            return sharding.constrain(
                pp, tuple(None if a == "fsdp" else a for a in ss.axes))
        return {k: walk(pp[k], ss[k]) for k in pp}

    return walk(p, specs)


def apply_layer(cfg: ModelConfig, p, x, positions, *, mixer: str, ffn: str,
                mode: str, cache=None, lengths=None, causal: bool = True,
                enc_out=None, cross_cache=None, block_tables=None,
                layer=None, lora=None, adapter_ids=None):
    """Returns (x, new_cache, new_cross_cache, aux).  ``block_tables``
    switches attention mixers to the paged-pool decode path (SSM mixers
    have no per-position KV and never see it); ``layer`` then indexes a
    stacked pool, and is None for a lone layer's pool.  ``lora`` is this
    layer's slice of the stacked multi-LoRA adapter tree (``{"mixer":
    {target: {"a", "b"}}}``); with per-row ``adapter_ids`` the attention
    mixers add each row's adapter shift (see ``attention.lora_shift``)."""
    if sharding.active() is not None:
        E_pad = p["moe"]["w_gate"].shape[0] if ffn == "moe" else None
        spec_tree = (dec_layer_specs(cfg) if "cross" in p
                     else layer_specs(cfg, mixer, ffn, E_pad))
        # EP expert weights keep their fsdp sharding (gathered at the
        # shard_map boundary); everything else is explicitly ZeRO-gathered
        skip = {"w_gate", "w_up", "w_down", "router"}
        if ffn == "moe":
            moe_p, moe_s = p["moe"], spec_tree["moe"]
            gathered_moe = dict(
                {k: moe_p[k] for k in moe_p if k in skip},
                **_gather_fsdp({k: moe_p[k] for k in moe_p
                                if k not in skip},
                               {k: moe_s[k] for k in moe_s
                                if k not in skip}))
            p = dict(_gather_fsdp(
                {k: v for k, v in p.items() if k != "moe"},
                {k: v for k, v in spec_tree.items() if k != "moe"}),
                moe=gathered_moe)
        else:
            p = _gather_fsdp(p, spec_tree)
    aux = jnp.zeros((), jnp.float32)
    new_cache = None
    new_cross = None
    # Megatron-style sequence parallelism: the residual stream is sharded
    # over ("act_batch", "act_qseq") so per-layer remat residuals stay small;
    # XLA inserts the AG/RS pairs around TP matmuls automatically.
    x = sharding.constrain(x, ("act_batch", "act_qseq", None))

    lmix = lora.get("mixer") if lora else None
    if mixer != "none":
        h = apply_norm(cfg, p["ln1"], x)
        if mixer == "gqa":
            o, new_cache = attn_mod.attention_block(
                cfg, p["mixer"], h, positions, mode=mode, cache=cache,
                lengths=lengths, causal=causal, block_tables=block_tables,
                layer=layer, lora=lmix, adapter_ids=adapter_ids)
        elif mixer == "mla":
            o, new_cache = mla_mod.mla_block(
                cfg, p["mixer"], h, positions, mode=mode, cache=cache,
                lengths=lengths, block_tables=block_tables, layer=layer,
                lora=lmix, adapter_ids=adapter_ids)
        elif mixer == "mamba":
            o, new_cache = ssm_mod.mamba_block(
                cfg, p["mixer"], h, mode=mode, cache=cache)
        x = x + o

    if "cross" in p:
        h = apply_norm(cfg, p["ln_cross"], x)
        if mode in ("train", "prefill"):
            assert enc_out is not None
            kv = attn_mod.cross_kv(cfg, p["cross"], enc_out)
            new_cross = kv if mode == "prefill" else None
        else:
            kv = cross_cache
        x = x + attn_mod.cross_attention_block(cfg, p["cross"], h, kv)

    if ffn in ("mlp", "moe"):
        with jax.named_scope("mlp"):
            h = apply_norm(cfg, p["ln2"], x)
            if ffn == "mlp":
                x = x + apply_mlp(cfg, p["mlp"], h)
            else:
                y, aux = moe_mod.moe_block(cfg, p["moe"], h)
                x = x + y

    x = sharding.constrain(x, ("act_batch", "act_qseq", None))
    return x, new_cache, new_cross, aux

"""Supervised fine-tuning: masked-CE over responses, full-parameter or
LoRA.  The LoRA step differentiates only the adapter tree (base frozen).

A trained adapter tree goes straight to serving via
:func:`publish_adapter` — no weight merge, no per-tenant model replica
(the shared-platform economics the paper is about)."""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.finetune.lora import LoraConfig, lora_merge
from repro.models import model as M
from repro.models.param import cast_tree
from repro.obs.peaks import chip_peak
from repro.training.optimizer import OptConfig, clip_by_global_norm, opt_update


def make_lora_sft_step(cfg: ModelConfig, opt_cfg: OptConfig,
                       base_params, lcfg: LoraConfig,
                       schedule_fn: Optional[Callable] = None,
                       compute_dtype=jnp.bfloat16):
    """Step over (adapters, opt_state, batch); base params are closed over
    and never updated."""
    base_c = cast_tree(base_params, compute_dtype)

    def step(adapters, opt_state, batch):
        lr = (schedule_fn(opt_state["step"]) if schedule_fn
              else jnp.asarray(opt_cfg.lr, jnp.float32))

        def loss_fn(ad):
            merged = lora_merge(base_c, ad, lcfg, compute_dtype)
            return M.train_loss(cfg, merged, batch)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(adapters)
        grads, gnorm = clip_by_global_norm(grads, opt_cfg.grad_clip)
        adapters, opt_state = opt_update(opt_cfg, grads, opt_state,
                                         adapters, lr)
        return adapters, opt_state, dict(metrics, grad_norm=gnorm, lr=lr)

    return step


def instrument_sft_step(step_fn, cfg: ModelConfig, obs,
                        peak_flops: Optional[float] = None,
                        clock: Optional[Callable[[], float]] = None):
    """Wrap an SFT step with host-side observability: step-time
    histogram, token counters, throughput + estimated-MFU gauges, and
    one trace span per step on the ``finetune`` track.

    The wrapper sits *outside* the jit (the step itself is untouched),
    so it times dispatch wall like the trainer loop and adds no device
    syncs.  MFU counts the full merged forward/backward (6*N*tokens) —
    LoRA still pays the base model's FLOPs even though only the adapter
    tree gets gradients."""
    import numpy as np
    reg = obs.registry
    h_step = reg.histogram("repro_finetune_step_seconds",
                           "SFT step wall time")
    c_steps = reg.counter("repro_finetune_steps_total",
                          "SFT optimizer steps completed")
    c_tokens = reg.counter("repro_finetune_tokens_total",
                           "SFT tokens consumed")
    g_tps = reg.gauge("repro_finetune_tokens_per_s",
                      "SFT throughput, last step")
    peak_flops = peak_flops or chip_peak(jax.devices()[0].device_kind,
                                         "bf16_flops")
    g_mfu = None
    if peak_flops:
        g_mfu = reg.gauge("repro_finetune_mfu_ratio",
                          "est. model FLOPs utilisation of the SFT step")
    n_params = cfg.param_count(active_only=True)
    clk = clock if clock is not None else obs.clock
    state = {"step": 0}

    def wrapped(params, opt_state, batch):
        t0 = clk()
        sp = obs.tracer.begin("finetune", f"sft_step {state['step']}",
                              cat="finetune")
        out = step_fn(params, opt_state, batch)
        wall = clk() - t0
        obs.tracer.end(sp)
        state["step"] += 1
        tok = batch.get("tokens") if hasattr(batch, "get") else None
        n_tok = int(np.prod(tok.shape)) if tok is not None else 0
        h_step.observe(wall)
        c_steps.inc()
        c_tokens.inc(n_tok)
        if wall > 0 and n_tok:
            g_tps.set(n_tok / wall)
            if g_mfu is not None:
                g_mfu.set(6.0 * n_params * n_tok / (wall * peak_flops))
        return out

    return wrapped


def publish_adapter(pool, name: str, adapters, lcfg: LoraConfig) -> str:
    """Export a trained LoRA adapter tree directly into a serving
    adapter pool (``serving.adapters.AdapterPool`` or an engine with
    ``adapter_slots > 0``) — the fine-tune -> serve handoff without
    ``lora_merge``.  Returns ``name`` (the id requests use)."""
    register = getattr(pool, "register_adapter", None) or pool.register
    register(name, adapters, lcfg)
    return name


class LoraSFTData:
    """Adapter for Trainer-style .batch() over an SFT dataset."""

    def __init__(self, ds):
        self.ds = ds

    def batch(self, step, shard=0, num_shards=1):
        return self.ds.batch(step, shard, num_shards)

"""Fault-tolerant training loop.

Maps the paper's batch-plane semantics onto JAX:

- checkpoint/restart: async sharding-aware checkpoints; any failure
  (simulated or real) resumes from the latest published step — on a real
  cluster the BatchPlane requeues the job and this loop restores.
- straggler mitigation: per-step node timings feed a detector; persistent
  stragglers trigger the elastic callback (drop node -> reshard -> resume),
  the §6.2 "baseline + delta" mechanism in reverse.
- elastic resize: rebuild the jitted step under a new mesh/sharding and
  restore the same checkpoint into it (diskless-node semantics: node-local
  state is always disposable).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.async_ckpt import AsyncCheckpointer
from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.models.param import abstract_params, param_axes
from repro.obs.peaks import chip_peak
from repro.parallel import sharding as sh
from repro.training.optimizer import OptConfig, opt_init, opt_state_axes
from repro.training.train_step import make_train_step


class SimulatedNodeFailure(RuntimeError):
    """Raised by failure injectors to model a node loss / preemption."""


@dataclasses.dataclass
class TrainerConfig:
    num_steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep_last: int = 3
    keep_every: int = 0
    log_every: int = 10
    straggler_ratio: float = 2.0     # x median step time counts as slow
    straggler_patience: int = 3
    # give up after this many CONSECUTIVE failed restore-and-retry
    # cycles (a failure loop that never completes a step — bad node,
    # corrupt input — would otherwise requeue forever); the counter
    # resets on every completed step
    max_restarts: int = 8


class StragglerDetector:
    def __init__(self, ratio: float, patience: int):
        self.ratio = ratio
        self.patience = patience
        self.strikes: Dict[str, int] = collections.defaultdict(int)
        self.history: List[float] = []

    def observe(self, node_times: Dict[str, float]) -> List[str]:
        """Feed per-node step durations; returns nodes flagged persistent."""
        med = float(np.median(list(node_times.values())))
        self.history.append(med)
        flagged = []
        for node, t in node_times.items():
            if t > self.ratio * med:
                self.strikes[node] += 1
                if self.strikes[node] >= self.patience:
                    flagged.append(node)
            else:
                self.strikes[node] = 0
        return flagged


class Trainer:
    def __init__(self, cfg: ModelConfig, opt_cfg: OptConfig, data,
                 tc: TrainerConfig, mesh=None, rules=None,
                 schedule_fn=None, seed: int = 0,
                 failure_injector: Optional[Callable[[int], None]] = None,
                 node_timer: Optional[Callable[[int], Dict[str, float]]] = None,
                 on_straggler: Optional[Callable[[str], None]] = None,
                 param_dtype=jnp.float32, obs=None,
                 peak_flops: Optional[float] = None):
        self.cfg, self.opt_cfg, self.tc = cfg, opt_cfg, tc
        self.data = data
        self.schedule_fn = schedule_fn
        self.failure_injector = failure_injector
        self.node_timer = node_timer
        self.on_straggler = on_straggler
        self.detector = StragglerDetector(tc.straggler_ratio,
                                          tc.straggler_patience)
        self.ckpt = AsyncCheckpointer(tc.ckpt_dir, tc.keep_last,
                                      tc.keep_every)
        self.metrics_log: List[Dict[str, Any]] = []
        self.restarts = 0
        self._consec_failures = 0
        self.param_dtype = param_dtype
        # observability: step-time/throughput/MFU series + lifecycle
        # events.  Host-side only — the timings below bracket dispatch
        # wall time exactly as the pre-existing `wall` log field did, so
        # attaching obs adds no device syncs to the step loop.
        self.obs = obs
        # MFU needs the chip's published peak; an unknown device kind
        # (the CPU among them) reports no MFU
        self.peak_flops = peak_flops or chip_peak(
            jax.devices()[0].device_kind, "bf16_flops")
        try:
            self._n_active = cfg.param_count(active_only=True)
        except TypeError:
            self._n_active = cfg.param_count()
        if obs is not None:
            reg = obs.registry
            self._h_step = reg.histogram(
                "repro_train_step_seconds", "train step wall time")
            self._c_steps = reg.counter(
                "repro_train_steps_total", "optimizer steps completed")
            self._c_tokens = reg.counter(
                "repro_train_tokens_total", "training tokens consumed")
            self._c_failures = reg.counter(
                "repro_train_failures_total",
                "simulated/real node failures hit")
            self._c_restores = reg.counter(
                "repro_train_restores_total",
                "checkpoint restores after failure")
            self._c_abandoned = reg.counter(
                "repro_train_restarts_abandoned_total",
                "runs abandoned after max_restarts consecutive "
                "failures")
            self._c_stragglers = reg.counter(
                "repro_train_stragglers_total",
                "persistent-straggler flags raised")
            self._g_tps = reg.gauge(
                "repro_train_tokens_per_s",
                "training throughput, last step")
            self._g_mfu = None
            if self.peak_flops:
                self._g_mfu = reg.gauge(
                    "repro_train_mfu_ratio",
                    "est. model FLOPs utilisation (6*N*tokens / "
                    "wall*peak)")
        self._build(mesh, rules)
        key = jax.random.PRNGKey(seed)
        self.params = M.init(cfg, key, param_dtype)
        self.opt_state = opt_init(opt_cfg, self.params)
        if mesh is not None:
            self.params = jax.device_put(self.params, self.p_sh)
            self.opt_state = jax.device_put(self.opt_state, self.o_sh)
        self.step = 0

    # ------------------------------------------------------------ build
    def _build(self, mesh, rules):
        self.mesh, self.rules = mesh, rules
        step_fn = make_train_step(self.cfg, self.opt_cfg, self.schedule_fn)
        if mesh is not None:
            axes = param_axes(M.model_specs(self.cfg))
            self.p_sh = sh.tree_shardings(axes, mesh, rules)
            self.o_sh = sh.tree_shardings(
                opt_state_axes(self.opt_cfg, axes), mesh, rules)

            def wrapped(params, opt_state, batch):
                with sh.use_rules(mesh, rules):
                    return step_fn(params, opt_state, batch)

            self._jit = jax.jit(wrapped,
                                in_shardings=(self.p_sh, self.o_sh, None),
                                out_shardings=(self.p_sh, self.o_sh, None),
                                donate_argnums=(0, 1))
        else:
            self.p_sh = self.o_sh = None
            self._jit = jax.jit(step_fn, donate_argnums=(0, 1))
        self.num_shards = mesh.shape.get("data", 1) if mesh else 1

    # ------------------------------------------------------------ ckpt
    def state_tree(self):
        return {"params": self.params, "opt": self.opt_state}

    def save(self, sync: bool = False):
        meta = {"step": self.step, "arch": self.cfg.name}
        if sync:
            self.ckpt.save_sync(self.step, self.state_tree(), meta)
        else:
            self.ckpt.save(self.step, self.state_tree(), meta)

    def restore_latest(self) -> bool:
        from repro.checkpoint import ckpt as C
        self.ckpt.wait()
        steps = C.list_steps(self.tc.ckpt_dir)
        if not steps:
            return False
        target = {"params": self.params, "opt": self.opt_state}
        shd = ({"params": self.p_sh, "opt": self.o_sh}
               if self.mesh is not None else None)
        state, manifest = C.restore(self.tc.ckpt_dir, target, shardings=shd)
        self.params, self.opt_state = state["params"], state["opt"]
        self.step = manifest["meta"].get("step", manifest["step"])
        return True

    # ------------------------------------------------------------ elastic
    def resize(self, mesh, rules):
        """Elastic resize: checkpoint -> rebuild -> reshard-restore."""
        self.save(sync=True)
        self._build(mesh, rules)
        assert self.restore_latest(), "resize requires a checkpoint"

    # ------------------------------------------------------------ loop
    def run(self, num_steps: Optional[int] = None) -> Dict[str, Any]:
        end = self.tc.num_steps if num_steps is None else self.step + num_steps
        obs = self.obs
        while self.step < end:
            t0 = time.time()
            sp = (obs.tracer.begin("train", f"step {self.step}",
                                   cat="train") if obs is not None else None)
            try:
                if self.failure_injector is not None:
                    self.failure_injector(self.step)
                batch = self.data.batch(self.step, shard=0,
                                        num_shards=1)
                batch = {k: jnp.asarray(v) for k, v in batch.items()
                         if k != "source"}
                self.params, self.opt_state, metrics = self._jit(
                    self.params, self.opt_state, batch)
                self.step += 1
                self._consec_failures = 0
            except SimulatedNodeFailure:
                # batch-plane behaviour: job requeued, state restored from
                # the last published checkpoint
                self.restarts += 1
                self._consec_failures += 1
                if obs is not None:
                    self._c_failures.inc()
                    obs.tracer.instant("train", "failure", cat="train",
                                       step=self.step)
                if self._consec_failures > self.tc.max_restarts:
                    # a restart loop that never completes a step: stop
                    # requeueing and surface the failure to the operator
                    if obs is not None:
                        self._c_abandoned.inc()
                        obs.tracer.instant("train", "abandon", cat="train",
                                           step=self.step,
                                           restarts=self.restarts)
                    if sp is not None:
                        obs.tracer.end(sp, outcome="abandoned")
                    raise
                if self.restore_latest():
                    if obs is not None:
                        self._c_restores.inc()
                        obs.tracer.instant("train", "restore", cat="train",
                                           step=self.step)
                else:
                    # no checkpoint yet: restart from scratch
                    key = jax.random.PRNGKey(0)
                    self.params = M.init(self.cfg, key, self.param_dtype)
                    self.opt_state = opt_init(self.opt_cfg, self.params)
                    if self.mesh is not None:
                        self.params = jax.device_put(self.params, self.p_sh)
                        self.opt_state = jax.device_put(
                            self.opt_state, self.o_sh)
                    self.step = 0
                if sp is not None:
                    obs.tracer.end(sp, outcome="failure")
                continue

            wall = time.time() - t0
            if sp is not None:
                obs.tracer.end(sp, outcome="ok")
            if obs is not None:
                tok = batch.get("tokens")
                n_tok = (int(np.prod(tok.shape)) if tok is not None
                         else sum(int(np.prod(v.shape))
                                  for v in batch.values()))
                self._h_step.observe(wall)
                self._c_steps.inc()
                self._c_tokens.inc(n_tok)
                if wall > 0:
                    self._g_tps.set(n_tok / wall)
                    if self._g_mfu is not None:
                        self._g_mfu.set(6.0 * self._n_active * n_tok
                                        / (wall * self.peak_flops))
            if self.node_timer is not None:
                for node in self.detector.observe(self.node_timer(self.step)):
                    if obs is not None:
                        self._c_stragglers.inc()
                        obs.tracer.instant("train", "straggler", cat="train",
                                           step=self.step, node=node)
                    if self.on_straggler is not None:
                        self.on_straggler(node)
            if self.step % self.tc.ckpt_every == 0:
                self.save()
                if obs is not None:
                    obs.tracer.instant("train", "checkpoint", cat="train",
                                       step=self.step)
            if self.step % self.tc.log_every == 0 or self.step == end:
                m = {k: float(v) for k, v in metrics.items()}
                m.update(step=self.step, wall=wall)
                self.metrics_log.append(m)
        self.ckpt.wait()
        return {"final_step": self.step, "restarts": self.restarts,
                "log": self.metrics_log}

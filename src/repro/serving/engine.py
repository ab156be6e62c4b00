"""Continuous-batching inference engine (the vLLM analogue, §4.4/§6.5).

One engine = one model replica: a fixed decode batch of ``max_batch``
slots, a waiting queue, bucketed prefill (pow2 buckets bound
recompilation), and per-request TTFT/ITL/E2EL metrics.  KV storage is
*paged* by default on architectures with position-sliceable caches
(GQA/MLA): a shared block pool + per-slot block tables
(:class:`~repro.serving.kvcache.PagedCacheSlots`), with copy-free prefix
sharing and preemption instead of over-commit.  SSM/hybrid,
encoder-decoder, and vision-prefixed models fall back to the dense
per-slot layout with block-ledger admission.  Decode and sampling are
fused in one jitted step (per-slot temperature/top-k/top-p vectors), so
a micro-step costs one device round-trip for the whole batch — and with
speculative decoding enabled (``speculative="ngram"|"draft"``) that one
round-trip emits up to ``spec_k + 1`` tokens per sequence via a
multi-token verify launch with in-jit accept/reject.

Scheduling policy — admission, chunked prefill, automatic radix-tree
prefix reuse, preemption — lives in
:class:`repro.serving.scheduler.ChunkedPrefillScheduler` (design notes in
serving/README.md).  The gateway (repro.core.gateway) routes requests
across replicas with prefix affinity; HA (repro.core.ha) runs replicas
active-active.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.finetune.quantize import dequantize_tree
from repro.models import model as M
from repro.parallel import sharding
from repro.serving.adapters import AdapterPool, supports_multi_lora
from repro.serving.faults import EngineFailure, EngineTimeout
from repro.serving.kvcache import (BlockLedger, CacheSlots, PagedCacheSlots,
                                   constrain_cache)
from repro.serving.metrics import MetricsCollector, TracingMetricsCollector
from repro.serving.sampling import (sample, sample_batched,
                                    spec_accept_batched)
from repro.serving.scheduler import ChunkedPrefillScheduler, SchedulerConfig
from repro.serving.speculative import make_drafter


def _is_quantized_params(tree) -> bool:
    """True when ``tree`` is a ``finetune.quantize.quantize_tree``
    artifact: its leaves are ``{"q", "scale"}`` / ``{"raw"}`` dicts
    (the same leaf test ``dequantize_tree`` keys on)."""
    found = False

    def chk(x):
        nonlocal found
        if isinstance(x, dict) and ("raw" in x or "q" in x):
            found = True
            return True
        return False

    jax.tree.leaves(tree, is_leaf=chk)
    return found


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: int = -1
    request_id: str = ""
    namespace: str = ""      # prefix-cache isolation domain (tenant/project)
    adapter: str = ""        # LoRA adapter name ("" = base model)
    extras: Optional[Dict[str, Any]] = None   # vision_embeds / frames
    # filled by the engine:
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # generated tokens already folded into the prompt by preemption —
    # repeated preemption must fold only the tokens emitted since
    n_folded: int = 0


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 4,
                 capacity: int = 512, block_size: int = 64,
                 clock: Callable[[], float] = time.monotonic,
                 seed: int = 0, name: str = "engine0",
                 sched: Optional[SchedulerConfig] = None,
                 paged: Optional[bool] = None,
                 pool_tokens: Optional[int] = None,
                 adapter_slots: int = 0,
                 adapter_rank_bucket: int = 8,
                 speculative: Optional[str] = None,
                 spec_k: int = 4,
                 draft_cfg=None, draft_params=None,
                 obs=None, faults=None,
                 mesh=None, rules=None,
                 role: str = "unified",
                 kv_dtype: str = "bf16"):
        """``paged=None`` auto-selects the paged KV path when the
        architecture supports it.  ``pool_tokens`` sizes the shared block
        pool (default ``max_batch * capacity`` — the dense footprint);
        because paged blocks are allocated on demand, a pool smaller than
        ``max_batch * capacity`` still serves ``max_batch`` concurrent
        sequences whenever their live lengths fit.  The paged pool's
        token-block size is the scheduler's ``prefix_block`` so radix
        nodes map 1:1 onto physical blocks (copy-free sharing).

        ``adapter_slots > 0`` enables multi-tenant LoRA serving: an
        :class:`~repro.serving.adapters.AdapterPool` with that many
        device-resident adapter slots (ranks padded to
        ``adapter_rank_bucket``).  Requests name an adapter via
        ``Request.adapter``; base and adapter'd requests share every
        fused decode step.

        ``speculative`` turns on speculative decoding: ``"ngram"``
        (prompt-lookup, model-free) or ``"draft"`` (a small compatible
        model — pass ``draft_cfg``/``draft_params``).  Each decode
        micro-step then drafts up to ``spec_k`` tokens per running
        sequence and scores them in ONE multi-token verify launch;
        accepted tokens are emitted in a burst, rejected ones rolled
        back.  Greedy outputs are token-identical to the
        non-speculative engine; sampled outputs follow the same
        distribution.  Requires position-sliceable KV
        (``M.supports_speculative`` — uniform GQA/MLA stacks, either KV
        layout).

        ``faults`` (a :class:`~repro.serving.faults.FaultInjector`,
        default off) arms deterministic fault injection: the engine
        checks it at admission, at every decode micro-step, and at
        every token emission, realising crash / hang / reject faults
        (see faults.py and docs/robustness.md).

        ``obs`` (an :class:`repro.obs.Observability`, default off)
        turns on lifecycle observability: per-request trace spans and
        push-style latency histograms stream through a
        :class:`TracingMetricsCollector`, the scheduler keeps its
        per-tick spans (which are ``jax.profiler`` annotations with or
        without ``obs``) and queue/occupancy gauges, and
        :meth:`collect_metrics` pulls KV-pool / prefix-cache /
        adapter-pool state into ``obs.registry`` on demand.  All
        instrumentation is host-side Python — nothing crosses the jit
        boundary or syncs the device.

        ``mesh`` (a ``jax.sharding.Mesh`` with a ``"model"`` axis, default
        None) makes the replica *tensor-parallel*: parameters are loaded
        as NamedShardings under ``rules`` (default
        ``make_rules("serving_tp")`` — head-sharded attention, row/col
        MLPs, replicated embeddings), the KV pool/cache shards on its
        head axis (MLA's latent stays replicated), and every fused jit
        traces under those rules so prefill, paged decode, multi-LoRA,
        and speculative verify all run SPMD without host round-trips.
        Block tables, lengths, and the whole scheduler stay host-side
        and layout-invariant.  ``mesh=None`` leaves the single-device
        code path bit-for-bit untouched.

        ``role`` selects the engine's place in a *disaggregated*
        serving pair (serving/README.md "Disaggregated serving"):
        ``"unified"`` (default — prefill and decode on one engine,
        byte-identical to the pre-role behaviour), ``"prefill"``
        (accepts raw prompts, runs prefill only, and emits a
        :class:`~repro.serving.kvcache.KVHandoff` into :attr:`outbox`
        instead of streaming tokens), or ``"decode"`` (rejects raw
        prompts; admits requests from :meth:`submit_handoff`, importing
        the migrated KV with zero re-prefill).  Both non-unified roles
        need the paged KV layout — the handoff is a block-table
        export/import.

        ``kv_dtype`` selects the paged pool's storage precision:
        ``"bf16"`` (default — byte-identical to the pre-option engine)
        or ``"int8"`` (symmetric per-block quantized KV with f32
        scales; the same ``pool_tokens`` budget buys ~2x the physical
        blocks, at a small accuracy-guarded decode error — see
        serving/README.md "Quantized serving").  Requires the paged
        layout.

        ``params`` may also be a release artifact from
        ``finetune.quantize.quantize_tree`` — the engine detects the
        quantized leaf layout and dequantizes at load, closing the
        lifecycle's quantize -> publish -> deploy loop."""
        if _is_quantized_params(params):
            # a published int8 weight artifact: restore serving dtypes
            # before sharding/jit so every downstream jaxpr sees plain
            # tensors (f32 matches the lifecycle release path)
            params = dequantize_tree(params, jnp.float32)
        self.cfg, self.params = cfg, params
        self.name = name
        self.clock = clock
        self.obs = obs
        self.mesh = mesh
        self.rules = None
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"serving mesh needs a 'model' axis, got "
                    f"{mesh.axis_names}")
            self.rules = rules or sharding.make_rules("serving_tp")
            self.params = jax.device_put(
                params, sharding.tree_shardings(
                    M.model_param_axes(cfg), mesh, self.rules))
        self.tp = 1 if mesh is None else int(mesh.devices.size)
        self.paged = M.supports_paged_cache(cfg) if paged is None else paged
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"unknown engine role {role!r}")
        if role != "unified" and not self.paged:
            raise ValueError(
                f"role={role!r} needs the paged KV layout (handoffs are "
                f"block exports); {cfg.name} resolved to dense")
        self.role = role
        # prefill role: completed (req, KVHandoff) pairs for the router
        self.outbox: deque = deque()
        # decode role: (req, KVHandoff) pairs waiting for admission
        self.handoffs: deque = deque()
        self.adapters: Optional[AdapterPool] = None
        if adapter_slots > 0:
            self.adapters = AdapterPool(cfg, params, slots=adapter_slots,
                                        rank_bucket=adapter_rank_bucket)
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', "
                             f"got {kv_dtype!r}")
        if kv_dtype == "int8" and not self.paged:
            raise ValueError(
                "kv_dtype='int8' needs the paged KV layout (per-block "
                f"scales live in the block pool); {cfg.name} resolved "
                "to dense")
        self.kv_dtype = kv_dtype
        sched = sched or SchedulerConfig()
        if self.paged:
            self.slots = PagedCacheSlots(
                cfg, max_batch, capacity, block_size=sched.prefix_block,
                pool_tokens=pool_tokens, mesh=mesh, rules=self.rules,
                kv_dtype=kv_dtype)
        else:
            self.slots = CacheSlots(cfg, max_batch, capacity,
                                    mesh=mesh, rules=self.rules)
        self.ledger = BlockLedger(capacity * max_batch, block_size)
        self.capacity = capacity
        self.queue: deque[Request] = deque()
        self.running: Dict[int, Request] = {}
        self.metrics = (TracingMetricsCollector(obs) if obs is not None
                        else MetricsCollector())
        self.key = jax.random.PRNGKey(seed)
        self._ids = itertools.count()
        self.healthy = True
        self.draining = False
        self.faults = faults
        self.steps = 0

        # every fused step traces under the engine's (mesh, rules) via
        # sharded_jit — with mesh=None that is plain jax.jit and the
        # constrain/constrain_cache calls are no-ops, so the
        # single-device jaxprs are byte-identical to the unsharded
        # engine's.  Cache/pool outputs are re-constrained before
        # returning so the donated buffers keep a stable NamedSharding
        # across micro-steps (no per-step resharding, no recompiles).
        # two axes trees: the *dense* cache axes for prefill and the
        # dense decode/verify steps (their cache trees never carry scale
        # leaves), and the slots' pool axes for the paged steps (GQA
        # leaves flat on (KV·head_dim); int8 pools add ``*_scale`` leaves)
        dense_axes = M.cache_axes(cfg)
        cache_axes = self.slots._axes
        mk_jit = lambda f, **kw: sharding.sharded_jit(  # noqa: E731
            f, mesh, self.rules, **kw)

        def _prefill_fn(p, b, lo, ai):
            logits, cache, aux = M.prefill(cfg, p, b, lora=lo,
                                           adapter_ids=ai)
            return logits, constrain_cache(cache, dense_axes), aux

        self._prefill = mk_jit(_prefill_fn)

        # decode + batched sampling fused in one jitted step: per-slot
        # temperature/top-k/top-p vectors in, sampled tokens out — the
        # scheduler does a single coalesced device_get per micro-step.
        # ``greedy`` is static: the all-greedy batch (the common case)
        # skips the two full-vocab sorts of the filtered sampler.
        # ``lo``/``ai`` are the stacked adapter tree + per-slot adapter
        # ids (both None on engines without an adapter pool) — multi-LoRA
        # rides the same micro-step, no extra launches.
        def _fused(p, t, c, l, key, temps, tks, tps, lo, ai, greedy):
            logits, nc = M.decode_step(cfg, p, t, c, l, lora=lo,
                                       adapter_ids=ai)
            nc = constrain_cache(nc, dense_axes)
            with jax.named_scope("head"):
                if greedy:
                    return jnp.argmax(logits, -1).astype(jnp.int32), nc
                return sample_batched(logits, key, temps, tks, tps), nc

        def _fused_paged(p, t, pool, bt, l, key, temps, tks, tps, lo, ai,
                         greedy):
            logits, np_ = M.decode_step_paged(cfg, p, t, pool, bt, l,
                                              lora=lo, adapter_ids=ai)
            np_ = constrain_cache(np_, cache_axes)
            with jax.named_scope("head"):
                if greedy:
                    return jnp.argmax(logits, -1).astype(jnp.int32), np_
                return sample_batched(logits, key, temps, tks, tps), np_

        self._decode_sample = mk_jit(_fused, static_argnums=(10,))
        self._decode_sample_paged = mk_jit(_fused_paged,
                                           donate_argnums=(2,),
                                           static_argnums=(11,))

        # speculative decoding: draft up to spec_k tokens per sequence,
        # score them in ONE multi-token verify launch, accept/reject
        # inside the jit (the whole batch still costs one device_get).
        self.spec_k = spec_k
        self.drafter = None
        if speculative:
            if not M.supports_speculative(cfg):
                raise ValueError(
                    "speculative decoding needs position-sliceable KV "
                    "(uniform GQA/MLA stacks) — rejected tokens cannot "
                    f"be rolled back on {cfg.name}")
            self.drafter = make_drafter(speculative, cfg, spec_k=spec_k,
                                        capacity=capacity,
                                        draft_cfg=draft_cfg,
                                        draft_params=draft_params)

        def _verify_fused(p, t, c, l, key, temps, tks, tps, dprobs, nd,
                          lo, ai, greedy):
            logits, nc = M.verify_step(cfg, p, t, c, l, lora=lo,
                                       adapter_ids=ai)
            nc = constrain_cache(nc, dense_axes)
            out, nem = spec_accept_batched(logits, t, dprobs, nd, key,
                                           temps, tks, tps, greedy)
            return out, nem, nc

        def _verify_fused_paged(p, t, pool, bt, l, key, temps, tks, tps,
                                dprobs, nd, lo, ai, greedy):
            logits, np_ = M.verify_step_paged(cfg, p, t, pool, bt, l,
                                              lora=lo, adapter_ids=ai)
            np_ = constrain_cache(np_, cache_axes)
            out, nem = spec_accept_batched(logits, t, dprobs, nd, key,
                                           temps, tks, tps, greedy)
            return out, nem, np_

        self._verify = mk_jit(_verify_fused, static_argnums=(12,))
        self._verify_paged = mk_jit(_verify_fused_paged,
                                    donate_argnums=(2,),
                                    static_argnums=(13,))
        self.scheduler = ChunkedPrefillScheduler(self, sched)

    # ------------------------------------------------------------ API
    def register_adapter(self, name: str, adapters, lcfg) -> None:
        """Publish a trained LoRA adapter to this engine's pool so
        requests can name it via ``Request.adapter`` (or the gateway's
        ``model@adapter``)."""
        if self.adapters is None:
            raise RuntimeError(
                "engine has no adapter pool (construct with "
                "adapter_slots > 0)")
        self.adapters.register(name, adapters, lcfg)

    def adapter_stats(self) -> Dict[str, int]:
        """Adapter-pool counters (zeros when multi-LoRA is disabled)."""
        if self.adapters is None:
            return {"registered": 0, "resident": 0, "pinned": 0,
                    "slots": 0, "loads": 0, "evictions": 0,
                    "acquire_waits": 0}
        return self.adapters.stats()

    def submit(self, req: Request) -> str:
        st = self.health()
        if st != "ok":
            raise EngineFailure(f"{self.name} is {st}", point="submit",
                                kind=st)
        if self.role == "decode":
            # decode-only admission: raw prompts have no KV to import —
            # route them through a prefill engine (or a unified one)
            raise EngineFailure(
                f"{self.name} is decode-role: submit_handoff() a "
                f"prefilled request, not a raw prompt", point="submit",
                kind="role")
        self._fault("admission")
        if not req.request_id:
            req.request_id = f"{self.name}-r{next(self._ids)}"
        self.metrics.arrival(req.request_id, self.clock(), len(req.prompt))
        self.queue.append(req)
        return req.request_id

    def submit_handoff(self, req: Request, handoff) -> str:
        """Submit a prefilled request plus its exported KV to a
        decode-role engine.  The request resumes with zero re-prefill:
        admission imports the handoff's blocks (adopting any prefix the
        local radix tree already holds) and streams only tokens past
        the handoff's coverage (none, unless a preemption fold grew the
        prompt)."""
        if self.role == "prefill":
            raise EngineFailure(
                f"{self.name} is prefill-role: it exports handoffs, it "
                f"does not import them", point="submit", kind="role")
        st = self.health()
        if st != "ok":
            raise EngineFailure(f"{self.name} is {st}", point="submit",
                                kind=st)
        self._fault("admission")
        if not req.request_id:
            req.request_id = handoff.request_id or \
                f"{self.name}-r{next(self._ids)}"
        self.metrics.arrival(req.request_id, self.clock(), len(req.prompt))
        self.handoffs.append((req, handoff))
        return req.request_id

    # -------------------------------------------------------- lifecycle
    def health(self) -> str:
        """``"ok"`` / ``"draining"`` (finishing in-flight work, not
        accepting new) / ``"down"`` (crashed; needs :meth:`recover`)."""
        if not self.healthy:
            return "down"
        if self.draining:
            return "draining"
        return "ok"

    def crash(self, reason: str = "") -> List[Request]:
        """Simulate the replica process dying: mark the engine down,
        evacuate every in-flight request (committed tokens folded into
        the prompt via the scheduler's preemption path — resubmission
        elsewhere is token-exact at temperature 0), and drop the prefix
        cache (its KV died with the process).  Returns the evacuated
        requests, oldest first, for the caller to reroute."""
        self.healthy = False
        reqs = self.scheduler.evacuate()
        self.scheduler.reset_cache()
        return reqs

    def recover(self) -> None:
        """Bring a crashed (or draining) engine back into rotation.
        State was already cleaned by :meth:`crash`, so recovery is just
        re-admitting traffic — the serving analogue of the trainer's
        restore-and-retry."""
        self.healthy = True
        self.draining = False

    def drain(self, max_steps: int = 100_000):
        """Stop accepting new requests but finish the in-flight ones —
        the graceful half of node reclamation.  Returns the metrics
        summary; call :meth:`recover` to re-enter rotation."""
        self.draining = True
        return self.run_until_idle(max_steps)

    def _fault(self, point: str) -> None:
        """Consult the bound injector at a fault point and realise
        whatever it schedules (crash / hang / reject).  No injector, or
        nothing scheduled: free."""
        inj = self.faults
        if inj is None:
            return
        spec = inj.check(point)
        if spec is None:
            return
        if spec.kind == "hang":
            if inj.clock_advance is not None:
                inj.clock_advance(spec.hang_s)
            return
        if spec.kind == "reject":
            raise EngineFailure(
                f"{self.name}: injected reject at {point}",
                point=point, kind="reject")
        self.crash(reason=f"injected crash at {point}")
        raise EngineFailure(
            f"{self.name}: injected crash at {point}",
            point=point, kind="crash")

    @property
    def num_active(self) -> int:
        # outbox is excluded: an exported handoff is the *router's* work
        # now, and counting it would wedge run_until_idle
        return len(self.running) + len(self.queue) + len(self.handoffs)

    @property
    def prefix_cache(self):
        return self.scheduler.prefix_cache

    def prefix_match_len(self, namespace: str, tokens) -> int:
        """Longest cached prefix for this prompt (0 when caching is off or
        the architecture is unsupported) — used for affinity routing."""
        return self.scheduler.match_len(namespace, tokens)

    def kv_stats(self) -> Dict[str, int]:
        """KV-memory accounting in blocks: live + peak usage, and total.
        Paged engines report real pool blocks (shared prefix blocks count
        once) plus per-device byte figures (on a TP mesh a GQA pool
        block is split across devices on its head axis, so per-device
        peak KV shrinks ~1/tp); dense engines report ledger
        reservations."""
        if self.paged:
            bp = self.slots.bp
            # bytes one device holds for the whole pool: shard size for
            # TP-sharded leaves, full size for replicated ones (MLA)
            dev_pool = sum(
                leaf.addressable_shards[0].data.nbytes
                for leaf in jax.tree.leaves(self.slots.pool))
            per_block = dev_pool // bp.num_blocks
            return {"kv_blocks_used": bp.num_used,
                    "kv_blocks_peak": bp.peak_used,
                    "kv_blocks_total": bp.num_blocks - 1,
                    "kv_block_size": self.slots.block_size,
                    "kv_tp_degree": self.tp,
                    "kv_block_bytes_per_device": per_block,
                    "kv_peak_bytes_per_device": per_block * bp.peak_used}
        return {"kv_blocks_used": self.ledger.total_blocks
                - self.ledger.free_blocks,
                "kv_blocks_peak": self.ledger.peak_blocks,
                "kv_blocks_total": self.ledger.total_blocks,
                "kv_block_size": self.ledger.block_size,
                "kv_tp_degree": self.tp}

    def collect_metrics(self, registry=None):
        """Pull every serving subsystem's state into a metrics registry
        (default: ``obs.registry``): scheduler queue/batch gauges, KV
        pool occupancy, prefix-cache hit/miss/evict, adapter-pool
        residency, and the request/speculative aggregates.  Returns the
        registry — call right before snapshotting/exporting."""
        reg = registry
        if reg is None:
            if self.obs is None:
                raise ValueError("engine has no obs handle; pass a "
                                 "registry explicitly")
            reg = self.obs.registry
        reg.gauge("repro_sched_queue_depth_requests",
                  "requests waiting for admission").set(len(self.queue))
        reg.gauge("repro_sched_running_requests",
                  "requests holding a decode slot").set(
            len(self.running))
        reg.gauge("repro_sched_batch_capacity_slots",
                  "decode slots in the fixed batch").set(self.slots.B)
        if self.paged:
            self.slots.bp.collect_metrics(
                reg, block_size=self.slots.block_size)
        else:
            self.ledger.collect_metrics(reg)
        if self.prefix_cache is not None:
            self.prefix_cache.collect_metrics(reg)
        if self.adapters is not None:
            self.adapters.collect_metrics(reg)
        self.metrics.collect(reg)
        return reg

    # ------------------------------------------------------------ steps
    def _sample(self, logits, req: Request):
        self.key, k = jax.random.split(self.key)
        return sample(logits, k, temperature=req.temperature,
                      top_k=req.top_k, top_p=req.top_p)

    def step(self):
        """One scheduler tick: admit up to N requests, then decode (and
        stream pending prefill chunks).  Decode runs every tick, so a
        deep queue can no longer starve running requests."""
        self.scheduler.tick()
        self.steps += 1

    def run_until_idle(self, max_steps: int = 100_000,
                       deadline: Optional[float] = None):
        """Drive the engine until no request is active.  ``deadline``
        (absolute, on the engine's clock) bounds the wall budget: when
        it passes with work still in flight, the remaining requests are
        evacuated (committed tokens folded, so they resume token-exact
        elsewhere) and :class:`EngineTimeout` carries them out."""
        while self.num_active and max_steps:
            if deadline is not None and self.clock() >= deadline:
                reqs = self.scheduler.evacuate()
                raise EngineTimeout(
                    f"{self.name}: deadline exceeded with "
                    f"{len(reqs)} request(s) in flight", requests=reqs)
            self.step()
            max_steps -= 1
        return self.metrics.summary()

"""Chunked-prefill scheduler policy for the inference engine.

The engine's old loop ("admit one request OR decode the batch") had two
problems: a deep waiting queue starved every running request (a tick
that admitted never decoded), and every prompt was prefilled from
scratch.  This policy object replaces it (see README.md):

- **Admission** pops up to ``admit_per_tick`` requests per tick.  Each
  admitted request first runs a longest-prefix match against the radix
  prefix cache (:mod:`repro.serving.prefix_cache`); the matched KV
  becomes the request's cache prefix and only the uncached suffix needs
  compute.  On the *paged* KV path the hit is copy-free — the matched
  physical blocks are spliced into the request's block table with a
  refcount bump.  On the dense fallback the matched segment is copied
  into the request's slot.
- **Decode runs every tick.**  Running requests emit at least one token
  per tick regardless of admission activity.
- **Chunked prefill.**  Uncached suffixes are consumed through the
  batched decode step — at most ``prefill_chunk`` suffix tokens per
  request per tick, as micro-steps in which *every* running slot
  advances: prefilling slots consume their next prompt token while
  decoding slots keep emitting.  A long prefill therefore never stalls
  a running decode (the old loop's ITL cliff).
- **Fused batched sampling.**  Each micro-step makes one jitted
  decode+sample call with per-slot temperature/top-k/top-p vectors and
  one coalesced ``device_get`` of the sampled tokens — not a per-slot
  ``int(tok[0])`` sync per running request.
- **Preemption, not over-commit (paged).**  Decode growth allocates real
  pool blocks.  On exhaustion the scheduler first evicts unpinned prefix
  tree leaves, then preempts the *latest-admitted* running request: its
  blocks are freed and it returns to the queue head with its generated
  tokens folded into the prompt, so resumption re-prefills (usually a
  prefix-cache hit) and continues token-exactly.
- **Speculative bursts.**  With a drafter attached
  (``InferenceEngine(speculative=...)``), micro-steps with decoding
  slots run :meth:`_spec_micro_step` instead: each decode slot drafts up
  to ``spec_k`` tokens, ONE multi-token verify launch scores them all,
  and the slot emits 1..spec_k+1 accepted tokens (rejected tail rolled
  back by length shrink + block trim).  Greedy outputs stay
  token-identical to the plain micro-step.

Exactness: suffix tokens pass through ``decode_step`` at their true
positions against the already-written prefix KV, which is the same math
as a full prefill (causal attention, identical RoPE positions); the
engine-vs-reference tests pin this token-for-token for both KV layouts.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import paged_kv_read_tokens
from repro.obs.tracer import Phase
from repro.serving.adapters import adapter_namespace
from repro.serving.prefix_cache import (Match, PagedPrefixCache, PrefixCache,
                                        supports_prefix_cache)


@dataclasses.dataclass
class SchedulerConfig:
    admit_per_tick: int = 1
    # max uncached suffix tokens consumed per request per tick; also the
    # one-shot prefill size cap for cache-miss prompts
    prefill_chunk: int = 512
    enable_prefix_cache: bool = True
    # token-block size of the radix tree; on the paged KV path this is
    # also the physical pool block size (node <-> block, 1:1)
    prefix_block: int = 16
    # KV token budget of the prefix cache; default = one full slot batch
    # (dense) / the pool size (paged)
    cache_capacity_tokens: Optional[int] = None
    # graceful degradation: after this many pressure events (kv-defers /
    # preemptions) step the ladder down one level (1 = suspend
    # speculative decoding, 2 = also pause admission); after this many
    # pressure-free ticks step back up
    degrade_after: int = 4
    restore_after: int = 6


def _bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048, 4096)) -> int:
    """Pow2 prefill-padding bucket (shared with speculative.py's
    draft-model prefill, so both compile against the same shape set)."""
    for b in buckets:
        if n <= b:
            return b
    return -(-n // 4096) * 4096


class ChunkedPrefillScheduler:
    """Policy object driving one engine's admission + decode loop."""

    def __init__(self, engine, config: Optional[SchedulerConfig] = None):
        from repro.models import model as M
        self.eng = engine
        self.config = config or SchedulerConfig()
        self.paged = getattr(engine, "paged", False)
        # disaggregated serving: "prefill" ticks export a KVHandoff when
        # a prompt's KV is complete (never decoding), "decode" ticks
        # admit from the engine's handoff queue (never raw prompts)
        self.role = getattr(engine, "role", "unified")
        self.supported = supports_prefix_cache(engine.cfg)
        self.prefix_cache: Optional[PrefixCache] = None
        if self.config.enable_prefix_cache and self.supported:
            if self.paged:
                cap = (self.config.cache_capacity_tokens
                       if self.config.cache_capacity_tokens is not None
                       else (engine.slots.bp.num_blocks - 1)
                       * engine.slots.block_size)
                self.prefix_cache = PagedPrefixCache(
                    engine.slots.bp,
                    block_size=engine.slots.block_size,
                    capacity_tokens=cap)
            else:
                cap = (self.config.cache_capacity_tokens
                       if self.config.cache_capacity_tokens is not None
                       else engine.capacity * engine.slots.B)
                self.prefix_cache = PrefixCache(
                    M.cache_axes(engine.cfg),
                    block_size=self.config.prefix_block,
                    capacity_tokens=cap)
        # slot -> index of the next prompt token to stream through decode
        self.pending: Dict[int, int] = {}
        # request_id -> pinned radix nodes (unpinned at finish/release)
        self._locked: Dict[str, List] = {}
        # slot -> admission sequence number (preemption picks the max)
        self._admit_order: Dict[int, int] = {}
        self._admit_seq = itertools.count()
        # slot -> device adapter id (rows without an entry decode as base)
        self._slot_adapter: Dict[int, int] = {}
        # decode role: slot -> the KVHandoff it was admitted from, kept
        # so preemption can requeue the pair (re-admission re-imports)
        self._slot_handoff: Dict[int, object] = {}
        # graceful-degradation ladder: 0 = normal, 1 = speculative
        # decoding suspended, 2 = admission paused too.  Pressure events
        # (kv admission defers, preemptions) push it down; pressure-free
        # ticks pull it back up.
        self.degrade_level = 0
        self._tick_pressure = 0   # pressure events in the current tick
        self._pressure = 0        # accumulated since last transition
        self._calm_ticks = 0      # consecutive pressure-free ticks
        # observability (engine-owned).  Every host phase is a profiler
        # annotation (``Phase``); with ``obs`` attached it is also a span
        # of ``obs.tracer`` and the push-side instruments below, which are
        # pre-registered so the per-tick path is attribute lookups +
        # appends, never registry lookups.
        self.obs = getattr(engine, "obs", None)
        self._tracer = self.obs.tracer if self.obs is not None else None
        if self.obs is not None:
            reg = self.obs.registry
            if self.paged:
                pool = engine.slots.pool
                # bytes of one KV position across all layers (scales
                # included on an int8 pool)
                self._kv_token_bytes = sum(
                    leaf.nbytes for leaf in jax.tree.leaves(pool)) // (
                    engine.slots.bp.num_blocks * engine.slots.block_size)
                self._c_kv_read = reg.counter(
                    "repro_kv_read_bytes_total",
                    "KV bytes the paged steps' attention read")
                self._c_kv_live = reg.counter(
                    "repro_kv_live_bytes_total",
                    "KV bytes of the live positions those steps needed")
            self._h_tick = reg.histogram(
                "repro_sched_tick_seconds",
                "wall time of one scheduler tick")
            self._h_occupancy = reg.histogram(
                "repro_sched_batch_occupancy_ratio",
                "running slots / batch size, sampled per micro-step",
                buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875,
                         1.0))
            self._g_queue = reg.gauge(
                "repro_sched_queue_depth_requests",
                "requests waiting for admission")
            self._g_running = reg.gauge(
                "repro_sched_running_requests",
                "requests holding a decode slot")
            self._c_deferred = reg.counter(
                "repro_sched_admit_deferred_total",
                "admissions deferred to a later tick",
                labelnames=("reason",))
            self._c_degrade = reg.counter(
                "repro_sched_degrade_transitions_total",
                "graceful-degradation ladder transitions",
                labelnames=("direction",))
            self._g_degrade = reg.gauge(
                "repro_sched_degrade_level_count",
                "degradation level (0 normal, 1 spec off, 2 admission "
                "paused)")
            self._c_handoff_out = reg.counter(
                "repro_serving_handoff_exported_total",
                "prefill-role KV handoffs exported to the outbox")
            self._c_handoff_in = reg.counter(
                "repro_serving_handoff_imported_total",
                "decode-role KV handoffs imported into a slot")
            self._c_handoff_blocks = reg.counter(
                "repro_serving_handoff_blocks_total",
                "physical KV blocks carried by handoffs (exported, or "
                "scattered on import — adopted blocks excluded)")
            self._c_handoff_bytes = reg.counter(
                "repro_serving_handoff_bytes_total",
                "host payload bytes gathered for exported handoffs")
            self._c_handoff_adopted = reg.counter(
                "repro_serving_handoff_adopted_blocks_total",
                "imported-handoff blocks satisfied by the decode-side "
                "radix tree (spliced, not re-uploaded)")

    def _defer(self, reason: str) -> bool:
        """Count a deferred admission (kv pressure / pinned adapter
        slots); returns False so call sites can ``return self._defer``."""
        if reason == "kv":
            self._tick_pressure += 1
        if self.obs is not None:
            self._c_deferred.labels(reason=reason).inc()
        return False

    # ------------------------------------------------------------ tick
    def _phase(self, name: str, **kw) -> Phase:
        """A host phase of the scheduler track (``repro.scheduler.*``)."""
        return Phase(self._tracer, "scheduler", name, "sched", **kw)

    def tick(self):
        eng, obs = self.eng, self.obs
        t0 = eng.clock() if obs is not None else 0.0
        with self._phase("tick", step=eng.steps, queued=len(eng.queue),
                         running=len(eng.running)):
            # no child span when the phase has no work
            if eng.queue or eng.handoffs:
                with self._phase("admit"):
                    self._admit_tick()
            if eng.running:
                with self._phase("decode"):
                    self._decode_tick()
            self._degrade_update()
        if obs is not None:
            self._h_tick.observe(eng.clock() - t0)
            self._g_queue.set(len(eng.queue))
            self._g_running.set(len(eng.running))

    def _admit_tick(self):
        if self.degrade_level >= 2:
            # deepest ladder rung: shed admission load entirely so the
            # running batch can finish and free pool blocks.  This defer
            # must NOT count as pressure or the pause would self-sustain.
            if self.eng.queue or self.eng.handoffs:
                self._defer("degraded")
            return
        admitted = 0
        while admitted < self.config.admit_per_tick and self._admit_one():
            admitted += 1

    # ------------------------------------------------- graceful degradation
    def _degrade_update(self):
        """End-of-tick ladder update: sustained pressure steps down
        (suspend speculation, then pause admission); sustained calm
        steps back up one rung at a time."""
        if self._tick_pressure:
            self._pressure += self._tick_pressure
            self._tick_pressure = 0
            self._calm_ticks = 0
            if (self._pressure >= self.config.degrade_after
                    and self.degrade_level < 2):
                self._pressure = 0
                self._set_degrade(self.degrade_level + 1)
            return
        self._calm_ticks += 1
        if self._calm_ticks >= self.config.restore_after:
            self._calm_ticks = 0
            self._pressure = 0
            if self.degrade_level > 0:
                self._set_degrade(self.degrade_level - 1)

    def _set_degrade(self, level: int):
        old, self.degrade_level = self.degrade_level, level
        if self.obs is not None:
            direction = "down" if level > old else "up"
            self._c_degrade.labels(direction=direction).inc()
            self._g_degrade.set(level)
            self.obs.tracer.instant(
                "scheduler", "degrade" if level > old else "restore",
                cat="sched", level=level)

    def drained(self) -> bool:
        return (not self.eng.queue and not self.eng.running
                and not self.eng.handoffs)

    def match_len(self, namespace: str, tokens) -> int:
        """Longest stored prefix (tokens) — used for affinity routing."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.match_len(namespace, tokens)

    @staticmethod
    def _ns(req) -> str:
        """Prefix-cache namespace: KV computed under a LoRA adapter is
        only reusable under that same adapter, so adapter'd requests get
        a dedicated radix tree within their tenant namespace."""
        return adapter_namespace(req.namespace, req.adapter)

    # ------------------------------------------------------------ admission
    def _admit_one(self) -> bool:
        if self.role == "decode":
            return self._admit_handoff()
        eng = self.eng
        if not eng.queue:
            return False
        if not eng.slots.free:
            return self._defer("slots")
        req = eng.queue[0]
        # a preempted request resumes with its generated tokens folded
        # into the prompt; only the *remaining* budget counts
        need = (len(req.prompt) + req.max_new_tokens - len(req.generated))
        if eng.drafter is not None:
            # the speculative verify step writes spec_k + 1 tail
            # positions before accept/reject, so the slot must be able
            # to address spec_k extra positions past the last real token
            need += eng.spec_k
        if need > eng.capacity or (req.adapter and (
                eng.adapters is None or not eng.adapters.has(req.adapter))):
            # can never fit / names an unknown adapter: explicit
            # rejection, not a silent "finish"
            eng.queue.popleft()
            req.done = True
            eng.metrics.reject(req.request_id, eng.clock())
            return True      # queue progressed; keep admitting
        n = len(req.prompt)
        chunk0 = n
        if self.supported and n > self.config.prefill_chunk:
            chunk0 = self.config.prefill_chunk
        if self.paged:
            # worst-case (cache-miss) block need for the first chunk;
            # eviction of unpinned tree leaves can free at most
            # evictable_blocks() more.  Admission is counted in physical
            # pool blocks, so an int8 pool (kv_dtype="int8") doubles the
            # admittable load at the same pool_tokens budget with no
            # change here — num_free simply starts ~2x higher.
            avail = eng.slots.bp.num_free
            if self.prefix_cache is not None:
                avail += self.prefix_cache.evictable_blocks()
            if eng.slots.blocks_for(chunk0) > avail:
                return self._defer("kv")
        elif not eng.ledger.can_admit(req.request_id, need):
            return self._defer("kv")
        aid = 0
        if req.adapter:
            # load-or-pin the adapter (refcount++).  None means every
            # device adapter slot is pinned by an in-flight request —
            # leave the request queued and retry next tick.
            aid = eng.adapters.acquire(req.adapter)
            if aid is None:
                return self._defer("adapter")
        eng.queue.popleft()
        if not self.paged:
            eng.ledger.admit(req.request_id, need)
        slot = eng.slots.allocate(req.request_id)
        if aid:
            self._slot_adapter[slot] = aid
        eng.metrics.prefill_start(req.request_id, eng.clock())

        cached = 0
        if self.prefix_cache is not None and not req.extras:
            m: Match = self.prefix_cache.match(self._ns(req), req.prompt)
            if self.paged:
                bs = eng.slots.block_size
                n_use = min(len(m.nodes), (n - 1) // bs)
                cached = n_use * bs
            else:
                cached = min(m.length, n - 1)
            # take the hit only when streaming the uncached suffix costs
            # no more model launches than the miss path (one one-shot
            # prefill chunk + streamed tail) — a short cached prefix on a
            # long prompt would otherwise *worsen* TTFT.  Paged hits are
            # whole-block, losing up to block_size-1 cached tokens to
            # rounding; grant exactly that slack so accept decisions
            # match the dense (token-granular) policy
            miss_launches = 1 + max(0, n - self.config.prefill_chunk)
            if self.paged:
                miss_launches += eng.slots.block_size - 1
            if cached > 0 and n - cached <= miss_launches:
                if self.paged:
                    nodes = m.nodes[:n_use]
                    self.prefix_cache.lock(nodes)
                    self._locked.setdefault(req.request_id, []).extend(nodes)
                    ids = self.prefix_cache.gather_block_ids(m, n_use)
                    # copy-free: refcount bump + table splice, no KV moved
                    eng.slots.adopt_prefix(slot, ids, cached)
                else:
                    self.prefix_cache.lock(m.nodes)
                    self._locked.setdefault(req.request_id,
                                            []).extend(m.nodes)
                    seg = self.prefix_cache.gather(m, cached)
                    seg = self._pad_segment(seg, min(_bucket(cached),
                                                     eng.capacity))
                    eng.slots.insert(slot, seg, cached)
                eng.metrics.prefix_hit(req.request_id, cached)
            else:
                cached = 0
        eng.running[slot] = req
        self._admit_order[slot] = next(self._admit_seq)

        if cached > 0:
            # stream the uncached suffix through decode micro-steps
            self.pending[slot] = cached
            return True

        # cache miss: one-shot prefill of the first chunk (the whole
        # prompt unless it exceeds prefill_chunk on a chunkable model)
        chunk = chunk0
        if self.paged and not self._ensure_blocks(slot, chunk):
            # pool exhausted even after eviction: put the request back
            # and wait for blocks to free up
            eng.running.pop(slot, None)
            self._admit_order.pop(slot, None)
            self._release_adapter(slot, req)
            eng.slots.release(slot)
            eng.queue.appendleft(req)
            return self._defer("kv")
        pad = _bucket(chunk)
        toks = np.zeros((1, pad), np.int32)
        toks[0, :chunk] = req.prompt[:chunk]
        n_front = (eng.cfg.frontend_tokens
                   if eng.cfg.frontend == "vision" else 0)
        batch = {"tokens": jnp.asarray(toks),
                 "prompt_lengths": jnp.asarray([chunk + n_front], jnp.int32)}
        if req.extras:
            batch.update({k: jnp.asarray(v) for k, v in req.extras.items()})
        lo, ai = self._lora_args([aid])
        rid = req.request_id
        with self._phase("prefill", rid=rid):
            logits, cache, _ = eng._prefill(eng.params, batch, lo, ai)
        from repro.models import model as M
        with self._phase("kv_insert", rid=rid):
            if self.paged:
                eng.slots.insert_prefill(slot, cache, chunk + n_front)
            else:
                cache = M.pad_cache(eng.cfg, cache, eng.capacity)
                eng.slots.insert(slot, cache, chunk + n_front)

        if chunk < n:
            self.pending[slot] = chunk
        elif self.role == "prefill":
            self._store_prompt(slot, req)
            self._handoff_out(slot, req)
        else:
            self._store_prompt(slot, req)
            with self._phase("first_token", rid=rid):
                tok = int(eng._sample(logits, req)[0])
            self._emit(slot, req, tok)
        return True

    # ----------------------------------------------------------- handoff
    def _admit_handoff(self) -> bool:
        """Decode-role admission: import a prefilled request's KV from
        the engine's handoff queue.  Mirrors :meth:`_admit_one`
        (capacity checks, adapter pins, prefix adoption, explicit
        rejection) but never runs prefill compute — the handoff blocks
        are spliced/scattered in and the standard pending-stream path
        re-feeds the final prompt token to produce the first-token
        logits on THIS engine.  Pool pressure defers (the pair stays
        queued); nothing is ever silently dropped."""
        eng = self.eng
        if not eng.handoffs:
            return False
        if not eng.slots.free:
            return self._defer("slots")
        req, ho = eng.handoffs[0]
        need = (len(req.prompt) + req.max_new_tokens - len(req.generated))
        if eng.drafter is not None:
            need += eng.spec_k
        if need > eng.capacity or (req.adapter and (
                eng.adapters is None or not eng.adapters.has(req.adapter))):
            eng.handoffs.popleft()
            req.done = True
            eng.metrics.reject(req.request_id, eng.clock())
            return True
        # worst-case block need for the imported prefix (prefix adoption
        # can only shrink it); eviction of unpinned tree leaves can free
        # at most evictable_blocks() more
        avail = eng.slots.bp.num_free
        if self.prefix_cache is not None:
            avail += self.prefix_cache.evictable_blocks()
        if eng.slots.blocks_for(ho.length) > avail:
            return self._defer("kv")
        aid = 0
        if req.adapter:
            aid = eng.adapters.acquire(req.adapter)
            if aid is None:
                return self._defer("adapter")
        eng.handoffs.popleft()
        slot = eng.slots.allocate(req.request_id)
        if aid:
            self._slot_adapter[slot] = aid
        eng.metrics.prefill_start(req.request_id, eng.clock())

        adopted_ids: List[int] = []
        adopted = 0
        if self.prefix_cache is not None and not req.extras:
            bs = eng.slots.block_size
            m: Match = self.prefix_cache.match(self._ns(req), req.prompt)
            # cap adoption so position ho.length - 1 (re-fed locally for
            # the first-token logits) lands in a privately imported
            # block — shared tree blocks are never written
            n_use = min(len(m.nodes), (ho.length - 1) // bs)
            if n_use > 0:
                nodes = m.nodes[:n_use]
                self.prefix_cache.lock(nodes)
                self._locked.setdefault(req.request_id, []).extend(nodes)
                adopted_ids = list(
                    self.prefix_cache.gather_block_ids(m, n_use))
                adopted = n_use * bs
                eng.metrics.prefix_hit(req.request_id, adopted)
        shortfall = eng.slots.blocks_for(ho.length) - len(adopted_ids)
        if eng.slots.bp.num_free < shortfall:
            self._reclaim(shortfall)
        ok = eng.slots.import_kv(slot, ho, adopted_ids, adopted)
        if not ok:
            # pool raced away between the avail check and the alloc:
            # roll the admission back completely and retry next tick
            self._release_adapter(slot, req)
            if self.prefix_cache is not None:
                nodes = self._locked.pop(req.request_id, None)
                if nodes:
                    self.prefix_cache.unlock(nodes)
            eng.slots.release(slot)
            eng.handoffs.appendleft((req, ho))
            return self._defer("kv")
        eng.running[slot] = req
        self._admit_order[slot] = next(self._admit_seq)
        self._slot_handoff[slot] = ho
        # resume point: the imported KV covers [0, ho.length); rewind one
        # token so the standard pending stream re-feeds the final prompt
        # token at its true position (rewriting identical KV in a private
        # block) and samples the first token here — token-identical to a
        # unified engine at temperature 0.  A preempted-and-refolded
        # request streams its folded suffix through the same path.
        eng.slots.lengths[slot] = ho.length - 1
        self.pending[slot] = ho.length - 1
        if self.obs is not None:
            self._c_handoff_in.inc()
            self._c_handoff_blocks.inc(ho.n_blocks - len(adopted_ids))
            if adopted_ids:
                self._c_handoff_adopted.inc(len(adopted_ids))
            self.obs.tracer.instant(
                "scheduler", "handoff_import", cat="sched",
                rid=req.request_id, tokens=ho.length,
                adopted_blocks=len(adopted_ids))
        return True

    def _handoff_out(self, slot: int, req):
        """Prefill-role completion: instead of sampling the first token,
        export the slot's finished KV as a host-side payload onto the
        engine's outbox and retire the slot.  The request is NOT done —
        a decode-role engine imports the payload and finishes it."""
        eng = self.eng
        ho = eng.slots.export_kv(req.request_id)
        ho.prompt_tokens = list(req.prompt)
        ho.adapter = req.adapter
        ho.exported_at = eng.clock()
        eng.metrics.handoff(req.request_id, eng.clock())
        eng.ledger.release(req.request_id)
        eng.slots.release(slot)
        eng.running.pop(slot, None)
        self.pending.pop(slot, None)
        self._admit_order.pop(slot, None)
        self._release_adapter(slot, req)
        self._release_drafter(slot)
        if self.prefix_cache is not None:
            nodes = self._locked.pop(req.request_id, None)
            if nodes:
                self.prefix_cache.unlock(nodes)
        eng.outbox.append((req, ho))
        if self.obs is not None:
            self._c_handoff_out.inc()
            self._c_handoff_blocks.inc(ho.n_blocks)
            self._c_handoff_bytes.inc(ho.payload_bytes)
            self.obs.tracer.instant(
                "scheduler", "handoff_export", cat="sched",
                rid=req.request_id, tokens=ho.length, blocks=ho.n_blocks)

    def _lora_args(self, ids):
        """(lora_tree, adapter_ids) for a model call — (None, None) on
        engines without an adapter pool, so the jit signature never
        changes mid-run."""
        if self.eng.adapters is None:
            return None, None
        return (self.eng.adapters.lora_tree(),
                jnp.asarray(np.asarray(ids, np.int32)))

    def _release_adapter(self, slot: int, req):
        """Unpin the request's adapter (refcount--; the weights stay
        resident for LRU reuse).  Keyed on the slot's pin entry so every
        ``acquire`` is paired with exactly one ``release``."""
        if self._slot_adapter.pop(slot, None) is not None:
            self.eng.adapters.release(req.adapter)

    def _release_drafter(self, slot: int):
        """Drop the drafter's per-slot state (draft KV cache / lookup
        index) when the slot turns over — finish or preemption."""
        if self.eng.drafter is not None:
            self.eng.drafter.release(slot)

    def _pad_segment(self, seg, target: int):
        """Pad a gathered segment's kvseq up to ``target`` so the slot
        insert compiles per pow2 bucket, not per exact match length."""
        from repro.serving.prefix_cache import tree_walk

        def one(arr, ax):
            i = ax.index("act_kvseq")
            if arr.shape[i] >= target:
                return arr
            pads = [(0, 0)] * arr.ndim
            pads[i] = (0, target - arr.shape[i])
            return jnp.pad(arr, pads)
        return tree_walk(one, seg, self.eng.slots._axes)

    # ------------------------------------------------------ paged memory
    def _ensure_blocks(self, slot: int, new_len: int) -> bool:
        """ensure_capacity with tree-eviction fallback (no preemption)."""
        eng = self.eng
        if eng.slots.ensure_capacity(slot, new_len):
            return True
        need = (eng.slots.blocks_for(new_len)
                - len(eng.slots.seq_blocks.get(slot, [])))
        self._reclaim(need)
        return eng.slots.ensure_capacity(slot, new_len)

    def _reclaim(self, n_blocks: int) -> bool:
        """Evict unpinned prefix-tree leaves until the pool has
        ``n_blocks`` free (shared leaves may free nothing — their blocks
        survive until the last running holder releases)."""
        bp = self.eng.slots.bp
        pc = self.prefix_cache
        while bp.num_free < n_blocks:
            if pc is None or not pc._evict_one():
                return False
        return True

    def _preempt_latest(self):
        """Free the latest-admitted running request's blocks and return
        it to the queue head.  Its generated tokens are folded into the
        prompt, so re-admission re-prefills (typically a prefix-cache
        hit) and generation resumes token-exactly."""
        eng = self.eng
        slot = max(eng.running, key=lambda s: self._admit_order.get(s, -1))
        req = eng.running.pop(slot)
        self.pending.pop(slot, None)
        self._admit_order.pop(slot, None)
        self._release_adapter(slot, req)
        self._release_drafter(slot)
        if self.prefix_cache is not None:
            nodes = self._locked.pop(req.request_id, None)
            if nodes:
                self.prefix_cache.unlock(nodes)
        fresh = req.generated[req.n_folded:]
        if fresh:
            req.prompt = list(req.prompt) + list(fresh)
            req.n_folded = len(req.generated)
        eng.slots.release(slot)
        eng.ledger.release(req.request_id)
        ho = self._slot_handoff.pop(slot, None)
        if ho is not None:
            # decode-role slot: the engine rejects raw prompts, so the
            # (request, handoff) pair requeues; re-admission re-imports
            # the payload and streams the folded suffix token-exactly
            eng.handoffs.appendleft((req, ho))
        else:
            eng.queue.appendleft(req)
        eng.metrics.preempt(req.request_id, eng.clock())
        self._tick_pressure += 1

    def evacuate(self) -> List:
        """Pull every in-flight request off the engine (crash/timeout
        path): running requests go through the preemption fold — their
        committed tokens become prompt suffix, slots/ledger/adapter
        pins/drafter state released — then the whole queue is drained.
        Returns the requests oldest-first, ready to resubmit anywhere
        token-exactly (at temperature 0)."""
        eng = self.eng
        while eng.running:
            self._preempt_latest()
        out = list(eng.queue)
        eng.queue.clear()
        # decode role: prefilled-but-waiting pairs evacuate as plain
        # requests — the handoff payload referenced a pool that may be
        # gone, so the gateway resubmits them for a fresh prefill
        out.extend(r for r, _ in eng.handoffs)
        eng.handoffs.clear()
        return out

    def reset_cache(self) -> None:
        """Drop the whole radix prefix cache (crash path: the cached KV
        lived in the dead process).  Call after :meth:`evacuate` — only
        unlocked nodes can be evicted."""
        pc = self.prefix_cache
        if pc is None:
            return
        while pc._evict_one():
            pass

    def _grow_all(self, n: int = 1):
        """Allocate the next ``n`` positions' blocks for every running
        slot (n = spec_k + 1 on speculative steps — rejected tail blocks
        are trimmed back after accept/reject), preempting latest-admitted
        requests when the pool (plus tree eviction) cannot supply them."""
        eng = self.eng
        while eng.running:
            stuck = None
            for slot in sorted(eng.running):
                if not self._ensure_blocks(slot,
                                           int(eng.slots.lengths[slot]) + n):
                    stuck = slot
                    break
            if stuck is None:
                return
            self._preempt_latest()

    # ------------------------------------------------------------ decode
    def _decode_tick(self):
        if not self.eng.running:
            return
        # while any slot is still prefilling (and the per-tick chunk
        # budget lasts), run extra micro-steps; every running slot
        # advances each micro-step, so decode is never stalled
        limit = max(1, self.config.prefill_chunk)
        steps = 0
        while True:
            self._micro_step()
            steps += 1
            if not self.pending or steps >= limit or not self.eng.running:
                break

    def _micro_step(self):
        """One fused decode+sample step.  Prefilling slots consume their
        next prompt token; decoding slots feed their last sampled token
        (its KV gets written now) and emit a new one.  Sampling runs
        batched inside the jitted step; the sampled tokens come back in
        one coalesced transfer.

        With a drafter attached, any tick with at least one *decoding*
        slot runs the speculative variant instead (prefilling slots ride
        along, advancing one prompt token as usual)."""
        eng = self.eng
        eng._fault("micro_step")
        if (eng.drafter is not None and self.degrade_level < 1
                and any(s not in self.pending for s in eng.running)):
            return self._spec_micro_step()
        with self._phase("micro_step") as sp:
            if eng.paged:
                with self._phase("grow"):
                    self._grow_all()
            if not eng.running:
                return
            npre = len(self.pending)
            sp.set(decoding=len(eng.running) - npre, prefilling=npre)
            with self._phase("inputs"):
                B = eng.slots.B
                toks = np.zeros((B, 1), np.int32)
                advance = np.zeros((B,), bool)
                for slot, req in eng.running.items():
                    advance[slot] = True
                    toks[slot, 0] = (req.prompt[self.pending[slot]]
                                     if slot in self.pending
                                     else req.generated[-1])
                samp = self._sampling_inputs()
                if eng.paged:
                    lengths = np.where(advance, eng.slots.lengths + 1,
                                       eng.slots.lengths).astype(np.int32)
                    kv = (eng.slots.pool, eng.slots.tables_device(),
                          jnp.asarray(lengths))
                else:
                    lengths = jnp.where(jnp.asarray(advance),
                                        eng.slots.lengths + 1,
                                        eng.slots.lengths)
                    kv = (eng.slots.cache, lengths)
                toks = jnp.asarray(toks)
            if eng.paged:
                sp.set(**self._kv_counts(lengths))
            with self._phase("dispatch"):
                if eng.paged:
                    out, eng.slots.pool = eng._decode_sample_paged(
                        eng.params, toks, *kv, *samp)
                else:
                    out, eng.slots.cache = eng._decode_sample(
                        eng.params, toks, *kv, *samp)
            eng.slots.lengths = lengths
            with self._phase("device_wait"):
                sampled = np.asarray(out)   # one device_get for the batch
            with self._phase("emit"):
                for slot, req in list(eng.running.items()):
                    if slot in self.pending:
                        self.pending[slot] += 1
                        if self.pending[slot] >= len(req.prompt):
                            # last prompt token consumed: its logits are
                            # the next-token logits — prefill is complete
                            del self.pending[slot]
                            self._store_prompt(slot, req)
                            if self.role == "prefill":
                                self._handoff_out(slot, req)
                            else:
                                self._emit(slot, req, int(sampled[slot]))
                    else:
                        self._emit(slot, req, int(sampled[slot]))

    def _sampling_inputs(self):
        """The step's sampling arguments as the fused jits take them:
        ``(key, temps, top_ks, top_ps, lora, adapter_ids, greedy)``, with
        a fresh key split off the engine's, and the batch occupancy
        sampled into the registry."""
        eng = self.eng
        B = eng.slots.B
        if self.obs is not None:
            self._h_occupancy.observe(len(eng.running) / B)
        temps = np.zeros((B,), np.float32)
        tks = np.zeros((B,), np.int32)
        tps = np.ones((B,), np.float32)
        for slot, req in eng.running.items():
            temps[slot] = req.temperature
            tks[slot] = req.top_k
            tps[slot] = req.top_p
        aids = np.zeros((B,), np.int32)
        for slot, idx in self._slot_adapter.items():
            aids[slot] = idx
        lo, ai = self._lora_args(aids)
        eng.key, key = jax.random.split(eng.key)
        return (key, jnp.asarray(temps), jnp.asarray(tks), jnp.asarray(tps),
                lo, ai, bool(np.all(temps <= 0.0)))

    def _kv_counts(self, lengths) -> Dict[str, int]:
        """KV positions a paged step needs (its running slots' lengths,
        the new tokens included) and the positions its attention reads;
        with ``obs`` attached both go into the registry as bytes."""
        eng = self.eng
        live = int(sum(int(lengths[s]) for s in eng.running))
        read = paged_kv_read_tokens(eng.slots.B, eng.slots.blocks_per_seq,
                                    eng.slots.block_size)
        if self.obs is not None:
            self._c_kv_live.inc(live * self._kv_token_bytes)
            self._c_kv_read.inc(read * self._kv_token_bytes)
        return {"live_tokens": live, "kv_read_tokens": read}

    def _spec_micro_step(self):
        """One speculative verify micro-step (variable tokens per tick).

        Per running *decode* slot: ask the drafter for up to spec_k
        candidate tokens (capped by the request's remaining budget),
        then score ``[last_emitted, draft_1..draft_n]`` in ONE jitted
        multi-token verify launch that also runs accept/reject
        (``sampling.spec_accept_batched``) — so a slot emits between 1
        and spec_k + 1 tokens per launch.  Prefilling slots ride along,
        consuming one prompt token (their draft count is 0, which
        degenerates to the plain micro-step for that row).

        The launch writes KV for all spec_k + 1 tail positions before
        the verdict is known; rejected positions are rolled back by
        shrinking the slot's length (stale KV past the length is never
        read and is overwritten when decode resumes there) and, on the
        paged path, returning the now-unreferenced tail blocks to the
        pool (``PagedCacheSlots.trim``).
        """
        eng = self.eng
        k = eng.spec_k
        T = k + 1
        with self._phase("spec_verify", k=k) as sp:
            if eng.paged:
                with self._phase("grow"):
                    self._grow_all(T)
            if not eng.running:
                return
            sp.set(slots=len(eng.running))
            B = eng.slots.B
            with self._phase("draft"):
                toks = np.zeros((B, T), np.int32)
                nd = np.zeros((B,), np.int32)
                # deterministic drafters (q = one-hot) skip the dense
                # (B,k,V) host buffer: the accept jit rebuilds q from
                # the token ids
                det = eng.drafter.deterministic
                dprobs = (None if det else
                          np.zeros((B, k, eng.cfg.vocab_padded), np.float32))
                advance = np.zeros((B,), bool)
                for slot, req in eng.running.items():
                    advance[slot] = True
                    if slot in self.pending:
                        # prefill rows advance exactly one prompt token
                        toks[slot, 0] = req.prompt[self.pending[slot]]
                        continue
                    toks[slot, 0] = req.generated[-1]
                    # drafting past the remaining budget is wasted
                    # verification: the launch emits at most n_draft + 1
                    cap = min(k, req.max_new_tokens - len(req.generated) - 1)
                    if cap <= 0:
                        continue
                    ctx = list(req.prompt) + list(req.generated)
                    drafts, qp = eng.drafter.propose(slot, ctx, cap,
                                                     req.temperature)
                    n = len(drafts)
                    if n:
                        toks[slot, 1:1 + n] = drafts
                        if not det:
                            dprobs[slot, :n] = qp
                        nd[slot] = n
            with self._phase("inputs"):
                key, temps, tks, tps, lo, ai, greedy = \
                    self._sampling_inputs()
                base = np.asarray(eng.slots.lengths, np.int32)
                lengths = np.where(advance, base + T, base).astype(np.int32)
                dp = None if det else jnp.asarray(dprobs)
                args = (jnp.asarray(toks),)
                args += ((eng.slots.pool, eng.slots.tables_device())
                         if eng.paged else (eng.slots.cache,))
                args += (jnp.asarray(lengths), key, temps, tks, tps, dp,
                         jnp.asarray(nd), lo, ai, greedy)
            if eng.paged:
                sp.set(**self._kv_counts(lengths))
            with self._phase("dispatch"):
                if eng.paged:
                    out, nem, eng.slots.pool = eng._verify_paged(
                        eng.params, *args)
                else:
                    out, nem, eng.slots.cache = eng._verify(
                        eng.params, *args)
            with self._phase("device_wait"):
                out = np.asarray(out)       # one device_get for the batch
                nem = np.asarray(nem)
            with self._phase("emit"):
                self._spec_emit(base, out, nem, nd)

    def _spec_emit(self, base, out, nem, nd):
        """Roll each slot back to its accepted burst and emit it."""
        eng = self.eng
        # roll lengths back to the accepted burst BEFORE emitting (a
        # finishing _emit releases the slot and resets its length)
        final = base.copy()
        for slot in eng.running:
            final[slot] = base[slot] + (1 if slot in self.pending
                                        else int(nem[slot]))
        if eng.paged:
            for slot in eng.running:
                eng.slots.trim(slot, int(final[slot]))
            eng.slots.lengths = final
        else:
            eng.slots.lengths = jnp.asarray(final)
        for slot, req in list(eng.running.items()):
            if slot in self.pending:
                self.pending[slot] += 1
                if self.pending[slot] >= len(req.prompt):
                    del self.pending[slot]
                    self._store_prompt(slot, req)
                    if self.role == "prefill":
                        self._handoff_out(slot, req)
                    else:
                        self._emit(slot, req, int(out[slot, 0]))
                continue
            n = int(nem[slot])
            emitted = 0
            for t in range(n):
                self._emit(slot, req, int(out[slot, t]))
                emitted += 1
                if req.done:
                    break  # EOS/budget mid-burst: drop the tail
            eng.metrics.speculative(int(nd[slot]), n - 1, emitted)

    # ------------------------------------------------------------ lifecycle
    def _store_prompt(self, slot: int, req):
        """Index this prompt's KV (from its slot, before any generated
        token could be confused for prompt) into the radix tree."""
        if self.prefix_cache is None or req.extras:
            return
        if len(req.prompt) < self.prefix_cache.block_size:
            return
        if self.paged:
            # zero-copy: donate the slot's own physical block ids (the
            # tree refcounts them; nothing is extracted or copied)
            ids = self.eng.slots.block_ids(slot)
            bs = self.eng.slots.block_size
            new = self.prefix_cache.insert(
                self._ns(req), req.prompt, lambda s, e: ids[s // bs])
        else:
            new = self.prefix_cache.insert(
                self._ns(req), req.prompt,
                lambda s, e: self.eng.slots.extract(slot, s, e))
        if new:
            self._locked.setdefault(req.request_id, []).extend(new)

    def _emit(self, slot: int, req, token: int):
        eng = self.eng
        # the fault fires BEFORE the token commits: a crash here drops
        # the uncommitted token, and temp-0 resumption re-derives it
        eng._fault("emission")
        req.generated.append(token)
        eng.metrics.token(req.request_id, eng.clock())
        if (token == req.eos_id
                or len(req.generated) >= req.max_new_tokens):
            req.done = True
            eng.metrics.finish(req.request_id, eng.clock())
            eng.ledger.release(req.request_id)
            eng.slots.release(slot)
            eng.running.pop(slot, None)
            self.pending.pop(slot, None)
            self._admit_order.pop(slot, None)
            self._slot_handoff.pop(slot, None)
            self._release_adapter(slot, req)
            self._release_drafter(slot)
            if self.prefix_cache is not None:
                nodes = self._locked.pop(req.request_id, None)
                if nodes:
                    self.prefix_cache.unlock(nodes)

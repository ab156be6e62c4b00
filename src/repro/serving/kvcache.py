"""KV-cache storage + block accounting for the serving engine.

Two storage layouts (README.md "Paged KV" section):

- ``CacheSlots`` — the original *dense* per-slot layout: ``max_batch``
  preallocated rows of ``capacity`` positions each, length-masked.  Kept
  as the fallback for architectures without position-sliceable KV
  (SSM/hybrid state, encoder-decoder, vision-prefixed).
- ``BlockPool`` + ``PagedCacheSlots`` — vLLM-style paged layout: one
  shared physical pool of ``block_size``-token blocks
  (``M.make_paged_pool``: stacked ``(layers, num_blocks, block_size,
  KV·head_dim)`` leaves for GQA, which the decode step carries through
  the layer scan and updates in place) plus per-slot block tables.
  Blocks are allocated on demand and ref-counted, so memory tracks
  *actual* sequence lengths (not worst-case capacity) and the radix
  prefix cache shares physical blocks with running requests instead of
  copying KV segments.

``BlockLedger`` is the admission-control account for the dense path (and
the node budget of the prefix cache); the paged path accounts in real
pool blocks instead.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.parallel import sharding


def tree_walk(fn, tree, axes):
    """Apply ``fn(leaf, axes_tuple)`` over a cache pytree with its axes
    (the single recursion every cache-shaped traversal shares)."""
    if isinstance(tree, dict):
        return {k: tree_walk(fn, tree[k], axes[k]) for k in tree}
    if isinstance(tree, list):
        return [tree_walk(fn, t, a) for t, a in zip(tree, axes)]
    return fn(tree, axes)


def tree_multi(fn, trees, axes):
    """Like :func:`tree_walk` over N structurally-identical pytrees:
    ``fn([leaf0, .., leafN], axes_tuple)``."""
    head = trees[0]
    if isinstance(head, dict):
        return {k: tree_multi(fn, [t[k] for t in trees], axes[k])
                for k in head}
    if isinstance(head, list):
        return [tree_multi(fn, [t[i] for t in trees], axes[i])
                for i in range(len(head))]
    return fn(trees, axes)


def constrain_cache(tree, axes):
    """Re-assert each cache leaf's sharding (inside a jit, under active
    rules) so donated caches/pools keep a *stable* NamedSharding across
    steps instead of whatever layout the partitioner picked last.  A
    no-op without an active rules context — the single-device jaxpr is
    untouched."""
    if sharding.active() is None:
        return tree
    return tree_walk(lambda a, ax: sharding.constrain(a, ax), tree, axes)


class BlockLedger:
    """Admission-control accounting in ``block_size``-token blocks.

    A pure bookkeeping object — it reserves *budget*, not storage: the
    dense engine charges each request's worst case (prompt + generation
    budget) here before touching a slot, and the prefix cache uses a
    dedicated ledger as its node budget.  Invariants:

    - Reservations are **rid-keyed and idempotent**: ``can_admit``/
      ``admit`` count blocks ``rid`` already holds toward its allowance,
      so re-admitting a retried request never double-charges.
    - **Never over-commits**: ``admit``/``grow`` raise once the pool is
      exhausted rather than silently handing out blocks that do not
      exist — the caller must preempt or reject (the PR-2 fix; the old
      ``grow`` silently over-committed).
    - ``release`` is unconditional and forgets the rid entirely;
      ``peak_blocks`` tracks the high-water mark for ``kv_stats``.
    """

    def __init__(self, capacity_tokens: int, block_size: int = 128):
        self.block_size = block_size
        self.total_blocks = capacity_tokens // block_size
        self.used: Dict[str, int] = {}
        self.peak_blocks = 0

    def blocks_for(self, tokens: int) -> int:
        return -(-tokens // self.block_size)

    @property
    def free_blocks(self) -> int:
        return self.total_blocks - sum(self.used.values())

    def _note_peak(self):
        self.peak_blocks = max(self.peak_blocks,
                               self.total_blocks - self.free_blocks)

    def can_admit(self, rid: str, tokens: int) -> bool:
        """Admission check for ``rid``.  Blocks ``rid`` already holds count
        toward its allowance, so re-admission (e.g. a retried request that
        never released) is idempotent rather than double-charged."""
        return (self.blocks_for(tokens)
                <= self.free_blocks + self.used.get(rid, 0))

    def admit(self, rid: str, tokens: int):
        need = self.blocks_for(tokens)
        if need > self.free_blocks + self.used.get(rid, 0):
            raise RuntimeError("KV cache exhausted")
        self.used[rid] = need
        self._note_peak()

    def grow(self, rid: str, tokens: int):
        """Grow ``rid``'s reservation to cover ``tokens``.

        Never over-commits: growth past the pool raises so the caller can
        preempt a running request (or reject) instead of silently handing
        out blocks that do not exist.
        """
        need = self.blocks_for(tokens)
        held = self.used.get(rid, 0)
        if need <= held:
            return
        if need - held > self.free_blocks:
            raise RuntimeError(
                f"KV cache exhausted: {rid} needs {need - held} more "
                f"block(s), {self.free_blocks} free — preempt or reject")
        self.used[rid] = need
        self._note_peak()

    def release(self, rid: str):
        self.used.pop(rid, None)

    def collect_metrics(self, reg) -> None:
        """Pull ledger occupancy into a metrics registry (the dense
        engine's ``repro_kv_*`` series — same names as the paged
        pool's, so dashboards are layout-agnostic)."""
        used = self.total_blocks - self.free_blocks
        reg.gauge("repro_kv_used_blocks",
                  "KV blocks currently reserved").set(used)
        reg.gauge("repro_kv_free_blocks",
                  "KV blocks available for admission").set(
            self.free_blocks)
        reg.gauge("repro_kv_peak_blocks",
                  "high-water mark of reserved KV blocks").set(
            self.peak_blocks)
        reg.gauge("repro_kv_capacity_blocks",
                  "total allocatable KV blocks").set(self.total_blocks)
        reg.gauge("repro_kv_block_size_tokens",
                  "tokens per KV block").set(self.block_size)


class CacheSlots:
    """Fixed decode batch of B slots, each with ``capacity`` positions.

    With ``mesh`` + ``rules`` the cache leaves are laid out as
    NamedShardings resolved from their logical axes (under
    ``serving_tp``: head-sharded for GQA, replicated for the MLA
    latent) and the insert jit traces under those rules, so a sharded
    engine's dense fallback keeps KV distributed too."""

    def __init__(self, cfg: ModelConfig, max_batch: int, capacity: int,
                 dtype=jnp.bfloat16, mesh=None, rules=None):
        self.cfg = cfg
        self.B = max_batch
        self.capacity = capacity
        self.mesh, self.rules = mesh, rules
        self.cache = M.make_cache(cfg, max_batch, capacity, dtype)
        self._axes = M.cache_axes(cfg)
        if mesh is not None:
            self.cache = jax.device_put(
                self.cache,
                sharding.tree_shardings(self._axes, mesh, rules))
        self.lengths = jnp.ones((max_batch,), jnp.int32)  # 1 = inert slot
        # deque: allocate() pops the head, release() appends — O(1) FIFO
        self.free: Deque[int] = deque(range(max_batch))
        self.slot_owner: Dict[int, str] = {}
        self._insert = sharding.sharded_jit(self._insert_impl, mesh, rules,
                                            donate_argnums=(0,))

    def _insert_impl(self, cache, prefill_cache, slot):
        """Write a single-sequence prefill cache (1, S, ...) into slot."""
        def one(leaves, ax):
            dst, src = leaves
            bi = ax.index("act_batch")
            src = src.astype(dst.dtype)
            start = [jnp.asarray(0, jnp.int32)] * dst.ndim
            start[bi] = slot
            # pad the seq dim of src up to dst (already <= capacity)
            pads = []
            for i, (ds, ss) in enumerate(zip(dst.shape, src.shape)):
                pads.append((0, (ds - ss) if i != bi else 0))
            src = jnp.pad(src, pads)
            return jax.lax.dynamic_update_slice(dst, src, start)

        out = tree_multi(one, [cache, prefill_cache], self._axes)
        return constrain_cache(out, self._axes)

    def allocate(self, rid: str) -> Optional[int]:
        if not self.free:
            return None
        slot = self.free.popleft()
        self.slot_owner[slot] = rid
        return slot

    def insert(self, slot: int, prefill_cache, length: int):
        self.cache = self._insert(self.cache, prefill_cache,
                                  jnp.asarray(slot, jnp.int32))
        self.lengths = self.lengths.at[slot].set(length)

    def extract(self, slot: int, start: int, end: int):
        """Copy KV for positions ``[start, end)`` out of ``slot``.

        Returns a pytree shaped like a single-sequence prefill cache
        (``act_batch == 1``, ``act_kvseq == end - start``) — the segment
        format the prefix cache stores.  Only meaningful for caches whose
        leaves all carry an ``act_kvseq`` axis (pure attention)."""
        def one(arr, ax):
            if "act_kvseq" not in ax:
                raise ValueError(
                    "extract() needs position-sliceable cache leaves "
                    f"(axes {ax} has no act_kvseq)")
            idx = [slice(None)] * arr.ndim
            idx[ax.index("act_batch")] = slice(slot, slot + 1)
            idx[ax.index("act_kvseq")] = slice(start, end)
            return arr[tuple(idx)]

        return tree_walk(one, self.cache, self._axes)

    def release(self, slot: int):
        self.slot_owner.pop(slot, None)
        self.lengths = self.lengths.at[slot].set(1)
        self.free.append(slot)

    @property
    def active_slots(self) -> List[int]:
        return sorted(self.slot_owner)


# ------------------------------------------------------------------ paged
NULL_BLOCK = 0   # reserved physical block: writes from inert slots and
                 # reads past a sequence's length land here, never on data


@dataclasses.dataclass
class KVHandoff:
    """Host-side KV migration payload (prefill engine -> decode engine).

    Produced by :meth:`PagedCacheSlots.export_kv` on the prefill side:
    the finished prefill's physical blocks gathered to host memory in
    block-major layout (each cache leaf becomes ``(n_blocks, ...)`` with
    the pool's block axis moved to the front, and a GQA position's KV
    heads split out of the pool's flat leaf as the dense cache holds
    them, so the payload does not depend on the pool's layout).
    Consumed by :meth:`PagedCacheSlots.import_kv` on the decode side, which
    allocates fresh pool blocks and scatters the payload back — or, for
    a prefix the decode-side radix tree already holds, splices the
    shared blocks in place of re-uploading them.

    The payload is plain host data: it survives the death of either
    engine, so a crash mid-handoff is recovered by re-importing the same
    object elsewhere (token-exact at temperature 0).  ``prompt_tokens``
    doubles as the prefix-cache key on the decode side; ``adapter``
    names the LoRA adapter whose pin must transfer with the request
    (the adapter *weights* must already be registered on the decode
    pool — the handoff moves KV, not parameters).
    """
    request_id: str
    length: int              # prompt tokens materialised in the blocks
    block_size: int
    n_blocks: int
    blocks: Any              # host pytree; leaf (n_blocks, ...) block-major
    prompt_tokens: List[int] = dataclasses.field(default_factory=list)
    adapter: str = ""
    exported_at: float = 0.0  # engine-clock export timestamp

    @property
    def payload_bytes(self) -> int:
        import jax as _jax
        return sum(leaf.nbytes for leaf in _jax.tree.leaves(self.blocks))


class BlockPool:
    """Ref-counted allocator over the physical blocks of a paged pool.

    One block id spans every layer leaf of the pool (see
    ``M.make_paged_pool``), so allocation is accounted in token blocks,
    not per-layer bytes.  Invariants:

    - **Null block**: block 0 is reserved and never allocated.  Inert
      decode slots scatter their (masked) writes there and block-table
      tails point there; the attention length mask guarantees it is
      never *read*, so no live KV can be corrupted by an idle slot.
    - **Refcount lifecycle**: ``alloc`` hands out ids at refcount 1
      (all-or-nothing for multi-block requests); ``incref`` adds holders
      — the radix prefix tree (one ref per stored node) and every
      running request that adopted the block via a prefix hit;
      ``decref`` frees a block only at refcount 0.  Consequence: tree
      eviction never invalidates a running request, and slot release
      never invalidates the tree.
    - **Shared blocks are read-only by construction**: the tree stores
      only whole prompt blocks, and a sequence writes strictly after its
      adopted prefix, in blocks it allocated privately — so a refcount
      > 1 block is never written.
    - ``incref``/``decref`` on an unallocated id raise — refcount bugs
      surface immediately instead of corrupting the free list.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("pool needs at least one allocatable block")
        self.num_blocks = num_blocks
        self.free: Deque[int] = deque(range(1, num_blocks))
        self.refs: Dict[int, int] = {}
        self.peak_used = 0

    @property
    def num_free(self) -> int:
        return len(self.free)

    @property
    def num_used(self) -> int:
        return (self.num_blocks - 1) - len(self.free)

    def alloc(self, n: int = 1) -> Optional[List[int]]:
        """Allocate ``n`` blocks (refcount 1 each), all-or-nothing."""
        if n > len(self.free):
            return None
        ids = [self.free.popleft() for _ in range(n)]
        for b in ids:
            self.refs[b] = 1
        self.peak_used = max(self.peak_used, self.num_used)
        return ids

    def incref(self, ids: Sequence[int]):
        for b in ids:
            if b not in self.refs:
                raise ValueError(f"incref on unallocated block {b}")
            self.refs[b] += 1

    def decref(self, ids: Sequence[int]) -> int:
        """Drop one reference per id; returns how many blocks were freed."""
        freed = 0
        for b in ids:
            r = self.refs.get(b)
            if r is None:
                raise ValueError(f"decref on unallocated block {b}")
            if r > 1:
                self.refs[b] = r - 1
            else:
                del self.refs[b]
                self.free.append(b)
                freed += 1
        return freed

    def collect_metrics(self, reg, block_size: int = 0) -> None:
        """Pull pool occupancy into a metrics registry.  Gauges track
        the live pool state ("is the KV pool thrashing?"); shared
        (refcount > 1) blocks — prefix-cache hits adopted by running
        requests — are reported separately so the copy-free sharing win
        is visible as a series, not just a benchmark row."""
        reg.gauge("repro_kv_used_blocks",
                  "physical KV blocks allocated").set(self.num_used)
        reg.gauge("repro_kv_free_blocks",
                  "physical KV blocks on the free list").set(
            self.num_free)
        reg.gauge("repro_kv_peak_blocks",
                  "high-water mark of allocated KV blocks").set(
            self.peak_used)
        reg.gauge("repro_kv_capacity_blocks",
                  "total allocatable KV blocks (excl. null)").set(
            self.num_blocks - 1)
        reg.gauge("repro_kv_shared_blocks",
                  "blocks referenced by more than one holder").set(
            sum(1 for r in self.refs.values() if r > 1))
        if block_size:
            reg.gauge("repro_kv_block_size_tokens",
                      "tokens per KV block").set(block_size)


class PagedCacheSlots:
    """Paged counterpart of :class:`CacheSlots`.

    ``max_batch`` block-table rows (one per decode slot) over a shared
    :class:`BlockPool` of ``pool_tokens // block_size`` physical blocks.
    A slot's KV lives wherever its table points, so

    - memory tracks actual lengths: short sequences hold few blocks, and
      more than ``pool_tokens / capacity`` sequences can run concurrently
      whenever their live lengths fit (the dense layout pins
      ``max_batch × capacity`` up front);
    - a prefix-cache hit is a table splice + refcount bump
      (``adopt_prefix``) — no KV bytes move in either direction;
    - growth is a real allocation (``ensure_capacity``), so running out
      of blocks is an explicit event the scheduler answers with tree
      eviction or preemption, never a silent over-commit.

    Shared (adopted) blocks are read-only by construction: the prefix
    cache stores only *whole* prompt blocks, and a sequence writes
    strictly after its adopted prefix, in blocks it allocated privately.

    Slot invariants: an inert slot has ``lengths[slot] == 1`` and a
    table full of ``NULL_BLOCK`` entries, so its decode-step writes land
    in the null block and its reads are masked out; ``release`` decrefs
    exactly the blocks in ``seq_blocks[slot]`` (the slot's own + adopted
    ids) and resets the table row.  ``tables_device`` caches the device
    copy of the table matrix and every table mutation invalidates it
    (``_touch_tables``), so a micro-step uploads tables at most once.
    """

    def __init__(self, cfg: ModelConfig, max_batch: int, capacity: int,
                 dtype=jnp.bfloat16, block_size: int = 16,
                 pool_tokens: Optional[int] = None, mesh=None, rules=None,
                 kv_dtype: str = "bf16"):
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', "
                             f"got {kv_dtype!r}")
        self.cfg = cfg
        self.B = max_batch
        self.capacity = capacity
        self.block_size = block_size
        self.kv_dtype = kv_dtype
        self.mesh, self.rules = mesh, rules
        self.blocks_per_seq = -(-capacity // block_size)
        pool_tokens = (max_batch * capacity if pool_tokens is None
                       else pool_tokens)
        # ``pool_tokens`` is a bf16-byte-equivalent budget: int8 blocks
        # cost half the bytes, so the same budget buys twice the physical
        # blocks — admission capacity doubles for free
        per_token = 2 if kv_dtype == "int8" else 1
        num_blocks = 1 + max((pool_tokens * per_token) // block_size,
                             self.blocks_per_seq)
        self.pool = M.make_paged_pool(cfg, num_blocks, block_size, dtype,
                                      kv_dtype=kv_dtype)
        self._axes = M.paged_pool_axes(cfg, kv_dtype)
        # the prefill caches written into the pool keep the dense layout
        self._cache_axes = M.cache_axes(cfg)
        if mesh is not None:
            # a pool leaf is (num_blocks, block_size, ...) in the cache's
            # (act_batch, act_kvseq, ...) axis slots; under serving_tp
            # both map to None, so the pool shards exactly on the KV-head
            # axis (GQA's flat KV·head_dim axis, in whole heads when
            # KV % tp == 0) or stays replicated (MLA latent) — block ids,
            # tables, and all host-side accounting are layout-invariant
            self.pool = jax.device_put(
                self.pool,
                sharding.tree_shardings(self._axes, mesh, rules))
        self.bp = BlockPool(num_blocks)
        self.tables = np.full((max_batch, self.blocks_per_seq), NULL_BLOCK,
                              np.int32)
        self.lengths = np.ones((max_batch,), np.int32)  # 1 = inert slot
        self.seq_blocks: Dict[int, List[int]] = {}
        self.free: Deque[int] = deque(range(max_batch))
        self.slot_owner: Dict[int, str] = {}
        self._tables_dev = None
        scatter_impl = (self._scatter_impl_q if kv_dtype == "int8"
                        else self._scatter_impl)
        self._scatter = sharding.sharded_jit(scatter_impl, mesh, rules,
                                             donate_argnums=(0,))
        # KV handoff (disaggregated prefill/decode): gather reads block
        # contents out (no donation — the pool stays live), the block
        # scatter writes an imported payload into freshly allocated ids
        self._gather = sharding.sharded_jit(self._gather_impl, mesh, rules)
        self._scatter_blocks = sharding.sharded_jit(
            self._scatter_blocks_impl, mesh, rules, donate_argnums=(0,))

    # ------------------------------------------------------------ tables
    def tables_device(self) -> jax.Array:
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self.tables)
        return self._tables_dev

    def _touch_tables(self):
        self._tables_dev = None

    # ------------------------------------------------------------ slots
    def allocate(self, rid: str) -> Optional[int]:
        if not self.free:
            return None
        slot = self.free.popleft()
        self.slot_owner[slot] = rid
        return slot

    def release(self, slot: int):
        self.slot_owner.pop(slot, None)
        ids = self.seq_blocks.pop(slot, [])
        if ids:
            self.bp.decref(ids)
        self.tables[slot, :] = NULL_BLOCK
        self._touch_tables()
        self.lengths[slot] = 1
        self.free.append(slot)

    @property
    def active_slots(self) -> List[int]:
        return sorted(self.slot_owner)

    # ------------------------------------------------------------ blocks
    def blocks_for(self, tokens: int) -> int:
        return -(-tokens // self.block_size)

    def ensure_capacity(self, slot: int, new_len: int) -> bool:
        """Allocate blocks so positions ``[0, new_len)`` are addressable.
        False when the pool cannot supply them (caller reclaims/preempts)."""
        have = self.seq_blocks.setdefault(slot, [])
        need = self.blocks_for(new_len)
        if need <= len(have):
            return True
        if need > self.blocks_per_seq:
            return False
        ids = self.bp.alloc(need - len(have))
        if ids is None:
            return False
        self.tables[slot, len(have):need] = ids
        have.extend(ids)
        self._touch_tables()
        return True

    def adopt_prefix(self, slot: int, ids: Sequence[int], length: int):
        """Copy-free prefix hit: splice shared physical blocks into this
        slot's table (refcount bump — the blocks themselves never move)."""
        assert length == len(ids) * self.block_size, "whole blocks only"
        assert not self.seq_blocks.get(slot), "adopt into a fresh slot"
        self.bp.incref(ids)
        self.seq_blocks[slot] = list(ids)
        self.tables[slot, :len(ids)] = ids
        self._touch_tables()
        self.lengths[slot] = length

    def block_ids(self, slot: int) -> List[int]:
        return list(self.seq_blocks.get(slot, []))

    def trim(self, slot: int, length: int):
        """Roll back a speculative over-allocation: decref blocks past
        ``blocks_for(length)`` and null their table entries.

        The speculative verify step writes k+1 tail positions before
        knowing how many survive accept/reject, so the scheduler grows
        every slot to ``len + k + 1`` up front and trims back to the
        accepted length here.  Only *privately allocated* tail blocks
        can be freed: a slot's length never shrinks below its adopted
        prefix (whole blocks, written strictly before any speculation),
        so shared blocks are never decref'd past their adoption."""
        have = self.seq_blocks.get(slot)
        if not have:
            return
        keep = self.blocks_for(max(int(length), 1))
        if keep >= len(have):
            return
        extra = have[keep:]
        del have[keep:]
        self.bp.decref(extra)
        self.tables[slot, keep:keep + len(extra)] = NULL_BLOCK
        self._touch_tables()

    # ------------------------------------------------------------ prefill
    def _blocked(self, src, ax, nblk):
        """A single-sequence prefill cache leaf (…, 1, S, *inner), padded
        or cut to ``nblk`` whole blocks and split into them: (…, nblk,
        block_size, *inner)."""
        bi, ki = ax.index("act_batch"), ax.index("act_kvseq")
        span = nblk * self.block_size
        if src.shape[ki] < span:
            pads = [(0, 0)] * src.ndim
            pads[ki] = (0, span - src.shape[ki])
            src = jnp.pad(src, pads)
        idx = [slice(None)] * src.ndim
        idx[ki] = slice(0, span)
        src = src[tuple(idx)]
        shape = list(src.shape)
        shape[bi:ki + 1] = [nblk, self.block_size]
        return src.reshape(shape)

    @staticmethod
    def _write_blocks(dst, src, ax, ids):
        """``dst`` with the blocks ``ids`` set to ``src`` (…, len(ids),
        block_size, *inner), reshaped at the write to the pool leaf's
        own layout (a GQA position's heads flat)."""
        bi = ax.index("act_batch")
        src = jnp.moveaxis(src, bi, 0)
        d = jnp.moveaxis(dst, bi, 0)
        src = src.reshape(src.shape[:bi + 2] + d.shape[bi + 2:])
        return jnp.moveaxis(d.at[ids].set(src.astype(dst.dtype)), 0, bi)

    def _scatter_impl(self, pool, prefill_cache, ids):
        """Write a single-sequence prefill cache (1, S, ...) into the
        ``len(ids)`` physical blocks named by ``ids``."""
        nblk = ids.shape[0]

        def one(leaves, ax):
            dst, src = leaves
            return self._write_blocks(
                dst, self._blocked(src.astype(dst.dtype), ax, nblk), ax,
                ids)

        out = tree_multi(one, [pool, prefill_cache], self._cache_axes)
        return constrain_cache(out, self._axes)

    def _scatter_impl_q(self, pool, prefill_cache, ids):
        """Int8 variant of :meth:`_scatter_impl`: quantize the dense
        (bf16) prefill cache into the int8 pool at write time, computing
        each block's symmetric scale over everything the scale leaf does
        not index (in-block positions and feature dims; per KV head when
        the leaf has a head axis).  The prefill cache carries no scale
        leaves — they are derived here."""
        nblk = ids.shape[0]

        def qone(dst, sc, src, ax, sc_ax):
            src = self._blocked(src.astype(jnp.float32), ax, nblk)
            # after the split the act_batch slot is the block axis and
            # the act_kvseq slot the in-block position; reduce the scale
            # over every axis the scale leaf does not keep
            ki = ax.index("act_kvseq")
            red = tuple(i for i, a in enumerate(ax) if i == ki or a not in
                        ("layers", "act_batch", "act_heads"))
            s_kd = jnp.max(jnp.abs(src), axis=red, keepdims=True) / 127.0
            q = jnp.clip(jnp.round(src / jnp.maximum(s_kd, 1e-12)),
                         -127, 127)
            return (self._write_blocks(dst, q, ax, ids),
                    self._write_blocks(sc, jnp.squeeze(s_kd, axis=red),
                                       sc_ax, ids))

        def walk(pl, pc, ax, dax):
            if isinstance(pl, dict):
                if any(k.endswith("_scale") for k in pl):
                    out: Dict[str, Any] = {}
                    for k in pl:
                        if k.endswith("_scale"):
                            continue
                        out[k], out[k + "_scale"] = qone(
                            pl[k], pl[k + "_scale"], pc[k], dax[k],
                            ax[k + "_scale"])
                    return out
                return {k: walk(pl[k], pc[k], ax[k], dax[k]) for k in pl}
            if isinstance(pl, list):
                return [walk(*a) for a in zip(pl, pc, ax, dax)]
            raise TypeError("int8 pool leaf without a scale sibling")

        out = walk(pool, prefill_cache, self._axes, self._cache_axes)
        return constrain_cache(out, self._axes)

    def insert_prefill(self, slot: int, prefill_cache, length: int):
        """Scatter a prefill cache for positions ``[0, length)`` into the
        slot's (already allocated) blocks.  Positions past ``length``
        inside the last block hold padding until decode overwrites them;
        attention masks them via ``lengths``."""
        nblk = self.blocks_for(length)
        ids = self.seq_blocks.get(slot, [])
        assert len(ids) >= nblk, "ensure_capacity() before insert_prefill()"
        self.pool = self._scatter(self.pool, prefill_cache,
                                  jnp.asarray(ids[:nblk], jnp.int32))
        self.lengths[slot] = length

    # ------------------------------------------------------------ handoff
    def _gather_impl(self, pool, ids):
        """Read the ``len(ids)`` physical blocks named by ``ids`` out of
        the pool, block axis first and each position's KV heads split out
        of the flat GQA leaf, as the dense cache holds them — the exact
        inverse of :meth:`_scatter_blocks_impl`."""
        def one(arr, ax):
            out = jnp.moveaxis(arr, ax.index("act_batch"), 0)[ids]
            if "act_kvseq" in ax and ax[-1] == "act_heads":
                out = out.reshape(out.shape[:-1]
                                  + (self.cfg.num_kv_heads, -1))
            return out

        return tree_walk(one, pool, self._axes)

    def _scatter_blocks_impl(self, pool, blocks, ids):
        """Write block-major payloads (leaf ``(len(ids), ...)``) into the
        physical blocks named by ``ids``."""
        def one(leaves, ax):
            dst, src = leaves
            return self._write_blocks(
                dst, jnp.moveaxis(src, 0, ax.index("act_batch")), ax, ids)

        out = tree_multi(one, [pool, blocks], self._axes)
        return constrain_cache(out, self._axes)

    def export_kv(self, rid: str) -> KVHandoff:
        """Export request ``rid``'s finished-prefill KV as a host-side
        :class:`KVHandoff` (block contents + length).  The slot keeps
        its blocks — the caller releases it after the export so a failed
        handoff never loses the KV mid-flight."""
        slot = next((s for s, r in self.slot_owner.items() if r == rid),
                    None)
        if slot is None:
            raise KeyError(f"export_kv: no slot owned by {rid!r}")
        length = int(self.lengths[slot])
        nblk = self.blocks_for(length)
        ids = self.seq_blocks.get(slot, [])[:nblk]
        assert len(ids) == nblk, "slot blocks do not cover its length"
        blocks = jax.device_get(
            self._gather(self.pool, jnp.asarray(ids, jnp.int32)))
        return KVHandoff(request_id=rid, length=length,
                         block_size=self.block_size, n_blocks=nblk,
                         blocks=blocks)

    def import_kv(self, slot: int, handoff: KVHandoff,
                  adopted_ids: Sequence[int] = (),
                  adopted_tokens: int = 0) -> bool:
        """Import a :class:`KVHandoff` into a fresh slot: allocate pool
        blocks for the payload (through :meth:`BlockPool.alloc`, so
        imported blocks are charged to the pool's peak accounting like
        any other allocation), scatter the block contents, and splice
        the table.  ``adopted_ids`` names shared-prefix blocks the
        decode-side radix tree already holds — those are refcount-spliced
        (:meth:`adopt_prefix`) instead of re-uploaded, and only the
        payload tail past ``adopted_tokens`` moves.

        Returns False when the pool cannot supply the private blocks;
        the caller must then roll back by releasing the slot (which
        decrefs any adopted prefix) and defer the handoff."""
        if handoff.block_size != self.block_size:
            raise ValueError(
                f"handoff block size {handoff.block_size} != pool block "
                f"size {self.block_size} — repack before migrating")
        if adopted_ids:
            self.adopt_prefix(slot, adopted_ids, adopted_tokens)
        if not self.ensure_capacity(slot, handoff.length):
            return False
        k0 = len(adopted_ids)
        if handoff.n_blocks > k0:
            ids = self.seq_blocks[slot][k0:handoff.n_blocks]
            tail = tree_walk(lambda a, ax: jnp.asarray(a[k0:]),
                             handoff.blocks, self._axes)
            self.pool = self._scatter_blocks(
                self.pool, tail, jnp.asarray(ids, jnp.int32))
        self.lengths[slot] = handoff.length
        return True

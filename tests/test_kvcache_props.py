"""Property tests for the paged-KV bookkeeping invariants.

Driven by hypothesis (the real package in CI; tests/_hypothesis_shim.py
in containers without it — keyword strategies only, deterministic seed).
Each test interprets a generated op script against the allocator and
checks the documented invariants after *every* op, not just at the end:

- refcounts are never negative (structurally: a tracked block's count is
  always >= 1, and the multiset of outstanding holds equals ``refs``);
- free + used + null == capacity, always;
- a block is never simultaneously free and allocated, and the null
  block is never handed out;
- ``adopt_prefix``/``trim``/``release`` round-trip: adopted (shared)
  blocks survive trim and release, privately grown tails are returned.
"""
from collections import Counter

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, scaled_down
from repro.serving.kvcache import (NULL_BLOCK, BlockLedger, BlockPool,
                                   PagedCacheSlots)

CFG = scaled_down(get_config("qwen1.5-4b"), num_layers=2, d_model=32,
                  d_ff=64, vocab_size=64, num_heads=2, num_kv_heads=2,
                  head_dim=8)


def _pool_invariants(bp: BlockPool, held: Counter):
    # free + used + null == capacity
    assert bp.num_free + bp.num_used + 1 == bp.num_blocks
    # refcounts never negative / never zero-but-tracked
    assert all(r >= 1 for r in bp.refs.values())
    # the allocator's view matches the holders' view exactly
    assert dict(held) == bp.refs
    # no block is both free and allocated; null is neither
    free = set(bp.free)
    assert not (free & set(bp.refs))
    assert NULL_BLOCK not in free and NULL_BLOCK not in bp.refs
    assert bp.peak_used >= bp.num_used


@settings(max_examples=30)
@given(num_blocks=st.integers(min_value=2, max_value=33),
       ops=st.lists(st.integers(min_value=0, max_value=10_000),
                    min_size=1, max_size=80))
def test_blockpool_random_walk(num_blocks, ops):
    bp = BlockPool(num_blocks)
    held = Counter()          # multiset of (block -> outstanding refs)
    order = []                # flat list for pseudo-random pick
    for op in ops:
        kind = op % 3
        if kind == 0:                                   # alloc n blocks
            n = (op // 3) % 4 + 1
            ids = bp.alloc(n)
            if ids is None:
                # all-or-nothing: a failed alloc changed nothing
                assert n > bp.num_free
            else:
                assert len(ids) == len(set(ids)) == n
                assert NULL_BLOCK not in ids
                held.update(ids)
                order.extend(ids)
        elif kind == 1 and order:                       # incref a holder
            b = order[op % len(order)]
            bp.incref([b])
            held[b] += 1
            order.append(b)
        elif kind == 2 and order:                       # decref a holder
            b = order.pop(op % len(order))
            bp.decref([b])
            held[b] -= 1
            if not held[b]:
                del held[b]
        _pool_invariants(bp, held)
    # drain every outstanding ref: the pool must come back whole
    bp.decref(list(order))
    assert bp.num_used == 0
    assert bp.num_free == bp.num_blocks - 1


@settings(max_examples=20)
@given(num_blocks=st.integers(min_value=2, max_value=9),
       extra=st.integers(min_value=0, max_value=5))
def test_blockpool_alloc_all_or_nothing(num_blocks, extra):
    bp = BlockPool(num_blocks)
    assert bp.alloc(bp.num_free + 1 + extra) is None
    assert bp.num_free == num_blocks - 1        # failed alloc is a no-op
    ids = bp.alloc(bp.num_free)                 # exact drain succeeds
    assert ids is not None and bp.num_free == 0
    bp.decref(ids)
    assert bp.num_free == num_blocks - 1


def test_blockpool_unallocated_ids_raise():
    bp = BlockPool(4)
    with pytest.raises(ValueError):
        bp.incref([2])
    with pytest.raises(ValueError):
        bp.decref([2])
    with pytest.raises(ValueError):
        bp.incref([NULL_BLOCK])
    with pytest.raises(ValueError):
        BlockPool(1)                            # nothing allocatable


@settings(max_examples=30)
@given(capacity=st.integers(min_value=1, max_value=40),
       block=st.sampled_from([1, 4, 16]),
       ops=st.lists(st.integers(min_value=0, max_value=10_000),
                    min_size=1, max_size=60))
def test_blockledger_random_walk(capacity, block, ops):
    led = BlockLedger(capacity * block, block_size=block)
    shadow = {}                                 # rid -> blocks held
    for op in ops:
        rid = f"r{op % 5}"
        kind = op % 3
        tokens = (op // 7) % (capacity * block + 1)
        if kind == 0:
            if led.can_admit(rid, tokens):
                led.admit(rid, tokens)
                shadow[rid] = led.blocks_for(tokens)
            else:
                with pytest.raises(RuntimeError):
                    led.admit(rid, tokens)
        elif kind == 1:
            need = led.blocks_for(tokens)
            held = shadow.get(rid, 0)
            if need - held <= led.free_blocks:
                led.grow(rid, tokens)
                if need > held:        # grow-to-less is a recorded no-op
                    shadow[rid] = need
            else:
                with pytest.raises(RuntimeError):
                    led.grow(rid, tokens)
        else:
            led.release(rid)
            shadow.pop(rid, None)
        # never over-committed, and accounting matches the shadow model
        assert led.free_blocks >= 0
        assert led.free_blocks == led.total_blocks - sum(shadow.values())
        assert led.used == shadow
        assert led.peak_blocks <= led.total_blocks


def _slots(pool_blocks=12, block_size=4):
    return PagedCacheSlots(CFG, max_batch=2, capacity=32,
                           block_size=block_size,
                           pool_tokens=pool_blocks * block_size)


@pytest.fixture(scope="module", autouse=True)
def _pool_shapes_allocated_once():
    """Allocate every pool shape the property tests use before their
    first example: the first allocation of a shape compiles, a one-time
    cost that hypothesis's per-example deadline would charge to whichever
    example happens to run first."""
    _slots()
    _slots(pool_blocks=4, block_size=4)
    _slots8()


@settings(max_examples=15)
@given(grow_to=st.integers(min_value=1, max_value=32),
       trim_to=st.integers(min_value=1, max_value=32))
def test_paged_slots_grow_trim_roundtrip(grow_to, trim_to):
    s = _slots()
    slot = s.allocate("req")
    assert s.ensure_capacity(slot, grow_to)
    bp = s.bp
    assert len(s.seq_blocks[slot]) == s.blocks_for(grow_to)
    s.trim(slot, min(trim_to, grow_to))
    keep = s.blocks_for(max(min(trim_to, grow_to), 1))
    kept = s.seq_blocks[slot]
    # trim keeps exactly the blocks covering the surviving length...
    assert len(kept) == min(keep, s.blocks_for(grow_to))
    # ...nulls the vacated table tail, and keeps table/seq_blocks aligned
    assert list(s.tables[slot, :len(kept)]) == kept
    assert all(b == NULL_BLOCK for b in s.tables[slot, len(kept):])
    assert bp.num_free + bp.num_used + 1 == bp.num_blocks
    s.release(slot)
    assert bp.num_used == 0                     # release returns it all
    assert s.lengths[slot] == 1                 # inert again


@settings(max_examples=15)
@given(nadopt=st.integers(min_value=1, max_value=4),
       extra_tokens=st.integers(min_value=0, max_value=16))
def test_paged_slots_adopt_is_refcounted_and_trim_safe(nadopt, extra_tokens):
    s = _slots()
    bp = s.bp
    # simulate the radix tree holding nadopt whole prompt blocks
    tree_ids = bp.alloc(nadopt)
    adopted_len = nadopt * s.block_size
    slot = s.allocate("req")
    s.adopt_prefix(slot, tree_ids, adopted_len)
    assert all(bp.refs[b] == 2 for b in tree_ids)     # tree + slot
    assert s.lengths[slot] == adopted_len
    # grow privately past the adopted prefix, then trim back to it:
    # shared blocks must never be freed by a speculative rollback
    assert s.ensure_capacity(slot, adopted_len + extra_tokens)
    s.trim(slot, adopted_len)
    assert s.seq_blocks[slot] == list(tree_ids)
    assert all(bp.refs[b] == 2 for b in tree_ids)
    # release drops the slot's ref; the tree's ref keeps the blocks live
    s.release(slot)
    assert all(bp.refs[b] == 1 for b in tree_ids)
    assert bp.num_used == nadopt
    bp.decref(tree_ids)                                # tree eviction
    assert bp.num_used == 0
    assert bp.num_free + bp.num_used + 1 == bp.num_blocks


@settings(max_examples=10)
@given(lens=st.lists(st.integers(min_value=1, max_value=24),
                     min_size=1, max_size=2))
def test_paged_slots_exhaustion_is_explicit(lens):
    s = _slots(pool_blocks=4, block_size=4)
    slots = []
    for i, ln in enumerate(lens):
        sl = s.allocate(f"r{i}")
        ok = s.ensure_capacity(sl, ln)
        if not ok:
            # a refused grow changed nothing: invariant still holds and
            # the slot can still be released cleanly
            assert s.bp.num_free + s.bp.num_used + 1 == s.bp.num_blocks
        slots.append(sl)
    for sl in slots:
        s.release(sl)
    assert s.bp.num_used == 0


# ---------------------------------------------------------------- int8
import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M


def _slots8(pool_blocks=12, block_size=4):
    return PagedCacheSlots(CFG, max_batch=2, capacity=32,
                           block_size=block_size,
                           pool_tokens=pool_blocks * block_size,
                           kv_dtype="int8")


def _pool_bytes(s):
    return sum(x.nbytes for x in jax.tree.leaves(s.pool))


@pytest.mark.parametrize("pool_blocks,block_size", [(12, 4), (8, 8)])
def test_int8_pool_accounting(pool_blocks, block_size):
    """Same pool_tokens budget: int8 carries 2x the allocatable blocks
    at ~half the per-block bytes (int8 payload + f32 scale sliver)."""
    b16 = _slots(pool_blocks, block_size)
    i8 = _slots8(pool_blocks, block_size)
    assert i8.bp.num_blocks - 1 == 2 * (b16.bp.num_blocks - 1)
    ratio = ((_pool_bytes(i8) / i8.bp.num_blocks)
             / (_pool_bytes(b16) / b16.bp.num_blocks))
    assert 0.45 < ratio < 0.6
    # payload leaves are int8, every one paired with a f32 scale leaf
    seen_scale = False
    for part in i8.pool.values():
        for k, leaf in part.items():
            if k.endswith("_scale"):
                assert leaf.dtype == jnp.float32
                seen_scale = True
            else:
                assert leaf.dtype == jnp.int8
                assert f"{k}_scale" in part
    assert seen_scale


@settings(max_examples=10)
@given(grow_to=st.integers(min_value=1, max_value=32),
       trim_to=st.integers(min_value=1, max_value=32))
def test_int8_slots_grow_trim_roundtrip(grow_to, trim_to):
    """Allocator invariants are dtype-blind: the bf16 grow/trim/release
    round-trip holds verbatim on an int8 pool."""
    s = _slots8()
    slot = s.allocate("req")
    assert s.ensure_capacity(slot, grow_to)
    bp = s.bp
    assert len(s.seq_blocks[slot]) == s.blocks_for(grow_to)
    s.trim(slot, min(trim_to, grow_to))
    kept = s.seq_blocks[slot]
    assert list(s.tables[slot, :len(kept)]) == kept
    assert all(b == NULL_BLOCK for b in s.tables[slot, len(kept):])
    assert bp.num_free + bp.num_used + 1 == bp.num_blocks
    s.release(slot)
    assert bp.num_used == 0
    assert s.lengths[slot] == 1


def test_int8_prefill_gather_roundtrip():
    """insert_prefill quantizes; export_kv gathers the int8 blocks plus
    scales; dequantizing recovers the source within the symmetric
    per-block error bound (<= block_scale / 2 <= global_max / 254)."""
    L, bs = 12, 4
    params = M.init(CFG, jax.random.PRNGKey(0), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, L), 1,
                              CFG.vocab_size).astype(jnp.int32)
    _, cache, _ = M.prefill(CFG, params, {
        "tokens": toks, "prompt_lengths": jnp.full((1,), L, jnp.int32)})
    s = _slots8(block_size=bs)
    slot = s.allocate("req")
    assert s.ensure_capacity(slot, L)
    s.insert_prefill(slot, cache, L)
    hand = s.export_kv("req")
    assert hand.length == L
    for part in hand.blocks.values():
        for k, leaf in part.items():
            assert leaf.dtype == (jnp.float32 if k.endswith("_scale")
                                  else jnp.int8)
    st_blocks = hand.blocks["stack"]
    for name in ("k", "v"):
        q = np.asarray(st_blocks[name], np.float32)      # (nb,l,bs,KV,D)
        sc = np.asarray(st_blocks[f"{name}_scale"])      # (nb,l,KV)
        deq = (q * sc[:, :, None, :, None]).transpose(1, 0, 2, 3, 4)
        deq = deq.reshape(q.shape[1], -1, q.shape[3], q.shape[4])[:, :L]
        src = np.asarray(cache["stack"][name][:, 0, :L], np.float32)
        err = float(np.max(np.abs(deq - src)))
        assert err <= float(np.max(np.abs(src))) / 250.0

"""Multi-tenant LoRA adapter pool for the serving engine (S-LoRA/Punica
style, the missing link between ``finetune/`` and ``serving/``).

The paper's platform serves *one* base model to many tenants, each with
their own fine-tuned adapter, on one GPU pool.  ``lora_merge`` forfeits
that: it bakes a single tenant's adapter into the weights, so every
tenant needs a full model replica.  The :class:`AdapterPool` instead
keeps the base weights shared and holds up to ``slots`` adapters
*stacked* on device:

- Per target weight (``wq``/``wk``/``wv``/``wo``, MLA's ``wuq``/
  ``wuk``/``wuv``) the pool owns one pair of stacked tensors
  ``A: (L, K, d_in, r_bucket)`` / ``B: (L, K, r_bucket, d_out)`` (layer
  axis matching the model's ``lax.scan`` stacks; ``K = slots + 1``).
- **Index 0 is the base model**: an all-zero adapter, so a decode batch
  mixing base-model rows with several tenants' adapter rows runs in ONE
  fused step — each row gathers its own A/B pair by index
  (``models.attention.lora_shift``), no weight merging, no per-tenant
  batch splitting.
- **Rank bucketing**: every adapter's rank is zero-padded to
  ``rank_bucket`` so all adapters share one gatherable stack and the
  decode step compiles once.  The ``alpha/rank`` scale is folded into B
  at registration, so the apply path is scale-free.
- **Ref-counting + LRU**: ``acquire`` pins an adapter while any request
  using it is in flight; eviction (to make room for a newly acquired
  adapter) only ever picks an *unpinned* resident, least-recently-used
  first.  Evicted adapters keep their host-side copy and reload on the
  next ``acquire`` — registration is not residency.

Gating mirrors the paged-KV path: uniform GQA/MLA attention stacks
(``supports_multi_lora``).
"""
from __future__ import annotations

import re
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.finetune.lora import DEFAULT_TARGETS, LoraConfig, lora_unflatten

_KEY_RE = re.compile(r"\[(?:'([^']+)'|(\d+))\]")


def supports_multi_lora(cfg: ModelConfig) -> bool:
    """True iff batched multi-LoRA decode is available: uniform GQA/MLA
    attention stacks (same shape of gating as ``supports_paged_cache``;
    SSM/hybrid mixers, encoder-decoder, and vision-prefixed models keep
    the merge-and-deploy path)."""
    from repro.models.model import stack_plan
    if getattr(cfg, "is_encoder_decoder", False):
        return False
    if getattr(cfg, "frontend", "text") == "vision":
        return False
    plan = stack_plan(cfg)
    return plan["kind"] == "uniform" and plan["mixer"] in ("gqa", "mla")


def adapter_namespace(namespace: str, adapter: str) -> str:
    """Prefix-cache namespace for a request: KV produced under an adapter
    is only valid for that adapter, so adapter'd requests get their own
    radix tree ('<tenant>//lora:<adapter>') and can never exchange cached
    KV with the base model or another adapter."""
    return f"{namespace}//lora:{adapter}" if adapter else namespace


def _parse_keystr(ks: str) -> Tuple:
    """``"['stack']['mixer']['wq']"`` -> ``("stack", "mixer", "wq")``
    (int for sequence indices) — inverts ``jax.tree_util.keystr``."""
    out: List[Any] = []
    for name, idx in _KEY_RE.findall(ks):
        out.append(name if name else int(idx))
    return tuple(out)


def _path_tuple(path) -> Tuple:
    out: List[Any] = []
    for e in path:
        if hasattr(e, "key"):
            out.append(e.key)
        elif hasattr(e, "idx"):
            out.append(e.idx)
        else:  # GetAttrKey etc.
            out.append(str(e))
    return tuple(out)


class AdapterPoolFull(RuntimeError):
    pass


class AdapterPool:
    """Device-resident stack of LoRA adapters over one base model.

    ``slots`` adapters can be resident at once (plus the implicit base at
    index 0); any number can be *registered* (host copies).  ``targets``
    defaults to the attention projections of ``finetune.lora``.
    """

    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 rank_bucket: int = 8, dtype=jnp.float32,
                 targets: Sequence[str] = DEFAULT_TARGETS):
        if not supports_multi_lora(cfg):
            raise ValueError(
                f"{cfg.name}: multi-LoRA serving needs a uniform GQA/MLA "
                "attention stack (merge-and-deploy still works)")
        if slots < 1:
            raise ValueError("pool needs at least one adapter slot")
        self.cfg = cfg
        self.slots = slots
        self.rank_bucket = rank_bucket
        self.dtype = dtype
        self.targets_allowed = tuple(targets)
        # target map: path tuple -> dict(kaxis, a_shape, b_shape) where
        # shapes are the *padded* per-adapter shapes (no K axis)
        self._targets: Dict[Tuple, Dict[str, Any]] = {}
        for path, leaf in jax.tree.flatten_with_path(params)[0]:
            pt = _path_tuple(path)
            if pt[-1] not in self.targets_allowed or leaf.ndim < 2:
                continue
            if leaf.ndim > 3:
                continue  # e.g. stacked MoE experts — not a LoRA target
            *batch, din, dout = leaf.shape
            ka = len(batch)  # 1 under a scanned stack, 0 for "first"
            self._targets[pt] = {
                "kaxis": ka,
                "a_shape": tuple(batch) + (din, rank_bucket),
                "b_shape": tuple(batch) + (rank_bucket, dout),
            }
        if not self._targets:
            raise ValueError("no LoRA-targetable params found")
        K = slots + 1
        self._lora = self._build_tree(
            lambda m: {"a": jnp.zeros(self._with_k(m["a_shape"],
                                                   m["kaxis"], K), dtype),
                       "b": jnp.zeros(self._with_k(m["b_shape"],
                                                   m["kaxis"], K), dtype)})
        self._kaxes = self._build_tree(lambda m: m["kaxis"])
        self._write = jax.jit(self._write_impl, donate_argnums=(0,))
        # host registry + residency bookkeeping
        self._host: Dict[str, Dict[Tuple, Dict[str, np.ndarray]]] = {}
        self._resident: "OrderedDict[str, int]" = OrderedDict()
        self._refs: Dict[str, int] = {}
        self._free: List[int] = list(range(1, K))
        self.loads = 0
        self.evictions = 0
        self.acquire_waits = 0

    # ------------------------------------------------------------ tree
    @staticmethod
    def _with_k(shape: Tuple[int, ...], kaxis: int, K: int):
        return shape[:kaxis] + (K,) + shape[kaxis:]

    def _build_tree(self, fn):
        """Materialize ``fn(meta)`` at every target path, nested like the
        params tree (dicts for names, lists for layer indices)."""
        root: Dict[str, Any] = {}
        for pt, meta in self._targets.items():
            node = root
            for i, k in enumerate(pt[:-1]):
                nxt = pt[i + 1]
                if isinstance(k, int):
                    while len(node) <= k:
                        node.append({} if not isinstance(nxt, int) else [])
                    node = node[k]
                else:
                    if k not in node:
                        node[k] = [] if isinstance(nxt, int) else {}
                    node = node[k]
            node[pt[-1]] = fn(meta)
        return root

    def _write_impl(self, tree, upd, idx):
        """Set adapter ``idx``'s A/B pair at every target (jitted, pool
        donated — an in-place load, not a copy of the whole stack)."""
        def walk(t, u, ka):
            if isinstance(t, dict) and set(t) == {"a", "b"} \
                    and not isinstance(ka, dict):
                out = {}
                for key in ("a", "b"):
                    arr = jnp.moveaxis(t[key], ka, 0)
                    arr = arr.at[idx].set(u[key].astype(arr.dtype))
                    out[key] = jnp.moveaxis(arr, 0, ka)
                return out
            if isinstance(t, dict):
                return {k: walk(t[k], u[k], ka[k]) for k in t}
            return [walk(x, y, z) for x, y, z in zip(t, u, ka)]

        return walk(tree, upd, self._kaxes)

    def lora_tree(self):
        """Current device adapter stacks — pass to
        ``model.decode_step(..., lora=...)`` with per-row adapter ids."""
        return self._lora

    # ------------------------------------------------------------ admin
    def register(self, name: str, adapters: Dict, lcfg: LoraConfig):
        """Upload a trained adapter under ``name`` (host copy; it becomes
        device-resident on first :meth:`acquire`).

        ``adapters`` is either the nested dict from ``lora_init``/SFT
        ({keystr: {"a", "b"}}) or the flat ``lora_export`` form
        ({"<keystr>.a": arr}).  Ranks are padded to ``rank_bucket``; the
        ``alpha/rank`` scale is folded into B.  Unsupported targets (e.g.
        MLP ``gate``/``up``/``down``) raise — silently dropping them
        would serve a *different* model than the tenant trained.
        """
        if not name:
            raise ValueError("adapter name must be non-empty")
        if self._refs.get(name, 0) > 0:
            raise ValueError(f"adapter {name!r} is pinned by in-flight "
                             "requests; cannot re-register")
        if any(k.endswith(".a") or k.endswith(".b") for k in adapters):
            adapters = lora_unflatten(adapters)   # stored-artifact form
        nested = {k: dict(v) for k, v in adapters.items()}
        host: Dict[Tuple, Dict[str, np.ndarray]] = {}
        for ks, ab in nested.items():
            pt = _parse_keystr(ks)
            meta = self._targets.get(pt)
            if meta is None:
                raise ValueError(
                    f"adapter {name!r} targets {ks} which this pool does "
                    f"not serve (targets: {sorted(self.targets_allowed)})")
            a = np.asarray(ab["a"], np.float32)
            b = np.asarray(ab["b"], np.float32)
            r = a.shape[-1]
            if r > self.rank_bucket:
                raise ValueError(
                    f"adapter {name!r} rank {r} exceeds the pool's rank "
                    f"bucket {self.rank_bucket}")
            want_a = meta["a_shape"][:-1] + (r,)
            want_b = meta["b_shape"][:-2] + (r,) + meta["b_shape"][-1:]
            if a.shape != want_a or b.shape != want_b:
                raise ValueError(
                    f"adapter {name!r} shape mismatch at {ks}: "
                    f"A{a.shape}/B{b.shape} vs A{want_a}/B{want_b}")
            pad_a = [(0, 0)] * a.ndim
            pad_a[-1] = (0, self.rank_bucket - r)
            pad_b = [(0, 0)] * b.ndim
            pad_b[-2] = (0, self.rank_bucket - r)
            host[pt] = {"a": np.pad(a, pad_a),
                        "b": np.pad(b, pad_b) * lcfg.scale}
        if not host:
            raise ValueError(f"adapter {name!r} is empty")
        self._host[name] = host
        if name in self._resident:     # hot re-register (e.g. retrain)
            self._load(name, self._resident[name])

    def deregister(self, name: str):
        """Forget ``name`` entirely (host copy and residency)."""
        if self._refs.get(name, 0) > 0:
            raise ValueError(f"adapter {name!r} is pinned; cannot "
                             "deregister")
        self._host.pop(name, None)
        idx = self._resident.pop(name, None)
        self._refs.pop(name, None)
        if idx is not None:
            self._free.append(idx)

    def has(self, name: str) -> bool:
        return name in self._host

    @property
    def registered(self) -> List[str]:
        return sorted(self._host)

    @property
    def resident(self) -> List[str]:
        return list(self._resident)

    # ------------------------------------------------------------ runtime
    def _load(self, name: str, idx: int):
        upd = self._build_tree(lambda m: {
            "a": jnp.zeros(m["a_shape"], self.dtype),
            "b": jnp.zeros(m["b_shape"], self.dtype)})
        for pt, ab in self._host[name].items():
            node = upd
            for k in pt[:-1]:
                node = node[k]
            node[pt[-1]] = {"a": jnp.asarray(ab["a"], self.dtype),
                            "b": jnp.asarray(ab["b"], self.dtype)}
        self._lora = self._write(self._lora, upd,
                                 jnp.asarray(idx, jnp.int32))
        self.loads += 1

    def acquire(self, name: str) -> Optional[int]:
        """Pin ``name`` and return its device index (the per-row adapter
        id for the decode batch).  Loads it into a free slot — evicting
        the LRU *unpinned* resident if needed — or returns ``None`` when
        every slot is pinned by in-flight requests (caller retries later).
        Raises ``KeyError`` for names never registered."""
        if name not in self._host:
            raise KeyError(f"unknown adapter {name!r}")
        if name in self._resident:
            self._resident.move_to_end(name)
            self._refs[name] = self._refs.get(name, 0) + 1
            return self._resident[name]
        if not self._free:
            victim = next((n for n in self._resident
                           if self._refs.get(n, 0) == 0), None)
            if victim is None:
                self.acquire_waits += 1
                return None
            self._free.append(self._resident.pop(victim))
            self.evictions += 1
        idx = self._free.pop()
        self._load(name, idx)
        self._resident[name] = idx
        self._refs[name] = self._refs.get(name, 0) + 1
        return idx

    def release(self, name: str):
        """Unpin one in-flight use (the adapter stays resident — warm for
        the tenant's next request — until LRU eviction needs the slot).
        Raises on an unbalanced release — like ``BlockPool.decref``, a
        refcount bug must surface immediately: silently under-counting
        would let eviction reload another tenant's weights into a device
        index a running request still decodes with."""
        if self._refs.get(name, 0) <= 0:
            raise ValueError(f"release of unpinned adapter {name!r}")
        self._refs[name] -= 1

    def stats(self) -> Dict[str, int]:
        return {"registered": len(self._host),
                "resident": len(self._resident),
                "pinned": sum(1 for n, r in self._refs.items() if r > 0),
                "slots": self.slots,
                "loads": self.loads,
                "evictions": self.evictions,
                "acquire_waits": self.acquire_waits}

    def collect_metrics(self, reg) -> None:
        """Pull adapter-pool residency/churn into a metrics registry:
        slot residency gauges plus load/evict/acquire-wait counters
        (an acquire-wait is a request left queued because every device
        slot was pinned — the multi-LoRA analogue of KV exhaustion)."""
        s = self.stats()
        reg.gauge("repro_adapters_registered_count",
                  "adapters registered (host copies)").set(
            s["registered"])
        reg.gauge("repro_adapters_resident_slots",
                  "device slots holding an adapter").set(s["resident"])
        reg.gauge("repro_adapters_pinned_slots",
                  "resident adapters pinned by in-flight requests").set(
            s["pinned"])
        reg.gauge("repro_adapters_capacity_slots",
                  "device adapter slots").set(s["slots"])
        reg.counter("repro_adapters_loads_total",
                    "host->device adapter loads").set(s["loads"])
        reg.counter("repro_adapters_evictions_total",
                    "LRU evictions of unpinned residents").set(
            s["evictions"])
        reg.counter("repro_adapters_acquire_waits_total",
                    "acquires deferred because all slots were "
                    "pinned").set(s["acquire_waits"])

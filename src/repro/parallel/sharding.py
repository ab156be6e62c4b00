"""Logical-axis sharding rules.

Model code annotates parameters and activations with *logical* axis names;
a per-(shape-kind) rule table maps them onto the production mesh axes
("pod", "data", "model").  This keeps every architecture's model code
mesh-agnostic while the launcher picks DP/FSDP/TP/SP/EP layouts per shape.

Scheme (see DESIGN.md §5) — chosen so that every assigned arch divides
evenly (head counts 12..64 do not divide 16; d_model/d_ff always do):

- train/prefill: batch→data(+pod), FSDP over "data" on each param's fsdp
  dim, TP over "model" for mlp/vocab/experts, and *context-parallel*
  attention (q-sequence over "model", KV all-gathered).
- decode: batch→data(+pod), params TP over "model" replicated over "data"
  (vLLM-style replica×TP), KV-cache sequence over "model".
- long (batch=1): KV/state over ("data","model") combined, SSM heads over
  "model".
- serving_tp: single-replica tensor parallelism for the inference engine
  (serving/README.md "Sharded serving"): params TP over "model" with NO
  fsdp (weights replicated along their fsdp dim), attention head-sharded
  (act_heads -> "model", so the paged KV pool shards on its KV-head axis
  and block tables stay host-side), MLPs row/col-sharded (act_ff ->
  "model"), embeddings and logits replicated (act_vocab -> None: the
  unembed output is all-gathered once per step so sampling runs
  replicated and token-identical on every device).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class RuleSet:
    name: str
    rules: Dict[str, AxisVal]

    def resolve(self, logical: Optional[str]) -> AxisVal:
        if logical is None:
            return None
        return self.rules.get(logical, None)

    def spec(self, axes: Sequence[Optional[str]]) -> P:
        parts = []
        used = set()
        for a in axes:
            r = self.resolve(a)
            if isinstance(r, str):
                r = (r,)
            if r:
                r = tuple(x for x in r if x not in used)
                used.update(r)
                parts.append(r if len(r) > 1 else (r[0] if r else None))
                if not r:
                    parts[-1] = None
            else:
                parts.append(None)
        return P(*parts)

    def replace(self, **kw) -> "RuleSet":
        new = dict(self.rules)
        new.update(kw)
        return RuleSet(self.name, new)


_BASE = {
    # parameters
    "fsdp": "data",
    "tensor": "model",
    "expert": "model",
    "layers": None,
    # activations
    "act_batch": ("data",),
    "act_qseq": "model",
    "act_kvseq": None,
    "act_heads": None,
    "act_ff": "model",
    "act_vocab": "model",
    "act_expert": "model",
    "act_ssm_heads": "model",
    "act_embed": None,
}


def make_rules(kind: str, multi_pod: bool = False, **overrides) -> RuleSet:
    """kind: train | prefill | decode | long | serving_tp."""
    r = dict(_BASE)
    batch = ("pod", "data") if multi_pod else ("data",)
    if kind in ("train", "prefill"):
        r["act_batch"] = batch
    elif kind == "decode":
        r.update(
            fsdp=None,
            act_batch=batch,
            act_qseq=None,
            act_kvseq="model",
        )
    elif kind == "long":
        kv = ("pod", "data", "model") if multi_pod else ("data", "model")
        r.update(
            fsdp=None,
            act_batch=None,
            act_qseq=None,
            act_kvseq=kv,
        )
    elif kind == "serving_tp":
        # one sharded replica: every batch/sequence axis stays local (the
        # engine's continuous batch is one replica's traffic), parameters
        # are pure-TP over "model" (no fsdp — a serving replica gains
        # nothing from gather-per-layer), attention is head-sharded so a
        # paged pool leaf (num_blocks, block_size, KV·hd) shards on its
        # flat KV-head axis (in whole heads when KV % tp == 0) and the
        # host-side block tables are untouched, and
        # logits are replicated (one all-gather per step) so sampling is
        # identical on every device.
        r.update(
            fsdp=None,
            # expert=None routes moe_block's "auto" dispatch to the exact
            # dense impl with replicated routed experts (shared experts
            # stay TP-sharded via "tensor"/act_ff): decode tokens-per-
            # step is tiny, so EP's per-step all-to-all costs more than
            # it saves — and the dense impl is the jax<0.5-safe oracle
            expert=None,
            act_batch=None,
            act_qseq=None,
            act_kvseq=None,
            act_heads="model",
            act_ssm_heads=None,
            act_vocab=None,
            act_expert=None,
        )
    else:
        raise ValueError(kind)
    r.update(overrides)
    return RuleSet(kind, r)


# ---------------------------------------------------------------------
# context: active (mesh, rules)
class _Ctx(threading.local):
    def __init__(self):
        self.stack = []


_CTX = _Ctx()


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: RuleSet):
    _CTX.stack.append((mesh, rules))
    try:
        with mesh:
            yield
    finally:
        _CTX.stack.pop()


def active() -> Optional[Tuple[Mesh, RuleSet]]:
    return _CTX.stack[-1] if _CTX.stack else None


def current_rules() -> Optional[RuleSet]:
    a = active()
    return a[1] if a else None


def current_mesh() -> Optional[Mesh]:
    a = active()
    return a[0] if a else None


def constrain(x, axes: Sequence[Optional[str]]):
    """with_sharding_constraint against the active rules; no-op otherwise."""
    a = active()
    if a is None:
        return x
    mesh, rules = a
    spec = rules.spec(axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named(axes: Sequence[Optional[str]], mesh=None, rules=None) -> NamedSharding:
    a = active()
    mesh = mesh or (a[0] if a else None)
    rules = rules or (a[1] if a else None)
    assert mesh is not None and rules is not None, "no active sharding rules"
    return NamedSharding(mesh, rules.spec(axes))


def tree_shardings(axes_tree, mesh: Mesh, rules: RuleSet):
    """Map a tree of logical-axis tuples to NamedShardings."""
    def _one(axes):
        return NamedSharding(mesh, rules.spec(axes))
    return jax.tree.map(_one, axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            a is None or isinstance(a, str) for a in x))


def sharded_jit(fn, mesh: Optional[Mesh] = None,
                rules: Optional[RuleSet] = None, **jit_kw):
    """``jax.jit(fn)`` whose trace (and every retrace) runs under
    ``use_rules(mesh, rules)`` so the ``constrain`` calls inside model
    code bind to real NamedShardings.  With ``mesh=None`` this is plain
    ``jax.jit`` — the single-device path compiles the identical jaxpr it
    always did (``constrain`` is a no-op without an active context)."""
    if mesh is None:
        return jax.jit(fn, **jit_kw)

    def wrapped(*args):
        with use_rules(mesh, rules):
            return fn(*args)

    return jax.jit(wrapped, **jit_kw)


def mesh_axis_size(axis: AxisVal) -> int:
    mesh = current_mesh()
    if mesh is None or axis is None:
        return 1
    if isinstance(axis, str):
        axis = (axis,)
    n = 1
    for a in axis:
        n *= mesh.shape[a]
    return n

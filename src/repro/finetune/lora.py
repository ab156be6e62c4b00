"""LoRA adapters (paper §4.3: "safe-by-default" fine-tuning blueprints).

Adapters are a sparse pytree mirroring selected 2-D (or stacked 3-D)
parameter leaves; ``merge`` materializes W + (alpha/r)·A·B in compute
dtype.  Training differentiates only the adapter tree, so the base model
cannot be damaged — the mechanism behind the catastrophic-forgetting
guarantee for non-expert tenants.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp


# default targets per mixer family; attention-specific entries are simply
# absent in attention-free archs (see recipes.applicability)
DEFAULT_TARGETS = ("wq", "wk", "wv", "wo", "wuk", "wuv", "wuq")
MAMBA_TARGETS = ("wx", "wz", "wo")
MLP_TARGETS = ("gate", "up", "down")


@dataclasses.dataclass(frozen=True)
class LoraConfig:
    rank: int = 8
    alpha: float = 16.0
    targets: Tuple[str, ...] = DEFAULT_TARGETS

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


def _leaf_name(path) -> str:
    last = path[-1]
    return getattr(last, "key", str(last))


def lora_init(params, lcfg: LoraConfig, key: jax.Array,
              dtype=jnp.float32):
    """Adapters {path_str: {"a": (..., din, r), "b": (..., r, dout)}}."""
    adapters = {}
    leaves = jax.tree.flatten_with_path(params)[0]
    keys = jax.random.split(key, max(len(leaves), 1))
    for (path, leaf), k in zip(leaves, keys):
        if _leaf_name(path) not in lcfg.targets or leaf.ndim < 2:
            continue
        *batch, din, dout = leaf.shape
        a = jax.random.normal(k, (*batch, din, lcfg.rank), jnp.float32)
        a = (a / jnp.sqrt(din)).astype(dtype)
        b = jnp.zeros((*batch, lcfg.rank, dout), dtype)
        adapters[jax.tree_util.keystr(path)] = {"a": a, "b": b}
    return adapters


def lora_merge(params, adapters, lcfg: LoraConfig, dtype=None):
    """Materialize merged weights; non-target leaves pass through."""
    flat = jax.tree.flatten_with_path(params)
    out = []
    for path, leaf in flat[0]:
        ks = jax.tree_util.keystr(path)
        if ks in adapters:
            ab = adapters[ks]
            delta = jnp.einsum("...ir,...ro->...io",
                               ab["a"].astype(jnp.float32),
                               ab["b"].astype(jnp.float32))
            leaf = (leaf.astype(jnp.float32)
                    + lcfg.scale * delta).astype(dtype or leaf.dtype)
        elif dtype is not None:
            leaf = leaf.astype(dtype)
        out.append(leaf)
    return jax.tree.unflatten(jax.tree.structure(params), out)


def lora_param_count(adapters) -> int:
    return sum(x.size for x in jax.tree.leaves(adapters))


def lora_export(adapters) -> Dict[str, jnp.ndarray]:
    """Flat dict for artifact storage (registered as an 'adapter')."""
    out = {}
    for k, ab in adapters.items():
        out[f"{k}.a"] = ab["a"]
        out[f"{k}.b"] = ab["b"]
    return out


def lora_randomize(adapters, key: jax.Array, scale: float = 0.05):
    """Give the zero-init B matrices small random values.

    A freshly ``lora_init``'d adapter is an *exact* zero delta (that is
    the identity-at-init guarantee); demos, benchmarks, and tests need
    adapters that actually shift outputs without running an SFT loop —
    this stands in for training."""
    out = {}
    for name, ab in adapters.items():
        key, k2 = jax.random.split(key)
        out[name] = {"a": ab["a"],
                     "b": scale * jax.random.normal(k2, ab["b"].shape,
                                                    ab["b"].dtype)}
    return out


def lora_unflatten(flat: Dict[str, jnp.ndarray]):
    """Invert :func:`lora_export`: flat ``{"<path>.a": arr}`` back to the
    nested ``{path: {"a", "b"}}`` adapter tree (so a stored artifact can
    be trained further or registered with a serving ``AdapterPool``)."""
    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    for k, v in flat.items():
        if not (k.endswith(".a") or k.endswith(".b")):
            raise ValueError(f"not an exported adapter leaf: {k!r}")
        out.setdefault(k[:-2], {})[k[-1]] = v
    return out

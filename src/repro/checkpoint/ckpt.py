"""Sharding-aware distributed checkpointing with elastic resharding.

Layout (one directory per step)::

    <root>/step_0000100/
      manifest.json       tree structure, per-leaf shape/dtype + shard files
      <leaf_id>.<k>.npy   one file per (leaf, shard) — written by the host
                          that owns the shard

On a real multi-host pod each process writes only its addressable shards
(shard files are keyed by their global index ranges, not by host), so
restore works under ANY new mesh/sharding: each host assembles its local
shards from the overlapping saved files (``jax.make_array_from_callback``).
This is what makes checkpoint/restart *elastic* — a 512-chip job can
restart on 256 chips after losing a pod.

Retention: ``keep_last`` + ``keep_every`` guard against the paper's
"checkpoint explosion" (§6.6); lineage metadata is recorded per save and
surfaced through ``repro.core.registry``.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _leaf_id(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return ".".join(out) or "root"


def _index_to_ranges(index, shape) -> List[Tuple[int, int]]:
    rng = []
    for sl, dim in zip(index, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = dim if sl.stop is None else int(sl.stop)
        rng.append((start, stop))
    return rng


def save(root: str, step: int, tree, extra_meta: Optional[Dict] = None,
         overwrite: bool = True) -> str:
    """Write every addressable shard of every leaf.  Returns the step dir."""
    d = os.path.join(root, f"step_{step:010d}")
    tmp = d + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    leaves, treedef = jax.tree.flatten_with_path(tree)
    manifest: Dict[str, Any] = {
        "step": step, "time": time.time(),
        "treedef": jax.tree.unflatten(
            jax.tree.structure(tree),
            list(range(len(leaves)))).__repr__()[:10000],
        "meta": extra_meta or {}, "leaves": [],
    }
    for path, leaf in leaves:
        lid = _leaf_id(path)
        arr = leaf if isinstance(leaf, jax.Array) else jnp.asarray(leaf)
        entry = {"id": lid, "shape": list(arr.shape),
                 "dtype": str(arr.dtype), "files": []}
        seen = set()
        shards = (arr.addressable_shards
                  if hasattr(arr, "addressable_shards") else None)
        if shards:
            for k, sh in enumerate(shards):
                ranges = tuple(_index_to_ranges(sh.index, arr.shape))
                if ranges in seen:  # replicated copies: write once
                    continue
                seen.add(ranges)
                fn = f"{lid}.{k}.npy"
                data = np.asarray(sh.data)
                if data.dtype.name == "bfloat16":  # numpy can't store bf16
                    data = data.astype(np.float32)
                np.save(os.path.join(tmp, fn), data)
                entry["files"].append({"file": fn,
                                       "ranges": [list(r) for r in ranges]})
        else:
            fn = f"{lid}.0.npy"
            data = np.asarray(arr)
            if data.dtype.name == "bfloat16":
                data = data.astype(np.float32)
            np.save(os.path.join(tmp, fn), data)
            entry["files"].append({
                "file": fn,
                "ranges": [[0, s] for s in arr.shape]})
        manifest["leaves"].append(entry)
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(d):
        if not overwrite:
            raise FileExistsError(d)
        shutil.rmtree(d)
    os.rename(tmp, d)  # atomic publish: partial saves never count
    return d


def _read_region(step_dir: str, entry: Dict, ranges) -> np.ndarray:
    """Assemble [start,stop) per dim from the saved shard files."""
    shape = [b - a for a, b in ranges]
    dtype = np.dtype(entry["dtype"]
                     .replace("bfloat16", "float32"))  # see below
    want_bf16 = entry["dtype"] == "bfloat16"
    out = np.zeros(shape, np.float32 if want_bf16 else dtype)
    for f in entry["files"]:
        fr = f["ranges"]
        inter = []
        ok = True
        for (a, b), (c, dd) in zip(ranges, fr):
            lo, hi = max(a, c), min(b, dd)
            if lo >= hi:
                ok = False
                break
            inter.append((lo, hi, a, c))
        if not ok:
            continue
        data = np.load(os.path.join(step_dir, f["file"]), mmap_mode="r")
        src = tuple(slice(lo - c, hi - c) for lo, hi, a, c in inter)
        dst = tuple(slice(lo - a, hi - a) for lo, hi, a, c in inter)
        out[dst] = np.asarray(data[src], out.dtype)
    return out


def restore(root: str, target, step: Optional[int] = None,
            shardings=None):
    """Restore into the structure of ``target`` (arrays or
    ShapeDtypeStructs).  ``shardings``: matching tree of Shardings (or None
    for single-device).  Resharding across topologies is automatic."""
    step_dir = (os.path.join(root, f"step_{step:010d}") if step is not None
                else latest_dir(root))
    if step_dir is None:
        raise FileNotFoundError(f"no checkpoint under {root}")
    with open(os.path.join(step_dir, "manifest.json")) as f:
        manifest = json.load(f)
    by_id = {e["id"]: e for e in manifest["leaves"]}
    leaves, treedef = jax.tree.flatten_with_path(target)
    shard_leaves = (jax.tree.leaves(shardings) if shardings is not None
                    else [None] * len(leaves))
    out = []
    for (path, leaf), shd in zip(leaves, shard_leaves):
        lid = _leaf_id(path)
        if lid not in by_id:
            raise KeyError(f"checkpoint missing leaf {lid}")
        entry = by_id[lid]
        shape = tuple(entry["shape"])
        dtype = jnp.dtype(entry["dtype"])
        if tuple(leaf.shape) != shape:
            raise ValueError(
                f"shape mismatch for {lid}: ckpt {shape} vs target "
                f"{tuple(leaf.shape)}")
        if shd is None:
            full = _read_region(step_dir, entry,
                                [(0, s) for s in shape])
            out.append(jnp.asarray(full).astype(dtype))
        else:
            arr = jax.make_array_from_callback(
                shape, shd, lambda idx, e=entry: jnp.asarray(
                    _read_region(step_dir, e, _index_to_ranges(idx, shape))
                ).astype(dtype))
            out.append(arr)
    return jax.tree.unflatten(treedef, out), manifest


def list_steps(root: str) -> List[int]:
    if not os.path.isdir(root):
        return []
    out = []
    for n in os.listdir(root):
        m = re.fullmatch(r"step_(\d+)", n)
        if m:
            out.append(int(m.group(1)))
    return sorted(out)


def latest_dir(root: str) -> Optional[str]:
    steps = list_steps(root)
    if not steps:
        return None
    return os.path.join(root, f"step_{steps[-1]:010d}")


def gc(root: str, keep_last: int = 3, keep_every: int = 0) -> List[int]:
    """Retention policy (paper §6.6): keep the newest ``keep_last`` plus
    every ``keep_every``-th step.  Returns deleted steps."""
    steps = list_steps(root)
    keep = set(steps[-keep_last:]) if keep_last else set()
    if keep_every:
        keep |= {s for s in steps if s % keep_every == 0}
    deleted = []
    for s in steps:
        if s not in keep:
            shutil.rmtree(os.path.join(root, f"step_{s:010d}"))
            deleted.append(s)
    return deleted

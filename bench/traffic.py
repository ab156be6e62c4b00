"""One generator for every traffic mix.

A mix is a JSON file under ``bench/mixes`` that gives parameters only:
the kind of load, the length distributions and their clips, the rate or
the backlog.  Every seed gets the same multiset of prompt lengths,
output lengths and inter-arrival gaps, drawn at evenly spaced quantiles
of the stated distributions; the seed only shuffles their order and
draws the token ids.  So two seeds ask for the same work, and runs with
different seeds spread no wider than runs of one seed.

Kinds:

- ``open_loop``: independent users.  Poisson arrivals at ``rate_per_s``;
  the requests due inside the measured window are measured, and the
  schedule goes on past the window (unmeasured) while they drain.
- ``closed``: a backlog that is all due when the window opens, larger
  than the window can drain; ``backlog_per_slot`` requests per decode
  slot.
"""
from __future__ import annotations

import dataclasses
import json
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due: float            # seconds after the window opens
    prompt: List[int]
    max_new_tokens: int
    measured: bool


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix["kind"] not in ("open_loop", "closed"):
        raise ValueError(f"{path}: unknown traffic kind {mix['kind']!r}")
    return mix


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def inv_cdf(spec: dict, q: np.ndarray) -> np.ndarray:
    """Lengths at quantiles ``q`` of ``spec``, rounded and clipped to
    ``[min, max]``."""
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def _draw(mix: dict, n: int, seed: int, stream: int):
    """Prompt and output lengths of ``n`` requests, in seed order."""
    rng = _rng(seed, stream)
    q = quantiles(n)
    return (rng.permutation(inv_cdf(mix["prompt"], q)),
            rng.permutation(inv_cdf(mix["output"], q)))


def _gaps(rate: float, n: int, seed: int, stream: int) -> np.ndarray:
    rng = _rng(seed, stream)
    return rng.permutation(-np.log1p(-quantiles(n)) / rate)


def generate(mix: dict, *, seconds: float, seed: int, vocab_size: int,
             slots: int) -> List[Request]:
    """The requests of one run, in the order they are due."""
    reqs: List[Request] = []
    if mix["kind"] == "closed":
        n = mix["backlog_per_slot"] * slots
        plens, olens = _draw(mix, n, seed, 0)
        dues = np.zeros(n)
        measured = np.ones(n, bool)
    else:
        rate = mix["rate_per_s"]
        n_in = max(1, round(rate * seconds))
        n_after = math.ceil(rate * mix["drain_cap_s"])
        p_in, o_in = _draw(mix, n_in, seed, 0)
        p_after, o_after = _draw(mix, n_after, seed, 1)
        plens = np.concatenate([p_in, p_after])
        olens = np.concatenate([o_in, o_after])
        # the window's gaps sum to the same total for every seed, so the
        # last measured request is always due at the same time
        g_in = _gaps(rate, n_in, seed, 2)
        g_in *= seconds / g_in.sum()
        g_after = _gaps(rate, n_after, seed, 3)
        dues = np.concatenate([np.cumsum(g_in) - g_in[0],
                               seconds + np.cumsum(g_after)
                               - g_after[:1].sum()])
        measured = np.arange(n_in + n_after) < n_in
    tok_rng = _rng(seed, 4)
    for i in range(len(plens)):
        prompt = tok_rng.integers(1, vocab_size - 1, int(plens[i]))
        reqs.append(Request(i, float(dues[i]), [int(t) for t in prompt],
                            int(olens[i]), bool(measured[i])))
    return reqs


def warm_lengths(mix: dict, prefill_chunk: int, block: int) -> List[int]:
    """One prompt length for each shape a one-shot prefill of this mix
    can take: the engine pads a prompt to a power-of-two bucket and
    writes it into as many ``block``-token KV blocks as it fills, and
    compiles one program per (bucket, blocks) pair.  A prompt past
    ``prefill_chunk`` is prefilled in a chunk of exactly that length."""
    lo = mix["prompt"]["min"]
    hi = min(mix["prompt"]["max"], prefill_chunk)
    first, last = -(-lo // block), -(-hi // block)
    return [min(max(k * block, lo), hi) for k in range(first, last + 1)]

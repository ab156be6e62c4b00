"""Random serving weights from a seed, made on the device in one jit.

The tree's layout, ``{path: (shape, kind)}``, is the model family's
(``layout`` in ``bench/families/<family>.py``); :func:`check_layout`
compares it with the program's own shapes before anything runs.
Values are the benchmark's: matrices are normal with variance 1/fan_in,
the embedding is standard normal, norm scales scatter around 1 and
biases are large enough that leaving one out moves every logit.  A
family whose weights need another kind of draw gives it in ``draws``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

VOCAB_ALIGN = 256


def vocab_padded(c) -> int:
    return -(-c["vocab_size"] // VOCAB_ALIGN) * VOCAB_ALIGN


def _nest(flat):
    tree = {}
    for path, v in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return tree


def seed_key(seed: int):
    """A key that uses all the bits of a seed wider than 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _draw(key, shape, kind, dtype, draws):
    if kind in draws:
        return draws[kind](key, shape, dtype)
    if kind == "embed":
        return jax.random.normal(key, shape, dtype)
    if kind == "matrix":
        fan_in = shape[-2]
        return jax.random.normal(key, shape, dtype) * (fan_in ** -0.5)
    if kind == "scale":
        return 1 + 0.1 * jax.random.normal(key, shape, dtype)
    if kind == "bias":
        return 0.2 * jax.random.normal(key, shape, dtype)
    raise ValueError(kind)


def make(lay, seed: int, shardings=None, dtype=jnp.bfloat16, draws=None):
    """The whole tree of layout ``lay`` in ``dtype``, made by one jitted
    call from ``seed``; ``shardings`` (a tree like the result) places
    each leaf as it is made, so no device ever holds more than its
    share.  ``draws`` maps a kind of the family's own to its draw,
    ``(key, shape, dtype) -> array``."""
    paths = sorted(lay)
    draws = draws or {}

    def gen(key):
        keys = jax.random.split(key, len(paths))
        return _nest({p: _draw(k, lay[p][0], lay[p][1], dtype, draws)
                      for p, k in zip(paths, keys)})

    return jax.jit(gen, out_shardings=shardings)(seed_key(seed))


def check_layout(lay, program_shapes) -> None:
    """Raise unless the program expects exactly the tree of shapes of
    layout ``lay``."""
    flat = {tuple(k.key for k in path): tuple(leaf.shape) for path, leaf
            in jax.tree_util.tree_flatten_with_path(program_shapes)[0]}
    ours = {p: tuple(s) for p, (s, _) in lay.items()}
    if flat != ours:
        diff = sorted(set(flat.items()) ^ set(ours.items()))
        raise ValueError(f"weight layout differs from the program's: "
                         f"{diff[:6]}")

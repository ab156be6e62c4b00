"""Random serving weights from a seed, made on the device in one jit.

The tree is laid out as the program loads a dense GQA decoder (layers
stacked on a leading axis); :func:`check_layout` compares it with the
program's own shapes before anything runs.  Values are the benchmark's:
matrices are normal with variance 1/fan_in, the embedding is standard
normal, norm scales scatter around 1 and the QKV biases are large
enough that leaving one out moves every logit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

VOCAB_ALIGN = 256


def vocab_padded(c) -> int:
    return -(-c["vocab_size"] // VOCAB_ALIGN) * VOCAB_ALIGN


def layout(c):
    """{path: (shape, kind)} of every weight; ``kind`` picks the draw."""
    d, L = c["hidden_size"], c["num_hidden_layers"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    ff, vp = c["intermediate_size"], vocab_padded(c)
    out = {
        ("embed", "embed"): ((vp, d), "embed"),
        ("embed", "unembed"): ((d, vp), "matrix"),
        ("ln_f", "scale"): ((d,), "scale"),
        ("stack", "ln1", "scale"): ((L, d), "scale"),
        ("stack", "ln2", "scale"): ((L, d), "scale"),
        ("stack", "mixer", "wq"): ((L, d, h * hd), "matrix"),
        ("stack", "mixer", "wk"): ((L, d, kv * hd), "matrix"),
        ("stack", "mixer", "wv"): ((L, d, kv * hd), "matrix"),
        ("stack", "mixer", "wo"): ((L, h * hd, d), "matrix"),
        ("stack", "mlp", "gate"): ((L, d, ff), "matrix"),
        ("stack", "mlp", "up"): ((L, d, ff), "matrix"),
        ("stack", "mlp", "down"): ((L, ff, d), "matrix"),
    }
    if c.get("attention_bias"):
        for n, w in (("bq", h), ("bk", kv), ("bv", kv)):
            out[("stack", "mixer", n)] = ((L, w * hd), "bias")
    return out


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


def _draw(key, shape, kind, dtype):
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


def make(c, seed: int, shardings=None, dtype=jnp.bfloat16):
    """The whole tree in ``dtype``, made by one jitted call from
    ``seed``; ``shardings`` (a tree like the result) places each leaf as
    it is made, so no device ever holds more than its share."""
    lay = layout(c)
    paths = sorted(lay)

    def gen(key):
        keys = jax.random.split(key, len(paths))
        return _nest({p: _draw(k, lay[p][0], lay[p][1], dtype)
                      for p, k in zip(paths, keys)})

    return jax.jit(gen, out_shardings=shardings)(seed_key(seed))


def check_layout(c, program_shapes) -> None:
    """Raise unless the program expects exactly this tree of shapes."""
    flat = {tuple(k.key for k in path): tuple(leaf.shape) for path, leaf
            in jax.tree_util.tree_flatten_with_path(program_shapes)[0]}
    ours = {p: tuple(s) for p, (s, _) in layout(c).items()}
    if flat != ours:
        diff = sorted(set(flat.items()) ^ set(ours.items()))
        raise ValueError(f"weight layout differs from the program's: "
                         f"{diff[:6]}")

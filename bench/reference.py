"""What every family's plain reference shares.

Each model family's forward pass is ``position_stats`` in its own
module, ``bench/families/<family>.py``; this module holds the parts they
share: float32 matrix products at ``highest`` precision, RMSNorm, rotary
embeddings on the two halves of each head, and :func:`pack`, which lays
the compared requests out as rows of tokens and targets.  It imports
nothing of the program.

``precision="int8"`` is the control: every weight and every activation
entering a matrix product is rounded to symmetric int8 levels (weights
per output channel, activations per token), the step below bf16 that a
faster path would take.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 128          # query positions per attention / head block


def q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def mm(x, w, precision):
    """x (..., k) times w (k, n) in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "int8":
        x, w = q8(x, -1), q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """x (B, S, H, hd): rotate the first and second halves of each head
    by angles pos * theta**(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, :, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def pack(sequences, pad_to: int = Q_BLOCK):
    """Rows of ``prompt + output`` token lists, right-padded with zeros
    to a common multiple of ``pad_to`` (rounded up to a power of two
    times it, so few lengths compile).  For each row the position
    before every output token holds that token as its target; other
    positions hold -1.  Returns (tokens, target, mask)."""
    longest = max(len(p) + len(o) - 1 for p, o in sequences)
    S = pad_to
    while S < longest:
        S *= 2
    tokens = np.zeros((len(sequences), S), np.int32)
    target = np.full((len(sequences), S), -1, np.int32)
    for i, (p, o) in enumerate(sequences):
        seq = list(p) + list(o[:-1])
        tokens[i, :len(seq)] = seq
        target[i, len(p) - 1:len(p) - 1 + len(o)] = o
    return tokens, target, target >= 0

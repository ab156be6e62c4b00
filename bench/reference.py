"""Plain reference forward pass of a dense GQA decoder.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: pre-norm residual blocks of RMSNorm, attention with QKV bias
(where the configuration has it), rotary embeddings on the two halves
of each head, causal softmax, and a SiLU-gated MLP; a final RMSNorm and
the output head.  It reads only the configuration file and the weights,
and imports nothing of the program.

It runs one layer at a time, upcasting that layer's bf16 weights, so it
fits beside the weights on the chip.  Attention and the output head run
in blocks of query positions.

``precision="int8"`` is the control: every weight and every activation
entering a matrix product is rounded to symmetric int8 levels (weights
per output channel, activations per token), the step below bf16 that a
faster path would take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 128          # query positions per attention / head block


def _q8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def _mm(x, w, precision):
    """x (..., k) times w (k, n) in float32."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x (B, S, H, hd): rotate the first and second halves of each head
    by angles pos * theta**(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, :, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dims(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return d, h, c["num_key_value_heads"], c.get("head_dim") or d // h


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def _layer(c, w, x, pos, *, precision):
    c = dict(c)
    B, S, d = x.shape
    _, h, kv, hd = _dims(c)
    eps = c["rms_norm_eps"]
    a = _rmsnorm(x, w["ln1"]["scale"], eps)
    m = w["mixer"]
    q, k, v = (_mm(a, m[n], precision) for n in ("wq", "wk", "wv"))
    if c.get("attention_bias"):
        q = q + m["bq"].astype(jnp.float32)
        k = k + m["bk"].astype(jnp.float32)
        v = v + m["bv"].astype(jnp.float32)
    q = _rope(q.reshape(B, S, h, hd), pos, c["rope_theta"])
    k = _rope(k.reshape(B, S, kv, hd), pos, c["rope_theta"])
    v = v.reshape(B, S, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                       precision=HIGHEST) / np.sqrt(hd)
        qi = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qi[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK))    # (n, B, Qb, h, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, h * hd)
    x = x + _mm(o, m["wo"], precision)
    a = _rmsnorm(x, w["ln2"]["scale"], eps)
    f = w["mlp"]
    g = _mm(a, f["gate"], precision)
    u = _mm(a, f["up"], precision)
    return x + _mm(jax.nn.silu(g) * u, f["down"], precision)


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def _head(c, ln_f, unembed, x, target, alt, *, precision):
    """Per position: the largest logit, the logits of ``target`` and of
    ``alt``, and the index of the largest."""
    c = dict(c)
    V = c["vocab_size"]
    B, S, _ = x.shape
    x = _rmsnorm(x, ln_f, c["rms_norm_eps"])

    def block(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * Q_BLOCK, Q_BLOCK, 1)
        tb = jax.lax.dynamic_slice_in_dim(target, i * Q_BLOCK, Q_BLOCK, 1)
        ab = jax.lax.dynamic_slice_in_dim(alt, i * Q_BLOCK, Q_BLOCK, 1)
        lg = _mm(xb, unembed[:, :V], precision)
        pick = lambda t: jnp.take_along_axis(  # noqa: E731
            lg, jnp.clip(t, 0, V - 1)[..., None], -1)[..., 0]
        return (jnp.max(lg, -1), pick(tb), pick(ab),
                jnp.argmax(lg, -1).astype(jnp.int32))

    outs = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
    return tuple(jnp.moveaxis(o, 0, 1).reshape(B, S) for o in outs)


def _freeze(c):
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size", "rms_norm_eps",
            "rope_theta", "attention_bias")
    return tuple(sorted((k, c.get(k)) for k in keys))


def position_stats(c, weights, tokens, target, alt=None, precision="f32"):
    """Run the reference over ``tokens`` (B, S) int32, S a multiple of
    :data:`Q_BLOCK`; rows are causal, so padding after a row's end does
    not reach its earlier positions.  ``target`` and ``alt`` (B, S) name
    a token per position whose logit is returned.  Returns numpy arrays
    (B, S): max logit, target logit, alt logit, argmax."""
    if tokens.shape[1] % Q_BLOCK:
        raise ValueError(f"sequence length {tokens.shape[1]} is not a "
                         f"multiple of {Q_BLOCK}")
    cf = _freeze(c)
    alt = target if alt is None else alt
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        B, S = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = jnp.take(weights["embed"]["embed"], tokens,
                     axis=0).astype(jnp.float32)
        stack = weights["stack"]
        for layer in range(c["num_hidden_layers"]):
            w = jax.tree.map(lambda a: a[layer], stack)
            x = _layer(cf, w, x, pos, precision=precision)
        out = _head(cf, weights["ln_f"]["scale"],
                    weights["embed"]["unembed"], x,
                    jnp.asarray(target, jnp.int32),
                    jnp.asarray(alt, jnp.int32), precision=precision)
    return tuple(np.asarray(o) for o in out)


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

"""The dense GQA decoder: pre-norm residual blocks of RMSNorm, attention
with grouped key/value heads (QKV bias where the configuration has it)
and rotary embeddings, a SiLU-gated MLP, a final RMSNorm and an untied
output head.  Configurations name it with ``"family": "gqa_decoder"``.

What a family module gives the benchmark, all from a configuration as
written in ``bench/configs`` (Hugging Face key names):

- :func:`program_config`: the program's model configuration;
- :func:`layout`: ``{path: (shape, kind)}`` of every weight, in the tree
  the program loads; a kind that ``bench/weights.py`` does not draw is
  drawn by the family's ``DRAWS[kind](key, shape, dtype)``;
- :func:`position_stats`: the plain reference's logits per position;
- :func:`decode_flops`, :func:`prefill_flops` and
  :func:`decode_step_work`: the required work the per-layer readers
  hold device time against;
- :data:`CUTS` and :func:`min_layers`: the keys a configuration may cut,
  by kind (``"depth"``, ``"experts"``, ``"vocab"``), and the fewest layers
  a depth cut keeps (``harness.check_cut``);
- ``SCOPES``, where the program names scopes of its own beyond
  ``spans.SCOPES``: this family's program names none.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import HIGHEST, Q_BLOCK, mm, rmsnorm, rope
from weights import vocab_padded
from work import BF16


CUTS = {"depth": "num_hidden_layers", "vocab": "vocab_size"}


def min_layers(c) -> int:
    """Every layer is of one kind, so one layer is a whole period; a depth
    cut keeps four."""
    return 4


def _dims(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return d, h, c["num_key_value_heads"], c.get("head_dim") or d // h


# -------------------------------------------------------- the program
def program_config(c: dict):
    """The program's model configuration, set to the values the
    configuration file states."""
    from repro.configs import get_config
    base = get_config(c["program_arch"])
    return dataclasses.replace(
        base, num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or c["hidden_size"]
        // c["num_attention_heads"],
        qkv_bias=bool(c.get("attention_bias")), rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"],
        tie_embeddings=bool(c.get("tie_word_embeddings")))


def layout(c):
    """{path: (shape, kind)} of every weight, layers stacked on a leading
    axis; ``kind`` picks the draw."""
    d, h, kv, hd = _dims(c)
    L = c["num_hidden_layers"]
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


# ------------------------------------------------ the plain reference
@functools.partial(jax.jit, static_argnames=("c", "precision"))
def _layer(c, w, x, pos, *, precision):
    c = dict(c)
    B, S, d = x.shape
    _, h, kv, hd = _dims(c)
    eps = c["rms_norm_eps"]
    a = rmsnorm(x, w["ln1"]["scale"], eps)
    m = w["mixer"]
    q, k, v = (mm(a, m[n], precision) for n in ("wq", "wk", "wv"))
    if c.get("attention_bias"):
        q = q + m["bq"].astype(jnp.float32)
        k = k + m["bk"].astype(jnp.float32)
        v = v + m["bv"].astype(jnp.float32)
    q = rope(q.reshape(B, S, h, hd), pos, c["rope_theta"])
    k = rope(k.reshape(B, S, kv, hd), pos, c["rope_theta"])
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
    x = x + mm(o, m["wo"], precision)
    a = rmsnorm(x, w["ln2"]["scale"], eps)
    f = w["mlp"]
    g = mm(a, f["gate"], precision)
    u = mm(a, f["up"], precision)
    return x + mm(jax.nn.silu(g) * u, f["down"], precision)


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def _head(c, ln_f, unembed, x, target, alt, *, precision):
    """Per position: the largest logit, the logits of ``target`` and of
    ``alt``, and the index of the largest."""
    c = dict(c)
    V = c["vocab_size"]
    B, S, _ = x.shape
    x = rmsnorm(x, ln_f, c["rms_norm_eps"])

    def block(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * Q_BLOCK, Q_BLOCK, 1)
        tb = jax.lax.dynamic_slice_in_dim(target, i * Q_BLOCK, Q_BLOCK, 1)
        ab = jax.lax.dynamic_slice_in_dim(alt, i * Q_BLOCK, Q_BLOCK, 1)
        lg = mm(xb, unembed[:, :V], precision)
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
    (B, S): max logit, target logit, alt logit, argmax.

    It runs one layer at a time, upcasting that layer's bf16 weights, so
    it fits beside the weights on the chip; attention and the output
    head run in blocks of query positions."""
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


# ---------------------------------------------------- required work
def layer_params(c) -> int:
    """Parameters of one decoder layer: attention (with QKV bias where
    the configuration has it), a gated MLP and two norms."""
    d, h, kv, hd = _dims(c)
    attn = d * hd * (h + 2 * kv) + h * hd * d
    if c.get("attention_bias"):
        attn += hd * (h + 2 * kv)
    return attn + 3 * d * c["intermediate_size"] + 2 * d


def body_params(c) -> int:
    """Parameters every token is multiplied by: all layers and the final
    norm, without the embedding lookup or the output head."""
    return c["num_hidden_layers"] * layer_params(c) + c["hidden_size"]


def head_params(c) -> int:
    return c["hidden_size"] * c["vocab_size"]


def total_params(c) -> int:
    """All parameters: the layers, the embedding table, the output head
    (unless tied) and the final norm."""
    n = body_params(c) + c["vocab_size"] * c["hidden_size"]
    if not c.get("tie_word_embeddings"):
        n += head_params(c)
    return n


def kv_bytes_per_token(c, itemsize: int = BF16) -> int:
    """Keys and values of one token over all layers."""
    _, _, kv, hd = _dims(c)
    return c["num_hidden_layers"] * 2 * kv * hd * itemsize


def attn_flops(c, context: int) -> int:
    """Scores and weighted sum of one query over ``context`` keys, all
    layers."""
    _, h, _, hd = _dims(c)
    return 4 * c["num_hidden_layers"] * h * hd * context


def decode_flops(c, context: int) -> int:
    """One generated token whose own position is number ``context``
    (counting from 1): the body, its attention, and the output head."""
    return 2 * (body_params(c) + head_params(c)) + attn_flops(c, context)


def prefill_flops(c, n: int) -> int:
    """A prompt of ``n`` tokens in one pass: the body for every token,
    causal attention, and the output head for the last position only."""
    _, h, _, hd = _dims(c)
    causal = 2 * c["num_hidden_layers"] * h * hd * n * (n + 1)
    return 2 * body_params(c) * n + causal + 2 * head_params(c)


def decode_step_work(c, running: float, live: float, tp: int = 1):
    """(FLOPs, least bytes read) of one decode step on one chip, for
    ``running`` sequences holding ``live`` tokens between them: its share
    of every layer weight and of the output head, read once and
    multiplied by each sequence, and of the live tokens' keys and
    values."""
    weights = body_params(c) + head_params(c)
    flops = (running * 2 * weights + live * attn_flops(c, 1)) / tp
    nbytes = (weights * BF16 + kv_bytes_per_token(c) * live) / tp
    return flops, nbytes

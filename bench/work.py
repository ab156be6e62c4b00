"""Required work of a dense GQA decoder, from its shapes alone.

FLOPs count a multiply-add as two operations.  Bytes are what a step
must read at least: every weight it multiplies by, and the keys and
values of the live tokens.  Neither counts what an implementation adds
(padding, a gather of a slot's whole capacity), so a roofline share
read against these numbers means the same whatever computes attention.

All functions take a configuration as written in ``bench/configs``
(Hugging Face key names).
"""
from __future__ import annotations

BF16 = 2


def _dims(c):
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    kv = c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    return d, h, kv, hd


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


def decode_step_bytes(c, contexts, tp: int = 1) -> float:
    """Least bytes one chip reads in a decode step over sequences whose
    lengths are ``contexts``: its share of the layer weights and of the
    output head, and its share of the live keys and values."""
    w = (body_params(c) + head_params(c)) * BF16
    return (w + kv_bytes_per_token(c) * sum(contexts)) / tp


def decode_step_flops(c, contexts, tp: int = 1) -> float:
    """FLOPs of one decode step on one chip."""
    return sum(decode_flops(c, n) for n in contexts) / tp


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time for the work, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

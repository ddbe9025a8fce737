"""Operations and bytes that the served path needs, from shapes alone.

A decode round takes every decoding row one token through every block of
its route.  What it needs is the hosted blocks' weights read once per hop,
each row's K/V read up to its own position (not to the cache's length),
and the hidden rows in and out; it computes two operations per weight per
row and the attention over each row's own context.  Model FLOPs of a token
add the head's projection over the vocabulary.
"""
from __future__ import annotations

from typing import Sequence


def block_params(m: dict) -> int:
    """Matmul parameters of one block."""
    d, H, Kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    attn = d * (H + 2 * Kv) * hd + H * hd * d
    mlp = (3 if m["mlp"] == "swiglu" else 2) * d * f
    return attn + mlp


def round_needs(m: dict, positions: Sequence[int], n_hops: int,
                width: int) -> tuple:
    """(flops, bytes) the round step programs need for one decode round in
    which rows at ``positions`` cross ``n_hops`` hops holding
    ``n_layers`` blocks between them, over round buffers ``width`` wide."""
    L, d, H, Kv, hd = (m["n_layers"], m["d_model"], m["n_heads"],
                       m["n_kv_heads"], m["head_dim"])
    bpe = 2  # bf16 weights, cache and activations
    ctx = sum(p + 1 for p in positions)
    flops = L * (2 * block_params(m) * len(positions) + 4 * H * hd * ctx)
    kv = L * 2 * Kv * hd * bpe * ctx
    weights = L * block_params(m) * bpe
    hidden = n_hops * 2 * width * d * bpe
    return flops, weights + kv + hidden


def token_model_flops(m: dict, position: int) -> int:
    """Model FLOPs of one decoded token at ``position``: every block, the
    attention over its context and the head."""
    L, H, hd = m["n_layers"], m["n_heads"], m["head_dim"]
    return (L * (2 * block_params(m) + 4 * H * hd * (position + 1))
            + 2 * m["d_model"] * m["vocab_size"])

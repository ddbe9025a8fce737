"""Plain reference of a dense decoder-only transformer, its weights and its
cost count.

The published blocks of OLMo (arXiv:2402.00838: non-parametric LayerNorm,
RoPE with rotate-half, SwiGLU) and BLOOM (LayerNorm with scale and bias,
ALiBi, tanh-GELU), written in plain ``jax.numpy`` in float32 at the
highest matmul precision, with no cache, no batching and no kernels: one
causal pass over the whole sequence.  It imports nothing of the program.

Departures from the published models, which the served program shares:
no bias on any projection (BLOOM has them), no LayerNorm on BLOOM's input
embeddings, and tied embeddings with no scaling.

:func:`make_params` is the benchmark's weight maker: random weights from
the seed, in one jitted call on the device, in the dtype they are served
in, laid out as the program takes them.  The reference calls it again
after the program's state is freed, so it takes nothing the program made.

A configuration's reference module is also where the benchmark finds the
architecture's cost count (:func:`round_needs`, :func:`token_model_flops`)
and the reference's program for a compile rehearsal
(:func:`lowered_reference`).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# contraction axes of each weight (per output channel quantisation)
_CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo_attn": (0, 1),
             "wg": (0,), "wu": (0,), "wi": (0,), "wo_mlp": (0,),
             "tok": (1,)}


def seed32(seed: int) -> int:
    """Any whole seed, however large, as a 31-bit PRNG seed."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               & 0x7FFFFFFF)


def _shapes(m: dict) -> Dict[str, tuple]:
    d, H, Kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    L, V = m["n_layers"], m["vocab_size"]
    # the embedding at OLMo's init_std: at 1.0 the residual stream would be
    # the current token's embedding, the tied head would copy it, and the
    # served tokens would say nothing about the blocks
    shapes = {"tok": ((V, d), 0.02),
              "wq": ((L, d, H, hd), d ** -0.5),
              "wk": ((L, d, Kv, hd), d ** -0.5),
              "wv": ((L, d, Kv, hd), d ** -0.5),
              "wo_attn": ((L, H, hd, d), (H * hd) ** -0.5),
              "wo_mlp": ((L, f, d), f ** -0.5)}
    if m["mlp"] == "swiglu":
        shapes.update(wg=((L, d, f), d ** -0.5), wu=((L, d, f), d ** -0.5))
    else:
        shapes.update(wi=((L, d, f), d ** -0.5))
    return shapes


def _norm_shapes(m: dict) -> Dict[str, tuple]:
    if m["norm_kind"] != "layernorm":
        return {}
    L, d = m["n_layers"], m["d_model"]
    return {f"{n}_{p}": (shape, p) for n, shape in
            (("ln1", (L, d)), ("ln2", (L, d)), ("final", (d,)))
            for p in ("scale", "bias")}


def _layout(flat: dict, m: dict) -> dict:
    """The flat leaves in the program's parameter layout."""
    def norm(name):
        if m["norm_kind"] != "layernorm":
            return {}
        return {"scale": flat[f"{name}_scale"], "bias": flat[f"{name}_bias"]}

    ffn = ({"wg": flat["wg"], "wu": flat["wu"], "wo": flat["wo_mlp"]}
           if m["mlp"] == "swiglu" else
           {"wi": flat["wi"], "wo": flat["wo_mlp"]})
    return {"embed": {"tok": flat["tok"], "final_norm": norm("final")},
            "segments": {"blocks": {
                "ln1": norm("ln1"), "ln2": norm("ln2"), "ffn": ffn,
                "attn": {"wq": flat["wq"], "wk": flat["wk"],
                         "wv": flat["wv"], "wo": flat["wo_attn"]}}}}


def flat_leaves(params: dict, m: dict) -> dict:
    """Inverse of :func:`_layout`."""
    b = params["segments"]["blocks"]
    flat = {"tok": params["embed"]["tok"], "wq": b["attn"]["wq"],
            "wk": b["attn"]["wk"], "wv": b["attn"]["wv"],
            "wo_attn": b["attn"]["wo"], "wo_mlp": b["ffn"]["wo"]}
    for k in ("wg", "wu", "wi"):
        if k in b["ffn"]:
            flat[k] = b["ffn"][k]
    if m["norm_kind"] == "layernorm":
        for name, tree in (("ln1", b["ln1"]), ("ln2", b["ln2"]),
                           ("final", params["embed"]["final_norm"])):
            flat[f"{name}_scale"] = tree["scale"]
            flat[f"{name}_bias"] = tree["bias"]
    return flat


def _hash_uniform(shape, seed: int, leaf: int):
    """U(-1, 1) from a counter hash of each element's index, the seed and
    the leaf: a few integer operations per element, so a 2.5-billion
    parameter block is made in well under a second on the chip (the
    library's counter-based generator took 16 s for 1.2 billion), and the
    same numbers come out on every backend."""
    n = int(np.prod(shape))
    idx = jax.lax.iota(jnp.uint32, n).reshape(shape)
    mix = (seed * 0x85EBCA6B + (leaf + 1) * 0xC2B2AE35) & 0xFFFFFFFF
    h = idx * jnp.uint32(0x9E3779B9) ^ jnp.uint32(mix)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return (h >> 8).astype(jnp.float32) * (2.0 / (1 << 24)) - 1.0


def make_params(m: dict, seed: int):
    """Random weights from ``seed``: each matrix uniform with standard
    deviation 1/sqrt(fan-in) (0.02 for the embedding) in ``param_dtype``;
    LayerNorm scales 1 + U(-0.1, 0.1) and biases U(-0.1, 0.1) in float32,
    so that a norm applied wrongly shows."""
    dt = jnp.dtype(m["param_dtype"])
    mats, norms = _shapes(m), _norm_shapes(m)
    names = sorted(mats) + sorted(norms)
    s32 = seed32(seed)

    def build():
        flat = {}
        for i, name in enumerate(names):
            if name in mats:
                shape, std = mats[name]
                u = _hash_uniform(shape, s32, i)
                flat[name] = (u * (std * math.sqrt(3.0))).astype(dt)
            else:
                shape, part = norms[name]
                u = 0.1 * _hash_uniform(shape, s32, i)
                flat[name] = u + 1.0 if part == "scale" else u
        return _layout(flat, m)

    return jax.jit(build)()


def _round_to(x, kind: str, axes):
    """``x`` rounded to ``kind`` ("int8", or "fp8" for float8 e4m3) with one
    symmetric scale over ``axes``, back in float32."""
    x = x.astype(jnp.float32)
    top = 127.0 if kind == "int8" else 448.0
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if kind == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def quantized_params(m: dict, seed: int, kind: str) -> dict:
    """The control's weights: :func:`make_params`' weights with every
    matrix rounded to ``kind`` with one symmetric scale per output channel,
    held in ``param_dtype`` (whose rounding of a level times its scale is
    far below the level's own).  Leaf by leaf, so that one copy of the
    weights is held.  With ``logits_at(..., lowp=kind)`` the activations
    entering each matrix are rounded too, one scale per token: the matmuls
    of a serving path in that precision."""
    flat = flat_leaves(make_params(m, seed), m)
    dt = jnp.dtype(m["param_dtype"])
    q = jax.jit(lambda w, kind, axes: _round_to(w, kind, axes).astype(dt),
                static_argnums=(1, 2))
    for name, axes in _CONTRACT.items():
        if name in flat:
            # the stacked layer axis shifts every contraction axis by one
            shift = 0 if name == "tok" else 1
            flat[name] = q(flat[name], kind, tuple(a + shift for a in axes))
    return _layout(flat, m)


# -- the forward pass ------------------------------------------------------

def _norm(x, scale, bias, kind, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    if kind == "layernorm":
        y = y * scale + bias
    return y


def _alibi_slopes(n_heads: int) -> np.ndarray:
    """Press et al.'s slopes, extended to non-powers of two as BLOOM does."""
    p = 2 ** int(math.floor(math.log2(n_heads)))
    slopes = [2.0 ** (-8.0 * (i + 1) / p) for i in range(p)]
    if p < n_heads:
        extra = [2.0 ** (-4.0 * (2 * i + 1) / p) for i in range(n_heads - p)]
        slopes += extra
    return np.asarray(slopes, np.float32)


def _rope(x, pos, theta):
    """Rotate-half RoPE over the whole head dimension."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None]  # (S, hd/2)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _block(m, h, w, pos):
    f32 = jnp.float32
    kind, eps = m["norm_kind"], m["norm_eps"]
    def qa(x, n_axes=1):  # one scale per token, over its last n_axes axes
        if m["lowp"] is None:
            return x
        return _round_to(x, m["lowp"], tuple(range(x.ndim - n_axes, x.ndim)))

    S = h.shape[0]
    x = qa(_norm(h, w.get("ln1_scale"), w.get("ln1_bias"), kind, eps))
    q = jnp.einsum("sd,dhk->shk", x, w["wq"].astype(f32))
    k = jnp.einsum("sd,dhk->shk", x, w["wk"].astype(f32))
    v = jnp.einsum("sd,dhk->shk", x, w["wv"].astype(f32))
    H, Kv = q.shape[1], k.shape[1]
    if Kv != H:
        k, v = jnp.repeat(k, H // Kv, axis=1), jnp.repeat(v, H // Kv, axis=1)
    if m["pos_kind"] == "rope":
        q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    scores = jnp.einsum("qhk,thk->hqt", q, k) / math.sqrt(q.shape[-1])
    rel = (pos[None, :] - pos[:, None]).astype(f32)  # key minus query
    if m["pos_kind"] == "alibi":
        scores = scores + jnp.asarray(_alibi_slopes(H))[:, None, None] * rel
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(scores, axis=-1), v)
    h = h + jnp.einsum("shk,hkd->sd", qa(att, 2), w["wo_attn"].astype(f32))
    x = qa(_norm(h, w.get("ln2_scale"), w.get("ln2_bias"), kind, eps))
    if m["mlp"] == "swiglu":
        a = (jax.nn.silu(x @ w["wg"].astype(f32)) * (x @ w["wu"].astype(f32)))
    else:
        a = jax.nn.gelu(x @ w["wi"].astype(f32), approximate=True)
    return h + qa(a) @ w["wo_mlp"].astype(f32)


def _jit_args(m: dict, params: dict, lowp: Optional[str]) -> tuple:
    """:func:`_logits_jit`'s arguments before the tokens and rows."""
    flat = flat_leaves(params, m)
    per_layer = {k: v for k, v in flat.items()
                 if k not in ("tok", "final_scale", "final_bias")}
    return (m["norm_kind"], m["norm_eps"], m["pos_kind"],
            float(m.get("rope_theta", 0.0)), m["mlp"], lowp, flat["tok"],
            flat.get("final_scale"), flat.get("final_bias"), per_layer)


def logits_at(m: dict, params: dict, tokens: np.ndarray,
              rows: np.ndarray, lowp: Optional[str] = None) -> np.ndarray:
    """float32 logits (len(rows), vocab) of the causal pass over ``tokens``
    at positions ``rows``: layer by layer, each layer's weights upcast
    inside the scan, so at most one layer is held in float32.  ``lowp``
    rounds every matrix's input activations to "int8" or "fp8" (the
    control, with :func:`quantized_params`)."""
    with jax.default_matmul_precision("highest"):
        out = _logits_jit(*_jit_args(m, params, lowp),
                          jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(rows, jnp.int32))
    return np.asarray(out, np.float32)


def lowered_reference(m: dict, params: dict, seq_len: int, n_rows: int,
                      sharding):
    """The program :func:`logits_at` runs over ``seq_len`` tokens read at
    ``n_rows`` positions, lowered on ``params`` given as shapes
    (``jax.ShapeDtypeStruct``), with the tokens and rows placed by
    ``sharding``: for a compile rehearsal, nothing run."""
    def spec(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)

    with jax.default_matmul_precision("highest"):
        return _logits_jit.lower(*_jit_args(m, params, None), spec(seq_len),
                                 spec(n_rows))


def _logits_impl(norm_kind, norm_eps, pos_kind, rope_theta, mlp, lowp, tok,
                 fscale, fbias, per_layer, tokens, rows):
    m = {"norm_kind": norm_kind, "norm_eps": norm_eps, "pos_kind": pos_kind,
         "rope_theta": rope_theta, "mlp": mlp, "lowp": lowp}
    pos = jnp.arange(tokens.shape[0])
    h = tok[tokens].astype(jnp.float32)

    def body(h, w):
        return _block(m, h, w, pos), None

    h, _ = jax.lax.scan(body, h, per_layer)
    h = _norm(h[rows], fscale, fbias, norm_kind, norm_eps)
    if lowp is not None:
        h = _round_to(h, lowp, (1,))
    return h @ tok.astype(jnp.float32).T


_logits_jit = jax.jit(_logits_impl, static_argnums=(0, 1, 2, 3, 4, 5))


# -- the cost count ----------------------------------------------------------
#
# A decode round takes every decoding row one token through every block of
# its route.  What it needs is the hosted blocks' weights read once per
# hop, each row's K/V read up to its own position (not to the cache's
# length), and the hidden rows in and out; it computes two operations per
# weight per row and the attention over each row's own context.  Model
# FLOPs of a token add the head's projection over the vocabulary.

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

"""Per-family block functions — the BPRR placement granularity.

Every block exposes three entry points used across the framework:

* ``init_<kind>(key, cfg)``               -> (params, axes)
* ``<kind>_full(params, cfg, sh, h, ...)`` -> (h, cache_entry)   train/prefill
* ``<kind>_decode(params, cfg, sh, h, cache_entry, pos)`` -> (h, cache_entry)

``cache_entry`` is the per-block serving state (KV / MLA latent / SSM state);
train passes ignore it.  The stack drivers in ``repro.models.model`` scan
these; the geo serving engine (``repro.serving.engine``) applies them one
block at a time according to the paper's placement ``(a_j, m_j)``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    ParamBuilder,
    ShardingCtx,
    apply_mlp,
    apply_norm,
    init_mlp,
    init_norm,
)

_BIG = 1 << 30

# Block kinds a stack can be made of — the granularity the serving layer's
# per-block StateSpec dispatch (repro.serving.kv_cache) is keyed on.
BLOCK_KINDS = ("decoder", "rwkv", "mamba", "mamba_shared", "enc", "dec")


def stack_block_kinds(cfg: ModelConfig):
    """Per-block kind tuple (length ``cfg.n_layers``) in BPRR block order.

    * dense / moe / vlm:  ("decoder",) * n_layers
    * rwkv6:              ("rwkv",) * n_layers
    * zamba2 hybrid:      "mamba" everywhere, except the last block of each
      shared-attention group (every ``shared_attn_period``-th) which is
      "mamba_shared" — a mamba mixer followed by the parameter-shared
      attention+MLP block (KV cache + SSM state on ONE block).
    * seamless enc-dec:   ("enc",) * n_enc + ("dec",) * n_dec.

    Raises ``ValueError`` for families outside :data:`BLOCK_KINDS` so the
    serving layer can surface the supported set.
    """
    if cfg.is_enc_dec:
        return (("enc",) * cfg.n_enc_layers) + (("dec",) * cfg.n_dec_layers)
    if cfg.family == "hybrid":
        period = cfg.shared_attn_period
        n_mega = (cfg.n_layers // period) * period
        return tuple(
            "mamba_shared" if (i < n_mega and i % period == period - 1)
            else "mamba" for i in range(cfg.n_layers))
    if cfg.family == "ssm":
        return ("rwkv",) * cfg.n_layers
    if cfg.family in ("dense", "moe", "vlm"):
        return ("decoder",) * cfg.n_layers
    raise ValueError(
        f"unknown block family {cfg.family!r} for {cfg.name!r}; supported "
        "stacks are built from block kinds " + ", ".join(BLOCK_KINDS))


def window_for_layer(cfg: ModelConfig, layer_idx):
    """Traced per-layer sliding window (gemma3 local:global pattern).

    Returns a scalar usable inside a scanned block: the window size for local
    layers, or a huge value for global layers.  ``layer_idx`` may be traced.
    """
    if cfg.sliding_window <= 0:
        return None
    if cfg.local_global_period <= 0:
        return cfg.sliding_window
    is_global = (layer_idx + 1) % cfg.local_global_period == 0
    return jnp.where(is_global, _BIG, cfg.sliding_window)


# ---------------------------------------------------------------------------
# Decoder block (dense / moe / vlm families; gemma3 pattern via window arg)
# ---------------------------------------------------------------------------


def init_decoder_block(key, cfg: ModelConfig):
    pb = ParamBuilder(key)
    pb.sub("ln1", init_norm, cfg)
    if cfg.attn_kind == "mla":
        pb.sub("attn", attn.init_mla, cfg)
    else:
        pb.sub("attn", attn.init_gqa, cfg)
    pb.sub("ln2", init_norm, cfg)
    if cfg.is_moe:
        pb.sub("ffn", moe_mod.init_moe, cfg)
    else:
        pb.sub("ffn", init_mlp, cfg)
    if cfg.sandwich_norm:
        pb.sub("post_ln1", init_norm, cfg)
        pb.sub("post_ln2", init_norm, cfg)
    return pb.build()


def decoder_block_full(params, cfg: ModelConfig, sh: ShardingCtx, h, positions,
                       layer_idx=0, prefix_kv=None, backend: str = "xla"):
    """Full-sequence decoder block.  Returns (h, cache_entry, aux).

    ``prefix_kv``: optional already-cached prefix for chunked prefill — a
    (k, v) pair for GQA or (latent, krope) for MLA covering positions
    [0, P).  ``positions`` must then be ``P + arange(S)``.  The returned
    ``cache_entry`` always covers only the positions in ``h``.
    ``backend``: compute backend for the attention core ("xla" | "pallas").
    """
    win = window_for_layer(cfg, layer_idx)
    x = apply_norm(params["ln1"], cfg, h)
    if cfg.attn_kind == "mla":
        a, kv = attn.apply_mla_full(params["attn"], cfg, sh, x, positions,
                                    prefix_kv=prefix_kv, backend=backend)
        cache = {"latent": kv[0], "krope": kv[1]}
    else:
        a, kv = attn.apply_gqa_full(params["attn"], cfg, sh, x, positions, win,
                                    prefix_kv=prefix_kv, backend=backend)
        cache = {"k": kv[0], "v": kv[1]}
    if cfg.sandwich_norm:
        a = apply_norm(params["post_ln1"], cfg, a)
    h = h + a
    x = apply_norm(params["ln2"], cfg, h)
    aux = {}
    if cfg.is_moe:
        m, aux = moe_mod.apply_moe(params["ffn"], cfg, sh, x)
    else:
        m = apply_mlp(params["ffn"], cfg, sh, x)
    if cfg.sandwich_norm:
        m = apply_norm(params["post_ln2"], cfg, m)
    h = sh.act(h + m, "batch", "seq_act", None)
    return h, cache, aux


def decoder_decode_entry(params, cfg: ModelConfig, h, pos):
    """ln1 and the new token's projections of one decode step.  h (B,1,d);
    ``pos`` scalar or (B,) per row.  Returns (query, entry): ``entry`` holds
    the block's cache leaves (``k``/``v``, or ``latent``/``krope`` for MLA)
    for the one new token, each (B,1,...), to be written at ``pos``."""
    x = apply_norm(params["ln1"], cfg, h)
    if cfg.attn_kind == "mla":
        return attn.mla_decode_entry(params["attn"], cfg, x, pos)
    return attn.gqa_decode_entry(params["attn"], cfg, x, pos)


def decoder_decode_attend(params, cfg: ModelConfig, h, query, cache, pos,
                          layer_idx=0, backend: str = "xla"):
    """The rest of the attention half: ``query`` attends over ``cache``
    (which already holds the new token at ``pos``) -> residual (+ sandwich
    post-norm).  Caches are (B,T,...); ``pos`` scalar or (B,)."""
    if cfg.attn_kind == "mla":
        a = attn.mla_decode_attend(params["attn"], cfg, query,
                                   cache["latent"], cache["krope"], pos,
                                   backend=backend)
    else:
        a = attn.gqa_decode_attend(params["attn"], cfg, query, cache["k"],
                                   cache["v"], pos,
                                   window_for_layer(cfg, layer_idx),
                                   backend=backend)
    if cfg.sandwich_norm:
        a = apply_norm(params["post_ln1"], cfg, a)
    return h + a


def decoder_block_attn_decode(params, cfg: ModelConfig, sh: ShardingCtx, h,
                              cache, pos, layer_idx=0, backend: str = "xla"):
    """Attention half of :func:`decoder_block_decode`: ln1 -> attention ->
    residual (+ sandwich post-norm), writing the new token into ``cache``
    at ``pos``.  Returns (h, cache) with the FFN half still to run."""
    query, new = decoder_decode_entry(params, cfg, h, pos)
    cache = {key: jax.lax.dynamic_update_slice_in_dim(
        cache[key], new[key].astype(cache[key].dtype), pos, axis=1)
        for key in new}
    return decoder_decode_attend(params, cfg, h, query, cache, pos,
                                 layer_idx, backend=backend), cache


def decoder_block_ffn(params, cfg: ModelConfig, sh: ShardingCtx, h):
    """FFN half of :func:`decoder_block_decode`: ln2 -> MoE/MLP ->
    residual.  Token-wise (position-free), so callers may regroup a
    (rows, 1, d) decode grid into any (B, S, d) factorization first —
    the EP decode path reshapes rows onto the (data, model) grid."""
    x = apply_norm(params["ln2"], cfg, h)
    if cfg.is_moe:
        m, _ = moe_mod.apply_moe(params["ffn"], cfg, sh, x)
    else:
        m = apply_mlp(params["ffn"], cfg, sh, x)
    if cfg.sandwich_norm:
        m = apply_norm(params["post_ln2"], cfg, m)
    return h + m


def decoder_block_decode(params, cfg: ModelConfig, sh: ShardingCtx, h, cache,
                         pos, layer_idx=0, backend: str = "xla"):
    """Single-token decoder block.  h (B,1,d).  Returns (h, cache)."""
    h, cache = decoder_block_attn_decode(params, cfg, sh, h, cache, pos,
                                         layer_idx, backend=backend)
    return decoder_block_ffn(params, cfg, sh, h), cache


# ---------------------------------------------------------------------------
# Encoder / decoder blocks (seamless enc-dec)
# ---------------------------------------------------------------------------


def init_encoder_block(key, cfg: ModelConfig):
    pb = ParamBuilder(key)
    pb.sub("ln1", init_norm, cfg)
    pb.sub("attn", attn.init_gqa, cfg)
    pb.sub("ln2", init_norm, cfg)
    pb.sub("ffn", init_mlp, cfg)
    return pb.build()


def encoder_block_full(params, cfg: ModelConfig, sh: ShardingCtx, h, positions,
                       backend: str = "xla"):
    """Bidirectional self-attention encoder block."""
    x = apply_norm(params["ln1"], cfg, h)
    q = attn._q_proj(params["attn"], cfg, x)
    k, v = attn._kv_proj(params["attn"], cfg, x)
    if cfg.pos_kind == "rope":
        cos, sin = attn.rope_angles(positions, cfg.head_dim, cfg.rope_theta)
        q = attn.apply_rope(q, cos, sin)
        k = attn.apply_rope(k, cos, sin)
    if attn._use_pallas_flash(backend, causal=False):
        out = attn.flash_attention(q, k, v, causal=False)
    else:
        G = cfg.n_heads // cfg.n_kv_heads
        k_exp = jnp.repeat(k, G, axis=2) if G > 1 else k
        v_exp = jnp.repeat(v, G, axis=2) if G > 1 else v
        out = attn.attention_core(q, k_exp, v_exp, positions, positions,
                                  causal=False)
    a = jnp.einsum("bshk,hkd->bsd", out,
                   params["attn"]["wo"].astype(x.dtype))
    h = h + a
    x = apply_norm(params["ln2"], cfg, h)
    h = h + apply_mlp(params["ffn"], cfg, sh, x)
    return sh.act(h, "batch", "seq_act", None)


def init_cross_decoder_block(key, cfg: ModelConfig):
    pb = ParamBuilder(key)
    pb.sub("ln1", init_norm, cfg)
    pb.sub("self_attn", attn.init_gqa, cfg)
    pb.sub("ln_cross", init_norm, cfg)
    pb.sub("cross_attn", attn.init_gqa, cfg)
    pb.sub("ln2", init_norm, cfg)
    pb.sub("ffn", init_mlp, cfg)
    return pb.build()


def cross_decoder_block_full(params, cfg: ModelConfig, sh: ShardingCtx, h,
                             positions, enc_h, prefix_kv=None, enc_kv=None,
                             backend: str = "xla"):
    """Decoder block with cross-attention.  Returns (h, cache_entry).

    ``prefix_kv``: optional already-cached self-attention (k, v) prefix for
    chunked prefill — same contract as ``decoder_block_full``: ``positions``
    must be ``P + arange(S)`` and the returned cache entry covers only the
    chunk.  ``enc_kv``: optional already-projected encoder cross-(k, v);
    when given, the ``gqa_encoder_kv`` projection of ``enc_h`` is skipped
    (it does not depend on the decoder offset, so chunked prefill computes
    it once at offset 0 and reuses the cached value after).
    """
    x = apply_norm(params["ln1"], cfg, h)
    a, kv = attn.apply_gqa_full(params["self_attn"], cfg, sh, x, positions,
                                prefix_kv=prefix_kv, backend=backend)
    h = h + a
    x = apply_norm(params["ln_cross"], cfg, h)
    if enc_kv is None:
        ck, cv = attn.gqa_encoder_kv(params["cross_attn"], cfg, sh, enc_h)
    else:
        ck, cv = enc_kv
    a, _ = attn.apply_gqa_full(params["cross_attn"], cfg, sh, x, positions,
                               cross_kv=(ck, cv), backend=backend)
    h = h + a
    x = apply_norm(params["ln2"], cfg, h)
    h = h + apply_mlp(params["ffn"], cfg, sh, x)
    h = sh.act(h, "batch", "seq_act", None)
    cache = {"k": kv[0], "v": kv[1], "ck": ck, "cv": cv}
    return h, cache


def cross_decoder_block_decode(params, cfg: ModelConfig, sh: ShardingCtx, h,
                               cache, pos, enc_len=None,
                               backend: str = "xla"):
    """Single-token cross-decoder block.

    ``enc_len``: optional (traced) number of VALID encoder positions in the
    ``ck``/``cv`` caches — required when they are allocated longer than the
    session's encoder output (the pooled serving path); ``None`` keeps the
    exact-length monolithic behaviour.
    """
    x = apply_norm(params["ln1"], cfg, h)
    a, ck, cv = attn.apply_gqa_decode(
        params["self_attn"], cfg, sh, x, cache["k"], cache["v"], pos,
        backend=backend)
    h = h + a
    x = apply_norm(params["ln_cross"], cfg, h)
    a, _, _ = attn.apply_gqa_decode(
        params["cross_attn"], cfg, sh, x, cache["ck"], cache["cv"], pos,
        cross=True, kv_len=enc_len, backend=backend)
    h = h + a
    x = apply_norm(params["ln2"], cfg, h)
    h = h + apply_mlp(params["ffn"], cfg, sh, x)
    return h, {"k": ck, "v": cv, "ck": cache["ck"], "cv": cache["cv"]}


# ---------------------------------------------------------------------------
# Mamba2 block (zamba2 backbone)
# ---------------------------------------------------------------------------


def init_mamba_block(key, cfg: ModelConfig):
    pb = ParamBuilder(key)
    pb.sub("ln", init_norm, cfg)
    pb.sub("mixer", ssm_mod.init_mamba, cfg)
    return pb.build()


def mamba_block_full(params, cfg: ModelConfig, sh: ShardingCtx, h,
                     backend: str = "xla"):
    x = apply_norm(params["ln"], cfg, h)
    y, state = ssm_mod.apply_mamba_full(params["mixer"], cfg, sh, x,
                                        backend=backend)
    return sh.act(h + y, "batch", "seq_act", None), state


def mamba_block_decode(params, cfg: ModelConfig, sh: ShardingCtx, h, state,
                       backend: str = "xla"):
    # single-step recurrence is elementwise — no kernel; ``backend`` is
    # accepted for call-site uniformity and ignored
    x = apply_norm(params["ln"], cfg, h)
    y, state = ssm_mod.apply_mamba_decode(params["mixer"], cfg, sh, x, state)
    return h + y, state


# ---------------------------------------------------------------------------
# Zamba2 shared attention block (params shared across invocations)
# ---------------------------------------------------------------------------


def init_zamba_shared(key, cfg: ModelConfig):
    """Attention+MLP on concat(hidden, embedding0), width 2*d_model."""
    width = 2 * cfg.d_model
    pb = ParamBuilder(key)
    pb.sub("ln1", init_norm, cfg, width)
    pb.sub("attn", attn.init_gqa, cfg, width)
    pb.sub("ln2", init_norm, cfg, width)
    pb.sub("ffn", init_mlp, cfg, width)
    return pb.build()


def zamba_shared_full(params, cfg: ModelConfig, sh: ShardingCtx, h, emb0,
                      positions, backend: str = "xla"):
    """Returns (h, cache_entry) — KV cache per invocation."""
    xc = jnp.concatenate([h, emb0], axis=-1)
    x = apply_norm(params["ln1"], cfg, xc)
    a, kv = attn.apply_gqa_full(params["attn"], cfg, sh, x, positions,
                                backend=backend)
    h = h + a
    xc = jnp.concatenate([h, emb0], axis=-1)
    x = apply_norm(params["ln2"], cfg, xc)
    h = h + apply_mlp(params["ffn"], cfg, sh, x)
    return sh.act(h, "batch", "seq_act", None), {"k": kv[0], "v": kv[1]}


def zamba_shared_decode(params, cfg: ModelConfig, sh: ShardingCtx, h, emb0,
                        cache, pos, backend: str = "xla"):
    xc = jnp.concatenate([h, emb0], axis=-1)
    x = apply_norm(params["ln1"], cfg, xc)
    a, ck, cv = attn.apply_gqa_decode(
        params["attn"], cfg, sh, x, cache["k"], cache["v"], pos,
        backend=backend)
    h = h + a
    xc = jnp.concatenate([h, emb0], axis=-1)
    x = apply_norm(params["ln2"], cfg, xc)
    h = h + apply_mlp(params["ffn"], cfg, sh, x)
    return h, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# RWKV6 block
# ---------------------------------------------------------------------------


def init_rwkv_block(key, cfg: ModelConfig):
    pb = ParamBuilder(key)
    pb.sub("ln1", init_norm, cfg)
    pb.sub("tm", ssm_mod.init_rwkv_tm, cfg)
    pb.sub("ln2", init_norm, cfg)
    pb.sub("cm", ssm_mod.init_rwkv_cm, cfg)
    return pb.build()


def rwkv_block_full(params, cfg: ModelConfig, sh: ShardingCtx, h,
                    backend: str = "xla"):
    x = apply_norm(params["ln1"], cfg, h)
    y, tm_state = ssm_mod.apply_rwkv_tm_full(params["tm"], cfg, sh, x,
                                             backend=backend)
    h = h + y
    x = apply_norm(params["ln2"], cfg, h)
    y, cm_shift = ssm_mod.apply_rwkv_cm(params["cm"], cfg, sh, x)
    h = sh.act(h + y, "batch", "seq_act", None)
    state = {"wkv": tm_state["wkv"], "shift_tm": tm_state["shift"],
             "shift_cm": cm_shift}
    return h, state


def rwkv_block_decode(params, cfg: ModelConfig, sh: ShardingCtx, h, state,
                      backend: str = "xla"):
    # single-step recurrence is elementwise — no kernel; ``backend`` is
    # accepted for call-site uniformity and ignored
    x = apply_norm(params["ln1"], cfg, h)
    y, tm_state = ssm_mod.apply_rwkv_tm_decode(
        params["tm"], cfg, sh, x,
        {"wkv": state["wkv"], "shift": state["shift_tm"]})
    h = h + y
    x = apply_norm(params["ln2"], cfg, h)
    y, cm_shift = ssm_mod.apply_rwkv_cm(params["cm"], cfg, sh, x,
                                        shift_state=state["shift_cm"])
    h = h + y
    state = {"wkv": tm_state["wkv"], "shift_tm": tm_state["shift"],
             "shift_cm": cm_shift}
    return h, state

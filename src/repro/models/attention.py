"""Attention: GQA (bias/qk-norm/sliding-window/ALiBi) and MLA (DeepSeek-V2).

Three compute paths, chosen statically from sequence length:

* ``dense``   — materialise (S, T) logits; used for S*T small (train_4k).
* ``flash``   — double python-loop over (q-chunk, kv-chunk) pairs with online
                softmax, skipping fully-masked upper-triangle pairs.  Unrolled
                (no ``lax.scan``) so XLA ``cost_analysis`` FLOP counts stay
                exact (scan bodies are counted once, see DESIGN.md §6) and
                peak memory stays O(chunk * chunk).
* ``decode``  — single-query attention over a KV cache (grouped einsum, no KV
                head expansion).

The serving engine selects between two COMPUTE BACKENDS per attention call
(threaded from ``serving.GeoServingSystem(backend=...)`` down through the
block functions): ``backend="xla"`` runs the paths above (the oracle — and
the dry-run lowering path: Pallas kernels cannot lower to the CPU backend
used by the 512-device dry-run), ``backend="pallas"`` dispatches to the
kernels in ``repro.kernels`` (interpret mode off-TPU, Mosaic on real TPUs)
whenever the kernels' ``*_unsupported`` predicates accept the call's
feature set, and falls back to the XLA path otherwise — a kernel gap can
cost performance, never correctness.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import (
    decode_attention,
    decode_attention_unsupported,
    flash_attention,
    flash_attention_unsupported,
)
from repro.kernels.runtime import NO_WINDOW, use_kernel
from repro.models.layers import (
    ParamBuilder,
    ShardingCtx,
    alibi_slopes,
    apply_rope,
    rope_angles,
    rms_norm_simple,
)

_NEG_INF = -1e30
_BIG_WINDOW = NO_WINDOW  # "no window" — shared sentinel, kernels/runtime.py
Q_CHUNK = 2048
KV_CHUNK = 1024
DENSE_MAX_T = 2048  # use the dense path when kv length <= this


def _dt(cfg):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# Mask / bias
# ---------------------------------------------------------------------------


def _mask_bias(q_pos, kv_pos, window, slopes=None, causal=True):
    """Additive f32 bias (H|1, S, T): causal + sliding window + optional ALiBi.

    ``window`` may be a traced scalar (data-dependent local/global layers).
    """
    diff = q_pos[:, None] - kv_pos[None, :]  # (S, T); >= 0 means past/self
    if causal:
        ok = (diff >= 0) & (diff < window)
    else:
        ok = jnp.ones_like(diff, dtype=bool)
    bias = jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32)[None]  # (1,S,T)
    if slopes is not None:
        bias = bias + slopes[:, None, None] * (-jnp.abs(diff))[None].astype(jnp.float32)
    return bias


# ---------------------------------------------------------------------------
# Core softmax-attention on (B, S, H, D) with expanded KV heads
# ---------------------------------------------------------------------------


def _dense_attn(q, k, v, bias):
    """q (B,S,H,D), k (B,T,H,D), v (B,T,H,Dv), bias (H|1,S,T) -> (B,S,H,Dv)."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale + bias[None]
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bthd->bshd", probs.astype(v.dtype), v)


def _flash_attn(q, k, v, q_pos, kv_pos, window, slopes=None, causal=True,
                q_start=0):
    """Double-chunked online-softmax attention (unrolled; no scan).

    (q-chunk, kv-chunk) pairs that are *statically* above the causal diagonal
    are skipped entirely — halving FLOPs vs dense-then-mask.  Safe with a
    traced ``window`` (a window only masks more, never less, than causal).
    Assumes q_pos/kv_pos are aligned aranges when ``causal`` (self-attention),
    with queries offset by the static ``q_start`` (chunked prefill: queries
    [q_start, q_start+S) attend over keys [0, q_start+S)).
    """
    B, S, H, D = q.shape
    T = k.shape[1]
    Dv = v.shape[-1]
    scale = 1.0 / np.sqrt(D)
    n_q = (S + Q_CHUNK - 1) // Q_CHUNK
    n_kv = (T + KV_CHUNK - 1) // KV_CHUNK
    outs = []
    dep = None  # forces sequential q-chunk scheduling (bounds peak memory)
    for qi in range(n_q):
        q_lo, q_hi = qi * Q_CHUNK, min(S, (qi + 1) * Q_CHUNK)
        qc = q[:, q_lo:q_hi]
        if dep is not None:
            # optimization_barrier ties this chunk's inputs to the previous
            # chunk's output so XLA cannot interleave all chains at once
            # (each chain holds an O(chunk*chunk) f32 logits block).
            qc, _ = jax.lax.optimization_barrier((qc, dep))
        qp = q_pos[q_lo:q_hi]
        m = jnp.full((B, H, q_hi - q_lo), _NEG_INF, jnp.float32)
        l = jnp.zeros((B, H, q_hi - q_lo), jnp.float32)
        acc = jnp.zeros((B, q_hi - q_lo, H, Dv), jnp.float32)
        for ki in range(n_kv):
            k_lo, k_hi = ki * KV_CHUNK, min(T, (ki + 1) * KV_CHUNK)
            if causal and k_lo > q_start + q_hi - 1:
                continue  # statically above the causal diagonal
            kc, vc = k[:, k_lo:k_hi], v[:, k_lo:k_hi]
            kp = kv_pos[k_lo:k_hi]
            logits = jnp.einsum(
                "bshd,bthd->bhst", qc, kc, preferred_element_type=jnp.float32
            ) * scale
            logits = logits + _mask_bias(qp, kp, window, slopes, causal)[None]
            blk_max = jnp.max(logits, axis=-1)
            new_m = jnp.maximum(m, blk_max)
            corr = jnp.exp(m - new_m)
            p = jnp.exp(logits - new_m[..., None])
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
                "bhst,bthd->bshd", p.astype(v.dtype), vc
            ).astype(jnp.float32)
            m = new_m
        out = acc / jnp.maximum(l.transpose(0, 2, 1)[..., None], 1e-30)
        out = out.astype(q.dtype)
        dep = out
        outs.append(out)
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def attention_core(q, k, v, q_pos, kv_pos, window=None, slopes=None,
                   causal=True, q_start=0):
    """Dispatch dense vs flash based on static shapes.

    ``q_start`` (static) is the absolute position of the first query — only
    used by the flash path's static causal-skip when queries are a suffix of
    the key range (chunked prefill); the mask itself is always positional.
    """
    window = _BIG_WINDOW if window is None else window
    S, T = q.shape[1], k.shape[1]
    if T <= DENSE_MAX_T and S * T <= DENSE_MAX_T * DENSE_MAX_T // 4:
        bias = _mask_bias(q_pos, kv_pos, window, slopes, causal)
        return _dense_attn(q, k, v, bias)
    return _flash_attn(q, k, v, q_pos, kv_pos, window, slopes, causal,
                       q_start)


def _use_pallas_flash(backend: str, *, causal=True, window=None, slopes=None,
                      q_start: int = 0) -> bool:
    """Dispatch predicate for full-sequence attention: the Pallas flash
    kernel serves the call iff the backend asks for it AND the kernel's own
    guard accepts the feature set (otherwise the XLA path is the
    fallback — same numbers, no silent mishandling; each fallback is
    counted in ``kernels.runtime.XLA_FALLBACKS``)."""
    return use_kernel(backend, "flash_attention",
                      flash_attention_unsupported(
                          causal=causal, window=window, slopes=slopes,
                          q_start=q_start))


def _use_pallas_decode(backend: str, *, causal=True, window=None,
                       slopes=None, kv_len=None, scale=None) -> bool:
    """Dispatch predicate for single-token decode attention (see
    :func:`_use_pallas_flash`)."""
    return use_kernel(backend, "decode_attention",
                      decode_attention_unsupported(
                          causal=causal, window=window, slopes=slopes,
                          kv_len=kv_len, scale=scale))


def decode_attention_xla(q, ck, cv, pos, window=None, slopes=None,
                         causal=True, kv_len=None):
    """Single-step attention over a cache without KV-head expansion.

    q (B,1,H,D); ck (B,T,Kv,D); cv (B,T,Kv,Dv); pos: current position scalar.
    ``kv_len``: optional (traced) count of valid cache positions — masks
    ``kv_pos >= kv_len``.  Needed by non-causal (cross) attention when the
    cache is allocated longer than the valid prefix; causal attention is
    already masked by ``pos``.
    """
    B, _, H, D = q.shape
    T, Kv = ck.shape[1], ck.shape[2]
    G = H // Kv
    window = _BIG_WINDOW if window is None else window
    qg = q.reshape(B, Kv, G, D)
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bkgd,btkd->bkgt", qg, ck,
                        preferred_element_type=jnp.float32) * scale
    kv_pos = jnp.arange(T)
    diff = pos - kv_pos
    ok = ((diff >= 0) & (diff < window)) if causal else jnp.ones((T,), bool)
    if kv_len is not None:
        ok = ok & (kv_pos < kv_len)
    if slopes is not None:
        logits = logits + (slopes.reshape(Kv, G)[None, :, :, None]
                           * (-jnp.abs(diff))[None, None, None, :])
    logits = jnp.where(ok[None, None, None, :], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs.astype(cv.dtype), cv)
    return out.reshape(B, 1, H, cv.shape[-1])


# ---------------------------------------------------------------------------
# GQA attention module
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: ModelConfig, width: Optional[int] = None):
    d = width or cfg.d_model
    H, Kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = _dt(cfg)
    pb = ParamBuilder(key)
    pb.dense("wq", (d, H, hd), ("embed_fsdp", "heads", "head_dim"), dt)
    pb.dense("wk", (d, Kv, hd), ("embed_fsdp", "kv_heads", "head_dim"), dt)
    pb.dense("wv", (d, Kv, hd), ("embed_fsdp", "kv_heads", "head_dim"), dt)
    pb.dense("wo", (H, hd, cfg.d_model), ("heads", "head_dim", "embed_fsdp"), dt)
    if cfg.qkv_bias:
        pb.zeros("bq", (H, hd), ("heads", "head_dim"), dt)
        pb.zeros("bk", (Kv, hd), ("kv_heads", "head_dim"), dt)
        pb.zeros("bv", (Kv, hd), ("kv_heads", "head_dim"), dt)
    if cfg.qk_norm:
        pb.ones("q_norm", (hd,), ("head_dim",), jnp.float32)
        pb.ones("k_norm", (hd,), ("head_dim",), jnp.float32)
    return pb.build()


def _q_proj(params, cfg, x):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
    return q


def _kv_proj(params, cfg, x):
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    return k, v


def gqa_encoder_kv(params, cfg: ModelConfig, sh: ShardingCtx, enc_h):
    """Cross-attention K/V from encoder states (computed once per session)."""
    k, v = _kv_proj(params, cfg, enc_h)
    return sh.act(k, "batch", "seq", "kv_heads_act", None), \
        sh.act(v, "batch", "seq", "kv_heads_act", None)


def apply_gqa_full(params, cfg: ModelConfig, sh: ShardingCtx, x, positions,
                   window=None, cross_kv=None, prefix_kv=None,
                   backend: str = "xla"):
    """Full-sequence attention (train / prefill).

    Returns (out, (k, v)) — k/v in un-expanded (B,S,Kv,hd) layout for caching
    (None for cross-attention).  ``cross_kv``: encoder (k, v) — non-causal.

    ``prefix_kv``: optional (k, v) of an already-prefilled prefix (chunked
    prefill).  The chunk's queries attend over prefix + chunk keys; the
    returned cache entry holds only the CHUNK's k/v (the prefix is already
    cached).  ``positions`` must then be ``P + arange(S_chunk)`` where P is
    the prefix length.  ``backend``: "xla" (oracle) or "pallas" (flash
    kernel when the feature set is supported, XLA fallback otherwise).
    """
    causal = cross_kv is None
    q_start = 0
    q = _q_proj(params, cfg, x)
    if causal:
        k, v = _kv_proj(params, cfg, x)
        if cfg.qk_norm:
            q = rms_norm_simple(q, params["q_norm"], cfg.norm_eps)
            k = rms_norm_simple(k, params["k_norm"], cfg.norm_eps)
        if cfg.pos_kind == "rope":
            cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        k = sh.act(k, "batch", "seq", "kv_heads_act", None)
        v = sh.act(v, "batch", "seq", "kv_heads_act", None)
        kv_out = (k, v)
        if prefix_kv is not None:
            pk, pv = prefix_kv
            q_start = pk.shape[1]
            k = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
            v = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
            kv_pos = jnp.arange(k.shape[1])
        else:
            kv_pos = positions
    else:
        k, v = cross_kv
        kv_pos = jnp.arange(k.shape[1])
        kv_out = None
    # Sequence-parallel attention (hillclimb B, EXPERIMENTS.md §Perf): when
    # the head count does not divide the model axis (qwen/llama4 40H,
    # gemma 8H), shard the QUERY sequence over "model" instead — attention
    # compute/memory drops by the axis size at the cost of replicated-KV
    # reads.  "attn_seq_q" maps to None for head-shardable archs.
    q = sh.act(q, "batch", "attn_seq_q", "heads_act", None)
    slopes = alibi_slopes(cfg.n_heads) if cfg.pos_kind == "alibi" else None
    win = window if causal else None  # non-causal ignores the window
    if _use_pallas_flash(backend, causal=causal, window=win, slopes=slopes,
                         q_start=q_start):
        # kernel contract: q_pos = q_start + arange(S), kv_pos = arange(T)
        # — exactly what the (chunked-)prefill call sites pass; GQA groups
        # are index-mapped inside the kernel (no KV head expansion copy)
        out = flash_attention(q, k, v, causal=causal, window=win,
                              slopes=slopes, q_start=q_start)
    else:
        G = cfg.n_heads // cfg.n_kv_heads
        k_exp = jnp.repeat(k, G, axis=2) if G > 1 else k
        v_exp = jnp.repeat(v, G, axis=2) if G > 1 else v
        out = attention_core(q, k_exp, v_exp, positions, kv_pos, window,
                             slopes, causal=causal, q_start=q_start)
    out = sh.act(out, "batch", "attn_seq_q", "heads_act", None)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y, kv_out


def apply_gqa_decode(params, cfg: ModelConfig, sh: ShardingCtx, x, cache_k,
                     cache_v, pos, window=None, cross: bool = False,
                     kv_len=None, backend: str = "xla"):
    """Single-token decode.  x (B,1,d), cache (B,T,Kv,hd).

    Self-attention: writes the new token's K/V into the cache at ``pos`` and
    attends over the updated cache.  Returns (y, cache_k, cache_v).
    Cross-attention: the cache is the (static) encoder KV; returned
    unchanged.  ``kv_len`` masks cache positions beyond the valid encoder
    prefix when the cache is over-allocated (pooled serving).  ``backend``:
    "xla" (oracle) or "pallas" (decode kernel when the feature set is
    supported, XLA fallback otherwise).
    """
    q = _q_proj(params, cfg, x)
    if not cross:
        k, v = _kv_proj(params, cfg, x)
        if cfg.qk_norm:
            q = rms_norm_simple(q, params["q_norm"], cfg.norm_eps)
            k = rms_norm_simple(k, params["k_norm"], cfg.norm_eps)
        if cfg.pos_kind == "rope":
            posv = jnp.asarray(pos)[None]
            cos, sin = rope_angles(posv, cfg.head_dim, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k.astype(cache_k.dtype), pos, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v.astype(cache_v.dtype), pos, axis=1)
    elif cfg.qk_norm:
        q = rms_norm_simple(q, params["q_norm"], cfg.norm_eps)

    slopes = alibi_slopes(cfg.n_heads) if cfg.pos_kind == "alibi" else None
    win = None if cross else window  # non-causal ignores the window
    if _use_pallas_decode(backend, causal=not cross, window=win,
                          slopes=slopes, kv_len=kv_len):
        out = decode_attention(q, cache_k, cache_v, pos, window=win,
                               slopes=slopes, causal=not cross,
                               kv_len=kv_len)
    else:
        out = decode_attention_xla(q, cache_k, cache_v, pos, window, slopes,
                                   causal=not cross, kv_len=kv_len)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLA attention module (DeepSeek-V2)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig):
    d, H = cfg.d_model, cfg.n_heads
    nope, rope = cfg.head_dim, cfg.rope_head_dim
    lora, qlora = cfg.kv_lora_rank, cfg.q_lora_rank
    dt = _dt(cfg)
    pb = ParamBuilder(key)
    pb.dense("wdq", (d, qlora), ("embed_fsdp", "qlora"), dt)
    pb.ones("q_norm", (qlora,), ("qlora",), jnp.float32)
    pb.dense("wuq", (qlora, H, nope + rope), ("qlora", "heads", "qk_dim"), dt)
    pb.dense("wdkv", (d, lora + rope), ("embed_fsdp", "kvlora"), dt)
    pb.ones("kv_norm", (lora,), ("kvlora",), jnp.float32)
    pb.dense("wuk", (lora, H, nope), ("kvlora", "heads", "qk_dim"), dt)
    pb.dense("wuv", (lora, H, nope), ("kvlora", "heads", "qk_dim"), dt)
    pb.dense("wo", (H, nope, d), ("heads", "qk_dim", "embed_fsdp"), dt)
    return pb.build()


def _mla_q(params, cfg, x, positions):
    nope, rope = cfg.head_dim, cfg.rope_head_dim
    cq = x @ params["wdq"].astype(x.dtype)
    cq = rms_norm_simple(cq, params["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsq,qhk->bshk", cq, params["wuq"].astype(x.dtype))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    cos, sin = rope_angles(positions, rope, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def mla_latent(params, cfg: ModelConfig, x, positions):
    """Down-project to the cached representation: latent (B,S,lora) + k_rope."""
    lora, rope = cfg.kv_lora_rank, cfg.rope_head_dim
    ckv = x @ params["wdkv"].astype(x.dtype)
    latent, k_rope = ckv[..., :lora], ckv[..., lora:]
    latent = rms_norm_simple(latent, params["kv_norm"], cfg.norm_eps)
    cos, sin = rope_angles(positions, rope, cfg.rope_theta)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    return latent, k_rope


def apply_mla_full(params, cfg: ModelConfig, sh: ShardingCtx, x, positions,
                   prefix_kv=None, backend: str = "xla"):
    """Full-sequence MLA (unabsorbed — faithful for train/prefill).

    Returns (out, (latent, k_rope)) for caching.  ``prefix_kv``: optional
    (latent, k_rope) of an already-prefilled prefix (chunked prefill); the
    prefix latents are up-projected alongside the chunk's and the chunk's
    queries attend over both.  The returned cache entry holds only the
    CHUNK's latent/k_rope.  ``backend``: "xla" or "pallas" (the flash
    kernel runs the up-projected per-head attention, Kv = H).
    """
    q_nope, q_rope = _mla_q(params, cfg, x, positions)
    latent, k_rope = mla_latent(params, cfg, x, positions)
    kv_out = (latent, k_rope)
    q_start = 0
    if prefix_kv is not None:
        plat, pkr = prefix_kv
        q_start = plat.shape[1]
        latent = jnp.concatenate([plat.astype(latent.dtype), latent], axis=1)
        k_rope = jnp.concatenate([pkr.astype(k_rope.dtype), k_rope], axis=1)
        kv_pos = jnp.arange(latent.shape[1])
    else:
        kv_pos = positions
    k_nope = jnp.einsum("bsl,lhk->bshk", latent, params["wuk"].astype(x.dtype))
    v = jnp.einsum("bsl,lhk->bshk", latent, params["wuv"].astype(x.dtype))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    krope_bc = jnp.broadcast_to(
        k_rope[:, :, None, :],
        k_nope.shape[:3] + (k_rope.shape[-1],))
    k = jnp.concatenate([k_nope, krope_bc], axis=-1)
    q = sh.act(q, "batch", "seq", "heads_act", None)
    k = sh.act(k, "batch", "seq", "heads_act", None)
    v = sh.act(v, "batch", "seq", "heads_act", None)
    if _use_pallas_flash(backend, q_start=q_start):
        # per-head K/V (the MLA up-projection), so Kv = H; the faithful
        # 1/sqrt(nope+rope) scale is 1/sqrt(Dk) here — the kernel default
        out = flash_attention(q, k, v, causal=True, q_start=q_start)
    else:
        out = attention_core(q, k, v, positions, kv_pos, q_start=q_start)
    out = sh.act(out, "batch", "seq", "heads_act", None)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    # steer XLA to reduce-scatter (not all-reduce + slice) into the
    # sequence-sharded residual layout (hillclimb A iter 3)
    y = sh.act(y, "batch", "seq_act", None)
    return y, kv_out


def apply_mla_decode(params, cfg: ModelConfig, sh: ShardingCtx, x,
                     cache_latent, cache_krope, pos, backend: str = "xla"):
    """Absorbed-form MLA decode: attend in latent space (MQA with kv_head=1).

    cache_latent (B,T,lora), cache_krope (B,T,rope).  Writes the new token's
    latent/k_rope at ``pos`` and attends.  Returns (y, cache_latent,
    cache_krope).  ``backend``: "xla" (the oracle pre-scales q to undo the
    helper's 1/sqrt(lora+rope)) or "pallas" (the kernel takes the faithful
    1/sqrt(nope+rope) scale directly).
    """
    nope, rope = cfg.head_dim, cfg.rope_head_dim
    posv = jnp.asarray(pos)[None]
    q_nope, q_rope = _mla_q(params, cfg, x, posv)
    new_latent, new_krope = mla_latent(params, cfg, x, posv)
    cache_latent = jax.lax.dynamic_update_slice_in_dim(
        cache_latent, new_latent.astype(cache_latent.dtype), pos, axis=1)
    cache_krope = jax.lax.dynamic_update_slice_in_dim(
        cache_krope, new_krope.astype(cache_krope.dtype), pos, axis=1)
    # absorb W_uk into the query:  q_lat[h] = q_nope[h] @ W_uk[:, h, :]^T
    q_lat = jnp.einsum("bshk,lhk->bshl", q_nope, params["wuk"].astype(x.dtype))
    q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)  # (B,1,H,lora+rope)
    keys = jnp.concatenate([cache_latent, cache_krope], axis=-1)[:, :, None, :]
    # the faithful softmax scale is 1/sqrt(nope+rope), not the
    # 1/sqrt(lora+rope) either helper would derive from q_eff's width
    faithful = 1.0 / np.sqrt(nope + rope)
    if _use_pallas_decode(backend, scale=faithful):
        ctx = decode_attention(q_eff, keys, cache_latent[:, :, None, :],
                               pos, scale=faithful)
    else:
        # decode_attention_xla has no scale override — pre-scale q so its
        # 1/sqrt(lora+rope) lands on the faithful value
        scale_fix = np.sqrt(q_eff.shape[-1]) * faithful
        ctx = decode_attention_xla(q_eff * scale_fix, keys,
                                   cache_latent[:, :, None, :], pos)
    # ctx (B,1,H,lora): apply W_uv per head then the output projection.
    v_heads = jnp.einsum("bshl,lhk->bshk", ctx, params["wuv"].astype(x.dtype))
    y = jnp.einsum("bshk,hkd->bsd", v_heads, params["wo"].astype(x.dtype))
    return y, cache_latent, cache_krope

"""State-space sequence mixers: Mamba2 (SSD) and RWKV6 (Finch) time/channel mix.

Both use *chunked* formulations: dense intra-chunk math + a log-depth
``jax.lax.associative_scan`` over per-chunk states.  No ``lax.scan`` /
``while`` appears in the full-sequence path, keeping XLA ``cost_analysis``
FLOP counts exact (scan bodies are counted once — DESIGN.md §6) and avoiding
O(S·state) memory.

Decode paths are single-step recurrences over carried state, mirroring what
the Pallas kernels in ``repro.kernels.{ssd,wkv6}`` implement for real TPUs.

State-dict key names are a SERVING CONTRACT: the geo engine's state pools
(``repro.serving.kv_cache``) dispatch writes by leaf name — ``k``/``v``
(and MLA ``latent``/``krope``) are length-indexed and written per chunk,
everything else (``ssm``, ``conv``, ``wkv``, ``shift*``) is recurrent and
overwritten whole.  Renaming a key here silently changes pool semantics;
keep names out of the length-indexed set unless the leaf really has a time
axis.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.ssd import ssd as _ssd_kernel
from repro.kernels.runtime import use_kernel
from repro.kernels.ssd import ssd_unsupported
from repro.kernels.wkv6 import wkv6 as _wkv6_kernel
from repro.kernels.wkv6 import wkv6_unsupported
from repro.models.layers import ParamBuilder, ShardingCtx, rms_norm_simple

MAMBA_CHUNK = 256
RWKV_CHUNK = 16
# Per-step log-decay clamp for RWKV6 (numerical-stability bound for the
# factored intra-chunk form; mirrored exactly by kernels/wkv6/ref.py).
RWKV_MIN_LOG_W = -5.0


def _dt(cfg):
    return jnp.dtype(cfg.param_dtype)


def _pad_to(x, mult: int, axis: int):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x, s
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), s


# ===========================================================================
# Mamba2 (SSD)
# ===========================================================================


def init_mamba(key, cfg: ModelConfig):
    """Projections are separate weights (not one fused in_proj) so each output
    dim shards cleanly under TP without re-shard at the split boundaries."""
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    w = cfg.conv_width
    conv_dim = di + 2 * n
    dt = _dt(cfg)
    pb = ParamBuilder(key)
    pb.dense("wz", (d, di), ("embed_fsdp", "inner"), dt)
    pb.dense("wx", (d, di), ("embed_fsdp", "inner"), dt)
    pb.dense("wB", (d, n), ("embed_fsdp", "state_nosplit"), dt)
    pb.dense("wC", (d, n), ("embed_fsdp", "state_nosplit"), dt)
    pb.dense("wdt", (d, h), ("embed_fsdp", "ssm_heads"), dt)
    pb.dense("conv_w", (w, conv_dim), ("conv", "inner_nosplit"), dt, scale=0.5)
    pb.zeros("conv_b", (conv_dim,), ("inner_nosplit",), dt)
    pb.const("dt_bias", jnp.zeros((h,), jnp.float32), ("ssm_heads",))
    pb.const("A_log", jnp.log(jnp.linspace(1.0, 16.0, h)), ("ssm_heads",))
    pb.zeros("D", (h,), ("ssm_heads",), jnp.float32)
    pb.ones("norm", (di,), ("inner_nosplit",), jnp.float32)
    pb.dense("out_proj", (di, d), ("inner", "embed_fsdp"), dt)
    return pb.build()


def _mamba_inputs(params, cfg: ModelConfig, x):
    """Shared projections for prefill and decode.

    Returns (z, (xc, B, C) pre-conv pieces, dt_raw).  The depthwise conv is
    applied per piece (it never mixes channels) so the TP sharding of xc
    ("inner" -> model axis) survives without a re-shard at split boundaries.
    """
    z = x @ params["wz"].astype(x.dtype)
    xc = x @ params["wx"].astype(x.dtype)
    Bp = x @ params["wB"].astype(x.dtype)
    Cp = x @ params["wC"].astype(x.dtype)
    dt_raw = x @ params["wdt"].astype(x.dtype)
    return z, (xc, Bp, Cp), dt_raw


def _conv_slices(params, cfg: ModelConfig):
    di, n = cfg.d_inner, cfg.ssm_state
    w, b = params["conv_w"], params["conv_b"]
    return ((w[:, :di], b[:di]), (w[:, di: di + n], b[di: di + n]),
            (w[:, di + n:], b[di + n:]))


def _mamba_post(params, cfg: ModelConfig, y, z):
    """Gated RMSNorm + output projection.  y/z: (..., d_inner)."""
    g = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    g = rms_norm_simple(g, params["norm"], cfg.norm_eps)
    return g @ params["out_proj"].astype(g.dtype)


def apply_mamba_full(params, cfg: ModelConfig, sh: ShardingCtx, x,
                     backend: str = "xla"):
    """Full-sequence Mamba2.  x (B,S,d) -> (y (B,S,d), state dict).

    state = {"ssm": (B,h,p,n) f32, "conv": (B, w-1, d_inner+2n)}.
    ``backend``: "xla" runs the chunked jnp scan below; "pallas" runs the
    ``repro.kernels.ssd`` kernel (carried state out) for the scan itself —
    projections/conv/gating stay jnp either way.
    """
    B, S, _ = x.shape
    di, n, h, w = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.conv_width
    p = cfg.ssm_head_dim
    z, pieces, dt_raw = _mamba_inputs(params, cfg, x)

    # causal depthwise conv (width w) applied per piece — preserves sharding
    def conv1d(piece, cw, cb):
        pad = jnp.pad(piece, ((0, 0), (w - 1, 0), (0, 0)))
        out = sum(pad[:, i: i + S] * cw[i].astype(x.dtype) for i in range(w))
        return jax.nn.silu(out + cb.astype(x.dtype)), pad[:, S:]

    tails = []
    convs = []
    for piece, (cw, cb) in zip(pieces, _conv_slices(params, cfg)):
        out, tail = conv1d(piece, cw, cb)
        convs.append(out)
        tails.append(tail)
    xc, Bm, Cm = convs[0], convs[1].astype(jnp.float32), convs[2].astype(jnp.float32)
    conv_tail = jnp.concatenate(tails, axis=-1)  # (B, w-1, di+2n) decode carry
    dtv = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])  # (B,S,h)
    A = -jnp.exp(params["A_log"])  # (h,) negative

    xh = xc.reshape(B, S, h, p).astype(jnp.float32)
    if use_kernel(backend, "ssd", ssd_unsupported()):
        y, ssm_state = _ssd_kernel(xh, Bm, Cm, dtv, A, params["D"])
        y = y.reshape(B, S, di).astype(x.dtype)
        y = sh.act(y, "batch", "seq", "inner_act")
        out = _mamba_post(params, cfg, y, z)
        return out, {"ssm": ssm_state, "conv": conv_tail.astype(jnp.float32)}
    # ---- chunked SSD ----
    Q = min(MAMBA_CHUNK, max(16, S))
    xh, S0 = _pad_to(xh, Q, 1)
    Bm, _ = _pad_to(Bm, Q, 1)
    Cm, _ = _pad_to(Cm, Q, 1)
    dtv, _ = _pad_to(dtv, Q, 1)
    Sp = xh.shape[1]
    nc = Sp // Q
    xh = xh.reshape(B, nc, Q, h, p)
    Bm = Bm.reshape(B, nc, Q, n)
    Cm = Cm.reshape(B, nc, Q, n)
    dtv = dtv.reshape(B, nc, Q, h)

    la = dtv * A  # (B,nc,Q,h) log-decay per step, <= 0
    seg = jnp.cumsum(la, axis=2)  # inclusive
    # intra-chunk:  Y[t] = sum_{i<=t} exp(seg[t]-seg[i]) * (C[t]·B[i]) dt[i] x[i]
    G = jnp.einsum("bcqn,bckn->bcqk", Cm, Bm)  # (B,nc,Q,Q)
    # clamp masked (upper-triangle) exponents to <= 0: they are discarded by
    # the mask, but exp(+big)=inf would poison the VJP (0 * inf = NaN)
    diff = jnp.minimum(seg[:, :, :, None, :] - seg[:, :, None, :, :], 0.0)
    decay = jnp.exp(diff)  # (B,nc,Q,Q,h)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    M = jnp.where(mask[None, None, :, :, None], G[..., None] * decay, 0.0)
    xb = xh * dtv[..., None]  # dt-weighted inputs
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", M, xb)
    # chunk-local end states and decays
    decay_to_end = jnp.exp(seg[:, :, -1:, :] - seg)  # (B,nc,Q,h)
    S_local = jnp.einsum("bcqh,bcqhp,bcqn->bchpn", decay_to_end * dtv, xh, Bm)
    A_chunk = jnp.exp(seg[:, :, -1, :])  # (B,nc,h)

    def combine(e1, e2):
        a1, s1 = e1
        a2, s2 = e2
        return a1 * a2, a2[..., None, None] * s1 + s2

    A_sc, S_sc = jax.lax.associative_scan(combine, (A_chunk, S_local), axis=1)
    # chunk-start states: shifted inclusive scan (zeros for the first chunk)
    S_start = jnp.concatenate(
        [jnp.zeros_like(S_sc[:, :1]), S_sc[:, :-1]], axis=1)
    y_inter = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", Cm, S_start, jnp.exp(seg))

    y = (y_intra + y_inter).reshape(B, Sp, h, p)[:, :S0]
    y = y + xh.reshape(B, Sp, h, p)[:, :S0] * params["D"][None, None, :, None]
    y = y.reshape(B, S0, di).astype(x.dtype)
    y = sh.act(y, "batch", "seq", "inner_act")
    out = _mamba_post(params, cfg, y, z[:, :S0])

    state = {"ssm": S_sc[:, -1], "conv": conv_tail.astype(jnp.float32)}
    return out, state


def apply_mamba_decode(params, cfg: ModelConfig, sh: ShardingCtx, x, state):
    """Single-token Mamba2 step.  x (B,1,d) -> (y (B,1,d), new state)."""
    B = x.shape[0]
    di, n, h, w = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.conv_width
    p = cfg.ssm_head_dim
    z, pieces, dt_raw = _mamba_inputs(params, cfg, x)
    conv_in = jnp.concatenate(pieces, axis=-1)  # (B,1,conv_dim)
    window = jnp.concatenate(
        [state["conv"].astype(x.dtype), conv_in], axis=1)  # (B,w,conv_dim)
    conv = jnp.einsum("bwc,wc->bc", window, params["conv_w"].astype(x.dtype))
    conv = jax.nn.silu(conv + params["conv_b"].astype(x.dtype))  # (B,conv_dim)
    new_conv_state = window[:, 1:].astype(jnp.float32)

    xc = conv[:, :di].reshape(B, h, p).astype(jnp.float32)
    Bm = conv[:, di: di + n].astype(jnp.float32)
    Cm = conv[:, di + n:].astype(jnp.float32)
    dtv = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    a = jnp.exp(dtv * A)  # (B,h)
    s = state["ssm"] * a[..., None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dtv, xc, Bm)
    y = jnp.einsum("bn,bhpn->bhp", Cm, s) + xc * params["D"][None, :, None]
    y = y.reshape(B, 1, di).astype(x.dtype)
    out = _mamba_post(params, cfg, y, z)
    return out, {"ssm": s, "conv": new_conv_state}


# ===========================================================================
# RWKV6 (Finch)
# ===========================================================================

_TM_LORA = 32
_DECAY_LORA = 64
_N_MIX = 5  # w, k, v, r, g


def init_rwkv_tm(key, cfg: ModelConfig):
    d = cfg.d_model
    h, hd = cfg.ssm_heads, cfg.ssm_head_dim
    dt = _dt(cfg)
    pb = ParamBuilder(key)
    pb.zeros("mu_x", (d,), ("embed_nosplit",), jnp.float32)
    pb.zeros("mu", (_N_MIX, d), ("mix", "embed_nosplit"), jnp.float32)
    pb.dense("mix_A", (d, _N_MIX * _TM_LORA), ("embed_nosplit", "lora"), jnp.float32)
    pb.dense("mix_B", (_N_MIX, _TM_LORA, d), ("mix", "lora", "embed_nosplit"),
             jnp.float32, scale=0.1)
    pb.dense("wr", (d, d), ("embed_fsdp", "heads_x_dim"), dt)
    pb.dense("wk", (d, d), ("embed_fsdp", "heads_x_dim"), dt)
    pb.dense("wv", (d, d), ("embed_fsdp", "heads_x_dim"), dt)
    pb.dense("wg", (d, d), ("embed_fsdp", "heads_x_dim"), dt)
    pb.const("w0", jnp.full((d,), -1.0, jnp.float32), ("embed_nosplit",))
    pb.dense("w_A", (d, _DECAY_LORA), ("embed_nosplit", "lora"), jnp.float32)
    pb.dense("w_B", (_DECAY_LORA, d), ("lora", "embed_nosplit"), jnp.float32,
             scale=0.1)
    pb.const("u", jnp.zeros((h, hd), jnp.float32), ("ssm_heads", "ssm_dim"))
    pb.ones("out_norm", (d,), ("embed_nosplit",), jnp.float32)
    pb.dense("wo", (d, d), ("heads_x_dim", "embed_fsdp"), dt)
    return pb.build()


def _rwkv_mix(params, x, sx):
    """Data-dependent token-shift interpolation (ddlerp) for w,k,v,r,g.

    x, sx: (B,S,d).  Returns 5 mixed tensors (B,S,d) in order w,k,v,r,g.
    """
    dx = (sx - x).astype(jnp.float32)
    xx = x.astype(jnp.float32) + dx * params["mu_x"]
    lo = jnp.tanh(xx @ params["mix_A"])  # (B,S,5*lora)
    lo = lo.reshape(*lo.shape[:-1], _N_MIX, _TM_LORA)
    delta = jnp.einsum("bsml,mld->msbd", lo, params["mix_B"]).transpose(0, 2, 1, 3)
    # delta: (5, B, S, d)
    outs = []
    for i in range(_N_MIX):
        mix = params["mu"][i] + delta[i]
        outs.append((x.astype(jnp.float32) + dx * mix).astype(x.dtype))
    return outs


def _rwkv_decay(params, xw):
    """Per-channel log-decay log(w_t) <= 0 with the stability clamp."""
    omega = params["w0"] + jnp.tanh(
        xw.astype(jnp.float32) @ params["w_A"]) @ params["w_B"]
    return jnp.clip(-jnp.exp(omega), RWKV_MIN_LOG_W, -1e-4)


def apply_rwkv_tm_full(params, cfg: ModelConfig, sh: ShardingCtx, x,
                       backend: str = "xla"):
    """Full-sequence RWKV6 time-mix.  x (B,S,d) -> (y, state dict).

    state = {"wkv": (B,h,hd,hd) f32, "shift": (B,d)} — last-token carry.
    ``backend``: "xla" runs ``_wkv6_chunked`` below; "pallas" runs the
    ``repro.kernels.wkv6`` kernel (carried state out) for the recurrence —
    mixing/decay/gating stay jnp either way.
    """
    B, S, d = x.shape
    h, hd = cfg.ssm_heads, cfg.ssm_head_dim
    sx = jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
    xw, xk, xv, xr, xg = _rwkv_mix(params, x, sx)
    r = (xr @ params["wr"].astype(x.dtype)).reshape(B, S, h, hd)
    k = (xk @ params["wk"].astype(x.dtype)).reshape(B, S, h, hd)
    v = (xv @ params["wv"].astype(x.dtype)).reshape(B, S, h, hd)
    g = jax.nn.silu((xg @ params["wg"].astype(x.dtype)).astype(jnp.float32))
    lw = _rwkv_decay(params, xw).reshape(B, S, h, hd)  # log decay per channel

    if use_kernel(backend, "wkv6", wkv6_unsupported()):
        y, wkv_state = _wkv6_kernel(
            r.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), lw, params["u"])
    else:
        y, wkv_state = _wkv6_chunked(
            r.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), lw, params["u"])
    # per-head group-norm then gate
    y = y.reshape(B, S, d)
    y = rms_norm_simple(
        y.reshape(B, S, h, hd), jnp.ones((hd,), jnp.float32), cfg.norm_eps
    ).reshape(B, S, d).astype(jnp.float32)
    y = (y * params["out_norm"] * g).astype(x.dtype)
    out = y @ params["wo"].astype(x.dtype)
    state = {"wkv": wkv_state, "shift": x[:, -1].astype(jnp.float32)}
    return out, state


def _wkv6_chunked(r, k, v, lw, u):
    """Chunked WKV6: out_t = r_t · (S_{t-1} + u ⊙ k_t ⊗ v_t);
    S_t = diag(w_t) S_{t-1} + k_t ⊗ v_t.   All args f32.

    r,k,v,lw: (B,S,h,hd);  u: (h,hd).  Returns (out (B,S,h,hd), S (B,h,hd,hd)).
    Intra-chunk decays use an explicit masked (Q,Q) tensor per channel —
    numerically safe for any clamped lw (DESIGN.md §5).
    """
    B, S, h, hd = r.shape
    Q = min(RWKV_CHUNK, max(4, S))
    r, S0 = _pad_to(r, Q, 1)
    k, _ = _pad_to(k, Q, 1)
    v, _ = _pad_to(v, Q, 1)
    lw, _ = _pad_to(lw, Q, 1)
    Sp = r.shape[1]
    nc = Sp // Q
    rc = r.reshape(B, nc, Q, h, hd)
    kc = k.reshape(B, nc, Q, h, hd)
    vc = v.reshape(B, nc, Q, h, hd)
    lwc = lw.reshape(B, nc, Q, h, hd)

    seg = jnp.cumsum(lwc, axis=2)  # inclusive within chunk
    segx = seg - lwc  # exclusive
    # intra-chunk: out[t] += sum_{i<t} (r_t ⊙ exp(segx_t - seg_i)) · k_i) v_i
    # (exponents clamped to <= 0: masked entries would otherwise be inf and
    # poison the VJP of the mask's where)
    decay = jnp.exp(jnp.minimum(
        segx[:, :, :, None] - seg[:, :, None, :, :], 0.0))  # (B,nc,Q,Q,h,hd)
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=-1)
    decay = jnp.where(mask[None, None, :, :, None, None], decay, 0.0)
    Amat = jnp.einsum("bcthd,bcihd,bctihd->bcthi", rc, kc, decay)
    y_intra = jnp.einsum("bcthi,bcihd->bcthd", Amat, vc)
    # current-token bonus:  (r_t · (u ⊙ k_t)) v_t
    bonus = jnp.einsum("bcthd,hd,bcthd->bcth", rc, u, kc)
    y_intra = y_intra + bonus[..., None] * vc
    # inter-chunk: out[t] += (r_t ⊙ exp(segx_t)) · S_chunk_start
    decay_to_end = jnp.exp(seg[:, :, -1:] - seg)  # (B,nc,Q,h,hd)
    S_local = jnp.einsum("bcihd,bcihe->bchde", kc * decay_to_end, vc)
    A_chunk = jnp.exp(seg[:, :, -1])  # (B,nc,h,hd)

    def combine(e1, e2):
        a1, s1 = e1
        a2, s2 = e2
        return a1 * a2, a2[..., None] * s1 + s2

    A_sc, S_sc = jax.lax.associative_scan(combine, (A_chunk, S_local), axis=1)
    S_start = jnp.concatenate(
        [jnp.zeros_like(S_sc[:, :1]), S_sc[:, :-1]], axis=1)
    y_inter = jnp.einsum("bcthd,bchde->bcthe", rc * jnp.exp(segx), S_start)

    out = (y_intra + y_inter).reshape(B, Sp, h, hd)[:, :S0]
    return out, S_sc[:, -1]


def apply_rwkv_tm_decode(params, cfg: ModelConfig, sh: ShardingCtx, x, state):
    """Single-token RWKV6 time-mix.  x (B,1,d)."""
    B, _, d = x.shape
    h, hd = cfg.ssm_heads, cfg.ssm_head_dim
    sx = state["shift"].astype(x.dtype)[:, None]
    xw, xk, xv, xr, xg = _rwkv_mix(params, x, sx)
    r = (xr @ params["wr"].astype(x.dtype)).reshape(B, h, hd).astype(jnp.float32)
    k = (xk @ params["wk"].astype(x.dtype)).reshape(B, h, hd).astype(jnp.float32)
    v = (xv @ params["wv"].astype(x.dtype)).reshape(B, h, hd).astype(jnp.float32)
    g = jax.nn.silu((xg @ params["wg"].astype(x.dtype)).astype(jnp.float32))
    lw = _rwkv_decay(params, xw).reshape(B, h, hd)

    S = state["wkv"]  # (B,h,hd,hd)
    kv = jnp.einsum("bhd,bhe->bhde", k, v)
    out = jnp.einsum("bhd,bhde->bhe", r, S + params["u"][None, ..., None] * kv)
    new_S = jnp.exp(lw)[..., None] * S + kv
    y = out.reshape(B, 1, d)
    y = rms_norm_simple(
        y.reshape(B, 1, h, hd), jnp.ones((hd,), jnp.float32), cfg.norm_eps
    ).reshape(B, 1, d).astype(jnp.float32)
    y = (y * params["out_norm"] * g).astype(x.dtype)
    out = y @ params["wo"].astype(x.dtype)
    return out, {"wkv": new_S, "shift": x[:, 0].astype(jnp.float32)}


def init_rwkv_cm(key, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    dt = _dt(cfg)
    pb = ParamBuilder(key)
    pb.zeros("mu_k", (d,), ("embed_nosplit",), jnp.float32)
    pb.zeros("mu_r", (d,), ("embed_nosplit",), jnp.float32)
    pb.dense("wk", (d, f), ("embed_fsdp", "mlp"), dt)
    pb.dense("wv", (f, d), ("mlp", "embed_fsdp"), dt)
    pb.dense("wr", (d, d), ("embed_fsdp", "embed_nosplit"), dt)
    return pb.build()


def apply_rwkv_cm(params, cfg: ModelConfig, sh: ShardingCtx, x, shift_state=None):
    """RWKV6 channel-mix.  Full-seq if shift_state is None (returns state)."""
    if shift_state is None:
        sx = jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
        new_state = x[:, -1].astype(jnp.float32)
    else:
        sx = shift_state.astype(x.dtype)[:, None]
        new_state = x[:, 0].astype(jnp.float32)
    dx = sx - x
    xk = x + dx * params["mu_k"].astype(x.dtype)
    xr = x + dx * params["mu_r"].astype(x.dtype)
    kk = jnp.square(jax.nn.relu(xk @ params["wk"].astype(x.dtype)))
    kk = sh.act(kk, "batch", "seq", "mlp_act")
    kv = kk @ params["wv"].astype(x.dtype)
    r = jax.nn.sigmoid((xr @ params["wr"].astype(x.dtype)).astype(jnp.float32))
    return (r * kv.astype(jnp.float32)).astype(x.dtype), new_state

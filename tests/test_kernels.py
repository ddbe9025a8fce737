"""Pallas kernel sweeps: shapes x dtypes x masking features vs pure oracles
(interpret mode).

For each kernel, sweep shapes/dtypes and assert_allclose against the
ref.py pure-jnp oracle — including every kernel<->oracle semantic gap the
pooled serving call sites exercise: per-row ``pos`` at mixed positions,
sliding-window + ALiBi masking, cross-attention ``kv_len``, chunked-prefill
``q_start``, MLA faithful scale, and carried recurrent state in/out.
Degenerate-grid regressions (T < block_kv; T % block_kv == 1 at
pos == T-1; fully-masked KV blocks under a small window) are pinned
explicitly, as are the ``*_unsupported`` dispatch guards.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (attention_ref, decode_attention,
                           decode_attention_ref,
                           decode_attention_unsupported, flash_attention,
                           flash_attention_unsupported, ssd, ssd_ref, wkv6,
                           wkv6_ref)

TOLS = {jnp.float32: 5e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOLS[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]


def _gqa_flat(q, k, v):
    B, Sq, H, Dk = q.shape
    Kv, Dv = k.shape[2], v.shape[-1]
    Skv = k.shape[1]
    return (q.transpose(0, 2, 1, 3).reshape(B * H, Sq, Dk),
            k.transpose(0, 2, 1, 3).reshape(B * Kv, Skv, Dk),
            v.transpose(0, 2, 1, 3).reshape(B * Kv, Skv, Dv))


# ---------------------------------------------------------------------------
# flash attention (prefill)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,Kv,D,window", [
    (1, 64, 2, 2, 16, None),
    (2, 100, 4, 2, 16, None),  # GQA + ragged blocks
    (1, 96, 4, 1, 32, None),  # MQA
    (2, 80, 2, 2, 16, 24),  # sliding window
])
def test_flash_attention_sweep(dtype, B, S, H, Kv, D, window):
    rng = np.random.RandomState(hash((B, S, H)) % 1000)
    q = jnp.asarray(rng.randn(B, S, H, D), dtype) * 0.3
    k = jnp.asarray(rng.randn(B, S, Kv, D), dtype) * 0.3
    v = jnp.asarray(rng.randn(B, S, Kv, D), dtype) * 0.3
    out = flash_attention(q, k, v, causal=True, window=window, block_q=32,
                          block_kv=32, interpret=True)
    qf, kf, vf = _gqa_flat(q, k, v)
    ref = attention_ref(qf, kf, vf, causal=True, window=window)
    ref = ref.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_flash_attention_small_window_fully_masked_blocks():
    """A kv block entirely outside the window must contribute exact zeros:
    NEG_INF is finite, so an unguarded exp(s - m) of an all-masked block
    would be 1 and corrupt the softmax denominator."""
    rng = np.random.RandomState(0)
    B, S, H, D = 1, 96, 2, 16
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.3
    # window 4 << block_kv 16: for late queries, several mid blocks run
    # (below the causal diagonal) but are entirely window-masked
    out = flash_attention(q, k, v, causal=True, window=4, block_q=16,
                          block_kv=16, interpret=True)
    qf, kf, vf = _gqa_flat(q, k, v)
    ref = attention_ref(qf, kf, vf, causal=True, window=4)
    ref = ref.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5,
                               rtol=5e-5)


def test_flash_attention_q_start_chunked_prefill():
    """Chunked prefill: the suffix chunk's queries over the full key range
    must equal the corresponding rows of the one-shot computation."""
    rng = np.random.RandomState(1)
    B, S, H, Kv, D = 2, 48, 4, 2, 16
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(B, S, Kv, D), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(B, S, Kv, D), jnp.float32) * 0.3
    full = flash_attention(q, k, v, causal=True, block_q=16, block_kv=16,
                           interpret=True)
    off = 32
    chunk = flash_attention(q[:, off:], k, v, causal=True, q_start=off,
                            block_q=16, block_kv=16, interpret=True)
    np.testing.assert_allclose(np.asarray(chunk), np.asarray(full[:, off:]),
                               atol=5e-5, rtol=5e-5)
    # and against the oracle with the same offset
    qf = q[:, off:].transpose(0, 2, 1, 3).reshape(B * H, S - off, D)
    _, kf, vf = _gqa_flat(q, k, v)
    ref = attention_ref(qf, kf, vf, causal=True, q_start=off)
    ref = ref.reshape(B, H, S - off, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(chunk), np.asarray(ref), atol=5e-5,
                               rtol=5e-5)


def test_flash_attention_alibi_slopes():
    from repro.models.layers import alibi_slopes

    rng = np.random.RandomState(2)
    B, S, H, D = 2, 40, 4, 16
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.3
    slopes = alibi_slopes(H)
    out = flash_attention(q, k, v, causal=True, slopes=slopes, block_q=16,
                          block_kv=16, interpret=True)
    qf, kf, vf = _gqa_flat(q, k, v)
    sl = np.broadcast_to(np.asarray(slopes)[None], (B, H)).reshape(B * H)
    ref = attention_ref(qf, kf, vf, causal=True, slopes=sl)
    ref = ref.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5,
                               rtol=5e-5)


def test_flash_attention_non_causal_cross_shapes():
    """Cross-attention shape regime: Sq != Skv and Dv != Dk, non-causal."""
    rng = np.random.RandomState(3)
    B, Sq, Skv, H, Kv, Dk, Dv = 2, 7, 19, 4, 2, 16, 8
    q = jnp.asarray(rng.randn(B, Sq, H, Dk), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(B, Skv, Kv, Dk), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(B, Skv, Kv, Dv), jnp.float32) * 0.3
    out = flash_attention(q, k, v, causal=False, block_q=4, block_kv=8,
                          interpret=True)
    qf, kf, vf = _gqa_flat(q, k, v)
    ref = attention_ref(qf, kf, vf, causal=False)
    ref = ref.reshape(B, H, Sq, Dv).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5,
                               rtol=5e-5)


def test_flash_attention_dynamic_traced_window():
    """gemma3's local:global pattern makes the window a traced per-layer
    scalar inside the scanned pooled step — the kernel takes it as a
    dynamic input, so one trace serves both local and global layers."""
    rng = np.random.RandomState(4)
    B, S, H, D = 1, 32, 2, 16
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.3

    @jax.jit
    def scan_windows(q, k, v, wins):
        def body(_, w):
            return None, flash_attention(q, k, v, causal=True, window=w,
                                         block_q=8, block_kv=8,
                                         interpret=True)
        return jax.lax.scan(body, None, wins)[1]

    outs = scan_windows(q, k, v, jnp.asarray([5, 1 << 30]))
    qf, kf, vf = _gqa_flat(q, k, v)
    for i, w in enumerate((5, None)):
        ref = attention_ref(qf, kf, vf, causal=True, window=w)
        ref = ref.reshape(B, H, S, D).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(outs[i]), np.asarray(ref),
                                   atol=5e-5, rtol=5e-5)


def test_flash_attention_guard_raises():
    assert flash_attention_unsupported() is None
    assert flash_attention_unsupported(causal=False) is None
    assert "window" in flash_attention_unsupported(causal=False, window=8)
    assert "q_start" in flash_attention_unsupported(causal=False, q_start=4)
    # non-causal ALiBi would bias from arange(Sq), not the caller's true
    # query positions — must fall back to XLA, not silently diverge
    assert "ALiBi" in flash_attention_unsupported(causal=False,
                                                  slopes=jnp.ones((2,)))
    assert flash_attention_unsupported(slopes=jnp.ones((2,))) is None
    q = jnp.zeros((1, 4, 2, 8))
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q[:, :, :2], q[:, :, :2], causal=False, window=8,
                        interpret=True)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


def _decode_flat(q, ck, cv):
    B, _, H, Dk = q.shape
    T, Kv = ck.shape[1], ck.shape[2]
    G = H // Kv
    return (q.reshape(B, Kv, G, Dk).reshape(B * Kv, G, Dk),
            ck.transpose(0, 2, 1, 3).reshape(B * Kv, T, Dk),
            cv.transpose(0, 2, 1, 3).reshape(B * Kv, T, cv.shape[-1]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Kv,Dk,Dv,T,pos", [
    (2, 4, 2, 16, 16, 128, 100),
    (1, 8, 1, 24, 16, 200, 63),  # MLA-like: MQA with asymmetric K/V dims
    (2, 2, 2, 32, 32, 96, 95),
])
def test_decode_attention_sweep(dtype, B, H, Kv, Dk, Dv, T, pos):
    rng = np.random.RandomState(hash((B, H, T)) % 1000)
    q = jnp.asarray(rng.randn(B, 1, H, Dk), dtype) * 0.3
    ck = jnp.asarray(rng.randn(B, T, Kv, Dk), dtype) * 0.3
    cv = jnp.asarray(rng.randn(B, T, Kv, Dv), dtype) * 0.3
    out = decode_attention(q, ck, cv, pos, block_kv=64, interpret=True)
    G = H // Kv
    qf, kf, vf = _decode_flat(q, ck, cv)
    ref = decode_attention_ref(qf, kf, vf, pos)
    ref = ref.reshape(B, Kv, G, Dv).reshape(B, 1, H, Dv)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_decode_attention_per_row_pos():
    """Pooled cache rows decode at DIFFERENT positions — the scalar-pos
    kernel of old would mask every row at the same length."""
    rng = np.random.RandomState(5)
    B, H, Kv, D, T = 4, 4, 2, 16, 96
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32) * 0.3
    ck = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    cv = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    pos = jnp.asarray([3, 40, 77, 95])
    out = decode_attention(q, ck, cv, pos, block_kv=32, interpret=True)
    qf, kf, vf = _decode_flat(q, ck, cv)
    ref = decode_attention_ref(qf, kf, vf, jnp.repeat(pos, Kv))
    ref = ref.reshape(B, Kv, H // Kv, D).reshape(B, 1, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5,
                               rtol=5e-5)
    # per-row results equal the scalar-pos call row by row
    for i in range(B):
        solo = decode_attention(q[i: i + 1], ck[i: i + 1], cv[i: i + 1],
                                int(pos[i]), block_kv=32, interpret=True)
        np.testing.assert_array_equal(np.asarray(solo[0]), np.asarray(out[i]))


@pytest.mark.parametrize("window", [4, 24])
def test_decode_attention_sliding_window(window):
    """Sliding-window decode incl. blocks fully outside the window (the
    NEG_INF exp(0)=1 regression: unguarded, a fully window-masked block
    adds block_kv to the denominator)."""
    rng = np.random.RandomState(6)
    B, H, Kv, D, T = 2, 4, 2, 16, 96
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32) * 0.3
    ck = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    cv = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    pos = jnp.asarray([90, 50])
    out = decode_attention(q, ck, cv, pos, window=window, block_kv=16,
                           interpret=True)
    qf, kf, vf = _decode_flat(q, ck, cv)
    ref = decode_attention_ref(qf, kf, vf, jnp.repeat(pos, Kv),
                               window=window)
    ref = ref.reshape(B, Kv, H // Kv, D).reshape(B, 1, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5,
                               rtol=5e-5)


def test_decode_attention_alibi_slopes():
    from repro.models.layers import alibi_slopes

    rng = np.random.RandomState(7)
    B, H, Kv, D, T = 2, 4, 2, 16, 64
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32) * 0.3
    ck = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    cv = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    pos = jnp.asarray([63, 10])
    slopes = alibi_slopes(H)
    out = decode_attention(q, ck, cv, pos, slopes=slopes, block_kv=16,
                           interpret=True)
    qf, kf, vf = _decode_flat(q, ck, cv)
    G = H // Kv
    sl = np.broadcast_to(np.asarray(slopes).reshape(Kv, G)[None],
                         (B, Kv, G)).reshape(B * Kv, G)
    ref = decode_attention_ref(qf, kf, vf, jnp.repeat(pos, Kv), slopes=sl)
    ref = ref.reshape(B, Kv, G, D).reshape(B, 1, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5,
                               rtol=5e-5)


def test_decode_attention_cross_kv_len():
    """Enc-dec cross decode: non-causal over an over-allocated cache, per-
    row kv_len masks the invalid tail."""
    rng = np.random.RandomState(8)
    B, H, Kv, D, T = 3, 4, 2, 16, 40
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32) * 0.3
    ck = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    cv = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    kv_len = jnp.asarray([5, 17, 40])
    out = decode_attention(q, ck, cv, 0, causal=False, kv_len=kv_len,
                           block_kv=16, interpret=True)
    qf, kf, vf = _decode_flat(q, ck, cv)
    ref = decode_attention_ref(qf, kf, vf, jnp.zeros((B * Kv,), jnp.int32),
                               causal=False, kv_len=jnp.repeat(kv_len, Kv))
    ref = ref.reshape(B, Kv, H // Kv, D).reshape(B, 1, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5,
                               rtol=5e-5)


def test_decode_attention_mla_faithful_scale():
    """MLA absorbed decode scales by 1/sqrt(nope+rope), not the
    1/sqrt(lora+rope) that q_eff's width implies — the kernel takes the
    faithful scale directly where the XLA helper needs a q pre-scale."""
    rng = np.random.RandomState(9)
    B, H, lora, rope, nope, T = 2, 4, 24, 8, 16, 48
    q = jnp.asarray(rng.randn(B, 1, H, lora + rope), jnp.float32) * 0.3
    ck = jnp.asarray(rng.randn(B, T, 1, lora + rope), jnp.float32) * 0.3
    cv = jnp.asarray(rng.randn(B, T, 1, lora), jnp.float32) * 0.3
    scale = 1.0 / np.sqrt(nope + rope)
    out = decode_attention(q, ck, cv, T - 1, scale=scale, block_kv=16,
                           interpret=True)
    qf, kf, vf = _decode_flat(q, ck, cv)
    ref = decode_attention_ref(qf, kf, vf, T - 1, scale=scale)
    ref = ref.reshape(B, 1, H, lora)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5,
                               rtol=5e-5)


@pytest.mark.parametrize("T,block_kv", [
    (5, 64),  # T < block_kv: degenerate single-block grid
    (65, 16),  # T % block_kv == 1: one-position trailing block
    (33, 32),
])
def test_decode_attention_padding_regressions(T, block_kv):
    """pos == T-1 with ragged cache padding: the zero-padded tail must
    never leak into the softmax."""
    rng = np.random.RandomState(T)
    B, H, Kv, D = 2, 4, 2, 16
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32) * 0.3
    ck = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    cv = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    out = decode_attention(q, ck, cv, T - 1, block_kv=block_kv,
                           interpret=True)
    qf, kf, vf = _decode_flat(q, ck, cv)
    ref = decode_attention_ref(qf, kf, vf, T - 1)
    ref = ref.reshape(B, Kv, H // Kv, D).reshape(B, 1, H, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5,
                               rtol=5e-5)


def test_decode_attention_guard_raises():
    assert decode_attention_unsupported() is None
    assert decode_attention_unsupported(causal=False, kv_len=4) is None
    assert "window" in decode_attention_unsupported(causal=False, window=8)
    q = jnp.zeros((1, 1, 2, 8))
    c = jnp.zeros((1, 4, 2, 8))
    with pytest.raises(ValueError, match="window"):
        decode_attention(q, c, c, 0, causal=False, window=8, interpret=True)


# ---------------------------------------------------------------------------
# WKV6
# ---------------------------------------------------------------------------


def _wkv_flat(x):
    B, S, H, hd = x.shape
    return np.asarray(x.astype(jnp.float32)).transpose(0, 2, 1, 3).reshape(
        B * H, S, hd)


def _wkv_inputs(rng, B, S, H, hd, dtype=jnp.float32):
    r = jnp.asarray(rng.randn(B, S, H, hd), dtype) * 0.4
    k = jnp.asarray(rng.randn(B, S, H, hd), dtype) * 0.4
    v = jnp.asarray(rng.randn(B, S, H, hd), dtype) * 0.4
    lw = jnp.clip(jnp.asarray(-np.exp(rng.randn(B, S, H, hd) * 0.5 - 1),
                              dtype), -5.0, -1e-4)
    u = jnp.asarray(rng.randn(H, hd), dtype) * 0.3
    return r, k, v, lw, u


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (2, 37, 3, 8, 8),
    (1, 64, 2, 16, 16),
    (2, 20, 1, 8, 16),  # chunk > padded seq handled
])
def test_wkv6_sweep(B, S, H, hd, chunk):
    rng = np.random.RandomState(hash((B, S, H)) % 1000)
    r, k, v, lw, u = _wkv_inputs(rng, B, S, H, hd)
    out, state = wkv6(r, k, v, lw, u, chunk=chunk, interpret=True)
    uf = np.broadcast_to(np.asarray(u, np.float32)[None],
                         (B, H, hd)).reshape(B * H, hd)
    ref, ref_state = wkv6_ref(_wkv_flat(r), _wkv_flat(k), _wkv_flat(v),
                              _wkv_flat(lw), uf)
    ref = np.asarray(ref).reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(state).reshape(B * H, hd, hd), np.asarray(ref_state),
        atol=1e-4, rtol=1e-3)


def test_wkv6_carried_state_resume():
    """Splitting a sequence and carrying the state across the split must
    reproduce the one-shot run — the contract that lets the kernel serve
    the pooled recurrent state (and chunked resume)."""
    rng = np.random.RandomState(10)
    B, S, H, hd, cut = 2, 26, 2, 8, 11  # ragged halves (pad exercised)
    r, k, v, lw, u = _wkv_inputs(rng, B, S, H, hd)
    out_full, s_full = wkv6(r, k, v, lw, u, chunk=8, interpret=True)
    o1, s1 = wkv6(r[:, :cut], k[:, :cut], v[:, :cut], lw[:, :cut], u,
                  chunk=8, interpret=True)
    o2, s2 = wkv6(r[:, cut:], k[:, cut:], v[:, cut:], lw[:, cut:], u, s1,
                  chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                               np.asarray(out_full), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# SSD (Mamba2)
# ---------------------------------------------------------------------------


def _ssd_inputs(rng, B, S, H, p, n, dtype=jnp.float32):
    x = jnp.asarray(rng.randn(B, S, H, p), dtype) * 0.4
    Bm = jnp.asarray(rng.randn(B, S, n), dtype) * 0.4
    Cm = jnp.asarray(rng.randn(B, S, n), dtype) * 0.4
    dt = jnp.asarray(np.abs(rng.randn(B, S, H)) * 0.5 + 0.1, dtype)
    A = jnp.asarray(-np.abs(rng.randn(H)) - 0.2, dtype)
    D = jnp.asarray(rng.randn(H), dtype)
    return x, Bm, Cm, dt, A, D


def _ssd_ref_args(x, Bm, Cm, dt, A, D):
    B, S, H, p = x.shape
    xf = np.asarray(x, np.float32).transpose(0, 2, 1, 3).reshape(B * H, S, p)
    dtf = np.asarray(dt, np.float32).transpose(0, 2, 1).reshape(B * H, S)
    Af = np.broadcast_to(np.asarray(A, np.float32)[None], (B, H)).reshape(-1)
    Df = np.broadcast_to(np.asarray(D, np.float32)[None], (B, H)).reshape(-1)
    return xf, np.asarray(Bm, np.float32), np.asarray(Cm, np.float32), \
        dtf, Af, Df


@pytest.mark.parametrize("B,S,H,p,n,chunk", [
    (2, 45, 3, 8, 4, 16),
    (1, 64, 2, 16, 8, 32),
    (1, 10, 1, 8, 4, 16),
])
def test_ssd_sweep(B, S, H, p, n, chunk):
    rng = np.random.RandomState(hash((B, S, p)) % 1000)
    x, Bm, Cm, dt, A, D = _ssd_inputs(rng, B, S, H, p, n)
    out, state = ssd(x, Bm, Cm, dt, A, D, chunk=chunk, interpret=True)
    ref, ref_state = ssd_ref(*_ssd_ref_args(x, Bm, Cm, dt, A, D))
    ref = np.asarray(ref).reshape(B, H, S, p).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(state).reshape(B * H, p, n), np.asarray(ref_state),
        atol=1e-4, rtol=1e-3)


def test_ssd_carried_state_resume():
    rng = np.random.RandomState(11)
    B, S, H, p, n, cut = 2, 30, 2, 8, 4, 13
    x, Bm, Cm, dt, A, D = _ssd_inputs(rng, B, S, H, p, n)
    out_full, s_full = ssd(x, Bm, Cm, dt, A, D, chunk=8, interpret=True)
    o1, s1 = ssd(x[:, :cut], Bm[:, :cut], Cm[:, :cut], dt[:, :cut], A, D,
                 chunk=8, interpret=True)
    o2, s2 = ssd(x[:, cut:], Bm[:, cut:], Cm[:, cut:], dt[:, cut:], A, D,
                 s1, chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                               np.asarray(out_full), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=1e-4, rtol=1e-3)


def test_ssd_kernel_matches_model_mamba():
    """The kernel and repro.models.ssm.apply_mamba_full agree through the
    full block math (same chunked formulation, different substrate)."""
    import jax

    from repro.configs import get_reduced_config
    from repro.models import NULL_SH
    from repro.models.ssm import apply_mamba_full, init_mamba

    cfg = get_reduced_config("zamba2_7b")
    params, _ = init_mamba(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 24, cfg.d_model), jnp.float32) * 0.2
    y_xla, st_xla = apply_mamba_full(params, cfg, NULL_SH, x)
    y_pl, st_pl = apply_mamba_full(params, cfg, NULL_SH, x,
                                   backend="pallas")
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_xla),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st_pl["ssm"]),
                               np.asarray(st_xla["ssm"]), atol=1e-4,
                               rtol=1e-3)
    np.testing.assert_array_equal(np.asarray(st_pl["conv"]),
                                  np.asarray(st_xla["conv"]))


def test_rwkv_tm_backends_agree():
    """apply_rwkv_tm_full routes the recurrence through the wkv6 kernel on
    the pallas backend; outputs and carried state match the jnp path."""
    import jax

    from repro.configs import get_reduced_config
    from repro.models import NULL_SH
    from repro.models.ssm import apply_rwkv_tm_full, init_rwkv_tm

    cfg = get_reduced_config("rwkv6_7b")
    params = init_rwkv_tm(jax.random.PRNGKey(0), cfg)[0]
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 19, cfg.d_model), jnp.float32) * 0.2
    y_xla, st_xla = apply_rwkv_tm_full(params, cfg, NULL_SH, x)
    y_pl, st_pl = apply_rwkv_tm_full(params, cfg, NULL_SH, x,
                                     backend="pallas")
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_xla),
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st_pl["wkv"]),
                               np.asarray(st_xla["wkv"]), atol=1e-4,
                               rtol=1e-3)


# ---------------------------------------------------------------------------
# Runtime knobs
# ---------------------------------------------------------------------------


def test_runtime_interpret_env_override(monkeypatch):
    """Interpret mode follows the backend alone: no environment variable
    can force the interpreter on a TPU."""
    from repro.kernels import runtime
    from repro.kernels.runtime import default_interpret, resolve_backend

    assert default_interpret() is (jax.default_backend() != "tpu")
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(runtime.jax, "default_backend", lambda: "tpu")
    assert default_interpret() is False
    monkeypatch.setattr(runtime.jax, "default_backend", lambda: "cpu")
    assert default_interpret() is True
    assert resolve_backend("xla") == "xla"
    assert resolve_backend("pallas") == "pallas"
    with pytest.raises(ValueError, match="pallas"):
        resolve_backend("cuda")


def test_pallas_fallbacks_are_counted(monkeypatch):
    """A ``backend="pallas"`` call that a kernel's predicate sends to XLA is
    counted by kernel and reason; XLA-backend calls are never counted."""
    import collections

    from repro.kernels import runtime
    from repro.models.attention import _use_pallas_decode, _use_pallas_flash

    monkeypatch.setattr(runtime, "XLA_FALLBACKS", collections.Counter())
    assert _use_pallas_flash("pallas") is True
    assert _use_pallas_flash("xla", causal=False, window=8) is False
    assert not runtime.XLA_FALLBACKS
    assert _use_pallas_flash("pallas", causal=False, window=8) is False
    assert _use_pallas_decode("pallas", causal=False, window=8) is False
    assert dict(runtime.XLA_FALLBACKS) == {
        "flash_attention: sliding-window masking on non-causal attention": 1,
        "decode_attention: sliding-window masking on non-causal (cross) "
        "decode attention": 1}

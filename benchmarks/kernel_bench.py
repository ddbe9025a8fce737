"""Kernel parity + analytic-intensity report.

Interpret-mode wall times on CPU are meaningless for TPU perf, so this
suite reports correctness (max err vs oracle) + arithmetic intensity
(FLOPs/byte) per kernel shape — the quantity that situates each kernel on
the TPU roofline (197 TFLOP/s / 819 GB/s ⇒ ridge at ~240 FLOPs/byte).

``throughput_scenarios`` additionally measures the pallas-vs-xla wall-time
ratio per serving hot path (decode attention, flash prefill) — the hook
``benchmarks/engine_validation.py --smoke`` records into
``BENCH_engine.json``: ~1x-and-meaningless in interpret mode on CPU, the
real signal on TPU runs where the kernels compile through Mosaic."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit, timed


def _best_of(fn, n: int = 3) -> float:
    """Min wall-seconds of ``n`` steady-state calls.

    Benchmark hygiene: ``fn`` must return a jax array; the FIRST call —
    trace + compile — is discarded, and every call is drained with
    ``block_until_ready`` so async dispatch cannot leak a call's work into
    the next measurement window."""
    fn().block_until_ready()  # discarded: trace + compile + first run
    best = float("inf")
    for _ in range(n):
        t0 = time.perf_counter()
        fn().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def throughput_scenarios(full: bool = False):
    """{scenario: metrics} for the pallas-vs-xla serving hot paths.

    ``kernels.decode.tput`` — pooled decode attention (rows of one cache
    pool, per-row positions); ``kernels.flash.tput`` — bucketed prefill
    attention.  Each row records tokens/s per backend plus their ratio.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import decode_attention, flash_attention
    from repro.models.attention import attention_core, decode_attention_xla

    # hygiene: jit BOTH sides so the steady-state window never re-traces —
    # the un-jitted pallas wrappers used to pay per-call tracing, skewing
    # the pallas-vs-xla ratio toward trace overhead instead of kernel time
    decode_pl = jax.jit(lambda q, ck, cv, pos: decode_attention(q, ck, cv,
                                                                pos))
    flash_pl = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    decode_xla = jax.jit(decode_attention_xla)
    core_xla = jax.jit(attention_core)
    rng = np.random.RandomState(0)
    out = {}

    # decode: B pooled rows at mixed positions over a long cache
    B, H, Kv, D = 8, 8, 2, 64
    T = 2048 if full else 512
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32) * 0.3
    ck = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    cv = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    pos = jnp.asarray(rng.randint(T // 2, T, size=B), jnp.int32)
    t_pl = _best_of(lambda: decode_pl(q, ck, cv, pos))
    # the XLA oracle takes a scalar pos; give it the max (same work shape)
    t_xla = _best_of(lambda: decode_xla(q, ck, cv, T - 1))
    out["kernels.decode.tput"] = {
        "pallas_tok_s": B / t_pl, "xla_tok_s": B / t_xla,
        "pallas_over_xla": t_xla / t_pl}

    # flash prefill: one bucket group's worth of rows
    B, S = 4, (1024 if full else 256)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.3
    k = jnp.asarray(rng.randn(B, S, Kv, D), jnp.float32) * 0.3
    v = jnp.asarray(rng.randn(B, S, Kv, D), jnp.float32) * 0.3
    t_pl = _best_of(lambda: flash_pl(q, k, v))
    G = H // Kv
    kx, vx = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    positions = jnp.arange(S)
    t_xla = _best_of(lambda: core_xla(q, kx, vx, positions, positions))
    out["kernels.flash.tput"] = {
        "pallas_tok_s": B * S / t_pl, "xla_tok_s": B * S / t_xla,
        "pallas_over_xla": t_xla / t_pl}
    return out


def run(full: bool = False):
    import jax.numpy as jnp

    from repro.kernels import (attention_ref, decode_attention,
                               decode_attention_ref, flash_attention)

    rng = np.random.RandomState(0)
    shapes = [(1, 256, 4, 2, 64)] + ([(2, 512, 8, 2, 64)] if full else [])
    for B, S, H, Kv, D in shapes:
        q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32) * 0.3
        k = jnp.asarray(rng.randn(B, S, Kv, D), jnp.float32) * 0.3
        v = jnp.asarray(rng.randn(B, S, Kv, D), jnp.float32) * 0.3
        flash_run = lambda: flash_attention(
            q, k, v, causal=True, block_q=64, block_kv=64
        ).block_until_ready()
        flash_run()  # warm-up: the timed call measures steady state
        out, us = timed(flash_run)
        qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
        kf = k.transpose(0, 2, 1, 3).reshape(B * Kv, S, D)
        vf = v.transpose(0, 2, 1, 3).reshape(B * Kv, S, D)
        ref = attention_ref(qf, kf, vf).reshape(B, H, S, D).transpose(0, 2, 1, 3)
        err = float(jnp.max(jnp.abs(out - ref)))
        flops = 2 * B * H * S * S * D * 2 / 2  # causal
        bytes_ = (B * S * (H + 2 * Kv) * D * 2 + B * S * H * D * 2)
        emit(f"kernel.flash.B{B}S{S}H{H}", us,
             f"max_err={err:.2e} intensity={flops/bytes_:.0f}flops/B")

    T = 4096 if full else 1024
    B, H, Kv, D = 2, 8, 2, 64
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32) * 0.3
    ck = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    cv = jnp.asarray(rng.randn(B, T, Kv, D), jnp.float32) * 0.3
    decode_run = lambda: decode_attention(
        q, ck, cv, T - 1, block_kv=256).block_until_ready()
    decode_run()  # warm-up: the timed call measures steady state
    out, us = timed(decode_run)
    G = H // Kv
    ref = decode_attention_ref(
        q.reshape(B, Kv, G, D).reshape(B * Kv, G, D),
        ck.transpose(0, 2, 1, 3).reshape(B * Kv, T, D),
        cv.transpose(0, 2, 1, 3).reshape(B * Kv, T, D), T - 1)
    err = float(jnp.max(jnp.abs(out.reshape(B * Kv, G, D) - ref)))
    flops = 2 * B * H * T * D * 2
    bytes_ = B * T * Kv * D * 2 * 2  # cache read dominates (bf16 on TPU)
    emit(f"kernel.decode.T{T}", us,
         f"max_err={err:.2e} intensity={flops/bytes_:.1f}flops/B "
         f"(memory-bound: cache-read limited)")


if __name__ == "__main__":
    run()

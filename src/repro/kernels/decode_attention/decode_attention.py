"""Pallas TPU decode attention: one query token over a long KV cache.

Grid = (B*Kv, kv_blocks); the per-(batch, kv-head) query group (G = H/Kv
rows) stays resident in VMEM while KV blocks stream through — the memory-
bound regime the Pallas kernel exists for (reads the cache exactly once at
bf16, vs the XLA path's f32 upcasts).  Handles GQA groups natively and MLA
absorbed decode as the Kv=1 special case with asymmetric K/V widths and a
caller-supplied faithful softmax scale.

The masking semantics mirror ``models.attention.decode_attention_xla``
exactly — the contract the pooled serving steps dispatch on:

* ``pos`` is PER ROW (shape ``(1, BKv)`` in SMEM, indexed by
  ``program_id(0)``): pooled cache rows decode at different positions.
* causal + sliding-window: valid iff ``0 <= pos - kv_pos < window``
  (``window`` is a dynamic scalar — gemma3's local:global pattern makes it
  a traced per-layer value inside the scanned pooled step).
* ``kv_len`` masks ``kv_pos >= kv_len`` per row — the enc-dec cross-
  attention case where the pooled cross-KV cache is allocated longer than
  the session's encoder output (``causal=False``).
* ALiBi: ``slopes (BKv, G)`` adds ``slope * -|pos - kv_pos|`` to the
  logits before masking (bloom).

KV blocks with no valid position still contribute exact zeros: masked
probabilities are zeroed explicitly (``NEG_INF`` is finite, so the naive
``exp(s - m)`` of an all-masked block would be ``exp(0) = 1`` and corrupt
the softmax denominator — the window/kv_len regression this file's tests
pin down).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import NO_WINDOW

NEG_INF = -1e30


def _kernel(pos_ref, kvl_ref, win_ref, *rest, block_kv, group, causal,
            has_slopes, scale):
    if has_slopes:
        slopes_ref, q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr = rest
    b = pl.program_id(0)
    ki = pl.program_id(1)
    n_kv = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    pos = pos_ref[0, b]
    kvl = kvl_ref[0, b]
    win = win_ref[0, 0]
    kv_start = ki * block_kv

    if causal:
        # skip blocks wholly past pos, wholly before the window, or wholly
        # past the valid cache prefix
        run = ((kv_start <= pos) & (kv_start + block_kv - 1 > pos - win)
               & (kv_start < kvl))
    else:
        run = kv_start < kvl

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (G, d_k)
        k = k_ref[0].astype(jnp.float32)  # (block_kv, d_k)
        v = v_ref[0].astype(jnp.float32)  # (block_kv, d_v)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (G, block_kv)
        kv_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_kv), 1)
        diff = pos - kv_pos
        if has_slopes:
            s = s + slopes_ref[0] * (-jnp.abs(diff).astype(jnp.float32))
        ok = kv_pos < kvl
        if causal:
            ok &= (diff >= 0) & (diff < win)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_new)
        # NEG_INF is finite: an all-masked block has m_new == NEG_INF and
        # exp(s - m_new) == 1 on masked entries — zero them explicitly
        p = jnp.where(ok, jnp.exp(s - m_new[:, None]), 0.0)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
        acc[...] = acc[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0] = (acc[...] / denom).astype(o_ref.dtype)


def decode_attention_bkv(q, k, v, pos, *, kv_len=None, window=None,
                         slopes=None, causal: bool = True,
                         scale: Optional[float] = None, block_kv: int = 256,
                         interpret: bool = False):
    """q (BKv, G, Dk); k (BKv, T, Dk); v (BKv, T, Dv).

    ``pos``: scalar or (BKv,) int32 — per-row current position.
    ``kv_len``: optional scalar or (BKv,) int32 valid-cache length.
    ``window``: optional scalar (python int or traced) sliding window.
    ``slopes``: optional (BKv, G) f32 ALiBi slopes.
    ``scale``: softmax scale; defaults to 1/sqrt(Dk) (MLA absorbed decode
    passes its faithful 1/sqrt(nope+rope) here).
    """
    BKv, G, Dk = q.shape
    T = k.shape[1]
    Dv = v.shape[-1]
    block_kv = min(block_kv, T)
    pad = (-T) % block_kv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    n_kv = k.shape[1] // block_kv
    kern = functools.partial(
        _kernel, block_kv=block_kv, group=G, causal=causal,
        has_slopes=slopes is not None,
        scale=float(scale) if scale is not None else 1.0 / np.sqrt(Dk))
    # SMEM scalars as (1, n) rows: under vmap (the pooled steps' per-row
    # idiom) each gains a leading batch dim, and its whole-array block then
    # still spans both trailing dims, as Mosaic requires
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(1, -1),
                               (1, BKv))
    kvl_arr = jnp.broadcast_to(
        jnp.asarray(T if kv_len is None else kv_len,
                    jnp.int32).reshape(1, -1), (1, BKv))
    win_arr = jnp.asarray(NO_WINDOW if window is None else window,
                          jnp.int32).reshape(1, 1)
    scalar_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] * 3
    inputs = [pos_arr, kvl_arr, win_arr]
    slope_specs = []
    if slopes is not None:
        # (BKv, G, 1): the block spans both trailing dims, as Mosaic requires
        slope_specs = [pl.BlockSpec((1, G, 1), lambda b, ki: (b, 0, 0))]
        inputs.append(jnp.asarray(slopes, jnp.float32)[:, :, None])
    out = pl.pallas_call(
        kern,
        grid=(BKv, n_kv),
        in_specs=scalar_specs + slope_specs + [
            pl.BlockSpec((1, G, Dk), lambda b, ki: (b, 0, 0)),
            pl.BlockSpec((1, block_kv, Dk), lambda b, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_kv, Dv), lambda b, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, Dv), lambda b, ki: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BKv, G, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, Dv), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs, q, k, v)
    return out

"""Pallas TPU kernel for the RWKV6 (Finch) recurrence, chunked.

    out_t = r_t · (S_{t-1} + u ⊙ k_t ⊗ v_t);   S_t = diag(w_t) S_{t-1} + k_t ⊗ v_t

Grid = (B*H, n_chunks) with the chunk axis innermost; the (hd, hd) f32 state
lives in VMEM scratch and carries across chunk steps (sequential TPU grid
execution).  Intra-chunk terms use the explicit masked decay tensor — the
numerically-safe formulation shared with the jnp path
(repro.models.ssm._wkv6_chunked, incl. the RWKV_MIN_LOG_W clamp).

State is carried IN and OUT: the scratch initialises from ``state_in``
(zeros for a fresh sequence) and the final carry is written to a second
output — what the recurrent serving pools store per session row, so the
kernel can serve pooled prefill (and chunked resume), not just full
sequences from scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sout_ref,
            state, *, chunk, hd):
    ci = pl.program_id(1)
    n_chunks = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)  # (Q, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)  # log decay, clamped <= 0
    u = u_ref[0].astype(jnp.float32)  # (1, hd)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # Mosaic has no cumsum: the inclusive prefix sum is a lower-triangular
    # matmul, at full f32 precision since the decays compound over it
    seg = jax.lax.dot_general(
        (row >= col).astype(jnp.float32), lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)  # inclusive (Q, hd)
    segx = seg - lw  # exclusive
    seg_end = seg[chunk - 1:chunk]  # (1, hd)
    # intra-chunk: A[t,i] = sum_c r[t,c] k[i,c] exp(segx[t,c]-seg[i,c]), i<t
    # exponents clamped <= 0 (masked upper-triangle entries would be inf)
    decay = jnp.exp(jnp.minimum(segx[:, None, :] - seg[None, :, :], 0.0))
    A = jnp.sum(r[:, None, :] * k[None, :, :] * decay, axis=2)
    A = jnp.where(row > col, A, 0.0)
    out = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    # bonus (current token): (r_t · (u ⊙ k_t)) v_t
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)  # (Q, 1)
    out = out + bonus * v
    # inter-chunk: r_t ⊙ exp(segx_t) against the carried state
    out = out + jax.lax.dot_general(
        r * jnp.exp(segx), state[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)
    # state update: S <- diag(prod w) S + sum_i (k_i ⊙ exp(seg_end - seg_i)) v_i^T
    # prod w as a column (hd, 1): the chunk's total log decay, lw^T · 1
    total = jax.lax.dot_general(
        lw, jnp.ones((chunk, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    decay_to_end = jnp.exp(seg_end - seg)  # (Q, hd)
    state[...] = (jnp.exp(total) * state[...]
                  + jax.lax.dot_general(
                      (k * decay_to_end), v, (((0,), (0,)), ((), ())),
                      preferred_element_type=jnp.float32))

    @pl.when(ci == n_chunks - 1)
    def _finish():
        sout_ref[0] = state[...]


def wkv6_bh(r, k, v, lw, u, state_in=None, *, chunk: int = 16,
            interpret: bool = False):
    """r/k/v/lw: (BH, S, hd); u: (BH, hd); state_in: optional (BH, hd, hd)
    f32 carry.  Returns (out (BH, S, hd), state_out (BH, hd, hd) f32).

    NOTE: trailing pad positions (S not a multiple of ``chunk``) are padded
    with zeros, which leave the state invariant (k=0 contributes nothing
    and lw=0 means decay exp(0)=1), so ``state_out`` is the state after
    exactly the S real steps."""
    BH, S, hd = r.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        padw = ((0, 0), (0, pad), (0, 0))
        r = jnp.pad(r, padw)
        k = jnp.pad(k, padw)
        v = jnp.pad(v, padw)
        lw = jnp.pad(lw, padw)
    if state_in is None:
        state_in = jnp.zeros((BH, hd, hd), jnp.float32)
    n_chunks = r.shape[1] // chunk
    kern = functools.partial(_kernel, chunk=chunk, hd=hd)
    out, state_out = pl.pallas_call(
        kern,
        grid=(BH, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, hd), lambda b, ci: (b, ci, 0)),
            # u as (BH, 1, hd): the block spans both trailing dims
            pl.BlockSpec((1, 1, hd), lambda b, ci: (b, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, ci: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, ci: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(r.shape, r.dtype),
            jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, lw, u[:, None, :], state_in.astype(jnp.float32))
    return out[:, :S], state_out

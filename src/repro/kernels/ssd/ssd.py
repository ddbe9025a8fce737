"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Per (batch, head): state (p, n) carried in VMEM scratch across sequence
chunks (grid = (B*H, n_chunks), chunk axis innermost):

    state_t = exp(dt_t A_h) state_{t-1} + dt_t x_t ⊗ B_t
    y_t     = C_t · state_t + D_h x_t

Intra-chunk uses the dense (Q, Q) decay matrix (MXU-friendly) exactly as the
jnp path in repro.models.ssm.apply_mamba_full.  B/C are head-shared
(ngroups=1) and index-mapped without replication.

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


def _kernel(a_ref, d_ref, x_ref, b_ref, c_ref, dt_ref, s0_ref, o_ref,
            sout_ref, state, *, chunk, p, n):
    bh = pl.program_id(0)
    ci = pl.program_id(1)
    n_chunks = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state[...] = s0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)  # (Q, p)
    Bm = b_ref[0].astype(jnp.float32)  # (Q, n)
    Cm = c_ref[0].astype(jnp.float32)  # (Q, n)
    dt = dt_ref[0].astype(jnp.float32)  # (Q, 1)
    A = a_ref[0, bh]  # scalar (negative), from SMEM
    D = d_ref[0, bh]

    la = dt * A  # (Q, 1) log decay per step
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (row >= col).astype(jnp.float32)
    # Mosaic has no cumsum and cannot broadcast a (1, 1) value: every sum
    # over steps is a matmul against a 0/1 matrix, at full f32 precision
    # since the decays compound over it.
    def steps_sum(lhs, rhs, dims):
        return jax.lax.dot_general(lhs, rhs, (dims, ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)

    seg = steps_sum(tril, la, ((1,), (0,)))  # inclusive, column (Q, 1)
    seg_row = steps_sum(la, tril, ((0,), (1,)))  # the same as a row (1, Q)
    # seg_end - seg[t] = sum over the steps after t
    to_end = steps_sum((row < col).astype(jnp.float32), la, ((1,), (0,)))
    # seg_end, broadcast down the state's p rows: (p, 1)
    total = steps_sum(jnp.ones((p, chunk), jnp.float32), la, ((1,), (0,)))
    # intra-chunk: Y[t] = sum_{i<=t} exp(seg[t]-seg[i]) (C_t·B_i) dt_i x_i
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    # exponents clamped <= 0 (masked upper-triangle entries would be inf)
    decay = jnp.exp(jnp.minimum(seg - seg_row, 0.0))
    M = jnp.where(row >= col, G * decay, 0.0)
    xb = x * dt
    y = jax.lax.dot_general(M, xb, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, p)
    # inter-chunk: Y[t] += C_t · (exp(seg[t]) state_in)   (state is (p, n))
    y = y + jnp.exp(seg) * jax.lax.dot_general(
        Cm, state[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    y = y + x * D
    o_ref[0] = y.astype(o_ref.dtype)
    # state update
    contrib = jax.lax.dot_general(
        (xb * jnp.exp(to_end)), Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # (p, n)
    state[...] = jnp.exp(total) * state[...] + contrib

    @pl.when(ci == n_chunks - 1)
    def _finish():
        sout_ref[0] = state[...]


def ssd_bh(x, Bm, Cm, dt, A, D, state_in=None, *, chunk: int = 64,
           interpret: bool = False):
    """x (BH, S, p); Bm/Cm (B, S, n) head-shared; dt (BH, S); A/D (BH,);
    state_in optional (BH, p, n) f32 carry.

    BH = B * H with head-major flattening (bh // H = batch).  Returns
    (out (BH, S, p), state_out (BH, p, n) f32).  Trailing pad positions
    (dt=0 ⇒ decay exp(0)=1, contribution 0) leave the state invariant, so
    ``state_out`` is the state after exactly the S real steps.
    """
    BH, S, p = x.shape
    B, _, n = Bm.shape
    H = BH // B
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad)))
    if state_in is None:
        state_in = jnp.zeros((BH, p, n), jnp.float32)
    n_chunks = x.shape[1] // chunk
    kern = functools.partial(_kernel, chunk=chunk, p=p, n=n)
    out, state_out = pl.pallas_call(
        kern,
        grid=(BH, n_chunks),
        in_specs=[
            # per-(batch, head) scalars A and D whole in SMEM, as (1, BH)
            # rows so that a vmapped call's blocks still span both
            # trailing dims
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, ci, H=H: (bh // H, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, ci, H=H: (bh // H, ci, 0)),
            # dt as a column (BH, S, 1): the block spans the trailing dim
            pl.BlockSpec((1, chunk, 1), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, p, n), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, p, n), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((BH, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(A.astype(jnp.float32)[None], D.astype(jnp.float32)[None], x, Bm, Cm,
      dt[:, :, None], state_in.astype(jnp.float32))
    return out[:, :S], state_out

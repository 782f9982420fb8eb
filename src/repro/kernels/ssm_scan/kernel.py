"""Pallas TPU kernel: Mamba selective-scan, chunked over time.

TPU adaptation of the CUDA selective-scan: the GPU kernel threads over
channels with registers holding h; on TPU we tile channels into VMEM blocks
and make the *chunk* dimension the innermost (sequential) grid axis so the
[bd, d_state] state lives in VMEM scratch across chunks.  Within a chunk the
recurrence is a ``fori_loop`` whose per-step work is [bd, d_state]
element-wise math + a [bd]-wide reduction — VPU work, with all HBM traffic
(inputs delta/B/C/x, output y) streamed once per chunk.

Grid: (batch, d_inner / bd, n_chunks); chunks innermost = sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(
    delta_ref,    # [chunk, bd]
    b_ref,        # [chunk, ds]
    c_ref,        # [chunk, ds]
    x_ref,        # [chunk, bd]
    a_ref,        # [bd, ds]     (A = -exp(a_log), precomputed by wrapper)
    h0_ref,       # [bd, ds]     initial state for this (batch, d-block)
    y_ref,        # [chunk, bd]  output
    hout_ref,     # [bd, ds]     final state
    h_ref,        # scratch [bd, ds] f32
    *,
    chunk: int,
    seq_len: int,
    n_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)                     # [bd, ds]

    def step(t, h_prev):
        dl = delta_ref[t, :].astype(jnp.float32)           # [bd]
        bt = b_ref[t, :].astype(jnp.float32)               # [ds]
        ct = c_ref[t, :].astype(jnp.float32)               # [ds]
        xt = x_ref[t, :].astype(jnp.float32)               # [bd]
        decay = jnp.exp(dl[:, None] * a)                   # [bd, ds]
        h_new = decay * h_prev + (dl * xt)[:, None] * bt[None, :]
        y = jnp.sum(h_new * ct[None, :], axis=1)           # [bd]
        valid = (ci * chunk + t) < seq_len                 # ragged tail guard
        y_ref[t, :] = jnp.where(valid, y, 0.0).astype(y_ref.dtype)
        # padded steps must not advance the state (streaming correctness)
        return jnp.where(valid, h_new, h_prev)

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h

    @pl.when(ci == n_chunks - 1)
    def _final():
        hout_ref[...] = h_ref[...].astype(hout_ref.dtype)


def ssm_scan(
    delta: jax.Array,   # [B, S, d_inner] f32
    b: jax.Array,       # [B, S, d_state]
    c: jax.Array,       # [B, S, d_state]
    x: jax.Array,       # [B, S, d_inner]
    a: jax.Array,       # [d_inner, d_state] (A = -exp(a_log))
    h0: jax.Array,      # [B, d_inner, d_state]
    *,
    chunk: int = 128,
    block_d: int = 512,
    interpret: bool = False,
):
    """Returns (y [B, S, d_inner], h_final [B, d_inner, d_state])."""
    bsz, s, di = delta.shape
    ds = b.shape[-1]
    chunk = min(chunk, s)
    bd = min(block_d, di)
    n_chunks = pl.cdiv(s, chunk)
    grid = (bsz, pl.cdiv(di, bd), n_chunks)
    kernel = functools.partial(
        _ssm_kernel, chunk=chunk, seq_len=s, n_chunks=n_chunks)
    y, hout = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, bd), lambda bb, dd, cc: (bb, cc, dd)),
            pl.BlockSpec((None, chunk, ds), lambda bb, dd, cc: (bb, cc, 0)),
            pl.BlockSpec((None, chunk, ds), lambda bb, dd, cc: (bb, cc, 0)),
            pl.BlockSpec((None, chunk, bd), lambda bb, dd, cc: (bb, cc, dd)),
            pl.BlockSpec((bd, ds), lambda bb, dd, cc: (dd, 0)),
            pl.BlockSpec((None, bd, ds), lambda bb, dd, cc: (bb, dd, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, bd), lambda bb, dd, cc: (bb, cc, dd)),
            pl.BlockSpec((None, bd, ds), lambda bb, dd, cc: (bb, dd, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, di), delta.dtype),
            jax.ShapeDtypeStruct((bsz, di, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(delta, b, c, x, a, h0)
    return y, hout

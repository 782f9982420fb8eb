"""Pallas TPU kernel: chunked-parallel mLSTM (xLSTM matrix memory).

Same schedule as `models.xlstm.mlstm_forward`: within a chunk the output is
an attention-like pair of [L, L] / [L, dh] matmuls weighted by stabilized
exponential gates; across chunks the [dh, dh] matrix state, the [dh]
normalizer and the scalar max-stabilizer are carried in VMEM scratch (the
chunk grid axis is sequential).

TPU-specific choices: the in-chunk cumulative sums/maxes are computed with
a lower-triangular matmul (MXU) and a log2(L)-step doubling max (VPU) —
no 1D sequential scans in the kernel body.

Grid: (batch*heads, n_chunks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_BIG = -1e30


def _cumsum_tri(x: jax.Array, tri: jax.Array) -> jax.Array:
    """Inclusive cumsum over axis 0 of [L] via lower-tri matmul (MXU)."""
    return jax.lax.dot_general(
        tri, x[:, None], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[:, 0]


def _cummax_doubling(x: jax.Array, length: int) -> jax.Array:
    """Inclusive running max over a [L] vector via log2(L) shifted maxes."""
    off = 1
    while off < length:
        shifted = jnp.concatenate([jnp.full((off,), NEG_BIG, x.dtype), x[:-off]])
        x = jnp.maximum(x, shifted)
        off *= 2
    return x


def _mlstm_kernel(
    q_ref, k_ref, v_ref,      # [chunk, dh]
    lf_ref, li_ref,           # [chunk]  log-forget / input-gate preacts
    h_out_ref,                # [chunk, dh]
    c_out_ref, n_out_ref, m_out_ref,   # final state outputs
    c_ref, n_ref, m_ref,      # scratch: [dh, dh], [dh], [1]
    *,
    chunk: int,
    seq_len: int,
    n_chunks: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_BIG)

    pos_valid = ci * chunk + jax.lax.iota(jnp.int32, chunk) < seq_len
    lf = jnp.where(pos_valid, lf_ref[...].astype(jnp.float32), 0.0)
    li = jnp.where(pos_valid, li_ref[...].astype(jnp.float32), NEG_BIG)
    q = q_ref[...].astype(jnp.float32)
    k = jnp.where(pos_valid[:, None], k_ref[...].astype(jnp.float32), 0.0)
    v = jnp.where(pos_valid[:, None], v_ref[...].astype(jnp.float32), 0.0)

    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    ).astype(jnp.float32)

    m0 = m_ref[0]
    c0 = c_ref[...]
    n0 = n_ref[...]

    b = _cumsum_tri(lf, tri)                               # [L]
    g = jnp.maximum(m0, _cummax_doubling(li - b, chunk))   # [L]
    m_i = b + g
    # intra weights D[i,t] = exp(li_t - b_t - g_i), t <= i
    lt = (li - b)[None, :] - g[:, None]
    d_w = jnp.where(tri > 0, jnp.exp(lt), 0.0)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    w_it = scores * d_w
    inter = jnp.exp(m0 - g)                                # [L]
    h_num = (
        jax.lax.dot_general(w_it, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(q, c0, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
        * inter[:, None]
    )
    # normalizer uses the decay weights only (no q.k scores)
    n_i = (
        jax.lax.dot_general(d_w, k, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
        + n0[None, :] * inter[:, None]
    )
    qn = jnp.sum(q * n_i, axis=1)
    denom = jnp.maximum(jnp.abs(qn), jnp.exp(-m_i))
    h_out_ref[...] = (h_num / denom[:, None]).astype(h_out_ref.dtype)

    # carry
    g_l = g[chunk - 1]
    m_new = m_i[chunk - 1]
    wc = jnp.exp(li - b - g_l)                             # [L]
    c_new = c0 * jnp.exp(m0 - g_l) + jax.lax.dot_general(
        v * wc[:, None], k, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [dh(v), dh(k)]
    n_new = n0 * jnp.exp(m0 - g_l) + jnp.sum(k * wc[:, None], axis=0)
    c_ref[...] = c_new
    n_ref[...] = n_new
    m_ref[0] = m_new

    @pl.when(ci == n_chunks - 1)
    def _final():
        c_out_ref[...] = c_ref[...]
        n_out_ref[...] = n_ref[...]
        m_out_ref[...] = m_ref[...]


def mlstm_scan(
    q: jax.Array,     # [BH, S, dh]   (k pre-scaled by 1/sqrt(dh))
    k: jax.Array,
    v: jax.Array,
    lf: jax.Array,    # [BH, S] logsigmoid(f-preact)
    li: jax.Array,    # [BH, S] input-gate preact
    *,
    chunk: int = 256,
    interpret: bool = False,
):
    """Zero initial state (the wrapper streams states via carry chunks).

    Returns (h [BH, S, dh], (C [BH, dh, dh], n [BH, dh], m [BH, 1]))."""
    bh, s, dh = q.shape
    chunk = min(chunk, s)
    n_chunks = pl.cdiv(s, chunk)
    kernel = functools.partial(
        _mlstm_kernel, chunk=chunk, seq_len=s, n_chunks=n_chunks)
    h, c, n, m = pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((None, chunk, dh), lambda b, cc: (b, cc, 0)),
            pl.BlockSpec((None, chunk, dh), lambda b, cc: (b, cc, 0)),
            pl.BlockSpec((None, chunk, dh), lambda b, cc: (b, cc, 0)),
            pl.BlockSpec((None, chunk), lambda b, cc: (b, cc)),
            pl.BlockSpec((None, chunk), lambda b, cc: (b, cc)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, dh), lambda b, cc: (b, cc, 0)),
            pl.BlockSpec((None, dh, dh), lambda b, cc: (b, 0, 0)),
            pl.BlockSpec((None, dh), lambda b, cc: (b, 0)),
            pl.BlockSpec((None, 1), lambda b, cc: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dh), q.dtype),
            jax.ShapeDtypeStruct((bh, dh, dh), jnp.float32),
            jax.ShapeDtypeStruct((bh, dh), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((dh,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v, lf, li)
    return h, (c, n, m)

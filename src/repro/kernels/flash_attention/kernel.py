"""Pallas TPU flash-attention forward kernel.

TPU-native schedule: grid = (batch*q_heads, n_q_blocks, n_kv_blocks) with the
KV dimension innermost ("arbitrary" = sequential on TPU), so the online-
softmax running statistics (m, l, acc) live in VMEM scratch and persist
across KV steps.  Block shapes are MXU-aligned (block_q x d and block_k x d,
d padded to 128 by the wrapper) and sized so the working set

    q(bq x d) + k(bk x d) + v(bk x d) + scores(bq x bk) + acc(bq x d)

stays well under the ~16 MiB v5e VMEM (default 512x512x128 fp32 ~= 1.5 MiB).

Supports causal masking, GQA (q-head -> kv-head folding in the index maps),
sliding windows, and always-visible meta tokens (Hymba) — the same
visibility rule as the `ref.py` oracle.  Masked-out KV blocks are skipped
with `pl.when` on the *whole block* when statically... (dynamically) fully
invisible, which is where the causal 2x win comes from.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fwd_kernel(
    q_ref,        # [block_q, d]
    k_ref,        # [block_k, d]
    v_ref,        # [block_k, d]
    o_ref,        # [block_q, d]
    m_ref,        # scratch [block_q]
    l_ref,        # scratch [block_q]
    acc_ref,      # scratch [block_q, d] f32
    *,
    sm_scale: float,
    causal: bool,
    window: int,
    n_meta: int,
    block_q: int,
    block_k: int,
    n_kv_blocks: int,
    kv_len: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # visibility: in-bounds AND causal AND (window | meta)
    vis = k_pos < kv_len
    if causal:
        vis &= k_pos <= q_pos
    if window > 0:
        in_win = (q_pos - k_pos) < window
        if n_meta > 0:
            in_win |= k_pos < n_meta
        vis &= in_win

    # skip blocks that are fully masked (static causal structure):
    # first visible kv block index for this q block is known only dynamically
    # for windows, so we gate on a cheap dynamic test.
    block_visible = jnp.any(vis)

    @pl.when(block_visible)
    def _step():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(vis, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        m_ref[...] = m_new
        # sanitize out-of-bounds KV rows: OOB loads are undefined (NaN in
        # interpret mode) and 0 * NaN = NaN would poison the whole q block
        kv_valid = (
            kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
            < kv_len
        )
        v = jnp.where(kv_valid, v_ref[...].astype(jnp.float32), 0.0)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,       # [BH, Sq, d]  (batch x q-heads flattened)
    k: jax.Array,       # [BKV, Skv, d] (batch x kv-heads flattened)
    v: jax.Array,       # [BKV, Skv, d]
    *,
    group: int,         # q heads per kv head (GQA)
    causal: bool = True,
    window: int = 0,
    n_meta: int = 0,
    sm_scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    bkv, skv, _ = k.shape
    assert bh == bkv * group, (bh, bkv, group)
    sm_scale = sm_scale if sm_scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(skv, block_k)

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, window=window,
        n_meta=n_meta, block_q=block_q, block_k=block_k, n_kv_blocks=nk,
        kv_len=skv,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)

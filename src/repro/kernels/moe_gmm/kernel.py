"""Pallas TPU kernel: grouped expert matmul over capacity buffers.

TPU adaptation of the fine-grained-MoE hotspot: after the EP dispatch
(`distributed.moe_ep`) tokens live in a dense [E_local, C, d] capacity
buffer, so the expert FFN is a *batched* matmul with MXU-aligned tiles —
no dynamic group boundaries inside the kernel (those were resolved by the
sort/compaction on dispatch).  Grid = (E, C/bc, f/bf, d/bd) with the
contraction dim innermost and an fp32 VMEM accumulator.

Default tiles (bc, bd, bf) = (128, 512, 512): working set
x(128x512) + w(512x512) + acc(128x512) fp32 ~= 1.6 MiB << 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k_blocks: int):
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == n_k_blocks - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(
    x: jax.Array,       # [E, C, d]  capacity buffers
    w: jax.Array,       # [E, d, f]  per-expert weights
    *,
    block_c: int = 128,
    block_d: int = 512,
    block_f: int = 512,
    interpret: bool = False,
) -> jax.Array:
    e, c, d = x.shape
    _, _, f = w.shape
    bc, bd, bf = min(block_c, c), min(block_d, d), min(block_f, f)
    grid = (e, pl.cdiv(c, bc), pl.cdiv(f, bf), pl.cdiv(d, bd))
    kernel = functools.partial(_gmm_kernel, n_k_blocks=grid[3])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bc, bd), lambda e_, i, j, kk: (e_, i, kk)),
            pl.BlockSpec((None, bd, bf), lambda e_, i, j, kk: (e_, kk, j)),
        ],
        out_specs=pl.BlockSpec((None, bc, bf), lambda e_, i, j, kk: (e_, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w)

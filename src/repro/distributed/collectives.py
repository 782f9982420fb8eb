"""Explicit collective/manual-partition helpers used where GSPMD's
automatic choices are wrong or buggy.

``embed_lookup``: token-embedding gather done under shard_map — each device
takes rows from its local [V, d/TP] shard for its local [B/DP, S] tokens.
Zero collectives, and it sidesteps a GSPMD dynamic-slice verifier bug that
the auto-partitioned gather trips at dbrx-132b sizes when the gather sits
inside the grad-accumulation loop.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def dp_tp_axes(mesh) -> Tuple[Tuple[str, ...], str]:
    names = mesh.axis_names
    dp = ("pod", "data") if "pod" in names else ("data",)
    return dp, "model"


def _dp_size(mesh, dp) -> int:
    n = 1
    for a in dp:
        n *= mesh.shape[a]
    return n


def embed_lookup(table: jax.Array, tokens: jax.Array, mesh) -> jax.Array:
    """table [V, d] (d sharded over TP), tokens [B, S] (B over DP)
    -> embeddings [B, S, d] (B over DP, d over TP)."""
    dp, tp = dp_tp_axes(mesh)

    def body(tbl, tok):
        return jnp.take(tbl, tok, axis=0)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, tp), P(dp, None)),
        out_specs=P(dp, None, tp),
        check_vma=False,
    )(table, tokens)


def usable_mesh(min_model: int = 2):
    """The ambient abstract mesh if it has a >1 'model' axis, else None
    (computations then run unsharded, which is correct on a single-device
    pool)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    if "model" not in mesh.axis_names or mesh.shape["model"] < min_model:
        return None
    return mesh


def sharded_kv_decode_attention(
    q: jax.Array,          # [B, Tq, H, D]
    k_cache: jax.Array,    # [B, S, KVH, D]  (S sharded over TP)
    v_cache: jax.Array,
    k_new: jax.Array,      # [B, Tq, KVH, D]
    v_new: jax.Array,
    q_pos: jax.Array,      # [B, Tq]
    kv_pos: jax.Array,     # [B, S]
    cursor: jax.Array,     # [] int32 write position
    mesh,
):
    """Flash-decoding over the model axis (beyond-paper decode hillclimb).

    Baseline decode shards the KV cache on kv-heads/head-dim, which GSPMD
    resolves with involuntary full rematerialization (replicate the 32k-long
    cache!).  Here the cache is sharded on the *sequence* dim: each TP rank
    writes the new KV if the slot falls in its range (scatter mode="drop"),
    attends over its local S/TP slice, and the partial softmax statistics
    (m, l, acc) are combined with three tiny psums of [B, H]-sized tensors
    instead of moving the cache.

    Returns (out [B, Tq, H, D], k_cache, v_cache) — cache still S-sharded.
    Full attention only (ring/window caches keep the baseline path).
    """
    import math as _math

    dp, tp = dp_tp_axes(mesh)
    tp_size = mesh.shape[tp]
    b, tq, h, d = q.shape
    s = k_cache.shape[1]
    kvh = k_cache.shape[2]
    g = h // kvh
    assert s % tp_size == 0
    s_loc = s // tp_size
    scale = 1.0 / _math.sqrt(d)

    def body(qb, kc, vc, kn, vn, qp, kp, cur):
        # local shapes: kc/vc [B_loc, S_loc, KVH, D]; kp [B_loc, S_loc]
        rank = jax.lax.axis_index(tp)
        # 1. localized cache write (slot may be on another rank -> dropped)
        slot = cur - rank * s_loc
        idx = slot + jnp.arange(kn.shape[1], dtype=jnp.int32)
        kc = kc.at[:, idx].set(kn.astype(kc.dtype), mode="drop")
        vc = vc.at[:, idx].set(vn.astype(vc.dtype), mode="drop")
        kp = kp.at[:, idx].set(qp.astype(kp.dtype), mode="drop")
        # 2. local partial attention
        qr = qb.reshape(b_loc, tq, kvh, g, d)
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qr, kc,
                        preferred_element_type=jnp.float32) * scale
        vis = (kp >= 0)[:, None] & (kp[:, None, :] <= qp[..., None])
        sc = jnp.where(vis[:, None, None], sc, -1e30)
        m_loc = sc.max(axis=-1)                              # [B,KVH,G,Tq]
        p = jnp.exp(sc - m_loc[..., None])
        l_loc = p.sum(axis=-1)
        acc_loc = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vc.dtype), vc)
        # 3. combine partial softmax statistics across TP
        m = jax.lax.pmax(m_loc, tp)
        corr = jnp.exp(m_loc - m)
        l = jax.lax.psum(l_loc * corr, tp)
        acc = jax.lax.psum(acc_loc.astype(jnp.float32) * corr[..., None], tp)
        out = (acc / jnp.maximum(l, 1e-30)[..., None])
        out = jnp.moveaxis(out, 3, 1).reshape(b_loc, tq, h, d)
        return out.astype(qb.dtype), kc, vc, kp

    dp_size = _dp_size(mesh, dp)
    b_loc = b // dp_size
    out, kc, vc, kp = jax.shard_map(
        body, mesh=mesh,
        in_specs=(
            P(dp, None, None, None),          # q
            P(dp, tp, None, None),            # k_cache (S sharded)
            P(dp, tp, None, None),            # v_cache
            P(dp, None, None, None),          # k_new
            P(dp, None, None, None),          # v_new
            P(dp, None),                      # q_pos
            P(dp, tp),                        # kv_pos
            P(),                              # cursor
        ),
        out_specs=(P(dp, None, None, None), P(dp, tp, None, None),
                   P(dp, tp, None, None), P(dp, tp)),
        check_vma=False,
    )(q, k_cache, v_cache, k_new, v_new, q_pos, kv_pos, cursor)
    return out, kc, vc, kp


def constrain_heads(x: jax.Array, heads_axis: int = 2) -> jax.Array:
    """Sharding constraint for [B, T, H, D]-shaped attention tensors.

    GSPMD's propagation gives up (and fully REPLICATES the downstream score
    tensors — observed 341 GiB/device on hymba-1.5b whose 25/5 heads don't
    divide the 16-way model axis) after the [B,T,H*D] -> [B,T,H,D] reshape.
    Pin: batch -> DP, heads -> TP if divisible else head_dim -> TP.
    No-op without an ambient mesh."""
    mesh = usable_mesh()
    if mesh is None or x.ndim < 3:
        return x
    dp, tp = dp_tp_axes(mesh)
    tp_size = mesh.shape[tp]
    spec = [None] * x.ndim
    if x.shape[0] % _dp_size(mesh, dp) == 0:
        spec[0] = dp
    if x.shape[heads_axis] % tp_size == 0:
        spec[heads_axis] = tp
    elif x.shape[-1] % tp_size == 0:
        spec[-1] = tp
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))

"""Ring attention: sequence-parallel exact attention over the 'seq' mesh axis.

The reference has no long-sequence machinery (SURVEY.md §5.7 — its attention
runs on ≤1024 tokens). Scaling this domain means higher image resolution
(256² ⇒ 65k tokens if attention were enabled at fine resolutions) and k>1
frames (more cross-attention pairs). This module makes that a first-class
capability: the H·W token axis is sharded over the mesh 'seq' axis, each
device holds one query block, and key/value blocks rotate around the ring via
`jax.lax.ppermute` (ICI neighbor exchange) while a numerically-stable online
softmax accumulates the output — compute and communication overlap, peak
memory is O(L·L/n) per device, and the result is EXACT attention.

Layout: q, k, v are (B, L_local, H, D); the accumulator runs in float32.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from novel_view_synthesis_3d_tpu.parallel.mesh import SEQ_AXIS

_NEG_INF = -1e30


def _shard_map(f, mesh, in_specs, out_specs):
    """jax.shard_map without the replication (vma) check — shared by the
    ring, ZeRO and pipeline shard_maps."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _block_update(q, k, v, m_prev, l_prev, o_prev, scale):
    """One flash-attention style block accumulation step.

    q: (B, Lq, H, D) · k, v: (B, Lk, H, D)
    m, l: (B, H, Lq) running max / normalizer · o: (B, Lq, H, D) f32.
    """
    s = jnp.einsum("blhd,bmhd->bhlm", q, k,
                   preferred_element_type=jnp.float32) * scale
    m_cur = jnp.maximum(m_prev, s.max(axis=-1))
    corr = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[..., None])  # (B, H, Lq, Lk)
    l_cur = l_prev * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhlm,bmhd->blhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o_cur = o_prev * corr.transpose(0, 2, 1)[..., None] + pv
    return m_cur, l_cur, o_cur


def ring_self_attention_local(q, k, v, *, axis_name: str = SEQ_AXIS,
                              scale: Optional[float] = None):
    """Per-shard body (call inside shard_map over `axis_name`)."""
    B, L, H, D = q.shape
    scale = (D ** -0.5) if scale is None else scale
    n = jax.lax.psum(1, axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    m0 = jnp.full((B, H, L), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, L), jnp.float32)
    o0 = jnp.zeros((B, L, H, D), jnp.float32)

    def body(_, carry):
        m, l, o, k_blk, v_blk = carry
        m, l, o = _block_update(q, k_blk, v_blk, m, l, o, scale)
        # Rotate k/v to the next ring neighbor while the next block computes.
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return m, l, o, k_blk, v_blk

    m, l, o, _, _ = jax.lax.fori_loop(0, n, body, (m0, l0, o0, k, v))
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(q, k, v, mesh: Mesh, *, axis_name: str = SEQ_AXIS,
                        scale: Optional[float] = None,
                        batch_axis: Optional[str] = None):
    """Exact attention with the token axis sharded over `axis_name`.

    q, k, v: GLOBAL (B, L, H, D) arrays (sharded or shardable); returns the
    attention output with the same global shape/sharding. `batch_axis`
    additionally shards the batch dim (composes SP with DP inside one
    shard_map — the train-step layout where batch rides the 'data' axis).
    """
    spec = P(batch_axis, axis_name, None, None)
    fn = _shard_map(
        partial(ring_self_attention_local, axis_name=axis_name, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    return fn(q, k, v)

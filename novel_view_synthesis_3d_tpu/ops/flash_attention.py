"""Fused multi-head attention as a Pallas TPU kernel.

The reference computes attention with `flax.nn.dot_product_attention`
(/root/reference/model/xunet.py:101), which materializes the (L, L) score
matrix in HBM between ops. This kernel keeps the whole
score→softmax→weighted-sum chain in VMEM, streaming one query block at a
time against the full key/value sequence (which for one (batch, head) pair
fits comfortably in VMEM at every config in the ladder — L ≤ 65k would not,
but attention only runs at coarse resolutions {8,16,32} ⇒ L ≤ 1024 tokens,
and cross-frame attention at k+1 frames tops out at a few thousand).

Layout notes (pallas_guide.md "Tiling Constraints"):
  - lanes (last dim) padded to a multiple of 128; sublanes to the dtype
    minimum. Padding is applied in the wrapper, masked inside the kernel
    with a statically-known length, and sliced off afterwards.
  - matmuls request `preferred_element_type=float32` so the MXU accumulates
    in f32 even for bf16 inputs; softmax runs in f32.

The backward pass is a custom VJP using the standard flash-attention
residuals (out, logsumexp): probabilities are recomputed from q·k and lse —
no (L, L) tensor is saved between forward and backward. For head_dim ≥
_PALLAS_BWD_MIN_HEAD_DIM the backward runs as two blocked Pallas kernels
(_dq_kernel over query blocks, _dkv_kernel over kv blocks — scores never
leave VMEM); below that, lane padding (D → 128) wastes more MXU than VMEM
residency saves, and an XLA einsum backward (_flash_bwd_xla) is used
instead (measured on v5e at D=16: ~20% faster train step).

Falls back to interpreter mode off-TPU so the same code path is unit-tested
on the CPU mesh (tests/test_flash_attention.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from novel_view_synthesis_3d_tpu.ops import _pallas

_NEG_INF = -1e30


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                 kv_len: int):
    """One query block vs. the full key/value sequence, entirely in VMEM.

    q_ref (1, Bq, D) · k_ref/v_ref (1, Lk_pad, D) · o_ref (1, Bq, D) ·
    lse_ref (1, Bq, 128) — lse broadcast across the lane dim to satisfy the
    TPU (sublane, lane) tiling constraint on output blocks.
    `kv_len` is the true (unpadded) kv length — static.
    """
    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if kv_len < k.shape[0]:  # mask padded kv columns (static condition)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < kv_len, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    o_ref[0] = (o / l).astype(o_ref.dtype)
    lse = m + jnp.log(l)  # (Bq, 1)
    lse_ref[0] = jnp.broadcast_to(lse, (lse.shape[0], lse_ref.shape[-1]))


def _flash_fwd_padded(q, k, v, *, scale: float, kv_len: int, block_q: int,
                      interpret: bool):
    """q (N, Lq_pad, Dp) · k,v (N, Lk_pad, Dp) → (out, lse)."""
    N, Lq, D = q.shape
    Lk = k.shape[1]
    grid = (N, Lq // block_q)
    kernel = functools.partial(_attn_kernel, scale=scale, kv_len=kv_len)
    mem = {} if interpret else {"memory_space": _pallas.VMEM}
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda n, i: (n, i, 0), **mem),
            pl.BlockSpec((1, Lk, D), lambda n, i: (n, 0, 0), **mem),
            pl.BlockSpec((1, Lk, D), lambda n, i: (n, 0, 0), **mem),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda n, i: (n, i, 0), **mem),
            pl.BlockSpec((1, block_q, 128), lambda n, i: (n, i, 0), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, Lq, D), q.dtype),
            jax.ShapeDtypeStruct((N, Lq, 128), jnp.float32),
        ],
        name="flash_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse[:, :, 0]


def _use_interpret() -> bool:
    return _pallas.use_interpret()


def resolve_flash(flag) -> bool:
    """Resolve a use_flash_attention config value ('auto' | bool);
    see ops/_pallas.resolve_flag for the shared semantics."""
    return _pallas.resolve_flag(flag, "use_flash_attention")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention(q, k, v, scale: float, block_q: int):
    out, _ = _flash_fwd_core(q, k, v, scale, block_q)
    return out


def _flash_fwd_core(q, k, v, scale: float, block_q: int):
    """(B, L, H, D) inputs → padded kernel call → unpadded (out, lse)."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    interpret = _use_interpret()
    # (B, L, H, D) → (B·H, L, D): heads become independent grid rows.
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Lq, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, Lk, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, Lk, D)
    # Query block: a multiple of 16 sublanes (covers the f32 and bf16 tile
    # minima) no larger than the padded query length. User-supplied block_q
    # is rounded up so any value Mosaic-compiles on hardware.
    block_q = ((block_q + 15) // 16) * 16
    bq = min(block_q, max(16, ((Lq + 15) // 16) * 16))
    qt = _pad_to(qt, 1, bq)
    kt = _pad_to(kt, 1, 128)
    vt = _pad_to(vt, 1, 128)
    if not interpret:  # lane alignment for the MXU
        qt = _pad_to(qt, 2, 128)
        kt = _pad_to(kt, 2, 128)
        vt = _pad_to(vt, 2, 128)
    out, lse = _flash_fwd_padded(qt, kt, vt, scale=scale, kv_len=Lk,
                                 block_q=bq, interpret=interpret)
    out = out[:, :Lq, :D].reshape(B, H, Lq, D).transpose(0, 2, 1, 3)
    lse = lse[:, :Lq].reshape(B, H, Lq)
    return out, lse


def _flash_vjp_fwd(q, k, v, scale: float, block_q: int):
    out, lse = _flash_fwd_core(q, k, v, scale, block_q)
    return out, (q, k, v, out, lse)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref, *,
               scale: float, kv_len: int):
    """dq for one query block: recompute p from lse, ds = p·(dp−δ)·scale,
    dq = ds·K. q/do (1,Bq,D) · k/v (1,Lk,D) · lse/dlt (1,Bq,128)."""
    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if kv_len < k.shape[0]:
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < kv_len, s, _NEG_INF)
    p = jnp.exp(s - lse_ref[0][:, :1])                       # (Bq, Lk)
    dp = jax.lax.dot_general(
        do_ref[0], v_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - dlt_ref[0][:, :1]) * scale
    dq_ref[0] = jax.lax.dot_general(
        ds.astype(k.dtype), k, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dk_ref,
                dv_ref, *, scale: float):
    """dk/dv for one kv block against the full query sequence.
    k/v (1,Bk,D) · q/do (1,Lq,D) · lse/dlt (1,Lq,128). Padded q rows carry
    lse=+inf ⇒ p=0 ⇒ they contribute nothing."""
    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (Lq, Bk)
    p = jnp.exp(s - lse_ref[0][:, :1])
    do = do_ref[0]
    dv_ref[0] = jax.lax.dot_general(
        p.astype(do.dtype), do, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(
        do, v_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (Lq, Bk)
    ds = p * (dp - dlt_ref[0][:, :1]) * scale
    dk_ref[0] = jax.lax.dot_general(
        ds.astype(q.dtype), q, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, g, scale: float, block_q: int):
    """Blocked Pallas backward: one pass for dq (grid over q blocks), one
    for dk/dv (grid over kv blocks); no (Lq, Lk) tensor ever leaves VMEM."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    interpret = _use_interpret()
    g32 = g.astype(jnp.float32)
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1)  # (B, Lq, H)

    def to_nld(x, L):
        return x.transpose(0, 2, 1, 3).reshape(B * H, L, x.shape[-1])

    qt, kt, vt = to_nld(q, Lq), to_nld(k, Lk), to_nld(v, Lk)
    dot = to_nld(g, Lq)
    lse_n = lse.reshape(B * H, Lq)
    dlt_n = delta.transpose(0, 2, 1).reshape(B * H, Lq)

    block_q = ((block_q + 15) // 16) * 16
    bq = min(block_q, max(16, ((Lq + 15) // 16) * 16))
    bk = min(block_q, max(16, ((Lk + 15) // 16) * 16))
    qt = _pad_to(qt, 1, bq)
    dot = _pad_to(dot, 1, bq)
    # kv must pad to a common multiple of the block size AND the 128-lane
    # tile so the (Lk_p // bk) grid covers every row exactly — padding to
    # max(bk, 128) alone leaves a partial trailing block unwritten when bk
    # doesn't divide 128.
    kv_mult = bk * 128 // math.gcd(bk, 128)
    kt = _pad_to(kt, 1, kv_mult)
    vt = _pad_to(vt, 1, kv_mult)
    # Padded q rows: lse=+inf makes their probabilities exactly 0.
    Lq_p, Lk_p = qt.shape[1], kt.shape[1]
    lse_p = jnp.pad(lse_n, ((0, 0), (0, Lq_p - Lq)),
                    constant_values=jnp.inf)
    dlt_p = jnp.pad(dlt_n, ((0, 0), (0, Lq_p - Lq)))
    # Lane-broadcast lse/delta to (N, L, 128) to satisfy output/input tiling.
    lse_b = jnp.broadcast_to(lse_p[..., None], lse_p.shape + (128,))
    dlt_b = jnp.broadcast_to(dlt_p[..., None], dlt_p.shape + (128,))
    if not interpret:
        qt = _pad_to(qt, 2, 128)
        kt = _pad_to(kt, 2, 128)
        vt = _pad_to(vt, 2, 128)
        dot = _pad_to(dot, 2, 128)
    N, _, Dp = qt.shape
    mem = {} if interpret else {"memory_space": _pallas.VMEM}

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, kv_len=Lk),
        grid=(N, Lq_p // bq),
        in_specs=[
            pl.BlockSpec((1, bq, Dp), lambda n, i: (n, i, 0), **mem),
            pl.BlockSpec((1, Lk_p, Dp), lambda n, i: (n, 0, 0), **mem),
            pl.BlockSpec((1, Lk_p, Dp), lambda n, i: (n, 0, 0), **mem),
            pl.BlockSpec((1, bq, Dp), lambda n, i: (n, i, 0), **mem),
            pl.BlockSpec((1, bq, 128), lambda n, i: (n, i, 0), **mem),
            pl.BlockSpec((1, bq, 128), lambda n, i: (n, i, 0), **mem),
        ],
        out_specs=pl.BlockSpec((1, bq, Dp), lambda n, i: (n, i, 0), **mem),
        out_shape=jax.ShapeDtypeStruct((N, Lq_p, Dp), q.dtype),
        name="flash_dq",
        interpret=interpret,
    )(qt, kt, vt, dot, lse_b, dlt_b)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale),
        grid=(N, Lk_p // bk),
        in_specs=[
            pl.BlockSpec((1, Lq_p, Dp), lambda n, j: (n, 0, 0), **mem),
            pl.BlockSpec((1, bk, Dp), lambda n, j: (n, j, 0), **mem),
            pl.BlockSpec((1, bk, Dp), lambda n, j: (n, j, 0), **mem),
            pl.BlockSpec((1, Lq_p, Dp), lambda n, j: (n, 0, 0), **mem),
            pl.BlockSpec((1, Lq_p, 128), lambda n, j: (n, 0, 0), **mem),
            pl.BlockSpec((1, Lq_p, 128), lambda n, j: (n, 0, 0), **mem),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, Dp), lambda n, j: (n, j, 0), **mem),
            pl.BlockSpec((1, bk, Dp), lambda n, j: (n, j, 0), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, Lk_p, Dp), k.dtype),
            jax.ShapeDtypeStruct((N, Lk_p, Dp), v.dtype),
        ],
        name="flash_dkv",
        interpret=interpret,
    )(qt, kt, vt, dot, lse_b, dlt_b)

    def from_nld(x, L):
        return x[:, :L, :D].reshape(B, H, L, D).transpose(0, 2, 1, 3)

    return from_nld(dq, Lq), from_nld(dk, Lk), from_nld(dv, Lk)


def _flash_bwd_xla(q, k, v, out, lse, g, scale: float):
    """Einsum backward with p recomputed from lse. Materializes (Lq, Lk) in
    HBM, but for small head_dim XLA's unpadded contractions beat the Pallas
    kernels' 128-lane padding (measured on v5e at D=16: ~20% faster step)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    p = jnp.exp(s - lse[..., None])                      # (B,H,Lq,Lk)
    g32 = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, g32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", g32, v.astype(jnp.float32))
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1)  # (B,Lq,H)
    ds = p * (dp - delta.transpose(0, 2, 1)[..., None]) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32))
    return dq, dk, dv


# Below this head_dim the Pallas backward's lane padding (D → 128) wastes
# more MXU than the fused VMEM residency saves.
_PALLAS_BWD_MIN_HEAD_DIM = 64


def _flash_vjp_bwd(scale: float, block_q: int, res, g):
    q, k, v, out, lse = res
    if q.shape[-1] >= _PALLAS_BWD_MIN_HEAD_DIM or _use_interpret():
        dq, dk, dv = _flash_bwd_pallas(q, k, v, out, lse, g, scale, block_q)
    else:
        dq, dk, dv = _flash_bwd_xla(q, k, v, out, lse, g, scale)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    scale: Optional[float] = None,
                    block_q: int = 256) -> jnp.ndarray:
    """Fused softmax(q·kᵀ/√D)·v. q (B, Lq, H, D), k/v (B, Lk, H, D).

    Drop-in for `flax.linen.dot_product_attention` (same layout/scaling).
    """
    D = q.shape[-1]
    scale = float(D ** -0.5) if scale is None else float(scale)
    return _flash_attention(q, k, v, scale, int(block_q))

"""Forward-only fused attention for the serving path.

ops/flash_attention.py exists for TRAINING: it carries logsumexp
residuals, a custom VJP, and two blocked backward kernels. None of that
is needed at serving time — the sampler never differentiates — so this
module is the inference twin: softmax(q·kᵀ/√D)·v as one Pallas pass per
(batch·head, query-block) grid row with NO residual outputs and no VJP
machinery (jax.custom_jvp/vjp bookkeeping costs trace time on every
step program build, and the lse output costs an HBM write per block).

Serving shapes are small — attention runs at the coarse UNet levels
({8,16,32} ⇒ L ≤ 1024 tokens; cross-frame attention at k+1 frames a few
thousand) — so one query block against the full key/value sequence fits
VMEM at every ladder config. Shapes whose resident slabs would exceed
the shared budget (ops/_pallas.SLAB_LIMIT_BYTES) fall back to the XLA
`nn.dot_product_attention` path PER SHAPE, and every decision is
recorded in a module-level coverage registry keyed by the logical
(B, Lq, Lk, heads, head_dim, dtype) shape — tools/summarize_bench.py
renders it so a serving config knows exactly which of its shapes ran
the kernel. The registry is populated at trace time (one entry per
compiled shape, like models/layers.log_once), not per step.

Off-TPU the kernel runs through the Pallas interpreter
(ops/_pallas.use_interpret) so tier-1 exercises the identical kernel
path; 'auto' resolves to TPU-only, the shared resolve_flag semantics.
"""

from __future__ import annotations

import functools
import threading
from typing import Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from novel_view_synthesis_3d_tpu.ops import _pallas

_NEG_INF = -1e30
_LANES = 128

# Coverage registry: logical shape → "kernel" | "fallback:vmem".
# Written at trace time (one entry per compiled shape), read by
# tools/summarize_bench.py and the service health snapshot.
ShapeKey = Tuple[int, int, int, int, int, str]
_coverage: Dict[ShapeKey, str] = {}
_coverage_lock = threading.Lock()


def attention_coverage() -> Dict[ShapeKey, str]:
    """Snapshot of per-shape kernel/fallback decisions made so far."""
    with _coverage_lock:
        return dict(_coverage)


def reset_attention_coverage() -> None:
    with _coverage_lock:
        _coverage.clear()


def _record(key: ShapeKey, decision: str) -> None:
    with _coverage_lock:
        _coverage[key] = decision


def resolve_serving_attention(flag) -> bool:
    """Resolve a use_serving_attention config value ('auto' | bool);
    see ops/_pallas.resolve_flag for the shared semantics."""
    return _pallas.resolve_flag(flag, "use_serving_attention")


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _serving_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                    kv_len: int):
    """One query block vs. the full kv sequence, entirely in VMEM.

    q_ref (1, Bq, D) · k_ref/v_ref (1, Lk_pad, D) · o_ref (1, Bq, D).
    `kv_len` is the true (unpadded) kv length — static, so the padded-
    column mask compiles away when there is no padding. Identical math
    to flash_attention's forward, minus the lse output."""
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


def _slab_bytes(bq: int, lk_p: int, d_p: int, itemsize: int) -> int:
    """Per-program VMEM residency: the q block, both kv slabs, and the
    f32 (Bq, Lk) score/probability working set."""
    return (bq + 2 * lk_p) * d_p * itemsize + bq * lk_p * 4


def serving_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      scale: Optional[float] = None,
                      block_q: int = 256) -> jnp.ndarray:
    """Fused forward-only softmax(q·kᵀ/√D)·v. q (B, Lq, H, D), k/v
    (B, Lk, H, D) — drop-in for `flax.linen.dot_product_attention`.

    Falls back to the XLA path per shape when the resident slabs exceed
    the shared VMEM budget; either way the decision lands in the
    coverage registry (attention_coverage)."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    key: ShapeKey = (B, Lq, Lk, H, D, jnp.dtype(q.dtype).name)
    scale = float(D ** -0.5) if scale is None else float(scale)
    interpret = _pallas.use_interpret()

    block_q = ((block_q + 15) // 16) * 16
    bq = min(block_q, max(16, ((Lq + 15) // 16) * 16))
    Lk_p = Lk + ((-Lk) % _LANES)
    D_p = D if interpret else D + ((-D) % _LANES)
    if not _pallas.fits_vmem(
            _slab_bytes(bq, Lk_p, D_p, jnp.dtype(q.dtype).itemsize)):
        _record(key, "fallback:vmem")
        return nn.dot_product_attention(q, k, v)
    _record(key, "kernel")

    # (B, L, H, D) → (B·H, L, D): heads become independent grid rows.
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Lq, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, Lk, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, Lk, D)
    qt = _pad_to(qt, 1, bq)
    kt = _pad_to(kt, 1, _LANES)
    vt = _pad_to(vt, 1, _LANES)
    if not interpret:  # lane alignment for the MXU
        qt = _pad_to(qt, 2, _LANES)
        kt = _pad_to(kt, 2, _LANES)
        vt = _pad_to(vt, 2, _LANES)
    N, Lq_p, Dp = qt.shape
    Lk_pad = kt.shape[1]
    mem = {} if interpret else {"memory_space": _pallas.VMEM}
    out = pl.pallas_call(
        functools.partial(_serving_kernel, scale=scale, kv_len=Lk),
        grid=(N, Lq_p // bq),
        in_specs=[
            pl.BlockSpec((1, bq, Dp), lambda n, i: (n, i, 0), **mem),
            pl.BlockSpec((1, Lk_pad, Dp), lambda n, i: (n, 0, 0), **mem),
            pl.BlockSpec((1, Lk_pad, Dp), lambda n, i: (n, 0, 0), **mem),
        ],
        out_specs=pl.BlockSpec((1, bq, Dp), lambda n, i: (n, i, 0), **mem),
        out_shape=jax.ShapeDtypeStruct((N, Lq_p, Dp), q.dtype),
        name="serving_attention",
        interpret=interpret,
    )(qt, kt, vt)
    return out[:, :Lq, :D].reshape(B, H, Lq, D).transpose(0, 2, 1, 3)

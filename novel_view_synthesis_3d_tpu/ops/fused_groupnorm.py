"""Fused GroupNorm(+swish) Pallas kernel for the HBM-bound UNet blocks.

Motivation (measured, r2): the base128 train step runs at ~83% of HBM
bandwidth and ~40% MXU — bytes, not FLOPs, bound. XLA lowers GroupNorm as
a reduce (read x) + a normalize map (read x again, write y): ≈ 2 reads +
1 write of the full activation per GN, twice per ResnetBlock
(/root/reference/model/xunet.py:63-92 has the same GN→swish and GN→FiLM
chains). This kernel keeps one sample-row's (H·W, C) slab resident in VMEM
and does stats + normalize + activation in a single pass: 1 read + 1 write
— removing ~a third of GN traffic from the step's byte budget.

Design:
  - grid = (N,) with N = B·F rows (per-frame statistics, the framework
    default; the reference-compat shared-stats path stays on XLA);
  - whole (H·W, C) slab per program; `fits_vmem` guards the slab size and
    callers fall back to XLA above it (paper256's 256²·256 top level);
  - statistics in float32 regardless of input dtype (bf16-safe);
  - forward = Pallas, backward = explicit jnp GN/swish VJP (the training
    step's backward was never the bandwidth win; sampling/eval are
    forward-only and get the full benefit).

Channel grouping matches flax.linen.GroupNorm: C is split into
(groups, C//groups) consecutive-channel blocks; eps defaults to flax's
1e-6 so the two paths are numerically interchangeable.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from novel_view_synthesis_3d_tpu.ops import _pallas

# Shared per-program VMEM slab budget (ops/_pallas.py): a 3 MiB input
# slab bounds the kernel's worst case at ~12 MiB on a ~16 MB/core part.
# base128's top level (128·128·128 bf16 = 4 MiB) falls back to XLA; its
# 64²·256 and lower levels (≤2 MiB) fuse.
_SLAB_LIMIT_BYTES = _pallas.SLAB_LIMIT_BYTES


def _use_interpret() -> bool:
    return _pallas.use_interpret()


def resolve_fused_gn(flag) -> bool:
    """Resolve a use_fused_groupnorm config value ('auto' | bool);
    see ops/_pallas.resolve_flag for the shared semantics."""
    return _pallas.resolve_flag(flag, "use_fused_groupnorm")


def fits_vmem(hw: int, c: int, dtype) -> bool:
    """True if one (H·W, C) slab fits the kernel's VMEM budget."""
    return _pallas.fits_vmem(hw * c * jnp.dtype(dtype).itemsize)


def round_to(a, dtype):
    """Round an f32 value to `dtype`'s grid, keeping the f32 container.

    The chip's compiler takes no bf16 vector arithmetic (and no bf16
    operand broadcast against f32 statistics), so the kernels compute in
    f32 and round after each op the XLA path runs in the module dtype —
    the same bits, op for op. A no-op at float32."""
    return a.astype(dtype).astype(jnp.float32)


def swish_in(y, dtype):
    """y·σ(y) with the XLA path's rounding: XLA expands the logistic to
    1/(1+exp(−y)) and rounds each op to the module dtype."""
    sig = round_to(1.0 / round_to(1.0 + round_to(jnp.exp(-y), dtype), dtype),
                   dtype)
    return (y * sig).astype(dtype)


def group_moments(x, groups: int, eps: float):
    """Two-pass GroupNorm statistics of one (HW, C) f32 slab.

    Returns (x − μ, rstd as a (1, C) row, μ and rstd as (1, G) rows).
    Group sums contract the channel axis against a (G, C) one-hot
    membership matrix, and the same matrix broadcasts group values back
    to channels: the per-group (HW, C) → (HW, G, C/G) reshape is a shape
    cast the chip's compiler refuses. E[(x−μ)²] is free of the
    E[x²]−E[x]² cancellation and costs no extra HBM traffic on a
    VMEM-resident slab."""
    hw, c = x.shape
    cg = c // groups
    ch = jax.lax.broadcasted_iota(jnp.int32, (groups, c), 1)
    lo = jax.lax.broadcasted_iota(jnp.int32, (groups, c), 0) * cg
    member = ((ch >= lo) & (ch < lo + cg)).astype(jnp.float32)   # (G, C)

    def dot(a, contract_member_axis):
        return jax.lax.dot_general(
            a, member, (((1,), (contract_member_axis,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    inv_n = 1.0 / float(hw * cg)
    mean = dot(jnp.sum(x, axis=0, keepdims=True), 1) * inv_n      # (1, G)
    xc = x - dot(mean, 0)
    var = dot(jnp.sum(jnp.square(xc), axis=0, keepdims=True), 1) * inv_n
    rstd = jax.lax.rsqrt(var + eps)
    return xc, dot(rstd, 0), mean, rstd


def slab_blocks(hw: int, c: int, groups: int):
    """(affine row, slab, statistics) BlockSpecs of a grid over N rows:
    affine rows as (1, C) and statistics as (N, 1, G), so that every
    block's last two dims equal the array's, as the TPU block rule
    requires (a (1, G) block over (N, G) is refused for N > 1)."""
    return (pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, hw, c), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, groups), lambda i: (i, 0, 0)))


def affine_row(a):
    """A (C,) GroupNorm parameter as the kernel's (1, C) f32 row."""
    return a.astype(jnp.float32).reshape(1, -1)


def _gn_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref,
               *, groups: int, eps: float, act: Optional[str]):
    x = x_ref[0].astype(jnp.float32)            # (HW, C)
    xc, rstd_c, mean, rstd = group_moments(x, groups, eps)
    # Round BEFORE the activation to mirror the XLA path's ordering
    # (nn.GroupNorm casts its output to the module dtype, then swish runs
    # in that dtype) — keeps the two paths interchangeable at bf16 too.
    y = round_to(xc * rstd_c * g_ref[...] + b_ref[...], y_ref.dtype)
    y_ref[0] = (swish_in(y, y_ref.dtype) if act == "swish"
                else y.astype(y_ref.dtype))
    mean_ref[0] = mean
    rstd_ref[0] = rstd


def _forward(x, scale, bias, groups: int, eps: float, act: Optional[str],
             out_dtype):
    n, hw, c = x.shape
    kernel = functools.partial(_gn_kernel, groups=groups, eps=eps, act=act)
    row, slab, stat = slab_blocks(hw, c, groups)
    y, mean, rstd = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[slab, row, row],
        out_specs=[slab, stat, stat],
        out_shape=[
            jax.ShapeDtypeStruct((n, hw, c), out_dtype or x.dtype),
            jax.ShapeDtypeStruct((n, 1, groups), jnp.float32),
            jax.ShapeDtypeStruct((n, 1, groups), jnp.float32),
        ],
        compiler_params=_pallas.slab_compiler_params(),
        name="fused_groupnorm",
        interpret=_use_interpret(),
    )(x, affine_row(scale), affine_row(bias))
    return y, mean.reshape(n, groups), rstd.reshape(n, groups)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_group_norm(x, scale, bias, groups: int = 32, eps: float = 1e-6,
                     act: Optional[str] = None, out_dtype=None):
    """GroupNorm(+optional swish) over (N, H·W, C) rows in one HBM pass.

    scale/bias are (C,) — flax GroupNorm's parameter shapes. Returns the
    normalized (activated) tensor in `out_dtype` (default x.dtype); the
    cast happens BEFORE the activation, mirroring the XLA path's
    nn.GroupNorm(dtype=out_dtype)-then-swish ordering so the two paths
    stay interchangeable even when x.dtype differs from the module dtype.
    Differentiable via an explicit XLA backward (see module docstring).
    """
    y, _, _ = _forward(x, scale, bias, groups, eps, act, out_dtype)
    return y


def _fwd(x, scale, bias, groups, eps, act, out_dtype):
    y, mean, rstd = _forward(x, scale, bias, groups, eps, act, out_dtype)
    return y, (x, scale, bias, mean, rstd)


def _bwd(groups, eps, act, out_dtype, res, g):
    x, scale, bias, mean, rstd = res
    n, hw, c = x.shape
    cg = c // groups
    xf = x.astype(jnp.float32).reshape(n, hw, groups, cg)
    xhat = ((xf - mean[:, None, :, None]) * rstd[:, None, :, None]
            ).reshape(n, hw, c)
    gamma = scale.astype(jnp.float32)
    z = xhat * gamma + bias.astype(jnp.float32)
    g = g.astype(jnp.float32)
    if act == "swish":
        sig = jax.nn.sigmoid(z)
        dz = g * (sig * (1.0 + z * (1.0 - sig)))
    else:
        dz = g
    dgamma = jnp.sum(dz * xhat, axis=(0, 1))
    dbeta = jnp.sum(dz, axis=(0, 1))
    dxhat = (dz * gamma).reshape(n, hw, groups, cg)
    m1 = jnp.mean(dxhat, axis=(1, 3), keepdims=True)
    xhat_g = xhat.reshape(n, hw, groups, cg)
    m2 = jnp.mean(dxhat * xhat_g, axis=(1, 3), keepdims=True)
    dx = (dxhat - m1 - xhat_g * m2) * rstd[:, None, :, None]
    return (dx.reshape(n, hw, c).astype(x.dtype),
            dgamma.astype(scale.dtype), dbeta.astype(bias.dtype))


fused_group_norm.defvjp(_fwd, _bwd)

"""Fused GroupNorm → FiLM-modulate → SiLU block epilogue.

The ResnetBlock tail (models/layers.py) is three bandwidth-bound
elementwise stages with an HBM round-trip between each: GroupNorm reads
and writes the (B·F, H·W, C) activation, the FiLM modulation reads it
back along with the SAME-SHAPE per-pixel scale/shift tensors (3DiM's
FiLM conditioning is spatial — scale/shift are full (H, W, C) maps, not
per-channel scalars), and the swish reads the result again. This kernel
runs the whole tail as ONE pass per (B·F) grid row:

    y = silu((1 + s) · (x̂·γ + β) + t)

with the row's x/s/t slabs resident in VMEM, f32 statistics, and the
same cast-before-activation ordering as the XLA path (nn.GroupNorm
casts to the module dtype, then the modulate/activate chain runs in
that dtype) so the two paths stay numerically interchangeable.

The FiLM Dense projection that PRODUCES s/t stays in XLA — it is a
matmul the MXU already handles; the win here is the elementwise tail's
byte budget. Backward is an explicit XLA VJP (same split as
ops/fused_groupnorm.py: sampling is forward-only and gets the full
benefit; training correctness is preserved without a Pallas backward).
Off-TPU the kernel runs through the Pallas interpreter, so tier-1
exercises the identical kernel path (ops/_pallas.use_interpret).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from novel_view_synthesis_3d_tpu.ops import _pallas
from novel_view_synthesis_3d_tpu.ops.fused_groupnorm import (
    affine_row,
    group_moments,
    round_to,
    slab_blocks,
    swish_in,
)


def resolve_fused_epilogue(flag) -> bool:
    """Resolve a use_fused_epilogue config value ('auto' | bool);
    see ops/_pallas.resolve_flag for the shared semantics."""
    return _pallas.resolve_flag(flag, "use_fused_epilogue")


def fits_vmem(hw: int, c: int, dtype) -> bool:
    """True if one grid row's resident slabs fit the kernel budget.

    Three same-shape input slabs stay resident per program (the
    activation row plus the FiLM scale and shift rows), so the shared
    single-slab budget is applied to 3× the row size."""
    return _pallas.fits_vmem(3 * hw * c * jnp.dtype(dtype).itemsize)


def _epilogue_kernel(x_ref, g_ref, b_ref, s_ref, t_ref, y_ref, mean_ref,
                     rstd_ref, *, groups: int, eps: float):
    x = x_ref[0].astype(jnp.float32)            # (HW, C)
    xc, rstd_c, mean, rstd = group_moments(x, groups, eps)
    # Round BEFORE modulate+activate, and after each op of that chain,
    # to mirror the XLA ordering: nn.GroupNorm casts its output to the
    # module dtype, then FiLM's h·(1+s)+t and the swish run in that
    # dtype (ops/fused_groupnorm.round_to: f32 arithmetic, same bits).
    dt = y_ref.dtype
    gn = round_to(xc * rstd_c * g_ref[...] + b_ref[...], dt)
    one_plus_s = round_to(1.0 + s_ref[0].astype(jnp.float32), dt)
    z = round_to(round_to(gn * one_plus_s, dt)
                 + t_ref[0].astype(jnp.float32), dt)
    y_ref[0] = swish_in(z, dt)
    mean_ref[0] = mean
    rstd_ref[0] = rstd


def _forward(x, gscale, gbias, fscale, fshift, groups: int, eps: float,
             out_dtype):
    n, hw, c = x.shape
    kernel = functools.partial(_epilogue_kernel, groups=groups, eps=eps)
    row, slab, stat = slab_blocks(hw, c, groups)
    y, mean, rstd = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[slab, row, row, slab, slab],
        out_specs=[slab, stat, stat],
        out_shape=[
            jax.ShapeDtypeStruct((n, hw, c), out_dtype or x.dtype),
            jax.ShapeDtypeStruct((n, 1, groups), jnp.float32),
            jax.ShapeDtypeStruct((n, 1, groups), jnp.float32),
        ],
        compiler_params=_pallas.slab_compiler_params(),
        name="fused_epilogue",
        interpret=_pallas.use_interpret(),
    )(x, affine_row(gscale), affine_row(gbias), fscale, fshift)
    return y, mean.reshape(n, groups), rstd.reshape(n, groups)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def fused_film_epilogue(x, gscale, gbias, fscale, fshift,
                        groups: int = 32, eps: float = 1e-6,
                        out_dtype=None):
    """silu((1+fscale)·GroupNorm(x)+fshift) over (N, H·W, C) rows in one
    HBM pass. gscale/gbias are the (C,) GroupNorm parameters;
    fscale/fshift are the per-pixel (N, H·W, C) FiLM tensors (already
    projected by the FiLM Dense, which stays in XLA)."""
    y, _, _ = _forward(x, gscale, gbias, fscale, fshift, groups, eps,
                       out_dtype)
    return y


def _fwd(x, gscale, gbias, fscale, fshift, groups, eps, out_dtype):
    y, mean, rstd = _forward(x, gscale, gbias, fscale, fshift, groups,
                             eps, out_dtype)
    return y, (x, gscale, gbias, fscale, fshift, mean, rstd)


def _bwd(groups, eps, out_dtype, res, g):
    x, gscale, gbias, fscale, fshift, mean, rstd = res
    n, hw, c = x.shape
    cg = c // groups
    xf = x.astype(jnp.float32).reshape(n, hw, groups, cg)
    xhat = ((xf - mean[:, None, :, None]) * rstd[:, None, :, None]
            ).reshape(n, hw, c)
    gamma = gscale.astype(jnp.float32)
    gn = xhat * gamma + gbias.astype(jnp.float32)
    s = fscale.astype(jnp.float32)
    z = gn * (1.0 + s) + fshift.astype(jnp.float32)
    g = g.astype(jnp.float32)
    sig = jax.nn.sigmoid(z)
    dz = g * (sig * (1.0 + z * (1.0 - sig)))
    dfshift = dz
    dfscale = dz * gn
    dgn = dz * (1.0 + s)
    dgamma = jnp.sum(dgn * xhat, axis=(0, 1))
    dbeta = jnp.sum(dgn, axis=(0, 1))
    dxhat = (dgn * gamma).reshape(n, hw, groups, cg)
    m1 = jnp.mean(dxhat, axis=(1, 3), keepdims=True)
    xhat_g = xhat.reshape(n, hw, groups, cg)
    m2 = jnp.mean(dxhat * xhat_g, axis=(1, 3), keepdims=True)
    dx = (dxhat - m1 - xhat_g * m2) * rstd[:, None, :, None]
    return (dx.reshape(n, hw, c).astype(x.dtype),
            dgamma.astype(gscale.dtype), dbeta.astype(gbias.dtype),
            dfscale.astype(fscale.dtype), dfshift.astype(fshift.dtype))


fused_film_epilogue.defvjp(_fwd, _bwd)

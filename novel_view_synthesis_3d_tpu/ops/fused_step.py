"""Fused denoise-step update as a Pallas TPU kernel.

The serving hot path runs the per-step reverse-process update 4–256
times per request (PAPER.md §3; 4–8 after progressive distillation).
After the UNet forward, XLA lowers that update as ~a dozen separate
elementwise HLOs — CFG guidance combine, x̂₀ reconstruction, clipping,
the ancestral/DDIM update line, the noise add — each reading and
writing the full (B, H, W, 3) latent in HBM. On a memory-bandwidth-
bound part that is ~12 HBM round trips for arithmetic the VPU finishes
in a fraction of one (the Gemma-on-TPU serving comparison in PAPERS.md:
per-step fusion is where TPU serving wins its bandwidth budget back).

This kernel runs the whole chain in ONE pass: each grid program holds
one batch row's latent, the two CFG network outputs, and the step noise
resident in VMEM, consumes the row's schedule coefficients from the
stepper's packed (B, len(STEP_COEF_KEYS)) matrix (sample/stepper.py —
the same device-argument contract that keeps t/steps/w out of the
program identity), and writes z_{t−1} once:

  ε̂  = (1+w)·ε̂_cond − w·ε̂_uncond                      (CFG combine)
  x̂₀ = objective⁻¹(z, ε̂)  [optionally cfg-rescaled]    (reconstruction)
  x̂₀ = clip(x̂₀, ±1)                                    (clipping)
  z' = ddpm | ddim update(x̂₀, z) + 1{t>0}·σ·ε'          (update + noise)

Layout: images are flattened to (B, M, 128) lane-aligned slabs (the
update is elementwise, so the image structure is irrelevant inside the
kernel; M pads to the f32 sublane tile on hardware) and the per-row
scalars ride in a lane-padded (B, 128) row-parameter matrix. All
arithmetic is float32 in the exact operation ORDER of the unfused jnp
path (sample/ddpm.py), so off-TPU interpret mode — the same contract as
ops/flash_attention.py: tier-1 runs the identical kernel code path —
is BIT-identical to the unfused sampler at cfg_rescale=0 and within
float tolerance at cfg_rescale>0 (the masked row-std reduction sums in
a different order than jnp.std).

`sampler='dpm++'` is not expressible as a single fused step (2M needs
cross-step x̂₀ history); callers degrade it the same way the stepper
does (first-order = η=0 DDIM) or keep the unfused scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from novel_view_synthesis_3d_tpu.ops import _pallas

_LANES = 128

# Row-parameter columns: STEP_COEF_KEYS order (sample/ddpm.py), then the
# per-row guidance weight. Indices are compile-time constants baked into
# the kernel; the VALUES are device arguments — one (B, 128) transfer
# carries every scalar the step reads, so the compiled program depends
# on the batch shape only (the stepper's program-cache contract).
_COEF_COLS = {
    "logsnr": 0, "sqrt_recip_acp": 1, "sqrt_recipm1_acp": 2,
    "sqrt_acp": 3, "sqrt_1macp": 4, "pm_coef1": 5, "pm_coef2": 6,
    "post_log_var": 7, "acp": 8, "acp_prev": 9, "nonzero": 10,
}
_W_COL = len(_COEF_COLS)


def resolve_fused_step(flag) -> bool:
    """Resolve a diffusion.fused_step config value ('auto' | bool);
    see ops/_pallas.resolve_flag for the shared semantics."""
    return _pallas.resolve_flag(flag, "diffusion.fused_step")


def fits_vmem(row_elems: int) -> bool:
    """True if one row's f32 working slab fits the shared VMEM budget.

    The kernel holds FIVE row slabs (z, ε̂_cond, ε̂_uncond, noise, out)
    plus f32 intermediates; the shared 3 MiB single-slab budget
    (ops/_pallas.py) already prices the working set at ~4× the slab, so
    the guard is on one f32 slab — 256² images (768 KiB) fuse, 512²+
    fall back to the unfused jnp chain."""
    return _pallas.fits_vmem(row_elems * 4)


def unfused_reference_step(z, eps_cond, eps_uncond, noise, coefs, w, *,
                           sampler: str, objective: str, eta: float = 0.0,
                           cfg_rescale: float = 0.0,
                           clip_denoised: bool = True) -> jnp.ndarray:
    """The unfused jnp twin of the kernel: same inputs, same math, same
    operation order, left to XLA to lower as separate HLOs.

    This IS the production unfused path (sample/ddpm.py calls it when
    diffusion.fused_step is off) and the parity reference the tier-1
    tests compare the kernel against bit-for-bit — one implementation,
    so the A/B benchmarks an HLO-fusion difference, never a math one.
    """
    if sampler not in ("ddpm", "ddim"):
        raise ValueError(f"sampler must be 'ddpm' or 'ddim'; "
                         f"got {sampler!r}")
    B = z.shape[0]

    def col(name):
        c = coefs[:, _COEF_COLS[name]].astype(jnp.float32)
        return c.reshape((B,) + (1,) * (z.ndim - 1))

    w_b = jnp.broadcast_to(w, (B,)).astype(jnp.float32).reshape(
        (B,) + (1,) * (z.ndim - 1))
    guided = (1.0 + w_b) * eps_cond - w_b * eps_uncond

    def to_x0(out):
        if objective == "eps":
            return col("sqrt_recip_acp") * z - col("sqrt_recipm1_acp") * out
        if objective == "x0":
            return out
        if objective == "v":
            return col("sqrt_acp") * z - col("sqrt_1macp") * out
        raise ValueError(f"unknown objective {objective!r}")

    x0 = to_x0(guided)
    if cfg_rescale > 0.0:
        x0_c = to_x0(eps_cond)
        axes = tuple(range(1, x0.ndim))
        std_c = jnp.std(x0_c, axis=axes, keepdims=True)
        std_g = jnp.std(x0, axis=axes, keepdims=True)
        rescaled = x0 * (std_c / jnp.maximum(std_g, 1e-8))
        x0 = cfg_rescale * rescaled + (1.0 - cfg_rescale) * x0
    if clip_denoised:
        x0 = jnp.clip(x0, -1.0, 1.0)
    nonzero = col("nonzero")
    if sampler == "ddpm":
        mean = col("pm_coef1") * x0 + col("pm_coef2") * z
        return mean + nonzero * jnp.exp(
            0.5 * col("post_log_var")) * noise
    acp = col("acp")
    acp_prev = col("acp_prev")
    eps_hat = (col("sqrt_recip_acp") * z - x0) / col("sqrt_recipm1_acp")
    sigma = (eta * jnp.sqrt((1.0 - acp_prev) / (1.0 - acp))
             * jnp.sqrt(jnp.maximum(1.0 - acp / acp_prev, 0.0)))
    dir_zt = jnp.sqrt(
        jnp.maximum(1.0 - acp_prev - sigma ** 2, 0.0)) * eps_hat
    return jnp.sqrt(acp_prev) * x0 + dir_zt + nonzero * sigma * noise


def _step_kernel(z_ref, ec_ref, eu_ref, nz_ref, rp_ref, o_ref, *,
                 sampler: str, objective: str, eta: float, phi: float,
                 clip_denoised: bool, n_valid: int):
    """One batch row's fused update, entirely in VMEM.

    z/ec/eu/nz/o refs are (1, M, 128) slabs; rp_ref is the (1, 1, 128)
    row-parameter vector (_COEF_COLS + w). `n_valid` is the true
    (unpadded) element count — static; only the cfg-rescale row-std
    reduction needs it (all other math is elementwise, and padded
    lanes are sliced off by the wrapper)."""
    rp = rp_ref[0, 0]

    def c(name):
        return rp[_COEF_COLS[name]]

    z = z_ref[0].astype(jnp.float32)
    ec = ec_ref[0].astype(jnp.float32)
    eu = eu_ref[0].astype(jnp.float32)
    w = rp[_W_COL]
    # CFG combine — same expression as sample/ddpm._cfg_eps.
    guided = (1.0 + w) * ec - w * eu

    def to_x0(out):
        if objective == "eps":
            return c("sqrt_recip_acp") * z - c("sqrt_recipm1_acp") * out
        if objective == "x0":
            return out
        return c("sqrt_acp") * z - c("sqrt_1macp") * out  # 'v'

    x0 = to_x0(guided)
    if phi > 0.0:
        # cfg-rescale (Lin et al. 2023): match x̂₀'s row std to the
        # conditional prediction's. Masked two-pass moments over the
        # VMEM-resident slab; padded lanes contribute nothing.
        x0_c = to_x0(ec)
        m_idx = jax.lax.broadcasted_iota(jnp.int32, z.shape, 0)
        l_idx = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
        mask = (m_idx * _LANES + l_idx) < n_valid
        inv_n = 1.0 / float(n_valid)

        def row_std(a):
            mean = jnp.sum(jnp.where(mask, a, 0.0)) * inv_n
            var = jnp.sum(jnp.where(mask, jnp.square(a - mean), 0.0)) * inv_n
            return jnp.sqrt(var)

        rescaled = x0 * (row_std(x0_c) / jnp.maximum(row_std(x0), 1e-8))
        x0 = phi * rescaled + (1.0 - phi) * x0
    if clip_denoised:
        x0 = jnp.clip(x0, -1.0, 1.0)

    nonzero = c("nonzero")
    noise = nz_ref[0].astype(jnp.float32)
    if sampler == "ddpm":
        mean = c("pm_coef1") * x0 + c("pm_coef2") * z
        z_next = mean + nonzero * jnp.exp(0.5 * c("post_log_var")) * noise
    else:  # ddim (and the dpm++ first-order fallback at eta=0)
        acp = c("acp")
        acp_prev = c("acp_prev")
        eps_hat = (c("sqrt_recip_acp") * z - x0) / c("sqrt_recipm1_acp")
        sigma = (eta * jnp.sqrt((1.0 - acp_prev) / (1.0 - acp))
                 * jnp.sqrt(jnp.maximum(1.0 - acp / acp_prev, 0.0)))
        dir_zt = jnp.sqrt(
            jnp.maximum(1.0 - acp_prev - sigma ** 2, 0.0)) * eps_hat
        z_next = (jnp.sqrt(acp_prev) * x0 + dir_zt
                  + nonzero * sigma * noise)
    o_ref[0] = z_next.astype(o_ref.dtype)


def fused_denoise_step(z: jnp.ndarray, eps_cond: jnp.ndarray,
                       eps_uncond: jnp.ndarray, noise: jnp.ndarray,
                       coefs: jnp.ndarray, w: jnp.ndarray, *,
                       sampler: str, objective: str, eta: float = 0.0,
                       cfg_rescale: float = 0.0,
                       clip_denoised: bool = True) -> jnp.ndarray:
    """z_{t−1} from one fused Pallas call over the whole ring batch.

    z / eps_cond / eps_uncond / noise: (B, H, W, C) (any (B, ...) image
    layout — the update is elementwise). `coefs` is the (B, K) per-row
    schedule-coefficient matrix in sample/ddpm.STEP_COEF_KEYS order
    (host-gathered by the stepper's ScheduleBank, or built on device
    from the schedule tables by the request sampler); `w` the (B,)
    per-row guidance weight. Returns z_{t−1} in z.dtype.
    """
    if sampler not in ("ddpm", "ddim"):
        raise ValueError(
            f"fused_denoise_step: sampler must be 'ddpm' or 'ddim' "
            f"(dpm++ 2M needs cross-step history); got {sampler!r}")
    if objective not in ("eps", "x0", "v"):
        raise ValueError(f"unknown objective {objective!r}")
    B = z.shape[0]
    L = int(np.prod(z.shape[1:]))
    interpret = _pallas.use_interpret()
    M = -(-L // _LANES)
    if not interpret:
        M = ((M + 7) // 8) * 8  # f32 sublane tile on hardware
    pad = M * _LANES - L

    def slab(a):
        a = a.reshape(B, L)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)))
        return a.reshape(B, M, _LANES)

    K = coefs.shape[-1]
    rp = jnp.zeros((B, _LANES), jnp.float32)
    rp = rp.at[:, :K].set(coefs.astype(jnp.float32))
    rp = rp.at[:, _W_COL].set(
        jnp.broadcast_to(w, (B,)).astype(jnp.float32))

    kernel = functools.partial(
        _step_kernel, sampler=sampler, objective=objective,
        eta=float(eta), phi=float(cfg_rescale),
        clip_denoised=bool(clip_denoised), n_valid=L)
    mem = {} if interpret else {"memory_space": _pallas.VMEM}
    out = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, M, _LANES), lambda i: (i, 0, 0), **mem),
            pl.BlockSpec((1, M, _LANES), lambda i: (i, 0, 0), **mem),
            pl.BlockSpec((1, M, _LANES), lambda i: (i, 0, 0), **mem),
            pl.BlockSpec((1, M, _LANES), lambda i: (i, 0, 0), **mem),
            # (B, 1, 128) view: the trailing (1, 128) block equals the
            # array's last two dims, which the TPU block rule requires
            # (a (1, 128) block over (B, 128) is refused for B > 1).
            pl.BlockSpec((1, 1, _LANES), lambda i: (i, 0, 0), **mem),
        ],
        out_specs=pl.BlockSpec((1, M, _LANES), lambda i: (i, 0, 0), **mem),
        out_shape=jax.ShapeDtypeStruct((B, M, _LANES), z.dtype),
        name="fused_step",
        interpret=interpret,
    )(slab(z), slab(eps_cond), slab(eps_uncond), slab(noise),
      rp.reshape(B, 1, _LANES))
    return out.reshape(B, M * _LANES)[:, :L].reshape(z.shape)

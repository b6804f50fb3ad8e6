"""Host-side schedule bank for the step-level serving scheduler.

The stepper's device program (`sample/ddpm.make_ring_step_fn`) is keyed
on the bucket SHAPE only; everything schedule-dependent — a row's
timestep position, its respaced ladder, its guidance weight — rides as
device arguments. This module owns the host side of that contract: for
each requested sampling-step count it builds (once, cached) the float32
coefficient tables of the respaced schedule, exactly the values
`DiffusionSchedule`'s jitted gathers would produce on device, so a host
`coefs[name][t]` gather feeds the program the same numbers the
whole-request `lax.scan` sampler reads from its on-device tables.

One bank per step count, one program per bucket: a mixed 4-step/256-step
warm sweep compiles NOTHING (asserted by tools/serve_bench.py and
tests/test_stepper.py) — the fix for the PR 3 cache key folding `steps`
into the program identity, which under step-level scheduling would have
recompiled per step-count.

The packed (B, K) matrix is ALSO the fused denoise-step kernel's
row-parameter contract (ops/fused_step.py consumes these exact columns
as device arguments; an import-time assert pins its baked indices to
STEP_COEF_KEYS), so `diffusion.fused_step` changes the program BODY,
never this host-side protocol or the cache-key shape.
"""

from __future__ import annotations

import threading
from typing import Dict

import jax.numpy as jnp
import numpy as np

from novel_view_synthesis_3d_tpu.config import DiffusionConfig
from novel_view_synthesis_3d_tpu.diffusion.schedules import sampling_schedule
from novel_view_synthesis_3d_tpu.sample.ddpm import STEP_COEF_KEYS


class StepBank:
    """Per-step-count coefficient tables (numpy float32, host-resident).

    `n` is the ACTUAL respaced ladder length (`respace` dedups timesteps,
    so n <= requested steps). A request walks t = n-1, n-2, …, 0; its
    per-step device argument is `table[t]`, one packed
    (len(STEP_COEF_KEYS),) row — the stepper stacks one such row per
    slot into the (B, K) matrix `make_ring_step_fn` consumes, so the
    whole ring's schedule state moves host→device in ONE transfer per
    step. `coefs` exposes the same values as named column views.
    """

    __slots__ = ("steps", "n", "table", "coefs")

    def __init__(self, config: DiffusionConfig, steps: int):
        sched = sampling_schedule(config, steps)
        n = sched.num_timesteps
        ts = jnp.arange(n)
        self.steps = int(steps)
        self.n = int(n)
        by_name: Dict[str, np.ndarray] = {
            # logsnr evaluated through the schedule's own jnp path (one
            # vectorized call) so the values match what the scan sampler
            # computes per step on device.
            "logsnr": np.asarray(sched.logsnr(ts), np.float32),
            "sqrt_recip_acp": np.asarray(
                sched.sqrt_recip_alphas_cumprod, np.float32),
            "sqrt_recipm1_acp": np.asarray(
                sched.sqrt_recipm1_alphas_cumprod, np.float32),
            "sqrt_acp": np.asarray(sched.sqrt_alphas_cumprod, np.float32),
            "sqrt_1macp": np.asarray(
                sched.sqrt_one_minus_alphas_cumprod, np.float32),
            "pm_coef1": np.asarray(sched.posterior_mean_coef1, np.float32),
            "pm_coef2": np.asarray(sched.posterior_mean_coef2, np.float32),
            "post_log_var": np.asarray(
                sched.posterior_log_variance_clipped, np.float32),
            "acp": np.asarray(sched.alphas_cumprod, np.float32),
            "acp_prev": np.asarray(sched.alphas_cumprod_prev, np.float32),
            "nonzero": (np.arange(n) > 0).astype(np.float32),
        }
        assert set(by_name) == set(STEP_COEF_KEYS)
        # (n, K) with columns in STEP_COEF_KEYS order — the layout the
        # compiled step program indexes.
        self.table = np.stack([by_name[k] for k in STEP_COEF_KEYS], axis=1)
        self.coefs: Dict[str, np.ndarray] = {
            k: self.table[:, i] for i, k in enumerate(STEP_COEF_KEYS)}


class FrameBank:
    """One trajectory request's DEVICE-RESIDENT frame bank.

    `x`/`R`/`t` are jax device arrays of shape (k_max, H, W, C) /
    (k_max, 3, 3) / (k_max, 3) holding the request's clean conditioning
    views: the source view at seed time, then every generated frame,
    committed in-jit by `sample/ddpm.make_bank_commit_fn` straight from
    the stepper's batched latent — a finished frame joins its own
    conditioning pool without touching the host. The serving stepper
    stacks the ring's banks (a device-side jnp.stack) into the
    (B, k_max, …) tensors `make_ring_step_fn` gathers from; because the
    per-slot arrays are the authoritative copy, a ring rebuild restacks
    bit-identically to what the previous carry held — trajectory rows
    stay ring-composition invariant.

    Overflow policy: SLIDING WINDOW over the most recent `cap` views
    (ring-buffer writes at total % cap, count saturates at cap). Chosen
    over reservoir sampling because it is deterministic — same request,
    same bank content, bit-identical orbit — and recency is what keeps
    long orbits locally consistent; the tradeoff (the original real
    view eventually leaves the window on orbits longer than cap) is
    deliberate and tested (tests/test_trajectory.py). `cap` may be
    smaller than the service-wide array size `k_max`: the program shape
    never changes per request, only the effective window."""

    __slots__ = ("k_max", "cap", "x", "R", "t", "count", "total")

    def __init__(self, k_max: int, cap: int, x0: np.ndarray,
                 R0: np.ndarray, t0: np.ndarray):
        if not 1 <= cap <= k_max:
            raise ValueError(
                f"FrameBank cap={cap} must be in [1, k_max={k_max}]")
        import jax as _jax

        self.k_max = int(k_max)
        self.cap = int(cap)
        H, W, C = np.asarray(x0).shape
        x = np.zeros((k_max, H, W, C), np.float32)
        R = np.zeros((k_max, 3, 3), np.float32)
        t = np.zeros((k_max, 3), np.float32)
        x[0], R[0], t[0] = x0, R0, t0
        # One upload per trajectory — the request's whole conditioning
        # lifetime happens on device after this. device_put COMMITS the
        # arrays, matching the placement of the jitted commit outputs
        # that replace them, so the commit program compiles exactly once
        # per (k_max, H, W) shape.
        self.x, self.R, self.t = _jax.device_put(
            (x, R, t), _jax.devices()[0])
        self.count = 1  # valid entries (saturates at cap)
        self.total = 1  # views ever written (window position source)

    def commit(self, commit_fn, frame_dev, R2: np.ndarray,
               t2: np.ndarray) -> int:
        """Write one finished frame (a device array row of the stepper's
        latent) at the sliding-window position via the jitted commit
        program; returns the position written."""
        pos = self.total % self.cap
        self.x, self.R, self.t = commit_fn(
            self.x, self.R, self.t, frame_dev,
            np.int32(pos), np.asarray(R2, np.float32),
            np.asarray(t2, np.float32))
        self.total += 1
        self.count = min(self.total, self.cap)
        return pos

    @property
    def latest(self) -> int:
        """Position of the most recent entry (stochastic_cond=False)."""
        return (self.total - 1) % self.cap


class ScheduleBank:
    """Thread-safe cache of StepBanks keyed by requested step count.

    Banks are tiny (n × 11 float32 scalars) and immutable, so the cache
    never evicts — a service serving every step count from 1 to
    diffusion.timesteps holds at most that many rows of coefficients.
    """

    def __init__(self, config: DiffusionConfig):
        self._config = config
        self._banks: Dict[int, StepBank] = {}
        self._lock = threading.Lock()
        # Build/hit counters: a bank build is a host-side schedule
        # respace (cheap, but each one is a NEW step count seen — the
        # service summary surfaces them so a bench run can show its
        # step-class mix at a glance).
        self.builds = 0
        self.hits = 0

    def get(self, steps: int) -> StepBank:
        with self._lock:
            bank = self._banks.get(steps)
            if bank is None:
                bank = self._banks[steps] = StepBank(self._config, steps)
                self.builds += 1
            else:
                self.hits += 1
            return bank

    def counters(self) -> dict:
        with self._lock:
            return {"banks_built": self.builds, "bank_hits": self.hits,
                    "step_classes": sorted(self._banks)}

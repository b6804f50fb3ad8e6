"""On-device DDPM ancestral sampler with classifier-free guidance.

TPU-native redesign of /root/reference/sampling.py:116-167, which runs 1000
host-side numpy steps, each dispatching TWO un-jitted Flax forward passes
(cond + uncond CFG). Here the ENTIRE reverse process is one XLA program:

  - `lax.scan` over the (optionally respaced) timestep ladder — no host
    round-trips, no per-step dispatch overhead;
  - CFG computed in a single forward pass on a doubled batch (2B) with
    cond_mask = [1…1, 0…0] instead of two applies — keeps the MXU fed with
    one large matmul stream per step. The unconditional half's pose
    embedding is one vector per frame (the mask zeroes the rays; what is
    left is the pose convolutions' biases), so the samplers that compute
    it once a trajectory (`make_sampler`, `make_request_sampler`) hand it
    to the model at 1 × 1 extent and every FiLM site projects it once a
    frame instead of once a pixel (models/xunet.
    precompute_guidance_pose_embs says when that holds);
  - guidance weight w, respacing (e.g. 256 of 1000 steps) and x̂₀ clipping
    are config fields (reference hardcodes w=3 at sampling.py:134);
  - k>1 stochastic conditioning (3DiM paper §3.2): each denoise step picks a
    random view from the conditioning pool — implemented with a traced
    `randint` + `jnp.take` inside the scan so one compilation serves any
    pool size up to the padded max.

Per-step math (reference sampling.py:119-151):
  ε̂ = (1+w)·ε̂_cond − w·ε̂_uncond
  x̂₀ = clip(√(1/ᾱ_t) z − √(1/ᾱ_t − 1) ε̂, ±1)
  z ← posterior_mean(x̂₀, z, t) + 1{t>0} · exp(½ log σ̃²_t) · ε′

`diffusion.sampler='ddim'` swaps the ancestral update for the DDIM
non-Markovian one (Song et al. 2021) — deterministic at `ddim_eta=0`,
ancestral-variance at `ddim_eta=1`; `diffusion.sampler='dpm++'` uses the
DPM-Solver++(2M) second-order multistep solver (Lu et al. 2022) for
comparable quality at ~8× fewer steps. The reference has only the
1000-step ancestral loop.

The file, top down:
  scan samplers (offline)  make_sampler, make_stochastic_sampler
  request sampler          make_request_sampler: one scan a request
  ring step                make_ring_step_fn: one step, any requests' rows
  the pieces they share    _raw_eps, _step_noise; the serving two: _guided_step
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from novel_view_synthesis_3d_tpu.config import DiffusionConfig
from novel_view_synthesis_3d_tpu.diffusion.schedules import DiffusionSchedule
from novel_view_synthesis_3d_tpu.models import require_family
from novel_view_synthesis_3d_tpu.models.xunet import (
    precompute_cond_feats,
    precompute_pose_embs,
)
from novel_view_synthesis_3d_tpu.ops import fused_step as fused_step_lib


def _raw_eps(model, params, model_batch: dict, precomputed=None):
    """(ε̂_cond, ε̂_uncond) network outputs via one doubled-batch forward.

    `precomputed`: what the model computed of the conditioning outside
    the step, for the DOUBLED (cond+uncond) layout — the mapping of the
    denoiser contract (models/__init__.py), its entries written into the
    batch after the doubling (so they are not concatenated twice) and
    handed through unread. The model's own `precompute(params, cond)`
    gives one; for the X-UNet the samplers may build it themselves:
    `pose_embs`, per level one (2B, F, H/2ˡ, W/2ˡ, emb) array or, rows in
    the same order, the pair (cond (B, F, H/2ˡ, W/2ˡ, emb), uncond (B, F,
    1, 1, emb)) — a 1 × 1 extent stands for one value at every pixel of
    the frame, which a caller may pass only where that is true of the
    rows (models/xunet.precompute_guidance_pose_embs decides it) — and
    `cond_feats`, stem features of the conditioning frame(s)
    (models/xunet.precompute_cond_feats): with them the step program
    convolves only the noised target frame."""
    B = model_batch["z"].shape[0]
    doubled = jax.tree.map(lambda a: jnp.concatenate([a, a], axis=0), model_batch)
    mask = jnp.concatenate([jnp.ones((B,)), jnp.zeros((B,))])
    if precomputed is not None:
        doubled.update(precomputed)
    eps = model.apply({"params": params}, doubled, cond_mask=mask, train=False)
    eps_cond, eps_uncond = jnp.split(eps, 2, axis=0)
    return eps_cond, eps_uncond


def _cfg_eps(model, params, model_batch: dict, w: float, precomputed=None):
    """(guided, conditional) network outputs; CFG combine applied here.
    The conditional output rides along for cfg_rescale."""
    eps_cond, eps_uncond = _raw_eps(model, params, model_batch, precomputed)
    return (1.0 + w) * eps_cond - w * eps_uncond, eps_cond


def _per_row_encode(model, params, cond: dict, mask):
    """Conditioning-branch encode, one row at a time.

    Returns the same `(pose_embs, cond_feats)` a batched
    `precompute_pose_embs` / `precompute_cond_feats` call would, but
    computed as B independent B=1 encodes concatenated back together.
    This is the cond cache's bit-identity keystone: XLA's conv lowering
    is BATCH-SIZE dependent (a row of a B=4 pose encode can differ ~1e-6
    from the same row encoded at B=1, observed on the multi-device CPU
    test mesh), so the cache — which encodes per request at admission,
    per bank entry at frame boundaries, and consumes rows stacked into
    arbitrary ring batches — standardizes EVERY encode on the B=1 row
    computation. A B=1 encode subgraph produces identical bits whether
    it runs standalone (the admission program) or embedded in a larger
    program (the uncached step recomputing it in-jit), so cached and
    uncached rows match bitwise at any batch composition
    (tests/test_cond_cache.py)."""
    B = cond["x"].shape[0]
    pose_rows, feat_rows = [], []
    for i in range(B):
        row = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, i, 1, 0), cond)
        m = jax.lax.dynamic_slice_in_dim(mask, i, 1, 0)
        pose_rows.append(precompute_pose_embs(model, params, row, m))
        feat_rows.append(precompute_cond_feats(model, params, row))
    pose_embs = tuple(
        jnp.concatenate([r[lvl] for r in pose_rows], axis=0)
        for lvl in range(len(pose_rows[0])))
    cond_feats = jnp.concatenate(feat_rows, axis=0)
    return pose_embs, cond_feats


def make_cond_encode_fn(model, *, param_transform=None):
    """Jitted conditioning-branch encode for the serving cond cache.

      encode(params, cond, mask) -> (pose_embs, cond_feats)

    with `pose_embs` a per-level tuple of (B, F, H/2ˡ, W/2ˡ, emb) pose
    embeddings (CFG mask baked in — zeros(B) encodes the uncond half)
    and `cond_feats` the (B, Fc, H, W, ch) stem features of the cond
    frame(s). The service (sample/service.py) calls this ONCE at
    admission — or once per frame-bank encode for trajectories, with B
    = k_max and the current target pose broadcast — and the results
    live device-resident on the ring slot; `make_ring_step_fn` built
    with `cond_cache=True` consumes them as device arguments instead of
    re-running rays → posenc → convs every denoise step. A separate
    jitted callable (like make_bank_commit_fn) so the step-program
    cache's entry accounting is untouched; compiles
    once per (B, H, W) admission shape, never on the warm step path.

    Internally row-unrolled (_per_row_encode) so a k_max-batched bank
    encode yields bit-identical rows to the B=1 admission encode — the
    invariant the steppers' gather/recompute equivalence rests on.

    `param_transform` must match the step program's (the int8 path
    dequantizes in-jit) so cached activations are computed from exactly
    the weights the step program would have used."""
    require_family(
        model.config, "xunet", "sample.ddpm.make_cond_encode_fn (the serving cond cache)",
        "a per-slot latent cache of the conditioning frame in place of pose embeddings and stem features")

    @jax.jit
    def encode(params, cond, mask):
        if param_transform is not None:
            params = param_transform(params)
        return _per_row_encode(model, params, cond, mask)

    return encode


def _assemble_cached_cond(cc3):
    """`_raw_eps`'s `precomputed` mapping — doubled (cond ‖ uncond) pose
    embeddings + stem features — from the cached halves:
    `cc3 = (pose_c, pose_u, feats_c)` with pose_c per-level (B, …),
    pose_u per-level (1, …) — the shared uncond half, broadcast here
    IN-program so guidance pairs store one encode — and feats_c
    (B, Fc, H, W, ch), which is CFG-mask-independent (only the pose
    embedding is zeroed) so the same tensor serves both halves. Pinned
    with optimization_barrier: the forward must see materialized inputs,
    exactly like the uncached program's in-jit conv outputs, so XLA
    cannot fuse the assembly into the UNet and drift the two programs a
    ulp apart (the barrier note above _ring_update_spec)."""
    pose_c, pose_u, feats_c = cc3
    pose_embs = tuple(
        jnp.concatenate([pc, jnp.broadcast_to(pu, pc.shape)], axis=0)
        for pc, pu in zip(pose_c, pose_u))
    cond_feats = jnp.concatenate([feats_c, feats_c], axis=0)
    pose_embs, cond_feats = jax.lax.optimization_barrier(
        (pose_embs, cond_feats))
    return {"pose_embs": pose_embs, "cond_feats": cond_feats}


def _step_noise(key, z):
    """N(0,1) noise for one reverse step.

    `key` is either a single PRNG key (one stream for the whole batch —
    the training-side samplers' historical behavior, bit-preserved) or a
    (B, 2) stacked key vector: one independent stream PER SAMPLE, which
    makes row i of a batched reverse process depend only on (cond_i,
    key_i) — the property `make_request_sampler` needs so the serving
    micro-batcher's padding and batch composition cannot change any
    request's image."""
    if key.ndim == 2:
        return jax.vmap(lambda k: jax.random.normal(k, z.shape[1:]))(key)
    return jax.random.normal(key, z.shape)


def _posterior_sample(schedule: DiffusionSchedule, x0, z, t, key):
    """Draw z_{t−1} ~ q(z_{t−1}|z_t, x̂₀); noiseless at t=0."""
    mean, _, log_var = schedule.q_posterior(x0, z, t)
    noise = _step_noise(key, z)
    nonzero = jnp.reshape(  # no noise at the final step; scalar or (B,) t
        (t > 0).astype(z.dtype), jnp.shape(t) + (1,) * (z.ndim - jnp.ndim(t)))
    return mean + nonzero * jnp.exp(0.5 * log_var) * noise


def _make_x0_fn(schedule: DiffusionSchedule, objective: str):
    """x̂₀ from the network output under the configured objective."""
    if objective == "eps":
        return schedule.predict_start_from_noise
    if objective == "x0":
        return lambda z, t, out: out
    if objective == "v":
        return schedule.predict_start_from_v
    raise ValueError(f"unknown objective {objective!r}")


def _make_update(schedule: DiffusionSchedule, config: DiffusionConfig,
                 memoryless: bool = False):
    """Bind the configured reverse-process update (ddpm | ddim | dpm++),
    converting the network output (eps | x0 | v per diffusion.objective) to
    x̂₀ first. Returns `(update, init_aux)`:

      update(z, t, outs, key, aux) -> (z_next, aux_next)
      init_aux(z0) -> initial per-trajectory solver state

    `aux` is empty for the memoryless samplers (ddpm, ddim) and carries the
    previous step's x̂₀ for the multistep dpm++ solver (DPM-Solver++(2M),
    Lu et al. 2022) — the scan carry threads it across steps.

    `memoryless=True` declares that the caller changes the conditioning
    between steps (stochastic conditioning re-draws the pool view every
    denoise step), so consecutive x̂₀ predictions are NOT samples of one
    ODE trajectory: the 2M extrapolation would read the conditioning jump
    as curvature and deterministically amplify it. dpm++ then degrades to
    its first-order update (= η=0 DDIM); ddpm/ddim are unaffected.

    CFG is applied in the network's output space before this conversion
    (guidance in eps-space and v-space coincide up to the linear maps here).
    `update` takes the (guided, conditional) output pair from _cfg_eps: the
    conditional branch feeds cfg_rescale (Lin et al. 2023) — after guidance,
    x̂₀ is rescaled toward the conditional prediction's per-sample std and
    blended with weight φ = config.cfg_rescale (0 = off, reference behavior).
    """
    x0_fn = _make_x0_fn(schedule, config.objective)
    clip_denoised = config.clip_denoised
    phi = config.cfg_rescale
    if not 0.0 <= phi <= 1.0:
        raise ValueError(f"cfg_rescale must be in [0, 1], got {phi}")

    def to_x0(z, t, outs):
        guided, cond_out = outs
        x0 = x0_fn(z, t, guided)
        if phi > 0.0:
            x0_c = x0_fn(z, t, cond_out)
            axes = tuple(range(1, x0.ndim))
            std_c = jnp.std(x0_c, axis=axes, keepdims=True)
            std_g = jnp.std(x0, axis=axes, keepdims=True)
            rescaled = x0 * (std_c / jnp.maximum(std_g, 1e-8))
            x0 = phi * rescaled + (1.0 - phi) * x0
        if clip_denoised:
            x0 = jnp.clip(x0, -1.0, 1.0)
        return x0

    def no_aux(z0):
        return ()

    if config.sampler == "ddim":
        eta = config.ddim_eta

        def update(z, t, outs, key, aux):
            noise = _step_noise(key, z)
            return schedule.ddim_step(to_x0(z, t, outs), z, t, noise, eta), aux

        return update, no_aux
    if config.sampler == "ddpm":

        def update(z, t, outs, key, aux):
            return _posterior_sample(schedule, to_x0(z, t, outs), z, t,
                                     key), aux

        return update, no_aux
    if config.sampler == "dpm++":
        if memoryless:

            def update(z, t, outs, key, aux):
                return schedule.ddim_step(to_x0(z, t, outs), z, t,
                                          0.0, 0.0), aux

            return update, no_aux

        def update(z, t, outs, key, aux):
            x0 = to_x0(z, t, outs)
            first = t >= schedule.num_timesteps - 1
            return schedule.dpmpp_2m_step(x0, aux, z, t, first), x0

        # The first step is first-order (no history); the zeros are never
        # read, they just give the scan carry a stable structure.
        return update, jnp.zeros_like
    raise ValueError(f"unknown sampler {config.sampler!r}")


def make_sampler(model, schedule: DiffusionSchedule, config: DiffusionConfig,
                 trajectory_every: int = 0,
                 trajectory_views: Optional[int] = None):
    """Jitted sampler for a fixed conditioning layout (k = model's Fc).

    sample(params, key, cond) -> (B, H, W, 3) images in [-1, 1], where cond
    holds x, R1, t1, R2, t2, K (the clean conditioning view(s) + poses).

    `trajectory_every=k` (k > 0) makes the sampler ALSO return the
    partially-denoised z after every k-th reverse step:
    sample(...) -> (final, trajectory) with trajectory
    (n_frames, B', H, W, 3) and final[:B'] == trajectory[-1], where
    n_frames = ceil(num_timesteps / k). k need not divide num_timesteps:
    the T//k full chunks run through a nested scan and any remainder steps
    run as one flat scan whose end state is appended as the last frame, so
    the final image is always captured. The RNG stream — and therefore the
    final image — is bit-identical to the flat sampler in every case.
    `trajectory_views` limits B' to the first n batch entries so a consumer
    that only wants one view's denoising film doesn't buy the whole batch's
    trajectory in HBM (B' = B when None).
    """
    w = config.guidance_weight
    update, init_aux = _make_update(schedule, config)
    T = schedule.num_timesteps
    if trajectory_every < 0 or trajectory_every > T:
        raise ValueError(
            f"trajectory_every must be in [0, {T}]; got {trajectory_every}")

    def body(cond, params, precomputed, carry, t):
        z, key, aux = carry
        key, k_step = jax.random.split(key)
        batch = dict(cond, z=z,
                     logsnr=jnp.full((z.shape[0],), schedule.logsnr(t)))
        outs = _cfg_eps(model, params, batch, w, precomputed=precomputed)
        z, aux = update(z, t, outs, k_step, aux)
        return (z, key, aux), None

    @jax.jit
    @jax.named_scope("lk.update")
    def sample(params, key, cond: dict) -> jnp.ndarray:
        z_shape = cond["x"].shape[:1] + cond["x"].shape[-3:]  # (B, H, W, 3)
        key, k_init = jax.random.split(key)
        z0 = jax.random.normal(k_init, z_shape)
        ts = jnp.arange(T - 1, -1, -1)
        # The conditioning is fixed for the whole reverse process: what
        # the model can compute of it ONCE (the X-UNet's pose embeddings,
        # the token denoiser's latent cache of the conditioning frame)
        # comes through this one seam instead of every scan step.
        precomputed = model.precompute(params, cond)
        step = partial(body, cond, params, precomputed)
        carry0 = (z0, key, init_aux(z0))

        if not trajectory_every:
            (z, _, _), _ = jax.lax.scan(step, carry0, ts)
            return z

        def outer(carry, ts_chunk):
            carry, _ = jax.lax.scan(step, carry, ts_chunk)
            z = carry[0]
            return carry, (z if trajectory_views is None
                           else z[:trajectory_views])

        n_chunks, rem = divmod(T, trajectory_every)
        chunks = ts[:n_chunks * trajectory_every].reshape(
            n_chunks, trajectory_every)
        carry, traj = jax.lax.scan(outer, carry0, chunks)
        if rem:
            carry, _ = jax.lax.scan(step, carry, ts[-rem:])
            z = carry[0]
            last = z if trajectory_views is None else z[:trajectory_views]
            traj = jnp.concatenate([traj, last[None]], axis=0)
        return carry[0], traj

    return sample


# Why the serving samplers pin the update's inputs with
# jax.lax.optimization_barrier before the per-step math: XLA is free to
# fuse the UNet epilogue / RNG / gather producers INTO the elementwise
# update chain, and its FMA-contraction choices there differ between
# program shapes — which would put the fused and unfused programs (and
# the two schedulers) a ulp apart before the update math even runs. The
# barrier makes every producer subgraph identical across programs, so
# one shared implementation (ops/fused_step.py: the Pallas kernel or
# its unfused reference twin) yields BIT-identical samplers — asserted
# in tier-1 (tests/test_fused_step.py). The Pallas call is a natural
# materialization boundary anyway; the unfused side forgoes only
# epilogue fusions whose producers materialize regardless. The
# training-side `make_sampler` is untouched (golden bit-compat).


def _refuse_fused_multistep(config: DiffusionConfig):
    """dpm++ 2M needs cross-step x̂₀ history, which a single fused step
    cannot express: an explicit diffusion.fused_step=True is a loud error
    (config.validate catches it earlier with the same message class),
    while 'auto' silently keeps the unfused multistep scan."""
    fused_step_lib.resolve_fused_step(config.fused_step)  # a typo'd flag
    if config.sampler == "dpm++" and config.fused_step is True:
        raise ValueError(
            "diffusion.fused_step=True requires sampler 'ddpm' or "
            "'ddim' — the dpm++ 2M multistep update carries x̂₀ "
            "history across steps and is not expressible as one "
            "fused step (use 'auto' to fuse where possible)")


def _ring_update_spec(config: DiffusionConfig):
    """`(use_fused, kwargs)` of the serving samplers' shared per-step
    update (ops/fused_step.py), validated: whether diffusion.fused_step
    asks for the kernel, and the keywords either twin is called with.

    `sampler='dpm++'` resolves to its first-order (history-free) update,
    = η=0 ddim, which the kernel serves like any ddim: ring membership
    changes between steps, so multistep history is invalid there, the
    same rule `_make_update` applies to stochastic conditioning (serve
    with serve.scheduler='request' for exact 2M, which never comes
    here)."""
    phi = config.cfg_rescale
    if not 0.0 <= phi <= 1.0:
        raise ValueError(f"cfg_rescale must be in [0, 1], got {phi}")
    if config.objective not in ("eps", "x0", "v"):
        raise ValueError(f"unknown objective {config.objective!r}")
    sampler = config.sampler
    eta = config.ddim_eta if sampler == "ddim" else 0.0
    if sampler == "dpm++":
        sampler = "ddim"
    if sampler not in ("ddpm", "ddim"):
        raise ValueError(f"unknown sampler {config.sampler!r}")
    return fused_step_lib.resolve_fused_step(config.fused_step), dict(
        sampler=sampler, objective=config.objective, eta=eta,
        cfg_rescale=phi, clip_denoised=config.clip_denoised)


def _guided_step(spec, z, ec, eu, noise, coefs, w):
    """z_next of one reverse step from the forward's (ε̂_cond, ε̂_uncond):
    CFG combine → x̂₀ + clip → ddpm/ddim update → noise add, by the fused
    Pallas kernel or its unfused reference twin (`spec`:
    _ring_update_spec) — one HBM pass or ~a dozen elementwise HLOs,
    identical math and RNG stream. `coefs` is the (B,
    len(STEP_COEF_KEYS)) matrix and `w` the (B,) guidance weights."""
    use_fused, kwargs = spec
    # Pinned inputs + one shared implementation: the fused and unfused
    # programs are bit-identical (the barrier note above).
    pinned = jax.lax.optimization_barrier((z, ec, eu, noise, coefs, w))
    # Per-shape fusion decision at trace time: rows past the VMEM slab
    # budget keep the unfused chain (same policy as the fused GroupNorm's
    # over-VMEM fallback).
    fused = use_fused and fused_step_lib.fits_vmem(np.prod(z.shape[1:]))
    step_impl = (fused_step_lib.fused_denoise_step if fused
                 else fused_step_lib.unfused_reference_step)
    return step_impl(*pinned, **kwargs)


def _sched_coef_row(schedule: DiffusionSchedule, t) -> jnp.ndarray:
    """(len(STEP_COEF_KEYS),) coefficient vector at traced timestep t.

    Device-side gather of exactly the values the stepper's host-side
    StepBank packs per row (sample/stepper.py) — the fused kernel
    consumes one contract whether coefficients arrive from the host
    bank (slot stepper) or from these on-device tables (scan sampler)."""
    return jnp.stack([
        schedule.logsnr(t),
        jnp.take(schedule.sqrt_recip_alphas_cumprod, t),
        jnp.take(schedule.sqrt_recipm1_alphas_cumprod, t),
        jnp.take(schedule.sqrt_alphas_cumprod, t),
        jnp.take(schedule.sqrt_one_minus_alphas_cumprod, t),
        jnp.take(schedule.posterior_mean_coef1, t),
        jnp.take(schedule.posterior_mean_coef2, t),
        jnp.take(schedule.posterior_log_variance_clipped, t),
        jnp.take(schedule.alphas_cumprod, t),
        jnp.take(schedule.alphas_cumprod_prev, t),
        (t > 0).astype(jnp.float32),
    ])


def make_request_sampler(model, schedule: DiffusionSchedule,
                         config: DiffusionConfig, *,
                         param_transform=None):
    """Per-sample-keyed sampler for the serving micro-batcher
    (sample/service.py).

    sample(params, keys, cond) -> (B, H, W, 3) with keys a (B, 2) stack
    of PRNG keys: row i's init noise and every per-step draw come from
    keys[i]'s stream ONLY, so the output row depends on (cond row i,
    keys[i]) alone — coalescing a request into any bucket, alongside any
    co-riders or pad rows, reproduces its solo image (CPU/TPU row math is
    per-sample; see test_serve.py padding-invariance tests). The
    training-side `make_sampler` keeps its single-key whole-batch stream
    untouched (bit-compatibility with every golden/e2e test).

    The model forward, CFG doubling, and pose-embedding hoist are shared
    with `make_sampler`; only the RNG layout differs.

    `diffusion.fused_step` routes the per-step update (CFG combine, x̂₀
    reconstruction + clip, ddpm/ddim update, noise add) through the
    fused Pallas kernel (ops/fused_step.py) — identical RNG stream and
    operation order, one HBM pass instead of ~a dozen elementwise HLOs.
    `param_transform` (optional) is applied to `params` INSIDE the jit —
    the int8 serving path passes the dequantizer here so weights rest in
    HBM quantized (sample/precision.py).
    """
    require_family(
        model.config, "xunet", "sample.ddpm.make_request_sampler",
        "it calls the model's precompute seam as make_sampler does, but row independence under padding and co-riders has only been held to the X-UNet (tests/test_serve.py)")
    w = config.guidance_weight
    T = schedule.num_timesteps
    # ddpm/ddim run the shared per-step implementation (_guided_step —
    # the same code the ring step runs, so the two schedulers stay
    # bit-aligned); dpm++ keeps the _make_update multistep scan (never
    # fused).
    shared_impl = config.sampler in ("ddpm", "ddim")
    if shared_impl:
        spec, init_aux = _ring_update_spec(config), lambda z0: ()
    else:
        _refuse_fused_multistep(config)
        update, init_aux = _make_update(schedule, config)

    @jax.jit
    @jax.named_scope("lk.update")
    def sample(params, keys, cond: dict) -> jnp.ndarray:
        if param_transform is not None:
            params = param_transform(params)
        z_shape = cond["x"].shape[-3:]  # (H, W, 3)
        both = jax.vmap(jax.random.split)(keys)       # (B, 2, 2)
        keys0, k_init = both[:, 0], both[:, 1]
        z0 = jax.vmap(lambda k: jax.random.normal(k, z_shape))(k_init)
        ts = jnp.arange(T - 1, -1, -1)
        precomputed = model.precompute(params, cond)
        B = keys.shape[0]

        def body(carry, t):
            z, ks, aux = carry
            both = jax.vmap(jax.random.split)(ks)
            ks, k_step = both[:, 0], both[:, 1]
            batch = dict(cond, z=z,
                         logsnr=jnp.full((z.shape[0],), schedule.logsnr(t)))
            if shared_impl:
                ec, eu = _raw_eps(model, params, batch, precomputed)
                # k_step is (B, 2): per-sample noise streams.
                noise = _step_noise(k_step, z)
                coefs = jnp.broadcast_to(_sched_coef_row(schedule, t),
                                         (B, len(STEP_COEF_KEYS)))
                wvec = jnp.full((B,), w, jnp.float32)
                z = _guided_step(spec, z, ec, eu, noise, coefs, wvec)
                return (z, ks, aux), None
            outs = _cfg_eps(model, params, batch, w, precomputed)
            z, aux = update(z, t, outs, k_step, aux)
            return (z, ks, aux), None

        (z, _, _), _ = jax.lax.scan(body, (z0, keys0, init_aux(z0)), ts)
        return z

    return sample


# Per-row schedule coefficients the slot stepper feeds as ONE DEVICE
# ARGUMENT — a (B, len(STEP_COEF_KEYS)) float32 matrix, column i holding
# STEP_COEF_KEYS[i] — covering every table value the per-step update math
# reads, so the compiled program depends on the bucket SHAPE only, never on
# a row's step count, schedule position, or guidance weight. One packed
# matrix instead of a dict of scalars keeps the per-step host→device
# traffic to a single transfer (the stepper uploads fresh coefficients
# EVERY step — this is its hottest host-side path). The bank that gathers
# rows per request lives in sample/stepper.py.
STEP_COEF_KEYS = (
    "logsnr",             # network conditioning at the row's original t
    "sqrt_recip_acp",     # √(1/ᾱ_t)   (eps→x0, and ddim's ε̂ inversion)
    "sqrt_recipm1_acp",   # √(1/ᾱ_t−1)
    "sqrt_acp",           # √ᾱ_t       (v→x0)
    "sqrt_1macp",         # √(1−ᾱ_t)
    "pm_coef1",           # ddpm posterior mean coefficients
    "pm_coef2",
    "post_log_var",       # ddpm clipped posterior log-variance
    "acp",                # ᾱ_t, ᾱ_{t−1} (ddim update)
    "acp_prev",
    "nonzero",            # 1.0 while t > 0 (no noise at the final step)
)

# The fused kernel bakes these column indices in (ops/fused_step.py);
# the two layouts must never drift.
assert tuple(fused_step_lib._COEF_COLS) == STEP_COEF_KEYS
assert fused_step_lib._W_COL == len(STEP_COEF_KEYS)


def _ring_head(z, keys, first, bank_state, stochastic):
    """`(z, keys_next, k_step, pick)`: what one ring step does before its
    conditioning — the RNG layout of the ring.

    Rows with first=True draw their init noise here, reproducing
    `make_request_sampler`'s pre-scan key split exactly: split(key) →
    (carry, k_init), z₀ = N(0,1) from k_init. Every row then splits its
    carry into (next_carry, k_step) exactly like that sampler's scan
    body. `bank_state` is None on a bank-free ring (pick None), else the
    (B, 2) [count, latest], and pick is (traj, idx): which rows are
    trajectory rows (count > 0) and the bank entry each conditions on —
    `latest`, or with `stochastic` a uniform draw over the first `count`
    entries from a THIRD stream. Single-shot rows must consume the exact
    two-way split of the bank-free program, so both splits are computed
    and selected per row — never assume split(k, 3)[:2] == split(k, 2)."""
    if bank_state is not None:
        count, latest = bank_state[:, 0], bank_state[:, 1]
        traj = count > 0
    both = jax.vmap(jax.random.split)(keys)
    k_carry, k_init = both[:, 0], both[:, 1]
    z0 = _step_noise(k_init, z)
    fmask = first.reshape(first.shape + (1,) * (z.ndim - 1))
    z = jnp.where(fmask, z0.astype(z.dtype), z)
    keys = jnp.where(first[:, None], k_carry, keys)
    two = jax.vmap(jax.random.split)(keys)
    if bank_state is None:
        return z, two[:, 0], two[:, 1], None
    if not stochastic:
        return z, two[:, 0], two[:, 1], (traj, latest)
    three = jax.vmap(lambda k: jax.random.split(k, 3))(keys)
    keys_next = jnp.where(traj[:, None], three[:, 0], two[:, 0])
    k_step = jnp.where(traj[:, None], three[:, 1], two[:, 1])
    idx = jax.vmap(
        lambda k, n: jax.random.randint(k, (), 0, n))(
            three[:, 2], jnp.maximum(count, 1))
    return z, keys_next, k_step, (traj, idx)


# The four conditioning sources of the ring step — the only place its
# four programs differ. Each takes (model, params, cond, pick, *what the
# program is handed past `w`) with pick = (traj, idx) on a bank-enabled
# ring, and returns the batch's conditioning entries and `_raw_eps`'s
# `precomputed` mapping. Every encode everywhere is the B=1 row
# computation (_per_row_encode) behind the same _assemble_cached_cond
# barrier, so the forward sees bit-identical inputs and identical traced
# structure whichever source fed it: encoding in the program matches the
# admission encode of the cache, and encoding a gathered bank view
# commutes bitwise with gathering bank entries that were row-encoded at
# the frame boundary (tests/test_cond_cache.py pins array_equal; a
# batched encode would drift co-riding rows ~1e-6).


def _cond_from_request(model, params, cond, pick):
    B = cond["x"].shape[0]
    pose_c, feats_c = _per_row_encode(model, params, cond, jnp.ones((B,)))
    pose_u = precompute_pose_embs(
        model, params, jax.tree.map(lambda a: a[:1], cond), jnp.zeros((1,)))
    return cond, _assemble_cached_cond((pose_c, pose_u, feats_c))


def _cond_from_slot_cache(model, params, cond, pick, cc):
    return cond, _assemble_cached_cond(cc)


def _pick_rows(bank, idx):
    """Row i's entry idx[i] of a (B, k_max, …) bank."""
    return jax.vmap(lambda b, i: jax.lax.dynamic_index_in_dim(
        b, i, 0, keepdims=False))(bank, idx)


def _cond_from_bank(model, params, cond, pick, R2, t2, bank_x, bank_R,
                    bank_t, bank_state):
    traj, idx = pick
    B = traj.shape[0]
    # Bank gather, then per-row select against the request cond.
    x_eff = jnp.where(traj.reshape((B, 1, 1, 1)),
                      _pick_rows(bank_x, idx), cond["x"])
    R1_eff = jnp.where(traj.reshape((B, 1, 1)),
                       _pick_rows(bank_R, idx), cond["R1"])
    t1_eff = jnp.where(traj.reshape((B, 1)),
                       _pick_rows(bank_t, idx), cond["t1"])
    # Pin the effective conditioning: the forward must see materialized
    # inputs, exactly like the bank-free program's cond PARAMETERS, so XLA
    # cannot fuse the gather/select producers into the UNet and drift
    # single-shot rows a ulp apart (the same rationale as the update
    # barrier).
    x_eff, R1_eff, t1_eff, R2, t2 = jax.lax.optimization_barrier(
        (x_eff, R1_eff, t1_eff, R2, t2))
    eff = {"x": x_eff, "R1": R1_eff, "t1": t1_eff,
           "R2": R2, "t2": t2, "K": cond["K"]}
    return _cond_from_request(model, params, eff, pick)


def _cond_from_bank_cache(model, params, cond, pick, R2, t2, bank_x, bank_R,
                          bank_t, bank_state, cc):
    # The same per-row gather/select, lifted from pixels to cached
    # activations; single-shot rows select the request-level cache. The
    # raw bank_x/bank_R/bank_t (and R2/t2, baked into the encodes) are
    # never read — kept for the commit path's carry structure only, XLA
    # drops them.
    traj, idx = pick
    pose_c, pose_u, feats_c, bank_pose, bank_feats = cc
    tmask = traj.reshape((traj.shape[0], 1, 1, 1, 1))
    sel_pose = tuple(
        jnp.where(tmask, _pick_rows(bp, idx), pc)
        for bp, pc in zip(bank_pose, pose_c))
    sel_feats = jnp.where(tmask, _pick_rows(bank_feats, idx), feats_c)
    return cond, _assemble_cached_cond((sel_pose, pose_u, sel_feats))


# [bank-enabled][cond_cache]
_COND_SOURCES = ((_cond_from_request, _cond_from_slot_cache),
                 (_cond_from_bank, _cond_from_bank_cache))


def make_ring_step_fn(model, config: DiffusionConfig, *, k_max=0,
                      cond_cache=False, param_transform=None):
    """ONE reverse-process step over a ring batch with per-row schedules:
    the serving stepper's device program (sample/service.py;
    docs/DESIGN.md "Continuous batching & distillation" and "Trajectory
    serving & stochastic conditioning").

      step(params, z, keys, first, cond, coefs, w, *bank, *cache)
        -> (z_next, keys_next, finite)

    with z (B, H, W, 3), keys a (B, 2) per-row PRNG carry, `first` a (B,)
    bool marking rows entering the ring THIS step, `coefs` a
    (B, len(STEP_COEF_KEYS)) float32 matrix (every schedule table value
    the update reads, gathered on host per row — one packed transfer per
    step), and w the (B,) per-row guidance weight. Rows are fully
    independent: row i's output depends on (z_i, keys_i, cond_i, coefs_i,
    w_i) alone, so a request's image is bit-identical whether it steps
    solo or interleaved with any co-riders joining/leaving the ring — the
    ring-composition invariance the service asserts
    (tests/test_stepper.py).

    RNG layout (_ring_head): entering rows draw their init noise here,
    then every row splits its carry — so a request stepped t times
    through this program sees the same RNG stream (and the same per-step
    math, _guided_step) as the whole-request sampler.

    The compiled program depends on the BUCKET SHAPE only: a mixed
    4-step/256-step batch, or mixed guidance weights, poses and bank
    fills, run one program — they are device arguments (the
    program-cache key contract, docs/DESIGN.md), and mixed single-shot +
    trajectory traffic compiles nothing after warmup. `k_max` and
    `cond_cache` are service constants (serve.k_max, serve.cond_cache)
    and part of the program; `param_transform` (optional) is applied to
    `params` INSIDE the jit — the int8 serving path passes the
    dequantizer here (sample/precision.py). dpm++ and
    `diffusion.fused_step`: _ring_update_spec.

    `finite` is a (B,) bool — a device-side all-reduce of
    isfinite(z_next) per row, the in-ring anomaly mask the service's
    quarantine consumes (docs/DESIGN.md "Serving survivability"); vital
    with a bank: a non-finite frame committed to it would poison every
    later frame's stochastic conditioning. It is computed FROM z_next and
    never feeds back into the update, so clean-path z/keys bits are
    untouched, and an extra output does not change the program-cache
    identity.

    `k_max > 0` gives every row a FRAME BANK:

      *bank = R2, t2, bank_x, bank_R, bank_t, bank_state

    `bank_x` (B, k_max, H, W, C) holds each row's clean conditioning
    frames (the request's source view plus every frame it has generated
    so far, committed in-jit by `make_bank_commit_fn`), `bank_R`/`bank_t`
    their poses, and `bank_state` a (B, 2) int32 of [count, latest]. Rows
    with count > 0 are TRAJECTORY rows: their conditioning view is drawn
    from the bank — uniformly over the first `count` entries with a third
    per-row PRNG split when `diffusion.stochastic_cond` is True (the 3DiM
    protocol), or the `latest` entry when False — and their target pose
    comes from the per-step (B, 3, 3)/(B, 3) `R2`/`t2` (the host uploads
    the CURRENT frame's pose each step, like the schedule coefficients,
    so advancing to the next orbit pose never rebuilds the ring). Rows
    with count == 0 are SINGLE-SHOT rows: they read their conditioning
    from `cond` and consume the IDENTICAL per-row RNG stream, so a
    single-shot request is BIT-identical whether it rides this program
    next to trajectory rows or the bank-free program of a service with
    serve.k_max=0 (tests/test_trajectory.py). The gather happens BEFORE
    the forward, so the update after it is the bank-free one.

    `cond_cache=True` hands the conditioning branch in as device
    arguments, stacked by the service from its ring slots:

      *cache = (cc,)
      cc = (pose_c, pose_u, feats_c[, bank_pose, bank_feats])

    the admission-time encode (make_cond_encode_fn): per-level (B, …)
    cond-half pose embeddings, the shared (1, …) uncond half, the
    (B, Fc, H, W, ch) cond stem features and, with a bank, per-level
    (B, k_max, …) bank-entry pose embeddings and (B, k_max, Fc, H, W, ch)
    bank-entry stem features — every bank entry encoded against the row's
    CURRENT target pose at the frame boundary (sample/service.py
    re-encodes when the target advances, exactly when it restacks
    R2/t2). The doubled CFG layout is assembled in-program
    (_assemble_cached_cond) and the UNet convolves only the noised target
    frame (models/xunet.py `cond_feats` seam); everything else — RNG
    stream, update math, anomaly mask — is the uncached program's, and
    the two produce BIT-identical rows (tests/test_cond_cache.py)."""
    require_family(
        model.config, "xunet", "sample.ddpm.make_ring_step_fn (the step ring)",
        "a latent cache per ring slot, written when a request is admitted and read by every step of its rows, and one per slot and frame of the bank for trajectories, re-made when the step's conditioning frame changes")
    stochastic = config.stochastic_cond
    if k_max and stochastic not in (True, False):
        raise ValueError(
            f"diffusion.stochastic_cond={stochastic!r} must be True "
            "(random bank view per step) or False (most recent frame)")
    spec = _ring_update_spec(config)
    source = _COND_SOURCES[k_max > 0][bool(cond_cache)]
    logsnr_col = STEP_COEF_KEYS.index("logsnr")

    def step(params, z, keys, first, cond, coefs, w, *rest):
        if param_transform is not None:
            params = param_transform(params)
        # Past w the program is handed R2, t2, bank_x, bank_R, bank_t,
        # bank_state where it has a bank, then cc where it has a cache.
        z, keys_next, k_step, pick = _ring_head(
            z, keys, first, rest[5] if k_max else None, stochastic)
        batch_cond, precomputed = source(model, params, cond, pick, *rest)
        batch = dict(batch_cond, z=z, logsnr=coefs[:, logsnr_col])
        ec, eu = _raw_eps(model, params, batch, precomputed)
        z_next = _guided_step(spec, z, ec, eu, _step_noise(k_step, z),
                              coefs, w)
        # Per-row anomaly mask: reduced on device so the host learns
        # "row i went non-finite" from a (B,) bool instead of pulling
        # the latent back every step. Read-only over z_next.
        finite = jnp.all(jnp.isfinite(z_next).reshape(len(z), -1), axis=1)
        return z_next, keys_next, finite

    # The program's name, in its lowered text and in a device trace
    # (jit_step, jit_step_cached), is the one each program has had.
    step.__name__ = "step_cached" if cond_cache else "step"
    return jax.jit(jax.named_scope("lk.update")(step))


def make_bank_commit_fn():
    """In-jit frame-bank writeback for the trajectory stepper.

      commit(bank_x, bank_R, bank_t, frame, pos, R2, t2)
        -> (bank_x, bank_R, bank_t)

    Writes `frame` — the device-resident row of the stepper latent that
    just finished denoising — into position `pos` of ONE slot's bank
    ((k_max, H, W, C) arrays, sample/stepper.FrameBank), with the pose
    it was generated at: the finished frame joins its own conditioning
    pool WITHOUT a host round-trip, so the next frame's stochastic
    conditioning reads it straight from HBM. `pos` is a traced scalar —
    one compiled program per (k_max, H, W) shape serves every slot,
    every ring bucket, and every sliding-window position."""

    @jax.jit
    @jax.named_scope("lk.update")
    def commit(bank_x, bank_R, bank_t, frame, pos, R2, t2):
        bank_x = jax.lax.dynamic_update_slice(
            bank_x, frame[None].astype(bank_x.dtype), (pos, 0, 0, 0))
        bank_R = jax.lax.dynamic_update_slice(
            bank_R, R2[None].astype(bank_R.dtype), (pos, 0, 0))
        bank_t = jax.lax.dynamic_update_slice(
            bank_t, t2[None].astype(bank_t.dtype), (pos, 0))
        return bank_x, bank_R, bank_t

    return commit


def make_stochastic_sampler(model, schedule: DiffusionSchedule,
                            config: DiffusionConfig, max_pool: int,
                            precompute_pose: Optional[bool] = None):
    """Sampler with 3DiM stochastic conditioning over a view pool.

    cond pool: x (B, max_pool, H, W, 3), R1 (B, max_pool, 3, 3),
    t1 (B, max_pool, 3); `num_views` (traced scalar ≤ max_pool) bounds the
    per-step random choice, so one compiled program serves a growing pool
    (autoregressive generation never recompiles).

    `precompute_pose`: hoist the pose-conditioning path out of the scan —
    embeddings for every (pool view, target) pair are computed once and
    indexed per step, and the unconditional CFG half is computed once
    through the real masked pipeline (conv biases and learned pos/ref
    embeddings survive the mask, so it is NOT zeros). Identical math to
    the in-loop path; costs max_pool× pose-embedding HBM residency for the
    whole trajectory, so None (default) auto-disables when that exceeds
    ~512 MB (e.g. 256px paper-scale pools) and falls back to in-loop
    computation.
    """
    require_family(
        model.config, "xunet", "sample.ddpm.make_stochastic_sampler",
        "a latent cache per pool view, gathered by the step's drawn view index")
    w = config.guidance_weight
    # memoryless: the conditioning view is re-drawn every denoise step, so
    # multistep solver history is invalid here (see _make_update).
    update, init_aux = _make_update(schedule, config, memoryless=True)

    @partial(jax.jit, static_argnames=())
    @jax.named_scope("lk.update")
    def sample(params, key, pool: dict, target_pose: dict,
               num_views: jnp.ndarray) -> jnp.ndarray:
        B, P, H, W, C = pool["x"].shape
        key, k_init = jax.random.split(key)
        z0 = jax.random.normal(k_init, (B, H, W, C))
        ts = jnp.arange(schedule.num_timesteps - 1, -1, -1)

        do_pre = precompute_pose
        if do_pre is None:
            # Level-0 embedding is (B, P, F, H, W, emb_ch); finer levels
            # add ~1/3 more. Auto-disable past ~512 MB residency.
            mcfg = model.config
            itemsize = jnp.dtype(mcfg.dtype).itemsize
            est = (4 / 3) * B * P * 2 * H * W * mcfg.emb_ch * itemsize
            do_pre = est <= 512 * 1024 * 1024

        pose_all = uncond_embs = None
        if do_pre:
            flat = {
                "x": pool["x"].reshape(B * P, H, W, C),
                "R1": pool["R1"].reshape(B * P, 3, 3),
                "t1": pool["t1"].reshape(B * P, 3),
                "R2": jnp.broadcast_to(target_pose["R2"][:, None],
                                       (B, P, 3, 3)).reshape(B * P, 3, 3),
                "t2": jnp.broadcast_to(target_pose["t2"][:, None],
                                       (B, P, 3)).reshape(B * P, 3),
                "K": jnp.broadcast_to(target_pose["K"][:, None],
                                      (B, P, 3, 3)).reshape(B * P, 3, 3),
            }
            pose_all = [p.reshape((B, P) + p.shape[1:])
                        for p in precompute_pose_embs(
                            model, params, flat, jnp.ones((B * P,)))]
            # Unconditional half ONCE through the real masked path; it is
            # pool-independent (the mask zeroes the pose embedding before
            # the convs), so any single pair serves.
            pair0 = {
                "x": pool["x"][:, 0], "R1": pool["R1"][:, 0],
                "t1": pool["t1"][:, 0], "R2": target_pose["R2"],
                "t2": target_pose["t2"], "K": target_pose["K"],
            }
            uncond_embs = precompute_pose_embs(model, params, pair0,
                                               jnp.zeros((B,)))

        def body(carry, t):
            z, key, aux = carry
            key, k_pick, k_step = jax.random.split(key, 3)
            # Stochastic conditioning: uniform over the first num_views
            # entries of the pool, re-drawn EVERY denoising step.
            idx = jax.random.randint(k_pick, (), 0, num_views)
            doubled_emb = None
            if do_pre:
                doubled_emb = tuple(
                    jnp.concatenate(
                        [jax.lax.dynamic_index_in_dim(p, idx, axis=1,
                                                      keepdims=False), u],
                        axis=0)
                    for p, u in zip(pose_all, uncond_embs))
            batch = {
                "x": jax.lax.dynamic_index_in_dim(pool["x"], idx, axis=1,
                                                  keepdims=False),
                "R1": jax.lax.dynamic_index_in_dim(pool["R1"], idx, axis=1,
                                                   keepdims=False),
                "t1": jax.lax.dynamic_index_in_dim(pool["t1"], idx, axis=1,
                                                   keepdims=False),
                "R2": target_pose["R2"],
                "t2": target_pose["t2"],
                "K": target_pose["K"],
                "z": z,
                "logsnr": jnp.full((B,), schedule.logsnr(t)),
            }
            outs = _cfg_eps(model, params, batch, w,
                            {"pose_embs": doubled_emb} if do_pre else None)
            z, aux = update(z, t, outs, k_step, aux)
            return (z, key, aux), None

        (z, _, _), _ = jax.lax.scan(body, (z0, key, init_aux(z0)), ts)
        return z

    return sample


def autoregressive_generate(model, schedule: DiffusionSchedule,
                            config: DiffusionConfig, params, key,
                            first_view: dict, target_poses: dict,
                            max_pool: Optional[int] = None,
                            sampler=None) -> jnp.ndarray:
    """Generate a trajectory of novel views autoregressively.

    Starting from the real view(s) in `first_view` (x (B,H,W,3) for one
    view — the 3DiM paper protocol — or (B,P0,H,W,3) for a pool of P0 real
    captures; R1/t1 ranked alike; K (B,3,3)), each target pose in
    `target_poses` (R2/t2: (B, N, …)) is sampled with stochastic
    conditioning over ALL available views, and the result joins the pool.
    Returns (B, N, H, W, 3). One compiled sampler serves every iteration
    (the pool is padded to `max_pool`). A caller looping over many batches
    should build the sampler once with `make_stochastic_sampler` and pass
    it as `sampler` so each call reuses the same jit cache.
    """
    if first_view["x"].ndim == 4:  # single real view → pool of one
        first_view = dict(
            first_view,
            x=first_view["x"][:, None],
            R1=first_view["R1"][:, None],
            t1=first_view["t1"][:, None],
        )
    B, P0, H, W, C = first_view["x"].shape
    N = target_poses["R2"].shape[1]
    max_pool = max_pool or (N + P0)
    if max_pool < P0:
        raise ValueError(f"max_pool {max_pool} < {P0} initial views")
    if sampler is None:
        sampler = make_stochastic_sampler(model, schedule, config, max_pool)

    # Pool padded with repeats of the first view (never selected: idx < n).
    pool = {
        "x": jnp.concatenate(
            [first_view["x"], jnp.broadcast_to(
                first_view["x"][:, :1], (B, max_pool - P0, H, W, C))], 1),
        "R1": jnp.concatenate(
            [first_view["R1"], jnp.broadcast_to(
                first_view["R1"][:, :1], (B, max_pool - P0, 3, 3))], 1),
        "t1": jnp.concatenate(
            [first_view["t1"], jnp.broadcast_to(
                first_view["t1"][:, :1], (B, max_pool - P0, 3))], 1),
    }
    outs = []
    for i in range(N):
        key, k_i = jax.random.split(key)
        target_pose = {
            "R2": target_poses["R2"][:, i],
            "t2": target_poses["t2"][:, i],
            "K": first_view["K"],
        }
        # Valid slots: views generated past a small max_pool are not stored
        # (guard below), so the draw range must cap at capacity — an
        # uncapped count would make randint exceed the pool and JAX's index
        # clamping would silently bias selection toward the last slot.
        img = sampler(params, k_i, pool, target_pose,
                      jnp.asarray(min(P0 + i, max_pool), jnp.int32))
        outs.append(img)
        if P0 + i < max_pool:
            pool["x"] = pool["x"].at[:, P0 + i].set(img)
            pool["R1"] = pool["R1"].at[:, P0 + i].set(target_pose["R2"])
            pool["t1"] = pool["t1"].at[:, P0 + i].set(target_pose["t2"])
    return jnp.stack(outs, axis=1)

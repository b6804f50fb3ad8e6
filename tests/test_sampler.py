"""Sampler tests: finite outputs in [-1,1] at T=8, CFG batching, stochastic
conditioning, autoregressive generation (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.config import DiffusionConfig, ModelConfig
from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
from novel_view_synthesis_3d_tpu.diffusion import make_schedule, respace
from novel_view_synthesis_3d_tpu.models.xunet import XUNet
from novel_view_synthesis_3d_tpu.sample.ddpm import (
    autoregressive_generate,
    make_sampler,
    make_stochastic_sampler,
)

TINY = ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                   attn_resolutions=(8,), dropout=0.0)


def _model_and_params(S=16, B=2):
    batch = make_example_batch(batch_size=B, sidelength=S)
    model = XUNet(TINY)
    model_batch = {
        "x": jnp.asarray(batch["x"]),
        "z": jnp.asarray(batch["target"]),
        "logsnr": jnp.zeros((B,)),
        "R1": jnp.asarray(batch["R1"]),
        "t1": jnp.asarray(batch["t1"]),
        "R2": jnp.asarray(batch["R2"]),
        "t2": jnp.asarray(batch["t2"]),
        "K": jnp.asarray(batch["K"]),
    }
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        model_batch, cond_mask=jnp.ones((B,)), train=False)
    cond = {k: model_batch[k] for k in ("x", "R1", "t1", "R2", "t2", "K")}
    return model, variables["params"], cond


def test_sampler_finite_in_range():
    dcfg = DiffusionConfig(timesteps=8, sample_timesteps=8, guidance_weight=3.0)
    sched = make_schedule(dcfg)
    model, params, cond = _model_and_params()
    sampler = make_sampler(model, sched, dcfg)
    imgs = sampler(params, jax.random.PRNGKey(0), cond)
    assert imgs.shape == (2, 16, 16, 3)
    arr = np.asarray(imgs)
    assert np.isfinite(arr).all()
    # x̂₀ clipping keeps the final image within a sane envelope.
    assert np.abs(arr).max() < 3.0


def test_sampler_respaced():
    dcfg = DiffusionConfig(timesteps=100, sample_timesteps=8)
    sched = respace(dcfg, 8)
    assert sched.num_timesteps == 8
    model, params, cond = _model_and_params()
    sampler = make_sampler(model, sched, dcfg)
    imgs = sampler(params, jax.random.PRNGKey(0), cond)
    assert np.isfinite(np.asarray(imgs)).all()


@pytest.mark.slow
def test_guidance_weight_zero_vs_nonzero():
    dcfg0 = DiffusionConfig(timesteps=4, guidance_weight=0.0)
    dcfg3 = DiffusionConfig(timesteps=4, guidance_weight=3.0)
    sched = make_schedule(dcfg0)
    model, params, cond = _model_and_params()
    # Perturb params so cond/uncond passes differ.
    params = jax.tree.map(
        lambda p: p + 0.01 * jax.random.normal(jax.random.PRNGKey(5), p.shape),
        params)
    i0 = make_sampler(model, sched, dcfg0)(params, jax.random.PRNGKey(0), cond)
    i3 = make_sampler(model, sched, dcfg3)(params, jax.random.PRNGKey(0), cond)
    assert not np.allclose(np.asarray(i0), np.asarray(i3))


def test_stochastic_conditioning_pool():
    dcfg = DiffusionConfig(timesteps=4)
    sched = make_schedule(dcfg)
    model, params, cond = _model_and_params()
    B, H = 2, 16
    max_pool = 3
    pool = {
        "x": jnp.broadcast_to(cond["x"][:, None], (B, max_pool, H, H, 3)),
        "R1": jnp.broadcast_to(cond["R1"][:, None], (B, max_pool, 3, 3)),
        "t1": jnp.broadcast_to(cond["t1"][:, None], (B, max_pool, 3)),
    }
    target_pose = {"R2": cond["R2"], "t2": cond["t2"], "K": cond["K"]}
    sampler = make_stochastic_sampler(model, sched, dcfg, max_pool)
    img = sampler(params, jax.random.PRNGKey(0), pool, target_pose,
                  jnp.asarray(2, jnp.int32))
    assert img.shape == (B, H, H, 3)
    assert np.isfinite(np.asarray(img)).all()


def test_autoregressive_generate():
    dcfg = DiffusionConfig(timesteps=2)
    sched = make_schedule(dcfg)
    model, params, cond = _model_and_params()
    first_view = {"x": cond["x"], "R1": cond["R1"], "t1": cond["t1"],
                  "K": cond["K"]}
    N = 3
    target_poses = {
        "R2": jnp.broadcast_to(cond["R2"][:, None], (2, N, 3, 3)),
        "t2": jnp.broadcast_to(cond["t2"][:, None], (2, N, 3)),
    }
    out = autoregressive_generate(model, sched, dcfg, params,
                                  jax.random.PRNGKey(0), first_view,
                                  target_poses)
    assert out.shape == (2, N, 16, 16, 3)
    assert np.isfinite(np.asarray(out)).all()


def test_ddim_eta0_ignores_step_noise():
    # At η=0 the per-step update must be invariant to the injected noise
    # (σ=0) — checked on the PRODUCTION update returned by _make_update with
    # two different noise keys, which a same-PRNGKey end-to-end comparison
    # could never detect.
    from novel_view_synthesis_3d_tpu.sample.ddpm import _make_update

    sched = make_schedule(DiffusionConfig(timesteps=16))
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.standard_normal((2, 16, 16, 3)), jnp.float32)
    eps = jnp.asarray(rng.standard_normal((2, 16, 16, 3)), jnp.float32)
    t = jnp.asarray([5, 5])
    upd0, _ = _make_update(sched, DiffusionConfig(
        timesteps=16, sampler="ddim", ddim_eta=0.0))
    a, _ = upd0(z, t, (eps, eps), jax.random.PRNGKey(0), ())
    b, _ = upd0(z, t, (eps, eps), jax.random.PRNGKey(123), ())
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # …and at η=1 the noise branch must be live.
    upd1, _ = _make_update(sched, DiffusionConfig(
        timesteps=16, sampler="ddim", ddim_eta=1.0))
    c, _ = upd1(z, t, (eps, eps), jax.random.PRNGKey(0), ())
    d, _ = upd1(z, t, (eps, eps), jax.random.PRNGKey(123), ())
    assert np.abs(np.asarray(c) - np.asarray(d)).max() > 1e-4


@pytest.mark.slow
def test_ddim_eta_changes_output_and_stays_finite():
    model, params, cond = _model_and_params()
    outs = {}
    for eta in (0.0, 1.0):
        dcfg = DiffusionConfig(timesteps=16, sample_timesteps=16,
                               sampler="ddim", ddim_eta=eta)
        sched = make_schedule(dcfg)
        sampler = make_sampler(model, sched, dcfg)
        outs[eta] = np.asarray(sampler(params, jax.random.PRNGKey(3), cond))
        assert np.isfinite(outs[eta]).all()
        assert np.abs(outs[eta]).max() < 3.0
    assert np.abs(outs[0.0] - outs[1.0]).max() > 1e-4


def test_ddim_respaced_matches_shapes():
    from novel_view_synthesis_3d_tpu.diffusion import respace

    dcfg = DiffusionConfig(timesteps=100, sample_timesteps=8, sampler="ddim")
    sched = respace(dcfg, 8)
    model, params, cond = _model_and_params()
    sampler = make_sampler(model, sched, dcfg)
    imgs = np.asarray(sampler(params, jax.random.PRNGKey(0), cond))
    assert imgs.shape == (2, 16, 16, 3)
    assert np.isfinite(imgs).all()


@pytest.mark.slow
def test_autoregressive_multi_view_pool_seed():
    # first_view with a pool axis (B, P0, ...) seeds stochastic
    # conditioning with P0 REAL views; the single-view form (B, ...) is
    # the P0=1 special case and must produce identical results.
    dcfg = DiffusionConfig(timesteps=6, sample_timesteps=6)
    sched = make_schedule(dcfg)
    model, params, cond = _model_and_params()
    N = 2
    target_poses = {
        "R2": jnp.stack([cond["R2"]] * N, axis=1),
        "t2": jnp.stack([cond["t2"]] * N, axis=1),
    }
    single = {"x": cond["x"], "R1": cond["R1"], "t1": cond["t1"],
              "K": cond["K"]}
    as_pool1 = {"x": cond["x"][:, None], "R1": cond["R1"][:, None],
                "t1": cond["t1"][:, None], "K": cond["K"]}
    a = autoregressive_generate(model, sched, dcfg, params,
                                jax.random.PRNGKey(0), single, target_poses)
    b = autoregressive_generate(model, sched, dcfg, params,
                                jax.random.PRNGKey(0), as_pool1,
                                target_poses)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # P0=2 real views: output differs (more conditioning) and stays finite.
    pool2 = {
        "x": jnp.stack([cond["x"], cond["x"] * 0.5], axis=1),
        "R1": jnp.stack([cond["R1"], cond["R2"]], axis=1),
        "t1": jnp.stack([cond["t1"], cond["t2"]], axis=1),
        "K": cond["K"],
    }
    c = autoregressive_generate(model, sched, dcfg, params,
                                jax.random.PRNGKey(0), pool2, target_poses)
    assert c.shape == (2, N, 16, 16, 3)
    assert np.isfinite(np.asarray(c)).all()
    import pytest
    with pytest.raises(ValueError, match="max_pool"):
        autoregressive_generate(model, sched, dcfg, params,
                                jax.random.PRNGKey(0), pool2, target_poses,
                                max_pool=1)


def test_dpmpp_step_reduces_to_ddim_on_constant_x0():
    # With x̂₀_cur == x̂₀_prev the 2M extrapolation is the identity, so every
    # dpm++ step must equal the η=0 DDIM step on the same x̂₀ — including the
    # low-order first/final steps.
    sched = make_schedule(DiffusionConfig(timesteps=16))
    rng = np.random.default_rng(1)
    z = jnp.asarray(rng.standard_normal((2, 8, 8, 3)), jnp.float32)
    c = jnp.asarray(rng.uniform(-1, 1, (2, 8, 8, 3)), jnp.float32)
    for t_val, first in [(15, True), (7, False), (0, False)]:
        t = jnp.asarray([t_val, t_val])
        got = sched.dpmpp_2m_step(c, c, z, t, jnp.asarray(first))
        want = sched.ddim_step(c, z, t, 0.0, 0.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)


def test_dpmpp_exact_on_constant_denoiser():
    # If the denoiser is exact and constant (x̂₀ ≡ c at every step), the
    # solver must land exactly on c at t=0 regardless of z_T — pins the
    # update algebra and the low-order final step in one go.
    sched = make_schedule(DiffusionConfig(timesteps=12))
    rng = np.random.default_rng(2)
    z = jnp.asarray(rng.standard_normal((1, 8, 8, 3)), jnp.float32)
    c = jnp.asarray(rng.uniform(-0.9, 0.9, (1, 8, 8, 3)), jnp.float32)
    aux = jnp.zeros_like(z)
    for i, t_val in enumerate(range(11, -1, -1)):
        t = jnp.asarray(t_val)
        z = sched.dpmpp_2m_step(c, aux, z, t, jnp.asarray(i == 0))
        aux = c
        assert np.isfinite(np.asarray(z)).all(), f"non-finite at t={t_val}"
    np.testing.assert_allclose(np.asarray(z), np.asarray(c), atol=1e-5)


def test_dpmpp_sampler_finite_and_deterministic():
    dcfg = DiffusionConfig(timesteps=8, sample_timesteps=8, sampler="dpm++",
                           guidance_weight=3.0)
    sched = make_schedule(dcfg)
    model, params, cond = _model_and_params()
    sampler = make_sampler(model, sched, dcfg)
    a = sampler(params, jax.random.PRNGKey(0), cond)
    b = sampler(params, jax.random.PRNGKey(0), cond)
    assert a.shape == (2, 16, 16, 3)
    assert np.isfinite(np.asarray(a)).all()
    # Deterministic ODE solver: same key (hence same z_T) → same image.
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Respaced from a long training schedule — the production usage.
    sched50 = respace(DiffusionConfig(timesteps=1000, sampler="dpm++"), 6)
    sampler50 = make_sampler(model, sched50,
                             DiffusionConfig(timesteps=1000, sampler="dpm++"))
    out = sampler50(params, jax.random.PRNGKey(1), cond)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.slow
def test_dpmpp_stochastic_sampler_finite():
    dcfg = DiffusionConfig(timesteps=8, sample_timesteps=8, sampler="dpm++")
    sched = make_schedule(dcfg)
    model, params, cond = _model_and_params()
    pool = {
        "x": jnp.stack([cond["x"], cond["x"]], axis=1),
        "R1": jnp.stack([cond["R1"], cond["R2"]], axis=1),
        "t1": jnp.stack([cond["t1"], cond["t2"]], axis=1),
    }
    target = {"R2": cond["R2"], "t2": cond["t2"], "K": cond["K"]}
    sampler = make_stochastic_sampler(model, sched, dcfg, max_pool=2)
    img = sampler(params, jax.random.PRNGKey(0), pool, target,
                  jnp.asarray(2, jnp.int32))
    assert img.shape == (2, 16, 16, 3)
    assert np.isfinite(np.asarray(img)).all()
    # Stochastic conditioning re-draws the view each step, so dpm++ must
    # degrade to its first-order update there — bit-identical to η=0 DDIM
    # (2M history would read the per-step conditioning jump as curvature).
    ddim_cfg = DiffusionConfig(timesteps=8, sample_timesteps=8,
                               sampler="ddim", ddim_eta=0.0)
    ddim = make_stochastic_sampler(model, make_schedule(ddim_cfg), ddim_cfg,
                                   max_pool=2)
    ref = ddim(params, jax.random.PRNGKey(0), pool, target,
               jnp.asarray(2, jnp.int32))
    np.testing.assert_array_equal(np.asarray(img), np.asarray(ref))


@pytest.mark.slow
def test_dpmpp_convergence_to_ode_solution():
    # Solver-order check on the REAL network ODE: with a fixed probability
    # flow (deterministic, w=0, perturbed params so the zero-init head is
    # live), coarse dpm++ solutions must approach the fine-grained DDIM
    # reference as steps double — a property of the solver, independent of
    # training.
    model, params, cond = _model_and_params()
    params = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(
            jax.random.PRNGKey(7), p.shape, p.dtype), params)
    base = dict(timesteps=128, guidance_weight=0.0)
    key = jax.random.PRNGKey(3)

    def run(sampler_kind, steps):
        dcfg = DiffusionConfig(sampler=sampler_kind, **base)
        sched = (respace(dcfg, steps) if steps != base["timesteps"]
                 else make_schedule(dcfg))
        return np.asarray(
            make_sampler(model, sched, dcfg)(params, key, cond))

    ref = run("ddim", 128)  # fine-grained first-order reference solution
    err = {n: np.abs(run("dpm++", n) - ref).mean() for n in (8, 32)}
    assert err[32] < err[8], f"dpm++ not converging: {err}"
    # Second order beats first order at the same coarse step count.
    err_ddim8 = np.abs(run("ddim", 8) - ref).mean()
    assert err[8] < err_ddim8, (err, err_ddim8)


def test_dpmpp_trajectory_matches_flat():
    dcfg = DiffusionConfig(timesteps=8, sample_timesteps=8, sampler="dpm++")
    sched = make_schedule(dcfg)
    model, params, cond = _model_and_params()
    flat = make_sampler(model, sched, dcfg)
    traj = make_sampler(model, sched, dcfg, trajectory_every=3)
    a = flat(params, jax.random.PRNGKey(0), cond)
    b, frames = traj(params, jax.random.PRNGKey(0), cond)
    # The aux (prev-x̂₀) carry must thread identically through the chunked
    # trajectory scans — final image bit-identical to the flat solver.
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(frames[-1]), np.asarray(b))


def test_unknown_sampler_rejected():
    import pytest

    from novel_view_synthesis_3d_tpu.sample.ddpm import _make_update

    dcfg = DiffusionConfig(timesteps=8, sampler="euler")
    sched = make_schedule(dcfg)
    with pytest.raises(ValueError, match="unknown sampler"):
        _make_update(sched, dcfg)


@pytest.mark.slow
def test_objectives_sample_finite():
    # x0- and v-objective samplers produce finite in-envelope images with
    # every update rule (the model is untrained; this pins the output→x̂₀
    # conversion plumbing, not quality).
    model, params, cond = _model_and_params()
    for objective in ("x0", "v"):
        for sampler_kind in ("ddpm", "ddim", "dpm++"):
            dcfg = DiffusionConfig(timesteps=8, sample_timesteps=8,
                                   objective=objective, sampler=sampler_kind)
            sched = make_schedule(dcfg)
            imgs = np.asarray(
                make_sampler(model, sched, dcfg)(
                    params, jax.random.PRNGKey(0), cond))
            assert np.isfinite(imgs).all(), (objective, sampler_kind)
            assert np.abs(imgs).max() < 3.0, (objective, sampler_kind)


def test_unknown_objective_rejected():
    import pytest

    from novel_view_synthesis_3d_tpu.sample.ddpm import _make_x0_fn

    dcfg = DiffusionConfig(timesteps=8)
    sched = make_schedule(dcfg)
    with pytest.raises(ValueError, match="unknown objective"):
        _make_x0_fn(sched, "score")


def test_trajectory_sampler_matches_flat():
    """trajectory_every returns intermediate frames; the final image is
    bit-identical to the flat sampler with the same key (nested scan keeps
    the RNG stream unchanged)."""
    dcfg = DiffusionConfig(timesteps=8, sample_timesteps=8)
    sched = make_schedule(dcfg)
    model, params, cond = _model_and_params()
    flat = make_sampler(model, sched, dcfg)
    traj2 = make_sampler(model, sched, dcfg, trajectory_every=2)
    key = jax.random.PRNGKey(7)
    ref = np.asarray(flat(params, key, cond))
    final, traj = traj2(params, key, cond)
    assert traj.shape == (4, 2, 16, 16, 3)
    np.testing.assert_array_equal(np.asarray(final), ref)
    np.testing.assert_array_equal(np.asarray(traj)[-1], ref)
    assert np.isfinite(np.asarray(traj)).all()
    # Early frames are noisier than the final one.
    assert np.std(np.asarray(traj)[0]) > np.std(ref) * 0.5


def test_trajectory_every_validation():
    import pytest

    dcfg = DiffusionConfig(timesteps=8, sample_timesteps=8)
    sched = make_schedule(dcfg)
    model, params, cond = _model_and_params()
    with pytest.raises(ValueError, match="trajectory_every"):
        make_sampler(model, sched, dcfg, trajectory_every=-1)
    with pytest.raises(ValueError, match="trajectory_every"):
        make_sampler(model, sched, dcfg, trajectory_every=9)


def test_trajectory_non_divisor_stride():
    # T=8, stride 3 → two full chunks (after steps 3 and 6) + the remainder
    # end-state appended: 3 frames, final frame bit-identical to the flat
    # sampler (same RNG stream). This is the prime-step-count gif fix.
    dcfg = DiffusionConfig(timesteps=8, sample_timesteps=8)
    sched = make_schedule(dcfg)
    model, params, cond = _model_and_params()
    flat = make_sampler(model, sched, dcfg)
    traj3 = make_sampler(model, sched, dcfg, trajectory_every=3)
    key = jax.random.PRNGKey(11)
    ref = np.asarray(flat(params, key, cond))
    final, traj = traj3(params, key, cond)
    assert traj.shape == (3, 2, 16, 16, 3)
    np.testing.assert_array_equal(np.asarray(final), ref)
    np.testing.assert_array_equal(np.asarray(traj)[-1], ref)


def test_trajectory_views_limits_batch():
    dcfg = DiffusionConfig(timesteps=8, sample_timesteps=8)
    sched = make_schedule(dcfg)
    model, params, cond = _model_and_params()
    full = make_sampler(model, sched, dcfg, trajectory_every=2)
    lim = make_sampler(model, sched, dcfg, trajectory_every=2,
                       trajectory_views=1)
    key = jax.random.PRNGKey(7)
    final_f, traj_f = full(params, key, cond)
    final_l, traj_l = lim(params, key, cond)
    assert traj_l.shape == (4, 1, 16, 16, 3)
    np.testing.assert_array_equal(np.asarray(final_l), np.asarray(final_f))
    np.testing.assert_array_equal(np.asarray(traj_l)[:, 0],
                                  np.asarray(traj_f)[:, 0])


def test_cfg_rescale_changes_output_and_stays_finite():
    model, params, cond = _model_and_params()
    # Perturb params: the zero-init head makes cond == uncond at init, and
    # rescale is a no-op when the two branches agree.
    params = jax.tree.map(
        lambda p: p + 0.01 * jax.random.normal(jax.random.PRNGKey(5), p.shape),
        params)
    key = jax.random.PRNGKey(0)
    imgs = {}
    for phi in (0.0, 0.7):
        dcfg = DiffusionConfig(timesteps=8, sample_timesteps=8,
                               guidance_weight=3.0, cfg_rescale=phi)
        sched = make_schedule(dcfg)
        out = make_sampler(model, sched, dcfg)(params, key, cond)
        arr = np.asarray(out)
        assert np.isfinite(arr).all(), phi
        imgs[phi] = arr
    # φ=0 must exactly reproduce the pre-feature sampler path; φ>0 differs.
    assert not np.array_equal(imgs[0.0], imgs[0.7])


def test_cfg_rescale_validation():
    import pytest

    model, params, cond = _model_and_params()
    dcfg = DiffusionConfig(timesteps=8, sample_timesteps=8, cfg_rescale=1.5)
    with pytest.raises(ValueError, match="cfg_rescale"):
        make_sampler(model, make_schedule(dcfg), dcfg)


def test_precomputed_pose_embs_match_inline():
    """The hoisted pose-conditioning path (batch['pose_embs']) reproduces
    the in-loop computation exactly — params untouched, identical math.
    The model's output head is zero-init, so perturb params first to get a
    non-trivial output."""
    from novel_view_synthesis_3d_tpu.models.xunet import precompute_pose_embs

    B = 2
    model, params, cond = _model_and_params(B=B)
    params = jax.tree.map(
        lambda p: p + 0.01 * jnp.arange(p.size, dtype=p.dtype
                                        ).reshape(p.shape) / p.size, params)
    batch = dict(cond, z=jnp.asarray(
        np.random.default_rng(0).normal(size=(B, 16, 16, 3))
    ).astype(jnp.float32), logsnr=jnp.linspace(-4.0, 7.0, B))
    mask = jnp.asarray([1.0, 0.0])  # exercise the CFG zeroing too

    out_inline = model.apply({"params": params}, batch, cond_mask=mask,
                             train=False)
    pose_embs = precompute_pose_embs(model, params, cond, mask)
    out_pre = model.apply({"params": params},
                          dict(batch, pose_embs=pose_embs),
                          cond_mask=mask, train=False)
    np.testing.assert_allclose(np.asarray(out_pre), np.asarray(out_inline),
                               rtol=1e-6, atol=1e-6)


def _perturbed(params):
    """The output head is zero-init and every bias starts at zero:
    perturb, so that the unconditional half's embedding (the pose
    convolutions' biases) and the output are non-trivial."""
    return jax.tree.map(
        lambda p: p + 0.01 * jnp.arange(p.size, dtype=p.dtype
                                        ).reshape(p.shape) / p.size, params)


def test_split_pose_embs_match_full_extent_and_inline():
    """A guidance pair given as (conditional rows at full extent,
    unconditional rows at 1 × 1) gives the output of the same rows at
    full extent and of the in-loop path, mask [1, 0]."""
    from novel_view_synthesis_3d_tpu.models.xunet import precompute_pose_embs

    model, params, cond = _model_and_params(B=1)
    params = _perturbed(params)
    pair = jax.tree.map(lambda a: jnp.concatenate([a, a], axis=0), cond)
    batch = dict(pair, z=jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 16, 16, 3))
    ).astype(jnp.float32), logsnr=jnp.linspace(-4.0, 7.0, 2))
    mask = jnp.asarray([1.0, 0.0])

    full = precompute_pose_embs(model, params, pair, mask)
    split = model.precompute(params, cond)["pose_embs"]
    for lvl, (f, (c, u)) in enumerate(zip(full, split)):
        side = 16 // 2 ** lvl
        assert f.shape == (2, 2, side, side, TINY.emb_ch)
        assert c.shape == (1, 2, side, side, TINY.emb_ch)
        assert u.shape == (1, 2, 1, 1, TINY.emb_ch)
        # one vector per frame, and not a zero one
        np.testing.assert_array_equal(
            np.asarray(f[1:]), np.broadcast_to(np.asarray(u), f[1:].shape))
        assert float(jnp.abs(u).max()) > 1e-3

    outs = {name: model.apply({"params": params},
                              dict(batch, **extra), cond_mask=mask,
                              train=False)
            for name, extra in (("inline", {}),
                                ("full", {"pose_embs": full}),
                                ("split", {"pose_embs": split}))}
    assert float(jnp.abs(outs["inline"]).max()) > 0.1
    for name in ("full", "split"):
        np.testing.assert_allclose(np.asarray(outs[name]),
                                   np.asarray(outs["inline"]),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("flags", [(), ("use_pos_emb",),
                                   ("use_ref_pose_emb",)],
                         ids=lambda f: "+".join(f) or "neither")
def test_sampler_matches_inline_loop_whatever_the_extent(flags, capsys):
    """`make_sampler` hands the model the unconditional half at 1 × 1
    extent only where it is one vector per frame: with a learned
    position table or a frame-identity embedding it keeps the full
    extent (and says why, once). Either way its images are those of a
    loop that recomputes the pose path inside every step."""
    import dataclasses

    from novel_view_synthesis_3d_tpu.sample import ddpm
    from novel_view_synthesis_3d_tpu.utils.profiling import reset_log_once

    cfg = dataclasses.replace(TINY, **{f: True for f in flags})
    model = XUNet(cfg)
    raw = make_example_batch(batch_size=2, sidelength=16)
    cond = {k: jnp.asarray(raw[k]) for k in ("x", "R1", "t1", "R2", "t2",
                                             "K")}
    batch = dict(cond, z=jnp.asarray(raw["target"]), logsnr=jnp.zeros((2,)))
    params = _perturbed(model.init(
        {"params": jax.random.PRNGKey(0)}, batch, cond_mask=jnp.ones((2,)),
        train=False)["params"])

    embs = model.precompute(params, cond)["pose_embs"]
    assert all(isinstance(e, tuple) != bool(flags) for e in embs)

    dcfg = DiffusionConfig(timesteps=8, sample_timesteps=2,
                           guidance_weight=3.0)
    sched = respace(dcfg, 2)
    reset_log_once()
    capsys.readouterr()
    imgs = make_sampler(model, sched, dcfg)(params, jax.random.PRNGKey(3),
                                            cond)
    err = capsys.readouterr().err
    assert ("FiLM sites project" in err) != bool(flags), err
    assert ("keeps full-extent pose embeddings" in err) == bool(flags), err
    for f in flags:
        assert f in err

    update, init_aux = ddpm._make_update(sched, dcfg)
    key, k_init = jax.random.split(jax.random.PRNGKey(3))
    z = jax.random.normal(k_init, imgs.shape)
    aux = init_aux(z)
    for t in (jnp.asarray(1), jnp.asarray(0)):
        key, k_step = jax.random.split(key)
        outs = ddpm._cfg_eps(
            model, params,
            dict(cond, z=z, logsnr=jnp.full((2,), sched.logsnr(t))),
            dcfg.guidance_weight)
        z, aux = update(z, t, outs, k_step, aux)
    # One jitted scan against eager steps, guidance amplifying both: they
    # sit 6e-5 to 1.2e-4 apart on either path; the unconditional half
    # scaled by 1.5 moves an image by 3e-2.
    assert float(jnp.mean(jnp.abs(z) < 1.0)) > 0.2  # not all clipped
    np.testing.assert_allclose(np.asarray(imgs), np.asarray(z),
                               rtol=0, atol=1e-3)


def test_split_pose_embs_leave_the_param_tree_alone(capsys):
    """Checkpoint layout: the tree `XUNet.init` builds is the one it built
    before a pair could be passed (paths and shapes pinned), `init` does
    not take the split path, and a call on the split path creates no
    parameter that tree lacks."""
    import hashlib

    from novel_view_synthesis_3d_tpu.models.xunet import (
        precompute_guidance_pose_embs)
    from novel_view_synthesis_3d_tpu.utils.profiling import reset_log_once

    def layout(tree):
        return sorted(("/".join(str(k.key) for k in path), tuple(leaf.shape))
                      for path, leaf in
                      jax.tree_util.tree_flatten_with_path(tree)[0])

    reset_log_once()
    capsys.readouterr()
    model, params, cond = _model_and_params(B=2)
    assert "FiLM sites" not in capsys.readouterr().err
    flat = layout(params)
    assert len(flat) == 178
    assert hashlib.sha256(repr(flat).encode()).hexdigest()[:16] == (
        "6f875995620f797a")
    film = [p for p, _ in flat if "/FiLM_0/" in p]
    assert film and all(p.split("/FiLM_0/")[1] in ("Dense_0/kernel",
                                                  "Dense_0/bias")
                        for p in film)

    split = precompute_guidance_pose_embs(model, params, cond)
    doubled = jax.tree.map(lambda a: jnp.concatenate([a, a], axis=0), cond)
    batch = dict(doubled, z=jnp.zeros((4, 16, 16, 3)),
                 logsnr=jnp.zeros((4,)), pose_embs=split)
    mask = jnp.concatenate([jnp.ones((2,)), jnp.zeros((2,))])
    out, grown = model.apply({"params": params}, batch, cond_mask=mask,
                             train=False, mutable=["params"])
    assert "FiLM sites" in capsys.readouterr().err
    assert out.shape == (4, 16, 16, 3)
    assert layout(grown.get("params", params)) == flat


@pytest.mark.slow
def test_stochastic_precompute_matches_inline_path():
    """The stochastic sampler's hoisted pose path (precompute_pose=True)
    must reproduce the in-loop path exactly — including the unconditional
    CFG half, which is NOT zeros (conv biases and learned embeddings
    survive the mask). Perturbed params make biases nonzero; learned
    pos/ref embeddings exercise the additive paths the mask doesn't kill."""
    import dataclasses

    for flags in ({}, {"use_pos_emb": True, "use_ref_pose_emb": True}):
        cfg = dataclasses.replace(TINY, **flags)
        batch = make_example_batch(batch_size=2, sidelength=16)
        model = XUNet(cfg)
        model_batch = {
            "x": jnp.asarray(batch["x"]), "z": jnp.asarray(batch["target"]),
            "logsnr": jnp.zeros((2,)), "R1": jnp.asarray(batch["R1"]),
            "t1": jnp.asarray(batch["t1"]), "R2": jnp.asarray(batch["R2"]),
            "t2": jnp.asarray(batch["t2"]), "K": jnp.asarray(batch["K"]),
        }
        params = model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)},
            model_batch, cond_mask=jnp.ones((2,)), train=False)["params"]
        params = jax.tree.map(
            lambda p: p + 0.02 * jax.random.normal(
                jax.random.PRNGKey(7), p.shape, p.dtype), params)
        cond = {k: model_batch[k] for k in ("x", "R1", "t1", "R2", "t2", "K")}

        dcfg = DiffusionConfig(timesteps=3)
        sched = make_schedule(dcfg)
        B, H, max_pool = 2, 16, 3
        pool = {
            "x": jnp.broadcast_to(cond["x"][:, None],
                                  (B, max_pool, H, H, 3)),
            "R1": jnp.broadcast_to(cond["R1"][:, None], (B, max_pool, 3, 3)),
            "t1": jnp.broadcast_to(cond["t1"][:, None], (B, max_pool, 3)),
        }
        target_pose = {"R2": cond["R2"], "t2": cond["t2"], "K": cond["K"]}
        key = jax.random.PRNGKey(11)
        args = (pool, target_pose, jnp.asarray(2, jnp.int32))
        out_pre = make_stochastic_sampler(
            model, sched, dcfg, max_pool, precompute_pose=True)(
                params, key, *args)
        out_inline = make_stochastic_sampler(
            model, sched, dcfg, max_pool, precompute_pose=False)(
                params, key, *args)
        np.testing.assert_allclose(np.asarray(out_pre),
                                   np.asarray(out_inline),
                                   rtol=2e-5, atol=2e-5)

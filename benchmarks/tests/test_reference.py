"""The plain reference against models/xunet at a tiny size on the CPU, in
float32 on both sides, on the benchmark's seeded (non-zero) weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import weights
from reference import xunet_ref as ref

TINY = dict(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
            attn_resolutions=(8,), attn_heads=4)


def _program(side=16, B=2, seed=3):
    from novel_view_synthesis_3d_tpu.config import ModelConfig
    from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
    from novel_view_synthesis_3d_tpu.models.xunet import XUNet

    model = XUNet(ModelConfig(dropout=0.0, use_flash_attention=False, **TINY))
    b = make_example_batch(batch_size=B, sidelength=side, seed=seed)
    batch = {k: jnp.asarray(b[k]) for k in ("x", "R1", "t1", "R2", "t2", "K")}
    rng = np.random.default_rng(seed)
    batch["z"] = jnp.asarray(rng.normal(size=(B, side, side, 3)), jnp.float32)
    batch["logsnr"] = jnp.asarray(rng.uniform(-5, 5, size=(B,)), jnp.float32)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, batch,
                           cond_mask=jnp.ones((B,)), train=False))["params"]
    return model, batch, weights.make_weights(seed, shapes)


@pytest.mark.parametrize("mask", [(1.0, 1.0), (1.0, 0.0)])
def test_forward_matches_program(mask):
    model, batch, params = _program()
    mask = jnp.asarray(mask)
    want = model.apply({"params": params}, batch, cond_mask=mask, train=False)
    m = dict(TINY, ch_mult=list(TINY["ch_mult"]))
    with jax.default_matmul_precision("highest"):
        got = ref.forward(params, m, batch, mask)
    assert float(jnp.std(want)) > 0.1  # the seeded weights give a live output
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_lower_precision_moves_the_output():
    _, batch, params = _program()
    m = dict(TINY, ch_mult=list(TINY["ch_mult"]))
    mask = jnp.ones((2,))
    full = ref.forward(params, m, batch, mask)
    gaps = {p: float(jnp.sqrt(jnp.mean(jnp.square(
        ref.forward(params, m, batch, mask, p) - full)))) for p in
        ("bf16", "fp8")}
    assert gaps["fp8"] > 3 * gaps["bf16"] > 0


def test_schedule_tables_match_program():
    from novel_view_synthesis_3d_tpu.config import DiffusionConfig
    from novel_view_synthesis_3d_tpu.sample.stepper import StepBank

    for steps in (4, 16, 64):
        bank = StepBank(DiffusionConfig(timesteps=1000), steps)
        tab = ref.cosine_tables(1000, steps)
        assert bank.n == len(tab["c1"])
        for mine, theirs in (("sqrt_recip", "sqrt_recip_acp"),
                             ("sqrt_recipm1", "sqrt_recipm1_acp"),
                             ("c1", "pm_coef1"), ("c2", "pm_coef2"),
                             ("log_var", "post_log_var")):
            np.testing.assert_allclose(tab[mine], bank.coefs[theirs],
                                       rtol=1e-6, err_msg=mine)
        np.testing.assert_allclose(
            ref.logsnr_cosine(tab["t_orig"], 1000), bank.coefs["logsnr"],
            rtol=1e-5, atol=1e-5)


def test_steps_judged_are_those_bfloat16_can_name():
    """Of a 16-step schedule, bfloat16 holds 1000·u to within 0.1 at the
    second step, the ninth, the tenth and the last five."""
    import sampling_check

    tab = ref.cosine_tables(1000, 16)
    lams = [float(ref.logsnr_cosine(tab["t_orig"][t], 1000))
            for t in range(15, -1, -1)]
    rng = np.random.default_rng(0)
    assert sampling_check.pick_steps(lams, "bfloat16", 0.1, 8, rng) == [
        1, 8, 9, 11, 12, 13, 14, 15]
    assert sampling_check.pick_steps(lams, "float32", 0.1, 16, rng) == list(
        range(16))
    few = sampling_check.pick_steps(lams, "bfloat16", 0.1, 3, rng)
    assert len(few) == 3 and few[-1] == 15 and set(few) <= {
        1, 8, 9, 11, 12, 13, 14, 15}


def test_program_eps_read_back_from_its_states():
    """In float32 on both sides the ε̂ read back from the program's states
    (the ancestral update inverted, noise redrawn from the key) is the
    reference's, at every step of the chain."""
    import harness
    import sampling_check
    import synth_data
    from novel_view_synthesis_3d_tpu.config import get_preset
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler

    over = dict(harness.read_json(harness.HERE, "rehearse.json")["overrides"])
    over.update({"model.dtype": "float32", "diffusion.sample_timesteps": 16,
                 "diffusion.sampler": "ddpm",
                 "diffusion.guidance_weight": 3.0})
    cfg = get_preset("paper256").override(**over).validate()
    seed, views, side = 5, 2, 16
    model, shapes, params = sampling_check.program_model(cfg, seed)
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, 16),
                           cfg.diffusion, trajectory_every=1)
    cond = {k: jnp.asarray(v) for k, v in synth_data.cond_views(
        views, side, seed).items()}
    key = weights.seed_key(seed)
    final, traj = sampler(params, key, cond)
    sample = {"key": key, "row": 1, "traj": np.asarray(traj[:, 1]),
              "cond": {k: np.asarray(a[1]) for k, a in cond.items()},
              "draw_shape": (views, side, side, 3)}
    tab = ref.cosine_tables(1000, 16)
    rows = sampling_check.step_gaps(
        ref, params, harness.model_sizes(cfg), tab, 1000, 3.0, sample,
        list(range(16)))
    assert rows[0]["pixels"] == 0  # the first step's x̂₀ is clipped everywhere
    assert sum(r["pixels"] for r in rows[8:]) > 0.2 * 8 * rows[0]["size"]
    for r in rows[1:]:
        assert sampling_check.pooled([r], "program") < 2e-4, r
        assert abs(r["clipped_prog"] - r["clipped_ref"]) <= 0.03 * r["size"]

"""A run driven past the harness's look for a chip (CPU, tiny sizes): sound,
and with the timed path broken underneath; and the control, the reference
in the precisions below the stated one, put in the program's place."""
import os

import jax
import numpy as np
import pytest

import harness
import run as bench_run
import sampling_check

CELL = "paper256.sample_scan"


def drive(workload, seed=7, seconds=3.0, bench_dir=harness.HERE):
    rehearse = harness.read_json(harness.HERE, "rehearse.json")
    cell = harness.load_cell(workload, bench_dir)
    cell["traffic"] = dict(cell["traffic"],
                           **rehearse["traffic"][cell["traffic"]["kind"]])
    env = {"t_start": 0.0, "compiles": harness.CompileCounter(),
           "rehearse": rehearse, "out_dir": os.path.join(
               harness.HERE, "out", "test")}
    out, res = bench_run.measure(cell, seed, seconds, False, env)
    return cell, out, res


def numbers(res):
    return {n["name"]: n for n in res["numbers"]}


def test_scan_sound_run():
    cell, out, res = drive(CELL)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"view_steps_per_s", "setup_s"}
    got = numbers(res)
    assert set(got) == {"eps_rel_rms", "uncompared_pixel_share",
                        "clipped_share_gap", "final_is_last_state"}
    assert got["eps_rel_rms"]["value"] > 0.01  # bfloat16, not float32


def breaking(monkeypatch, alter):
    """The program's make_sampler, its result altered where it is made."""
    from novel_view_synthesis_3d_tpu.sample import ddpm

    real = ddpm.make_sampler

    def make(*a, **kw):
        sampler = real(*a, **kw)
        return lambda p, k, c: alter(*sampler(p, k, c))

    monkeypatch.setattr(ddpm, "make_sampler", make)


@pytest.mark.parametrize("fault, failing", [
    # the returned image altered, the states left as they were
    (lambda final, traj: (final * 0.8, traj), "final_is_last_state"),
    # one step's state altered (what every later step then starts from)
    (lambda final, traj: (final, traj.at[8].multiply(0.9)), "eps_rel_rms"),
    # a sampler that does not clip x̂₀: stand-in, the states pushed outward
    (lambda final, traj: (final * 1.5, traj * 1.5), "eps_rel_rms"),
])
def test_scan_with_the_timed_path_broken(monkeypatch, fault, failing):
    breaking(monkeypatch, fault)
    _, out, res = drive(CELL)
    assert out["correct"] is False
    assert numbers(res)[failing]["ok"] is False


def test_control_fails_the_limit():
    """The reference in fp8, in the program's place at the program's own
    inputs, reads over the limit that the program's bfloat16 reads under
    (tiny size; the chip's readings at the cell's size are in PERF.md)."""
    import jax.numpy as jnp

    import synth_data
    import weights
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler

    cell = harness.load_cell(CELL)
    rehearse = harness.read_json(harness.HERE, "rehearse.json")
    tr = cell["traffic"]
    cfg = harness.build_config(
        cell, {"diffusion.sample_timesteps": int(tr["steps"]),
               "diffusion.sampler": tr["sampler"],
               "diffusion.guidance_weight": float(tr["guidance_weight"])},
        rehearse)
    n, side, views = cfg.diffusion.sample_timesteps, 16, 2
    ref = harness.load_module(os.path.join(
        harness.HERE, cell["config"]["reference"]), "xunet_ref")
    tab = ref.cosine_tables(cfg.diffusion.timesteps, n)
    lams = [float(ref.logsnr_cosine(tab["t_orig"][t], 1000))
            for t in range(n - 1, -1, -1)]
    limit = tr["limits"]["eps_rel_rms"]
    for seed in (11, 2 ** 31 + 12, 13):
        model, shapes, params = sampling_check.program_model(cfg, seed)
        sampler = make_sampler(model, sampling_schedule(cfg.diffusion, n),
                               cfg.diffusion, trajectory_every=1)
        cond = {k: jnp.asarray(v) for k, v in synth_data.cond_views(
            views, side, seed).items()}
        key = weights.seed_key(seed)
        final, traj = sampler(params, key, cond)
        sample = {"key": key, "row": 0, "traj": np.asarray(traj[:, 0]),
                  "cond": {k: np.asarray(a[0]) for k, a in cond.items()},
                  "draw_shape": (views, side, side, 3)}
        steps = sampling_check.pick_steps(
            lams, "bfloat16", tr["check"]["timestep_tol"],
            tr["check"]["steps"], np.random.default_rng(seed))
        rows = sampling_check.step_gaps(
            ref, params, harness.model_sizes(cfg), tab, 1000,
            cfg.diffusion.guidance_weight, sample, steps,
            tuple(cell["config"]["control_precisions"]))
        sound = sampling_check.pooled(rows, "program")
        for p in cell["config"]["control_precisions"]:
            control = sampling_check.pooled(rows, p)
            assert 3 * sound < control, (seed, p, sound, control)
            assert sound < limit < control, (seed, p, sound, limit, control)

"""The token-denoiser cell driven past the harness's look for a chip (CPU,
the traffic file's own tiny sizes): sound; with the latent-cache path
broken underneath; and the control — the reference in fp8 — put in the
program's place."""
import os

import jax
import numpy as np
import pytest

import harness
import run as bench_run
import token_check

CELL = "ms4_denoiser128.sample_scan_tokens"
NAMES = {"eps_rel_rms", "excluded_token_share", "uncompared_pixel_share",
         "clipped_share_gap", "held_rows_lost", "final_is_last_state"}


def drive(seed=7, seconds=6.0, trace=False):
    cell = harness.load_cell(CELL)
    env = {"t_start": 0.0, "compiles": harness.CompileCounter(),
           "rehearse": {"traffic": {}}, "out_dir": os.path.join(
               harness.HERE, "out", "test_tokens")}
    out, res = bench_run.measure(cell, seed, seconds, trace, env)
    return cell, out, res


def numbers(res):
    return {n["name"]: n for n in res["numbers"]}


def test_sound_run():
    cell, out, res = drive(seed=2 ** 31 + 5)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"view_steps_per_s", "setup_s"}
    got = numbers(res)
    assert set(got) == NAMES
    assert got["eps_rel_rms"]["value"] > 0.003  # bfloat16, not float32
    counts = np.asarray(res["counters"]["routing_counts"])
    k = res["counters"]["sizes"]
    assert counts.shape == (k["num_hidden_layers"], k["held_experts"][1])
    assert counts.sum() > 0


def test_traced_rehearsal_reads_the_counter_metrics():
    _, out, _ = drive(trace=True)
    assert out["correct"] is True
    assert out["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert "scan_call_p50_ms" in out["metrics"]
    # device-trace readers find no chip's capture on the CPU: left out
    assert "moe_experts_roofline" not in out["metrics"]


@pytest.mark.parametrize("fault", ["stale_cache", "no_rope_on_cached_keys"])
def test_with_the_cache_path_broken(monkeypatch, fault):
    """The once-a-call pass hands the steps another view's cache, or one
    whose rotary keys were left unrotated: every state the sampler writes
    is then off, and the run reads incorrect."""
    from novel_view_synthesis_3d_tpu.models import token_denoiser

    real = token_denoiser.TokenDenoiser.precompute

    def broken(self, params, cond):
        pre = real(self, params, cond)
        if fault == "stale_cache":
            alter = lambda c, r: (jax.numpy.roll(c, 1, axis=0),  # noqa: E731
                                  jax.numpy.roll(r, 1, axis=0))
        else:
            alter = lambda c, r: (c, jax.numpy.roll(r, 1, axis=-1))  # noqa: E731
        return {"latent_cache": tuple(alter(c, r)
                                      for c, r in pre["latent_cache"])}

    monkeypatch.setattr(token_denoiser.TokenDenoiser, "precompute", broken)
    _, out, res = drive()
    assert out["correct"] is False
    assert numbers(res)["eps_rel_rms"]["ok"] is False


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
    cfg, tr = cell["kind"].build(cell, {"rehearse": True})
    n, side, views = cfg.diffusion.sample_timesteps, 16, 2
    ref, tables = token_check.load_refs(cell)
    T, w = cfg.diffusion.timesteps, cfg.diffusion.guidance_weight
    tab = tables.cosine_tables(T, n)
    m = token_check.model_sizes(cfg)
    limit = tr["limits"]["eps_rel_rms"]
    for seed in (11, 2 ** 31 + 12):
        model, shapes, params = token_check.program_model(
            cfg, seed, token_check.replicas(cell))
        sampler = make_sampler(model, sampling_schedule(cfg.diffusion, n),
                               cfg.diffusion, trajectory_every=1)
        cond = {k: jnp.asarray(v) for k, v in synth_data.cond_views(
            views, side, seed).items()}
        key = weights.seed_key(seed)
        final, traj = sampler(params, key, cond)
        sample = {"key": key, "row": 0, "traj": np.asarray(traj[:, 0]),
                  "cond": {k: np.asarray(a[0]) for k, a in cond.items()},
                  "draw_shape": (views, side, side, 3)}
        steps = token_check.pick(cell, tables, tab, T, n, seed)
        batch, mask, z_ins, noises = token_check.step_inputs(
            tables, tab, T, sample, steps)
        got = token_check.reference_pass(ref, m, seed, shapes, batch, mask,
                                         tuple(cell["config"][
                                             "control_precisions"]),
                                         token_check.replicas(cell))
        rows = token_check.step_rows(
            m, tab, w, sample, steps, z_ins, noises, got,
            float(tr["check"]["router_margin"]))
        sound = token_check.sampling_check.pooled(rows, "program")
        for p in cell["config"]["control_precisions"]:
            control = token_check.sampling_check.pooled(rows, p)
            assert 3 * sound < control, (seed, p, sound, control)
            assert sound < limit < control, (seed, p, sound, limit, control)


@pytest.mark.parametrize("which", ["group", "row"])
def test_with_held_rows_lost_after_the_product(which):
    """The grouped product loses its fullest group's rows, or one row of
    them, in every layer of every step: ε̂ hardly moves (a token's held
    experts add little beside attention and the shared expert), the
    program's expert layer run alone reads it, and the run is incorrect."""
    with token_check.rows_lost(which):
        _, out, res = drive(seed=2 ** 31 + 5)
    got = numbers(res)
    assert out["correct"] is False
    assert got["held_rows_lost"]["ok"] is False
    assert got["held_rows_lost"]["value"] >= (1 if which == "row" else 8)
    if which == "row":
        assert got["eps_rel_rms"]["ok"] is True  # what ε̂ cannot see

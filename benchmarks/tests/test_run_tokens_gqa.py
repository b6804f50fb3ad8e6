"""The grouped-query token cell driven past the harness's look for a chip
(CPU, the traffic file's own tiny sizes): sound; with the key/value-cache
path broken underneath; with held rows lost after the product; and the
control — the reference in fp8 — put in the program's place."""
import os

import jax
import numpy as np
import pytest

import harness
import run as bench_run
import token_check_gqa as check

CELL = "st21_denoiser256.sample_scan_swa"
NAMES = {"eps_rel_rms", "excluded_token_share", "uncompared_pixel_share",
         "clipped_share_gap", "held_rows_lost", "final_is_last_state"}


def drive(seed=7, seconds=5.0, trace=False):
    cell = harness.load_cell(CELL)
    env = {"t_start": 0.0, "compiles": harness.CompileCounter(),
           "rehearse": {"traffic": {}}, "out_dir": os.path.join(
               harness.HERE, "out", "test_tokens_gqa")}
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
    # every expert is held: each token's top-3, none dropped
    tokens = res["counters"]["counted_rows"] * 16
    assert (counts.sum(axis=1) == 3 * tokens).all()


def test_traced_rehearsal_reads_the_counter_metrics():
    _, out, _ = drive(trace=True)
    assert out["correct"] is True
    assert out["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert out["metrics"]["attn_keys_visited_over_visible"]["value"] >= 1.0
    assert "scan_call_p50_ms" in out["metrics"]
    # device-trace readers find no chip's capture on the CPU: left out
    assert "attn_window_roofline" not in out["metrics"]


@pytest.mark.parametrize("fault", ["stale_cache", "unrotated_cached_keys"])
def test_with_the_cache_path_broken(monkeypatch, fault):
    """The once-a-call pass hands the steps another view's key/value
    cache, or one whose keys sit at the wrong positions: every state the
    sampler writes is then off, and the run reads incorrect."""
    from novel_view_synthesis_3d_tpu.models import token_denoiser

    real = token_denoiser.TokenDenoiser.precompute

    def broken(self, params, cond):
        pre = real(self, params, cond)
        if fault == "stale_cache":
            alter = lambda k, v: (jax.numpy.roll(k, 1, axis=0),  # noqa: E731
                                  jax.numpy.roll(v, 1, axis=0))
        else:
            alter = lambda k, v: (jax.numpy.roll(k, 5, axis=1), v)  # noqa: E731
        return {"kv_cache": tuple(alter(k, v) for k, v in pre["kv_cache"])}

    monkeypatch.setattr(token_denoiser.TokenDenoiser, "precompute", broken)
    _, out, res = drive()
    assert out["correct"] is False
    assert numbers(res)["eps_rel_rms"]["ok"] is False


@pytest.mark.parametrize("which", ["group", "row"])
def test_with_held_rows_lost_after_the_product(which):
    """The grouped product loses its fullest group's rows, or one row of
    them, in every layer of every step: the program's expert layer run
    alone on the reference's gates and choice reads it, and the run is
    incorrect."""
    with check.rows_lost(which):
        _, out, res = drive(seed=2 ** 31 + 5)
    got = numbers(res)
    assert out["correct"] is False
    assert got["held_rows_lost"]["ok"] is False
    assert got["held_rows_lost"]["value"] >= (1 if which == "row" else 8)


def test_control_fails_the_limit_and_adoption_is_what_it_says():
    """The reference in fp8, in the program's place at the program's own
    inputs, reads over the limit that the program's bfloat16 reads under;
    with nothing adopted (margin 0) the program's own flips at near ties
    show as a larger gap, and a program that routes at random is left out
    token by token (tiny size; the chip's readings are in PERF.md)."""
    import jax.numpy as jnp

    import synth_data
    import weights
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler

    cell = harness.load_cell(CELL)
    cfg, tr = cell["kind"].build(cell, {"rehearse": True})
    n, side, views = cfg.diffusion.sample_timesteps, 16, 2
    ref, tables = check.load_refs(cell)
    T, w = cfg.diffusion.timesteps, cfg.diffusion.guidance_weight
    tab = tables.cosine_tables(T, n)
    m = check.model_sizes(cfg)
    limit = tr["limits"]["eps_rel_rms"]
    margin = float(tr["check"]["router_margin"])
    seed = 2 ** 31 + 12
    model, shapes, params = check.program_model(cfg, seed)
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, n),
                           cfg.diffusion, trajectory_every=1)
    cond = {k: jnp.asarray(v) for k, v in synth_data.cond_views(
        views, side, seed).items()}
    key = weights.seed_key(seed)
    final, traj = sampler(params, key, cond)
    sample = {"key": key, "row": 0, "traj": np.asarray(traj[:, 0]),
              "cond": {k: np.asarray(a[0]) for k, a in cond.items()},
              "draw_shape": (views, side, side, 3)}
    steps = check.pick(cell, tables, tab, T, n, seed)
    batch, mask, z_ins, noises = check.step_inputs(tables, tab, T, sample,
                                                   steps)
    choice = check.program_choices(model, params, batch, mask)

    def read(choice, margin, precs=()):
        got = check.reference_pass(ref, m, seed, shapes, batch, mask,
                                   choice, margin, precs)
        rows = check.step_rows(m, tab, w, sample, steps, z_ins, noises, got,
                                margin)
        return got, rows, check.pooled_numbers(rows)

    got, rows, sound = read(choice, margin,
                            tuple(cell["config"]["control_precisions"]))
    assert 0 < got["adopted"] < 0.2 and sound["excluded_token_share"] < 0.1
    for p in cell["config"]["control_precisions"]:
        control = check.sampling_check.pooled(rows, p)
        assert 3 * sound["eps_rel_rms"] < control
        assert sound["eps_rel_rms"] < limit < control
    _, _, bare = read(choice, 0.0)
    assert bare["eps_rel_rms"] >= sound["eps_rel_rms"]
    assert bare["excluded_token_share"] == 0.0
    shuffled = np.random.default_rng(0).permuted(
        np.broadcast_to(np.arange(8), choice.shape[:-1] + (8,)),
        axis=-1)[..., :choice.shape[-1]].astype(choice.dtype)
    got, _, lost = read(shuffled, margin)
    assert got["adopted"] < 0.02
    assert lost["excluded_token_share"] > sound["excluded_token_share"]

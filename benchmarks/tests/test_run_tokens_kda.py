"""The KDA token cell driven past the harness's look for a chip (CPU, the
traffic file's own tiny sizes): sound; with either kind of cache broken
underneath — the KDA state zeroed, stale, or the convolution's tail lost;
the latent stale —; with held rows lost after the product; and the
controls — the reference in fp8, and the reference with the KDA state
zeroed at the target frame's first token — put in the program's place."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

import harness
import run as bench_run
import token_check_kda as check

CELL = "kl48_denoiser256.sample_scan_kda"
NAMES = {"eps_rel_rms", "excluded_token_share", "uncompared_pixel_share",
         "clipped_share_gap", "held_rows_lost", "final_is_last_state"}


def drive(seed=7, seconds=8.0, trace=False):
    cell = harness.load_cell(CELL)
    env = {"t_start": 0.0, "compiles": harness.CompileCounter(),
           "rehearse": {"traffic": {}}, "out_dir": os.path.join(
               harness.HERE, "out", "test_tokens_kda")}
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
    # the layers that HAVE experts: the leading dense layer has no row
    assert counts.shape == (k["num_hidden_layers"] - 1, k["held_experts"][1])
    # every expert is held: each token's top-4 (both replicas of its two
    # best prototypes), none dropped
    tokens = res["counters"]["counted_rows"] * 16
    assert (counts.sum(axis=1) == 4 * tokens).all()
    assert res["counters"]["cond_cache_bytes"] == {
        "recurrent_state": 3 * 2 * (4 * 16 * 16 * 2 + 3 * 3 * 4 * 16),
        "latent": 2 * 16 * (16 + 8)}


def test_traced_rehearsal_reads_the_counter_metrics():
    _, out, _ = drive(trace=True)
    assert out["correct"] is True
    assert out["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert out["metrics"]["cond_cache_mb_per_row"]["value"] == pytest.approx(
        (3 * 2 * (4 * 16 * 16 * 2 + 3 * 3 * 4 * 16) + 2 * 16 * 24) / 1e6)
    assert "scan_call_p50_ms" in out["metrics"]
    # device-trace readers find no chip's capture on the CPU: left out
    for name in ("kda_core_roofline", "kl_mla_core_roofline",
                 "kl_moe_experts_roofline", "layer_ms_per_call.kda_core"):
        assert name not in out["metrics"]


FAULTS = {
    # the state every step's scan is entered with: zeroed, or another view's
    "zeroed_state": lambda i, e: (jnp.zeros_like(e[0]), e[1]),
    "stale_state": lambda i, e: (jnp.roll(e[0], 1, axis=0), e[1]),
    # the convolution's first three target tokens read the wrong rows
    "lost_conv_tail": lambda i, e: (e[0], jnp.zeros_like(e[1])),
}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["stale_latent"])
def test_with_either_kind_of_cache_broken(monkeypatch, fault):
    """The once-a-call pass hands the steps a KDA state that holds nothing
    or another view's, a lost convolution tail, or another view's latent:
    every state the sampler writes is then off, and the run reads
    incorrect."""
    from novel_view_synthesis_3d_tpu.models import token_denoiser

    real = token_denoiser.TokenDenoiser.precompute

    def broken(self, params, cond):
        entries = []
        for i, e in enumerate(real(self, params, cond)["layer_cache"]):
            state = self.layer.cache_kind(i) == "recurrent_state"
            if fault == "stale_latent" and not state:
                e = tuple(jnp.roll(a, 1, axis=0) for a in e)
            elif fault in FAULTS and state:
                e = FAULTS[fault](i, e)
            entries.append(e)
        return {"layer_cache": tuple(entries)}

    monkeypatch.setattr(token_denoiser.TokenDenoiser, "precompute", broken)
    _, out, res = drive()
    assert out["correct"] is False
    assert numbers(res)["eps_rel_rms"]["ok"] is False


@pytest.mark.parametrize("which", ["group", "row"])
def test_with_held_rows_lost_after_the_product(which):
    """The grouped product loses its fullest group's rows, or one row of
    them, in every expert layer of every step: the program's expert layer
    run alone on the reference's gates and choice reads it, and the run is
    incorrect."""
    with check.rows_lost(which):
        _, out, res = drive(seed=2 ** 31 + 5)
    got = numbers(res)
    assert out["correct"] is False
    assert got["held_rows_lost"]["ok"] is False
    assert got["held_rows_lost"]["value"] >= (1 if which == "row" else 8)


def test_controls_fail_the_limit_and_adoption_is_what_it_says():
    """The reference in fp8, and the reference with every KDA layer's
    state zeroed at the target frame's first token, each in the program's
    place at the program's own inputs, read over the limit that the
    program's bfloat16 reads under; with nothing adopted (margin 0) the
    program's own flips at near ties show as a larger gap, and a program
    that routes at random is left out token by token (tiny size,
    independent router columns; the chip's readings are in PERF.md)."""
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
    wargs = dict(check.weight_args(cell), router_replicas=1)
    model, shapes, params = check.program_model(cfg, seed, wargs)
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
    assert choice.shape[0] == len(check.expert_layers(m)) == 3
    controls = tuple(cell["config"]["control_precisions"]) + (
        check.ZEROED_STATE,)

    def read(choice, margin, controls=()):
        got = check.reference_pass(ref, m, seed, shapes, batch, mask,
                                   choice, margin, controls, wargs)
        rows = check.step_rows(m, tab, w, sample, steps, z_ins, noises, got,
                                margin)
        return got, rows, check.pooled_numbers(rows)

    got, rows, sound = read(choice, margin, controls)
    assert got["adopted"] < 0.2 and sound["excluded_token_share"] < 0.1
    assert sorted(got["half_life"]) == [0, 1, 2]   # the KDA layers
    for q in got["half_life"].values():             # 95 % … 5 %, in tokens
        assert q == sorted(q, reverse=True) and q[-1] > 0
    for p in controls:
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
    assert lost["excluded_token_share"] >= sound["excluded_token_share"]

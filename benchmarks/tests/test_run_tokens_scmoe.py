"""The shortcut-connected-MoE token cell driven past the harness's look for
a chip (CPU, the traffic file's own tiny sizes): sound; with this
mechanism's seams broken underneath — the first attention's latent handed
to the second, the branch joined one sublayer early, the identity part
dropped, an identity id given an expert's row —; with held rows lost after
the product; and the controls — the reference in fp8, and the reference
with each of the three faults of lcf_ref.CONTROLS — put in the program's
place."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import run as bench_run
import token_check_scmoe as check

CELL = "lcf_denoiser256.sample_scan_scmoe"
NAMES = {"eps_rel_rms", "excluded_token_share", "uncompared_pixel_share",
         "clipped_share_gap", "held_rows_lost", "final_is_last_state"}


def drive(seed=7, seconds=8.0, trace=False):
    cell = harness.load_cell(CELL)
    env = {"t_start": 0.0, "compiles": harness.CompileCounter(),
           "rehearse": {"traffic": {}}, "out_dir": os.path.join(
               harness.HERE, "out", "test_tokens_scmoe")}
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
    assert counts.shape == (k["num_layers"], k["held_experts"][1]) == (2, 8)
    # a token's six choices of 48 outputs: one held on average under
    # uniform choices — correlated tokens concentrate, the seed's luck —,
    # some of the others identities, a share of the tokens with none held
    tokens = res["counters"]["counted_rows"] * 16
    assert 0 < counts.sum(axis=1).min() and \
        counts.sum(axis=1).max() < 3 * tokens
    shares = res["counters"]["routing_choice_shares"]
    assert 0.1 < shares["zero_choice_share"] < 0.7
    assert 0.05 < shares["tokens_without_held_share"] < 0.8
    assert shares["held_rows_per_layer_step"] == pytest.approx(
        counts.sum() / (2 * res["counters"]["counted_rows"] / 2))
    # two latents a layer: 2 layers × 2 × 16 tokens × (16 + 8) × bfloat16
    assert res["counters"]["cond_cache_bytes"] == {
        "latent": 2 * 2 * 16 * (16 + 8) * 2}


def test_traced_rehearsal_reads_the_counter_metrics():
    _, out, res = drive(trace=True)
    assert out["correct"] is True
    shares = res["counters"]["routing_choice_shares"]
    assert out["metrics"]["moe_zero_choice_share"]["value"] == \
        pytest.approx(100 * shares["zero_choice_share"])
    assert out["metrics"]["moe_tokens_without_held_share"]["value"] == \
        pytest.approx(100 * shares["tokens_without_held_share"])
    assert out["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert out["metrics"]["moe_rows_visited_over_held"]["value"] >= 1.0
    assert 0 < out["metrics"]["moe_combine_fetched_over_choices"][
        "value"] < 0.5
    assert out["metrics"]["cond_cache_mb_per_row"]["value"] == pytest.approx(
        2 * 2 * 16 * 24 * 2 / 1e6)
    assert "scan_call_p50_ms" in out["metrics"]
    # device-trace readers find no chip's capture on the CPU: left out
    for name in ("lcf_mla_core_roofline", "lcf_moe_experts_roofline",
                 "layer_ms_per_call.moe_zero", "lcf_token_mfu.scan"):
        assert name not in out["metrics"]


def test_the_readers_on_a_run_that_chose_no_held_expert():
    """The rows are the seed's: where no token chose a held expert the
    expert roofline reads 0 and the utilisation the dense path's, neither
    fails; the readers of another trunk's sizes return nothing."""
    spec = harness.read_json(os.path.dirname(harness.HERE), "BENCHMARK.json")
    assert [m["workloads"] for m in spec["per_layer"]
            if m["name"].startswith("lcf_")] == [[CELL]] * 3
    cell = harness.load_cell(CELL)
    cfg, _ = cell["kind"].build(cell, {"rehearse": None})
    sizes = check.model_sizes(cfg)
    counters = {"sizes": sizes, "views": 1, "steps": 8, "kind": "scan",
                "chips": 1, "units_per_s": 1.6, "counted_rows": 6,
                "routing_counts": [[0] * 16] * 4, "device_kind": "TPU v5 lite",
                "peaks": harness.read_json(harness.HERE, "peaks.json")}
    mfu = harness.layer_reader("lcf_token_mfu.scan")(None, None, counters)
    assert 55.0 < mfu < 60.0          # 71 TFLOP × 1.6 / 197 TFLOP/s
    some = dict(counters, routing_counts=[[128] * 16] * 4)
    assert mfu < harness.layer_reader("lcf_token_mfu.scan")(
        None, None, some) < 1.02 * mfu
    for name in ("lcf_mla_core_roofline", "lcf_moe_experts_roofline"):
        assert harness.layer_reader(name)(None, None, counters) is None
        other = dict(counters, sizes={"hidden_size": 64})
        assert harness.layer_reader(name)(None, None, other) is None
    assert harness.layer_reader("moe_zero_choice_share")(
        None, None, counters) is None


def wrong_latent(real):
    def broken(self, params, cond):
        out = real(self, params, cond)
        return dict(out, layer_cache=tuple(
            (first, first) for first, _ in out["layer_cache"]))
    return broken


def joined_early(layer, i, p, h, tables, cache):
    """`LongcatFlashLayer.__call__` with m added at h₂."""
    from novel_view_synthesis_3d_tpu.models import token_denoiser as td

    k = layer.config.tokens
    dt, eps = jnp.dtype(layer.config.dtype), k.rms_norm_eps
    B, L, _ = h.shape
    c0, c1 = (None, None) if cache is None else cache
    h, own_0 = layer._mla(p["mla_0"], h, tables, c0)
    b32 = td.rms_norm(h, p["mlp_norm_0"]["scale"], eps).reshape(B * L, -1)
    top_p, top_i = td.route(b32, p["router"], k)
    b = b32.astype(dt)
    routed, counts = td.held_expert_part(b, top_p, top_i, p["experts"], k)
    m = (routed + td.identity_part(b, top_p, top_i, k)).reshape(B, L, -1)
    h = h + td.gated_mlp(b, p["mlp_0"]).reshape(B, L, -1) + m   # early
    h, own_1 = layer._mla(p["mla_1"], h, tables, c1)
    h = h + td.gated_mlp(td.rms_norm(
        h, p["mlp_norm_1"]["scale"], eps).astype(dt), p["mlp_1"])
    return h, (own_0, own_1), (counts, top_i.reshape(B, L, -1))


@pytest.mark.parametrize("fault", ["wrong_latent", "joined_early",
                                   "identity_dropped", "identity_gets_a_row"])
def test_with_a_seam_of_the_double_layer_broken(monkeypatch, fault):
    """The second attention reads the first one's latent; the branch joins
    one sublayer early; the identity experts give nothing; an identity id
    is given the row of a real expert: every state the sampler writes is
    then off and the run reads incorrect — the last two also in the
    expert branch run alone."""
    from novel_view_synthesis_3d_tpu.models import token_denoiser as td

    if fault == "wrong_latent":
        monkeypatch.setattr(td.TokenDenoiser, "precompute",
                            wrong_latent(td.TokenDenoiser.precompute))
    elif fault == "joined_early":
        monkeypatch.setattr(td.LongcatFlashLayer, "__call__", joined_early)
    elif fault == "identity_dropped":
        monkeypatch.setattr(td, "identity_part",
                            lambda b, top_p, top_i, k: jnp.zeros_like(b))
    else:
        real = td.held_expert_part
        monkeypatch.setattr(
            td, "held_expert_part", lambda b, top_p, top_i, p, k: real(
                b, top_p, jnp.where(top_i >= k.n_routed_experts,
                                    top_i - k.n_routed_experts, top_i),
                p, k))
    _, out, res = drive()
    got = numbers(res)
    assert out["correct"] is False
    assert got["eps_rel_rms"]["ok"] is False
    if fault.startswith("identity"):
        assert got["held_rows_lost"]["ok"] is False


@pytest.mark.parametrize("which", ["group", "row"])
def test_with_held_rows_lost_after_the_product(which):
    """The grouped product loses its fullest group's rows, or one row of
    them, in every layer of every step: the program's expert branch run
    alone on the reference's gates and choice reads it, and the run is
    incorrect."""
    with check.rows_lost(which):
        _, out, res = drive(seed=2 ** 31 + 5)
    got = numbers(res)
    assert out["correct"] is False
    assert got["held_rows_lost"]["ok"] is False
    assert got["held_rows_lost"]["value"] >= (1 if which == "row" else 4)


def test_controls_fail_the_limit_and_adoption_is_what_it_says():
    """The reference in fp8, and the reference with the identity part left
    out, the branch joined early, or the latent scales left out, each in
    the program's place at the program's own inputs, read over the limit
    that the program's bfloat16 reads under; with nothing adopted (margin
    0) the program's own flips at near ties show as a larger gap, and a
    program that routes at random is left out token by token (tiny size;
    the chip's readings are in PERF.md)."""
    import synth_data
    import weights
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler

    cell = harness.load_cell(CELL)
    cfg, tr = cell["kind"].build(cell, {"rehearse": True})
    n, side, views = cfg.diffusion.sample_timesteps, 16, 1
    ref, tables = check.load_refs(cell)
    T, w = cfg.diffusion.timesteps, cfg.diffusion.guidance_weight
    tab = tables.cosine_tables(T, n)
    m = check.model_sizes(cfg)
    limit = tr["limits"]["eps_rel_rms"]
    margin = float(tr["check"]["router_margin"])
    seed = 2 ** 31 + 12
    wargs = check.weight_args(cell)
    assert wargs == {"router_replicas": 1}
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
    assert choice.shape == (2, 2 * len(steps), 32, 6)
    # a step's rows at a time is the whole batch at once
    np.testing.assert_array_equal(choice, np.asarray(jax.jit(
        model.routing_choices)(params, batch, mask)))
    controls = tuple(cell["config"]["control_precisions"]) + check.CONTROLS
    assert controls == ("fp8", "no_identity", "early_join",
                        "no_latent_scale")

    def read(choice, margin, controls=()):
        got = check.reference_pass(ref, m, seed, shapes, batch, mask,
                                   choice, margin, controls, wargs)
        rows = check.step_rows(m, tab, w, sample, steps, z_ins, noises, got,
                               margin)
        return got, rows, check.pooled_numbers(rows)

    got, rows, sound = read(choice, margin, controls)
    assert got["adopted"] < 0.2 and sound["excluded_token_share"] < 0.1
    for p in controls:
        control = check.sampling_check.pooled(rows, p)
        assert 3 * sound["eps_rel_rms"] < control, p
        assert sound["eps_rel_rms"] < limit < control, p
    _, _, bare = read(choice, 0.0)
    assert bare["eps_rel_rms"] >= sound["eps_rel_rms"]
    assert bare["excluded_token_share"] == 0.0
    shuffled = np.random.default_rng(0).permuted(
        np.broadcast_to(np.arange(48), choice.shape[:-1] + (48,)),
        axis=-1)[..., :choice.shape[-1]].astype(choice.dtype)
    got, _, lost = read(shuffled, margin)
    assert got["adopted"] < 0.02
    assert lost["excluded_token_share"] >= sound["excluded_token_share"]

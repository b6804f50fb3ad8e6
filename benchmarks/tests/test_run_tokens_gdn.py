"""The Olmo-Hybrid token cell driven past the harness's look for a chip
(CPU, the traffic file's own tiny sizes): sound; with each kind of cache
entry broken underneath — the delta-rule state zeroed or stale, the
convolution's tail lost, a full layer's keys and values stale —; and the
controls — the reference in fp8, with every delta-rule state zeroed at the
target frame's first token, with β without its factor 2, and with the
decay switched off — put in the program's place."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

import harness
import run as bench_run
import token_check_gdn as check

CELL = "oh7_denoiser256.sample_scan_gdn"
NAMES = {"eps_rel_rms", "uncompared_pixel_share", "clipped_share_gap",
         "final_is_last_state"}
# a row's cache at the tiny size: 3 delta-rule layers' state (4 × 12 × 24
# float32) and tail (3 × 192 bfloat16), one full layer's 16 rows of k and v
# (64 wide)
CACHE = {"recurrent_state": 3 * (4 * 12 * 24 * 4 + 3 * 192 * 2),
         "keys_values": 2 * 16 * 64 * 2}


def drive(seed=7, seconds=8.0, trace=False):
    cell = harness.load_cell(CELL)
    env = {"t_start": 0.0, "compiles": harness.CompileCounter(),
           "rehearse": {"traffic": {}}, "out_dir": os.path.join(
               harness.HERE, "out", "test_tokens_gdn")}
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
    assert "routing_counts" not in res["counters"]   # no router to count
    assert res["counters"]["cond_cache_bytes"] == CACHE
    assert res["counters"]["attn_key_columns"] == [0, 0]   # no window
    assert res["counters"]["sizes"]["linear_key_head_dim"] == 12


def test_traced_rehearsal_reads_the_counter_metrics():
    _, out, _ = drive(trace=True)
    assert out["correct"] is True
    assert out["metrics"]["cond_cache_mb_per_row"]["value"] == pytest.approx(
        sum(CACHE.values()) / 1e6)
    assert "scan_call_p50_ms" in out["metrics"]
    # device-trace readers find no chip's capture on the CPU: left out
    for name in ("gdn_core_roofline", "oh7_attn_full_roofline",
                 "layer_ms_per_call.gdn_core", "layer_ms_per_call.gdn_conv",
                 "part_ms_per_call.gdn_proj.matmul"):
        assert name not in out["metrics"]


def test_the_cells_metrics_have_readers_that_pass_on_another_trunk():
    """Every per-layer metric this cell lists resolves to a reader, and
    the three this trunk brings return nothing — they do not raise — where
    the sizes are another trunk's or the program has no such stamp (the
    driver lays these files over the parent's checkout)."""
    cell = harness.load_cell(CELL)
    names = {m["name"] for m in cell["per_layer"]}
    assert {"gdn_core_roofline", "oh7_attn_full_roofline",
            "oh7_token_mfu.scan", "layer_ms_per_call.gdn_core",
            "part_ms_per_call.gdn_conv.kernel"} <= names
    counters = {"sizes": {"hidden_size": 64, "mamba_d_state": 8},
                "kind": "scan", "steps": 8, "views": 1, "chips": 1,
                "units_per_s": 1.0}
    for name in ("gdn_core_roofline", "oh7_attn_full_roofline",
                 "oh7_token_mfu.scan"):
        assert harness.layer_reader(name)([], None, counters) is None
    ours = dict(counters, sizes=check.model_sizes(
        cell["kind"].build(cell, {"rehearse": True})[0]))
    for name in ("gdn_core_roofline", "oh7_attn_full_roofline"):
        assert harness.layer_reader(name)([], None, ours) is None  # no trace


FAULTS = {
    # the state every step's scan is entered with: zeroed, or another row's
    "zeroed_state": ("recurrent_state",
                     lambda e: (jnp.zeros_like(e[0]), e[1])),
    "stale_state": ("recurrent_state",
                    lambda e: (jnp.roll(e[0], 1, axis=0), e[1])),
    # the convolution's first three target tokens read the wrong rows
    "lost_conv_tail": ("recurrent_state",
                       lambda e: (e[0], jnp.zeros_like(e[1]))),
    # the guidance rows' keys and values swapped
    "stale_keys_values": ("keys_values", lambda e: tuple(
        jnp.roll(a, 1, axis=0) for a in e)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_with_a_kind_of_cache_broken(monkeypatch, fault):
    """The once-a-call pass hands the steps a delta-rule state that holds
    nothing or the other row's, a lost convolution tail, or the other
    row's keys and values: every state the sampler writes is then off, and
    the run reads incorrect."""
    from novel_view_synthesis_3d_tpu.models import token_denoiser

    real = token_denoiser.TokenDenoiser.precompute
    kind, spoil = FAULTS[fault]

    def broken(self, params, cond):
        entries = [spoil(e) if self.layer.cache_kind(i) == kind else e
                   for i, e in enumerate(real(self, params,
                                              cond)["layer_cache"])]
        return {"layer_cache": tuple(entries)}

    monkeypatch.setattr(token_denoiser.TokenDenoiser, "precompute", broken)
    _, out, res = drive()
    assert out["correct"] is False
    assert numbers(res)["eps_rel_rms"]["ok"] is False


def test_controls_fail_the_limit():
    """The reference in fp8, with every delta-rule layer's state zeroed at
    the target frame's first token, with β = sigmoid(·) alone and with g =
    0, each in the program's place at the program's own inputs, read over
    the limit that the program's bfloat16 reads under (tiny size; the
    chip's readings are in PERF.md)."""
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
    seed = 2 ** 31 + 12
    wargs = check.weight_args(cell)
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
    controls = tuple(cell["config"]["control_precisions"]) + check.CONTROLS
    assert controls == ("fp8", "zeroed_state", "beta_unscaled", "no_decay")
    got = check.reference_pass(ref, m, seed, shapes, batch, mask, controls,
                               wargs)
    rows = check.step_rows(m, tab, w, sample, steps, z_ins, noises, got, 0.0)
    sound = check.pooled_numbers(rows)
    assert sound["excluded_token_share"] == 0.0     # nothing to leave out
    assert sorted(got["half_life"]) == [0, 1, 2]    # the delta-rule layers
    for q in got["half_life"].values():             # 95 % … 5 %, in tokens
        assert q == sorted(q, reverse=True) and q[-1] > 0
    for p in controls:
        control = check.sampling_check.pooled(rows, p)
        assert sound["eps_rel_rms"] < limit < control, (
            p, sound["eps_rel_rms"], control)

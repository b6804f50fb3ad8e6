"""The Laguna token cell driven past the harness's look for a chip (CPU,
the traffic file's own tiny sizes): sound; with the timed path broken
underneath — a window layer's tail stale, a full layer's cache stale or
empty, the head gate lost, the rotary laws swapped in the
PROGRAM —; with held rows lost after the product; and the controls — the
reference in fp8 and with each of the trunk's four planted faults — put in
the program's place."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

import harness
import run as bench_run
import token_check_headmix as check

CELL = "lgs_denoiser256.sample_scan_headmix"
NAMES = {"eps_rel_rms", "excluded_token_share", "uncompared_pixel_share",
         "clipped_share_gap", "held_rows_lost", "final_is_last_state"}
# a row of the doubled batch at the tiny size: 2 full layers keep 16 rows
# of 2 heads of 16, 3 window layers 7; keys and values, float32 under the
# rehearsal's bfloat16 compute → the program's dtype
FULL, TAIL = 2 * 2 * 16 * 2 * 16, 3 * 2 * 7 * 2 * 16


def drive(seed=7, seconds=8.0, trace=False):
    cell = harness.load_cell(CELL)
    env = {"t_start": 0.0, "compiles": harness.CompileCounter(),
           "rehearse": {"traffic": {}}, "out_dir": os.path.join(
               harness.HERE, "out", "test_tokens_headmix")}
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
    # the router is tied in 2 replicas: each token's top-4 are both
    # replicas of its two best prototypes, exactly 2 of them held
    tokens = res["counters"]["counted_rows"] * 16
    assert (counts.sum(axis=1) == 2 * tokens).all()
    itemsize = res["counters"]["cond_cache_bytes"]["keys_values"] // FULL
    assert itemsize in (2, 4)
    assert res["counters"]["cond_cache_bytes"] == {
        "keys_values": FULL * itemsize, "window_tail": TAIL * itemsize}
    # three window layers of 16 queries against [7 ; 16] under a window of 8
    assert res["counters"]["attn_key_columns"][1] == 3 * sum(
        16 - max(r - 7, 0) + max(7 - r, 0) for r in range(16))


def test_traced_rehearsal_reads_the_counter_metrics():
    _, out, res = drive(seconds=16.0, trace=True)
    assert out["correct"] is True
    assert out["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert out["metrics"]["moe_combine_fetched_over_choices"][
        "value"] == pytest.approx(0.5, abs=0.01)
    assert out["metrics"]["cond_cache_mb_per_row"]["value"] == pytest.approx(
        sum(res["counters"]["cond_cache_bytes"].values()) / 1e6)
    assert out["metrics"]["attn_keys_visited_over_visible"]["value"] >= 1.0
    assert "scan_call_p50_ms" in out["metrics"]
    # device-trace readers find no chip's capture on the CPU: left out
    for name in ("lgs_attn_window_roofline", "lgs_attn_full_roofline",
                 "lgs_moe_experts_roofline", "layer_ms_per_call.attn_gate"):
        assert name not in out["metrics"]


CACHE_FAULTS = {
    # another view's rows in a window layer's tail / a full layer's cache
    "stale_tail": ("window_tail", lambda e: tuple(
        jnp.roll(a, 1, axis=0) for a in e)),
    "stale_full_cache": ("keys_values", lambda e: tuple(
        jnp.roll(a, 1, axis=0) for a in e)),
    # a cache that holds nothing
    "zeroed_full_cache": ("keys_values", lambda e: tuple(
        jnp.zeros_like(a) for a in e)),
}


@pytest.mark.parametrize("fault", sorted(CACHE_FAULTS))
def test_with_either_kind_of_cache_broken(monkeypatch, fault):
    """The once-a-call pass hands the steps a cache entry that is another
    view's or empty, in the window layers or in the full ones: every state
    the sampler writes is then off, and the run reads incorrect. (A tail
    that is one row off reads CORRECT at this size, 0.04 against a limit
    of 0.2: a window query sees 1.3 cached keys of its 10 on average, at
    the cell's size 32 of 2560 — PERF.md section 7 says what that
    leaves unseen.)"""
    from novel_view_synthesis_3d_tpu.models import token_denoiser

    real = token_denoiser.TokenDenoiser.precompute
    kind, spoil = CACHE_FAULTS[fault]

    def broken(self, params, cond):
        return {"layer_cache": tuple(
            spoil(e) if self.layer.cache_kind(i) == kind else e
            for i, e in enumerate(real(self, params, cond)["layer_cache"]))}

    monkeypatch.setattr(token_denoiser.TokenDenoiser, "precompute", broken)
    _, out, res = drive()
    assert out["correct"] is False
    assert numbers(res)["eps_rel_rms"]["ok"] is False


@pytest.mark.parametrize("fault", ["no_head_gate", "one_law_for_both"])
def test_with_the_timed_layer_broken(monkeypatch, fault):
    """The PROGRAM loses the gate (a sigmoid that reads 1) or rotates every
    layer by the sliding law: the reference does neither, and the run reads
    incorrect."""
    import jax
    from novel_view_synthesis_3d_tpu.models import token_denoiser

    if fault == "no_head_gate":
        real = jax.nn.sigmoid
        # the router's softmax and the experts' silu do not call it
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: jnp.ones_like(
            real(x)))
    else:
        real = token_denoiser.LagunaLayer.tables

        def one_law(self, positions):
            t = real(self, positions)
            return dict(t, full_attention=t["sliding_attention"])

        monkeypatch.setattr(token_denoiser.LagunaLayer, "tables", one_law)
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


@pytest.fixture(scope="module")
def one_call():
    """One timed-path call at the tiny size on INDEPENDENT router columns
    (0 to 4 held choices a token), and what the comparison needs of it."""
    import synth_data
    import weights
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler

    cell = harness.load_cell(CELL)
    cfg, tr = cell["kind"].build(cell, {"rehearse": True})
    n, side, views = cfg.diffusion.sample_timesteps, 16, 1
    ref, tables = check.load_refs(cell)
    T = cfg.diffusion.timesteps
    tab = tables.cosine_tables(T, n)
    seed = 2 ** 31 + 12
    model, shapes, params = check.program_model(cfg, seed, 1)
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
    m = check.model_sizes(cfg)

    def read(choice, margin, controls=()):
        got = check.reference_pass(ref, m, seed, shapes, batch, mask,
                                   choice, margin, controls, 1)
        rows = check.step_rows(m, tab, cfg.diffusion.guidance_weight, sample,
                               steps, z_ins, noises, got, margin)
        return got, rows, check.pooled_numbers(rows)

    return cell, tr, m, choice, read


def test_the_choices_are_the_expert_layers_and_spread(one_call):
    cell, tr, m, choice, read = one_call
    assert choice.shape[0] == len(check.expert_layers(m)) == 4
    held = (choice < m["held_experts"][1]).sum(axis=-1)
    assert held.min() < 2 < held.max()       # independent columns: 0 to 4


@pytest.mark.parametrize("control", ("fp8",) + check.CONTROLS)
def test_a_control_fails_the_limit(one_call, control):
    """The reference in fp8, with the head gate left out, the rotary laws
    swapped, the x 2.5 left out or the window layers at full visibility,
    each in the program's place at the program's own inputs, reads over
    the limit that the program's bfloat16 reads under (tiny size; the
    chip's readings are in PERF.md)."""
    cell, tr, m, choice, read = one_call
    limit = tr["limits"]["eps_rel_rms"]
    got, rows, sound = read(choice, float(tr["check"]["router_margin"]),
                            (control,))
    reading = check.sampling_check.pooled(rows, control)
    assert 3 * sound["eps_rel_rms"] < reading
    assert sound["eps_rel_rms"] < limit < reading


def test_adoption_is_what_it_says(one_call):
    """With nothing adopted (margin 0) the program's own flips at near ties
    show as a larger gap; a program that routes at random is left out token
    by token."""
    cell, tr, m, choice, read = one_call
    margin = float(tr["check"]["router_margin"])
    got, _, sound = read(choice, margin)
    assert got["adopted"] < 0.2 and sound["excluded_token_share"] < 0.1
    _, _, bare = read(choice, 0.0)
    assert bare["eps_rel_rms"] >= sound["eps_rel_rms"]
    assert bare["excluded_token_share"] == 0.0
    shuffled = np.random.default_rng(0).permuted(
        np.broadcast_to(np.arange(16), choice.shape[:-1] + (16,)),
        axis=-1)[..., :choice.shape[-1]].astype(choice.dtype)
    got, _, lost = read(shuffled, margin)
    assert got["adopted"] < 0.02
    assert lost["excluded_token_share"] >= sound["excluded_token_share"]

"""The token denoiser's sixth trunk (models/token_denoiser.py,
`LongcatFlashLayer`: the shortcut-connected double layer — two latent
attentions with scaled latents and two dense MLPs a layer, one expert
branch that leaves after the first attention and joins after the second
MLP — over a router whose last outputs are identity experts) against the
benchmark's plain reference (benchmarks/reference/lcf_ref.py) at a small
size on the CPU, in float32 on both sides: 16 tokens a frame, 2 double
layers, 4 heads of 16 + 8 on 16, a router of 32 real + 16 identity outputs,
top-6, experts 0–7 held. Weights are the benchmark's seeded ones
(benchmarks/scmoe_weights.py).

Tolerances as tests/test_token_denoiser_kda.py: both sides compute in
float32 and differ by the order of their sums; TOL = 2e-5, and the
reference with its matmul inputs rounded to bfloat16 reads ~3e-2.
"""

import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import harness  # noqa: E402
import scmoe_weights  # noqa: E402
import synth_data  # noqa: E402
import token_check_scmoe  # noqa: E402
from novel_view_synthesis_3d_tpu.config import (  # noqa: E402
    PRESET_NAMES, TOKEN_TRUNKS, Config, LongcatFlashTrunkConfig, get_preset)
from novel_view_synthesis_3d_tpu.diffusion.schedules import (  # noqa: E402
    sampling_schedule)
from novel_view_synthesis_3d_tpu.models import (  # noqa: E402
    build_denoiser, token_denoiser)
from novel_view_synthesis_3d_tpu.models.vocab import (  # noqa: E402
    SCMOE_TOKEN_LAYER_KINDS, layer_of, layer_part_of)
from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler  # noqa: E402

TOL = 2e-5
SIDE = 16
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "sample_scan_scmoe.json")) as _fh:
    SMALL = dict(json.load(_fh)["rehearse"]["overrides"], **{
        "model.dtype": "float32", "model.param_dtype": "float32",
        "diffusion.sample_timesteps": 4})
ref = harness.load_module(os.path.join(
    ROOT, "benchmarks", "reference", "lcf_ref.py"), "lcf_ref")


def small_cfg(**over):
    return get_preset("lcf_denoiser256").override(
        **dict(SMALL, **over)).validate()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def seeded(cfg, seed=5):
    model = build_denoiser(cfg.model)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}))["params"]
    return model, scmoe_weights.make_weights(seed, shapes)


def doubled_batch(seed=3, rows=2):
    """One view twice (conditional row, unconditional row)."""
    cond = {k: jnp.asarray(np.repeat(v, rows, axis=0))
            for k, v in synth_data.cond_views(1, SIDE, seed).items()}
    key = jax.random.PRNGKey(seed)
    z = jnp.repeat(jax.random.normal(key, (1, SIDE, SIDE, 3)), rows, axis=0)
    return dict(cond, z=z, logsnr=jnp.full((rows,), 0.7)), \
        jnp.asarray([1.0, 0.0] * (rows // 2))


_WANT = {}


def reference(params, m, batch, mask):
    if "eps" not in _WANT:
        _WANT["eps"] = ref.forward(params, m, batch, mask)
    return _WANT["eps"]


@pytest.fixture(scope="module", params=["xla", "kernel"])
def small(request):
    """The trunk through XLA's attention and through the Pallas kernel
    (interpreted); the grouped products and the combine are kernels in
    both."""
    cfg = small_cfg(**{
        "model.use_flash_attention": request.param == "kernel"})
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    return cfg, model, params, batch, mask, token_check_scmoe.model_sizes(cfg)


# ---------------------------------------------------------------------------
# The frame: one forward, the once-a-call pass, the two latents a layer
# ---------------------------------------------------------------------------
def test_full_forward_matches_the_reference(small):
    cfg, model, params, batch, mask, m = small
    got = model.apply({"params": params}, batch, cond_mask=mask)
    assert got.shape == (2, SIDE, SIDE, 3) and got.dtype == jnp.float32
    assert rel(got, reference(params, m, batch, mask)) < TOL


def test_precompute_then_step_matches_the_full_forward(small):
    """The once-a-call pass and a step from its caches — TWO latents a
    layer, one per attention, each (c_kv after its scale, the rotated
    shared key) — are the reference's one pass over both frames."""
    cfg, model, params, batch, mask, m = small
    cond = {k: batch[k][:1] for k in ("x", "R1", "t1", "K")}
    extra = model.precompute(params, cond)
    cache = extra["layer_cache"]
    assert len(cache) == 2 and set(extra) == {"layer_cache", "derived"}
    for pair in cache:
        assert [tuple(a.shape for a in latent) for latent in pair] == [
            ((2, 16, 16), (2, 16, 8))] * 2
    assert set(extra["derived"]["layer_1"]) == {"mla_0", "mla_1"}
    got = model.apply({"params": params}, dict(batch, **extra),
                      cond_mask=mask)
    assert rel(got, reference(params, m, batch, mask)) < TOL
    assert model.cond_cache_bytes(SIDE) == {
        "latent": 2 * 2 * 16 * (16 + 8) * 4}
    assert model.window_key_columns(SIDE) == (0, 0)
    # a latent of the wrong attention handed to the second one shows
    swapped = dict(extra, layer_cache=tuple((a, a) for a, _ in cache))
    wrong = model.apply({"params": params}, dict(batch, **swapped),
                        cond_mask=mask)
    assert rel(wrong, got) > 100 * TOL


@pytest.mark.parametrize("attention", ["xla", "kernel"])
def test_heads_of_the_cells_widths_match_the_reference(attention):
    """Heads of 128 + 64 on 128, the cell's (`shares_key_part`): both
    attentions of a double layer derive `q_b`'s nope and rotary columns
    apart, the pair-swapped kernel for the rotary columns ALONE and the
    keys' columns without an identity block, rotate the rotary operand
    where its product writes it, and hand the attention kernel two
    products a score; the rotated shared key goes in as ONE (B, Lk, 64)
    operand. The reference's forward on whole 192-wide heads, in one pass
    and from the once-a-call pass's latents."""
    cfg = small_cfg(**{
        "model.tokens.num_attention_heads": 2,
        "model.tokens.qk_nope_head_dim": 128,
        "model.tokens.qk_rope_head_dim": 64, "model.tokens.v_head_dim": 128,
        "model.use_flash_attention": attention == "kernel"})
    assert token_denoiser.shares_key_part(cfg.model.tokens)
    assert token_denoiser.shares_key_part(
        get_preset("lcf_denoiser256").model.tokens)
    assert not token_denoiser.shares_key_part(small_cfg().model.tokens)
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    want = ref.forward(params, token_check_scmoe.model_sizes(cfg), batch,
                       mask)
    got = model.apply({"params": params}, batch, cond_mask=mask)
    assert rel(got, want) < TOL
    cond = {k: batch[k][:1] for k in ("x", "R1", "t1", "K")}
    extra = model.precompute(params, cond)
    for n in ("mla_0", "mla_1"):
        assert set(extra["derived"]["layer_0"][n]) == {
            "q_b_nope", "q_b_rope", "q_b_rope_pair", "k_nope", "v_b"}
    assert extra["layer_cache"][0][1][1].shape == (2, 16, 64)
    got = model.apply({"params": params}, dict(batch, **extra),
                      cond_mask=mask)
    assert rel(got, want) < TOL


def test_the_layer_is_the_equations_written_out(small):
    """One double layer over both frames at once (no cache: every token
    sees every token, so the reference's mask is lifted) — the branch
    leaves after the first attention and joins after the second MLP."""
    cfg, model, params, _, _, m = small
    p = token_denoiser.laid_over(params["layer_1"],
                                 model.layer.derive(1, params["layer_1"]))
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(2, 16, 64)), jnp.float32)
    tables = model.layer.tables(np.arange(16))
    got, own, (counts, chosen) = model.layer(1, p, h, tables, None)
    assert len(own) == 2 and chosen.shape == (2, 16, 6)
    k = cfg.model.tokens

    def norm(x, s):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * s

    def mla(q, x):     # x + MLA(N(x)): the program's own sublayer
        return model.layer._mla(q, x, tables, None)

    h1, own0 = mla(p["mla_0"], h)
    b = norm(h1, p["mlp_norm_0"]["scale"])
    flat = b.reshape(32, 64)
    gates, ids = token_denoiser.route(flat, p["router"], k)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(
        chosen).reshape(32, 6))
    moe = (ref.experts_part(p["experts"], m, flat, gates, ids, "f32")[0]
           + ref.identity_part(m, flat, gates, ids)).reshape(2, 16, 64)
    h2 = h1 + ref.gated_mlp(p["mlp_0"], b, "f32")

    def second_half(x):
        h3 = mla(p["mla_1"], x)[0]
        return h3 + ref.gated_mlp(
            p["mlp_1"], norm(h3, p["mlp_norm_1"]["scale"]), "f32")

    want = second_half(h2) + moe
    assert rel(got, want) < TOL
    np.testing.assert_array_equal(own[0][0], own0[0])
    # joined one sublayer early it is another layer
    assert rel(second_half(h2 + moe), want) > 100 * TOL


def test_the_latents_are_scaled_after_their_norms_and_the_rotary_key_is_not():
    """c_kv = RMSNorm(c)·(hidden / kv rank)^½ is what the cache holds; the
    shared key part is rotated and never scaled; with the flags off the
    latents stay at their norms' output."""
    cfg = small_cfg()
    model, params = seeded(cfg)
    p = token_denoiser.laid_over(params["layer_0"],
                                 model.layer.derive(0, params["layer_0"]))
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(1, 16, 64)), jnp.float32)
    tables = model.layer.tables(np.arange(16))
    _, (c_kv, k_rope) = model.layer._mla(p["mla_0"], h, tables, None)
    a = token_denoiser.rms_norm(h, p["mla_0"]["norm"]["scale"], 1e-5)
    kv_a = a @ p["mla_0"]["kv_a"]["kernel"]
    want = token_denoiser.rms_norm(kv_a[..., :16],
                                   p["mla_0"]["kv_norm"]["scale"], 1e-5)
    np.testing.assert_allclose(c_kv, want * (64 / 16) ** 0.5, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(
        k_rope, ref.rope_rotate(kv_a[..., 16:], np.arange(16), 1e7),
        rtol=1e-5, atol=1e-6)
    plain = build_denoiser(small_cfg(**{
        "model.tokens.mla_scale_q_lora": False,
        "model.tokens.mla_scale_kv_lora": False}).model)
    _, (c_plain, _) = plain.layer._mla(p["mla_0"], h, tables, None)
    np.testing.assert_allclose(c_plain, want, rtol=1e-5, atol=1e-6)


def test_the_cut_is_the_uncut_stacks_first_layers():
    """The configuration runs the first 4 of 28 double layers: at the small
    size, the 2-layer cut's ε̂ is the head on the UNCUT 3-layer reference's
    hidden state after layer 2, on the uncut tree's own weights."""
    uncut = small_cfg(**{"model.tokens.num_layers": 3})
    _, params3 = seeded(uncut)
    model = build_denoiser(small_cfg().model)
    params2 = {g: v for g, v in params3.items() if g != "layer_2"}
    batch, mask = doubled_batch()
    m3 = token_check_scmoe.model_sizes(uncut)
    m2 = dict(m3, num_layers=2, num_hidden_layers=2)
    want = ref.forward(params3, m2, batch, mask)
    got = model.apply({"params": params2}, batch, cond_mask=mask)
    assert rel(got, want) < TOL
    assert rel(ref.forward(params3, m3, batch, mask), want) > 0.1


def test_the_three_controls_and_the_precision_all_show():
    """The reference's own controls at the small size: the identity part
    left out, the branch joined one sublayer early, the latent scales left
    out, and bfloat16 inputs, each far past the tolerance."""
    cfg = small_cfg()
    _, params = seeded(cfg)
    batch, mask = doubled_batch()
    m = token_check_scmoe.model_sizes(cfg)
    want = reference(params, m, batch, mask)
    assert token_check_scmoe.CONTROLS == ref.CONTROLS
    for control in ref.CONTROLS:
        assert rel(ref.forward(params, m, batch, mask, control=control),
                   want) > 100 * TOL, control
    assert rel(ref.forward(params, m, batch, mask, "bf16"), want) > 100 * TOL


def test_guided_eps_through_make_sampler(small):
    cfg, model, params, _, _, m = small
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, 4),
                           cfg.diffusion, trajectory_every=1)
    cond = {k: jnp.asarray(v) for k, v in synth_data.cond_views(
        1, SIDE, 11).items()}
    final, traj = sampler(params, jax.random.PRNGKey(2), cond)
    assert traj.shape == (4, 1, SIDE, SIDE, 3)
    assert bool(jnp.isfinite(traj).all())
    np.testing.assert_array_equal(final, traj[-1])


def test_routing_counts_and_choices_are_the_programs_own(small):
    """A row a layer; ids up to router_width − 1; the counts are of the
    held real experts alone."""
    cfg, model, params, batch, mask, m = small
    counts = np.asarray(model.routing_counts(params, batch, mask))
    choice = np.asarray(model.routing_choices(params, batch, mask))
    assert counts.shape == (2, 8) and choice.shape == (2, 2, 32, 6)
    assert choice.min() >= 0 and choice.max() < 48
    assert (choice >= 32).any() and (choice < 8).any()
    own = choice[:, :, 16:]
    np.testing.assert_array_equal(
        counts, np.stack([np.bincount(o[o < 8], minlength=8) for o in own]))
    got = token_check_scmoe.program_choices(model, params, batch, mask)
    np.testing.assert_array_equal(got, choice)
    shares = token_check_scmoe.choice_shares(got, m)
    assert shares["zero_choice_share"] == pytest.approx(
        float((own >= 32).mean()))
    assert shares["tokens_without_held_share"] == pytest.approx(
        float((~(own < 8).any(-1)).mean()))
    assert shares["held_rows_per_layer_step"] == pytest.approx(
        counts.sum() / 2)
    np.testing.assert_array_equal(token_check_scmoe.program_counts(
        model, params, batch, mask), counts)


# ---------------------------------------------------------------------------
# The router wider than the experts; the branch's two parts; the shares
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def wide():
    """The router at the PUBLISHED width — 512 real + 256 identity outputs,
    top-12 — on a hidden size of 64, experts 0–15 held."""
    cfg = small_cfg(**{"model.tokens.n_routed_experts": 512,
                       "model.tokens.zero_expert_num": 256,
                       "model.tokens.moe_topk": 12,
                       "model.tokens.held_experts": [0, 16]})
    model, params = seeded(cfg, seed=8)
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.normal(size=(256, 64)), jnp.float32)
    return cfg, params["layer_1"], b, token_check_scmoe.model_sizes(cfg)


def with_held(k, held):
    return dataclasses.replace(k, held_experts=held)


def test_route_over_768_outputs_softmax_bias_in_the_choice_alone(wide):
    cfg, p, b, m = wide
    k = cfg.model.tokens
    assert (k.router_width, k.num_experts_per_tok) == (768, 12)
    assert p["router"]["kernel"].shape == (64, 768)
    assert k.router_activation == "softmax" and not k.norm_topk_prob
    scores = jax.nn.softmax(b @ p["router"]["kernel"], axis=-1)
    top_p, top_i = token_denoiser.route(b, p["router"], k)
    # a token's twelve choices hold a varying count of real experts
    real = np.asarray((top_i < 512).sum(axis=1))
    assert real.min() <= 5 and real.max() >= 11 and 7 < real.mean() < 9
    # the gate is 6 × the score, not renormalised
    np.testing.assert_allclose(
        np.asarray(top_p), 6.0 * np.asarray(jnp.take_along_axis(
            scores, top_i, axis=1)), rtol=1e-6)
    assert float(top_p.sum(axis=1).std()) > 0.01
    # the reference's choice and gates, where its margin is clear
    gates, ids, gap, _, _ = ref.router(p["router"], m, b)
    clear = np.asarray(gap) > 1e-7
    assert clear.mean() > 0.9
    np.testing.assert_array_equal(np.asarray(top_i)[clear],
                                  np.asarray(ids)[clear])
    np.testing.assert_allclose(np.asarray(top_p)[clear],
                               np.asarray(gates)[clear], rtol=1e-5)
    # the bias moves a choice and never a gate: one that decides it
    forced = dict(p["router"], bias=jnp.zeros((768,)).at[
        jnp.asarray([3, 600, 700])].set(1.0))
    f_p, f_i = token_denoiser.route(b, forced, k)
    assert all({3, 600, 700} <= set(row) for row in np.asarray(f_i).tolist())
    np.testing.assert_allclose(
        np.asarray(f_p), 6.0 * np.asarray(jnp.take_along_axis(
            scores, f_i, axis=1)), rtol=1e-6)
    # and the seeded one (a tenth of a uniform score) moves some choices
    none = dict(p["router"], bias=jnp.zeros((768,)))
    moved = np.asarray(jnp.sort(token_denoiser.route(b, none, k)[1], 1)
                       != jnp.sort(top_i, 1)).any(axis=1)
    assert 0.02 < moved.mean() < 0.9


def test_the_benchmarks_bias_is_on_the_scores_scale(wide):
    _, p, _, _ = wide
    bias = np.asarray(p["router"]["bias"], np.float64)
    assert 0.05 / 768 < bias.std() < 0.2 / 768 and abs(bias.mean()) < 3e-5


@pytest.mark.parametrize("case", ["none-held", "all-identity", "all-held",
                                  "seeded"])
def test_the_branch_on_tokens_with_no_held_choice_and_identities(wide, case):
    """The held experts' part and the identity part against the
    reference's, on choices forced by a bias: every choice an absent
    expert (the held part exactly 0), every choice an identity (the held
    part 0, m = b × the gates' sum), every choice held, and the seed's."""
    cfg, p, b, m = wide
    k = cfg.model.tokens
    ids = {"none-held": range(100, 112), "all-identity": range(520, 532),
           "all-held": range(2, 14)}.get(case)
    router = p["router"] if ids is None else dict(
        p["router"], bias=jnp.zeros((768,)).at[jnp.asarray(list(ids))].set(
            1.0))
    top_p, top_i = token_denoiser.route(b, router, k)
    part, counts = token_denoiser.held_expert_part(b, top_p, top_i,
                                                   p["experts"], k)
    zero = token_denoiser.identity_part(b, top_p, top_i, k)
    want, want_counts = ref.experts_part(p["experts"], m, b, top_p, top_i,
                                         "f32")
    want_zero = ref.identity_part(m, b, top_p, top_i)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(want_counts))
    if case in ("none-held", "all-identity"):
        assert int(counts.sum()) == 0
        assert float(jnp.max(jnp.abs(part))) == 0.0 == float(
            jnp.max(jnp.abs(want)))
    else:
        assert rel(part, want) < TOL
    if case == "all-identity":
        np.testing.assert_allclose(
            zero, b * top_p.sum(axis=1, keepdims=True), rtol=1e-6)
    if case in ("none-held", "all-held"):
        assert float(jnp.max(jnp.abs(zero))) == 0.0
    else:
        assert rel(zero, want_zero) < TOL
    if case == "seeded":    # 0.25 held choices a token: most have none
        held = np.asarray((top_i < 16).sum(axis=1))
        assert (held == 0).mean() > 0.5 and held.max() >= 2
    # the check's own read-out of the branch holds both parts apart
    aux = {"b": b[None], "gates": top_p[None], "chosen": top_i[None],
           "routed": want[None], "zero": want_zero[None]}
    experts = token_check_scmoe.expert_layer(cfg)
    miss = token_check_scmoe.routed_miss(experts, p, aux, 0, 1)
    assert miss.shape == (1, 256) and miss.max() < 1e-4
    if case == "seeded":
        # an identity id given an expert's row: the lost-row count sees it
        wrong = dict(aux, chosen=jnp.where(top_i >= 512, top_i - 512,
                                           top_i)[None])
        assert token_check_scmoe.routed_miss(
            experts, p, wrong, 0, 1).max() > 0.08


def test_the_32_shares_and_the_identity_part_once_are_the_uncut_layer():
    """`held_experts` (0, 1), (1, 1), … (31, 1): the parts the 32 shares of
    an expert-parallel layer compute, plus the identity part counted ONCE,
    add up to the uncut reference's whole branch — and with the two dense
    MLPs and attentions (replicated: every share computes them alike) to
    its whole layer."""
    cfg = small_cfg(**{"model.tokens.held_experts": [0, 32]})
    model, params = seeded(cfg, seed=8)
    p = params["layer_1"]
    k, m = cfg.model.tokens, token_check_scmoe.model_sizes(cfg)
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    # the uncut reference's whole layer, and the tokens its branch reads
    want, aux = ref.layer(p, m, h, parts=True)
    b = aux["b"].reshape(64, 64)
    top_p, top_i = token_denoiser.route(b, p["router"], k)
    total, counted, held_by_token = 0.0, 0, []
    for s in range(32):
        stack = jax.tree.map(lambda a: a[s:s + 1], p["experts"])
        part, counts = token_denoiser.held_expert_part(
            b, top_p, top_i, stack, with_held(k, (s, 1)))
        share, _ = ref.experts_part(p["experts"], m, b, top_p, top_i, "f32",
                                    (s, 1))
        if float(jnp.max(jnp.abs(share))) == 0.0:
            assert float(jnp.max(jnp.abs(part))) == 0.0
        else:
            assert rel(part, share) < TOL
        total, counted = total + part, counted + int(counts.sum())
        held_by_token.append(np.asarray((top_i == s).sum(axis=1)))
    real = int((top_i < 32).sum())
    assert counted == real < b.shape[0] * 6      # the rest are identities
    assert {0, 1} <= set(np.concatenate(held_by_token).tolist())
    moe = total + token_denoiser.identity_part(b, top_p, top_i, k)
    assert rel(moe, aux["routed"].reshape(64, 64)
               + aux["zero"].reshape(64, 64)) < TOL
    # the identity part counted 32 times over would be another layer
    assert rel(total + 32 * token_denoiser.identity_part(b, top_p, top_i, k),
               moe) > 1.0
    # the whole layer — what every share computes alike, then that sum —
    # frame by frame through the two latents, as the reference's one pass
    whole = build_denoiser(cfg.model)
    q = token_denoiser.laid_over(p, whole.layer.derive(1, p))
    first, cache, _ = whole.layer(1, q, h[:, :16],
                                  whole.layer.tables(np.arange(16)), None)
    second, _, _ = whole.layer(1, q, h[:, 16:], whole.layer.tables(
        np.arange(16) + 16), cache)
    assert rel(jnp.concatenate([first, second], axis=1), want) < TOL


# ---------------------------------------------------------------------------
# Vocabulary, configuration, refusals
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("path,want,part", [
    ("jit(sampler)/precompute/og.layer_0/lk.mla_core/pt.kernel/flash_fwd",
     ("layer_0", "mla_core"), "mla_core.kernel"),
    ("jit(sampler)/while/body/lk.update/og.layer_1/lk.moe_zero/mul",
     ("layer_1", "moe_zero"), "moe_zero"),
    ("jit(sampler)/while/body/lk.update/og.layer_1/lk.dense_mlp/pt.matmul/"
     "dot_general", ("layer_1", "dense_mlp"), "dense_mlp.matmul"),
    ("jit(sampler)/while/body/lk.update/og.layer_2/lk.moe_route/pt.gather/"
     "gather", ("layer_2", "moe_route"), "moe_route.gather"),
    ("jit(sampler)/while/body/lk.update/og.layer_3/lk.moe_experts/pt.kernel/"
     "gmm", ("layer_3", "moe_experts"), "moe_experts.kernel"),
])
def test_layer_of_reads_the_trunks_paths(path, want, part):
    assert layer_of(path) == want
    assert layer_part_of(path) == (want[0], part)


def test_compiled_sampler_stamps_are_the_trunks_vocabulary():
    """Every stamp of the compiled sampler is one of this trunk's kinds,
    none doubled; every layer stamps every layer kind."""
    cfg = small_cfg()
    model, params = seeded(cfg)
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, 4),
                           cfg.diffusion, trajectory_every=1)
    cond = {k: jnp.asarray(v) for k, v in synth_data.cond_views(
        1, SIDE, 9).items()}
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        text = sampler.lower(params, jax.ShapeDtypeStruct((2,), jnp.uint32),
                             cond).compile().as_text()
    finally:
        jax.config.update(flag, before)
    paths = {p.split(";", 1)[0]
             for p in re.findall(r'op_name="([^"]+)"', text)}
    seen = {}
    for path in paths:
        stamps = re.findall(r"lk\.(\w+)", path)
        assert len(stamps) == len(set(stamps)), path
        block, kind = layer_of(path)
        seen.setdefault(kind, set()).add(block)
    assert set(seen) - {"other", "unattributed"} == set(
        SCMOE_TOKEN_LAYER_KINDS)
    for kind in ("mla_proj", "mla_core", "dense_mlp", "moe_route",
                 "moe_experts", "moe_zero"):
        assert seen[kind] == {"layer_0", "layer_1"}, kind


def test_preset_is_the_published_config_cut_as_the_file_says():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lcf_denoiser256.json")) as fh:
        conf = json.load(fh)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = [c for c in json.load(fh)["configs"]
                 if c["name"] == "lcf_denoiser256"][0]
    assert entry["source"] == conf["source"] == (
        "https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/"
        "main/config.json")
    assert entry["reduced"] == conf["reduced"] == [
        "num_layers", "n_routed_experts", "sample_timesteps"]
    assert conf["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                 "sample_timesteps": 256}
    # every number of the catalog row's `config`, under the same key
    published = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
        "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
        "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072,
        "rms_norm_eps": 1e-05, "rope_theta": 10000000,
        "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12}
    for name, value in published.items():
        assert (conf["published"][name] if name in conf["reduced"]
                else conf[name]) == value, name
    assert (conf["num_layers"], conf["n_routed_experts"]) == (4, 16)
    cfg = get_preset(conf["preset"]).validate()
    k = cfg.model.tokens
    assert isinstance(k, LongcatFlashTrunkConfig)
    m = token_check_scmoe.model_sizes(cfg)
    for name, value in conf.items():
        if name in m and name not in ("name", "n_routed_experts"):
            assert m[name] == value, name
    # the router keeps its published width; 16 of the 512 are held
    assert (k.n_routed_experts, k.router_width, tuple(k.held_experts)) == (
        512, 768, (0, 16))
    assert (k.num_hidden_layers, k.qk_head_dim, cfg.data.img_sidelength) == (
        4, 192, 256)
    assert conf["assumed"]["router_replicas"] == 1
    shapes = token_denoiser.param_shapes(cfg.model)
    size = {g: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(t))
            for g, t in shapes.items()}
    layer = {n: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(t))
             for n, t in shapes["layer_0"].items()}
    assert 90.5e6 < layer["mla_0"] == layer["mla_1"] < 90.6e6
    assert 226.4e6 < layer["mlp_0"] == layer["mlp_1"] < 226.5e6
    assert layer["router"] == 6144 * 768 + 768
    assert layer["experts"] == 16 * 3 * 6144 * 2048
    assert 1242.7e6 < size["layer_0"] < 1242.9e6
    trunk = sum(v for g, v in size.items() if g.startswith("layer_"))
    assert 4.970e9 < trunk < 4.972e9               # 9.94 GB in bfloat16
    assert sum(size.values()) == 5061678080        # with the adapters
    model = build_denoiser(cfg.model)
    assert model.cond_cache_bytes(256) == {
        "latent": 4 * 2 * 4096 * (512 + 64) * 2}


def test_token_trunks_are_six_and_read_back_by_their_keys():
    # the sixth of them (a seventh came with PR 47, behind it)
    assert len(TOKEN_TRUNKS) >= 6 and TOKEN_TRUNKS[5] is \
        LongcatFlashTrunkConfig
    seen = set()
    for name in PRESET_NAMES:
        cfg = get_preset(name)
        if cfg.model.family != "tokens":
            continue
        again = Config.from_json(cfg.to_json())
        assert again == cfg
        assert type(again.model.tokens) is type(cfg.model.tokens)
        seen.add(type(cfg.model.tokens))
    assert seen == set(TOKEN_TRUNKS)
    small = small_cfg()
    assert Config.from_json(small.to_json()) == small


def test_config_refusals():
    for over, word in [
        ({"model.tokens.held_experts": [30, 4]}, "held_experts"),
        ({"model.tokens.held_experts": [32, 8]}, "held_experts"),
        ({"model.tokens.moe_topk": 49}, "moe_topk"),
        ({"model.tokens.zero_expert_type": "zero"}, "zero_expert_type"),
        ({"model.tokens.attention_method": "MHA"}, "attention_method"),
        ({"model.tokens.attention_bias": True}, "attention_bias"),
        ({"model.tokens.qk_rope_head_dim": 7}, "qk_rope_head_dim"),
        ({"data.img_sidelength": 18}, "patch_size"),
    ]:
        with pytest.raises(ValueError, match=word):
            small_cfg(**over)
    # the router may be as wide as asked: top-k past the REAL experts
    small_cfg(**{"model.tokens.moe_topk": 40})


def test_train_ring_and_mesh_refuse_the_trunk_by_name():
    from novel_view_synthesis_3d_tpu.sample import ddpm
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    cfg = small_cfg()
    model = build_denoiser(cfg.model)
    with pytest.raises(NotImplementedError, match="model.family='tokens'"):
        Trainer(config=cfg, use_grain=False)
    with pytest.raises(NotImplementedError, match="model.family='tokens'"):
        ddpm.make_ring_step_fn(model, cfg.diffusion, k_max=0)

    class FourChips:
        shape = {"data": 4}

    with pytest.raises(NotImplementedError, match="one chip"):
        build_denoiser(cfg.model, mesh=FourChips())


def test_a_gradient_through_the_expert_branch_raises_by_name():
    """Forward only, as the other trunks: the grouped product has no
    backward and says so."""
    cfg = small_cfg()
    model, params = seeded(cfg)
    batch, mask = doubled_batch()

    def loss(p):
        return jnp.sum(model.apply({"params": p}, batch, cond_mask=mask))

    with pytest.raises(NotImplementedError):
        jax.grad(loss)(params)

"""The token denoiser's seventh trunk (models/token_denoiser.py,
`LagunaLayer`: grouped-query heads whose COUNT depends on the layer on one
set of key/value heads, a rotary law a layer kind, a one-sided window with
a truncated cache entry, a sigmoid gate a head on the attention's output,
a leading dense layer, then a softmax router top-k renormalised and scaled
beside a shared expert) against the benchmark's plain reference
(benchmarks/reference/lgs_ref.py) and against the equations written out
here, at a small size on the CPU, in float32 on both sides: 16 tokens a
frame under a window of 8, 5 layers (dense + full, window x 3, full), 4
heads in a full layer and 6 under the window on 2 key/value heads of 16,
16 experts top-4 of width 32 on independent router columns. Weights are
the benchmark's seeded ones (benchmarks/token_weights.py).

Tolerances as tests/test_token_denoiser.py: both sides compute in float32
and differ by the order of their sums; TOL = 2e-5 is ~50× what they read.
"""

import dataclasses
import json
import math
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
import synth_data  # noqa: E402
import token_check  # noqa: E402
import token_check_headmix  # noqa: E402
import token_weights  # noqa: E402
from novel_view_synthesis_3d_tpu.config import (  # noqa: E402
    PRESET_NAMES, TOKEN_TRUNKS, Config, LagunaTrunkConfig, get_preset)
from novel_view_synthesis_3d_tpu.diffusion.schedules import (  # noqa: E402
    sampling_schedule)
from novel_view_synthesis_3d_tpu.models import (  # noqa: E402
    build_denoiser, token_denoiser)
from novel_view_synthesis_3d_tpu.models.vocab import (  # noqa: E402
    HEADMIX_TOKEN_LAYER_KINDS, layer_of)
from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler  # noqa: E402

TOL = 2e-5
SIDE = 16
SMALL = {
    "model.tokens.hidden_size": 64, "model.tokens.num_hidden_layers": 5,
    "model.tokens.num_key_value_heads": 2, "model.tokens.head_dim": 16,
    "model.tokens.num_attention_heads_per_layer": [4, 6, 6, 6] * 12,
    "model.tokens.sliding_window": 8,
    "model.tokens.intermediate_size": 96,
    "model.tokens.num_experts": 16, "model.tokens.num_experts_per_tok": 4,
    "model.tokens.moe_intermediate_size": 32,
    "model.tokens.shared_expert_intermediate_size": 32,
    "model.tokens.held_experts": [0, 16], "data.img_sidelength": SIDE,
    "model.dtype": "float32", "model.param_dtype": "float32",
    "diffusion.sample_timesteps": 4,
}
ref = harness.load_module(os.path.join(
    ROOT, "benchmarks", "reference", "lgs_ref.py"), "lgs_ref")


def small_cfg(**over):
    return get_preset("lgs_denoiser256").override(
        **dict(SMALL, **over)).validate()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def seeded(cfg, seed=5, router_replicas=1):
    model = build_denoiser(cfg.model)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}))["params"]
    return model, token_weights.make_weights(
        seed, shapes, router_replicas=router_replicas)


def doubled_batch(seed=3, rows=2):
    """One view twice (conditional row, unconditional row)."""
    cond = {k: jnp.asarray(np.repeat(v, rows, axis=0))
            for k, v in synth_data.cond_views(1, SIDE, seed).items()}
    key = jax.random.PRNGKey(seed)
    z = jnp.repeat(jax.random.normal(key, (1, SIDE, SIDE, 3)), rows, axis=0)
    return dict(cond, z=z, logsnr=jnp.full((rows,), 0.7)), \
        jnp.asarray([1.0, 0.0] * (rows // 2))


@pytest.fixture(scope="module", params=["xla", "kernel"])
def small(request):
    """The trunk through XLA's attention and through the Pallas kernel
    (interpreted): grouped heads of two counts and the band in both."""
    cfg = small_cfg(**{
        "model.use_flash_attention": request.param == "kernel"})
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    return cfg, model, params, batch, mask, \
        token_check_headmix.model_sizes(cfg)


@pytest.fixture(scope="module")
def small_once():
    """As `small`, once: for what reads the reference, the router or the
    expert layer alone and never the attention path."""
    cfg = small_cfg()
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    return cfg, model, params, batch, mask, \
        token_check_headmix.model_sizes(cfg)


def test_full_forward_matches_the_reference(small):
    cfg, model, params, batch, mask, m = small
    eps = model.apply({"params": params}, batch, cond_mask=mask, train=False)
    want = ref.forward(params, m, batch, mask)
    assert eps.shape == (2, SIDE, SIDE, 3) and eps.dtype == jnp.float32
    assert rel(eps, want) < TOL
    assert rel(eps[0], eps[1]) > 1e-2   # the ray term is masked in one row


def test_precompute_then_step_matches_the_full_forward(small):
    """Prefill of the conditioning frame into each layer's cache — a full
    layer's whole frame, a window layer's last window − 1 rows, keys
    rotated by the layer's own law —, then the target's tokens alone
    against [cache ; own], is the reference's ONE forward over both frames
    under its dense (2L, 2L) predicate."""
    cfg, model, params, batch, mask, m = small
    cond = {k: v[:1] for k, v in batch.items() if k not in ("z", "logsnr")}
    pre = model.precompute(params, cond)
    k = cfg.model.tokens
    assert set(pre) == {"layer_cache"}
    assert len(pre["layer_cache"]) == k.num_hidden_layers == 5
    L = (SIDE // k.patch_size) ** 2
    for i, (keys, values) in enumerate(pre["layer_cache"]):
        rows = k.sliding_window - 1 if k.is_window(i) else L
        assert keys.shape == values.shape == (
            2, rows, k.num_key_value_heads, k.head_dim), i
    eps = model.apply({"params": params}, dict(batch, **pre), cond_mask=mask,
                      train=False)
    assert rel(eps, ref.forward(params, m, batch, mask)) < TOL
    batch2 = dict(batch, z=batch["z"] * 0.5 + 0.1,
                  logsnr=jnp.full((2,), -2.0))
    eps2 = model.apply({"params": params}, dict(batch2, **pre),
                       cond_mask=mask, train=False)
    assert rel(eps2, ref.forward(params, m, batch2, mask)) < TOL


# ---------------------------------------------------------------------------
# Each layer kind against the equations, written out in numpy: no function
# of the program's or the reference's is called on this side.
# ---------------------------------------------------------------------------
def _rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _frequencies(law, dim):
    theta = float(law["rope_theta"])
    freq = theta ** (-np.arange(0, dim, 2) / dim)
    if law["rope_type"] != "yarn":
        return freq
    orig = law["original_max_position_embeddings"]
    low, high = (dim * math.log(orig / (law[b] * 2 * math.pi))
                 / (2 * math.log(theta)) for b in ("beta_fast", "beta_slow"))
    low, high = max(math.floor(low), 0), min(math.ceil(high), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return freq * (1 - ramp) + freq / law["factor"] * ramp


def _rotate(x, law):
    """x (S, heads, D): a prefix of every head's lanes rotated at positions
    0 … S−1, pairs (j, j + half) inside the prefix."""
    S, _, D = x.shape
    dim = int(D * law["partial_rotary_factor"])
    ang = np.arange(S)[:, None] * _frequencies(law, dim)[None]
    af = law.get("attention_factor", 1.0)
    cos, sin = np.cos(ang)[:, None] * af, np.sin(ang)[:, None] * af
    out = x.copy()
    a, b = x[..., :dim // 2], x[..., dim // 2:dim]
    out[..., :dim // 2] = a * cos - b * sin
    out[..., dim // 2:dim] = b * cos + a * sin
    return out


def layer_by_the_equations(p, m, i, h):
    """ISSUE 47's layer i over one row h (S, hidden), S = two frames."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    eps = m["rms_norm_eps"]
    S, _ = h.shape
    N, NKV, D = m["num_attention_heads_per_layer"][i], \
        m["num_key_value_heads"], m["head_dim"]
    sliding = m["layer_types"][i] == "sliding_attention"
    law = m["rope_parameters"][m["layer_types"][i]]
    a = _rms(h, p["attn_norm"]["scale"], eps)
    q = _rotate((a @ p["q"]["kernel"]).reshape(S, N, D), law)
    k = _rotate((a @ p["k"]["kernel"]).reshape(S, NKV, D), law)
    v = (a @ p["v"]["kernel"]).reshape(S, NKV, D)
    g = 1.0 / (1.0 + np.exp(-(a @ p["head_gate"]["kernel"])))     # (S, N)
    pos = np.arange(S)
    frame = pos // (S // 2)
    seen = frame[:, None] >= frame[None, :]
    if sliding:
        seen &= pos[:, None] - pos[None, :] < m["sliding_window"]
    o = np.zeros((S, N, D))
    for n in range(N):
        kv = n // (N // NKV)
        s = np.where(seen, q[:, n] @ k[:, kv].T / math.sqrt(D), -np.inf)
        w = np.exp(s - s.max(axis=-1, keepdims=True))
        o[:, n] = g[:, n, None] * ((w / w.sum(axis=-1, keepdims=True))
                                   @ v[:, kv])
    h = h + o.reshape(S, N * D) @ p["o"]["kernel"]
    b = _rms(h, p["mlp_norm"]["scale"], eps)

    def mlp(q, x):
        return (_silu(x @ q["gate"]["kernel"]) * (x @ q["up"]["kernel"])) \
            @ q["down"]["kernel"]

    if m["mlp_layer_types"][i] == "dense":
        return h + mlp(p["mlp"], b)
    logits = b @ p["router"]["kernel"]
    s = np.exp(logits - logits.max(axis=-1, keepdims=True))
    s /= s.sum(axis=-1, keepdims=True)
    first, count = m["held_experts"]
    out = h + mlp(p["shared"], b)
    for t in range(S):
        top = np.argsort(-s[t])[:m["num_experts_per_tok"]]
        gates = s[t, top] / s[t, top].sum() * m["moe_routed_scaling_factor"]
        for e, gate in zip(top, gates):
            if first <= e < first + count:
                one = jax.tree.map(lambda a: a[e - first], p["experts"])
                out[t] += gate * mlp(one, b[t])
    return out


def through_the_cache(model, i, p, h):
    """The program's layer i over h (B, 2L, hidden) as a sampler runs it:
    the first frame alone, then the second against the first's cache
    entry. → (its output over both frames, the second pass's routing)."""
    L = h.shape[1] // 2
    t0, t1 = (model.layer.tables(np.arange(L) + f * L) for f in (0, 1))
    first, cache, _ = model.layer(i, p, h[:, :L], t0, None)
    second, _, routed = model.layer(i, p, h[:, L:], t1, cache)
    return jnp.concatenate([first, second], axis=1), routed


@pytest.mark.parametrize("i", [0, 1, 4])
def test_each_layer_kind_is_the_equations_written_out(small, i):
    """Layer 0 (4 heads, full, yarn on half the lanes, the dense MLP),
    layer 1 (6 heads under the window, plain rotary on all lanes, experts)
    and layer 4 (4 heads, full, experts): the program's layer over the
    conditioning frame and then over the target's tokens through the
    frame's cache entry, and the reference's over both frames at once."""
    cfg, model, params, batch, mask, m = small
    k = cfg.model.tokens
    L = (SIDE // k.patch_size) ** 2
    rng = np.random.default_rng(i)
    h = rng.normal(size=(2 * L, k.hidden_size))
    p = params[f"layer_{i}"]
    want = layer_by_the_equations(p, m, i, h)
    got_ref, _ = ref.layer(p, m, jnp.asarray(h, jnp.float32)[None], i)
    assert rel(got_ref[0], want) < TOL
    got, _ = through_the_cache(model, i, p,
                               jnp.asarray(h, jnp.float32)[None])
    assert rel(got[0], want) < TOL


@pytest.mark.parametrize("i", [1, 2, 3])
def test_a_window_layers_cache_entry_is_its_tail_and_changes_nothing(
        small, i):
    """What a window layer keeps of a frame is its last window − 1 rows of
    keys and values; handed the WHOLE frame's instead, a step gives the
    same result: no target query sees an earlier row."""
    cfg, model, params, batch, mask, m = small
    k = cfg.model.tokens
    L, W = (SIDE // k.patch_size) ** 2, k.sliding_window
    rng = np.random.default_rng(10 + i)
    cond, own = (jnp.asarray(rng.normal(size=(2, L, k.hidden_size)),
                             jnp.float32) for _ in range(2))
    p = params[f"layer_{i}"]
    t0, t1 = (model.layer.tables(np.arange(L) + f * L) for f in (0, 1))
    _, tail, _ = model.layer(i, p, cond, t0, None)
    assert tail[0].shape[1] == tail[1].shape[1] == W - 1
    # the whole frame's keys and values, as a full layer would keep them
    k_full = dataclasses.replace(k, sliding_window=L + 1)
    whole_model = build_denoiser(dataclasses.replace(cfg.model,
                                                     tokens=k_full))
    _, whole, _ = whole_model.layer(i, p, cond, t0, None)
    assert whole[0].shape[1] == L
    np.testing.assert_array_equal(np.asarray(whole[0][:, L - W + 1:]),
                                  np.asarray(tail[0]))
    np.testing.assert_array_equal(np.asarray(whole[1][:, L - W + 1:]),
                                  np.asarray(tail[1]))
    from_tail, _, _ = model.layer(i, p, own, t1, tail)
    from_whole, _, _ = model.layer(i, p, own, t1, whole)
    assert rel(from_tail, from_whole) < TOL


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_planted_fault_fails_the_comparison(small_once, control):
    """What the comparison must be able to see: the reference with the
    head gate left out, the rotary laws swapped between the layer kinds,
    the × 2.5 left out or the window layers at full visibility is not the
    program."""
    cfg, model, params, batch, mask, m = small_once
    eps = model.apply({"params": params}, batch, cond_mask=mask, train=False)
    fault = ref.forward(params, m, batch, mask, "f32", control)
    assert rel(eps, fault) > 1e-2


def test_a_lower_precision_fails_the_comparison(small_once):
    cfg, model, params, batch, mask, m = small_once
    want = ref.forward(params, m, batch, mask)
    assert rel(ref.forward(params, m, batch, mask, "bf16"), want) > 100 * TOL
    assert rel(ref.forward(params, m, batch, mask, "fp8"), want) > 1000 * TOL


def test_reference_mask_is_the_frame_rule_and_the_one_sided_window():
    m = {"layer_types": ["full_attention", "sliding_attention"],
         "sliding_window": 3}
    full, band = ref.visible(m, 0, 8), ref.visible(m, 1, 8)
    # a conditioning token sees its frame only; a target token every key
    assert full[:4, :4].all() and not full[:4, 4:].any() and full[4:].all()
    # one-sided: keys AFTER the query inside its frame stay visible, keys
    # a window or more behind it do not — inside the frame and across
    want = np.array([[p - q < 3 for q in range(8)] for p in range(8)])
    assert (band == (full & want)).all()
    assert band[7, 5] and not band[7, 4] and band[4, 7]
    assert band[4, 2] and not band[4, 1] and not band[6].tolist()[:4].count(
        True)
    assert (ref.visible(m, 1, 8, "full_visibility") == full).all()


def test_the_two_rotary_laws():
    """`tables` hands a pair: yarn over the first half of a head's lanes
    with cos and sin scaled, plain over all of them; each is the
    reference's own frequencies."""
    cfg = get_preset("lgs_denoiser256").validate()
    k = cfg.model.tokens
    m = token_check_headmix.model_sizes(cfg)
    tables = token_denoiser.trunk_layer(cfg.model).tables(
        np.arange(4096, 4100))
    assert set(tables) == {"full_attention", "sliding_attention"}
    cos_f, sin_f = tables["full_attention"]
    cos_s, sin_s = tables["sliding_attention"]
    assert cos_f.shape == (4, 32) and cos_s.shape == (4, 64)
    af = k.rope_parameters.full_attention.attention_factor
    np.testing.assert_allclose(cos_f ** 2 + sin_f ** 2, af * af, rtol=1e-5)
    np.testing.assert_allclose(cos_s ** 2 + sin_s ** 2, 1.0, rtol=1e-5)
    for kind, dim, (cos, _) in (("full_attention", 64, (cos_f, sin_f)),
                                ("sliding_attention", 128, (cos_s, sin_s))):
        law = m["rope_parameters"][kind]
        freq = ref.rotary_frequencies(law, dim)
        np.testing.assert_allclose(freq, _frequencies(law, dim), rtol=1e-12)
        np.testing.assert_allclose(
            cos, np.cos(np.arange(4096, 4100)[:, None] * freq[None])
            * law["attention_factor"], atol=2e-6)
    # yarn: the fastest pairs keep θ's frequency, the slowest take ÷ 128
    law = m["rope_parameters"]["full_attention"]
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    yarn = ref.rotary_frequencies(law, 64)
    np.testing.assert_allclose(yarn[0], plain[0])
    np.testing.assert_allclose(yarn[-1], plain[-1] / 128)


def test_guided_eps_through_make_sampler(small):
    """Every step of `make_sampler(trajectory_every=1)` — no edit to
    sample/ddpm.py — against the reference's guided ε̂."""
    cfg, model, params, _, _, m = small
    n, views = cfg.diffusion.sample_timesteps, 2
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, n),
                           cfg.diffusion, trajectory_every=1)
    cond = {k: jnp.asarray(v) for k, v in synth_data.cond_views(
        views, SIDE, 9).items()}
    key = jax.random.PRNGKey(4)
    final, traj = sampler(params, key, cond)
    assert float(jnp.max(jnp.abs(final - traj[-1]))) == 0.0
    tables = harness.load_module(os.path.join(
        ROOT, "benchmarks", "reference", "xunet_ref.py"), "xunet_ref")
    T, w = cfg.diffusion.timesteps, cfg.diffusion.guidance_weight
    tab = tables.cosine_tables(T, n)
    sample = {"traj": np.asarray(traj[:, 1]), "key": key, "row": 1,
              "cond": {k: np.asarray(a[1]) for k, a in cond.items()},
              "draw_shape": (views, SIDE, SIDE, 3)}
    steps = list(range(n))
    batch, mask, z_ins, noises = token_check.step_inputs(
        tables, tab, T, sample, steps)
    eps = np.asarray(ref.forward(params, m, batch, mask), np.float64)
    got = {"eps": {"f32": eps}, "layer_margin": np.full(
        (1, 2 * n, (SIDE // m["patch_size"]) ** 2), np.inf)}
    rows = token_check.step_rows(m, tab, w, sample, steps, z_ins, noises,
                                 got, 0.0)
    assert sum(r["pixels"] for r in rows) > 100
    assert token_check.sampling_check.pooled(rows, "program") < 10 * TOL


# ---------------------------------------------------------------------------
# The expert layer: the shares of an expert-parallel layer add up
# ---------------------------------------------------------------------------
def test_the_two_shares_add_up_to_the_uncut_layer(small_once):
    """`held_experts` [0, 8] and [8, 8] — the two chips of the deployment
    — give layers whose routed parts, with attention and the shared expert
    counted ONCE, add up to the reference's layer with every expert held;
    every token's top-4 of 16 land on one of the two, renormalised and
    × 2.5."""
    cfg, model, params, batch, mask, m = small_once
    k = cfg.model.tokens
    L = (SIDE // k.patch_size) ** 2
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=(2, 2 * L, k.hidden_size)), jnp.float32)
    p = params["layer_4"]
    uncut, aux = ref.layer(p, m, h, 4, parts=True)
    np.testing.assert_allclose(np.asarray(aux["gates"].sum(axis=-1)), 2.5,
                               rtol=1e-5)
    # without the routed part: what both chips compute alike
    once = uncut - aux["routed"]
    total, counted = once, 0
    for first in (0, 8):
        share = build_denoiser(dataclasses.replace(
            cfg.model, tokens=dataclasses.replace(
                k, held_experts=(first, 8))))
        stack = dict(p, experts=jax.tree.map(
            lambda a: a[first:first + 8], p["experts"]))
        out, (counts, chosen) = through_the_cache(share, 4, stack, h)
        want, _ = ref.layer(p, m, h, 4, held=(first, 8))
        assert rel(out, want) < TOL
        assert chosen.shape == (2, L, 4)
        total, counted = total + (out - once), counted + int(counts.sum())
    assert counted == 2 * L * k.num_experts_per_tok        # none dropped
    assert rel(total, uncut) < TOL


def test_routing_choices_and_counts_are_the_expert_layers(small_once):
    """The leading dense layer has no router: `routing_choices` and
    `routing_counts` have a row an EXPERT layer, and the choices are the
    reference's wherever its margin is clear."""
    cfg, model, params, batch, mask, m = small_once
    k = cfg.model.tokens
    L = (SIDE // k.patch_size) ** 2
    choice = np.asarray(model.routing_choices(params, batch, mask))
    assert token_check_headmix.expert_layers(m) == [1, 2, 3, 4]
    assert choice.shape == (4, 2, 2 * L, k.num_experts_per_tok)
    h = ref.embed(params, m, batch, mask)
    for i in range(k.num_hidden_layers):
        h, parts = ref.layer(params[f"layer_{i}"], m, h, i, parts=True)
        if i == 0:
            assert set(parts) == {"attn"}
            continue
        clear = np.asarray(parts["margin"]) > 1e-4
        np.testing.assert_array_equal(np.sort(choice[i - 1][clear]),
                                      np.sort(np.asarray(parts["chosen"])[
                                          clear]))
    counts = np.asarray(model.routing_counts(params, batch, mask))
    assert counts.shape == (4, 16)
    assert counts.sum(axis=1).tolist() == [2 * L * 4] * 4


def test_a_tied_router_gives_every_token_its_share_of_held_choices():
    """`router_replicas` 2, as the cell's: column e + 8 = column e, so a
    token's top-4 are both replicas of its two best prototypes and experts
    0-7 hold exactly 2 of them, whatever the seed."""
    cfg = small_cfg(**{"model.tokens.held_experts": [0, 8]})
    batch, mask = doubled_batch()
    L = (SIDE // cfg.model.tokens.patch_size) ** 2
    for seed in (5, 6):
        model, params = seeded(cfg, seed, router_replicas=2)
        kernel = np.asarray(params["layer_1"]["router"]["kernel"])
        np.testing.assert_array_equal(kernel[:, :8], kernel[:, 8:])
        choice = np.asarray(model.routing_choices(params, batch, mask))
        assert ((choice < 8).sum(axis=-1) == 2).all()
        counts = np.asarray(model.routing_counts(params, batch, mask))
        assert counts.sum(axis=1).tolist() == [2 * L * 2] * 4


def test_reference_adopts_a_choice_only_inside_the_margin(small_once):
    """lgs_ref.router with the program's choice: a token at a near tie
    takes a set that swaps its 4th for its 5th, is left out (`excluded`)
    for a set that reaches further down, and a token at a clear margin
    keeps the reference's own whatever it is handed."""
    cfg, model, params, batch, mask, m = small_once
    p = params["layer_2"]
    b = jnp.asarray(np.random.default_rng(0).normal(size=(48, 64)),
                    jnp.float32)
    gates, own, gap, _, _ = ref.router(p["router"], m, b)
    order = np.argsort(-np.asarray(b @ p["router"]["kernel"]), axis=1)
    k = m["num_experts_per_tok"]
    swapped = np.concatenate([order[:, :k - 1], order[:, k:k + 1]], axis=1)
    far = np.concatenate([order[:, :k - 1], order[:, -1:]], axis=1)
    thr = float(np.median(np.asarray(gap)))
    near = np.asarray(gap) < thr
    assert near.any() and (~near).any()
    g, chosen, _, adopted, excluded = ref.router(
        p["router"], m, b, jnp.asarray(swapped), thr)
    np.testing.assert_array_equal(np.asarray(adopted), near)
    assert not np.asarray(excluded).any()
    np.testing.assert_array_equal(np.asarray(chosen)[near], swapped[near])
    np.testing.assert_array_equal(np.asarray(chosen)[~near],
                                  np.asarray(own)[~near])
    np.testing.assert_allclose(np.asarray(g.sum(axis=1)), 2.5, rtol=1e-5)
    _, chosen, _, adopted, excluded = ref.router(
        p["router"], m, b, jnp.asarray(far), thr)
    assert not np.asarray(adopted).any()
    np.testing.assert_array_equal(np.asarray(excluded), near)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(own))
    # the program's gates are the reference's
    top_p, top_i = token_denoiser.route(b, p["router"], cfg.model.tokens)
    np.testing.assert_array_equal(np.asarray(top_i), np.asarray(own))
    np.testing.assert_allclose(np.asarray(top_p), np.asarray(gates),
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# Scopes, the preset, the config
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("path,want", [
    ("jit(sample)/lk.update/og.layer_3/lk.gqa_proj/dot_general",
     ("layer_3", "gqa_proj")),
    ("jit(sample)/lk.update/og.layer_0/lk.attn_full/flash_fwd",
     ("layer_0", "attn_full")),
    ("jit(sample)/lk.update/og.layer_1/lk.attn_window/pt.kernel/flash_fwd",
     ("layer_1", "attn_window")),
    ("jit(sample)/lk.update/og.layer_1/lk.attn_gate/logistic",
     ("layer_1", "attn_gate")),
    ("jit(sample)/precompute/og.layer_2/lk.attn_gate/multiply",
     ("layer_2", "attn_gate")),
    ("jit(sample)/lk.update/og.layer_0/lk.dense_mlp/pt.matmul/dot_general",
     ("layer_0", "dense_mlp")),
    ("jit(sample)/lk.update/og.layer_4/lk.moe_shared/pt.matmul/dot_general",
     ("layer_4", "moe_shared")),
])
def test_layer_of_reads_the_trunks_paths(path, want):
    assert layer_of(path) == want


def test_compiled_sampler_stamps_are_the_trunks_vocabulary():
    """Every stamp of the compiled sampler is one of this trunk's kinds,
    none doubled; a window layer's attention is `attn_window` in a step
    AND in the once-a-call pass (the window is shorter than a frame), a
    full layer's `attn_full`; the gate is stamped in every layer that
    attends."""
    cfg = small_cfg()
    model, params = seeded(cfg)
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, 4),
                           cfg.diffusion, trajectory_every=1)
    cond = {k: jnp.asarray(v) for k, v in synth_data.cond_views(
        2, SIDE, 9).items()}
    # A cached executable carries the scopes of whatever compiled first.
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
        seen.setdefault(kind, set()).add((block, "precompute" in path))
    assert set(seen) - {"other", "unattributed"} == set(
        HEADMIX_TOKEN_LAYER_KINDS)
    assert seen["attn_window"] == {(f"layer_{i}", pre) for i in (1, 2, 3)
                                   for pre in (False, True)}
    # (the once-a-call pass needs the last layer's keys and values only:
    # its attention, gate and experts feed nothing and are not there)
    assert seen["attn_full"] == {("layer_0", False), ("layer_0", True),
                                 ("layer_4", False)}
    assert seen["attn_gate"] == seen["attn_window"] | seen["attn_full"]
    assert seen["dense_mlp"] == {("layer_0", False), ("layer_0", True)}
    assert ("layer_4", True) in seen["gqa_proj"]
    assert ("layer_4", True) not in seen["moe_experts"]
    assert {b for b, _ in seen["moe_shared"]} == {
        f"layer_{i}" for i in (1, 2, 3, 4)}
    labels = {label for label, _ in token_denoiser.op_groups(cfg.model)}
    assert {b for v in seen.values() for b, _ in v} - {""} <= labels


def test_preset_is_the_published_config_cut_as_the_file_says():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lgs_denoiser256.json")) as fh:
        conf = json.load(fh)
    cfg = get_preset(conf["preset"]).validate()
    k = cfg.model.tokens
    assert isinstance(k, LagunaTrunkConfig)
    m = token_check_headmix.model_sizes(cfg)
    published = token_check_headmix.model_sizes(dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model,
                                       tokens=LagunaTrunkConfig())))
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "sample_timesteps"]
    for name, value in conf.items():
        if name == "rope_parameters":   # as published: yarn's keys where
            for kind, law in value.items():          # the law is yarn
                for key, v in law.items():
                    assert m[name][kind][key] == v, (kind, key)
        elif name in conf["reduced"]:
            assert published[name] == conf["published"][name], name
        elif name in m and name != "held_experts":
            assert m[name] == published[name] == value, name
    assert conf["num_hidden_layers"] == k.num_hidden_layers == 5
    # the router keeps its 256 outputs; 128 are HELD here
    assert conf["num_experts"] == k.held_experts[1] == 128
    assert tuple(k.held_experts) == (0, 128) and k.num_experts == 256
    assert k.num_experts_per_tok == 10 and k.routed_scaling_factor == 2.5
    assert list(k.layer_types[:5]) == ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert list(k.num_attention_heads_per_layer[:5]) == [48, 72, 72, 72, 48]
    assert list(k.mlp_layer_types[:5]) == ["dense"] + ["sparse"] * 4
    assert len(k.layer_types) == len(k.mlp_layer_types) == len(
        k.num_attention_heads_per_layer) == 48
    assert conf["assumed"]["router_replicas"] == 2
    assert cfg.data.img_sidelength == 256
    shapes = token_denoiser.param_shapes(cfg.model)
    count = {g: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(t))
             for g, t in shapes.items()}
    assert count["layer_0"] == 157_440_000
    assert count["layer_1"] == count["layer_3"] == 1_281_325_056
    assert count["layer_4"] == 1_262_376_960
    assert sum(count.values()) == 5_290_048_512      # 10.58 GB in bfloat16
    assert "5 290 048 512" in conf["why_reduced"]["num_hidden_layers"]
    model = build_denoiser(cfg.model)
    # three window layers: a target query sees its frame's rest, 511
    # behind it and the cached rows less than 512 behind
    visited, visible = model.window_key_columns(256)
    L = 4096
    one = sum(L - max(r - 511, 0) + max(511 - r, 0) for r in range(L))
    assert visible == 3 * one and 2500 < one / L < 2600
    assert 1.0 < visited / visible < 1.25
    by_kind = model.cond_cache_bytes(256)
    assert by_kind == {"keys_values": 2 * 2 * L * 8 * 128 * 2,
                       "window_tail": 3 * 2 * 511 * 8 * 128 * 2}


def test_token_trunks_are_seven_and_read_back_by_their_keys():
    assert len(TOKEN_TRUNKS) == 7 and TOKEN_TRUNKS[6] is LagunaTrunkConfig
    assert "lgs_denoiser256" in PRESET_NAMES
    cfg = small_cfg()
    again = Config.from_json(cfg.to_json())
    assert again == cfg and isinstance(again.model.tokens, LagunaTrunkConfig)
    law = again.model.tokens.rope_parameters.full_attention
    assert law.rope_type == "yarn" and law.partial_rotary_factor == 0.5
    for name in PRESET_NAMES:
        other = get_preset(name)
        if other.model.tokens is not None:
            assert type(Config.from_json(other.to_json()).model.tokens) \
                is type(other.model.tokens), name


@pytest.mark.parametrize("over,word", [
    ({"model.tokens.layer_types": ["full_attention"] * 5}, "one length"),
    ({"model.tokens.num_attention_heads_per_layer": [4, 6, 6, 6, 4]},
     "one length"),
    ({"model.tokens.mlp_layer_types": ["dense"] + ["sparse"] * 3},
     "one length"),
    ({"model.tokens.num_attention_heads_per_layer": [4, 5, 6, 6] * 12},
     "multiple of num_key_value_heads"),
    ({"model.tokens.layer_types": ["full_attention", "local"] * 24},
     "layer_types"),
    ({"model.tokens.sliding_window": 0}, "sliding_window"),
    ({"model.tokens.mlp_layer_types": ["sparse"] * 48}, "mlp_only_layers"),
    ({"model.tokens.mlp_only_layers": [0, 1]}, "mlp_only_layers"),
    ({"model.tokens.rope_parameters.full_attention.partial_rotary_factor":
      0.3}, "even number"),
    ({"model.tokens.rope_parameters.sliding_attention.rope_type": "llama3"},
     "rope_type"),
    ({"model.tokens.gating": "per-element"}, "not carried"),
    ({"model.tokens.attention_bias": True}, "not carried"),
    ({"model.tokens.moe_router_logit_softcapping": 30.0}, "not carried"),
    ({"model.tokens.held_experts": [12, 8]}, "held_experts"),
    ({"model.tokens.num_experts_per_tok": 17}, "num_experts_per_tok"),
    ({"data.img_sidelength": 18}, "patch_size"),
])
def test_laguna_errors_refuse(over, word):
    with pytest.raises(ValueError, match=word):
        small_cfg(**over)


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


def test_a_gradient_through_the_trunk_raises_by_name():
    """Forward only, as the other trunks: grouped heads under a window and
    the grouped product have no backward and say so."""
    cfg = small_cfg()
    model, params = seeded(cfg)
    batch, mask = doubled_batch()

    def loss(p):
        return jnp.sum(model.apply({"params": p}, batch, cond_mask=mask))

    with pytest.raises(NotImplementedError):
        jax.grad(loss)(params)

"""The token denoiser's second trunk (models/token_denoiser.py,
`SmallThinkerLayer`: grouped-query heads, a window or none and rotary or
none per layer, the router ahead of attention, ReGLU experts top-k all
held) against the benchmark's plain reference
(benchmarks/reference/st21_ref.py) at a small size on the CPU, in float32
on both sides: 16 tokens a frame under a window of 16 — so that the window
binds exactly as at the cell's size, a target token r seeing the cached
tokens c > r —, one period of 4 layers, 4 query heads on 2 key/value
heads, 8 experts top-3 on independent router columns. Weights are the
benchmark's seeded ones (benchmarks/token_weights.py).

Tolerances as tests/test_token_denoiser.py: both sides compute in float32
and differ by the order of their sums; TOL = 2e-5 is ~50× what they read,
and the reference with its matmul inputs rounded to bfloat16 reads ~5e-3.
"""

import os
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
import token_check_gqa  # noqa: E402
import token_weights  # noqa: E402
from novel_view_synthesis_3d_tpu.config import (  # noqa: E402
    Config, SmallThinkerTrunkConfig, get_preset)
from novel_view_synthesis_3d_tpu.diffusion.schedules import (  # noqa: E402
    sampling_schedule)
from novel_view_synthesis_3d_tpu.models import (  # noqa: E402
    build_denoiser, token_denoiser)
from novel_view_synthesis_3d_tpu.models.vocab import (  # noqa: E402
    GQA_TOKEN_LAYER_KINDS, layer_of)
from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler  # noqa: E402

TOL = 2e-5
SIDE = 16
SMALL = {
    "model.tokens.hidden_size": 64, "model.tokens.num_hidden_layers": 4,
    "model.tokens.num_attention_heads": 4,
    "model.tokens.num_key_value_heads": 2, "model.tokens.head_dim": 16,
    "model.tokens.sliding_window_size": 16,
    "model.tokens.moe_num_primary_experts": 8,
    "model.tokens.moe_num_active_primary_experts": 3,
    "model.tokens.moe_ffn_hidden_size": 32,
    "model.tokens.held_experts": [0, 8], "data.img_sidelength": SIDE,
    "model.dtype": "float32", "model.param_dtype": "float32",
    "diffusion.sample_timesteps": 4,
}
ref = harness.load_module(os.path.join(
    ROOT, "benchmarks", "reference", "st21_ref.py"), "st21_ref")


def small_cfg(**over):
    return get_preset("st21_denoiser256").override(
        **dict(SMALL, **over)).validate()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def seeded(cfg, seed=5):
    model = build_denoiser(cfg.model)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}))["params"]
    return model, token_weights.make_weights(seed, shapes)


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
    (interpreted): grouped heads and the band in both."""
    cfg = small_cfg(**{
        "model.use_flash_attention": request.param == "kernel"})
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    return cfg, model, params, batch, mask, token_check_gqa.model_sizes(cfg)


def test_full_forward_matches_the_reference(small):
    cfg, model, params, batch, mask, m = small
    eps = model.apply({"params": params}, batch, cond_mask=mask, train=False)
    want = ref.forward(params, m, batch, mask)
    assert eps.shape == (2, SIDE, SIDE, 3) and eps.dtype == jnp.float32
    assert rel(eps, want) < TOL
    assert rel(eps[0], eps[1]) > 1e-2   # the ray term is masked in one row


def test_precompute_then_step_matches_the_full_forward(small):
    """Prefill of the conditioning frame into the key/value cache (keys
    rotated where the layer rotates), then the target's tokens alone
    against [cache ; own] under the window, is the reference's ONE forward
    over both frames under its dense (2L, 2L) predicate."""
    cfg, model, params, batch, mask, m = small
    cond = {k: v[:1] for k, v in batch.items() if k not in ("z", "logsnr")}
    pre = model.precompute(params, cond)
    k = cfg.model.tokens
    assert set(pre) == {"kv_cache"}
    assert len(pre["kv_cache"]) == k.num_hidden_layers
    L = (SIDE // k.patch_size) ** 2
    for keys, values in pre["kv_cache"]:
        assert keys.shape == values.shape == (
            2, L, k.num_key_value_heads, k.head_dim)
    eps = model.apply({"params": params}, dict(batch, **pre), cond_mask=mask,
                      train=False)
    assert rel(eps, ref.forward(params, m, batch, mask)) < TOL
    batch2 = dict(batch, z=batch["z"] * 0.5 + 0.1,
                  logsnr=jnp.full((2,), -2.0))
    eps2 = model.apply({"params": params}, dict(batch2, **pre),
                       cond_mask=mask, train=False)
    assert rel(eps2, ref.forward(params, m, batch2, mask)) < TOL


def test_the_window_the_rotary_layout_and_the_precision_all_show(small):
    """What the comparison must be able to see: a reference without the
    window, with rotary in the wrong layers, or in a lower precision than
    stated is not the program."""
    cfg, model, params, batch, mask, m = small
    want = ref.forward(params, m, batch, mask)
    layers = len(m["rope_layout"])
    for other in (dict(m, sliding_window_layout=[0] * layers),
                  dict(m, sliding_window_size=8),
                  dict(m, rope_layout=[1] * layers),
                  dict(m, rope_layout=[0] * layers)):
        assert rel(ref.forward(params, other, batch, mask), want) > 1e-2
    assert rel(ref.forward(params, m, batch, mask, "bf16"), want) > 100 * TOL
    assert rel(ref.forward(params, m, batch, mask, "fp8"), want) > 1000 * TOL


def test_reference_mask_is_the_frame_rule_and_the_one_sided_window():
    m = {"sliding_window_layout": [0, 1], "sliding_window_size": 4}
    full, band = ref.visible(m, 0, 8), ref.visible(m, 1, 8)
    # a conditioning token sees its frame only; a target token every key
    assert full[:4, :4].all() and not full[:4, 4:].any() and full[4:].all()
    # the window cuts nothing inside a frame (p − p′ < 4 there, and keys
    # AFTER the query stay visible) and leaves target r the cached c > r
    assert (band[:4] == full[:4]).all() and band[4:, 4:].all()
    assert (band[4:, :4] == np.triu(np.ones((4, 4), bool), 1)).all()


def test_guided_eps_through_make_sampler(small):
    """Every step of `make_sampler(trajectory_every=1)` — no edit to
    sample/ddpm.py: its precompute seam hands the K/V cache through as it
    does the other trunk's latent — against the reference's guided ε̂."""
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
# The expert layer: one function for both trunks, here with every expert
# held, three live choices a token at unequal gates
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def layer8():
    cfg = small_cfg()
    model, params = seeded(cfg, seed=8)
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.normal(size=(48, 64)), jnp.float32)
    return cfg, params["layer_1"], b, token_check_gqa.model_sizes(cfg)


@pytest.mark.parametrize("shares", [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_layer(layer8, shares):
    """`held_experts` (0, k), (k, k), …: the parts the shares of an
    expert-parallel layer compute add up to the layer with every expert
    held, and each is the reference's loop over that share — ReGLU, gates
    the softmax over the three chosen logits."""
    cfg, p, b, m = layer8
    k = cfg.model.tokens
    top_p, top_i = token_denoiser.route(b, p["router"], k)
    np.testing.assert_allclose(np.asarray(top_p.sum(axis=1)), 1.0, atol=1e-6)
    assert float(jnp.max(top_p) - jnp.min(top_p)) > 0.1     # unequal gates
    gates, chosen, _, _, _ = ref.router(p["router"], m, b)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(top_i))
    np.testing.assert_allclose(np.asarray(gates), np.asarray(top_p),
                               atol=1e-6)
    whole, _ = ref.experts_part(p["experts"], m, b, gates, chosen, "f32")
    per = k.n_routed_experts // shares
    total, counted = 0.0, 0
    for s in range(shares):
        held = (s * per, per)
        ks = SmallThinkerTrunkConfig(**dict(
            {f.name: getattr(k, f.name)
             for f in k.__dataclass_fields__.values()}, held_experts=held))
        stack = jax.tree.map(lambda a: a[s * per:(s + 1) * per],
                             p["experts"])
        part, counts = token_denoiser.held_expert_part(b, top_p, top_i,
                                                       stack, ks)
        want, ref_counts = ref.experts_part(p["experts"], m, b, gates,
                                            chosen, "f32", held)
        assert rel(part, want) < TOL
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(ref_counts))
        total, counted = total + part, counted + int(counts.sum())
    assert counted == b.shape[0] * k.num_experts_per_tok  # none dropped
    assert rel(total, whole) < TOL


def test_routing_is_taken_from_the_attentions_input(small):
    """The router's logits are RMSNorm(x)·W_r of the layer's INPUT: the
    program's chosen experts for both frames equal the reference's, whose
    router never sees the attention's output."""
    cfg, model, params, batch, mask, m = small
    choice = np.asarray(model.routing_choices(params, batch, mask))
    k = cfg.model.tokens
    L = (SIDE // k.patch_size) ** 2
    assert choice.shape == (k.num_hidden_layers, 2, 2 * L,
                            k.num_experts_per_tok)
    _, auxes = ref.forward(params, m, batch, mask, aux=True)
    h = ref.embed(params, m, batch, mask)
    for i, aux in enumerate(auxes):
        h, parts = ref.layer(params[f"layer_{i}"], m, h, i, parts=True)
        clear = np.asarray(aux["margin"]) > 1e-4
        np.testing.assert_array_equal(choice[i][clear],
                                      np.asarray(parts["chosen"])[clear])
    counts = np.asarray(model.routing_counts(params, batch, mask))
    assert counts.sum(axis=1).tolist() == [
        2 * L * k.num_experts_per_tok] * k.num_hidden_layers


def test_reference_adopts_a_choice_only_inside_the_margin(layer8):
    """st21_ref.router with the program's choice: a token at a near tie
    takes a set that swaps its sixth for its seventh, is left out
    (`excluded`) for a set that reaches further down, and a token at a
    clear margin keeps the reference's own whatever it is handed."""
    cfg, p, b, m = layer8
    gates, own, gap, _, _ = ref.router(p["router"], m, b)
    logits = np.asarray(b @ p["router"]["kernel"])
    order = np.argsort(-logits, axis=1)
    k = m["moe_num_active_primary_experts"]
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
    np.testing.assert_allclose(np.asarray(g.sum(axis=1)), 1.0, atol=1e-6)
    _, chosen, _, adopted, excluded = ref.router(
        p["router"], m, b, jnp.asarray(far), thr)
    assert not np.asarray(adopted).any()
    np.testing.assert_array_equal(np.asarray(excluded), near)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(own))


# ---------------------------------------------------------------------------
# Scopes, the preset, the config
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("path,want", [
    ("jit(sample)/lk.update/og.layer_3/lk.gqa_proj/dot_general",
     ("layer_3", "gqa_proj")),
    ("jit(sample)/lk.update/og.layer_0/lk.attn_full/flash_fwd",
     ("layer_0", "attn_full")),
    ("jit(sample)/lk.update/og.layer_1/lk.attn_window/transpose",
     ("layer_1", "attn_window")),
    ("jit(sample)/precompute/og.layer_1/lk.attn_full/flash_fwd",
     ("layer_1", "attn_full")),
    ("jit(sample)/lk.update/og.layer_2/lk.moe_route/top_k",
     ("layer_2", "moe_route")),
])
def test_layer_of_reads_the_trunks_paths(path, want):
    assert layer_of(path) == want


def test_compiled_sampler_stamps_are_the_trunks_vocabulary():
    """Every stamp of the compiled sampler is one of this trunk's kinds,
    none doubled; a window layer's step is `attn_window`, a full layer's
    step and the once-a-call pass of every layer `attn_full`."""
    import re

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
    # of the `;`-joined paths of instructions XLA merged, the first holds
    paths = {p.split(";", 1)[0]
             for p in re.findall(r'op_name="([^"]+)"', text)}
    seen = {}
    for path in paths:
        stamps = re.findall(r"lk\.(\w+)", path)
        assert len(stamps) == len(set(stamps)), path
        block, kind = layer_of(path)
        seen.setdefault(kind, set()).add((block, "precompute" in path))
    assert set(seen) - {"other", "unattributed"} == set(
        GQA_TOKEN_LAYER_KINDS)
    assert seen["attn_window"] == {(f"layer_{i}", False) for i in (1, 2, 3)}
    # (the once-a-call pass needs the last layer's keys and values only:
    # its attention and experts feed nothing and are not in the program)
    assert seen["attn_full"] == {("layer_0", False)} | {
        (f"layer_{i}", True) for i in range(3)}
    assert ("layer_3", True) in seen["gqa_proj"]
    assert ("layer_3", True) not in seen["moe_experts"]
    labels = {label for label, _ in token_denoiser.op_groups(cfg.model)}
    assert {b for v in seen.values() for b, _ in v} - {""} <= labels


def test_preset_is_the_published_config_cut_as_the_file_says():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "st21_denoiser256.json")) as fh:
        conf = json.load(fh)
    cfg = get_preset(conf["preset"]).validate()
    k = cfg.model.tokens
    assert isinstance(k, SmallThinkerTrunkConfig)
    m = token_check_gqa.model_sizes(cfg)
    for name, value in conf.items():
        if name in m and name not in ("name", "held_experts"):
            assert m[name] == value, name
    assert conf["reduced"] == ["num_hidden_layers", "sample_timesteps"]
    assert conf["published"]["num_hidden_layers"] == 52 == \
        SmallThinkerTrunkConfig().num_hidden_layers
    # three whole periods: [full without rotary, window with rotary x 3]
    assert k.num_hidden_layers == 12
    assert list(k.rope_layout[:12]) == [0, 1, 1, 1] * 3 == list(
        k.sliding_window_layout[:12])
    assert tuple(k.held_experts) == (0, 64) and k.num_experts_per_tok == 6
    assert cfg.data.img_sidelength == 256      # 4096 tokens = the window
    assert (cfg.data.img_sidelength // k.patch_size) ** 2 == \
        k.sliding_window_size
    assert conf["assumed"]["router_replicas"] == 1
    shapes = token_denoiser.param_shapes(cfg.model)
    layer = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(
        shapes["layer_0"]))
    assert 398e6 < layer < 399.5e6           # 398.6 M: 0.797 GB in bfloat16
    model = build_denoiser(cfg.model)
    visited, visible = model.window_key_columns(256)
    assert visible == 9 * (4096 * 8192 - 4096 * 4097 // 2)
    assert 1.0 < visited / visible < 1.25


def test_config_round_trip_and_refusals():
    cfg = small_cfg()
    again = Config.from_json(cfg.to_json())
    assert again == cfg
    assert isinstance(again.model.tokens, SmallThinkerTrunkConfig)
    # the other trunk still round-trips to its own class
    other = get_preset("ms4_denoiser128")
    assert type(Config.from_json(other.to_json()).model.tokens) is type(
        other.model.tokens)
    for over, word in [
        ({"model.tokens.held_experts": [6, 4]}, "held_experts"),
        ({"model.tokens.num_key_value_heads": 3}, "num_key_value_heads"),
        ({"model.tokens.rope_layout": [0, 1]}, "rope_layout"),
        ({"data.img_sidelength": 18}, "patch_size"),
    ]:
        with pytest.raises(ValueError, match=word):
            small_cfg(**over)
    with pytest.raises(KeyError, match="no trunk"):
        Config.from_dict({"model": {"tokens": {"q_lora_rank": 8,
                                               "head_dim": 8}}})

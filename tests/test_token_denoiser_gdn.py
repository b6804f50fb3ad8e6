"""The token denoiser's fifth trunk (models/token_denoiser.py,
`OlmoHybridLayer`: Gated DeltaNet layers with a recurrent-state cache, full
attention under a QK norm with a key/value cache, every sublayer's OUTPUT
normalised inside the residual, a dense MLP in each, no expert layer)
against the benchmark's plain reference (benchmarks/reference/oh7_ref.py)
at a small size on the CPU, in float32 on both sides: 16 tokens a frame, 4
layers — Gated DeltaNet × 3, full attention —, 4 delta-rule heads with keys
of 12 on values of 24, 4 query on 4 key/value heads of 16. Weights are the
benchmark's seeded ones (benchmarks/gdn_weights.py).

Tolerances as tests/test_token_denoiser_kda.py: both sides compute in
float32 and differ by the order of their sums; TOL = 2e-5, and the
reference with its matmul inputs rounded to bfloat16 reads ~3e-2.
"""

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

import gdn_weights  # noqa: E402
import harness  # noqa: E402
import synth_data  # noqa: E402
import token_check_gdn  # noqa: E402
from novel_view_synthesis_3d_tpu.config import (  # noqa: E402
    PRESET_NAMES, TOKEN_TRUNKS, Config, OlmoHybridTrunkConfig, get_preset)
from novel_view_synthesis_3d_tpu.diffusion.schedules import (  # noqa: E402
    sampling_schedule)
from novel_view_synthesis_3d_tpu.models import (  # noqa: E402
    build_denoiser, token_denoiser)
from novel_view_synthesis_3d_tpu.models.vocab import (  # noqa: E402
    GDN_TOKEN_LAYER_KINDS, layer_of, layer_part_of)
from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler  # noqa: E402

TOL = 2e-5
SIDE = 16
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "sample_scan_gdn.json")) as _fh:
    SMALL = dict(json.load(_fh)["rehearse"]["overrides"], **{
        "model.dtype": "float32", "model.param_dtype": "float32",
        "diffusion.sample_timesteps": 4})
ref = harness.load_module(os.path.join(
    ROOT, "benchmarks", "reference", "oh7_ref.py"), "oh7_ref")


def small_cfg(**over):
    return get_preset("oh7_denoiser256").override(
        **dict(SMALL, **over)).validate()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def seeded(cfg, seed=5):
    model = build_denoiser(cfg.model)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}))["params"]
    return model, gdn_weights.make_weights(seed, shapes)


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
    (interpreted); the short convolution is its kernel in both."""
    cfg = small_cfg(**{
        "model.use_flash_attention": request.param == "kernel"})
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    return cfg, model, params, batch, mask, token_check_gdn.model_sizes(cfg)


# ---------------------------------------------------------------------------
# A layer of each kind against the form written out
# ---------------------------------------------------------------------------
def test_a_full_layer_normalises_q_and_k_whole_and_its_output_in_the_residual():
    """h + RMSNorm(W_o·softmax(q kᵀ/√d) v): q and k RMS-normalised over
    the WHOLE 64-wide projection before the split into 4 heads, no norm on
    the layer's input, no positional term, the cache's keys in front."""
    cfg = small_cfg()
    model, params = seeded(cfg)
    p, at = params["layer_3"], params["layer_3"]["attn"]
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(2, 16, 64)), jnp.float32)
    cache = tuple(jnp.asarray(rng.normal(size=(2, 16, 4, 16)), jnp.float32)
                  for _ in range(2))
    got, own = model.layer._attn(p, h, cache)

    def whole_norm(x, scale):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                            + 1e-6) * scale

    q = whole_norm(h @ at["q"]["kernel"], at["q_norm"]["scale"])
    k = whole_norm(h @ at["k"]["kernel"], at["k_norm"]["scale"])
    v = h @ at["v"]["kernel"]
    np.testing.assert_allclose(own[0], k.reshape(2, 16, 4, 16), rtol=1e-5,
                               atol=1e-6)
    keys = jnp.concatenate([cache[0], k.reshape(2, 16, 4, 16)], axis=1)
    values = jnp.concatenate([cache[1], v.reshape(2, 16, 4, 16)], axis=1)
    s = jnp.einsum("bqnd,bknd->bnqk", q.reshape(2, 16, 4, 16), keys) * 0.25
    o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), values)
    mixed = o.reshape(2, 16, 64) @ at["o"]["kernel"]
    want = h + whole_norm(mixed, p["mix_norm"]["scale"])
    assert rel(got, want) < TOL
    # a norm a head instead would be another layer
    per_head = (h @ at["q"]["kernel"]).reshape(2, 16, 4, 16)
    per_head = per_head / jnp.sqrt(jnp.mean(
        per_head ** 2, axis=-1, keepdims=True) + 1e-6)
    assert rel(per_head.reshape(2, 16, 64) * at["q_norm"]["scale"], q) > 0.05


def test_a_delta_rule_layer_matches_the_reference_from_a_cached_state():
    """One Gated DeltaNet layer over a frame entered with a state and a
    tail is the reference's mixer over [the frame before ; the frame],
    token by token from zeros — its second half."""
    cfg = small_cfg()
    model, params = seeded(cfg)
    m = token_check_gdn.model_sizes(cfg)
    p = params["layer_1"]
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    first, cache = model.layer._gdn(p, h[:, :16], None)
    state, tail = cache
    assert state.shape == (2, 4, 12, 24) and state.dtype == jnp.float32
    assert tail.shape == (2, 3, 4 * (12 + 12 + 24))
    second, _ = model.layer._gdn(p, h[:, 16:], cache)
    mixed, _ = ref.gated_delta_net(p["gdn"], m, h, "f32")
    want = h + ref.rms_norm(mixed, p["mix_norm"]["scale"], 1e-6)
    assert rel(jnp.concatenate([first, second], axis=1), want) < TOL
    # β reaches past 1 on these weights: the factor 2 is exercised
    beta = 2 * jax.nn.sigmoid(h @ p["gdn"]["b"]["kernel"])
    assert float(beta.max()) > 1.2 and float(beta.min()) < 0.8


# ---------------------------------------------------------------------------
# The frame: one forward, the once-a-call pass, the caches
# ---------------------------------------------------------------------------
def test_full_forward_matches_the_reference(small):
    cfg, model, params, batch, mask, m = small
    got = model.apply({"params": params}, batch, cond_mask=mask)
    assert got.shape == (2, SIDE, SIDE, 3) and got.dtype == jnp.float32
    assert rel(got, reference(params, m, batch, mask)) < TOL


def test_precompute_then_step_matches_the_full_forward(small):
    """The once-a-call pass and a step from its caches — a delta-rule state
    and its convolution's tail, a full layer's keys and values — are the
    reference's ONE token-by-token pass over both frames."""
    cfg, model, params, batch, mask, m = small
    cond = {k: batch[k][:1] for k in ("x", "R1", "t1", "K")}
    extra = model.precompute(params, cond)
    cache = extra["layer_cache"]
    assert [model.layer.cache_kind(i) for i in range(4)] == [
        "recurrent_state"] * 3 + ["keys_values"]
    for state, tail in cache[:3]:
        assert state.shape == (2, 4, 12, 24) and state.dtype == jnp.float32
        assert tail.shape == (2, 3, 192)
    assert [a.shape for a in cache[3]] == [(2, 16, 4, 16)] * 2
    got = model.apply({"params": params}, dict(batch, **extra),
                      cond_mask=mask)
    assert rel(got, reference(params, m, batch, mask)) < TOL
    assert model.cond_cache_bytes(SIDE) == {
        "recurrent_state": 3 * (4 * 12 * 24 + 3 * 192) * 4,
        "keys_values": 2 * 16 * 64 * 4}
    assert model.window_key_columns(SIDE) == (0, 0)


def test_the_cut_is_the_uncut_stacks_first_layers():
    """The configuration runs the first 16 of 32 layers: at the small size,
    the 4-layer cut's ε̂ is the head on the UNCUT 8-layer reference's
    hidden state after layer 4, on the uncut tree's own weights."""
    uncut = small_cfg(**{"model.tokens.num_hidden_layers": 8})
    _, params8 = seeded(uncut)
    cut = small_cfg()
    model = build_denoiser(cut.model)
    params4 = {g: v for g, v in params8.items()
               if not g.startswith("layer_") or int(g[6:]) < 4}
    batch, mask = doubled_batch()
    m8 = token_check_gdn.model_sizes(uncut)
    assert [ref.is_full_attention(m8, i) for i in range(8)] == [
        False, False, False, True] * 2
    h4 = ref.forward(params8, m8, batch, mask, layers=4)
    want = ref.head(params8, m8, h4, SIDE)
    got = model.apply({"params": params4}, batch, cond_mask=mask)
    assert rel(got, want) < TOL
    # and the uncut stack's own ε̂ is another number
    assert rel(ref.forward(params8, m8, batch, mask), want) > 0.1


def test_the_lost_state_the_plainer_rules_and_the_precision_all_show():
    """The reference's own controls at the small size: every delta-rule
    state zeroed at the target frame's first token, β without its factor
    2, the decay switched off, and bfloat16 inputs, each far past the
    tolerance."""
    cfg = small_cfg()
    _, params = seeded(cfg)
    batch, mask = doubled_batch()
    m = token_check_gdn.model_sizes(cfg)
    want = reference(params, m, batch, mask)
    assert token_check_gdn.CONTROLS == ref.CONTROLS
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


# ---------------------------------------------------------------------------
# Vocabulary, configuration, refusals
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("path,want,part", [
    ("jit(sampler)/precompute/og.layer_0/lk.gdn_core/pt.layout/transpose",
     ("layer_0", "gdn_core"), "gdn_core.layout"),
    ("jit(sampler)/while/body/lk.update/og.layer_1/lk.gdn_core/while/body/"
     "dot_general", ("layer_1", "gdn_core"), "gdn_core"),
    ("jit(sampler)/while/body/lk.update/og.layer_2/lk.gdn_conv/pt.kernel/"
     "short_conv_fwd", ("layer_2", "gdn_conv"), "gdn_conv.kernel"),
    ("jit(sampler)/while/body/lk.update/og.layer_0/lk.gdn_proj/pt.matmul/"
     "dot_general", ("layer_0", "gdn_proj"), "gdn_proj.matmul"),
    ("jit(sampler)/while/body/lk.update/og.layer_3/lk.attn_full/pt.kernel/"
     "flash_fwd", ("layer_3", "attn_full"), "attn_full.kernel"),
    ("jit(sampler)/while/body/lk.update/og.layer_1/lk.gdn_proj/"
     "jit(_norm_call)/pt.kernel/head_norm_fwd/pallas_call",
     ("layer_1", "gdn_proj"), "gdn_proj.kernel"),
])
def test_layer_of_reads_the_trunks_paths(path, want, part):
    assert layer_of(path) == want
    assert layer_part_of(path) == (want[0], part)


def test_compiled_sampler_stamps_are_the_trunks_vocabulary():
    """Every stamp of the compiled sampler is one of this trunk's kinds,
    none doubled; each kind in the layers of its kind only."""
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
        GDN_TOKEN_LAYER_KINDS)
    linear = {"layer_0", "layer_1", "layer_2"}
    for kind in ("gdn_proj", "gdn_conv", "gdn_core"):
        assert seen[kind] == linear
    assert seen["attn_full"] == seen["gqa_proj"] == {"layer_3"}
    assert seen["dense_mlp"] == linear | {"layer_3"}


def test_preset_is_the_published_config_cut_in_depth_alone():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "oh7_denoiser256.json")) as fh:
        conf = json.load(fh)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = [c for c in json.load(fh)["configs"]
                 if c["name"] == "oh7_denoiser256"][0]
    assert entry["source"] == conf["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/"
        "config.json")
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "sample_timesteps"]
    assert conf["published"] == {"num_hidden_layers": 32,
                                 "sample_timesteps": 256}
    # every number of the catalog row's `config`, under the same key
    published = {
        "vocab_size": 100352, "hidden_size": 3840,
        "intermediate_size": 11008, "num_hidden_layers": 32,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "max_position_embeddings": 65536, "rms_norm_eps": 1e-06,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4}
    for name, value in published.items():
        want = conf["published"].get(name, value)
        assert (conf["published"][name] if name in conf["reduced"]
                else conf[name]) == want, name
    assert conf["num_hidden_layers"] == 16
    assert conf["layer_types"] == (["linear_attention"] * 3
                                   + ["full_attention"]) * 8
    assert conf["rope_parameters"] == {"rope_theta": None}
    assert conf["linear_allow_neg_eigval"] is True
    cfg = get_preset(conf["preset"]).validate()
    k = cfg.model.tokens
    assert isinstance(k, OlmoHybridTrunkConfig)
    m = token_check_gdn.model_sizes(cfg)
    for name, value in conf.items():
        if name in m and name != "name":
            assert list(m[name]) == value if isinstance(value, list) \
                else m[name] == value, name
    assert (k.head_dim, k.num_hidden_layers, cfg.data.img_sidelength) == (
        128, 16, 256)
    assert [k.is_full_attention(i) for i in range(16)] == [
        False, False, False, True] * 4
    shapes = token_denoiser.param_shapes(cfg.model)
    size = {g: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(t))
            for g, t in shapes.items()}
    assert 215.5e6 < size["layer_0"] < 215.7e6     # GDN 88.75 + MLP 126.81
    assert 185.7e6 < size["layer_3"] < 185.9e6     # attention 58.99 + MLP
    trunk = sum(v for g, v in size.items() if g.startswith("layer_"))
    assert 3.329e9 < trunk < 3.331e9               # 6.66 GB in bfloat16
    assert 3.36e9 < sum(size.values()) < 3.38e9    # with the adapters
    model = build_denoiser(cfg.model)
    by_kind = model.cond_cache_bytes(256)
    assert by_kind == {
        "recurrent_state": 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2),
        "keys_values": 4 * 2 * 4096 * 3840 * 2}
    assert 279.0e6 < sum(by_kind.values()) < 279.1e6


def test_token_trunks_are_five_and_read_back_by_their_keys():
    # the fifth of them (a sixth came with PR 44, behind it)
    assert len(TOKEN_TRUNKS) >= 5 and TOKEN_TRUNKS[4] is \
        OlmoHybridTrunkConfig
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
        ({"model.tokens.layer_types": ["linear_attention", "window"] * 2},
         "layer_types"),
        ({"model.tokens.num_hidden_layers": 40}, "layer_types"),
        ({"model.tokens.linear_num_key_heads": 2}, "linear_num_value_heads"),
        ({"model.tokens.num_key_value_heads": 3}, "num_key_value_heads"),
        ({"model.tokens.hidden_act": "gelu"}, "hidden_act"),
        ({"model.tokens.attention_bias": True}, "attention_bias"),
        ({"data.img_sidelength": 18}, "patch_size"),
    ]:
        with pytest.raises(ValueError, match=word):
            small_cfg(**over)


def test_a_trunk_without_experts_routes_nothing_and_says_so(small):
    cfg, model, params, batch, mask, _ = small
    for read in (model.routing_counts, model.routing_choices):
        with pytest.raises(NotImplementedError,
                           match="OlmoHybridTrunkConfig is a trunk without "
                                 "expert layers"):
            read(params, batch, mask)


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


def test_init_and_the_benchmarks_weights_draw_the_decay_as_published():
    cfg = small_cfg(**{"model.tokens.linear_num_key_heads": 64,
                       "model.tokens.linear_num_value_heads": 64})
    model = build_denoiser(cfg.model)
    p = model.init({"params": jax.random.PRNGKey(0)})["params"]
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}))["params"]
    for gdn in (p["layer_0"]["gdn"],
                gdn_weights.make_group(7, shapes, "layer_0")["gdn"]):
        A = jnp.exp(gdn["A_log"])
        assert 0.0 < float(A.min()) and float(A.max()) <= 16.0
        assert float(A.max()) > 8.0 and float(A.min()) < 4.0  # U(0, 16)
        step = jax.nn.softplus(gdn["dt_bias"])
        assert 1e-3 * 0.999 <= float(step.min())
        assert float(step.max()) <= 1e-1 * 1.001
    assert bool((p["layer_0"]["mix_norm"]["scale"] == 1).all())
    assert "attn_norm" not in p["layer_0"] and "norm" not in p["layer_3"]

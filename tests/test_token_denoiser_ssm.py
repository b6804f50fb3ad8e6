"""The token denoiser's fourth trunk (models/token_denoiser.py,
`Phi4FlashLayer`: Mamba layers with a recurrent-state cache, differential
attention under a window and full, gated memory units and cross layers
that read what two layers publish, a dense MLP in each, no expert layer)
against the benchmark's plain reference
(benchmarks/reference/p4f_ref.py) at a small size on the CPU, in float32 on
both sides: 16 tokens a frame under a window of 6, 8 layers — Mamba,
window, Mamba, window, Mamba, full, gated memory unit, cross —, 4 query
heads on 2 key/value heads of 16, 8 states a channel. Weights are the
benchmark's seeded ones (benchmarks/ssm_weights.py).

Tolerances as tests/test_token_denoiser_kda.py: both sides compute in
float32 and differ by the order of their sums; TOL = 2e-5, and the
reference with its matmul inputs rounded to bfloat16 reads ~1e-2.
"""

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
import ssm_weights  # noqa: E402
import synth_data  # noqa: E402
import token_check_ssm  # noqa: E402
from novel_view_synthesis_3d_tpu.config import (  # noqa: E402
    Config, Phi4FlashTrunkConfig, get_preset)
from novel_view_synthesis_3d_tpu.diffusion.schedules import (  # noqa: E402
    sampling_schedule)
from novel_view_synthesis_3d_tpu.models import (  # noqa: E402
    build_denoiser, token_denoiser)
from novel_view_synthesis_3d_tpu.models.vocab import (  # noqa: E402
    SSM_TOKEN_LAYER_KINDS, layer_of)
from novel_view_synthesis_3d_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention)
from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler  # noqa: E402

TOL = 2e-5
SIDE = 16
SMALL = {
    "model.tokens.hidden_size": 64, "model.tokens.num_hidden_layers": 8,
    "model.tokens.num_attention_heads": 4,
    "model.tokens.num_key_value_heads": 2,
    "model.tokens.intermediate_size": 96, "model.tokens.sliding_window": 6,
    "model.tokens.mamba_d_state": 8, "data.img_sidelength": SIDE,
    "model.dtype": "float32", "model.param_dtype": "float32",
    "diffusion.sample_timesteps": 4,
}
KINDS = ["mamba", "attn_window", "mamba", "attn_window", "mamba",
         "attn_full", "gmu", "attn_cross"]
ref = harness.load_module(os.path.join(
    ROOT, "benchmarks", "reference", "p4f_ref.py"), "p4f_ref")


def small_cfg(**over):
    return get_preset("p4f_denoiser256").override(
        **dict(SMALL, **over)).validate()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def seeded(cfg, seed=5):
    model = build_denoiser(cfg.model)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}))["params"]
    return model, ssm_weights.make_weights(seed, shapes)


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
    (interpreted): keys 16 wide against a value pair of 32 in both."""
    cfg = small_cfg(**{
        "model.use_flash_attention": request.param == "kernel"})
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    return cfg, model, params, batch, mask, token_check_ssm.model_sizes(cfg)


# ---------------------------------------------------------------------------
# The layer kind of index i
# ---------------------------------------------------------------------------
def source_rule(N, mb, i):
    """The source's modeling code, written out: a layer is a Mamba layer
    where its index is a multiple of mb_per_layer; from N/2 on it is in the
    cross-decoder's reach (`yoco_mb`), from N/2 + 2 on it IS the
    cross-decoder (`yoco_cross`: a Mamba slot becomes a gated memory unit,
    attention becomes cross-attention); attention below N/2 is under the
    window, layer N/2 + 1 over everything."""
    use_mamba = i % mb == 0
    yoco_cross = i >= N // 2 + 2
    if use_mamba:
        return "gmu" if yoco_cross else "mamba"
    if yoco_cross:
        return "attn_cross"
    return "attn_window" if i < N // 2 else "attn_full"


@pytest.mark.parametrize("N", [8, 32])
def test_layer_kind_follows_the_sources_rule(N):
    k = Phi4FlashTrunkConfig(num_hidden_layers=N)
    kinds = [k.layer_kind(i) for i in range(N)]
    assert kinds == [source_rule(N, 2, i) for i in range(N)]
    if N == 8:
        assert kinds == KINDS
    else:
        assert [kinds.count(x) for x in (
            "mamba", "attn_window", "attn_full", "gmu", "attn_cross")] == [
                9, 8, 1, 7, 7]
        assert kinds[16] == "mamba" and kinds[17] == "attn_full"
        assert k.lambda_init(0) == pytest.approx(0.2)
        assert k.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))


# ---------------------------------------------------------------------------
# Differential attention's two maps against dense XLA
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("window", [None, 24], ids=["full", "window"])
def test_a_map_at_key_width_64_value_width_128_matches_dense_xla(window):
    """One softmax map of a differential layer through the kernel
    (interpreted): 4 query pairs on 2 key pairs, keys 64 wide against a
    128-wide value pair, the key axis [23 cached rows ; 48 own] — no
    multiple of a key block — and, windowed, a band of 24."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 48, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 71, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 71, 2, 128)), jnp.float32)
    got = flash_attention(q, k, v, scale=0.125, window=window)
    want = token_denoiser._attention(q, k, v, 0.125, False, window)
    assert got.shape == want.shape == (2, 48, 4, 128)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # and against the sum written out, for one query pair of group 1
    s = jnp.einsum("bqd,bkd->bqk", q[:, :, 3], k[:, :, 1]) * 0.125
    if window is not None:
        seen = np.arange(71)[None] > (23 + np.arange(48))[:, None] - window
        s = jnp.where(seen, s, -jnp.inf)
    np.testing.assert_allclose(
        want[:, :, 3], jax.nn.softmax(s, axis=-1) @ v[:, :, 1], rtol=2e-5,
        atol=2e-5)


@pytest.mark.parametrize("i", [1, 5, 7], ids=["window", "full", "cross"])
def test_a_differential_layer_matches_the_form_written_out(i):
    """(1 − λ⁰)·RMSNorm((A¹ − λA²)·V) of one layer, the maps dense under
    the layer's mask, against the layer with a conditioning frame's cache
    (a window layer's the tail alone) or the published keys and values."""
    cfg = small_cfg()
    model, params = seeded(cfg)
    k, layer = cfg.model.tokens, model.layer
    p = params[f"layer_{i}"]
    rng = np.random.default_rng(i)
    h = jnp.asarray(rng.normal(size=(2, 16, 64)), jnp.float32)
    kv = [jnp.asarray(rng.normal(size=(2, n, 32)), jnp.float32)
          for n in (16, 16)]
    if i == 7:
        pub, cache = {"kv": (jnp.tile(kv[0], (1, 2, 1)),
                             jnp.tile(kv[1], (1, 2, 1)))}, None
        keys, values = pub["kv"]
    else:
        tail = 5 if i == 1 else 16
        pub, cache = {}, (kv[0][:, -tail:], kv[1][:, -tail:])
    got, _ = layer._attn(i, p, h, cache, pub)
    a = token_denoiser.layer_norm(h, p["norm"], 1e-5)
    at = p["attn"]
    qkv = a @ at["qkv"]["kernel"] + at["qkv"]["bias"]
    q = qkv[..., :64].reshape(2, 16, 2, 2, 16)
    if i != 7:
        keys = jnp.concatenate([cache[0], qkv[..., 64:96]], axis=1)
        values = jnp.concatenate([cache[1], qkv[..., 96:]], axis=1)
    Lk = keys.shape[1]
    kp = keys.reshape(2, Lk, 1, 2, 16)
    vp = values.reshape(2, Lk, 1, 32)
    seen = np.ones((16, Lk), bool)
    if i == 1:   # query r at position 5 + r of [tail ; own] sees j > p − 6
        seen = np.arange(Lk)[None] > (5 + np.arange(16))[:, None] - 6
    maps = [jax.nn.softmax(jnp.where(seen, jnp.einsum(
        "bqpd,bkd->bpqk", q[:, :, :, s], kp[:, :, 0, s]) * 0.25, -jnp.inf),
        axis=-1) for s in (0, 1)]
    lam0 = k.lambda_init(i)
    lam = jnp.exp(jnp.sum(at["lambda_q1"] * at["lambda_k1"])) \
        - jnp.exp(jnp.sum(at["lambda_q2"] * at["lambda_k2"])) + lam0
    o = jnp.einsum("bpqk,bkd->bqpd", maps[0] - lam * maps[1], vp[:, :, 0])
    o = token_denoiser.rms_norm(o, at["sub_norm"]["scale"], 1e-5) \
        * (1.0 - lam0)
    want = h + o.reshape(2, 16, 64) @ at["o"]["kernel"] + at["o"]["bias"]
    assert rel(got, want) < TOL
    assert (i == 5) == ("kv" in pub and i != 7)


# ---------------------------------------------------------------------------
# The frame: one forward, the once-a-call pass, the caches
# ---------------------------------------------------------------------------
def test_full_forward_matches_the_reference(small):
    cfg, model, params, batch, mask, m = small
    got = model.apply({"params": params}, batch, cond_mask=mask)
    assert got.shape == (2, SIDE, SIDE, 3) and got.dtype == jnp.float32
    assert rel(got, reference(params, m, batch, mask)) < TOL


def test_precompute_then_step_matches_the_full_forward(small):
    """The once-a-call pass (layers 0-5 only) and a step from its caches —
    a Mamba state and tail, a window's tail, the one shared key/value
    cache — are the reference's one forward over both frames."""
    cfg, model, params, batch, mask, m = small
    cond = {k: batch[k][:1] for k in ("x", "R1", "t1", "K")}
    extra = model.precompute(params, cond)
    cache = extra["layer_cache"]
    assert [e is None for e in cache] == [False] * 6 + [True] * 2
    state, tail = cache[0]
    assert state.shape == (2, 8, 128) and state.dtype == jnp.float32
    assert tail.shape == (2, 3, 128)
    assert cache[1][0].shape == (2, 5, 32)      # the window's tail alone
    assert cache[5][0].shape == (2, 16, 32)     # layer 5's frame whole
    got = model.apply({"params": params}, dict(batch, **extra),
                      cond_mask=mask)
    assert rel(got, reference(params, m, batch, mask)) < TOL
    assert model.cond_cache_bytes(SIDE) == {
        "recurrent_state": 3 * (8 * 128 + 3 * 128) * 4,
        "window_tail": 2 * 2 * 5 * 32 * 4, "keys_values": 2 * 16 * 32 * 4}
    # two window layers; query r sees keys r … 20 of [5 tail rows ; 16 own]
    visited, visible = model.window_key_columns(SIDE)
    assert visible == 2 * sum(21 - r for r in range(16))
    assert visited >= visible


def test_the_window_tail_alone_suffices(monkeypatch):
    """A window layer's cache of the WHOLE conditioning frame gives a step
    the same ε̂, to the bit, as the last window − 1 rows: no target query
    sees an earlier row."""
    cfg = small_cfg()
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    cond = {k: batch[k][:1] for k in ("x", "R1", "t1", "K")}
    tails = model.precompute(params, cond)
    real = token_denoiser.Phi4FlashLayer._attn

    def whole_frame(self, i, layer, h, cache, published):
        out, own = real(self, i, layer, h, cache, published)
        if self.window(i) is not None and cache is None:
            a = token_denoiser.layer_norm(h, layer["norm"], 1e-5)
            qkv = a @ layer["attn"]["qkv"]["kernel"] \
                + layer["attn"]["qkv"]["bias"]
            own = (qkv[..., 64:96], qkv[..., 96:])
        return out, own

    monkeypatch.setattr(token_denoiser.Phi4FlashLayer, "_attn", whole_frame)
    wholes = model.precompute(params, cond)
    monkeypatch.undo()
    assert tails["layer_cache"][1][0].shape == (2, 5, 32)
    assert wholes["layer_cache"][1][0].shape == (2, 16, 32)
    np.testing.assert_array_equal(wholes["layer_cache"][1][0][:, -5:],
                                  tails["layer_cache"][1][0])
    got = [model.apply({"params": params}, dict(batch, **c), cond_mask=mask)
           for c in (tails, wholes)]
    np.testing.assert_array_equal(got[0], got[1])


def _stamps(text):
    return {kind for path in re.findall(r'op_name="([^"]+)"', text)
            for kind in re.findall(r"lk\.(\w+)", path.split(";", 1)[0])}


def test_the_once_a_call_pass_stops_at_the_last_cached_layer():
    """`precompute` lowers with no gated memory unit and no cross layer in
    it — by construction, not by the compiler's dead-code pass: the
    UNOPTIMISED text has none — and with every kind of the self-decoder;
    a step has them all."""
    cfg = small_cfg()
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    cond = {k: batch[k][:1] for k in ("x", "R1", "t1", "K")}
    once = jax.jit(model.precompute).lower(params, cond).as_text(
        debug_info=True)
    assert "lk.ssm_core" in once and "lk.attn_window" in once
    assert "lk.gmu" not in once and "lk.attn_cross" not in once
    assert "og.layer_5" in once and "og.layer_6" not in once
    extra = model.precompute(params, cond)
    step = jax.jit(lambda p, b: model.apply(
        {"params": p}, b, cond_mask=mask)).lower(
        params, dict(batch, **extra)).as_text(debug_info=True)
    for kind in ("ssm_proj", "ssm_conv", "ssm_core", "gqa_proj",
                 "attn_window", "attn_full", "attn_cross", "gmu",
                 "dense_mlp"):
        assert f"lk.{kind}" in step, kind


def test_the_lost_caches_and_the_precision_all_show():
    """The reference's own controls at the small size: every Mamba state
    zeroed at the target frame's first token, the cross layers' shared
    cache lost, and bfloat16 inputs, each far past the tolerance."""
    cfg = small_cfg()
    _, params = seeded(cfg)
    batch, mask = doubled_batch()
    m = token_check_ssm.model_sizes(cfg)
    want = reference(params, m, batch, mask)
    assert rel(ref.forward(params, m, batch, mask, zero_state_at=16),
               want) > 100 * TOL
    assert rel(ref.forward(params, m, batch, mask, lost_shared_cache=True),
               want) > 100 * TOL
    assert rel(ref.forward(params, m, batch, mask, "bf16"), want) > 100 * TOL


def test_guided_eps_through_make_sampler(small):
    """`make_sampler`'s first state is the guided ε̂ of the reference put
    through the ancestral update."""
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
@pytest.mark.parametrize("path,want", [
    ("jit(sampler)/precompute/og.layer_0/lk.ssm_core/pt.kernel/ssm_fwd",
     ("layer_0", "ssm_core")),
    ("jit(sampler)/while/body/lk.update/og.layer_6/lk.gmu/pt.matmul/dot",
     ("layer_6", "gmu")),
    ("jit(sampler)/while/body/lk.update/og.layer_7/lk.attn_cross/pt.kernel/"
     "flash_fwd", ("layer_7", "attn_cross")),
    ("jit(sampler)/while/body/lk.update/og.layer_2/lk.ssm_conv/mul",
     ("layer_2", "ssm_conv")),
])
def test_layer_of_reads_the_trunks_paths(path, want):
    assert layer_of(path) == want


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
        SSM_TOKEN_LAYER_KINDS)
    for kind in ("ssm_proj", "ssm_conv", "ssm_core"):
        assert seen[kind] == {"layer_0", "layer_2", "layer_4"}
    assert seen["attn_window"] == {"layer_1", "layer_3"}
    assert seen["attn_full"] == {"layer_5"}
    assert seen["gmu"] == {"layer_6"} and seen["attn_cross"] == {"layer_7"}
    assert seen["gqa_proj"] == {"layer_1", "layer_3", "layer_5", "layer_7"}
    assert seen["dense_mlp"] == {f"layer_{i}" for i in range(8)}


def test_preset_is_the_published_config_uncut():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "p4f_denoiser256.json")) as fh:
        conf = json.load(fh)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = [c for c in json.load(fh)["configs"]
                 if c["name"] == "p4f_denoiser256"][0]
    assert entry["source"] == conf["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
        "main/config.json")
    assert entry["reduced"] == conf["reduced"] == ["sample_timesteps"]
    cfg = get_preset(conf["preset"]).validate()
    k = cfg.model.tokens
    assert isinstance(k, Phi4FlashTrunkConfig)
    m = token_check_ssm.model_sizes(cfg)
    for name, value in conf.items():
        if name in m and name != "name":
            assert m[name] == value, name
    assert (k.hidden_size, k.num_hidden_layers, k.num_attention_heads,
            k.num_key_value_heads, k.head_dim, k.sliding_window,
            k.intermediate_size, k.mb_per_layer) == (
                2560, 32, 40, 20, 64, 512, 10240, 2)
    assert (k.mamba_d_inner, k.mamba_d_state, k.mamba_d_conv,
            k.mamba_dt_rank) == (5120, 16, 4, 160)
    assert cfg.data.img_sidelength == 256
    shapes = token_denoiser.param_shapes(cfg.model)
    size = {g: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(t))
            for g, t in shapes.items()}
    assert 119.8e6 < size["layer_0"] < 120.0e6      # Mamba 41.24 + MLP 78.64
    assert 98.3e6 < size["layer_1"] < 98.4e6        # attention 19.67 + MLP
    assert 104.8e6 < size["layer_18"] < 104.9e6     # GMU 26.21 + MLP
    assert 91.7e6 < size["layer_19"] < 91.8e6       # cross 13.11 + MLP
    trunk = sum(v for g, v in size.items() if g.startswith("layer_"))
    assert 3.339e9 < trunk < 3.341e9                # 6.68 GB in bfloat16
    model = build_denoiser(cfg.model)
    by_kind = model.cond_cache_bytes(256)
    assert by_kind == {
        "recurrent_state": 9 * (16 * 5120 * 4 + 3 * 5120 * 2),
        "window_tail": 8 * 2 * 511 * 1280 * 2,
        "keys_values": 2 * 4096 * 1280 * 2}
    assert 45.0e6 < sum(by_kind.values()) < 45.2e6
    visited, visible = model.window_key_columns(256)
    assert visible == 8 * int(2559.5 * 4096) and visited >= visible


def test_config_round_trip_and_refusals():
    cfg = small_cfg()
    again = Config.from_json(cfg.to_json())
    assert again == cfg
    assert isinstance(again.model.tokens, Phi4FlashTrunkConfig)
    for name in ("ms4_denoiser128", "st21_denoiser256", "kl48_denoiser256"):
        other = get_preset(name)
        assert type(Config.from_json(other.to_json()).model.tokens) is type(
            other.model.tokens)
    for over, word in [
        ({"model.tokens.num_hidden_layers": 6}, "N/2 must be a Mamba"),
        ({"model.tokens.mb_per_layer": 1}, "N/2 must be a Mamba"),
        ({"model.tokens.num_key_value_heads": 1}, "pairs adjacent heads"),
        ({"model.tokens.hidden_act": "gelu"}, "hidden_act"),
        ({"data.img_sidelength": 18}, "patch_size"),
    ]:
        with pytest.raises(ValueError, match=word):
            small_cfg(**over)


def test_a_trunk_without_experts_routes_nothing_and_says_so(small):
    cfg, model, params, batch, mask, _ = small
    for read in (model.routing_counts, model.routing_choices):
        with pytest.raises(NotImplementedError,
                           match="Phi4FlashTrunkConfig is a trunk without "
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


def test_init_draws_mamba_as_the_public_implementation_does():
    cfg = small_cfg()
    model = build_denoiser(cfg.model)
    p = model.init({"params": jax.random.PRNGKey(0)})["params"]
    mamba = p["layer_0"]["mamba"]
    np.testing.assert_allclose(jnp.exp(mamba["A_log"][7]), np.arange(1, 9),
                               rtol=1e-6)
    assert bool((mamba["D"] == 1).all())
    step = jax.nn.softplus(mamba["dt"]["bias"])
    assert 1e-3 <= float(step.min()) and float(step.max()) <= 1e-1 * 1.001
    assert float(jnp.abs(mamba["dt"]["kernel"]).max()) <= 4 ** -0.5
    assert bool((p["layer_1"]["norm"]["bias"] == 0).all())
    assert 0.0 < float(jnp.std(p["layer_1"]["attn"]["lambda_q1"])) < 0.2

"""The token denoiser (models/token_denoiser.py) against the benchmark's
plain reference (benchmarks/reference/ms4_ref.py) at a small size on the
CPU, in float32 on both sides: hidden 64, 2 layers, 8 experts top-2, 4
held (32 experts in the share test). Weights are the benchmark's seeded
ones (benchmarks/token_weights.py), so no output adapter is zero.

Tolerances. Both sides compute in float32 here and differ by the order of
their sums (a grouped product over sorted rows against a dense masked
loop; a cached prefix against one masked softmax): TOL = 2e-5 relative
rms is ~50× the 4e-7 they read, and the same reference with its matmul
inputs rounded to bfloat16 reads ~1e-2, so a lower precision in the
reference's place fails every one of these (asserted once, below).
"""

import dataclasses
import math
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
import token_weights  # noqa: E402
from novel_view_synthesis_3d_tpu.config import get_preset  # noqa: E402
from novel_view_synthesis_3d_tpu.diffusion.schedules import (  # noqa: E402
    sampling_schedule)
from novel_view_synthesis_3d_tpu.models import (  # noqa: E402
    build_denoiser, token_denoiser)
from novel_view_synthesis_3d_tpu.models.vocab import (  # noqa: E402
    TOKEN_LAYER_KINDS, layer_of, layer_part_of)
from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler  # noqa: E402

TOL = 2e-5
SIDE = 16
SMALL = {
    "model.tokens.hidden_size": 64, "model.tokens.num_hidden_layers": 2,
    "model.tokens.num_attention_heads": 4, "model.tokens.q_lora_rank": 32,
    "model.tokens.kv_lora_rank": 16, "model.tokens.qk_nope_head_dim": 8,
    "model.tokens.qk_rope_head_dim": 8, "model.tokens.v_head_dim": 16,
    "model.tokens.n_routed_experts": 8,
    "model.tokens.num_experts_per_tok": 2,
    "model.tokens.moe_intermediate_size": 32,
    "model.tokens.held_experts": [0, 4], "data.img_sidelength": SIDE,
    "model.dtype": "float32", "model.param_dtype": "float32",
    "diffusion.sample_timesteps": 4,
}
ref = harness.load_module(os.path.join(
    ROOT, "benchmarks", "reference", "ms4_ref.py"), "ms4_ref")


def small_cfg(**over):
    return get_preset("ms4_denoiser128").override(
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


@pytest.fixture(scope="module")
def small():
    cfg = small_cfg()
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    return cfg, model, params, batch, mask, token_check.model_sizes(cfg)


def test_full_forward_matches_the_reference(small):
    cfg, model, params, batch, mask, m = small
    eps = model.apply({"params": params}, batch, cond_mask=mask, train=False)
    want = ref.forward(params, m, batch, mask)
    assert eps.shape == (2, SIDE, SIDE, 3) and eps.dtype == jnp.float32
    assert rel(eps, want) < TOL
    # the two guidance rows differ (the ray term is masked in one)
    assert rel(eps[0], eps[1]) > 1e-2


def test_a_lower_precision_in_the_references_place_fails(small):
    cfg, model, params, batch, mask, m = small
    want = ref.forward(params, m, batch, mask)
    assert rel(ref.forward(params, m, batch, mask, "bf16"), want) > 100 * TOL
    assert rel(ref.forward(params, m, batch, mask, "fp8"), want) > 1000 * TOL


def test_precompute_then_step_matches_the_full_forward(small):
    """Prefill of the conditioning frame into the latent cache, then the
    target's tokens alone against [cache ; own], is the reference's ONE
    masked forward over both frames."""
    cfg, model, params, batch, mask, m = small
    cond = {k: v[:1] for k, v in batch.items() if k not in ("z", "logsnr")}
    pre = model.precompute(params, cond)
    k = cfg.model.tokens
    assert len(pre["latent_cache"]) == k.num_hidden_layers
    c_kv, k_rope = pre["latent_cache"][0]
    L = (SIDE // k.patch_size) ** 2
    assert c_kv.shape == (2, L, k.kv_lora_rank)
    assert k_rope.shape == (2, L, k.qk_rope_head_dim)
    eps = model.apply({"params": params}, dict(batch, **pre), cond_mask=mask,
                      train=False)
    assert rel(eps, ref.forward(params, m, batch, mask)) < TOL
    # and a second step with another state reuses the same cache
    batch2 = dict(batch, z=batch["z"] * 0.5 + 0.1,
                  logsnr=jnp.full((2,), -2.0))
    eps2 = model.apply({"params": params}, dict(batch2, **pre),
                       cond_mask=mask, train=False)
    assert rel(eps2, ref.forward(params, m, batch2, mask)) < TOL


def test_shipped_attention_form_against_the_other(small):
    """The program up-projects keys and values from the latent; the
    reference also writes the absorbed form. All three agree."""
    cfg, model, params, batch, mask, m = small
    eps = model.apply({"params": params}, batch, cond_mask=mask, train=False)
    absorbed = ref.forward(params, m, batch, mask, "f32", "absorbed")
    assert rel(eps, absorbed) < TOL
    assert rel(ref.forward(params, m, batch, mask), absorbed) < TOL


def test_guided_eps_through_make_sampler(small):
    """Every step of `make_sampler(trajectory_every=1)`: the program's
    guided ε̂, read back from the returned states by inverting the update,
    against the reference's guided ε̂ from the program's input state."""
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
    # (1 + w) amplifies the rows' 4e-7 by up to 7, the inversion divides
    # by c1: still float32 rounding, three orders under bfloat16's.
    assert token_check.sampling_check.pooled(rows, "program") < 10 * TOL


# ---------------------------------------------------------------------------
# Rotary embedding against hand values
# ---------------------------------------------------------------------------
def test_rope_interleaved_pairs_by_hand():
    k = small_cfg().model.tokens
    x = jnp.asarray([[[1.0, 0.0, 0.0, 2.0, 3.0, 4.0, 0.5, -1.0]]])
    cos, sin, _ = token_denoiser.rope_tables(np.array([3]), k)
    out = np.asarray(token_denoiser.apply_rope(x, cos, sin, True))[0, 0]
    inv = token_denoiser.yarn_inv_freq(k.rope_parameters, 8)
    for i in range(4):
        a, b = float(x[0, 0, 2 * i]), float(x[0, 0, 2 * i + 1])
        c, s = math.cos(3 * inv[i]), math.sin(3 * inv[i])
        assert out[2 * i] == pytest.approx(a * c - b * s, abs=1e-6)
        assert out[2 * i + 1] == pytest.approx(b * c + a * s, abs=1e-6)
    # the half-split form pairs (i, i + dim/2) instead
    half = np.asarray(token_denoiser.apply_rope(x, cos, sin, False))[0, 0]
    c, s = math.cos(3 * inv[0]), math.sin(3 * inv[0])
    assert half[0] == pytest.approx(1.0 * c - 3.0 * s, abs=1e-6)
    assert half[4] == pytest.approx(3.0 * c + 1.0 * s, abs=1e-6)


@pytest.mark.parametrize("interleave", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_queries_rotated_where_their_product_writes_them(interleave, dtype):
    """`rotated_queries` — x·q_b with every head's rotary lanes rotated by
    way of a second product against pair-swapped columns — is, to the bit,
    the product sliced head by head, its rotary part through `apply_rope`
    and the two parts concatenated again."""
    heads, nope, rope, L = 3, 8, 8, 5
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (2, L, 16), dtype)
    q_b = {"kernel": jax.random.normal(ks[1], (16, heads * (nope + rope)),
                                       dtype)}
    ang = np.random.RandomState(1).uniform(0, 6, (L, rope // 2))
    cos, sin = (f(ang).astype(np.float32) for f in (np.cos, np.sin))
    q = jnp.dot(x, q_b["kernel"]).reshape(2, L, heads, nope + rope)
    want = jnp.concatenate(
        [q[..., :nope],
         token_denoiser.apply_rope(q[..., nope:], cos, sin, interleave)],
        axis=-1).reshape(2, L, -1)
    got = token_denoiser.rotated_queries(
        x, q_b, token_denoiser.pair_swapped_kernel(q_b, heads, nope,
                                                   interleave),
        heads, cos, sin, interleave)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("dn,dr,dv", [(8, 8, 16), (16, 8, 16)])
def test_latent_keys_and_values_each_by_a_product_of_their_own(dn, dr, dv):
    """`latent_keys_values` — the values by their columns of `kv_b`, the
    keys by theirs over an identity block that carries the shared part to
    every head — is, to the bit, `kv_b`'s whole product sliced head by
    head and the shared part concatenated behind every head's keys."""
    heads, rank, B, Lk = 3, 12, 2, 7
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    c_kv = jax.random.normal(ks[0], (B, Lk, rank), jnp.bfloat16)
    shared = jax.random.normal(ks[1], (B, Lk, dr), jnp.bfloat16)
    kv_b = {"kernel": jax.random.normal(
        ks[2], (rank, heads * (dn + dv)), jnp.bfloat16)}
    kv = jnp.dot(c_kv, kv_b["kernel"]).reshape(B, Lk, heads, dn + dv)
    want_k = jnp.concatenate(
        [kv[..., :dn],
         jnp.broadcast_to(shared[:, :, None, :], (B, Lk, heads, dr))],
        axis=-1)
    keys, values = token_denoiser.latent_keys_values(
        c_kv, shared, heads=heads,
        **token_denoiser.latent_kernels(kv_b, heads, dn, dr))
    np.testing.assert_array_equal(np.asarray(keys, np.float32),
                                  np.asarray(want_k, np.float32))
    np.testing.assert_array_equal(np.asarray(values, np.float32),
                                  np.asarray(kv[..., dn:], np.float32))


def _widths(heads, dn, dr, interleave):
    """The four fields `attention_kernels` and `shares_key_part` read."""
    import types

    return types.SimpleNamespace(
        num_attention_heads=heads, qk_nope_head_dim=dn, qk_rope_head_dim=dr,
        rope_interleave=interleave)


@pytest.mark.parametrize("interleave", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_split_queries_are_the_slices_of_the_whole_product(interleave,
                                                           dtype):
    """At 128 + 64 (`shares_key_part`) the queries leave as two operands:
    the nope one is the nope slice of x·q_b, the rotary one `apply_rope`
    of its rotary slice — `rotated_queries` on the rotary columns ALONE,
    against their own pair-swapped kernel with no zero column — to the bit
    what `rotated_queries` writes into the whole 192-wide head."""
    heads, dn, dr, L = 2, 128, 64, 5
    k = _widths(heads, dn, dr, interleave)
    assert token_denoiser.shares_key_part(k)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (2, L, 16), dtype)
    p = {"q_b": {"kernel": jax.random.normal(
             ks[1], (16, heads * (dn + dr)), dtype)},
         "kv_b": {"kernel": jax.random.normal(
             ks[2], (12, heads * (dn + 128)), dtype)}}
    d = token_denoiser.attention_kernels(k, p, "q_b", rotary=True)
    assert {n: v["kernel"].shape for n, v in d.items()} == {
        "q_b_nope": (16, heads * dn), "q_b_rope": (16, heads * dr),
        "q_b_rope_pair": (16, heads * dr), "k_nope": (12, heads * dn),
        "v_b": (12, heads * 128)}
    ang = np.random.RandomState(1).uniform(0, 6, (L, dr // 2))
    cos, sin = (f(ang).astype(np.float32) for f in (np.cos, np.sin))
    q = jnp.dot(x, p["q_b"]["kernel"]).reshape(2, L, heads, dn + dr)
    nope = jnp.dot(x, d["q_b_nope"]["kernel"])
    rope = token_denoiser.rotated_queries(
        x, d["q_b_rope"], d["q_b_rope_pair"], heads, cos, sin, interleave)
    assert nope.dtype == rope.dtype == q.dtype
    np.testing.assert_array_equal(
        np.asarray(nope, np.float32).reshape(2, L, heads, dn),
        np.asarray(q[..., :dn], np.float32))
    np.testing.assert_array_equal(
        np.asarray(rope, np.float32).reshape(2, L, heads, dr),
        np.asarray(token_denoiser.apply_rope(q[..., dn:], cos, sin,
                                             interleave), np.float32))
    whole = token_denoiser.rotated_queries(
        x, p["q_b"], token_denoiser.pair_swapped_kernel(
            p["q_b"], heads, dn, interleave), heads, cos, sin, interleave)
    np.testing.assert_array_equal(
        np.asarray(whole, np.float32).reshape(2, L, heads, dn + dr),
        np.concatenate([np.asarray(nope, np.float32).reshape(
            2, L, heads, dn), np.asarray(rope, np.float32).reshape(
                2, L, heads, dr)], axis=-1))
    # a trunk without a rotary derives no pair kernel, under its own name
    assert set(token_denoiser.attention_kernels(
        k, {"q": p["q_b"], "kv_b": p["kv_b"]}, "q", rotary=False)) == {
        "q_nope", "q_rope", "k_nope", "v_b"}


@pytest.mark.parametrize("dn,dr,rotary", [(64, 64, True), (16, 8, False),
                                          (128, 128, True)])
def test_heads_of_whole_lane_blocks_keep_the_one_operand_kernels(dn, dr,
                                                                 rotary):
    """Where a head is a whole number of lane blocks (64 + 64), or its own
    part is none (16 + 8: the toy sizes), `attention_kernels` derives what
    it derived: `latent_kernels`' pair and, for a trunk that rotates, the
    whole pair-swapped kernel."""
    heads = 2
    k = _widths(heads, dn, dr, True)
    assert not token_denoiser.shares_key_part(k)
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    p = {"q_b": {"kernel": jax.random.normal(ks[0],
                                             (8, heads * (dn + dr)))},
         "kv_b": {"kernel": jax.random.normal(ks[1],
                                              (6, heads * (dn + 16)))}}
    d = token_denoiser.attention_kernels(k, p, "q_b", rotary=rotary)
    want = token_denoiser.latent_kernels(p["kv_b"], heads, dn, dr)
    if rotary:
        want["q_b_pair"] = token_denoiser.pair_swapped_kernel(
            p["q_b"], heads, dn, True)
    assert set(d) == set(want)
    for n in want:
        np.testing.assert_array_equal(d[n]["kernel"], want[n]["kernel"])


def test_split_keys_are_kv_bs_key_columns_and_no_identity_block():
    """`k_nope` — the keys' columns of `kv_b` side by side — gives, to the
    bit, the nope lanes of `latent_keys_values`' keys, and `v_b` its
    values; the shared part is no product's business any more."""
    heads, dn, dr, dv, rank, B, Lk = 2, 128, 64, 128, 12, 2, 7
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    c_kv = jax.random.normal(ks[0], (B, Lk, rank), jnp.bfloat16)
    shared = jax.random.normal(ks[1], (B, Lk, dr), jnp.bfloat16)
    kv_b = {"kernel": jax.random.normal(
        ks[2], (rank, heads * (dn + dv)), jnp.bfloat16)}
    keys, values = token_denoiser.latent_keys_values(
        c_kv, shared, heads=heads,
        **token_denoiser.latent_kernels(kv_b, heads, dn, dr))
    k_nope, v_b = token_denoiser.split_columns(kv_b, heads, dn)
    assert k_nope["kernel"].shape == (rank, heads * dn)
    np.testing.assert_array_equal(
        np.asarray(jnp.dot(c_kv, k_nope["kernel"]), np.float32).reshape(
            B, Lk, heads, dn), np.asarray(keys[..., :dn], np.float32))
    np.testing.assert_array_equal(
        np.asarray(jnp.dot(c_kv, v_b["kernel"]), np.float32).reshape(
            B, Lk, heads, dv), np.asarray(values, np.float32))
    np.testing.assert_array_equal(
        np.asarray(keys[..., dn:], np.float32), np.asarray(
            jnp.broadcast_to(shared[:, :, None], (B, Lk, heads, dr)),
            np.float32))


def test_rms_norm_over_runs_of_lanes_is_the_norm_of_the_4d_view():
    """`rms_norm_lane_groups` — the statistic and its way back as products
    with the groups' indicator, a float32 as the three bfloat16 terms that
    hold all its bits — is `rms_norm` of x seen as (…, groups, width) to
    float32's rounding: the indicator's products are exact (a group's
    statistic comes back bit for bit), only the mean's float32 sum runs in
    another order. Values over six decades, so a dropped low term (2⁻¹⁷
    of the statistic) could not hide."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (2, 9, 5 * 16)) \
        * 10.0 ** jax.random.uniform(ks[1], (2, 9, 5 * 16), minval=-3,
                                     maxval=3)
    scale = jax.random.normal(ks[2], (16,), jnp.bfloat16)
    want = token_denoiser.rms_norm(x.reshape(2, 9, 5, 16), scale,
                                   1e-5).reshape(2, 9, -1)
    got = jax.jit(token_denoiser.rms_norm_lane_groups,
                  static_argnums=2)(x, scale, 16, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=4 * 2.0 ** -24, atol=0)


def test_yarn_blend_at_the_published_sizes_by_hand():
    """dim 64, θ 10000, factor 128, original length 8192, β 32/1: the
    correction dimensions are ⌊64·ln(8192/(32·2π))/(2·ln 10000)⌋ = 12 and
    ⌈64·ln(8192/(2π))/(2·ln 10000)⌉ = 25. Pairs below 12 keep θ^(−2i/64),
    pairs from 25 on are ÷ 128, between them the linear ramp."""
    rope = get_preset("ms4_denoiser128").model.tokens.rope_parameters
    inv = token_denoiser.yarn_inv_freq(rope, 64)
    base = 10000.0 ** (-np.arange(32) / 32.0)
    assert math.floor(64 * math.log(8192 / (32 * 2 * math.pi))
                      / (2 * math.log(10000))) == 12
    assert math.ceil(64 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(10000))) == 25
    np.testing.assert_allclose(inv[:13], base[:13], rtol=1e-12)
    np.testing.assert_allclose(inv[25:], base[25:] / 128, rtol=1e-12)
    r = (18 - 12) / (25 - 12)
    assert inv[18] == pytest.approx(base[18] * (1 - r) + base[18] / 128 * r,
                                    rel=1e-12)
    np.testing.assert_allclose(ref.yarn_inv_freq(
        dataclasses.asdict(rope), 64), inv, rtol=1e-12)


def test_query_position_scale_past_the_original_length():
    k = get_preset("ms4_denoiser128").model.tokens
    _, _, scale = token_denoiser.rope_tables(
        np.array([0, 2047, 8191, 8192, 20000]), k)
    want = [1.0, 1.0, 1.0, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(3)]
    np.testing.assert_allclose(scale, want, rtol=1e-6)
    m = {"rope_parameters": dataclasses.asdict(k.rope_parameters)}
    np.testing.assert_allclose(ref.query_position_scale(
        np.array([0, 2047, 8191, 8192, 20000]), m), want, rtol=1e-12)
    assert token_denoiser.softmax_scale(k) == pytest.approx(
        128 ** -0.5 * (0.1 * math.log(128) + 1) ** 2)


def test_a_sequence_past_8192_scales_its_queries():
    """With the original length shrunk to 8 the second frame's tokens lie
    past it: program and reference still agree, and the scale matters."""
    over = {"model.tokens.rope_parameters": dict(dataclasses.asdict(
        small_cfg().model.tokens.rope_parameters),
        original_max_position_embeddings=8, factor=4.0)}
    cfg = small_cfg(**over)
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    m = token_check.model_sizes(cfg)
    eps = model.apply({"params": params}, batch, cond_mask=mask, train=False)
    assert rel(eps, ref.forward(params, m, batch, mask)) < TOL
    flat = dict(m, rope_parameters=dict(m["rope_parameters"],
                                        llama_4_scaling_beta=0.0))
    assert rel(eps, ref.forward(params, flat, batch, mask)) > 100 * TOL


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_heads_of_128_and_64_take_two_operands_and_match_the_reference(
        kernel):
    """The same layer at heads of 128 + 64 on 128 — whole lane blocks of
    their own beside half a block all heads share, where `shares_key_part`
    chooses the two-operand form — with its second frame past the original
    length (the position scale goes on BOTH query operands): the reference
    on whole 192-wide heads, through XLA's attention and through the
    kernel's two products a score."""
    over = {"model.tokens.rope_parameters": dict(dataclasses.asdict(
        small_cfg().model.tokens.rope_parameters),
        original_max_position_embeddings=8, factor=4.0),
        "model.tokens.num_attention_heads": 2,
        "model.tokens.qk_nope_head_dim": 128,
        "model.tokens.qk_rope_head_dim": 64,
        "model.tokens.v_head_dim": 128,
        "model.use_flash_attention": kernel}
    cfg = small_cfg(**over)
    assert token_denoiser.shares_key_part(cfg.model.tokens)
    assert not token_denoiser.shares_key_part(small_cfg().model.tokens)
    assert not token_denoiser.shares_key_part(
        get_preset("ms4_denoiser128").model.tokens)
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    m = token_check.model_sizes(cfg)
    want = ref.forward(params, m, batch, mask)
    eps = model.apply({"params": params}, batch, cond_mask=mask, train=False)
    assert rel(eps, want) < TOL
    cond = {k: v[:1] for k, v in batch.items() if k not in ("z", "logsnr")}
    pre = model.precompute(params, cond)
    assert set(pre["derived"]["layer_0"]) == {
        "q_b_nope", "q_b_rope", "q_b_rope_pair", "k_nope", "v_b"}
    eps = model.apply({"params": params}, dict(batch, **pre), cond_mask=mask,
                      train=False)
    assert rel(eps, want) < TOL


# ---------------------------------------------------------------------------
# The expert layer is told which experts it holds
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def layer32():
    """One layer with 32 routed experts, all weights held, and a token
    block: what the four shares of 8 divide."""
    cfg = small_cfg(**{"model.tokens.n_routed_experts": 32,
                       "model.tokens.num_experts_per_tok": 4,
                       "model.tokens.held_experts": [0, 32],
                       "model.tokens.num_hidden_layers": 1})
    model, params = seeded(cfg, seed=8)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 64))
    return cfg, params["layer_0"], h, token_check.model_sizes(cfg)


def test_the_four_shares_add_up_to_the_uncut_layer(layer32):
    """Routed parts of experts 0–7, 8–15, 16–23, 24–31, each from a layer
    that holds only those, plus the shared expert and attention counted
    once, are the uncut reference layer's output."""
    cfg, p, h, m = layer32
    k = cfg.model.tokens
    want, aux = ref.layer(p, m, h, parts=True)
    b32 = token_denoiser.rms_norm(h + aux["attn"], p["mlp_norm"]["scale"],
                                  k.rms_norm_eps).reshape(-1, 64)
    top_p, top_i = token_denoiser.route(b32, p["router"], k)
    total, counted = 0.0, 0
    for first in (0, 8, 16, 24):
        share = dataclasses.replace(k, held_experts=(first, 8))
        held = jax.tree.map(lambda a: a[first:first + 8], p["experts"])
        part, counts = token_denoiser.held_expert_part(
            b32, top_p, top_i, held, share)
        # the same share, written plainly
        plain, _ = ref.experts_part(
            held, dict(m, held_experts=[first, 8]), b32, top_p, top_i, "f32")
        assert rel(part, plain) < TOL
        assert 0 < float(jnp.max(jnp.abs(part)))
        total = total + part
        counted += int(counts.sum())
    assert counted == b32.shape[0] * k.num_experts_per_tok  # every choice
    assert rel(total.reshape(h.shape), aux["routed"]) < TOL
    out = h + aux["attn"] + aux["shared"] + total.reshape(h.shape)
    assert rel(out, want) < TOL


def test_every_token_on_one_held_expert_loses_none(layer32):
    """A router driven so that every token's first choice is held expert 3
    (of experts 0–7 held): that expert is given every token, nothing is
    dropped, and the output is the reference's."""
    cfg, p, h, m = layer32
    k = dataclasses.replace(cfg.model.tokens, held_experts=(0, 8))
    b32 = token_denoiser.rms_norm(h, p["mlp_norm"]["scale"],
                                  k.rms_norm_eps).reshape(-1, 64)
    # a constant feature whose router row is +50 on expert 3's logit
    b_aug = jnp.concatenate([b32, jnp.ones((b32.shape[0], 1))], axis=1)
    kernel = jnp.concatenate(
        [p["router"]["kernel"], 50.0 * jax.nn.one_hot(3, 32)[None]], axis=0)
    top_p, top_i = token_denoiser.route(b_aug, {"kernel": kernel}, k)
    assert bool(jnp.all(top_i[:, 0] == 3))
    held = jax.tree.map(lambda a: a[:8], p["experts"])
    part, counts = token_denoiser.held_expert_part(b32, top_p, top_i, held, k)
    assert int(counts[3]) == b32.shape[0]
    assert int(counts.sum()) == int(jnp.sum(top_i < 8))
    plain, ref_counts = ref.experts_part(
        held, dict(m, held_experts=[0, 8]), b32, top_p, top_i, "f32")
    assert rel(part, plain) < TOL
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref_counts))


def test_routing_counts_are_the_programs_own(small):
    cfg, model, params, batch, mask, m = small
    counts = np.asarray(model.routing_counts(params, batch, mask))
    k = cfg.model.tokens
    assert counts.shape == (k.num_hidden_layers, k.held_experts[1])
    _, auxes = ref.forward(params, m, batch, mask, aux=True)
    L = (SIDE // k.patch_size) ** 2
    for layer, aux in zip(counts, auxes):
        # the program counts its pass over the target's tokens
        assert int(layer.sum()) == int(np.asarray(
            aux["held_hits"])[:, L:].sum())


# Group sizes against the 128-row tile: what the layout and the product
# have to get right whatever the router does.
RAGGED = {
    "an_empty_group_and_a_tail": [5, 0, 30, 13],
    "groups_past_one_tile": [300, 0, 7, 249],
    "nothing_held": [0, 0, 0, 0],
    "exactly_one_tile": [128, 0, 128, 0],
    "one_row": [0, 1, 0, 0],
    "a_row_past_a_tile": [129, 1, 127, 256],
}


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_grouped_matmul_is_the_shipped_kernel(case):
    """ops/grouped_matmul.py through the Pallas interpreter (the kernel the
    chip compiles) against a product per group over its whole span — pad
    rows are multiplied like any other; the rows past the last span are
    unspecified and not compared."""
    from novel_view_synthesis_3d_tpu.ops import grouped_matmul as gm

    sizes = np.asarray(RAGGED[case], np.int32)
    spans = gm.span_sizes(sizes)
    assert np.all(spans % gm.ROW_TILE == 0) and np.all(spans >= sizes)
    assert np.all(spans - sizes < gm.ROW_TILE)
    assert gm.rows_visited(sizes) == sum(
        math.ceil(n / gm.ROW_TILE) * gm.ROW_TILE for n in sizes)
    rows = gm.buffer_rows(int(sizes.sum()), len(sizes))
    assert rows >= spans.sum()
    k1, k2 = jax.random.split(jax.random.PRNGKey(int(sizes.sum())))
    lhs = jax.random.normal(k1, (rows, 48), jnp.float32)
    rhs = jax.random.normal(k2, (len(sizes), 48, 40), jnp.float32)
    got = np.asarray(gm.grouped_matmul(lhs, rhs, jnp.asarray(spans)))
    start = 0
    for g, n in enumerate(spans):
        if n:
            assert rel(got[start:start + n],
                       np.asarray(lhs[start:start + n]) @ np.asarray(rhs[g])
                       ) < TOL
        start += n
    assert got.shape == (rows, 40)


def test_column_blocks_and_the_worst_case_buffer():
    """The weights' column block adapts to K and N alone (all of N where
    an expert's block fits a VMEM slot, else the widest divisor that
    does), and the static buffer holds every way T·k rows can fall into
    `groups` aligned spans."""
    from novel_view_synthesis_3d_tpu.ops import grouped_matmul as gm

    assert gm._column_block(4096, 2048, 2) == 2048    # the cell's gate, up
    assert gm._column_block(2048, 4096, 2) == 4096    # and down
    assert gm._column_block(8192, 4096, 2) == 1024    # a wider expert
    assert gm._column_block(48, 40, 4) == 40
    rng = np.random.default_rng(0)
    for assignments, groups in ((32768, 32), (100, 3), (128, 1), (1, 4)):
        rows = gm.buffer_rows(assignments, groups)
        assert rows % gm.ROW_TILE == 0
        # every group one row past whole tiles: the most pad there can be
        worst = np.ones(min(groups, assignments), np.int64)
        worst[0] += assignments - worst.size
        assert gm.rows_visited(worst) <= rows
        for _ in range(20):
            sizes = rng.multinomial(assignments, rng.dirichlet(
                np.full(groups, 0.3)))
            assert gm.rows_visited(sizes) <= rows


# The combine kernel against the XLA form it replaced. (T, K, H, experts,
# held (first, count), dtype, VMEM slot bytes — small slots make small
# tiles, so a few hundred tokens walk many tiles and both slots —, what
# the case plants.)
COMBINE = {
    "one_tile_h64_float32": (96, 2, 64, 8, (0, 4), jnp.float32, None, ""),
    "ragged_tokens_bfloat16": (100, 4, 128, 16, (4, 8), jnp.bfloat16, None,
                               ""),
    "many_tiles_two_slots": (200, 4, 128, 16, (4, 8), jnp.bfloat16, 62000,
                             ""),
    "many_tiles_float32": (150, 3, 64, 8, (2, 5), jnp.float32, 30000, ""),
    "all_k_held": (130, 6, 256, 8, (0, 8), jnp.bfloat16, 160000, ""),
    "nothing_held": (64, 4, 128, 16, (4, 8), jnp.bfloat16, 46000,
                     "nothing_held"),
    "a_token_without_a_held_choice": (96, 2, 128, 8, (0, 2), jnp.bfloat16,
                                      23000, "some_none"),
    "a_held_choice_of_weight_zero": (160, 4, 128, 8, (0, 6), jnp.bfloat16,
                                     54000, "zero_weight"),
    "one_expert_takes_everything": (256, 2, 128, 8, (3, 2), jnp.bfloat16,
                                    39000, "one_expert"),
    "float16_rows": (48, 2, 128, 4, (0, 4), jnp.float16, None, ""),
}


def _xla_combine(y, back, w, dtype):
    """The combine as it stood until the kernel: one gather a choice, its
    cast, mask and weighted float32 sum, one cast at the end."""
    out = jnp.zeros((back.shape[0], y.shape[-1]), jnp.float32)
    for c in range(back.shape[1]):
        yc = jnp.take(y, back[:, c], axis=0).astype(jnp.float32)
        wc = w[:, c:c + 1]
        out = out + jnp.where(wc > 0, yc * wc, 0.0)
    return out.astype(dtype)


@pytest.mark.parametrize("case", sorted(COMBINE))
def test_expert_combine_is_the_shipped_kernel(case, monkeypatch):
    """ops/expert_combine.py through the Pallas interpreter (the kernel the
    chip compiles) against the XLA form written out above, on
    held_expert_part's own layout: BITWISE equal — the choice order, the
    float32 sum and the one cast are the same operations. `y` is NaN in
    every row no fetched choice points at (pad rows, the rows past the last
    span, rows of weight 0), so a chunk's other rows, a skipped choice's
    stale slot and a masked choice never reach a sum."""
    from novel_view_synthesis_3d_tpu.ops import expert_combine as ec
    from novel_view_synthesis_3d_tpu.ops import grouped_matmul as gm

    T, K, H, n_experts, (first, count), dtype, slot_bytes, plant = \
        COMBINE[case]
    if slot_bytes:
        monkeypatch.setattr(ec, "SLOT_BYTES", slot_bytes)
        tt = ec.token_tile(T, K, count, H, jnp.dtype(dtype).itemsize
                           if dtype != jnp.float16 else 4)
        assert T > 2 * tt, (case, tt)                 # both slots, refilled
    ec._combine.clear_cache()
    rng = np.random.default_rng(len(case))
    top_i = _choices({"nothing_held": "nothing_held",
                      "one_expert": "everything_held"}.get(
        plant, "independent" if count < n_experts else "everything_held"),
        T, K, first, count, n_experts, rng)
    if plant == "one_expert":
        top_i[:, 0] = first + 1
        top_i[:, 1] = (first + count) % n_experts     # not held
    top_p = rng.random((T, K)).astype(np.float32) + 0.05
    top_i = jnp.asarray(top_i, jnp.int32)
    local = top_i.reshape(-1) - first
    is_held = (local >= 0) & (local < count)
    slot = jnp.where(is_held, local, count)
    sizes = jnp.bincount(slot, length=count + 1)[:count].astype(jnp.int32)
    spans = gm.span_sizes(sizes)
    spare = gm.buffer_rows(T * K, count) - T * K
    i = jnp.arange(spare)
    pad_slot = jnp.where(i % gm.ROW_TILE < jnp.repeat(
        spans - sizes, gm.ROW_TILE, total_repeat_length=spare),
        jnp.minimum(i // gm.ROW_TILE, count), count)
    order = jnp.argsort(jnp.concatenate([pad_slot, slot]), stable=True)
    back = jnp.argsort(order)[spare:].reshape(T, K)
    w = np.where(np.asarray(is_held).reshape(T, K), top_p, 0.0)
    if plant == "zero_weight":
        w[::3, 1] = 0.0
    held_per_token = (w > 0).sum(-1)
    if plant in ("", "some_none") and count < n_experts:
        assert 0 in held_per_token and held_per_token.max() >= 2
    if case == "all_k_held":
        assert np.all(held_per_token == K)
    y = rng.standard_normal((gm.buffer_rows(T * K, count), H)).astype(
        np.float32)
    read = np.zeros(len(y), bool)
    read[np.asarray(back)[w > 0]] = True
    assert read.sum() == (w > 0).sum()
    y[~read] = np.nan
    y, w = jnp.asarray(y).astype(dtype), jnp.asarray(w, jnp.float32)
    try:
        got = ec.combine(y, back, w, slot, sizes, dtype)
    finally:
        ec._combine.clear_cache()
    want = _xla_combine(y, back, w, dtype)
    assert got.shape == (T, H) and got.dtype == want.dtype
    assert not np.isnan(np.asarray(got, np.float32)).any()
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert ec.rows_fetched(sizes) == int(np.asarray(is_held).sum())


def test_token_tiles_and_the_worst_case_slot():
    """The token tile adapts to (K, experts held, H, the rows' bytes) alone:
    the widest whose VMEM slot holds the chunks a tile can need in the
    worst case — and no routing needs more: every expert's range a row
    past a chunk's end at both ends is the bound, random ranges stay
    under it."""
    from novel_view_synthesis_3d_tpu.ops import expert_combine as ec

    assert ec.token_tile(8192, 4, 32, 4096, 2) == 512      # the three cells
    assert ec.token_tile(16384, 6, 64, 2560, 2) == 512
    assert ec.token_tile(16384, 8, 128, 2304, 2) == 256
    assert ec.token_tile(96, 2, 4, 64, 4) == 96            # no wider than T
    assert ec.token_tile(100, 2, 4, 64, 4) == 112
    for tt, K, groups, H in ((512, 4, 32, 4096), (512, 6, 64, 2560),
                             (256, 8, 128, 2304)):
        assert (ec.chunks_max(tt, K, groups) * ec.CHUNK * H * 2
                <= ec.SLOT_BYTES
                < ec.chunks_max(2 * tt, K, groups) * ec.CHUNK * H * 2)
    rng = np.random.default_rng(0)
    for tt, K, groups in ((32, 4, 8), (16, 2, 64), (64, 6, 5)):
        most = ec.chunks_max(tt, K, groups)
        for _ in range(50):
            # a tile's assignments among the experts, each range anywhere
            count = rng.multinomial(tt * K, rng.dirichlet(
                np.full(groups, 0.3)))
            first = rng.integers(0, 1 << 16, groups)
            chunks = np.where(count > 0, -(-(first + count) // ec.CHUNK)
                              - first // ec.CHUNK, 0)
            assert chunks.sum() <= most
        # the worst case: as many experts as may be, one row each across a
        # chunk's end... (a range of one row touches one chunk; of two, two)
        count = np.zeros(groups, np.int64)
        count[:min(groups, tt * K // 2)] = 2
        count[0] += tt * K - count.sum()
        first = np.full(groups, ec.CHUNK - 1)
        chunks = np.where(count > 0, -(-(first + count) // ec.CHUNK)
                          - first // ec.CHUNK, 0)
        assert chunks.sum() <= most


def _spy_on_the_products(monkeypatch):
    """Every (lhs, group_sizes) that held_expert_part hands the seam the
    benchmark's control replaces, the product itself untouched."""
    calls = []
    real = token_denoiser.grouped_matmul

    def spy(lhs, rhs, group_sizes):
        calls.append((np.asarray(lhs), np.asarray(group_sizes)))
        return real(lhs, rhs, group_sizes)

    monkeypatch.setattr(token_denoiser, "grouped_matmul", spy)
    return calls


def _choices(case, T, K, first, count, n_experts, rng):
    """top_i (T, K), distinct experts a token, by the case's name."""
    held = np.arange(first, first + count)
    absent = np.setdiff1d(np.arange(n_experts), held)
    if case == "nothing_held":
        return np.stack([rng.choice(absent, K, replace=False)
                         for _ in range(T)])
    if case == "everything_held":
        return np.stack([rng.choice(held, K, replace=False)
                         for _ in range(T)])
    top_i = np.stack([rng.choice(n_experts, K, replace=False)
                      for _ in range(T)])
    if case == "independent":
        return top_i
    # one held expert given exactly `n` tokens' first choice, the other
    # held experts nothing
    n = {"exactly_one_tile": 128, "one_row": 1, "a_row_past_a_tile": 129}[
        case]
    top_i = np.stack([rng.choice(absent, K, replace=False)
                      for _ in range(T)])
    top_i[rng.choice(T, n, replace=False), 0] = held[1]
    return top_i


@pytest.mark.parametrize("case", [
    "independent", "nothing_held", "everything_held", "exactly_one_tile",
    "one_row", "a_row_past_a_tile"])
def test_layout_gives_every_held_assignment_one_row_in_whole_tiles(
        layer32, monkeypatch, case):
    """What held_expert_part hands the grouped product, and what comes
    back: spans of whole row tiles laid end to end in the worst case's
    static buffer, a span's live rows at its END holding exactly the
    tokens assigned to that expert (in assignment order), the returned
    counts the unpadded ones, and the result the dense per-expert loop's —
    so the combine read each of those rows and no other. Tokens have 0 to
    k held choices at unequal gates ("independent"), none, or all."""
    from novel_view_synthesis_3d_tpu.ops import grouped_matmul as gm

    cfg, p, _, m = layer32
    first, count, K, T = 8, 5, 4, 160
    k = dataclasses.replace(cfg.model.tokens, held_experts=(first, count))
    rng = np.random.default_rng(len(case))
    top_i = _choices(case, T, K, first, count, 32, rng)
    top_p = rng.random((T, K)).astype(np.float32) + 0.05
    top_p /= top_p.sum(-1, keepdims=True)
    b = jax.random.normal(jax.random.PRNGKey(3), (T, 64))
    held = jax.tree.map(lambda a: a[first:first + count], p["experts"])
    calls = _spy_on_the_products(monkeypatch)
    part, counts = token_denoiser.held_expert_part(
        b, jnp.asarray(top_p), jnp.asarray(top_i, jnp.int32), held, k)

    is_held = (top_i >= first) & (top_i < first + count)
    want_counts = np.bincount(top_i[is_held] - first, minlength=count)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    if case == "independent":
        assert set(is_held.sum(-1)) >= {0, 1, 2}      # ragged in the token
    assert len(calls) == 3                            # gate, up, down
    x, spans = calls[0]
    for _, again in calls[1:]:
        np.testing.assert_array_equal(again, spans)
    np.testing.assert_array_equal(spans, gm.span_sizes(want_counts))
    assert x.shape[0] == gm.buffer_rows(T * K, count) >= spans.sum()
    assert gm.rows_visited(want_counts) == spans.sum()
    flat_expert = top_i.reshape(-1)
    token_of = np.arange(T * K) // K
    end = 0
    for g in range(count):
        end += spans[g]
        tokens = token_of[flat_expert == first + g]   # assignment order
        np.testing.assert_array_equal(
            x[end - want_counts[g]:end], np.asarray(b)[tokens])
    plain, ref_counts = ref.experts_part(
        held, dict(m, held_experts=[first, count]), b, jnp.asarray(top_p),
        jnp.asarray(top_i), "f32")
    np.testing.assert_array_equal(np.asarray(ref_counts), want_counts)
    if is_held.any():
        assert rel(part, plain) < TOL
    else:
        assert float(jnp.max(jnp.abs(part))) == 0.0


@pytest.mark.parametrize("which", ["group", "row"])
def test_the_benchmarks_planted_fault_hits_live_rows(layer32, which):
    """benchmarks/token_check.rows_lost finds a group's rows by the running
    sum of the sizes the seam is handed and zeroes the span or its LAST
    row: with the live rows at a span's end, the tokens of the expert with
    the longest span lose their part (all of them, or exactly one token)."""
    from novel_view_synthesis_3d_tpu.ops.grouped_matmul import span_sizes

    cfg, p, _, _ = layer32
    first, count, K, T = 0, 8, 4, 96
    k = dataclasses.replace(cfg.model.tokens, held_experts=(first, count))
    rng = np.random.default_rng(4)
    top_i = np.stack([rng.choice(32, K, replace=False) for _ in range(T)])
    top_p = np.full((T, K), 0.25, np.float32)
    b = jax.random.normal(jax.random.PRNGKey(4), (T, 64))
    held = jax.tree.map(lambda a: a[:count], p["experts"])
    args = (b, jnp.asarray(top_p), jnp.asarray(top_i, jnp.int32), held, k)
    sound, counts = token_denoiser.held_expert_part(*args)
    with token_check.rows_lost(which):
        faulty, _ = token_denoiser.held_expert_part(*args)
    changed = np.flatnonzero(np.any(np.asarray(sound != faulty), axis=-1))
    fullest = int(np.argmax(span_sizes(np.asarray(counts))))
    given = np.flatnonzero(np.any(top_i == first + fullest, axis=-1))
    if which == "group":
        np.testing.assert_array_equal(changed, given)
    else:
        assert len(changed) == 1 and changed[0] == given[-1]


@pytest.mark.parametrize("first, count", [(0, 32), (8, 16), (5, 3), (31, 1)])
def test_held_part_is_the_dense_loop_on_independent_router_columns(
        layer32, first, count):
    """The layer's own router (independent columns: tokens with 0–4 held
    choices, unequal renormalised gates) through the sort, the aligned
    spans, the three products and the combine, against the reference's
    loop over the held experts under a dense mask. (0, 32) holds every
    assignment: the static buffer's worst case."""
    cfg, p, h, m = layer32
    k = dataclasses.replace(cfg.model.tokens, held_experts=(first, count))
    b32 = token_denoiser.rms_norm(h, p["mlp_norm"]["scale"],
                                  k.rms_norm_eps).reshape(-1, 64)
    top_p, top_i = token_denoiser.route(b32, p["router"], k)
    held = jax.tree.map(lambda a: a[first:first + count], p["experts"])
    part, counts = jax.jit(
        lambda *a: token_denoiser.held_expert_part(*a, k))(
            b32, top_p, top_i, held)
    plain, ref_counts = ref.experts_part(
        held, dict(m, held_experts=[first, count]), b32, top_p, top_i, "f32")
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref_counts))
    assert int(counts.sum()) == int(jnp.sum(
        (top_i >= first) & (top_i < first + count)))
    assert rel(part, plain) < TOL
    if count == 32:
        assert int(counts.sum()) == top_i.size


# ---------------------------------------------------------------------------
# Scopes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("path, want", [
    ("jit(sample)/lk.update/while/body/closed_call/og.layer_1/lk.moe_experts"
     "/gmm", ("layer_1", "moe_experts")),
    ("jit(sample)/lk.update/precompute/og.layer_0/lk.mla_core/dot_general",
     ("layer_0", "mla_core")),
    ("jit(sample)/lk.update/precompute/og.prelude/lk.pose/lk.patch/sin",
     ("prelude", "pose")),
    ("jit(sample)/lk.update/while/body/closed_call/og.final/lk.patch/dot",
     ("final", "patch")),
    ("jit(sample)/lk.update/while/body/closed_call/og.layer_3/add",
     ("layer_3", "other")),
    ("jit(sample)/lk.update/while/body/mul", ("", "update")),
])
def test_layer_of_reads_the_trunks_paths(path, want):
    assert layer_of(path) == want


def test_compiled_sampler_stamps_are_the_token_vocabulary(small):
    import re

    cfg, model, params, _, _, _ = small
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, 4),
                           cfg.diffusion, trajectory_every=1)
    cond = {k: jnp.asarray(v) for k, v in synth_data.cond_views(
        2, SIDE, 1).items()}
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        text = sampler.lower(params, jax.ShapeDtypeStruct((2,), jnp.uint32),
                             cond).compile().as_text()
    finally:
        jax.config.update(flag, before)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    stamps = {s for p in paths for s in re.split(r"[/();]", p)
              if s.startswith("lk.")}
    assert stamps == {"lk." + k for k in TOKEN_LAYER_KINDS}
    labels = {label for label, _ in token_denoiser.op_groups(cfg.model)}
    blocks = {layer_of(p)[0] for p in paths} - {""}
    assert blocks == labels
    assert any("/precompute/" in p for p in paths)
    # The combine's kernel is its kind's `gather`, not a `kernel`
    # (models/vocab.py names the exception): every expert layer of the
    # step and of the once-a-call pass has it, under that stamp alone.
    combine = [p for part in paths for p in part.split(";")
               if "/moe_combine" in p]
    assert len({layer_of(p)[0] for p in combine}) == \
        cfg.model.tokens.num_hidden_layers
    for p in combine:
        assert "/lk.moe_experts/" in p and "/pt.gather/moe_combine" in p, p
        assert "pt.kernel" not in p, p
        assert layer_part_of(p)[1] == "moe_experts.gather", p
    top = set(params)
    assert {n for _, names in token_denoiser.op_groups(cfg.model)
            for n in names} == top


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------
def test_preset_is_the_published_config_cut_as_the_file_says():
    import json

    cfg = get_preset("ms4_denoiser128").validate()
    k = cfg.model.tokens
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ms4_denoiser128.json")) as fh:
        doc = json.load(fh)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    for name, value in doc.items():
        if hasattr(k, name) and name not in ("rope_parameters",
                                             "n_routed_experts"):
            assert getattr(k, name) == value, name
    rope = dataclasses.asdict(k.rope_parameters)
    assert {n: doc["rope_parameters"][n] for n in rope} == rope
    # the router keeps the published width; the file counts what is held
    assert k.n_routed_experts == doc["published"]["n_routed_experts"] == 128
    assert k.held_experts == (0, doc["n_routed_experts"]) == (0, 32)
    assert k.num_hidden_layers == 6 and k.num_experts_per_tok == 4
    assert cfg.model.dtype == cfg.model.param_dtype == "bfloat16"
    assert k.qk_head_dim == doc["qk_head_dim"] == doc["head_dim"]
    if os.path.exists(catalog):
        with open(catalog) as fh:
            rows = [json.loads(line) for line in fh]
        row = next(r for r in rows if r["name"] == "Mistral-Small-4-119B-2603")
        assert doc["source"] == row["source_url"]
        for name, value in row["config"].items():
            if name not in doc["reduced"]:
                assert doc[name] == value, name
    # 2 bytes a parameter: the arithmetic of the cut
    shapes = token_denoiser.param_shapes(cfg.model)
    layer = sum(math.prod(s.shape) for s in jax.tree.leaves(
        shapes["layer_0"]))
    assert 855e6 < layer < 865e6            # 859 M: 1.72 GB in bfloat16


def test_config_round_trip_and_refusals():
    from novel_view_synthesis_3d_tpu.config import Config

    cfg = small_cfg()
    assert Config.from_json(cfg.to_json()) == cfg
    assert get_preset("paper256").model.tokens is None
    for over, word in [
        ({"model.tokens.held_experts": [6, 4]}, "held_experts"),
        ({"data.img_sidelength": 18}, "patch_size"),
        ({"model.num_cond_frames": 2}, "conditioning"),
        ({"model.family": "unet"}, "family"),
    ]:
        with pytest.raises(ValueError, match=word):
            small_cfg(**over)
    with pytest.raises(ValueError, match="tokens"):
        get_preset("tiny64").override(**{"model.family": "tokens"}).validate()


def test_replicated_router_gives_each_share_one_assignment_a_token():
    """The cell's seeded router (benchmarks/token_weights.py,
    `router_replicas`): 32 columns as 8 prototypes at 4 experts each. A
    token's top-4 are the replicas of its best prototype, one in each
    share of 8, so the held share is given exactly one assignment a
    token in every layer — and program and reference still agree."""
    cfg = small_cfg(**{"model.tokens.n_routed_experts": 32,
                       "model.tokens.num_experts_per_tok": 4,
                       "model.tokens.held_experts": [8, 8]})
    model = build_denoiser(cfg.model)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}))["params"]
    params = token_weights.make_weights(6, shapes, router_replicas=4)
    kernel = np.asarray(params["layer_0"]["router"]["kernel"])
    for j in range(1, 4):
        np.testing.assert_array_equal(kernel[:, :8], kernel[:, 8 * j:8 * j + 8])
    one = token_weights.make_group(6, shapes, "layer_1", router_replicas=4)
    np.testing.assert_array_equal(
        np.asarray(one["router"]["kernel"]),
        np.asarray(params["layer_1"]["router"]["kernel"]))
    batch, mask = doubled_batch()
    counts = np.asarray(model.routing_counts(params, batch, mask))
    tokens = 2 * (SIDE // cfg.model.tokens.patch_size) ** 2
    assert counts.sum(axis=1).tolist() == [tokens] * 2
    m = token_check.model_sizes(cfg)
    eps = model.apply({"params": params}, batch, cond_mask=mask, train=False)
    assert rel(eps, ref.forward(params, m, batch, mask)) < TOL
    with pytest.raises(ValueError, match="replicas"):
        token_weights.make_weights(6, shapes, router_replicas=5)

"""The token denoiser's third trunk (models/token_denoiser.py,
`KimiLinearLayer`: KDA layers with a recurrent-state cache, latent
attention without a positional term beside them, a leading dense layer, a
sigmoid router with a correction bias, one shared expert) and its two
sequence operators (ops/kda.py) against the benchmark's plain reference
(benchmarks/reference/kl48_ref.py) at a small size on the CPU, in float32
on both sides: 16 tokens a frame, 4 layers — KDA + dense, KDA + experts,
KDA + experts, latent attention + experts —, 4 heads of 16, 8 experts
top-3 on independent router columns (so tokens have 0 to 3 held choices
where a share is cut). Weights are the benchmark's seeded ones
(benchmarks/kda_weights.py).

Tolerances as tests/test_token_denoiser_gqa.py: both sides compute in
float32 and differ by the order of their sums (the program's scan is
chunked, the reference's token by token); TOL = 2e-5 is ~25× what they
read, and the reference with its matmul inputs rounded to bfloat16 reads
~3e-2.
"""

import dataclasses
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
import kda_weights  # noqa: E402
import synth_data  # noqa: E402
import token_check  # noqa: E402
import token_check_kda  # noqa: E402
from novel_view_synthesis_3d_tpu.config import (  # noqa: E402
    Config, KimiLinearTrunkConfig, get_preset)
from novel_view_synthesis_3d_tpu.diffusion.schedules import (  # noqa: E402
    sampling_schedule)
from novel_view_synthesis_3d_tpu.models import (  # noqa: E402
    build_denoiser, token_denoiser)
from novel_view_synthesis_3d_tpu.models.vocab import (  # noqa: E402
    KDA_TOKEN_LAYER_KINDS, layer_of)
from novel_view_synthesis_3d_tpu.ops import kda  # noqa: E402
from novel_view_synthesis_3d_tpu.ops.short_conv import short_conv  # noqa: E402
from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler  # noqa: E402

TOL = 2e-5
SIDE = 16
SMALL = {
    "model.tokens.hidden_size": 64, "model.tokens.num_hidden_layers": 4,
    "model.tokens.num_attention_heads": 4, "model.tokens.kv_lora_rank": 16,
    "model.tokens.qk_nope_head_dim": 16, "model.tokens.qk_rope_head_dim": 8,
    "model.tokens.v_head_dim": 16,
    "model.tokens.linear_attn_config.num_heads": 4,
    "model.tokens.linear_attn_config.head_dim": 16,
    "model.tokens.intermediate_size": 96, "model.tokens.num_experts": 8,
    "model.tokens.num_experts_per_token": 3,
    "model.tokens.moe_intermediate_size": 32,
    "model.tokens.held_experts": [0, 8], "data.img_sidelength": SIDE,
    "model.dtype": "float32", "model.param_dtype": "float32",
    "diffusion.sample_timesteps": 4,
}
# layer index → (mixer, feed-forward) at SMALL's depth
KINDS = {0: ("kda", "dense"), 1: ("kda", "experts"), 3: ("mla", "experts")}
ref = harness.load_module(os.path.join(
    ROOT, "benchmarks", "reference", "kl48_ref.py"), "kl48_ref")


def small_cfg(**over):
    return get_preset("kl48_denoiser256").override(
        **dict(SMALL, **over)).validate()


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


def seeded(cfg, seed=5):
    model = build_denoiser(cfg.model)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}))["params"]
    return model, kda_weights.make_weights(seed, shapes)


def doubled_batch(seed=3, rows=2):
    """One view twice (conditional row, unconditional row)."""
    cond = {k: jnp.asarray(np.repeat(v, rows, axis=0))
            for k, v in synth_data.cond_views(1, SIDE, seed).items()}
    key = jax.random.PRNGKey(seed)
    z = jnp.repeat(jax.random.normal(key, (1, SIDE, SIDE, 3)), rows, axis=0)
    return dict(cond, z=z, logsnr=jnp.full((rows,), 0.7)), \
        jnp.asarray([1.0, 0.0] * (rows // 2))


_WANT = {}


def reference(tag, params, m, batch, mask):
    """The reference's ε̂ of the module's seeded weights on `batch`, made
    once a tag: both attention paths are held to the same numbers."""
    if tag not in _WANT:
        _WANT[tag] = ref.forward(params, m, batch, mask)
    return _WANT[tag]


@pytest.fixture(scope="module", params=["xla", "kernel"])
def small(request):
    """The trunk through XLA's attention and through the Pallas kernel
    (interpreted): keys 24 wide against values 16 in both."""
    cfg = small_cfg(**{
        "model.use_flash_attention": request.param == "kernel"})
    model, params = seeded(cfg)
    batch, mask = doubled_batch()
    return cfg, model, params, batch, mask, token_check_kda.model_sizes(cfg)


# ---------------------------------------------------------------------------
# ops/kda.py: the chunked scan (the Pallas kernel `kda_fwd`, interpreted
# here) and the short convolution
# ---------------------------------------------------------------------------
def kda_inputs(L, rate, seed=0, B=2, H=3, dk=16, dv=24):
    """Near-parallel keys (a mostly white frame's are), decays planted at
    `rate` = A·softplus(·) a token; heads apart, as the reference takes
    them."""
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    k = n(B, L, H, dk)
    k = 0.3 * k + k[:, :1]
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = n(B, L, H, dk)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    g = -rate * jax.nn.softplus(n(B, L, H, dk) + 1.0)
    return (q, k, n(B, L, H, dv), g, jax.nn.sigmoid(n(B, L, H)),
            n(B, H, dk, dv))


def chunked(q, k, v, g, beta, S0=None, **kw):
    """`kda_chunked` on heads given apart: it takes them side by side in
    the last axis, (B, L, H·d), and returns o so."""
    o, S = kda.kda_chunked(*(x.reshape(x.shape[:2] + (-1,))
                             for x in (q, k, v, g)), beta, S0, **kw)
    return o.reshape(v.shape), S


def close(got, want, tol=1e-5):
    """Both sides float32, differing by the order of their sums: the
    largest difference against the largest value."""
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.max(jnp.abs(got - want))) < tol * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("L,rate,with_state", [
    (128, 0.05, True),     # two whole chunks, slow decay, entered mid-way
    (150, 1.0, True),      # no multiple of the chunk
    (150, 16.0, True),     # A = 16: 1/Γ would overflow within a sub-block
    (150, 16.0, False),    # the sequence's start
    (7, 0.3, True),        # shorter than a sub-block
    (300, 1.0, True),      # more than one run of chunks: two grid steps
    (69, 1.0, True),       # ends inside the second chunk's first sub-block
])
def test_kda_chunked_is_the_token_by_token_recurrence(L, rate, with_state):
    q, k, v, g, beta, S0 = kda_inputs(L, rate)
    S0 = S0 if with_state else None
    if rate == 16.0:   # the planted decays do overflow the naive factor
        with np.errstate(over="ignore"):
            assert not np.isfinite(np.exp(-np.cumsum(
                np.asarray(g[:, :kda.CHUNK], np.float32), axis=1))).all()
    o, S = chunked(q, k, v, g, beta, S0)
    want_o, want_S = ref.delta_rule(q, k, v, g, beta, S0)
    assert o.shape == v.shape and S.shape == (2, 3, 16, 24)
    close(o, want_o)
    close(S, want_S)


def test_kda_chunked_fast_decay_neither_overflows_nor_nans():
    """g = −16 a token on every channel (and −16·softplus beside it): a
    chunk's running sum passes −1000, every factor is taken from a
    difference ≤ 0, an underflow to 0 is the value."""
    q, k, v, _, beta, S0 = kda_inputs(150, 1.0)
    for g in (jnp.full(q.shape, -16.0),
              -16.0 * jax.nn.softplus(kda_inputs(150, 1.0, seed=1)[0] + 3)):
        o, S = chunked(q, k, v, g, beta, S0)
        want_o, want_S = ref.delta_rule(q, k, v, g, beta, S0)
        close(o, want_o)
        close(S, want_S)


@pytest.mark.parametrize("H", [3, 4], ids=["a-head-a-step", "two-heads-a-step"])
def test_kda_chunked_rows_and_heads_keep_their_own_state(H):
    """B = 4 with another `S0` a row (and head): the state is a scratch
    the grid re-enters at every (row, heads) — nothing of one reaches
    another, whatever the order: a row computed alone is the row computed
    among four (to the last bits: XLA on the CPU compiles the one-row
    program apart)."""
    q, k, v, g, beta, S0 = kda_inputs(140, 0.5, B=4, H=H)
    S0 = S0 * jnp.arange(1.0, 5.0)[:, None, None, None]
    o, S = chunked(q, k, v, g, beta, S0)
    want_o, want_S = ref.delta_rule(q, k, v, g, beta, S0)
    assert S.shape == (4, H, 16, 24)
    close(o, want_o)
    close(S, want_S)
    for row in (0, 3):
        alone = chunked(*(x[row:row + 1] for x in (q, k, v, g, beta, S0)))
        close(alone[0][0], o[row], 1e-6)
        close(alone[1][0], S[row], 1e-6)


def test_kda_chunked_takes_bfloat16_as_the_same_values_widened():
    """q k v as the model hands them, in bfloat16: the kernel widens them
    in VMEM, so the result is that of the same values given in float32 —
    to the bit, tighter than the 1e-5 both forms are held to."""
    q, k, v, g, beta, S0 = kda_inputs(150, 1.0)
    low = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    o, S = chunked(*low, g, beta, S0)
    assert o.dtype == jnp.float32 and S.dtype == jnp.float32
    wide = [x.astype(jnp.float32) for x in low]
    same_o, same_S = chunked(*wide, g, beta, S0)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(same_o))
    np.testing.assert_array_equal(np.asarray(S), np.asarray(same_S))
    want_o, want_S = ref.delta_rule(*wide, g, beta, S0)
    close(o, want_o)
    close(S, want_S)


@pytest.mark.parametrize("chunk", [16, 32, 48, 128])
def test_kda_chunked_any_chunk_of_whole_sub_blocks(chunk):
    """One, two, three (no power of two) and eight sub-blocks a chunk:
    the merge of the sub-blocks' inverses pairs them inside a chunk."""
    q, k, v, g, beta, S0 = kda_inputs(200, 1.0, H=2)
    o, S = chunked(q, k, v, g, beta, S0, chunk=chunk)
    want_o, want_S = ref.delta_rule(q, k, v, g, beta, S0)
    close(o, want_o)
    close(S, want_S)


def test_kda_chunked_frame_by_frame_is_one_pass():
    """The state a frame leaves is what the next is entered with."""
    q, k, v, g, beta, S0 = kda_inputs(96, 0.2)
    o, S = chunked(q, k, v, g, beta, S0)
    first = [x[:, :40] for x in (q, k, v, g, beta)]
    rest = [x[:, 40:] for x in (q, k, v, g, beta)]
    o1, S1 = chunked(*first, S0)
    o2, S2 = chunked(*rest, S1)
    assert rel(jnp.concatenate([o1, o2], axis=1), o) < 1e-6
    assert rel(S2, S) < 1e-6


def test_kda_chunked_has_no_backward_and_says_so():
    q, k, v, g, beta, S0 = kda_inputs(32, 0.2)
    with pytest.raises(NotImplementedError, match="kda_chunked has no "
                                                  "backward"):
        jax.grad(lambda v: chunked(q, k, v, g, beta, S0)[0].sum())(v)
    with pytest.raises(ValueError, match="chunk"):
        chunked(q, k, v, g, beta, S0, chunk=24)


def test_kda_chunked_on_the_chip_takes_whole_lane_blocks_only(monkeypatch):
    """Compiled, a head must be whole 128-lane blocks of (B, L, H·d); the
    refusal names the widths (the interpreter, above, takes any)."""
    monkeypatch.setattr(kda._pallas, "use_interpret", lambda: False)
    q, k, v, g, beta, S0 = kda_inputs(32, 0.2)
    with pytest.raises(ValueError, match="d_k=16, d_v=24"):
        chunked(q, k, v, g, beta, S0)


def test_short_conv_tail_frame_by_frame_is_one_pass():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 10, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    y, tail = short_conv(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        jax.nn.silu(ref.causal_conv(x, w))), atol=1e-6)
    y1, t1 = short_conv(x[:, :6], w)
    y2, t2 = short_conv(x[:, 6:], w, t1)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(tail))
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(x[:, -3:]))
    # a frame shorter than the taps still hands on three rows
    _, t = short_conv(x[:, :2], w, t1)
    assert t.shape == (2, 3, 8)


# ---------------------------------------------------------------------------
# The trunk against the reference
# ---------------------------------------------------------------------------
def test_full_forward_matches_the_reference(small):
    cfg, model, params, batch, mask, m = small
    eps = model.apply({"params": params}, batch, cond_mask=mask, train=False)
    want = reference("batch", params, m, batch, mask)
    assert eps.shape == (2, SIDE, SIDE, 3) and eps.dtype == jnp.float32
    assert rel(eps, want) < TOL
    assert rel(eps[0], eps[1]) > 1e-2   # the ray term is masked in one row


def test_precompute_then_step_matches_the_full_forward(small):
    """Prefill of the conditioning frame into TWO kinds of cache — a KDA
    layer's state and tail, a latent layer's latent — then the target's
    tokens alone from them, is the reference's ONE token-by-token forward
    over both frames."""
    cfg, model, params, batch, mask, m = small
    cond = {k: v[:1] for k, v in batch.items() if k not in ("z", "logsnr")}
    pre = model.precompute(params, cond)
    k = cfg.model.tokens
    lin = k.linear_attn_config
    # the caches, and the latent layers' key and value kernels made once
    assert set(pre) == {"layer_cache", "derived"}
    assert {n for n, d in pre["derived"].items() if d} == {
        f"layer_{i}" for i in range(k.num_hidden_layers)
        if k.is_full_attention(i)}
    assert len(pre["layer_cache"]) == k.num_hidden_layers
    L = (SIDE // k.patch_size) ** 2
    for i, entry in enumerate(pre["layer_cache"]):
        if k.is_full_attention(i):
            assert model.layer.cache_kind(i) == "latent"
            assert entry[0].shape == (2, L, k.kv_lora_rank)
            assert entry[1].shape == (2, L, k.qk_rope_head_dim)
        else:
            assert model.layer.cache_kind(i) == "recurrent_state"
            assert entry[0].shape == (2, lin.num_heads, lin.head_dim,
                                      lin.head_dim)
            assert entry[0].dtype == jnp.float32
            assert entry[1].shape == (2, lin.short_conv_kernel_size - 1,
                                      3 * lin.num_heads * lin.head_dim)
    eps = model.apply({"params": params}, dict(batch, **pre), cond_mask=mask,
                      train=False)
    assert rel(eps, reference("batch", params, m, batch, mask)) < TOL
    batch2 = dict(batch, z=batch["z"] * 0.5 + 0.1,
                  logsnr=jnp.full((2,), -2.0))
    eps2 = model.apply({"params": params}, dict(batch2, **pre),
                       cond_mask=mask, train=False)
    assert rel(eps2, reference("batch2", params, m, batch2, mask)) < TOL
    by_kind = model.cond_cache_bytes(SIDE)
    assert by_kind == {
        "recurrent_state": 3 * 4 * (4 * 16 * 16 + 3 * 3 * 4 * 16),
        "latent": 4 * L * (16 + 8)}


@pytest.mark.parametrize("i", sorted(KINDS), ids=[
    "+".join(KINDS[i]) for i in sorted(KINDS)])
def test_each_kind_of_layer_matches_the_reference(small, i):
    """Layer i of the program — the conditioning frame from nothing, then
    the target frame from what that left — is the reference's layer i over
    the sequence of both; and a reference of another kind is not."""
    cfg, model, params, _, _, m = small
    k = cfg.model.tokens
    assert ("mla" if k.is_full_attention(i) else "kda",
            "dense" if k.is_dense(i) else "experts") == KINDS[i]
    rng = np.random.default_rng(i)
    h = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    p = params[f"layer_{i}"]
    mine = token_denoiser.laid_over(p, model.layer.derive(i, p))
    first, cache, _ = model.layer(i, mine, h[:, :16], None, None)
    second, _, (counts, chosen) = model.layer(i, mine, h[:, 16:], None,
                                              cache)
    got = jnp.concatenate([first, second], axis=1)
    want, aux = ref.layer(p, m, h, i, parts=True)
    assert rel(got - h, want - h) < TOL
    assert (counts is None) == k.is_dense(i)
    if not k.is_dense(i):
        np.testing.assert_array_equal(np.asarray(chosen),
                                      np.asarray(aux["chosen"])[:, 16:])
    if not k.is_full_attention(i):
        # the cached state is needed: without it the target frame differs
        lost, _, _ = model.layer(i, mine, h[:, 16:], None, (
            jnp.zeros_like(cache[0]), cache[1]))
        assert rel(lost - h[:, 16:], second - h[:, 16:]) > 1e-2
        zeroed, _ = ref.layer(p, m, h, i, zero_state_at=16)
        assert rel(jnp.concatenate([first, lost], axis=1), zeroed) < TOL


@pytest.mark.parametrize("attention", ["xla", "kernel"])
def test_latent_layer_at_the_cells_head_widths_matches_the_reference(
        attention):
    """Heads of 128 + 64 on 128, the cell's: the latent layer derives its
    queries' nope and shared-part columns apart and the keys' without an
    identity block (`shares_key_part`), and hands the attention kernel two
    products a score — the reference's layer on whole 192-wide heads,
    frame by frame from the cache, through XLA and through the kernel."""
    cfg = small_cfg(**{
        "model.tokens.num_attention_heads": 2,
        "model.tokens.qk_nope_head_dim": 128,
        "model.tokens.qk_rope_head_dim": 64, "model.tokens.v_head_dim": 128,
        "model.use_flash_attention": attention == "kernel"})
    k = cfg.model.tokens
    assert token_denoiser.shares_key_part(k)
    assert token_denoiser.shares_key_part(
        get_preset("kl48_denoiser256").model.tokens)
    assert not token_denoiser.shares_key_part(small_cfg().model.tokens)
    model, params = seeded(cfg)
    m = token_check_kda.model_sizes(cfg)
    i = 3
    p = params[f"layer_{i}"]
    derived = model.layer.derive(i, p)
    assert set(derived["mla"]) == {"q_nope", "q_rope", "k_nope", "v_b"}
    mine = token_denoiser.laid_over(p, derived)
    h = jnp.asarray(np.random.default_rng(i).normal(size=(2, 32, 64)),
                    jnp.float32)
    first, cache, _ = model.layer(i, mine, h[:, :16], None, None)
    second, _, _ = model.layer(i, mine, h[:, 16:], None, cache)
    assert cache[1].shape == (2, 16, 64)
    want, _ = ref.layer(p, m, h, i, parts=True)
    assert rel(jnp.concatenate([first, second], axis=1) - h, want - h) < TOL
    # and the whole trunk, the once-a-call pass then a step
    batch, mask = doubled_batch()
    cond = {n: v[:1] for n, v in batch.items() if n not in ("z", "logsnr")}
    eps = model.apply({"params": params},
                      dict(batch, **model.precompute(params, cond)),
                      cond_mask=mask, train=False)
    assert rel(eps, ref.forward(params, m, batch, mask)) < TOL


def test_the_lost_state_and_the_precision_both_show():
    """What the comparison must be able to see: a reference whose KDA
    state is lost between the frames, or in a lower precision than
    stated, is not the program (the reference alone: no attention path of
    the program's is in it)."""
    cfg = small_cfg()
    _, params = seeded(cfg)
    batch, mask = doubled_batch()
    m = token_check_kda.model_sizes(cfg)
    want = reference("batch", params, m, batch, mask)
    L = (SIDE // m["patch_size"]) ** 2
    assert rel(ref.forward(params, m, batch, mask, zero_state_at=L),
               want) > 1e-2
    assert rel(ref.forward(params, m, batch, mask, "bf16"), want) > 100 * TOL
    assert rel(ref.forward(params, m, batch, mask, "fp8"), want) > 1000 * TOL
    seen = ref.visible(8)
    assert seen[:4, :4].all() and not seen[:4, 4:].any() and seen[4:].all()


def test_guided_eps_through_make_sampler(small):
    """Every step of `make_sampler(trajectory_every=1)` — no edit to
    sample/ddpm.py: its precompute seam hands the two kinds of cache
    through as one pytree — against the reference's guided ε̂."""
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
# The expert layer: `route` with a sigmoid and a bias, the shares
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def layer8():
    cfg = small_cfg()
    model, params = seeded(cfg, seed=8)
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.normal(size=(48, 64)), jnp.float32)
    return cfg, params["layer_1"], b, token_check_kda.model_sizes(cfg)


def with_held(k, held):
    return dataclasses.replace(k, held_experts=held)


def test_route_scores_with_a_sigmoid_and_the_bias_only_chooses(layer8):
    cfg, p, b, m = layer8
    k = cfg.model.tokens
    assert k.router_activation == "sigmoid"
    scores = jax.nn.sigmoid(b @ p["router"]["kernel"])
    # a bias that decides the choice: experts 5, 6, 7 whatever the scores
    forced = dict(p["router"], bias=jnp.asarray([0.0] * 5 + [10.0] * 3))
    top_p, top_i = token_denoiser.route(b, forced, k)
    assert set(np.asarray(top_i).ravel().tolist()) == {5, 6, 7}
    # ... and is not in the gate: the chosen scores over their sum × 2.446
    chosen = jnp.take_along_axis(scores, top_i, axis=1)
    np.testing.assert_allclose(
        np.asarray(top_p), np.asarray(
            chosen / chosen.sum(axis=1, keepdims=True)
            * k.routed_scaling_factor), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(top_p.sum(axis=1)),
                               k.routed_scaling_factor, rtol=1e-6)
    # the seeded bias: the reference's choice and gates
    top_p, top_i = token_denoiser.route(b, p["router"], k)
    gates, ids, gap, _, _ = ref.router(p["router"], m, b)
    clear = np.asarray(gap) > 1e-5
    np.testing.assert_array_equal(np.asarray(top_i)[clear],
                                  np.asarray(ids)[clear])
    np.testing.assert_allclose(np.asarray(top_p)[clear],
                               np.asarray(gates)[clear], atol=1e-6)
    # the other trunks score with a softmax and have no bias to read
    other = get_preset("ms4_denoiser128").model.tokens
    assert other.router_activation == "softmax"


@pytest.mark.parametrize("held", [(5, 3), (0, 5)],
                         ids=["every-choice-held", "no-choice-held"])
def test_tokens_with_all_and_with_none_of_their_choices_held(layer8, held):
    """Every token sent to experts 5, 6, 7: a share that holds them
    computes every choice, a share that holds 0-4 adds exactly nothing."""
    cfg, p, b, m = layer8
    k = with_held(cfg.model.tokens, held)
    forced = dict(p["router"], bias=jnp.asarray([0.0] * 5 + [10.0] * 3))
    top_p, top_i = token_denoiser.route(b, forced, k)
    stack = jax.tree.map(lambda a: a[held[0]:held[0] + held[1]],
                         p["experts"])
    part, counts = token_denoiser.held_expert_part(b, top_p, top_i, stack, k)
    want, _ = ref.experts_part(p["experts"], m, b, top_p, top_i, "f32", held)
    if held == (5, 3):
        assert counts.tolist() == [b.shape[0]] * 3
        assert rel(part, want) < TOL
    else:
        assert counts.tolist() == [0] * 5
        assert float(jnp.max(jnp.abs(part))) == 0.0 == float(
            jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("shares", [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_layer(layer8, shares):
    """`held_experts` (0, k), (k, k), …: the routed parts the shares of an
    expert-parallel layer compute, plus the shared expert ONCE, add up to
    the uncut reference's feed-forward layer."""
    cfg, p, b, m = layer8
    k = cfg.model.tokens
    top_p, top_i = token_denoiser.route(b, p["router"], k)
    per = k.n_routed_experts // shares
    total, counted, held_by_token = 0.0, 0, []
    for s in range(shares):
        held = (s * per, per)
        stack = jax.tree.map(lambda a: a[s * per:(s + 1) * per],
                             p["experts"])
        part, counts = token_denoiser.held_expert_part(
            b, top_p, top_i, stack, with_held(k, held))
        want, ref_counts = ref.experts_part(p["experts"], m, b, top_p, top_i,
                                            "f32", held)
        assert rel(part, want) < TOL
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(ref_counts))
        total, counted = total + part, counted + int(counts.sum())
        held_by_token.append(np.asarray(
            ((top_i >= held[0]) & (top_i < held[0] + per)).sum(axis=1)))
    assert counted == b.shape[0] * k.num_experts_per_tok  # none dropped
    if shares > 1:   # independent columns: from none to all a share can
        assert {0, min(3, per)} <= set(
            np.concatenate(held_by_token).tolist())
    total = total + token_denoiser.gated_mlp(b, p["shared"])
    # the uncut reference: h + FFN(h) of a layer whose input norm is 1
    gates, ids, _, _, _ = ref.router(p["router"], m, b)
    whole = ref.experts_part(p["experts"], m, b, gates, ids, "f32")[0] \
        + ref.gated_mlp(p["shared"], b, "f32")
    assert rel(total, whole) < TOL


# ---------------------------------------------------------------------------
# Scopes, the preset, the config, the refusals
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("path,want", [
    ("jit(sample)/lk.update/og.layer_1/lk.kda_proj/dot_general",
     ("layer_1", "kda_proj")),
    ("jit(sample)/lk.update/og.layer_0/lk.kda_conv/mul",
     ("layer_0", "kda_conv")),
    ("jit(sample)/lk.update/og.layer_2/lk.kda_core/while/body/dot_general",
     ("layer_2", "kda_core")),
    ("jit(sample)/precompute/og.layer_0/lk.dense_mlp/dot_general",
     ("layer_0", "dense_mlp")),
    ("jit(sample)/lk.update/og.layer_3/lk.mla_core/flash_fwd",
     ("layer_3", "mla_core")),
    # o's gated head-wise norm: the kernel's time is `kda_proj`'s
    ("jit(sample)/lk.update/og.layer_1/lk.kda_proj/jit(_norm_call)/"
     "pt.kernel/head_norm_fwd/pallas_call", ("layer_1", "kda_proj")),
])
def test_layer_of_reads_the_trunks_paths(path, want):
    assert layer_of(path) == want


def test_compiled_sampler_stamps_are_the_trunks_vocabulary():
    """Every stamp of the compiled sampler is one of this trunk's kinds,
    none doubled; KDA's three kinds in the KDA layers only, the latent
    kinds in the latent layer only, `dense_mlp` in the leading layer."""
    import re

    cfg = small_cfg()
    model, params = seeded(cfg)
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, 4),
                           cfg.diffusion, trajectory_every=1)
    cond = {k: jnp.asarray(v) for k, v in synth_data.cond_views(
        2, SIDE, 9).items()}
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
        KDA_TOKEN_LAYER_KINDS)
    for kind in ("kda_proj", "kda_conv", "kda_core"):
        assert seen[kind] == {"layer_0", "layer_1", "layer_2"}
    assert seen["mla_core"] == {"layer_3"} == seen["mla_proj"]
    assert seen["dense_mlp"] == {"layer_0"}
    assert seen["moe_experts"] <= {"layer_1", "layer_2", "layer_3"}
    labels = {label for label, _ in token_denoiser.op_groups(cfg.model)}
    assert {b for v in seen.values() for b in v} - {""} <= labels


def test_preset_is_the_published_config_cut_as_the_file_says():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kl48_denoiser256.json")) as fh:
        conf = json.load(fh)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = [c for c in json.load(fh)["configs"]
                 if c["name"] == "kl48_denoiser256"][0]
    assert entry["source"] == conf["source"]
    assert entry["reduced"] == conf["reduced"] == [
        "num_hidden_layers", "num_experts", "sample_timesteps"]
    cfg = get_preset(conf["preset"]).validate()
    k = cfg.model.tokens
    assert isinstance(k, KimiLinearTrunkConfig)
    m = token_check_kda.model_sizes(cfg)
    for name, value in conf.items():
        if name in m and name not in ("name", "num_experts"):
            assert m[name] == value, name
    # the router keeps its published width; 128 of its experts are held
    assert conf["num_experts"] == 128 == k.held_experts[1]
    assert conf["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                 "sample_timesteps": 256}
    published = KimiLinearTrunkConfig()
    assert (published.num_hidden_layers, published.num_experts) == (27, 256)
    assert k.num_experts == 256 and k.num_experts_per_tok == 8
    # the leading dense layer once, then one whole period of four
    assert k.num_hidden_layers == 5
    assert [k.is_full_attention(i) for i in range(5)] == [
        False, False, False, True, False]
    assert [k.is_dense(i) for i in range(5)] == [True] + [False] * 4
    assert cfg.data.img_sidelength == 256
    assert conf["assumed"]["router_replicas"] == 2
    shapes = token_denoiser.param_shapes(cfg.model)
    size = {g: sum(int(np.prod(s.shape)) for s in jax.tree.leaves(t))
            for g, t in shapes.items()}
    assert 103.0e6 < size["layer_0"] < 103.5e6      # KDA 39.5 + dense 63.7
    assert 953.0e6 < size["layer_1"] < 953.5e6      # KDA + 913.6 of experts
    assert 942.5e6 < size["layer_3"] < 943.0e6      # MLA 29.1 + experts
    assert 3.92e9 < sum(size.values()) < 3.925e9    # 7.84 GB in bfloat16
    model = build_denoiser(cfg.model)
    assert model.cond_cache_bytes(256) == {
        "recurrent_state": 4 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2),
        "latent": 4096 * (512 + 64) * 2}
    assert model.window_key_columns(256) == (0, 0)


def test_config_round_trip_and_refusals():
    cfg = small_cfg()
    again = Config.from_json(cfg.to_json())
    assert again == cfg
    assert isinstance(again.model.tokens, KimiLinearTrunkConfig)
    assert again.model.tokens.linear_attn_config.num_heads == 4
    for name in ("ms4_denoiser128", "st21_denoiser256"):
        other = get_preset(name)
        assert type(Config.from_json(other.to_json()).model.tokens) is type(
            other.model.tokens)
    for over, word in [
        ({"model.tokens.held_experts": [6, 4]}, "held_experts"),
        ({"model.tokens.linear_attn_config.full_attn_layers": [2, 3],
          "model.tokens.linear_attn_config.kda_layers": [1, 2]},
         "kda_layers"),
        ({"model.tokens.topk_group": 2}, "grouped top-k"),
        ({"model.tokens.mla_use_nope": False}, "mla_use_nope"),
        ({"data.img_sidelength": 18}, "patch_size"),
    ]:
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

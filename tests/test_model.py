"""X-UNet shape/behavior tests (SURVEY.md §4: per-block + end-to-end)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.config import ModelConfig
from novel_view_synthesis_3d_tpu.models.layers import (
    AttnBlock,
    FiLM,
    FrameConv,
    GroupNorm,
    ResnetBlock,
)
from novel_view_synthesis_3d_tpu.models.xunet import XUNet


def make_batch(rng, B=2, S=16, n_cond=1):
    ks = jax.random.split(rng, 9)
    b = {
        "x": jax.random.uniform(ks[0], (B, S, S, 3), minval=-1, maxval=1),
        "z": jax.random.normal(ks[1], (B, S, S, 3)),
        "logsnr": jax.random.uniform(ks[2], (B,), minval=-20, maxval=20),
        "R1": jnp.broadcast_to(jnp.eye(3), (B, 3, 3)),
        "t1": jax.random.normal(ks[3], (B, 3)),
        "R2": jnp.broadcast_to(jnp.eye(3), (B, 3, 3)),
        "t2": jax.random.normal(ks[4], (B, 3)),
        "K": jnp.broadcast_to(
            jnp.array([[S / 2.0, 0, S / 2.0], [0, S / 2.0, S / 2.0], [0, 0, 1]]),
            (B, 3, 3)),
    }
    if n_cond > 1:
        b["x"] = jnp.broadcast_to(b["x"][:, None], (B, n_cond, S, S, 3))
        b["R1"] = jnp.broadcast_to(b["R1"][:, None], (B, n_cond, 3, 3))
        b["t1"] = jnp.broadcast_to(b["t1"][:, None], (B, n_cond, 3))
    return b


TINY = ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                   attn_resolutions=(8,), dropout=0.0)


def init_and_apply(cfg, batch, cond_mask=None, train=False):
    model = XUNet(cfg)
    B = batch["z"].shape[0]
    if cond_mask is None:
        cond_mask = jnp.ones((B,))
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        batch, cond_mask=cond_mask, train=train)
    out = model.apply(variables, batch, cond_mask=cond_mask, train=train,
                      rngs={"dropout": jax.random.PRNGKey(2)})
    return variables, out


def test_forward_shape_and_finite():
    batch = make_batch(jax.random.PRNGKey(0), B=2, S=16)
    _, out = init_and_apply(TINY, batch)
    assert out.shape == (2, 16, 16, 3)
    assert np.all(np.isfinite(np.asarray(out)))


def test_zero_init_output_head():
    # With zero-init final conv, untrained output must be exactly 0.
    batch = make_batch(jax.random.PRNGKey(0), B=1, S=16)
    _, out = init_and_apply(TINY, batch)
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_jit_apply():
    batch = make_batch(jax.random.PRNGKey(0), B=2, S=16)
    model = XUNet(TINY)
    cond_mask = jnp.ones((2,))
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        batch, cond_mask=cond_mask, train=False)

    @jax.jit
    def fwd(v, b, m):
        return model.apply(v, b, cond_mask=m, train=False)

    out = fwd(variables, batch, cond_mask)
    assert out.shape == (2, 16, 16, 3)


@pytest.mark.slow
def test_cond_mask_changes_output_after_training_params():
    """CFG: zeroed pose embedding must give a different output than cond=1
    once params are non-degenerate (perturb them away from zero-init)."""
    batch = make_batch(jax.random.PRNGKey(0), B=2, S=16)
    model = XUNet(TINY)
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        batch, cond_mask=jnp.ones((2,)), train=False)
    variables = jax.tree.map(
        lambda p: p + 0.01 * jax.random.normal(jax.random.PRNGKey(7), p.shape),
        variables)
    out_c = model.apply(variables, batch, cond_mask=jnp.ones((2,)), train=False)
    out_u = model.apply(variables, batch, cond_mask=jnp.zeros((2,)), train=False)
    assert not np.allclose(np.asarray(out_c), np.asarray(out_u))


@pytest.mark.slow
def test_k2_conditioning_frames():
    batch = make_batch(jax.random.PRNGKey(0), B=2, S=16, n_cond=2)
    cfg = ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                      attn_resolutions=(8,), dropout=0.0, num_cond_frames=2)
    _, out = init_and_apply(cfg, batch)
    assert out.shape == (2, 16, 16, 3)


@pytest.mark.slow
def test_configurable_ch_mult_depth():
    # The reference cannot change ch_mult without editing source; we can.
    batch = make_batch(jax.random.PRNGKey(0), B=1, S=32)
    cfg = ModelConfig(ch=32, ch_mult=(1, 2, 2), emb_ch=32, num_res_blocks=1,
                      attn_resolutions=(8,), dropout=0.0)
    _, out = init_and_apply(cfg, batch)
    assert out.shape == (1, 32, 32, 3)


def test_dropout_train_uses_rng():
    batch = make_batch(jax.random.PRNGKey(0), B=1, S=16)
    cfg = ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                      attn_resolutions=(8,), dropout=0.5)
    model = XUNet(cfg)
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        batch, cond_mask=jnp.ones((1,)), train=True)
    variables = jax.tree.map(
        lambda p: p + 0.01 * jax.random.normal(jax.random.PRNGKey(7), p.shape),
        variables)
    o1 = model.apply(variables, batch, cond_mask=jnp.ones((1,)), train=True,
                     rngs={"dropout": jax.random.PRNGKey(2)})
    o2 = model.apply(variables, batch, cond_mask=jnp.ones((1,)), train=True,
                     rngs={"dropout": jax.random.PRNGKey(3)})
    # Different dropout keys → different outputs (the reference baked one key
    # at trace time, train.py:66 — a bug our framework fixes by construction).
    assert not np.allclose(np.asarray(o1), np.asarray(o2))


# ---------------------------------------------------------------------------
# Blocks: each takes and returns (B·F, H, W, C), rows batch-major, F beside it
# ---------------------------------------------------------------------------
def test_groupnorm_per_frame_vs_shared():
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 8, 8, 32))
    # Make frame 1 have a huge offset; per-frame GN must normalize each frame
    # to ~zero mean independently, shared GN must not.
    h = h.at[:, 1].add(100.0).reshape(4, 8, 8, 32)
    gn_pf = GroupNorm(per_frame=True)
    out_pf = gn_pf.apply(gn_pf.init(jax.random.PRNGKey(1), h), h)
    gn_sh = GroupNorm(per_frame=False, frames=2)
    out_sh = gn_sh.apply(gn_sh.init(jax.random.PRNGKey(1), h), h)
    assert out_pf.shape == out_sh.shape == h.shape
    m0 = float(jnp.abs(out_pf[1::2].mean()))
    m1 = float(jnp.abs(out_sh[1::2].mean()))
    assert m0 < 1e-4          # per-frame: frame 1 normalized on its own
    assert m1 > 0.5           # shared stats: offset leaks through


def test_groupnorm_output_is_in_the_module_dtype_on_a_float32_input():
    """The norm casts to the module's dtype and the activation runs in
    that dtype: a bfloat16 module on a float32 input gives bfloat16, the
    float32 module's values to bfloat16's precision."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 8, 64), jnp.float32)
    low = GroupNorm(act="swish", dtype=jnp.bfloat16)
    full = GroupNorm(act="swish")
    params = jax.tree.map(lambda a: a + 0.3,
                          low.init(jax.random.PRNGKey(3), x))
    y, y32 = low.apply(params, x), full.apply(params, x)
    assert (y.dtype, y32.dtype) == (jnp.bfloat16, jnp.float32)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_groupnorm_swish_is_swish_of_the_plain_norm(dtype):
    """`act='swish'` is the nonlinearity applied to the norm's output in
    the module's dtype, on the same parameter tree — to the bit."""
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8, 64), dtype)
    plain = GroupNorm(dtype=dtype)
    params = jax.tree.map(lambda a: a + 0.3,
                          plain.init(jax.random.PRNGKey(1), x))
    assert set(params["params"]) == {"GroupNorm_0"}
    assert set(params["params"]["GroupNorm_0"]) == {"scale", "bias"}
    y = plain.apply(params, x)
    ys = GroupNorm(act="swish", dtype=dtype).apply(params, x)
    assert y.dtype == ys.dtype == dtype
    np.testing.assert_array_equal(np.asarray(ys, np.float32),
                                  np.asarray(nn.swish(y), np.float32))


def test_resnet_block_resample_shapes():
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 32))
    emb = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 32))
    blk = ResnetBlock(features=64, resample=None)
    v = blk.init(jax.random.PRNGKey(2), h, emb, train=False)
    assert blk.apply(v, h, emb, train=False).shape == (2, 8, 8, 64)

    emb_dn = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 4, 32))
    blk = ResnetBlock(resample="down")
    v = blk.init(jax.random.PRNGKey(2), h, emb_dn, train=False)
    assert blk.apply(v, h, emb_dn, train=False).shape == (2, 4, 4, 32)

    emb_up = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16, 32))
    blk = ResnetBlock(resample="up")
    v = blk.init(jax.random.PRNGKey(2), h, emb_up, train=False)
    assert blk.apply(v, h, emb_up, train=False).shape == (2, 16, 16, 32)


def test_attn_block_cross_matches_reference_semantics_f2():
    """For F=2, generalized cross attention must reduce to frame0↔frame1
    with PRE-update frame-0 keys (reference model/xunet.py:118-121)."""
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 4, 4, 32))

    def rows(a):
        return a.reshape((4,) + a.shape[2:])

    blk = AttnBlock(attn_type="cross", frames=2, attn_heads=4)
    v = blk.init(jax.random.PRNGKey(1), rows(h))
    out = blk.apply(v, rows(h))
    assert out.shape == rows(h).shape
    # Permuting the two frames on input permutes them on output (symmetry of
    # the shared-weight cross exchange).
    out_swap = blk.apply(v, rows(h[:, ::-1]))
    np.testing.assert_allclose(
        np.asarray(out_swap),
        np.asarray(rows(out.reshape(h.shape)[:, ::-1])), atol=1e-5)


def test_film_zero_emb_is_identity():
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 8))
    emb = jnp.zeros((2, 4, 4, 8))
    film = FiLM(features=8)
    v = film.init(jax.random.PRNGKey(1), h, emb)
    # Dense(swish(0)) = bias-init = 0 → scale=shift=0 → identity.
    np.testing.assert_allclose(np.asarray(film.apply(v, h, emb)),
                               np.asarray(h), rtol=1e-6)


def test_frameconv_equivalent_to_per_frame_conv():
    h = jax.random.normal(jax.random.PRNGKey(0), (6, 8, 8, 4))
    conv = FrameConv(6)
    v = conv.init(jax.random.PRNGKey(1), h)
    out = conv.apply(v, h)
    assert out.shape == (6, 8, 8, 6)
    # Frame independence: conv(frames separately) == conv(stacked).
    out0 = conv.apply(v, h[::3])
    np.testing.assert_allclose(np.asarray(out[::3]), np.asarray(out0),
                               atol=1e-5)


@pytest.mark.slow
def test_remat_modes_same_params_and_grads():
    """Every remat mode must yield the SAME param tree (checkpoints trained
    with remat on/off are interchangeable — nn.remat's 'CheckpointXUNetBlock'
    class name would otherwise fork the tree) and identical outputs/grads."""
    import dataclasses

    batch = make_batch(jax.random.PRNGKey(3))
    results = {}
    for remat in (False, True, "full", "dots", "none"):
        cfg = dataclasses.replace(TINY, remat=remat)
        model = XUNet(cfg)
        v = model.init({"params": jax.random.PRNGKey(0)}, batch,
                       cond_mask=jnp.ones((batch["z"].shape[0],)),
                       train=False)

        def loss(p):
            out = model.apply({"params": p}, batch,
                              cond_mask=jnp.ones((batch["z"].shape[0],)),
                              train=False)
            return jnp.sum((out - 0.5) ** 2)

        g = jax.jit(jax.grad(loss))(v["params"])
        results[str(remat)] = (v["params"], jax.device_get(g))

    base_params, base_grads = results["False"]
    base_paths = [jax.tree_util.keystr(p) for p, _ in
                  jax.tree_util.tree_flatten_with_path(base_params)[0]]
    for mode, (params, grads) in results.items():
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]]
        assert paths == base_paths, f"param tree differs for remat={mode}"
        for a, b in zip(jax.tree.leaves(base_grads), jax.tree.leaves(grads)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


def test_remat_rejects_unknown_mode():
    import dataclasses

    batch = make_batch(jax.random.PRNGKey(3))
    with pytest.raises(ValueError, match="remat"):
        XUNet(dataclasses.replace(TINY, remat="bogus")).init(
            {"params": jax.random.PRNGKey(0)}, batch,
            cond_mask=jnp.ones((batch["z"].shape[0],)), train=False)

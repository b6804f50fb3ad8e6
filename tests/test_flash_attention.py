"""Fused Pallas attention vs. the XLA reference path.

Runs in interpreter mode on the CPU test mesh (ops/flash_attention.py picks
interpret automatically off-TPU) — the same kernel code compiles via Mosaic
on real TPU.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.ops import flash_attention as fa
from novel_view_synthesis_3d_tpu.ops.flash_attention import (
    flash_attention, forward_blocks)


def _ref_attention(q, k, v):
    return nn.dot_product_attention(q, k, v)


def _qkv(seed, B, Lq, Lk, H, D, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, Lq, H, D), dtype),
            jax.random.normal(ks[1], (B, Lk, H, D), dtype),
            jax.random.normal(ks[2], (B, Lk, H, D), dtype))


@pytest.mark.parametrize(
    "B,Lq,Lk,H,D",
    [
        (2, 64, 64, 4, 8),     # tiny64 self-attn shape class
        (1, 100, 300, 2, 16),  # ragged lengths → padding/masking path
        (2, 256, 256, 4, 64),
        (1, 50, 50, 2, 8),     # a length no multiple of 16 (or of 128)
        (2, 256, 320, 4, 32),  # several query blocks, keys ragged past a
                               # lane block, a head of 32
        # Past 1024 padded keys a grid step walks the key axis in blocks
        # (forward_blocks): a whole multiple of the key block; a ragged Lk
        # whose padding boundary falls inside the last block; Lq != Lk
        # with a ragged Lq too.
        (1, 128, 2048, 2, 16),
        (1, 64, 1300, 2, 16),
        (2, 100, 1536, 1, 32),
    ],
)
def test_matches_xla_attention(B, Lq, Lk, H, D):
    q, k, v = _qkv(0, B, Lq, Lk, H, D)
    assert (forward_blocks(Lq, Lk, D, 4)[2] > 1) == (Lk > 1024)
    out = flash_attention(q, k, v, block_q=64)
    ref = _ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_blocked_form_in_bfloat16_is_as_close_as_the_single_block():
    """The token trunk's dtype and head width: against float32 attention
    over the same bfloat16 operands, the walk over key blocks may be off
    by no more than the one-block body is (its rescaling is float32; both
    round p to bfloat16 before p·v), with a little room for the order of
    the sums."""
    B, Lq, Lk, H, D = 1, 128, 2048, 2, 128
    q, k, v = _qkv(4, B, Lq, Lk, H, D, jnp.bfloat16)
    want = _ref_attention(*(x.astype(jnp.float32) for x in (q, k, v)))
    flat = lambda x: x.reshape(B, -1, H * D)  # the heads side by side

    def err(block_k):
        out, _ = fa._flash_fwd_padded(
            flat(q), flat(k), flat(v), heads=(H, H), scale=D ** -0.5,
            kv_len=Lk, block_q=Lq, block_k=block_k, with_lse=False,
            interpret=True)
        out = out.reshape(B, Lq, H, D)
        return float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))

    bq, bk, key_blocks = forward_blocks(Lq, Lk, D, 2)
    assert key_blocks == Lk // bk > 1
    single = err(Lk)
    assert 0 < single < 0.02  # bfloat16's own rounding of the output
    assert err(bk) <= 1.5 * single
    shipped = flash_attention(q, k, v).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(shipped - want))) <= 1.5 * single


@pytest.mark.parametrize("Lq,Lk", [(96, 1280), (64, 1100), (48, 200)])
def test_lse_is_the_reference_logsumexp(Lq, Lk):
    """What the backward kernels read: lse = log Σ exp(scale · q·kᵀ) over
    the true keys, from the blocked form (running max and sum) as from the
    one-block body; the primal call does not write it."""
    B, H, D = 1, 2, 16
    q, k, v = _qkv(5, B, Lq, Lk, H, D)
    scale = D ** -0.5
    out, lse = fa._flash_fwd_core(q, k, v, scale, 32, with_lse=True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.scipy.special.logsumexp(s, -1)),
        atol=1e-5, rtol=1e-5)
    primal, none = fa._flash_fwd_core(q, k, v, scale, 32, with_lse=False)
    assert none is None
    np.testing.assert_array_equal(np.asarray(primal), np.asarray(out))


def _xunet_attention_shapes():
    """(preset, tokens, head width) of every attention the X-UNet presets
    run: self and cross attention both see one frame's H·W tokens."""
    from novel_view_synthesis_3d_tpu.config import get_preset

    shapes = []
    for name in ("tiny64", "base128", "paper256", "pod64"):
        cfg = get_preset(name)
        m = cfg.model
        for level, mult in enumerate(m.ch_mult):
            res = cfg.data.img_sidelength >> level
            if res in m.attn_resolutions:
                shapes.append((name, res * res, m.ch * mult // m.attn_heads))
    return shapes


def test_forward_blocks_at_the_shapes_that_run():
    """The mechanism's counter: whether the key axis is walked in blocks
    is a function of shapes. The token cell's steps (1024 queries, 2048
    keys) take the blocked form with a query block that fills the frame;
    its once-a-call pass (1024 keys) and every X-UNet preset keep the
    one-block body at the query block they had."""
    assert forward_blocks(1024, 2048, 128, 2) == (1024, 512, 4)
    assert forward_blocks(1024, 1024, 128, 2) == (256, 1024, 1)
    shapes = _xunet_attention_shapes()
    assert {(L, D) for _, L, D in shapes} == {
        (1024, 16), (1024, 64), (256, 128), (1024, 256), (256, 256)}
    for name, L, D in shapes:
        bq, bk, key_blocks = forward_blocks(L, L, D, 2)
        assert (bq, bk, key_blocks) == (min(256, L), max(128, L), 1), name
    # block_q= stays an upper bound, rounded up to 16 sublanes.
    assert forward_blocks(1024, 2048, 128, 2, block_q=200) == (208, 512, 4)
    assert forward_blocks(100, 300, 16, 4, block_q=64) == (64, 384, 1)
    assert forward_blocks(40, 1300, 16, 4) == (48, 512, 3)


@pytest.mark.parametrize("L,Lk", [(48, 48), (40, 1100)])
def test_gradients_match_xla(L, Lk):
    """The second case's forward is the blocked form (three key blocks,
    the last one ragged): the backward kernels read its lse."""
    B, H, D = 1, 2, 8
    q, k, v = _qkv(1, B, L, Lk, H, D)
    assert (forward_blocks(L, Lk, D, 4)[2] > 1) == (Lk > 1024)

    def f_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, block_q=16)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(_ref_attention(q, k, v)))

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=1e-5, rtol=1e-4)


def test_gradients_block_size_not_dividing_128():
    """Regression: bk ∤ 128 once left a partial trailing kv block unwritten
    in the dk/dv grid (kv padding must be a common multiple of bk and 128)."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, L, H, D = 1, 120, 2, 64  # D ≥ 64 → Pallas backward path
    q = jax.random.normal(ks[0], (B, L, H, D))
    k = jax.random.normal(ks[1], (B, L, H, D))
    v = jax.random.normal(ks[2], (B, L, H, D))

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=112) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(_ref_attention(q, k, v) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        assert np.isfinite(np.asarray(gf)).all()
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=1e-4, rtol=1e-3)


def test_jit_and_vmap_compatible():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, L, H, D = 2, 32, 2, 8
    q = jax.random.normal(ks[0], (B, L, H, D))
    k = jax.random.normal(ks[1], (B, L, H, D))
    v = jax.random.normal(ks[2], (B, L, H, D))
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, block_q=16))(q, k, v)
    ref = _ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_model_flag_wires_kernel():
    """XUNet(use_flash_attention=True) ≈ XUNet(False) with identical params."""
    from novel_view_synthesis_3d_tpu.config import ModelConfig
    from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
    from novel_view_synthesis_3d_tpu.models.xunet import XUNet

    raw = make_example_batch(batch_size=1, sidelength=16, seed=0)
    batch = {
        "x": jnp.asarray(raw["x"]),
        "z": jnp.asarray(raw["target"]),
        "logsnr": jnp.zeros((1,)),
        "R1": jnp.asarray(raw["R1"]), "t1": jnp.asarray(raw["t1"]),
        "R2": jnp.asarray(raw["R2"]), "t2": jnp.asarray(raw["t2"]),
        "K": jnp.asarray(raw["K"]),
    }
    cond_mask = jnp.ones((1,))
    base = ModelConfig(ch=32, ch_mult=(1, 2), num_res_blocks=1,
                       attn_resolutions=(8,))
    m0 = XUNet(base)
    params = m0.init({"params": jax.random.PRNGKey(0),
                      "dropout": jax.random.PRNGKey(1)},
                     batch, cond_mask=cond_mask, train=False)["params"]
    out0 = m0.apply({"params": params}, batch, cond_mask=cond_mask,
                    train=False)
    import dataclasses
    m1 = XUNet(dataclasses.replace(base, use_flash_attention=True))
    out1 = m1.apply({"params": params}, batch, cond_mask=cond_mask,
                    train=False)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1),
                               atol=1e-5, rtol=1e-5)


def test_attention_layer_runs_the_kernel_per_data_shard():
    """AttnLayer with a mesh wraps the kernel in a shard_map over 'data'
    (ops/_pallas.over_data_axis — the compiled kernel cannot be
    partitioned by GSPMD): same values as the unwrapped layer, output
    still batch-sharded."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from novel_view_synthesis_3d_tpu.config import MeshConfig
    from novel_view_synthesis_3d_tpu.models.layers import AttnLayer
    from novel_view_synthesis_3d_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(MeshConfig(data=4))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 24, 32))
    plain = AttnLayer(attn_heads=4, use_flash=True)
    params = plain.init(jax.random.PRNGKey(1), q=x, kv=x)
    want = plain.apply(params, q=x, kv=x)
    sharded = jax.device_put(x, NamedSharding(mesh, P("data")))
    layer = AttnLayer(attn_heads=4, use_flash=True, mesh=mesh)
    got = jax.jit(lambda p, x: layer.apply(p, q=x, kv=x))(params, sharded)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert got.sharding.spec[0] == "data"
    text = jax.jit(lambda p, x: layer.apply(p, q=x, kv=x)).lower(
        params, sharded).as_text()
    assert "shard_map" in text or "manual" in text


# ---------------------------------------------------------------------------
# Grouped key/value heads and a one-sided window (forward only)
# ---------------------------------------------------------------------------
def _banded_reference(q, k, v, scale, window, q_offset):
    """Dense einsum: query head h on key/value head h // group; the query
    of row i at position q_offset + i sees key j iff j > position −
    window."""
    B, Lq, H, D = q.shape
    Lk, Hkv = k.shape[1], k.shape[2]
    k, v = (jnp.repeat(t, H // Hkv, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if window is not None:
        seen = np.arange(Lk)[None] > q_offset + np.arange(Lq)[:, None] \
            - window
        s = jnp.where(jnp.asarray(seen)[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


BANDED = {
    # name: (Lq, Lk, H, Hkv, D, window, q_offset, block_q)
    "grouped_heads_no_window": (64, 128, 4, 2, 16, None, None, 1024),
    "grouped_heads_one_key_block": (64, 128, 4, 2, 16, 64, None, 1024),
    # the trunk's geometry in small: queries are the second half of the
    # keys and the window is a frame long, row r sees cached keys c > r
    "window_of_a_frame_ragged": (200, 400, 6, 2, 16, 200, None, 64),
    # past 1024 keys the walk is blocked (512 keys a block). The band's
    # edge inside a block for every row:
    "edge_inside_a_block": (256, 1536, 2, 2, 8, 700, 1111, 128),
    # the edge of the first row of each query block ON a block boundary
    # (q_offset − window + 1 = 512; query blocks of 128 and 512 rows):
    "edge_at_block_boundaries": (256, 1536, 2, 1, 8, 769, 1280, 128),
    "query_block_as_long_as_a_key_block": (1024, 2048, 1, 1, 8, 1024, None,
                                           512),
    # a band that leaves whole key blocks out for every query block (the
    # first 512 keys are never visited) and for some (dynamic skip):
    "skips_whole_blocks": (256, 2048, 4, 2, 8, 300, 1500, 64),
    "ragged_keys_and_rows": (100, 1300, 3, 1, 16, 333, 1100, 64),
    # a window that never binds takes the plain body
    "window_longer_than_the_sequence": (64, 128, 2, 2, 16, 500, None, 64),
}


@pytest.mark.parametrize("name", sorted(BANDED))
def test_grouped_heads_and_window_match_a_dense_einsum(name):
    Lq, Lk, H, Hkv, D, window, q_offset, block_q = BANDED[name]
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    q = jax.random.normal(ks[0], (2, Lq, H, D))
    k = jax.random.normal(ks[1], (2, Lk, Hkv, D))
    v = jax.random.normal(ks[2], (2, Lk, Hkv, D))
    out = flash_attention(q, k, v, scale=0.3, window=window,
                          q_offset=q_offset, block_q=block_q)
    ref = _banded_reference(q, k, v, 0.3, window,
                            Lk - Lq if q_offset is None else q_offset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    if window is not None and name != "window_longer_than_the_sequence":
        off = Lk - Lq if q_offset is None else q_offset
        assert fa.window_binds(Lq, window, off)
        # and the band matters: without it the answer is another
        assert float(jnp.max(jnp.abs(
            out - _banded_reference(q, k, v, 0.3, None, 0)))) > 1e-2


def test_band_walk_skips_what_no_row_sees():
    """`band_key_columns` is the walk the kernel makes, from shapes: at
    the token trunk's size (a frame of 4096 queries against [cache ; own],
    window 4096) a head visits 6656 of 8192 key columns a row on average
    for the 6143.5 its rows see. `_band_blocks` lists a query block's key
    blocks last first, leaves out the ones none of its rows sees and
    marks the ones on the band's edge."""
    visited, visible = fa.band_key_columns(4096, 8192, 4096, 4096)
    assert visible == 4096 * 8192 - sum(range(1, 4097))   # c > r
    assert visited == 1024 * (8192 + 7168 + 6144 + 5120)
    assert 1.0 < visited / visible < 1.25
    # no window to speak of: every column visited is seen
    assert fa.band_key_columns(256, 2048, 10 ** 6, 1792)[0] == 256 * 2048
    # 256 rows from position 1500 under a window of 300: keys 1201… for
    # the first row, 1456… for the last
    assert fa._band_blocks(2048, 512, (300, 1500), 256) == [
        (1536, False), (1024, True)]
    # the trunk's third query block: cached keys from 2049 on, the edge
    # crossing two key blocks
    assert fa._band_blocks(8192, 512, (4096, 4096 + 2048), 1024)[-3:] == [
        (3072, False), (2560, True), (2048, True)]
    # the plain body's blocks are untouched by all this
    assert forward_blocks(4096, 8192, 128, 2) == (1024, 512, 16)


def test_grouped_heads_and_window_are_forward_only():
    q, k, v = _qkv(3, 1, 32, 64, 4, 8)
    k, v = k[:, :, :2], v[:, :, :2]
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda q: jnp.sum(flash_attention(q, k, v)))(q)
    with pytest.raises(ValueError, match="do not divide"):
        flash_attention(q[:, :, :3], k, v)


VALUE_WIDTHS = {
    # name: (Lq, Lk, H, Hkv, D, Dv, window): values narrower (latent
    # attention whose keys carry a part no value has: 192 against 128 at
    # the third token trunk's widths) and wider than the keys
    "trunk_widths_one_key_block": (64, 128, 2, 2, 192, 128, None),
    "trunk_widths_blocked_walk": (256, 1536, 2, 2, 192, 128, None),
    "narrow_keys_wide_values": (100, 300, 3, 3, 8, 24, None),
    "with_grouped_heads_and_a_window": (200, 400, 4, 2, 24, 16, 200),
}


@pytest.mark.parametrize("name", sorted(VALUE_WIDTHS))
def test_values_of_another_width_than_the_keys_match_xla(name):
    Lq, Lk, H, Hkv, D, Dv, window = VALUE_WIDTHS[name]
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    q = jax.random.normal(ks[0], (2, Lq, H, D))
    k = jax.random.normal(ks[1], (2, Lk, Hkv, D))
    v = jax.random.normal(ks[2], (2, Lk, Hkv, Dv))
    scale = D ** -0.5
    out = flash_attention(q, k, v, scale=scale, window=window)
    assert out.shape == (2, Lq, H, Dv)
    ref = _banded_reference(q, k, v, scale, window, Lk - Lq)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # forward only, and it says so
    with pytest.raises(NotImplementedError, match="another width"):
        jax.grad(lambda q: jnp.sum(flash_attention(q, k, v)))(q)



# ---------------------------------------------------------------------------
# The kernel's own operands: token-major, a head a block of lanes
# ---------------------------------------------------------------------------
TOKEN_MAJOR = {
    # name: (Lq, Lk, H, Hkv, D, Dv, window, block_q); every head padded in
    # place to whole 128-lane blocks, as on the chip
    "grouped_heads_under_a_window": (256, 1536, 6, 2, 16, 16, 700, 128),
    "latent_widths_192_on_128": (64, 1280, 2, 2, 192, 128, None, 64),
    "pairs_of_64_on_128": (128, 256, 4, 2, 64, 128, None, 64),
    "rows_short_of_a_block": (100, 300, 3, 1, 24, 24, None, 64),
    "rows_short_of_a_block_under_a_window": (200, 400, 4, 2, 24, 16, 200,
                                             64),
}


@pytest.mark.parametrize("name", sorted(TOKEN_MAJOR))
def test_token_major_operands_match_a_dense_einsum(name):
    """`_flash_fwd_padded` on what the chip's lane hands it — (B, L', H·D')
    with D' whole lane blocks, the pad lanes of every head zero, K and V
    with their own fewer heads — against the dense einsum: query head h
    reads the lanes of key/value head h // (H // Hkv), and the output
    has the values' padded width a head, the heads side by side."""
    Lq, Lk, H, Hkv, D, Dv, window, block_q = TOKEN_MAJOR[name]
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    q = jax.random.normal(ks[0], (B, Lq, H, D))
    k = jax.random.normal(ks[1], (B, Lk, Hkv, D))
    v = jax.random.normal(ks[2], (B, Lk, Hkv, Dv))
    bq, bk, _ = forward_blocks(Lq, Lk, D, 4, block_q)
    qt, kt, vt = (fa._heads_side_by_side(x, block, 128)
                  for x, block in ((q, bq), (k, bk), (v, bk)))
    Dp, Dvp = -(-D // 128) * 128, -(-Dv // 128) * 128
    assert qt.shape == (B, -(-Lq // bq) * bq, H * Dp)
    assert kt.shape[2] == Hkv * Dp and vt.shape[2] == Hkv * Dvp
    out, none = fa._flash_fwd_padded(
        qt, kt, vt, heads=(H, Hkv), scale=0.3, kv_len=Lk, block_q=bq,
        block_k=bk, with_lse=False, interpret=True,
        band=None if window is None else (window, Lk - Lq))
    assert none is None and out.shape == (B, qt.shape[1], H * Dvp)
    out = out.reshape(B, -1, H, Dvp)
    ref = _banded_reference(q, k, v, 0.3, window, Lk - Lq)
    np.testing.assert_allclose(np.asarray(out[:, :Lq, :, :Dv]),
                               np.asarray(ref), atol=2e-5, rtol=2e-5)
    # the pad lanes of the output are the zero values' sums
    assert not np.asarray(out[:, :Lq, :, Dv:]).any()
    # and the wrapper, which pads nothing but the token axis here, agrees
    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, scale=0.3, window=window,
                                   block_q=block_q)),
        np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_vjp_forward_hands_the_backward_a_head_major_lse():
    """The VJP's forward goes through the token-major kernel and still
    hands the backward (B, H, Lq) — row b·H + h of the kernel's lse output
    is (b, h) —: lse is the reference's logsumexp head by head, and the
    gradients through `_flash_bwd_pallas` (D ≥ 64) are XLA's."""
    B, Lq, Lk, H, D = 2, 40, 1100, 3, 64
    q, k, v = _qkv(7, B, Lq, Lk, H, D)
    scale = D ** -0.5
    out, (_, _, _, saved, lse) = fa._flash_vjp_fwd(q, k, v, scale, 16)
    assert lse.shape == (B, H, Lq)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.scipy.special.logsumexp(s, -1)),
        atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(saved), np.asarray(out))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_ref_attention(q, k, v)),
                               atol=1e-5, rtol=1e-5)
    g_flash = jax.grad(lambda *a: jnp.sum(jnp.sin(flash_attention(
        *a, block_q=16))), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: jnp.sum(jnp.sin(_ref_attention(*a))),
                     argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# A key part all heads share, as an operand of its own
# ---------------------------------------------------------------------------
SHARED_PART = {
    # name: (Lq, Lk, H, w, dtype, block_q): heads of 128 lanes of their own
    # beside w shared ones (the latent trunks' 128 + 64; a head's w lanes
    # lie in the lane block of 128/w heads, every place in it exercised),
    # the key axis one block or walked, with and without a pad
    "pairs_one_key_block": (48, 200, 2, 64, jnp.float32, 1024),
    "pairs_blocked_walk_keys_padded": (96, 1100, 4, 64, jnp.float32, 1024),
    "pairs_blocked_walk_two_query_blocks": (80, 1536, 2, 64, jnp.float32,
                                            64),
    "pairs_bfloat16_blocked_walk": (64, 1280, 4, 64, jnp.bfloat16, 1024),
    "pairs_bfloat16_one_key_block": (40, 130, 2, 64, jnp.bfloat16, 1024),
    "four_heads_a_lane_block": (40, 300, 4, 32, jnp.float32, 1024),
}


@pytest.mark.parametrize("name", sorted(SHARED_PART))
def test_shared_key_part_as_its_own_operand_matches_a_dense_einsum(name):
    """`flash_attention(…, shared=(qs, ks))` — a score the sum of the
    head's own product and its w more lanes against the ONE key part —
    against the dense float32 einsum on the concatenated (128 + w)-wide
    heads, the shared part copied under every head."""
    Lq, Lk, H, w, dtype, block_q = SHARED_PART[name]
    B, D, Dv = 2, 128, 128
    assert fa.shared_part_fits(H, D, w)
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 5)
    q = jax.random.normal(ks[0], (B, Lq, H, D), dtype)
    qs = jax.random.normal(ks[1], (B, Lq, H, w), dtype)
    k = jax.random.normal(ks[2], (B, Lk, H, D), dtype)
    shared = jax.random.normal(ks[3], (B, Lk, w), dtype)
    v = jax.random.normal(ks[4], (B, Lk, H, Dv), dtype)
    out = flash_attention(q, k, v, shared=(qs, shared), block_q=block_q)
    assert out.shape == (B, Lq, H, Dv) and out.dtype == dtype
    f32 = jnp.float32
    whole_q = jnp.concatenate([q, qs], axis=-1).astype(f32)
    whole_k = jnp.concatenate(
        [k, jnp.broadcast_to(shared[:, :, None], (B, Lk, H, w))],
        axis=-1).astype(f32)
    ref = _banded_reference(whole_q, whole_k, v.astype(f32),
                            (D + w) ** -0.5, None, Lk - Lq)
    tol = 2e-5 if dtype == f32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=tol, rtol=tol)
    if dtype == f32:
        # every head reads ITS lanes of qs: another head's would show
        swapped = flash_attention(q, k, v, shared=(qs[:, :, ::-1], shared),
                                  block_q=block_q)
        assert np.abs(np.asarray(swapped) - np.asarray(ref)).max() > 1e-2


def test_shared_key_part_is_forward_only_and_refuses_what_it_cannot_lay():
    q, k, v = _qkv(3, 1, 32, 64, 2, 128)
    qs, ks = q[..., :64], k[:, :, 0, :64]
    with pytest.raises(NotImplementedError, match="shared key"):
        jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, shared=(qs, ks))))(q)
    with pytest.raises(ValueError, match="under a window"):
        flash_attention(q, k, v, shared=(qs, ks), window=40)
    # heads that are no whole lane blocks, a part that is one, or a number
    # of heads that leaves a lane block half filled: the one-operand form
    assert not fa.shared_part_fits(2, 64, 64)
    assert not fa.shared_part_fits(2, 128, 128)
    assert not fa.shared_part_fits(3, 128, 64)
    assert not fa.shared_part_fits(2, 128, 48)
    with pytest.raises(ValueError, match="no shared key part"):
        flash_attention(q[..., :64], k[..., :64], v, shared=(qs, ks))


LOWERED_WITHOUT_A_SHARED_PART = {
    # sha256 of the CPU-lowered text (the kernel through the interpreter)
    # of a caller that hands no second operand, from PR 44's tree: the
    # shared part is an OPTIONAL operand of one kernel body, and without
    # it the program is the one it was
    "forward": (
        "3caab162e9e855555020eb7144db386b18a6ed302079a0e5784d368f20fa6022"),
    "vjp": (
        "d651a2a4512bd876058939c376a256dff8a07247768a82c150b81a10eda935cc"),
    "values_narrower_than_keys": (
        "e396d8dfe727b040b13c8a2795968b8be59064b2c985d360c80a82e10c3be2d2"),
}


@pytest.mark.parametrize("name", sorted(LOWERED_WITHOUT_A_SHARED_PART))
def test_without_a_shared_part_the_lowered_text_is_what_it_was(name):
    import hashlib

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    small = (S(2, 40, 3, 64), S(2, 1100, 3, 64), S(2, 1100, 3, 64))
    fn, operands = {
        "forward": (lambda q, k, v: flash_attention(q, k, v, block_q=16),
                    small),
        "vjp": (jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, block_q=16))), argnums=(0, 1, 2)), small),
        "values_narrower_than_keys": (
            lambda q, k, v: flash_attention(q, k, v),
            (S(2, 64, 2, 192), S(2, 1280, 2, 192), S(2, 1280, 2, 128))),
    }[name]
    text = jax.jit(fn).lower(*operands).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        LOWERED_WITHOUT_A_SHARED_PART[name]

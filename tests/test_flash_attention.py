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

from novel_view_synthesis_3d_tpu.ops.flash_attention import flash_attention


def _ref_attention(q, k, v):
    return nn.dot_product_attention(q, k, v)


@pytest.mark.parametrize(
    "B,Lq,Lk,H,D",
    [
        (2, 64, 64, 4, 8),     # tiny64 self-attn shape class
        (1, 100, 300, 2, 16),  # ragged lengths → padding/masking path
        (2, 256, 256, 4, 64),
    ],
)
def test_matches_xla_attention(B, Lq, Lk, H, D):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Lq, H, D))
    k = jax.random.normal(ks[1], (B, Lk, H, D))
    v = jax.random.normal(ks[2], (B, Lk, H, D))
    out = flash_attention(q, k, v, block_q=64)
    ref = _ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_gradients_match_xla():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, L, H, D = 1, 48, 2, 8
    q = jax.random.normal(ks[0], (B, L, H, D))
    k = jax.random.normal(ks[1], (B, L, H, D))
    v = jax.random.normal(ks[2], (B, L, H, D))

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


"""FSDP sharding and sequence-parallel attention equivalence tests.

On the 8-device CPU mesh (conftest.py): an FSDP-sharded train step must be
numerically equivalent to the replicated step, and a sequence-parallel model
forward must match the single-sharding forward.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from novel_view_synthesis_3d_tpu.config import (
    Config, DiffusionConfig, MeshConfig, ModelConfig, TrainConfig)
from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
from novel_view_synthesis_3d_tpu.diffusion import make_schedule
from novel_view_synthesis_3d_tpu.models.xunet import XUNet
from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib
from novel_view_synthesis_3d_tpu.parallel.mesh import fsdp_spec
from novel_view_synthesis_3d_tpu.train.state import create_train_state
from novel_view_synthesis_3d_tpu.train.step import make_train_step
from novel_view_synthesis_3d_tpu.train.trainer import _sample_model_batch

from jax.sharding import PartitionSpec as P
import pytest


def _tiny_cfg(**over):
    base = dict(
        model=ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                          attn_resolutions=(8,), dropout=0.0),
        diffusion=DiffusionConfig(timesteps=50),
        train=TrainConfig(batch_size=8, lr=1e-3, cond_drop_prob=0.1,
                          ema_decay=0.0),
    )
    base.update(over)
    return Config(**base)


def test_fsdp_spec_rules():
    mesh = mesh_lib.make_mesh(MeshConfig(data=8, model=1, seq=1))
    # Large divisible tensor → sharded on its largest divisible axis.
    assert fsdp_spec(mesh, (256, 384)) == P(None, "data")
    assert fsdp_spec(mesh, (1024, 64)) == P("data", None)
    # Small tensors and indivisible shapes stay replicated.
    assert fsdp_spec(mesh, (32,)) == P()
    assert fsdp_spec(mesh, (129, 257)) == P()
    assert fsdp_spec(mesh, ()) == P()


@pytest.mark.slow
def test_fsdp_step_matches_replicated():
    cfg = _tiny_cfg()
    schedule = make_schedule(cfg.diffusion)
    model = XUNet(cfg.model)
    batch = make_example_batch(batch_size=8, sidelength=16, seed=0)

    def run(fsdp: bool, steps: int = 3):
        mesh = mesh_lib.make_mesh(MeshConfig(data=8, model=1, seq=1))
        state = create_train_state(cfg.train, model,
                                   _sample_model_batch(batch))
        sharding = mesh_lib.state_shardings(mesh, state, fsdp)
        state = jax.device_put(state, sharding)
        step = make_train_step(cfg, model, schedule, mesh,
                               state_sharding=sharding)
        db = mesh_lib.shard_batch(mesh, batch)
        losses = []
        for _ in range(steps):
            state, m = step(state, db)
            losses.append(float(jax.device_get(m["loss"])))
        return losses, jax.device_get(state.params)

    losses_r, params_r = run(False)
    losses_f, params_f = run(True)
    np.testing.assert_allclose(losses_r, losses_f, rtol=1e-5)
    flat_r = jax.tree.leaves(params_r)
    flat_f = jax.tree.leaves(params_f)
    for a, b in zip(flat_r, flat_f):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_fsdp_actually_shards_large_params():
    cfg = _tiny_cfg()
    mesh = mesh_lib.make_mesh(MeshConfig(data=8, model=1, seq=1))
    model = XUNet(cfg.model)
    batch = make_example_batch(batch_size=8, sidelength=16, seed=0)
    state = create_train_state(cfg.train, model, _sample_model_batch(batch))
    sharding = mesh_lib.state_shardings(mesh, state, True)
    state = jax.device_put(state, sharding)
    sharded_leaves = [
        x for x in jax.tree.leaves(state.params)
        if hasattr(x, "sharding") and x.sharding.spec != P()]
    assert sharded_leaves, "expected at least some params sharded over 'data'"
    for x in sharded_leaves:
        assert x.size % 8 == 0
        # Per-device shard is 1/8 of the global array.
        db = x.sharding.shard_shape(x.shape)
        assert int(np.prod(db)) == x.size // 8


@pytest.mark.slow
def test_sequence_parallel_forward_matches_dense():
    mesh = mesh_lib.make_mesh(MeshConfig(data=2, model=1, seq=4))
    mcfg = ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                       attn_resolutions=(8, 16), dropout=0.0)
    raw = make_example_batch(batch_size=2, sidelength=16, seed=1)
    batch = {
        "x": jnp.asarray(raw["x"]),
        "z": jnp.asarray(raw["target"]),
        "logsnr": jnp.zeros((2,)),
        "R1": jnp.asarray(raw["R1"]), "t1": jnp.asarray(raw["t1"]),
        "R2": jnp.asarray(raw["R2"]), "t2": jnp.asarray(raw["t2"]),
        "K": jnp.asarray(raw["K"]),
    }
    cond_mask = jnp.ones((2,))
    dense = XUNet(mcfg)
    params = dense.init({"params": jax.random.PRNGKey(0),
                         "dropout": jax.random.PRNGKey(1)},
                        batch, cond_mask=cond_mask, train=False)["params"]
    out_dense = dense.apply({"params": params}, batch, cond_mask=cond_mask,
                            train=False)
    sp = XUNet(dataclasses.replace(mcfg, sequence_parallel=True), mesh=mesh)
    out_sp = sp.apply({"params": params}, batch, cond_mask=cond_mask,
                      train=False)
    np.testing.assert_allclose(np.asarray(out_dense), np.asarray(out_sp),
                               atol=1e-4, rtol=1e-4)


def test_host_side_init_matches_default():
    """create_train_state(on_cpu=True) — the accelerator start-up path
    — must produce the identical param tree (structure AND values; threefry
    is backend-deterministic) as the default init, including under the
    flash/sequence-parallel model variants it swaps out during init."""
    mesh = mesh_lib.make_mesh(MeshConfig(data=2, model=1, seq=4))
    mcfg = ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                       attn_resolutions=(8,), dropout=0.0,
                       use_flash_attention=True, sequence_parallel=True)
    batch = make_example_batch(batch_size=8, sidelength=16, seed=0)
    model = XUNet(mcfg, mesh=mesh)
    tcfg = TrainConfig(batch_size=8, ema_decay=0.999)
    sample = _sample_model_batch(batch)
    s_host = create_train_state(tcfg, model, sample, on_cpu=True)
    s_default = create_train_state(tcfg, model, sample, on_cpu=False)
    ja, jb = jax.tree.flatten(s_host.params), jax.tree.flatten(s_default.params)
    assert ja[1] == jb[1], "param tree structure differs"
    for a, b in zip(ja[0], jb[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # Optimizer + EMA state trees exist and mirror params.
    assert jax.tree.structure(s_host.ema_params) == jax.tree.structure(
        s_default.ema_params)


@pytest.mark.slow
def test_pod64_preset_scaled_one_step():
    """pod64 (BASELINE ladder step 5) structure: data=-1 mesh absorption +
    FSDP + bf16/remat flags — executed scaled-down on the 8-device mesh."""
    from novel_view_synthesis_3d_tpu.config import get_preset

    cfg = get_preset("pod64")
    assert cfg.train.fsdp and cfg.model.remat
    assert cfg.mesh.data == -1
    cfg = cfg.override(**{
        "train.batch_size": 8, "data.img_sidelength": 32, "model.ch": 32,
        "model.ch_mult": [1, 2], "model.emb_ch": 32,
        "model.num_res_blocks": 1, "model.dtype": "float32",
        "model.remat": False})
    mesh = mesh_lib.make_mesh(cfg.mesh)
    assert mesh.shape["data"] == 8  # -1 absorbed all virtual devices
    batch = make_example_batch(batch_size=8, sidelength=32)
    model = XUNet(cfg.model)
    schedule = make_schedule(cfg.diffusion)
    state = create_train_state(cfg.train, model, _sample_model_batch(batch))
    sharding = mesh_lib.state_shardings(mesh, state, cfg.train.fsdp)
    state = jax.device_put(state, sharding)
    step = make_train_step(cfg, model, schedule, mesh,
                           state_sharding=sharding)
    state, m = step(state, mesh_lib.shard_batch(mesh, batch))
    assert np.isfinite(float(jax.device_get(m["loss"])))


@pytest.mark.slow
def test_dryrun_multichip_entrypoint():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "_graft", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.dryrun_multichip(8)


class TestFitLocalMeshWarnings:
    """fit_local_mesh must be loud about every fallback/recompute decision
    (VERDICT r2 weak #5: a silently dropped mesh request turns a 'sharded'
    bench into an unlabeled single-device run)."""

    def test_non_divisible_claims_warn_and_return_none(self):
        # 8 virtual devices, model×seq = 3 doesn't divide → None + warning.
        import warnings

        with warnings.catch_warnings(record=True) as ws:
            warnings.simplefilter("always")
            mesh = mesh_lib.fit_local_mesh(MeshConfig(data=4, model=3, seq=1))
        assert mesh is None
        assert any("UNSHARDED" in str(w.message) for w in ws)

    def test_data_axis_recompute_warns(self):
        # Config claims data=2 but 8 devices / (model=1×seq=1) = 8 → warn.
        import warnings

        with warnings.catch_warnings(record=True) as ws:
            warnings.simplefilter("always")
            mesh = mesh_lib.fit_local_mesh(MeshConfig(data=2, model=1, seq=1))
        assert mesh is not None
        assert mesh.devices.size == 8
        assert any("mesh.data=2 replaced by 8" in str(w.message) for w in ws)

    def test_matching_config_is_silent(self):
        import warnings

        with warnings.catch_warnings(record=True) as ws:
            warnings.simplefilter("always")
            mesh = mesh_lib.fit_local_mesh(MeshConfig(data=-1, model=2, seq=1))
        assert mesh is not None
        assert not [w for w in ws if "fit_local_mesh" in str(w.message)]

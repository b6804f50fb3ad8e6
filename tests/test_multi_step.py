"""Fused multi-step dispatch (train.steps_per_dispatch): K scanned steps in
one XLA program must be SEMANTICALLY identical to K single dispatches — same
per-step fold_in(rng, step) keys, same optimizer trajectory — with only the
host dispatch count changing. (The reference has one dispatch per step plus
a host round trip per batch, train.py:130-155; this is the TPU-native lever
that amortizes that overhead for small models.)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.config import (
    Config, DiffusionConfig, MeshConfig, ModelConfig, TrainConfig,
)
from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
from novel_view_synthesis_3d_tpu.diffusion import make_schedule
from novel_view_synthesis_3d_tpu.models.xunet import XUNet
from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib
from novel_view_synthesis_3d_tpu.train.state import create_train_state
from novel_view_synthesis_3d_tpu.train.step import make_train_step
from novel_view_synthesis_3d_tpu.train.trainer import _sample_model_batch

CFG = Config(
    model=ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                      attn_resolutions=(8,), dropout=0.1),
    diffusion=DiffusionConfig(timesteps=100),
    train=TrainConfig(batch_size=4, lr=1e-3, cond_drop_prob=0.1),
)
K = 3


def _state(cfg, batch):
    model = XUNet(cfg.model)
    return model, create_train_state(cfg.train, model,
                                     _sample_model_batch(batch))


@pytest.mark.slow
def test_fused_matches_sequential():
    """K fused-scan steps == K single dispatches on the same batches: the
    param trajectories must coincide (identical ops; tolerance only for
    compiler fusion-order float drift)."""
    mesh = mesh_lib.make_mesh(MeshConfig(data=1, model=1, seq=1),
                              devices=jax.devices()[:1])
    schedule = make_schedule(CFG.diffusion)
    batches = [make_example_batch(batch_size=4, sidelength=16, seed=s)
               for s in range(K)]

    model, state_a = _state(CFG, batches[0])
    step1 = make_train_step(CFG, model, schedule, mesh)
    state_a = mesh_lib.replicate(mesh, state_a)
    losses = []
    for b in batches:
        state_a, m = step1(state_a, mesh_lib.shard_batch(mesh, b))
        losses.append(float(m["loss"]))

    cfg_k = dataclasses.replace(
        CFG, train=dataclasses.replace(CFG.train, steps_per_dispatch=K))
    model, state_b = _state(cfg_k, batches[0])
    stepk = make_train_step(cfg_k, model, schedule, mesh)
    state_b = mesh_lib.replicate(mesh, state_b)
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    state_b, mk = stepk(
        state_b, mesh_lib.shard_batch(mesh, stacked, stacked=True))

    assert int(state_b.step) == int(state_a.step) == K
    # Window-mean metrics vs the sequential per-step values.
    np.testing.assert_allclose(float(mk["loss"]), np.mean(losses), rtol=1e-5)
    # Tolerance rationale: the scan body and the standalone step compile to
    # different fusion orders, so gradients differ at the ulp level — and
    # Adam's mu/(sqrt(nu)+eps) normalization maps a near-zero gradient to a
    # near-±lr update, so for those elements ulp drift moves the update by
    # O(lr) regardless of magnitude (observed: ~3e-5 abs on ~0.01% of
    # elements after 3 steps at lr=1e-3). The STRONG semantic check is the
    # mean-loss match above at rtol=1e-5: a wrong per-step key, batch slice,
    # or skipped update shifts losses at the 1e-2 level. The param check
    # (atol well under one update magnitude lr*K) guards the scan carry.
    flat_a = jax.tree.leaves(jax.device_get(state_a.params))
    flat_b = jax.tree.leaves(jax.device_get(state_b.params))
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-4)


@pytest.mark.slow
def test_fused_on_dp_mesh():
    """The stacked batch shards over 'data' under K>1 (leading step axis
    replicated) and the fused step runs on an 8-device mesh."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = mesh_lib.make_mesh(MeshConfig(data=8, model=1, seq=1))
    cfg = dataclasses.replace(
        CFG, train=dataclasses.replace(CFG.train, batch_size=8,
                                       steps_per_dispatch=2))
    schedule = make_schedule(cfg.diffusion)
    batches = [make_example_batch(batch_size=8, sidelength=16, seed=s)
               for s in range(2)]
    model, state = _state(cfg, batches[0])
    state = mesh_lib.replicate(mesh, state)
    stepk = make_train_step(cfg, model, schedule, mesh)
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    device_batch = mesh_lib.shard_batch(mesh, stacked, stacked=True)
    state, m = stepk(state, device_batch)
    assert int(state.step) == 2
    assert np.isfinite(float(m["loss"]))


def test_steps_per_dispatch_validated():
    base = TrainConfig(num_steps=100, log_every=50, save_every=0)
    ok = dataclasses.replace(base, steps_per_dispatch=10)
    Config(train=ok).validate()
    for bad in (
        dataclasses.replace(base, steps_per_dispatch=0),
        dataclasses.replace(base, steps_per_dispatch=3),   # 100 % 3
        dataclasses.replace(base, steps_per_dispatch=10, log_every=25),
        dataclasses.replace(base, steps_per_dispatch=10, eval_every=5),
        dataclasses.replace(base, steps_per_dispatch=10, profile_steps=5),
    ):
        with pytest.raises(ValueError, match="steps_per_dispatch"):
            Config(train=bad).validate()


@pytest.mark.slow
def test_trainer_runs_fused(tmp_path):
    """Trainer end-to-end with steps_per_dispatch=2: stacks host batches,
    advances 2 steps per dispatch, logs/saves at aligned cadences."""
    from novel_view_synthesis_3d_tpu.data.synthetic import write_synthetic_srn
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    from novel_view_synthesis_3d_tpu.config import DataConfig

    root = tmp_path / "data"
    write_synthetic_srn(str(root), 2, 4, 16)
    cfg = Config(
        model=ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32,
                          num_res_blocks=1, attn_resolutions=(8,)),
        diffusion=DiffusionConfig(timesteps=8, sample_timesteps=4),
        data=DataConfig(root_dir=str(root), img_sidelength=16),
        train=TrainConfig(batch_size=8, num_steps=4, steps_per_dispatch=2,
                          log_every=2, save_every=4, lr=1e-3,
                          checkpoint_dir=str(tmp_path / "ckpt"),
                          results_folder=str(tmp_path / "res")),
    )
    trainer = Trainer(config=cfg, use_grain=False)
    trainer.train()
    assert trainer.step == 4
    import csv
    rows = list(csv.DictReader(open(tmp_path / "res" / "metrics.csv")))
    assert [int(r["step"]) for r in rows] == [2, 4]
    assert all(np.isfinite(float(r["loss"])) for r in rows)


@pytest.mark.slow
def test_fused_lr_is_last_step_value():
    """Under fused dispatch, logged lr is the LAST step's schedule value —
    a schedule position, not a window mean (ADVICE r4). With a 10-step
    linear warmup and K=3 from step 0, lr(2) = 2e-4 vs mean 1e-4."""
    mesh = mesh_lib.make_mesh(MeshConfig(data=1, model=1, seq=1),
                              devices=jax.devices()[:1])
    cfg = dataclasses.replace(
        CFG, train=dataclasses.replace(CFG.train, steps_per_dispatch=K,
                                       warmup_steps=10, num_steps=99))
    schedule = make_schedule(cfg.diffusion)
    batches = [make_example_batch(batch_size=4, sidelength=16, seed=s)
               for s in range(K)]
    model, state = _state(cfg, batches[0])
    state = mesh_lib.replicate(mesh, state)
    stepk = make_train_step(cfg, model, schedule, mesh)
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *batches)
    state, m = stepk(state, mesh_lib.shard_batch(mesh, stacked,
                                                 stacked=True))
    lr = cfg.train.lr
    np.testing.assert_allclose(float(m["lr"]), lr * 2 / 10, rtol=1e-6)

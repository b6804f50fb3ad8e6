"""Conditioning-sensitivity standing metric (VERDICT r3 item 3).

The r2/r3 quality postmortem (record deleted in PR 21): an attn_resolutions
set matching no UNet level cut the ONLY path from the conditioning image to
the target frame, and the model trained as an unconditional pose-memorizer
whose seen-pose PSNR looked healthy. The diagnostic that caught it — output
delta under a swapped conditioning image — is now a standing metric; these
tests pin that it (a) fires exactly 0.0 on the inert-attention class,
(b) is positive for a healthy conditioned model, and (c) reaches eval.csv
through the in-loop probe.
"""

import csv
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.config import ModelConfig
from novel_view_synthesis_3d_tpu.eval.evaluate import (
    cond_sensitivity,
    make_cond_sensitivity_fn,
)
from novel_view_synthesis_3d_tpu.models.xunet import XUNet

HEALTHY = ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                      attn_resolutions=(8,), dropout=0.0)
# The postmortem class: a 16px 2-level UNet runs its levels at {16, 8}, so
# attention "at 4" never fires. Config.validate() now rejects this, but the
# metric must still catch a model built around validation (or a future
# regression of the guard).
INERT = ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                    attn_resolutions=(4,), dropout=0.0)


def make_eval_batch(rng, B=4, S=16):
    ks = jax.random.split(rng, 6)
    return {
        "x": jax.random.uniform(ks[0], (B, S, S, 3), minval=-1, maxval=1),
        "target": jax.random.uniform(ks[1], (B, S, S, 3), minval=-1,
                                     maxval=1),
        "R1": jnp.broadcast_to(jnp.eye(3), (B, 3, 3)),
        "t1": jax.random.normal(ks[2], (B, 3)),
        "R2": jnp.broadcast_to(jnp.eye(3), (B, 3, 3)),
        "t2": jax.random.normal(ks[3], (B, 3)),
        "K": jnp.broadcast_to(
            jnp.array([[S / 2.0, 0, S / 2.0],
                       [0, S / 2.0, S / 2.0],
                       [0, 0, 1]]), (B, 3, 3)),
    }


def init_params(cfg, batch):
    model = XUNet(cfg)
    mb = {k: batch[k] for k in ("x", "R1", "t1", "R2", "t2", "K")}
    mb["z"] = batch["target"]
    mb["logsnr"] = jnp.zeros((batch["target"].shape[0],))
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        mb, cond_mask=jnp.ones((batch["target"].shape[0],)), train=False)
    return model, variables["params"]


def perturb(params, scale=0.05):
    """Fresh-init output is exactly 0 (zero-init head), which makes the
    relative delta 0/ε — perturb every param deterministically so the
    network is generically non-degenerate."""
    rng = np.random.default_rng(0)
    return jax.tree.map(
        lambda a: np.asarray(a)
        + scale * rng.standard_normal(a.shape).astype(np.asarray(a).dtype),
        params)


def test_healthy_model_is_sensitive():
    batch = make_eval_batch(jax.random.PRNGKey(0))
    model, params = init_params(HEALTHY, batch)
    sens = cond_sensitivity(model, perturb(params), batch,
                            key=jax.random.PRNGKey(2))
    assert sens is not None
    assert sens > 1e-3, f"healthy model scored cond_sens={sens}"


def test_inert_attention_scores_exactly_zero():
    batch = make_eval_batch(jax.random.PRNGKey(0))
    model, params = init_params(INERT, batch)
    sens = cond_sensitivity(model, perturb(params), batch,
                            key=jax.random.PRNGKey(2))
    assert sens == 0.0, (
        f"inert-attention model must score exactly 0, got {sens}")


def test_vacuous_swap_returns_none():
    batch = make_eval_batch(jax.random.PRNGKey(0))
    model, params = init_params(HEALTHY, batch)
    # All conditioning images identical: rolled == original, delta would be
    # 0 by construction — the probe must decline, not report a false alarm.
    same = dict(batch, x=jnp.broadcast_to(batch["x"][:1], batch["x"].shape))
    assert cond_sensitivity(model, params, same,
                            key=jax.random.PRNGKey(2)) is None
    # B=1: nothing to swap with.
    one = jax.tree.map(lambda a: a[:1], batch)
    assert cond_sensitivity(model, params, one,
                            key=jax.random.PRNGKey(2)) is None


def test_zero_output_returns_none():
    # A model whose output is identically zero (fresh zero-init head, or a
    # collapsed run) must NOT score the 0.0 alarm value — the ratio is
    # meaningless there, not evidence of inert conditioning.
    batch = make_eval_batch(jax.random.PRNGKey(0))
    model, params = init_params(HEALTHY, batch)
    assert cond_sensitivity(model, params, batch,
                            key=jax.random.PRNGKey(2)) is None


def test_cached_fn_matches_fresh():
    batch = make_eval_batch(jax.random.PRNGKey(0))
    model, params = init_params(HEALTHY, batch)
    params = perturb(params)
    fn = make_cond_sensitivity_fn(model)
    delta, scale = (float(v) for v in fn(params, jax.random.PRNGKey(2),
                                         batch))
    wrapped = cond_sensitivity(model, params, batch,
                               key=jax.random.PRNGKey(2))
    cached = cond_sensitivity(None, params, batch,
                              key=jax.random.PRNGKey(2), fn=fn)
    assert delta / scale == pytest.approx(wrapped)
    assert cached == pytest.approx(wrapped)


def test_log_eval_rotates_on_header_change(tmp_path):
    from novel_view_synthesis_3d_tpu.train.metrics import MetricsLogger

    logger = MetricsLogger(str(tmp_path))
    logger.log_eval(10, {"psnr": 9.7, "ssim": 0.5})
    # An upgraded build adds cond_sens: the old file must rotate aside
    # rather than appending misaligned rows under the stale header.
    logger.log_eval(20, {"psnr": 9.8, "ssim": 0.5, "cond_sens": 0.12})
    path = os.path.join(str(tmp_path), "eval.csv")
    with open(path) as fh:
        rows = list(csv.reader(fh))
    assert rows[0] == ["step", "cond_sens", "psnr", "ssim"]
    assert rows[1][0] == "20"
    assert os.path.exists(path + ".old")
    logger.close()


@pytest.mark.slow
def test_trainer_eval_logs_cond_sens(tmp_path):
    from novel_view_synthesis_3d_tpu.config import (
        Config, DataConfig, DiffusionConfig, TrainConfig)
    from novel_view_synthesis_3d_tpu.data.synthetic import write_synthetic_srn
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    root = tmp_path / "srn"
    write_synthetic_srn(str(root), num_instances=2, views_per_instance=4,
                        image_size=16)
    cfg = Config(
        model=ModelConfig(ch=32, ch_mult=(1,), num_res_blocks=1,
                          attn_resolutions=(16,)),
        diffusion=DiffusionConfig(timesteps=8, sample_timesteps=8),
        data=DataConfig(root_dir=str(root), img_sidelength=16,
                        loader="python", num_workers=0),
        train=TrainConfig(batch_size=8, num_steps=3, lr=1e-2,
                          save_every=0, log_every=1, eval_every=0,
                          eval_sample_steps=2,
                          checkpoint_dir=str(tmp_path / "ckpt"),
                          results_folder=str(tmp_path / "results")))
    tr = Trainer(config=cfg)
    # Fresh init: the zero-init output head makes the probe degenerate —
    # cond_sens must be NaN (stable eval.csv schema), not the 0.0 alarm.
    logged0 = tr.eval_step(0, num=4)
    assert logged0 is not None and np.isnan(logged0["cond_sens"])
    # After a few (high-lr) steps the output is non-degenerate and the
    # 16px-level attention makes the model genuinely conditioned.
    tr.train()
    logged = tr.eval_step(3, num=4)
    assert logged is not None and "cond_sens" in logged
    assert logged["cond_sens"] > 0.0
    with open(os.path.join(str(tmp_path / "results"), "eval.csv")) as fh:
        header = fh.readline().strip().split(",")
    assert "cond_sens" in header

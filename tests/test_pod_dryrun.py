"""Pod-shaped multichip dryruns (VERDICT r1 item 8).

The conftest pins this test process to an 8-device CPU mesh, so pod-scale
shapes run in subprocesses with their own XLA_FLAGS. Two shapes:

  - 32 devices as (data=8, model=2, seq=2): the generic dryrun_multichip
    composition (dp + fsdp + tp + sp together) at 4× the round-1 shape;
  - 64 devices as the pod64 preset's own mesh (data=64, fsdp, grad_accum=1,
    EMA) — the composition tested at the shape the preset claims to serve.
    Model dims are scaled down (the 256-ch paper model is infeasible on 64
    virtual CPU devices) but every sharding/flag path is the preset's own.

Subprocesses inherit the persistent compilation cache, so reruns are cheap.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(n_devices: int, code: str, timeout: int = 900) -> str:
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
    )
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        try:
            proc.communicate(timeout=10)  # reap; close pipes
        except subprocess.TimeoutExpired:
            pass  # uninterruptible child — abandon it
        pytest.fail(f"{n_devices}-device dryrun timed out")
    assert proc.returncode == 0, out
    return out


@pytest.mark.slow
def test_dryrun_32_devices():
    out = _run(32, "import __graft_entry__ as g; g.dryrun_multichip(32)")
    assert "dryrun_multichip(32): ok" in out
    assert "mesh=(8x2x2)" in out and "fsdp=True" in out


@pytest.mark.slow
def test_pod64_preset_shape_dryrun():
    code = """
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from novel_view_synthesis_3d_tpu.config import get_preset
from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
from novel_view_synthesis_3d_tpu.diffusion import make_schedule
from novel_view_synthesis_3d_tpu.models.xunet import XUNet
from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib
from novel_view_synthesis_3d_tpu.train.state import create_train_state
from novel_view_synthesis_3d_tpu.train.step import make_train_step
from novel_view_synthesis_3d_tpu.train.trainer import _sample_model_batch

cfg = get_preset("pod64").override(**{
    "model.ch": 32, "model.ch_mult": [1, 2], "model.emb_ch": 32,
    "model.num_res_blocks": 1, "model.attn_resolutions": [8],
    "model.remat": False, "data.img_sidelength": 16,
    "train.batch_size": 64,
})
assert cfg.train.fsdp and cfg.train.grad_accum_steps == 1
mesh = mesh_lib.make_mesh(cfg.mesh)
assert dict(mesh.shape)["data"] == 64, mesh.shape
batch = make_example_batch(batch_size=cfg.train.batch_size, sidelength=16)
model = XUNet(cfg.model)
state = create_train_state(cfg.train, model, _sample_model_batch(batch))
sh = mesh_lib.state_shardings(mesh, state, cfg.train.fsdp, tp=cfg.train.tp)
state = jax.device_put(state, sh)
step = make_train_step(cfg, model, make_schedule(cfg.diffusion), mesh,
                       state_sharding=sh)
state, metrics = step(state, mesh_lib.shard_batch(mesh, batch))
loss = float(jax.device_get(metrics["loss"]))
assert jnp.isfinite(loss) and int(jax.device_get(state.step)) == 1
print(f"pod64-shape ok loss={loss:.4f}")
"""
    out = _run(64, code)
    assert "pod64-shape ok" in out

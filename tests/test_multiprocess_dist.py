"""True multi-process distributed integration test.

test_multihost.py mocks process topology; this test actually SPAWNS two
JAX processes (4 virtual CPU devices each), wires them together with
`jax.distributed.initialize` via parallel.dist.initialize_distributed, and
runs the real jitted DP train step over the global 8-device mesh — per-host
local batches assembled with the `make_array_from_process_local_data` branch
of parallel.mesh.shard_batch, gradient all-reduce crossing the process
boundary over the distributed runtime. This is the closest a single machine
gets to the pod path (SURVEY.md §2.3 "TPU-native equivalents to build":
jax.distributed.initialize for multi-host pods).
"""

import os
import subprocess
import sys
import socket

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import sys
pid = int(sys.argv[1]); port = sys.argv[2]
import jax
jax.config.update("jax_platforms", "cpu")  # before any backend query
from novel_view_synthesis_3d_tpu.utils.xla_cache import (
    setup_compilation_cache)
setup_compilation_cache()

from novel_view_synthesis_3d_tpu.parallel.dist import (
    initialize_distributed, local_batch_size, process_shard)

initialize_distributed(coordinator_address=f"127.0.0.1:{port}",
                       num_processes=2, process_id=pid)
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, len(jax.devices())
assert local_batch_size(8) == 4
assert process_shard(8) == (pid, 2)

import numpy as np
import jax.numpy as jnp
from novel_view_synthesis_3d_tpu.config import (
    Config, DataConfig, DiffusionConfig, MeshConfig, ModelConfig, TrainConfig)
from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
from novel_view_synthesis_3d_tpu.diffusion import make_schedule
from novel_view_synthesis_3d_tpu.models.xunet import XUNet
from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib
from novel_view_synthesis_3d_tpu.train.state import create_train_state
from novel_view_synthesis_3d_tpu.train.step import make_train_step
from novel_view_synthesis_3d_tpu.train.trainer import _sample_model_batch

# Gloo context rendezvous discipline: every NEW communicator clique does a
# key-value rendezvous with a hard ~30s window (not configurable through
# jax.distributed.initialize — only coordinator timeouts are). Any stage
# where the two workers' wall-clock diverges by more than that (an XUNet
# compile under machine load) must therefore be followed by a barrier()
# BEFORE the next collective-creating call, so each fresh rendezvous starts
# with the workers in lock-step. The warm all-reduce both establishes the
# first context and doubles as that barrier (its program is cached after
# the first call).
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
import numpy as np  # noqa: E402

_warm_mesh = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(8), ("d",))
_warm_sum = jax.jit(lambda x: x.sum(),
                    out_shardings=NamedSharding(_warm_mesh, P()))

def barrier():
    w = jax.make_array_from_process_local_data(
        NamedSharding(_warm_mesh, P("d")), np.ones((4,), np.float32), (8,))
    total = float(jax.device_get(_warm_sum(w)))
    assert total == 8.0, total

barrier()

cfg = Config(
    model=ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                      attn_resolutions=(8,), dropout=0.0),
    diffusion=DiffusionConfig(timesteps=50),
    # 16px batches below: keep the config coherent (attn@8 = the real
    # bottleneck level) so Trainer's validate() passes in the probe stage.
    data=DataConfig(img_sidelength=16),
    train=TrainConfig(batch_size=8, lr=1e-3, ema_decay=0.0),
    mesh=MeshConfig(data=8, model=1, seq=1),
)
mesh = mesh_lib.make_mesh(cfg.mesh)

# The same global batch on every process; each host contributes its local
# rows (rows [4*pid, 4*pid+4) of the global batch).
global_batch = make_example_batch(batch_size=8, sidelength=16, seed=0)
local = {k: v[4 * pid:4 * pid + 4] for k, v in global_batch.items()}

model = XUNet(cfg.model)
state = create_train_state(cfg.train, model, _sample_model_batch(global_batch))
barrier()  # init compile stagger ends here; replicate() rendezvouses fresh
state = mesh_lib.replicate(mesh, state)
step = make_train_step(cfg, model, make_schedule(cfg.diffusion), mesh)

device_batch = mesh_lib.shard_batch(mesh, local)
# AOT-compile the step so the heavy (possibly asymmetric-duration) compile
# finishes BEFORE the execution that creates its communicators; the barrier
# then bounds the rendezvous stagger to microseconds.
compiled_step = step.lower(state, device_batch).compile()
barrier()
losses = []
for _ in range(3):
    state, m = compiled_step(state, device_batch)
    losses.append(float(jax.device_get(m["loss"])))
assert np.isfinite(losses).all(), losses
# Params must remain identical across processes: compare a checksum via a
# replicated-mean reduction (any divergence would differ per process).
def tree_checksum(tree):
    return float(jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda x: float(np.sum(np.abs(x))), tree)))

checksum = tree_checksum(jax.device_get(state.params))
print(f"RESULT {pid} losses={losses} checksum={checksum:.6f}", flush=True)

# --- pod-safe in-loop probe (trainer._probe_host_params path) ---
# Every host joins the replication collective; only process 0 samples.
# Build a minimal Trainer around a synthetic iterator on this topology.
import itertools, tempfile
from novel_view_synthesis_3d_tpu.train.trainer import Trainer

tdir = tempfile.mkdtemp(prefix=f"probe{pid}_")
probe_cfg = cfg.override(**{
    "diffusion.sample_timesteps": 2, "train.eval_sample_steps": 2,
    "train.num_steps": 1, "train.save_every": 0, "train.log_every": 1,
    "train.eval_every": 0, "train.sample_every": 0,
    # FSDP so the probe's replicate() is a REAL cross-process all-gather
    # of non-fully-addressable shards, not a no-op reshard.
    "train.fsdp": True,
    "train.results_folder": tdir, "train.checkpoint_dir": tdir + "/ck",
    "train.handle_preemption": False, "train.resume": False,
})
local_iter = itertools.repeat(local)
barrier()
trainer = Trainer(config=probe_cfg, data_iter=local_iter)
barrier()  # trainer setup (init compile) staggers; resync before probing
out_eval = trainer.eval_step(0)
path = trainer.dump_samples(0, num=2, sample_steps=2)
if pid == 0:
    assert out_eval is not None and np.isfinite(out_eval["psnr"])
    assert path is not None and __import__("os").path.exists(path)
else:
    assert out_eval is None and path is None
print(f"PROBE {pid} ok={out_eval}", flush=True)

# --- host-EMA on a pod (trainer._host_params replicate path) ---
# Every host joins the replication collective inside the EMA fold; the
# folded host buffer must be IDENTICAL across processes (it ships in the
# checkpoint, so divergence would corrupt saves).
ema_cfg = probe_cfg.override(**{
    "train.ema_decay": 0.5, "train.ema_host": True,
    "train.ema_host_every": 1,
    "train.results_folder": tdir + "/ema",
    "train.checkpoint_dir": tdir + "/ckema",
})
barrier()
tr2 = Trainer(config=ema_cfg, data_iter=itertools.repeat(local))
assert tr2._host_ema_pending  # __init__ made NO collective (seed deferred)
barrier()  # init compile stagger ends; the seed pull rendezvouses fresh
tr2._maybe_update_host_ema(1, force=True)
assert tr2._host_ema_step == 1 and not tr2._host_ema_pending
print(f"EMA {pid} checksum={tree_checksum(tr2._host_ema):.8f}", flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_train_step(tmp_path):
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(WORKER)
    port = _free_port()
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    procs = [
        subprocess.Popen([sys.executable, str(worker_py), str(i), str(port)],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True, env=env)
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-4000:]}"
    results = {}
    emas = {}
    for out in outs:
        line = [ln for ln in out.splitlines() if ln.startswith("RESULT")][0]
        pid = int(line.split()[1])
        results[pid] = line.split(" ", 2)[2]
        ema = [ln for ln in out.splitlines() if ln.startswith("EMA")][0]
        emas[int(ema.split()[1])] = ema.split(" ", 2)[2]
    # Both processes computed the same global step: identical losses and
    # identical post-step parameter checksums.
    assert results[0] == results[1], results
    # Host-EMA fold is process-consistent (FSDP shards -> replicate ->
    # identical fold on every host).
    assert emas[0] == emas[1], emas

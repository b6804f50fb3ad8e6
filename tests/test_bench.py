"""bench.py surface tests (the driver runs bench.py on real hardware; these
pin the config plumbing and the analyze subcommand on the CPU mesh)."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.smoke

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = ["model.ch=32", "model.ch_mult=[1,2]", "model.emb_ch=32",
        "model.num_res_blocks=1", "model.attn_resolutions=[8]",
        "data.img_sidelength=16", "train.batch_size=8",
        "diffusion.timesteps=8", "diffusion.sample_timesteps=8"]


@pytest.mark.slow
def test_bench_analyze_emits_roofline_json():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"),
         "analyze", "tiny64"] + TINY,
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO_ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    result = json.loads(line)
    assert result["metric"] == "analyze_tiny64"
    assert result["flops_per_step"] > 0
    assert result["bytes_accessed_per_step"] > 0
    assert result["arithmetic_intensity_flop_per_byte"] > 0
    assert result["batch_size"] == 8


def test_bench_effective_accum_reexported():
    # bench.build honors mesh.model×mesh.seq claims; quick import check of
    # the pieces bench.py wires together.
    sys.path.insert(0, REPO_ROOT)
    import bench
    assert callable(bench.build)
    assert callable(bench.bench_analyze)


def test_bench_data_python_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"),
         "data", "python", "3"],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO_ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(
        [l for l in out.stdout.splitlines() if l.startswith("{")][-1])
    assert result["metric"] == "data_imgs_per_sec_python"
    assert result["value"] > 0


def test_bench_does_not_downgrade_to_cpu():
    """Device-or-fail: a bench whose platform does not answer exits 3
    with a one-line reason and prints NO number — it never re-pins to the
    CPU and carries on under another label."""
    env = dict(os.environ,
               # A platform name no host provides: backend init fails
               # everywhere, including real TPU VMs (JAX_PLATFORMS="tpu"
               # there would run a REAL device bench and fail the test).
               JAX_PLATFORMS="nonexistent_backend")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py"),
         "tiny64", "1"] + TINY,
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO_ROOT)
    assert out.returncode == 3, (out.returncode, out.stderr[-2000:])
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]
    assert "error:" in out.stderr and "nonexistent_backend" in out.stderr

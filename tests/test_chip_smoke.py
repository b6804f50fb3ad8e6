"""chip_smoke.py rehearsed on the CPU: its phases reach the end at tiny
overrides, its four-chip path runs on four virtual devices, and in no
case does a CPU run exit 0 or print `"ok": true`.

Each run is a process of its own: the script asks JAX for its platform
and device count at start-up.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Compile in seconds; widths are NOT base128's — which is why the script
# calls such a run a rehearsal and refuses to pass it.
TINY = ["model.ch=32", "model.ch_mult=[1,2]", "model.emb_ch=32",
        "model.num_res_blocks=1", "model.attn_resolutions=[8]",
        "diffusion.timesteps=8", "diffusion.sample_timesteps=4",
        "data.img_sidelength=16", "train.batch_size=8"]


def _run(args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={devices}"))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")] + args,
        cwd=REPO, env=env, capture_output=True, text=True, timeout=780)
    return proc.returncode, proc.stdout, proc.stderr


def _json_lines(stdout):
    return [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith('{"')]


def test_no_accelerator_no_result():
    """As the driver runs it, on a machine without a chip: exit 3 before
    anything is built, and no result line at all."""
    rc, stdout, stderr = _run([])
    assert rc == 3, stderr[-2000:]
    assert stdout.strip() == ""
    assert "needs a TPU" in stderr


def test_phases_reach_the_end_and_still_fail_on_cpu():
    rc, stdout, stderr = _run(TINY)
    assert rc == 4, stderr[-3000:]
    assert '"ok": true' not in stdout
    lines = _json_lines(stdout)
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert list(phases) == ["environment", "kernels_vs_twins",
                            "kernel_evidence", "train", "sample", "serve"]
    assert phases["environment"]["compile_cache_dir"]
    assert len(phases["kernels_vs_twins"]["max_abs_err"]) == 10
    assert len(phases["train"]["losses"]) == 5
    assert phases["train"]["checkpoint_steps"] == [5]
    assert phases["sample"]["guidance_weight"] > 0
    assert phases["serve"]["programs_built"] == 2
    assert phases["serve"]["served"] == 4
    for name in ("train", "sample", "serve"):
        assert phases[name]["compile_s"] > 0
        assert phases[name]["steady_s"] > 0
    # The contract's last line, and nothing else in it.
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert stdout.rstrip().splitlines()[-1] == json.dumps(lines[-1])


def test_four_chip_path_runs_only_the_dp_check():
    rc, stdout, stderr = _run(["--chips", "4"] + TINY, devices=4)
    assert rc == 4, stderr[-3000:]
    assert '"ok": true' not in stdout
    lines = _json_lines(stdout)
    assert [ln.get("phase") for ln in lines[:-1]] == [
        "environment", "data_parallel"]
    dp = lines[1]
    assert dp["dp"]["shard_devices"] == [0, 1, 2, 3]
    assert dp["one_device"]["shard_devices"] == [0]
    assert len(dp["dp"]["metrics"]) == 2
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}

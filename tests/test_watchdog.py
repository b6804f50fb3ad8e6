"""Hang-and-stall robustness drills (utils/watchdog.py,
train/supervisor.py) — tier-1, CPU, deterministic.

Every stall-shaped recovery path is driven by an injected hang
(utils/faultinject.py NVS3D_FI_STALL_*_AT):

  data stall   → watchdog fires, diagnosis bundle, checkpoint-and-exit
  step stall   → cross-host-agreed checkpoint-and-exit, resumable
  save stall   → degrade with diagnosis; the run still completes
  supervised   → crash/stall child restarted with backoff, resumes from
                 the last intact checkpoint, bounded by max_restarts
"""

import json
import os
import subprocess
import sys
import time

import pytest

from novel_view_synthesis_3d_tpu.config import (
    Config, DataConfig, DiffusionConfig, MeshConfig, ModelConfig,
    TrainConfig, WatchdogConfig,
)
from novel_view_synthesis_3d_tpu.data.synthetic import write_synthetic_srn
from novel_view_synthesis_3d_tpu.train import supervisor
from novel_view_synthesis_3d_tpu.utils import faultinject, watchdog

pytestmark = [pytest.mark.faultinject, pytest.mark.smoke]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Watchdog unit behavior (no trainer, no sleeping through real budgets)
# ---------------------------------------------------------------------------
def test_phase_within_budget_never_fires(tmp_path):
    fired = []
    wd = watchdog.Watchdog({"train_step_s": 60.0}, fired.append,
                           check_interval_s=0.01,
                           diagnosis_dir=str(tmp_path), query_device=False)
    with wd:
        with wd.phase("train_step"):
            time.sleep(0.05)
    assert not fired and wd.stall_count == 0


def test_expired_phase_fires_once_with_diagnosis(tmp_path):
    events = []
    clock = {"t": 0.0}
    wd = watchdog.Watchdog(
        {"train_step_s": 10.0},
        lambda phase, path: events.append((phase, path)),
        diagnosis_dir=str(tmp_path), query_device=False,
        _clock=lambda: clock["t"])
    wd.beat("data_fetch")
    wd._enter("train_step")
    clock["t"] = 5.0
    assert wd.check() is None  # under budget
    clock["t"] = 11.0
    assert wd.check() == "train_step"
    assert wd.check() is None  # one stall per phase entry, not per poll
    assert [p for p, _ in events] == ["train_step"]
    bundle = open(events[0][1]).read()
    # The bundle carries what a postmortem needs: the blown budget, every
    # heartbeat's age, and all-thread stacks.
    assert "phase 'train_step'" in bundle and "budget 10.0s" in bundle
    assert "data_fetch: 11.0" in bundle
    assert "all-thread stacks" in bundle and "test_watchdog" in bundle
    # Re-arming the phase resets the one-shot: a NEW entry can stall again.
    wd._exit("train_step")
    wd._enter("train_step")
    clock["t"] = 30.0
    assert wd.check() == "train_step"
    assert wd.stall_count == 2


def test_zero_budget_disables_phase(tmp_path):
    wd = watchdog.Watchdog({"eval_s": 0.0}, diagnosis_dir=str(tmp_path),
                           query_device=False, _clock=lambda: 0.0)
    wd._enter("eval")
    wd._clock = lambda: 1e9
    assert wd.check(now=1e9) is None and wd.stall_count == 0


def test_from_config_budget_mapping(tmp_path):
    wcfg = WatchdogConfig(step_s=1.5, data_fetch_s=2.5, compile_s=3.5,
                          checkpoint_save_s=4.5, eval_s=5.5)
    wd = watchdog.from_config(wcfg, diagnosis_dir=str(tmp_path))
    assert wd.budgets == {"train_step_s": 1.5, "data_fetch_s": 2.5,
                          "compile_s": 3.5, "checkpoint_save_s": 4.5,
                          "eval_s": 5.5}
    assert isinstance(watchdog.from_config(WatchdogConfig(enabled=False)),
                      watchdog.NullWatchdog)


def test_null_watchdog_surface():
    wd = watchdog.NullWatchdog()
    with wd.phase("train_step"):
        pass
    wd.beat("x")
    assert wd.start() is wd and wd.check() is None
    wd.stop()


def test_hard_exit_kills_a_truly_wedged_process(tmp_path):
    # The monitor thread must end a process whose main thread never comes
    # back (the uninterruptible-IO case): run one in a subprocess
    # and assert it dies with EXIT_STALL, fast, with the bundle on stderr.
    code = (
        "import time\n"
        "from novel_view_synthesis_3d_tpu.utils import watchdog\n"
        "wd = watchdog.Watchdog({'train_step_s': 0.2}, hard_exit_s=0.2,\n"
        "                       check_interval_s=0.05,\n"
        f"                      diagnosis_dir={str(tmp_path)!r},\n"
        "                       query_device=False).start()\n"
        "with wd.phase('train_step'):\n"
        "    time.sleep(600)\n"
    )
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == watchdog.EXIT_STALL
    assert time.monotonic() - t0 < 60
    assert "hard-exiting" in proc.stderr
    assert "all-thread stacks" in proc.stderr


# ---------------------------------------------------------------------------
# Fault-injection stall spec parsing
# ---------------------------------------------------------------------------
def test_stall_spec_parsing(monkeypatch):
    assert faultinject.stall_spec("step") is None
    monkeypatch.setenv("NVS3D_FI_STALL_STEP_AT", "7")
    assert faultinject.stall_spec("step") == (7, 30.0)
    monkeypatch.setenv("NVS3D_FI_STALL_STEP_AT", "7:1.25")
    assert faultinject.stall_spec("step") == (7, 1.25)
    monkeypatch.setenv("NVS3D_FI_STALL_STEP_AT", "bogus")
    with pytest.raises(ValueError):
        faultinject.stall_spec("step")
    monkeypatch.setenv("NVS3D_FI_STALL_DATA_AT", "2:0.5")
    assert "NVS3D_FI_STALL_DATA_AT" in faultinject.armed()
    # Exact-step match only; elsewhere the hook is inert and free.
    assert faultinject.maybe_stall("data", 1) == 0.0
    t0 = time.monotonic()
    assert faultinject.maybe_stall("data", 2) == 0.5
    assert time.monotonic() - t0 >= 0.5


# ---------------------------------------------------------------------------
# Trainer drills: the three stall shapes, end to end on CPU
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def srn_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("srn_wd")
    write_synthetic_srn(str(root), num_instances=2, views_per_instance=4,
                        image_size=16)
    return str(root)


def _cfg(srn_root, tmp, *, wd=None, **train_kw):
    kw = dict(batch_size=8, lr=1e-3, num_steps=8, save_every=2, log_every=1,
              seed=0, resume=True,
              checkpoint_dir=os.path.join(str(tmp), "ckpt"),
              results_folder=os.path.join(str(tmp), "results"),
              watchdog=wd or WatchdogConfig(check_interval_s=0.1))
    kw.update(train_kw)
    return Config(
        model=ModelConfig(ch=32, ch_mult=(1,), num_res_blocks=1,
                          attn_resolutions=(), dropout=0.0),
        diffusion=DiffusionConfig(timesteps=8, sample_timesteps=4),
        data=DataConfig(root_dir=srn_root, img_sidelength=16, num_workers=0),
        train=TrainConfig(**kw),
        mesh=MeshConfig(data=-1),
    ).validate()


def _events(tmp):
    path = os.path.join(str(tmp), "results", "events.csv")
    if not os.path.exists(path):
        return []
    with open(path) as fh:
        return fh.read().strip().splitlines()[1:]


def _stall_files(tmp, phase):
    res = os.path.join(str(tmp), "results")
    return [f for f in os.listdir(res) if f.startswith(f"stall_{phase}_")]


def test_step_stall_checkpoints_and_exits(srn_root, tmp_path, monkeypatch):
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    # Budgets sized for a contended host (machine-speed independence): the
    # injected sleep is 3× the budget, the budget is ~10× a tiny-model CPU
    # step, so only the injected hang can plausibly blow it.
    monkeypatch.setenv("NVS3D_FI_STALL_STEP_AT", "3:6")
    cfg = _cfg(srn_root, tmp_path,
               wd=WatchdogConfig(step_s=2.0, check_interval_s=0.25))
    tr = Trainer(config=cfg, use_grain=False)
    tr.train()
    # Exited at the stalled step, not at num_steps — and checkpointed
    # there, so a restart resumes instead of replaying from scratch.
    assert tr.stalled and tr.step == 3
    tr.ckpt.wait()
    assert tr.ckpt.latest_step() == 3
    assert any(",stall," in ln and "train_step" in ln
               for ln in _events(tmp_path))
    assert _stall_files(tmp_path, "train_step")
    tr.ckpt.close()

    # The resumed run (stall env cleared) completes from the checkpoint.
    monkeypatch.delenv("NVS3D_FI_STALL_STEP_AT")
    tr2 = Trainer(config=cfg, use_grain=False)
    assert tr2.step == 3
    tr2.train()
    assert tr2.step == 8 and not tr2.stalled
    tr2.ckpt.close()


def test_data_stall_fires_watchdog_and_exits(srn_root, tmp_path,
                                             monkeypatch):
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    # A mid-run host batch fetch the loop cannot ride out, whatever the
    # machine's speed: at `data.prefetch` 1 the producer reaches fetch
    # ordinal 4 only once the loop has taken batch 2 (the cold start is
    # over), and one buffered batch is all the loop has before it waits.
    # With the default buffer of 4 and the stall at ordinal 2, a host
    # with slow compiles and saves spent the whole 6 s on buffered
    # batches and the drill never fired (PR 29's sandbox, parent too).
    monkeypatch.setenv("NVS3D_FI_STALL_DATA_AT", "4:12")
    cfg = _cfg(srn_root, tmp_path,
               wd=WatchdogConfig(data_fetch_s=2.0, check_interval_s=0.25))
    cfg = cfg.override(**{"data.prefetch": 1})
    tr = Trainer(config=cfg, use_grain=False)
    tr.train()
    assert tr.stalled and 0 < tr.step < 8
    assert any(",stall," in ln and "data_fetch" in ln
               for ln in _events(tmp_path))
    assert _stall_files(tmp_path, "data_fetch")
    tr.ckpt.close()


def test_save_stall_degrades_and_run_completes(srn_root, tmp_path,
                                               monkeypatch):
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    monkeypatch.setenv("NVS3D_FI_STALL_SAVE_AT", "4:6")
    cfg = _cfg(srn_root, tmp_path,
               wd=WatchdogConfig(checkpoint_save_s=2.0,
                                 check_interval_s=0.25))
    tr = Trainer(config=cfg, use_grain=False)
    tr.train()
    # Degrade, not exit: a save that is itself stuck must not trigger an
    # exit path that ends in another save. Diagnosis still lands.
    assert not tr.stalled and tr.step == 8
    stall_lines = [ln for ln in _events(tmp_path) if ",stall," in ln]
    assert stall_lines and all("checkpoint_save" in ln for ln in stall_lines)
    assert any("degrading" in ln for ln in stall_lines)
    assert _stall_files(tmp_path, "checkpoint_save")
    tr.ckpt.close()


def test_clean_run_records_no_stall(srn_root, tmp_path):
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    cfg = _cfg(srn_root, tmp_path)  # production-shaped budgets
    tr = Trainer(config=cfg, use_grain=False)
    tr.train()
    assert tr.step == 8 and not tr.stalled
    assert not any(",stall," in ln for ln in _events(tmp_path))
    assert tr.watchdog.stall_count == 0
    tr.ckpt.close()


# ---------------------------------------------------------------------------
# Supervisor: restart on crash/stall, bounded, resumes from checkpoint
# ---------------------------------------------------------------------------
def _script(tmp_path, name, body):
    path = tmp_path / name
    path.write_text(body)
    return [sys.executable, str(path)]


def test_supervisor_clean_child_no_restart(tmp_path):
    rc = supervisor.supervise(
        _script(tmp_path, "ok.py", "print('fine')\n"),
        results_folder=str(tmp_path / "res"), max_restarts=3,
        backoff_s=0.01)
    assert rc == 0
    # A clean first run leaves no supervisor events at all.
    assert not os.path.exists(tmp_path / "res" / "events.csv")


def test_supervisor_restarts_crash_then_completes(tmp_path):
    # Child crashes until its scratch file has 2 lines — two restarts.
    marker = tmp_path / "attempts.txt"
    body = (
        "import os, sys\n"
        f"p = {str(marker)!r}\n"
        "n = len(open(p).readlines()) if os.path.exists(p) else 0\n"
        "open(p, 'a').write(f'{n}\\n')\n"
        "print('gen', os.environ['NVS3D_SUPERVISED_RESTARTS'])\n"
        "sys.exit(0 if n >= 2 else 1)\n")
    rc = supervisor.supervise(
        _script(tmp_path, "flaky.py", body),
        results_folder=str(tmp_path / "res"), max_restarts=3,
        backoff_s=0.01)
    assert rc == 0
    events = open(tmp_path / "res" / "events.csv").read()
    assert events.count("supervised_restart") == 2
    assert "crash rc=1" in events
    assert "supervised_complete" in events


def test_supervisor_restart_budget_exhausted(tmp_path):
    rc = supervisor.supervise(
        _script(tmp_path, "boom.py", "import sys; sys.exit(9)\n"),
        results_folder=str(tmp_path / "res"), max_restarts=2,
        backoff_s=0.01)
    assert rc == 9
    events = open(tmp_path / "res" / "events.csv").read()
    assert events.count("supervised_restart") == 2
    assert "supervised_giveup" in events


def test_supervisor_child_timeout_counts_as_stall(tmp_path):
    # The supervisor's own last-resort guard: a child that hangs with its
    # in-process watchdog dead is killed and restarted.
    marker = tmp_path / "ran.txt"
    body = (
        "import os, time\n"
        f"p = {str(marker)!r}\n"
        "if os.path.exists(p):\n"
        "    raise SystemExit(0)\n"
        "open(p, 'w').write('x')\n"
        "time.sleep(600)\n")
    rc = supervisor.supervise(
        _script(tmp_path, "hang.py", body),
        results_folder=str(tmp_path / "res"), max_restarts=2,
        backoff_s=0.01, child_timeout_s=2.0)
    assert rc == 0
    events = open(tmp_path / "res" / "events.csv").read()
    assert "supervised_timeout" in events
    assert "stall; restart 1/2" in events


def test_supervised_trainer_stall_restart_resumes_and_completes(
        srn_root, tmp_path):
    # THE acceptance drill: a real training child stalls (injected hang),
    # its watchdog checkpoints-and-exits with EXIT_STALL, the supervisor
    # restarts it, and the restarted child resumes from the last intact
    # checkpoint and completes — all within train.max_restarts.
    res = os.path.join(str(tmp_path), "results")
    overrides = [
        "model.ch=32", "model.ch_mult=[1]", "model.num_res_blocks=1",
        "model.attn_resolutions=[]", "model.dropout=0.0",
        "diffusion.timesteps=8", "diffusion.sample_timesteps=4",
        f"data.root_dir={srn_root}", "data.img_sidelength=16",
        "data.num_workers=0", "train.batch_size=8", "train.num_steps=6",
        "train.save_every=2", "train.log_every=1",
        f"train.results_folder={res}",
        "train.checkpoint_dir=" + os.path.join(str(tmp_path), "ckpt"),
        "train.watchdog.step_s=2.0", "train.watchdog.check_interval_s=0.25",
    ]
    argv = [sys.executable, "-m", "novel_view_synthesis_3d_tpu", "train",
            "--no-grain"] + overrides
    env = dict(os.environ, NVS3D_FI_STALL_STEP_AT="2:6",
               JAX_PLATFORMS="cpu")
    rc = supervisor.supervise(argv, results_folder=res, max_restarts=2,
                              backoff_s=0.05, env=env)
    assert rc == 0
    events = open(os.path.join(res, "events.csv")).read()
    assert "stall" in events  # the child's watchdog row
    assert events.count("supervised_restart") == 1
    assert "supervised_resume" in events  # gen-1 child resumed from ckpt
    assert "supervised_complete" in events
    # metrics.csv carries the restart generation next to the loss curve,
    # and the resumed rows continue PAST the stall step (no replay from 0).
    with open(os.path.join(res, "metrics.csv")) as fh:
        lines = fh.read().strip().splitlines()
    header = lines[0].split(",")
    rows = [dict(zip(header, ln.split(","))) for ln in lines[1:]]
    assert max(int(r["restarts"]) for r in rows) == 1
    gen1 = [int(r["step"]) for r in rows if int(r["restarts"]) == 1]
    assert gen1 and min(gen1) > 1 and max(gen1) == 6


# ---------------------------------------------------------------------------
# Config plumbing
# ---------------------------------------------------------------------------
def test_watchdog_config_validation():
    import dataclasses

    base = Config()
    for bad in (dict(check_interval_s=0.0), dict(step_s=-1.0),
                dict(hard_exit_s=-0.1)):
        cfg = dataclasses.replace(
            base, train=dataclasses.replace(
                base.train, watchdog=WatchdogConfig(**bad)))
        with pytest.raises(ValueError):
            cfg.validate()
    with pytest.raises(ValueError, match="max_restarts"):
        dataclasses.replace(
            base, train=dataclasses.replace(
                base.train, max_restarts=-1)).validate()


def test_watchdog_config_dotted_override_roundtrip():
    cfg = Config().apply_cli(["train.watchdog.step_s=12.5",
                              "train.watchdog.enabled=False",
                              "train.max_restarts=7"]).validate()
    assert cfg.train.watchdog.step_s == 12.5
    assert cfg.train.watchdog.enabled is False
    assert cfg.train.max_restarts == 7
    back = Config.from_json(cfg.to_json())
    assert isinstance(back.train.watchdog, WatchdogConfig)
    assert back.train.watchdog.step_s == 12.5

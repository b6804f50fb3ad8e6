"""Device-or-fail, one process per chip, and the compile cache's place.

  - the platform that answers is the one that was asked for, in THIS
    process (parallel/dist.answered_platform): cli verbs and bench.py exit
    3 with a one-line reason otherwise, and nothing re-pins to the CPU;
  - an accelerator `device_kind` missing from the one peak table raises;
  - launchers leave the chip to their children: `train --supervise`
    spawns before it touches JAX, the fleet launcher hands each replica
    one chip through its environment and refuses more replicas than chips;
  - `utils/xla_cache.setup_compilation_cache` is the only writer of
    `jax_compilation_cache_dir`: JAX_COMPILATION_CACHE_DIR wins, the
    default is inside the checkout.
"""

import ast
import os
import subprocess
import sys
import types

import jax
import pytest

from novel_view_synthesis_3d_tpu.obs import devmon
from novel_view_synthesis_3d_tpu.parallel import dist
from novel_view_synthesis_3d_tpu.serve import fleet_supervisor
from novel_view_synthesis_3d_tpu.utils import xla_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the platform asked for answers, or the entry point exits 3
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("asked,reason", [
    ("cpu", None),                       # the explicit CPU lane
    ("cpu,tpu", None),                   # first entry is what was asked for
    ("tpu", "'tpu' was asked for and 'cpu' answered"),
    ("tpu,cpu", "'tpu' was asked for and 'cpu' answered"),
    ("", "fell back to the CPU by itself"),
])
def test_answered_platform_is_the_one_asked_for(asked, reason, capsys):
    # The backend that answers stays the suite's CPU (it is already up);
    # only the request moves.
    was = jax.config.jax_platforms
    jax.config.update("jax_platforms", asked)
    try:
        if reason is None:
            assert dist.answered_platform() == "cpu"
            return
        with pytest.raises(RuntimeError, match=reason):
            dist.answered_platform()
        with pytest.raises(SystemExit) as exc:
            dist.require_platform()
    finally:
        jax.config.update("jax_platforms", was)
    assert exc.value.code == dist.EXIT_BACKEND_UNREACHABLE == 3
    assert capsys.readouterr().err.startswith("error: ")


def test_cli_train_unmet_platform_exits_3(tmp_path):
    """`nvs3d train` asking for a platform that cannot initialise here is
    a structured exit in seconds — in-process, no probe child, no hang."""
    proc = subprocess.run(
        [sys.executable, "-m", "novel_view_synthesis_3d_tpu", "train",
         "--no-grain"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="nonexistent_backend"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 3, proc.stderr[-2000:]
    errors = [ln for ln in proc.stderr.splitlines()
              if ln.startswith("error: ")]
    assert len(errors) == 1 and "nonexistent_backend" in errors[0]


def test_unknown_accelerator_kind_raises():
    cpu = jax.devices()[0]
    assert devmon.device_peak_flops(cpu) is None
    assert devmon.device_peak_bytes_per_s(cpu) is None
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert devmon.device_peak_flops(v5e) == 197e12
    assert devmon.device_peak_bytes_per_s(v5e) == 819e9
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v9x")
    with pytest.raises(KeyError, match="TPU v9x"):
        devmon.device_peak_flops(unknown)
    with pytest.raises(KeyError, match="peak table"):
        devmon.device_peak_bytes_per_s(unknown)


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------
def test_train_supervise_spawns_before_touching_jax():
    """The supervising parent reaches its spawn point with no JAX backend
    initialised — under a platform request that would fail if it tried."""
    code = (
        "import sys\n"
        "import novel_view_synthesis_3d_tpu.train.supervisor as s\n"
        "from jax._src import xla_bridge\n"
        "def fake(argv, **kw):\n"
        "    print('BACKENDS', sorted(xla_bridge._backends)); return 0\n"
        "s.supervise = fake\n"
        "from novel_view_synthesis_3d_tpu.cli import main\n"
        "sys.exit(main(['train', '--supervise', '--preset', 'base128']))\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="nonexistent_backend"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "BACKENDS []" in proc.stdout


def test_assign_chips_cpu_lane_is_unchanged():
    env = {"JAX_PLATFORMS": "cpu"}
    assert fleet_supervisor.assign_chips(4, chips=0, environ=env) == [{}] * 4


def test_assign_chips_one_chip_per_replica():
    overlays = fleet_supervisor.assign_chips(
        4, chips=4, environ={"JAX_PLATFORMS": "tpu,cpu"})
    assert [o["TPU_VISIBLE_CHIPS"] for o in overlays] == list("0123")
    assert len({o["TPU_PROCESS_PORT"] for o in overlays}) == 4
    assert all(o["TPU_PROCESS_BOUNDS"] == "1,1,1" for o in overlays)


@pytest.mark.parametrize("platforms", ["tpu,cpu", ""])
def test_assign_chips_refuses_more_replicas_than_chips(platforms):
    with pytest.raises(RuntimeError, match="2 replica processes .* 1 chip"):
        fleet_supervisor.assign_chips(
            2, chips=1, environ={"JAX_PLATFORMS": platforms})


def test_default_spawn_applies_the_spec_env(tmp_path, monkeypatch):
    spec = tmp_path / "r0.spec.json"
    spec.write_text('{"name": "r0", "env": {"TPU_VISIBLE_CHIPS": 2}}')
    env = fleet_supervisor.spec_env(str(spec))
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    assert env["PATH"] == os.environ["PATH"]
    spec.write_text('{"name": "r0"}')
    assert fleet_supervisor.spec_env(str(spec)) == dict(os.environ)


def test_fleet_launcher_refuses_in_seconds_without_a_backend():
    """serve_bench --fleet asking for two replica processes where the
    platform asked for is an accelerator and the host shows fewer chips:
    a loud non-zero exit before anything is built or spawned. The
    launcher's platform cannot initialise here, so reaching the refusal
    also shows it brought up no backend of its own first."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--fleet", "--fleet-replicas",
         str(fleet_supervisor.host_chips() + 1)],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="nonexistent_backend"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None), proc.stdout[-2000:]
    assert "replica processes asked for on a host with" in proc.stderr
    assert not proc.stdout.strip()


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------
@pytest.fixture()
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_env_wins(tmp_path, monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    got = xla_cache.setup_compilation_cache(
        default_dir=str(tmp_path / "spec_default"))
    assert got == str(tmp_path / "c") and os.path.isdir(got)
    assert jax.config.jax_compilation_cache_dir == got
    assert not (tmp_path / "spec_default").exists()
    monkeypatch.setenv("NVS3D_NO_COMPILE_CACHE", "1")
    assert xla_cache.setup_compilation_cache() is None


def test_cache_default_is_inside_the_checkout(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert xla_cache.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert xla_cache.setup_compilation_cache() == xla_cache.DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def _python_sources():
    roots = [os.path.join(REPO, "novel_view_synthesis_3d_tpu"),
             os.path.join(REPO, "tools")]
    for root in roots:
        for dirpath, _, names in os.walk(root):
            for name in names:
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)
    for name in ("bench.py", "chip_smoke.py", "__graft_entry__.py",
                 os.path.join("tests", "conftest.py")):
        yield os.path.join(REPO, name)


def test_one_writer_of_the_cache_dir():
    """No file but utils/xla_cache.py names the option to set it: not as
    a config.update key, not as an attribute store."""
    writers = set()
    for path in _python_sources():
        with open(path) as fh:
            tree = ast.parse(fh.read(), path)
        for node in ast.walk(tree):
            names_it = (
                (isinstance(node, ast.Constant)
                 and node.value == "jax_compilation_cache_dir")
                or (isinstance(node, ast.Attribute)
                    and node.attr == "jax_compilation_cache_dir"
                    and isinstance(node.ctx, ast.Store)))
            if names_it:
                writers.add(os.path.relpath(path, REPO))
    assert writers == {os.path.join(
        "novel_view_synthesis_3d_tpu", "utils", "xla_cache.py")}

"""Self-healing fleet: supervised replica resurrection.

`FleetSupervisor` owns the replica PROCESSES the way train/supervisor.py
owns the training process (the PR 2 discipline): detect death, restart
into the SAME spec with bounded exponential backoff, give up loudly when
the budget is spent. Three detectors, cheapest first:

  1. process exit — ``proc.poll()`` is not None (SIGKILL, OOM, crash);
  2. stale heartbeat — the replica's ready-file mtime (touched every
     ``heartbeat_s`` by serve/replica_main.py) is older than
     ``supervisor_heartbeat_max_age_s``: the process is alive but its
     event loop is wedged. A stat, no HTTP round-trip to a hung server;
  3. consecutive /healthz failures — ``supervisor_health_fails`` probe
     errors in a row (half-dead network path, wedged HTTP thread pool).

Resurrection respawns ``python -m …serve.replica_main <spec.json>`` with
the same spec file, which pins the SAME port (``adopt`` rewrites the
spec with the concrete port from the first ready file) — so the
replica's URL never changes and the router readmits it through its
natural health poll, no router-side registration dance. Before the
``replica_resurrect`` event fires, the supervisor verifies the new
process is READY (ready-file pid matches the spawn) and HEALTHY
(/healthz status ok) and serving the EXPECTED model version (the
registry channel head when the spec names a registry, else the version
the dead incarnation last reported): a resurrected replica that came
back wrong is killed and the attempt counts against the budget.

Backoff: ``min(cap, backoff_s * 2**(restarts-1))`` per slot. Budget
exhaustion (``supervisor_max_restarts``) marks the slot FAILED loudly
(``replica_giveup`` event + stderr) and stops touching it — a
crash-looping spec needs a human, not a hotter loop.

Everything external is injectable (spawn, probe, heartbeat age, clock,
sleep) so tier-1 tests drill every detector with fakes; the defaults
drive real subprocesses for serve_bench --fleet's chaos phases.

One process per chip: a chip belongs to one process at a time, so the
LAUNCHER never brings up an accelerator backend (it counts chips from
device nodes, `host_chips`) and hands each replica process exactly one
chip through its environment BEFORE the child imports JAX
(`assign_chips`; the overlay is stored under "env" in the replica's spec
file, so a respawn gets its predecessor's chip). More replica processes
than chips is refused loudly, not left to hang.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from novel_view_synthesis_3d_tpu import obs
from novel_view_synthesis_3d_tpu.config import RouterConfig


@dataclasses.dataclass
class ReplicaSpec:
    """One supervised slot: where the replica's spec/ready files live
    and the URL the fleet knows it by (stable across respawns)."""

    name: str
    spec_path: str        # replica_main spec JSON (respawned verbatim)
    ready_file: str
    url: str = ""         # filled from the ready file on adopt
    log_path: str = ""    # respawned stdout/stderr sink ("" = inherit)


class _Slot:
    __slots__ = ("spec", "proc", "restarts", "health_fails", "failed",
                 "last_version", "resurrections")

    def __init__(self, spec: ReplicaSpec):
        self.spec = spec
        self.proc = None
        self.restarts = 0
        self.health_fails = 0
        self.failed = False
        self.last_version = ""
        self.resurrections = 0


def host_chips() -> int:
    """TPU chips this host exposes, counted from their device nodes —
    asking JAX would take the chips the replicas need."""
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def assign_chips(n_replicas: int, *, chips: Optional[int] = None,
                 environ=None) -> List[Dict[str, str]]:
    """Environment overlay for each of `n_replicas` replica processes.

    On the CPU lane (JAX_PLATFORMS=cpu asked for) the overlays are empty
    and any number of replicas may share the host. Otherwise replica i
    is confined to chip i as a one-chip slice of its own; asking for
    more replica processes than the host has chips raises — the extra
    process would fail or hang on a chip another process holds.
    `chips` defaults to `host_chips()`."""
    environ = os.environ if environ is None else environ
    if environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu":
        return [{} for _ in range(n_replicas)]
    if chips is None:
        chips = host_chips()
    if n_replicas > chips:
        raise RuntimeError(
            f"{n_replicas} replica processes asked for on a host with "
            f"{chips} chip(s): a chip belongs to one process at a time "
            "(fewer replicas, or JAX_PLATFORMS=cpu for the CPU lane)")
    return [{
        "TPU_VISIBLE_CHIPS": str(i),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{8476 + i}",
        "TPU_PROCESS_PORT": str(8476 + i),
        "CLOUD_TPU_TASK_ID": "0",
    } for i in range(n_replicas)]


def spec_env(spec_path: str) -> Dict[str, str]:
    """This process's environment plus the chip overlay the launcher
    stored in the replica's spec file."""
    with open(spec_path) as fh:
        overlay = json.load(fh).get("env") or {}
    return dict(os.environ, **{str(k): str(v) for k, v in overlay.items()})


def _default_spawn(spec: ReplicaSpec):
    cmd = [sys.executable, "-m",
           "novel_view_synthesis_3d_tpu.serve.replica_main",
           spec.spec_path]
    env = spec_env(spec.spec_path)
    if spec.log_path:
        with open(spec.log_path, "ab") as log:
            return subprocess.Popen(cmd, stdout=log,
                                    stderr=subprocess.STDOUT, env=env)
    return subprocess.Popen(cmd, env=env)


def _default_probe(spec: ReplicaSpec) -> dict:
    from novel_view_synthesis_3d_tpu.serve.replica import HttpReplica

    return HttpReplica(spec.name, spec.url, health_timeout_s=3.0,
                       connect_timeout_s=3.0).healthz()


class FleetSupervisor:
    """Watches replica processes; resurrects the dead, demotes nothing
    (slow-but-alive is the ROUTER's problem — gray-failure demotion and
    hedging live there; the supervisor only acts on dead/wedged)."""

    def __init__(self, specs: List[ReplicaSpec], *,
                 rcfg: Optional[RouterConfig] = None,
                 bus=None, registry=None,
                 spawn: Optional[Callable[[ReplicaSpec], object]] = None,
                 probe: Optional[Callable[[ReplicaSpec], dict]] = None,
                 heartbeat_age: Optional[
                     Callable[[ReplicaSpec], Optional[float]]] = None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep):
        self.rcfg = rcfg or RouterConfig()
        self.bus = bus
        self._spawn = spawn or _default_spawn
        self._probe = probe or _default_probe
        self._heartbeat_age = heartbeat_age or self._ready_file_age
        self._clock = clock
        self._sleep = sleep
        self._slots: Dict[str, _Slot] = {
            s.name: _Slot(s) for s in specs}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        reg = registry if registry is not None else obs.get_registry()
        self._m_restarts = reg.counter(
            "nvs3d_replica_restarts_total",
            "replica processes resurrected by the fleet supervisor")

    # -- wiring --------------------------------------------------------
    def adopt(self, name: str, proc) -> None:
        """Register an already-running replica process (the launcher
        spawned the first generation; the supervisor owns respawns).
        Reads the ready file to learn the URL and PINS the concrete
        port into the spec file so every respawn binds the same
        address — the router's replica handles stay valid."""
        slot = self._slots[name]
        slot.proc = proc
        try:
            with open(slot.spec.ready_file) as fh:
                ready = json.load(fh)
        except (OSError, ValueError):
            return
        if ready.get("url"):
            slot.spec.url = ready["url"]
        port = int(ready.get("port") or 0)
        if port:
            try:
                with open(slot.spec.spec_path) as fh:
                    spec_json = json.load(fh)
                if int(spec_json.get("port", 0)) != port:
                    spec_json["port"] = port
                    tmp = slot.spec.spec_path + ".tmp"
                    with open(tmp, "w") as fh:
                        json.dump(spec_json, fh, indent=1)
                    os.replace(tmp, slot.spec.spec_path)
            except (OSError, ValueError):
                pass  # unpinned port: respawn still works, URL may move

    # -- detection -----------------------------------------------------
    @staticmethod
    def _ready_file_age(spec: ReplicaSpec) -> Optional[float]:
        try:
            return max(0.0, time.time()
                       - os.path.getmtime(spec.ready_file))
        except OSError:
            return None  # not ready yet / mid-replace: no signal

    def check(self) -> List[str]:
        """One scan over all slots; resurrects anything dead/wedged.
        Returns the names acted on (for tests and the bench)."""
        acted = []
        for name, slot in sorted(self._slots.items()):
            if slot.failed or slot.proc is None:
                continue
            reason = self._diagnose(slot)
            if reason is None:
                continue
            acted.append(name)
            self._resurrect(slot, reason)
        return acted

    def _diagnose(self, slot: _Slot) -> Optional[str]:
        rc = slot.proc.poll()
        if rc is not None:
            return f"process exited rc={rc}"
        age = self._heartbeat_age(slot.spec)
        max_age = float(self.rcfg.supervisor_heartbeat_max_age_s)
        if age is not None and max_age > 0 and age > max_age:
            return f"heartbeat stale ({age:.1f}s > {max_age:.1f}s)"
        try:
            snap = self._probe(slot.spec)
        except Exception as e:
            slot.health_fails += 1
            if slot.health_fails >= int(self.rcfg.supervisor_health_fails):
                return (f"{slot.health_fails} consecutive health "
                        f"probe failures (last: {e})")
            return None
        slot.health_fails = 0
        if snap.get("model_version"):
            slot.last_version = str(snap["model_version"])
        return None

    # -- resurrection --------------------------------------------------
    def _expected_version(self, slot: _Slot) -> str:
        """The model version the resurrected replica must report: the
        registry channel head when the spec subscribes to one (the new
        process boots from it), else whatever the dead incarnation last
        reported ("" = no constraint — synthetic weights)."""
        try:
            with open(slot.spec.spec_path) as fh:
                spec_json = json.load(fh)
            reg = spec_json.get("registry") or {}
            if reg.get("dir"):
                from novel_view_synthesis_3d_tpu.registry import (
                    RegistryStore)

                head = RegistryStore(reg["dir"]).read_channel(
                    reg.get("channel", "stable"))
                if head:
                    return head
        except Exception:
            pass
        return slot.last_version

    def _resurrect(self, slot: _Slot, reason: str) -> bool:
        name = slot.spec.name
        slot.restarts += 1
        slot.health_fails = 0
        if slot.restarts > int(self.rcfg.supervisor_max_restarts):
            slot.failed = True
            detail = (f"replica {name} dead ({reason}) and restart "
                      f"budget spent ({self.rcfg.supervisor_max_restarts})"
                      " — slot FAILED, human needed")
            self._event("replica_giveup", detail)
            print(f"[fleet-supervisor] GIVING UP: {detail}",
                  file=sys.stderr, flush=True)
            return False
        self._event("replica_dead", f"replica {name}: {reason} "
                                    f"(restart {slot.restarts}/"
                                    f"{self.rcfg.supervisor_max_restarts})")
        self._kill_quietly(slot.proc)
        delay = min(float(self.rcfg.supervisor_backoff_cap_s),
                    float(self.rcfg.supervisor_backoff_s)
                    * (2.0 ** (slot.restarts - 1)))
        if delay > 0:
            self._sleep(delay)
        expected = self._expected_version(slot)
        try:
            os.remove(slot.spec.ready_file)
        except OSError:
            pass  # stale ready file would fake readiness via old pid
        slot.proc = self._spawn(slot.spec)
        if not self._await_ready(slot):
            # Spawn died or never became ready: leave the corpse for
            # the next scan, which re-detects and burns another retry.
            self._event("replica_resurrect_failed",
                        f"replica {name}: respawn not ready within "
                        f"{self.rcfg.supervisor_ready_timeout_s:.0f}s")
            return False
        try:
            snap = self._probe(slot.spec)
        except Exception as e:
            self._event("replica_resurrect_failed",
                        f"replica {name}: respawn unprobeable ({e})")
            return False
        got = str(snap.get("model_version", ""))
        if snap.get("status") != "ok" or (expected and got != expected):
            # Came back wrong — kill it; the exit is re-detected and
            # the attempt has already burned a unit of budget.
            self._event("replica_resurrect_failed",
                        f"replica {name}: respawn unhealthy "
                        f"(status={snap.get('status')!r}, "
                        f"version={got!r}, want {expected!r})")
            self._kill_quietly(slot.proc)
            return False
        slot.resurrections += 1
        slot.last_version = got or expected
        self._m_restarts.inc(replica=name)
        self._event(
            "replica_resurrect",
            f"replica {name} resurrected ({reason}; backoff {delay:.1f}s,"
            f" restart {slot.restarts}/{self.rcfg.supervisor_max_restarts},"
            f" pid {getattr(slot.proc, 'pid', '?')},"
            f" version {got or '<synthetic>'})")
        return True

    def _await_ready(self, slot: _Slot) -> bool:
        deadline = self._clock() + float(
            self.rcfg.supervisor_ready_timeout_s)
        pid = getattr(slot.proc, "pid", None)
        while self._clock() < deadline:
            if slot.proc.poll() is not None:
                return False
            try:
                with open(slot.spec.ready_file) as fh:
                    ready = json.load(fh)
            except (OSError, ValueError):
                ready = None
            if ready is not None and (pid is None
                                      or ready.get("pid") == pid):
                if ready.get("url"):
                    slot.spec.url = ready["url"]
                return True
            self._sleep(0.05)
        return False

    @staticmethod
    def _kill_quietly(proc) -> None:
        try:
            if proc is not None and proc.poll() is None:
                proc.kill()
            if proc is not None:
                proc.wait(timeout=10.0)
        except Exception:
            pass

    # -- lifecycle -----------------------------------------------------
    def start(self) -> "FleetSupervisor":
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="fleet-supervisor")
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.wait(float(self.rcfg.supervisor_poll_s)):
            try:
                self.check()
            except Exception as e:  # pragma: no cover - defensive
                print(f"[fleet-supervisor] scan error: {e!r}",
                      file=sys.stderr, flush=True)

    def close(self, timeout: float = 10.0) -> None:
        """Stop the scan thread. Does NOT kill the replicas — process
        retirement is the launcher's call (SIGTERM → drain)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    # -- introspection -------------------------------------------------
    def status(self) -> Dict[str, dict]:
        out = {}
        for name, slot in sorted(self._slots.items()):
            out[name] = {
                "pid": getattr(slot.proc, "pid", None),
                "alive": (slot.proc is not None
                          and slot.proc.poll() is None),
                "restarts": slot.restarts,
                "resurrections": slot.resurrections,
                "health_fails": slot.health_fails,
                "failed": slot.failed,
                "model_version": slot.last_version,
            }
        return out

    def procs(self) -> Dict[str, object]:
        """Current process handle per slot (respawns replace the
        launcher's originals — teardown must SIGTERM THESE)."""
        return {name: slot.proc for name, slot in self._slots.items()
                if slot.proc is not None}

    def _event(self, kind: str, detail: str) -> None:
        if self.bus is not None:
            self.bus.event(0, kind, detail, echo="[fleet]")

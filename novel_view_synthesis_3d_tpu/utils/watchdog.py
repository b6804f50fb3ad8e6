"""Heartbeat watchdog: in-run detection of hangs and stalls.

Every judged-bench failure this repo has suffered was a fault that HANGS,
not one that raises (BENCH_r0* rc=3: unreachable backend; the 2400 s
base128 sampling stall an external watcher had to kill). PR 1's fault
ladder recovers from faults that raise or corrupt; this module is its
stall-shaped counterpart (docs/DESIGN.md "Stall recovery").

Model: the training loop marks which PHASE it is in (`data_fetch`,
`compile`, `train_step`, `checkpoint_save`, `eval`) via the `phase()`
context manager; a monitor thread checks armed phases against per-phase
wall-clock budgets (config.py `train.watchdog.*` — compile budgets
separate from steady-state step budgets). On expiry it:

  1. captures a DIAGNOSIS BUNDLE — every thread's stack, the age of every
     heartbeat ever seen, device memory stats if the backend answers —
     and writes it to `<results>/stall_<phase>_<n>.txt`;
  2. invokes `on_stall(phase, diagnosis_path)` exactly once per phase
     entry (the Trainer logs an events.csv `stall` row and either flags a
     cross-host-agreed checkpoint-and-exit or degrades, per phase);
  3. optionally HARD-EXITS: if the phase is still stuck `hard_exit_s`
     seconds past its budget — the main thread never returned to observe
     the soft flag, i.e. a true wedge such as uninterruptible IO —
     the monitor dumps a final bundle and `os._exit(EXIT_STALL)` so a
     supervisor (train/supervisor.py) can restart the host. One stuck
     host exiting beats one stuck host wedging the whole slice.

The monitor thread is a daemon sleeping on an Event between checks; with
no armed phase it costs one dict scan per `check_interval_s`.
"""

from __future__ import annotations

import io
import os
import sys
import threading
import time
import traceback
from typing import Callable, Dict, Optional

# Process exit code for a watchdog-declared stall (soft checkpoint-and-exit
# in cli.cmd_train, or the monitor's hard exit). Distinct from
# parallel/dist.EXIT_BACKEND_UNREACHABLE (3): a stall mid-run is a
# different diagnosis than a platform that did not answer at start-up.
EXIT_STALL = 74

# Canonical phase name -> config.WatchdogConfig budget field.
PHASE_BUDGET_FIELDS = {
    "data_fetch": "data_fetch_s",
    "compile": "compile_s",
    "train_step": "step_s",
    "checkpoint_save": "checkpoint_save_s",
    "eval": "eval_s",
}
PHASES = tuple(PHASE_BUDGET_FIELDS)


def thread_stacks() -> str:
    """Formatted stacks of every live thread (the core of the bundle)."""
    out = io.StringIO()
    frames = sys._current_frames()
    names = {t.ident: t.name for t in threading.enumerate()}
    for ident, frame in frames.items():
        out.write(f"--- thread {names.get(ident, '?')} (id {ident}) ---\n")
        traceback.print_stack(frame, file=out)
    return out.getvalue()


def device_memory_stats(timeout_s: float = 2.0) -> str:
    """Best-effort per-device memory stats.

    Queried in a throwaway thread with a bounded join: on a wedged backend
    the query itself can hang, and the diagnosis bundle must never block
    the diagnosis."""
    result = {"text": f"(no answer within {timeout_s:.0f}s)"}

    def query():
        try:
            import jax

            lines = []
            for d in jax.local_devices():
                stats = getattr(d, "memory_stats", lambda: None)()
                if stats:
                    keep = {k: v for k, v in stats.items()
                            if "bytes" in k or "allocs" in k}
                    lines.append(f"{d}: {keep}")
                else:
                    lines.append(f"{d}: (no memory_stats)")
            result["text"] = "\n".join(lines) or "(no local devices)"
        except Exception as exc:
            result["text"] = f"(unavailable: {type(exc).__name__}: {exc})"

    t = threading.Thread(target=query, daemon=True, name="wd-memstats")
    t.start()
    t.join(timeout_s)
    return result["text"]


class Watchdog:
    """Monitor thread over named heartbeats and armed phase deadlines."""

    def __init__(self, budgets: Dict[str, float],
                 on_stall: Optional[Callable[[str, str], None]] = None,
                 *, check_interval_s: float = 2.0,
                 hard_exit_s: float = 0.0,
                 diagnosis_dir: str = ".",
                 query_device: bool = True,
                 _clock: Callable[[], float] = time.monotonic):
        self.budgets = dict(budgets)
        self.on_stall = on_stall
        self.check_interval_s = check_interval_s
        self.hard_exit_s = hard_exit_s
        self.diagnosis_dir = diagnosis_dir
        self.query_device = query_device
        self._clock = _clock
        self._lock = threading.Lock()
        # phase -> entry time while armed; absent when idle. The trainer is
        # single-threaded so at most a couple of phases nest (eval inside
        # nothing, data_fetch inside train() only) — a dict keeps it exact.
        self._armed: Dict[str, float] = {}
        self._flagged: Dict[str, bool] = {}  # on_stall fired for this entry
        self._last_beat: Dict[str, float] = {}
        self.stall_count = 0
        self.stalled_phases: list = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle -----------------------------------------------------
    def start(self) -> "Watchdog":
        if self._thread is None:
            self._stop.clear()  # restartable: train() may run twice
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="watchdog")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def __enter__(self) -> "Watchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- feeding -------------------------------------------------------
    def beat(self, name: str) -> None:
        """Record a named heartbeat (diagnosis context; no deadline)."""
        with self._lock:
            self._last_beat[name] = self._clock()

    def phase(self, name: str) -> "_PhaseGuard":
        """Arm `name`'s deadline for the duration of a with-block."""
        return _PhaseGuard(self, name)

    def _enter(self, name: str) -> None:
        with self._lock:
            self._armed[name] = self._clock()
            self._flagged[name] = False
            self._last_beat[name] = self._armed[name]

    def _exit(self, name: str) -> None:
        with self._lock:
            self._armed.pop(name, None)
            self._flagged.pop(name, None)
            self._last_beat[name] = self._clock()

    # -- monitoring ----------------------------------------------------
    def _run(self) -> None:
        while not self._stop.wait(self.check_interval_s):
            self.check()

    def check(self, now: Optional[float] = None) -> Optional[str]:
        """One monitor pass; returns the phase that newly stalled, if any.

        Public for tests (and callable with an explicit `now` so drills
        need not actually sleep through production-scale budgets)."""
        now = self._clock() if now is None else now
        with self._lock:
            armed = dict(self._armed)
            flagged = dict(self._flagged)
        newly_stalled = None
        for name, since in armed.items():
            budget = self.budgets.get(f"{name}_s",
                                      self.budgets.get(name, 0.0))
            if not budget or budget <= 0:
                continue
            over = (now - since) - budget
            if over <= 0:
                continue
            if not flagged.get(name):
                with self._lock:
                    if self._flagged.get(name):  # raced another check()
                        continue
                    self._flagged[name] = True
                    self.stall_count += 1
                    self.stalled_phases.append(name)
                path = self._write_diagnosis(name, now - since, budget)
                newly_stalled = name
                if self.on_stall is not None:
                    try:
                        self.on_stall(name, path)
                    except Exception:
                        traceback.print_exc()
            if self.hard_exit_s and over > self.hard_exit_s:
                self._hard_exit(name, now - since, budget)
        return newly_stalled

    def heartbeat_ages(self, now: Optional[float] = None) -> Dict[str, float]:
        now = self._clock() if now is None else now
        with self._lock:
            return {k: now - v for k, v in sorted(self._last_beat.items())}

    def _bundle(self, name: str, elapsed: float, budget: float) -> str:
        lines = [
            f"STALL: phase {name!r} armed for {elapsed:.1f}s "
            f"(budget {budget:.1f}s)",
            f"wall time: {time.strftime('%Y-%m-%d %H:%M:%S')}",
            "",
            "heartbeat ages (s since last beat):",
        ]
        for k, age in self.heartbeat_ages().items():
            lines.append(f"  {k}: {age:.1f}")
        lines += ["", "device memory:",
                  device_memory_stats() if self.query_device
                  else "(device query disabled)",
                  "", "all-thread stacks:", thread_stacks()]
        return "\n".join(lines)

    def _write_diagnosis(self, name: str, elapsed: float,
                         budget: float) -> str:
        text = self._bundle(name, elapsed, budget)
        path = os.path.join(
            self.diagnosis_dir, f"stall_{name}_{self.stall_count}.txt")
        try:
            os.makedirs(self.diagnosis_dir, exist_ok=True)
            with open(path, "w") as fh:
                fh.write(text)
        except OSError as exc:  # diagnosis must never be the second fault
            print(f"watchdog: could not write {path!r} ({exc}); bundle "
                  "follows on stderr", file=sys.stderr)
            print(text, file=sys.stderr)
        return path

    def _hard_exit(self, name: str, elapsed: float, budget: float) -> None:
        print(f"watchdog: phase {name!r} still stuck {elapsed:.1f}s after "
              f"a {budget:.1f}s budget (+{self.hard_exit_s:.1f}s grace) — "
              f"hard-exiting {EXIT_STALL} for the supervisor",
              file=sys.stderr, flush=True)
        print(self._bundle(name, elapsed, budget), file=sys.stderr,
              flush=True)
        os._exit(EXIT_STALL)


class _PhaseGuard:
    def __init__(self, wd: Watchdog, name: str):
        self._wd, self._name = wd, name

    def __enter__(self):
        self._wd._enter(self._name)
        return self

    def __exit__(self, *exc) -> None:
        self._wd._exit(self._name)


class NullWatchdog:
    """Disabled watchdog with the same surface (train.watchdog.enabled=False
    keeps the Trainer free of `if wd is not None` at every phase)."""

    stall_count = 0
    stalled_phases: list = []

    def start(self) -> "NullWatchdog":
        return self

    def stop(self) -> None:
        pass

    def beat(self, name: str) -> None:
        pass

    def phase(self, name: str):
        return _NullGuard()

    def check(self, now=None):
        return None


class _NullGuard:
    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        pass


def from_config(wcfg, on_stall=None, diagnosis_dir: str = ".",
                query_device: bool = True):
    """Watchdog (or NullWatchdog) from a config.WatchdogConfig."""
    if not wcfg.enabled:
        return NullWatchdog()
    budgets = {f"{p}_s": getattr(wcfg, field)
               for p, field in PHASE_BUDGET_FIELDS.items()}
    return Watchdog(budgets, on_stall,
                    check_interval_s=wcfg.check_interval_s,
                    hard_exit_s=wcfg.hard_exit_s,
                    diagnosis_dir=diagnosis_dir,
                    query_device=query_device)

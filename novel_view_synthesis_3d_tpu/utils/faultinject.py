"""Deterministic fault injection for the fault-tolerance subsystem.

Every recovery path in the training stack (train/guard.py anomaly guard,
train/checkpoint.py integrity fallback, data/srn.py record quarantine,
trainer SIGTERM drill) is exercised by injecting the fault it recovers
from, on CPU, in tier-1 tests (tests/test_fault_injection.py). Injection
points are env-driven so a test — or a chaos drill on a real pod — can arm
them without touching config files; with no NVS3D_FI_* variable set, every
hook is inert and the hot path pays nothing (the NaN-loss hook is read at
TRACE time, so a clean build contains no injection ops at all).

Injection points:

  NVS3D_FI_NAN_LOSS_AT      comma list of global steps; the jitted train
                            step overwrites loss AND gradients with NaN at
                            those steps (read when make_train_step traces —
                            set it before the Trainer is built).
  NVS3D_FI_NAN_GRAD_GROUP   layer-group label (models/xunet.op_groups,
                            e.g. "XUNetBlock_1"); scopes the NaN-step
                            gradient poisoning above to that group's
                            params only (loss is still poisoned). The
                            NaN-provenance drill: the numerics
                            observatory must name exactly this group as
                            first_bad_layer. Trace-time read; inert
                            without NVS3D_FI_NAN_LOSS_AT.
  NVS3D_FI_RAISE_ON_RECORD  comma list of flat record indices;
                            SRNDataset.pair raises InjectedFault for them
                            (read per call).
  NVS3D_FI_SIGTERM_AT       single step; the Trainer sends itself SIGTERM
                            when the loop reaches it (read per call).
  NVS3D_FI_STALL_DATA_AT    "<step>[:<seconds>]"; the Trainer's host batch
  NVS3D_FI_STALL_STEP_AT    fetch / train-step dispatch / checkpoint save
  NVS3D_FI_STALL_SAVE_AT    SLEEPS for <seconds> (default 30) when the
                            loop is at exactly that global step — the hang
                            drill for utils/watchdog.py. Exact-step match,
                            so a supervised restart that resumes PAST the
                            armed step does not re-stall.
  NVS3D_FI_CORRUPT_SHARD_AT comma list of packed-shard ordinals; the
                            packed-record reader (data/records.py) sees a
                            FLIPPED BYTE in those shards' streams at open
                            (sha256 mismatch → shard quarantined). The
                            mutation is in-memory — disk is untouched.
  NVS3D_FI_TRUNCATE_SHARD_AT same, but the stream is cut in half (torn
                            tail → end marker missing → quarantined),
                            the shape a host dying mid-write leaves.

Serving-plane points (sample/service.py stepper ring, registry/watcher.py;
the chaos drills in tests/test_serve_chaos.py and `serve_bench --chaos`):

  NVS3D_FI_SERVE_NAN_AT     "<dispatch>[:<row>]" (row defaults to 0); the
                            stepper poisons ring row <row>'s carried z
                            with NaN just before ring dispatch number
                            <dispatch> — the device-side finite mask must
                            quarantine exactly that slot. Exact-dispatch
                            match, so it fires once.
  NVS3D_FI_SERVE_WORKER_DIE_AT
                            single dispatch ordinal; the service worker
                            thread raises InjectedFault OUTSIDE the ring
                            try-block at that dispatch (worker-death
                            drill for the serve supervisor). One shot:
                            cleared on fire so the restarted worker
                            lives.
  NVS3D_FI_SERVE_DISPATCH_RAISE_AT
                            comma list of dispatch ordinals; the ring
                            step / group dispatch raises InjectedFault
                            INSIDE the guarded region (fail-the-ring,
                            keep-serving drill).
  NVS3D_FI_SERVE_SWAP_FAIL  integer N; the next N registry swap attempts
                            (RegistryWatcher.poll_once) raise
                            InjectedFault before verify — the circuit
                            breaker / half-open-recovery drill. The
                            counter decrements per fire and the env var
                            is cleared at 0, so the (N+1)th attempt
                            succeeds.
  NVS3D_FI_SERVE_SLOW_STEP  "<dispatch>[:<seconds>]"; the stepper SLEEPS
                            for <seconds> (default 30) at exactly that
                            ring dispatch — the wedged-worker drill for
                            SamplingService.stop()'s join-timeout
                            diagnosis and the brownout step-debt drill.
                            "*[:<seconds>]" slows EVERY dispatch — the
                            gray-failure drill: the replica stays alive
                            and healthy-looking but its p99 inflates,
                            which the fleet router's demotion + hedging
                            defenses must absorb.
  NVS3D_FI_SERVE_HEARTBEAT_STOP
                            "1": the replica process's ready-file
                            heartbeat thread stops touching the file —
                            the wedged-process drill for the fleet
                            supervisor's heartbeat-age detector (the
                            process is alive, its event loop is not).

plus `truncate_checkpoint`, a direct helper that corrupts an on-disk Orbax
step the way a mid-write preemption does (the checkpoint-fallback drill).
"""

from __future__ import annotations

import os
import signal
from typing import List, Optional, Tuple


class InjectedFault(RuntimeError):
    """A fault raised by the injection harness (never by real code)."""


def _int_list(env: str) -> Tuple[int, ...]:
    raw = os.environ.get(env, "").strip()
    if not raw:
        return ()
    try:
        return tuple(int(v) for v in raw.split(",") if v.strip())
    except ValueError as e:
        raise ValueError(f"{env}={raw!r} must be a comma list of ints") from e


def nan_loss_steps() -> Tuple[int, ...]:
    """Steps whose loss/grads the train step poisons (trace-time read)."""
    return _int_list("NVS3D_FI_NAN_LOSS_AT")


def nan_grad_group() -> str:
    """Layer-group label scoping the NaN-step grad poisoning ("" = whole
    tree, the default). Trace-time read, like nan_loss_steps."""
    return os.environ.get("NVS3D_FI_NAN_GRAD_GROUP", "").strip()


def record_fault_indices() -> Tuple[int, ...]:
    return _int_list("NVS3D_FI_RAISE_ON_RECORD")


def maybe_raise_record(flat_idx: int) -> None:
    """Hook for SRNDataset.pair: raise for records armed via env."""
    if flat_idx in record_fault_indices():
        raise InjectedFault(
            f"injected data fault at record {flat_idx} "
            "(NVS3D_FI_RAISE_ON_RECORD)")


def sigterm_step() -> Optional[int]:
    steps = _int_list("NVS3D_FI_SIGTERM_AT")
    return steps[0] if steps else None


def maybe_sigterm(step: int) -> bool:
    """Hook for the Trainer loop: deliver SIGTERM to this process at the
    armed step (the preemption drill). Returns True if the signal fired."""
    at = sigterm_step()
    if at is not None and step >= at:
        os.kill(os.getpid(), signal.SIGTERM)
        # One shot: clear so the rescheduled (resumed) run isn't re-killed.
        os.environ.pop("NVS3D_FI_SIGTERM_AT", None)
        return True
    return False


def corrupt_shard_ordinals() -> Tuple[int, ...]:
    """Packed-shard ordinals whose open-time stream gets a flipped byte."""
    return _int_list("NVS3D_FI_CORRUPT_SHARD_AT")


def truncate_shard_ordinals() -> Tuple[int, ...]:
    """Packed-shard ordinals whose open-time stream is torn (truncated)."""
    return _int_list("NVS3D_FI_TRUNCATE_SHARD_AT")


def maybe_corrupt_shard_bytes(ordinal: int, data: bytes) -> bytes:
    """Hook for the packed-record reader (data/records.py): mutate shard
    `ordinal`'s byte stream AS READ at open. Truncation halves the stream
    (a torn tail — the end marker vanishes); corruption XORs one middle
    byte (the sha256 re-hash catches it). Disk is never touched, so the
    same corpus serves clean runs and drills; with neither env var set
    the stream passes through untouched."""
    if ordinal in truncate_shard_ordinals():
        data = data[: len(data) // 2]
    if ordinal in corrupt_shard_ordinals() and data:
        i = len(data) // 2
        data = data[:i] + bytes([data[i] ^ 0x01]) + data[i + 1:]
    return data


_STALL_ENVS = {
    "data": "NVS3D_FI_STALL_DATA_AT",
    "step": "NVS3D_FI_STALL_STEP_AT",
    "save": "NVS3D_FI_STALL_SAVE_AT",
}
_DEFAULT_STALL_S = 30.0


def stall_spec(kind: str) -> Optional[Tuple[int, float]]:
    """(step, seconds) armed for a stall kind ('data'|'step'|'save').

    Env format "<step>" (default 30 s) or "<step>:<seconds>"."""
    env = _STALL_ENVS[kind]
    raw = os.environ.get(env, "").strip()
    if not raw:
        return None
    step_s, _, dur_s = raw.partition(":")
    try:
        return int(step_s), float(dur_s) if dur_s else _DEFAULT_STALL_S
    except ValueError as e:
        raise ValueError(
            f"{env}={raw!r} must be '<step>' or '<step>:<seconds>'") from e


def maybe_stall(kind: str, step: int) -> float:
    """Hook for the Trainer's phases: sleep if a stall of `kind` is armed
    at exactly this step (the hang drill). Returns the seconds slept (0.0
    when inert). Exact match — a resumed run past the armed step runs
    clean, so supervised-restart drills terminate."""
    spec = stall_spec(kind)
    if spec is None or spec[0] != step:
        return 0.0
    import time

    print(f"[faultinject] stalling {kind} at step {step} for "
          f"{spec[1]:.1f}s ({_STALL_ENVS[kind]})", flush=True)
    time.sleep(spec[1])
    return spec[1]


def serve_nan_spec() -> Optional[Tuple[int, int]]:
    """(dispatch, row) armed for the ring NaN-poison drill.

    Env format "<dispatch>" (row 0) or "<dispatch>:<row>"."""
    raw = os.environ.get("NVS3D_FI_SERVE_NAN_AT", "").strip()
    if not raw:
        return None
    disp_s, _, row_s = raw.partition(":")
    try:
        return int(disp_s), int(row_s) if row_s else 0
    except ValueError as e:
        raise ValueError(
            f"NVS3D_FI_SERVE_NAN_AT={raw!r} must be '<dispatch>' or "
            "'<dispatch>:<row>'") from e


def maybe_serve_worker_die(dispatch: int) -> None:
    """Hook for the service worker loop (OUTSIDE the per-dispatch guard):
    raise at the armed ring dispatch, killing the thread. One shot — the
    env var is cleared so the supervisor's restarted worker runs clean."""
    ats = _int_list("NVS3D_FI_SERVE_WORKER_DIE_AT")
    if ats and dispatch >= ats[0]:
        os.environ.pop("NVS3D_FI_SERVE_WORKER_DIE_AT", None)
        raise InjectedFault(
            f"injected worker death at ring dispatch {dispatch} "
            "(NVS3D_FI_SERVE_WORKER_DIE_AT)")


def maybe_serve_dispatch_raise(dispatch: int) -> None:
    """Hook INSIDE the guarded ring-step/dispatch region: raise at the
    armed dispatch ordinals (fail-the-group, keep-serving drill)."""
    if dispatch in _int_list("NVS3D_FI_SERVE_DISPATCH_RAISE_AT"):
        raise InjectedFault(
            f"injected dispatch failure at ring dispatch {dispatch} "
            "(NVS3D_FI_SERVE_DISPATCH_RAISE_AT)")


def maybe_serve_swap_fail() -> None:
    """Hook for RegistryWatcher.poll_once: fail the next N swap attempts,
    decrementing the armed count so attempt N+1 succeeds (the half-open
    recovery drill)."""
    raw = os.environ.get("NVS3D_FI_SERVE_SWAP_FAIL", "").strip()
    if not raw:
        return
    try:
        n = int(raw)
    except ValueError as e:
        raise ValueError(
            f"NVS3D_FI_SERVE_SWAP_FAIL={raw!r} must be an int") from e
    if n <= 0:
        os.environ.pop("NVS3D_FI_SERVE_SWAP_FAIL", None)
        return
    if n - 1 <= 0:
        os.environ.pop("NVS3D_FI_SERVE_SWAP_FAIL", None)
    else:
        os.environ["NVS3D_FI_SERVE_SWAP_FAIL"] = str(n - 1)
    raise InjectedFault(
        "injected registry swap failure (NVS3D_FI_SERVE_SWAP_FAIL, "
        f"{n - 1} left)")


def serve_slow_step_spec() -> Optional[Tuple[Optional[int], float]]:
    """(dispatch, seconds) armed for the slow-ring-step drill; dispatch
    is None for the every-dispatch ("*") gray-failure form.

    Env format "<dispatch>" (default 30 s), "<dispatch>:<seconds>", or
    "*[:<seconds>]"."""
    raw = os.environ.get("NVS3D_FI_SERVE_SLOW_STEP", "").strip()
    if not raw:
        return None
    disp_s, _, dur_s = raw.partition(":")
    try:
        at = None if disp_s.strip() == "*" else int(disp_s)
        return at, float(dur_s) if dur_s else _DEFAULT_STALL_S
    except ValueError as e:
        raise ValueError(
            f"NVS3D_FI_SERVE_SLOW_STEP={raw!r} must be '<dispatch>', "
            "'<dispatch>:<seconds>', or '*[:<seconds>]'") from e


_slow_step_announced = False


def maybe_serve_slow_step(dispatch: int) -> float:
    """Hook for the stepper ring: sleep if armed at exactly this dispatch
    (the wedged-worker drill) or at EVERY dispatch ("*" — the
    gray-failure drill). Returns seconds slept (0.0 when inert)."""
    spec = serve_slow_step_spec()
    if spec is None or (spec[0] is not None and spec[0] != dispatch):
        return 0.0
    import time

    global _slow_step_announced
    if spec[0] is not None or not _slow_step_announced:
        _slow_step_announced = True
        print(f"[faultinject] slow ring step at dispatch {dispatch} for "
              f"{spec[1]:.1f}s (NVS3D_FI_SERVE_SLOW_STEP"
              f"{', every dispatch' if spec[0] is None else ''})",
              flush=True)
    time.sleep(spec[1])
    return spec[1]


def serve_heartbeat_stopped() -> bool:
    """Hook for the replica process's ready-file heartbeat thread: True
    while NVS3D_FI_SERVE_HEARTBEAT_STOP is armed, freezing the mtime so
    the fleet supervisor's heartbeat-age detector sees a wedged process
    that is still answering nothing-in-particular."""
    return os.environ.get(
        "NVS3D_FI_SERVE_HEARTBEAT_STOP", "").strip() == "1"


def armed() -> List[str]:
    """Names of the NVS3D_FI_* variables currently set (for loud logging:
    a production entry point should refuse to run silently with faults
    armed)."""
    return sorted(k for k in os.environ
                  if k.startswith("NVS3D_FI_") and os.environ[k].strip())


def truncate_checkpoint(directory: str, step: Optional[int] = None,
                        keep_bytes: int = 16) -> List[str]:
    """Corrupt an on-disk Orbax checkpoint step like a torn write would.

    Truncates every regular file under the step directory to `keep_bytes`
    (metadata and array data alike), which is what a host dying mid-save
    leaves behind. Returns the corrupted paths. `step=None` corrupts the
    NEWEST step dir — the auto-resume target, i.e. the worst case the
    fallback restore must handle.
    """
    directory = os.path.abspath(directory)
    step_dirs = sorted(
        (int(d), os.path.join(directory, d))
        for d in os.listdir(directory) if d.isdigit())
    if not step_dirs:
        raise FileNotFoundError(f"no checkpoint steps under {directory!r}")
    if step is None:
        _, target = step_dirs[-1]
    else:
        matches = [p for s, p in step_dirs if s == step]
        if not matches:
            raise FileNotFoundError(
                f"no step {step} under {directory!r} "
                f"(have {[s for s, _ in step_dirs]})")
        target = matches[0]
    corrupted = []
    for root, _, files in os.walk(target):
        for fn in files:
            path = os.path.join(root, fn)
            try:
                size = os.path.getsize(path)
                with open(path, "r+b") as fh:
                    fh.truncate(min(size, keep_bytes))
                corrupted.append(path)
            except OSError:
                continue
    return corrupted

"""Profiling + debug instrumentation (SURVEY.md §5.1-5.2).

The reference has zero instrumentation (one print at train.py:157, an unused
tqdm import). Here:

  - `trace_window`: jax.profiler trace of a step window, viewable in
    TensorBoard/XProf (device + host timelines, HLO cost analysis);
  - `StepTimer`: lightweight wall-clock step timing with percentile summary
    (no profiler overhead, always-on capable);
  - `enable_nan_checks` / `check_finite`: jax_debug_nans config plus an
    explicit in-jit finite-check via `jax.debug` error checking for debug
    runs (the "sanitizer" role — the reference has no native code to TSAN,
    its failure mode is silent NaNs).
"""

from __future__ import annotations

import collections
import contextlib
import os
import sys
import threading
import time
from typing import Dict, Iterator, Optional

import jax
import numpy as np


@contextlib.contextmanager
def trace_window(log_dir: str, enabled: bool = True) -> Iterator[None]:
    """jax.profiler trace context; no-op when disabled."""
    if not enabled:
        yield
        return
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Wall-clock per-step timing with summary statistics.

    `units_per_measure` > 1 marks each measured region as covering that
    many steps (fused multi-step dispatch): recorded times are normalized
    to per-step so summaries stay comparable across dispatch widths
    (within-window per-step variation is unobservable, so each window
    contributes its mean).

    Retains only the most recent `window` measurements (the same
    deque(maxlen) pattern and count-vs-window semantics as ServiceStats:
    a million-step run must not grow host memory per step). `summary()`
    percentiles reflect the sliding window; `steps` is the total ever
    measured."""

    def __init__(self, units_per_measure: int = 1, window: int = 4096):
        self._times: "collections.deque" = collections.deque(
            maxlen=max(1, window))
        self._count = 0  # measures ever taken (window-independent)
        self._t0: Optional[float] = None
        self._units = max(1, units_per_measure)

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self) -> float:
        assert self._t0 is not None, "start() not called"
        dt = (time.perf_counter() - self._t0) / self._units
        self._times.append(dt)
        self._count += 1
        self._t0 = None
        return dt

    @contextlib.contextmanager
    def measure(self) -> Iterator[None]:
        self.start()
        try:
            yield
        finally:
            self.stop()

    @property
    def last_s(self) -> Optional[float]:
        """Most recent per-step seconds (None before the first stop) —
        the live step-rate estimate the MFU gauge divides by."""
        return self._times[-1] if self._times else None

    def summary(self) -> dict:
        if not self._times:
            return {}
        arr = np.asarray(self._times)
        return {
            "steps": self._count * self._units,
            "mean_s": float(arr.mean()),
            "p50_s": float(np.percentile(arr, 50)),
            "p90_s": float(np.percentile(arr, 90)),
            "p99_s": float(np.percentile(arr, 99)),
        }


class ServiceStats:
    """Serving-side instrumentation: per-request span timings plus a
    requests-per-second counter (sample/service.py).

    Spans are named ('queue_wait', 'compile', 'device', …); each record is
    one request's seconds in that span. Thread-safe — the micro-batcher's
    worker thread records while callers read summaries. Percentiles use
    the same p50/p90/p99 ladder as StepTimer so serving and training
    timing read alike.

    Each span keeps only the most recent `window` records (a long-lived
    service serving millions of requests must not grow host memory per
    request): percentiles reflect that sliding window, while `count` is
    the total ever recorded for the span."""

    def __init__(self, window: int = 4096):
        self._lock = threading.Lock()
        self._window = max(1, window)
        self._spans: Dict[str, "collections.deque"] = {}
        self._span_totals: Dict[str, int] = {}
        self._requests = 0
        self._t0: Optional[float] = None

    def record_span(self, name: str, seconds: float) -> None:
        with self._lock:
            dq = self._spans.get(name)
            if dq is None:
                dq = self._spans[name] = collections.deque(
                    maxlen=self._window)
            dq.append(float(seconds))
            self._span_totals[name] = self._span_totals.get(name, 0) + 1

    def count_requests(self, n: int = 1) -> None:
        """Count completed requests; the RPS window opens at the first."""
        with self._lock:
            if self._t0 is None:
                self._t0 = time.perf_counter()
            self._requests += n

    def span_summary(self, name: str) -> dict:
        with self._lock:
            vals = list(self._spans.get(name, ()))
            total = self._span_totals.get(name, 0)
        if not vals:
            return {}
        arr = np.asarray(vals)
        return {
            "count": total,
            "mean_s": float(arr.mean()),
            "p50_s": float(np.percentile(arr, 50)),
            "p90_s": float(np.percentile(arr, 90)),
            "p99_s": float(np.percentile(arr, 99)),
        }

    def summary(self) -> dict:
        with self._lock:
            names = sorted(self._spans)
            requests = self._requests
            elapsed = (time.perf_counter() - self._t0
                       if self._t0 is not None else 0.0)
        out: dict = {"requests": requests}
        if elapsed > 0:
            out["requests_per_sec"] = requests / elapsed
        for name in names:
            out[name] = self.span_summary(name)
        return out


_logged_once: set = set()


def log_once(key, msg: str) -> bool:
    """Emit `msg` on stderr the FIRST time `key` is seen; drop repeats.

    For conditions that are worth exactly one line per process — e.g. a
    configuration the guidance pair's 1 × 1 unconditional embedding does
    not hold for (models/xunet.py): the condition fires per traced call
    site, and a log per trace would be noise while zero logs hides a perf
    cliff."""
    if key in _logged_once:
        return False
    _logged_once.add(key)
    print(msg, file=sys.stderr, flush=True)
    return True


def reset_log_once(key=None) -> None:
    """Forget `key` (or, with no argument, every key) so the next
    log_once fires again. For tests: the once-per-process set otherwise
    leaks one-shot state across cases — an assertion that a message WAS
    logged passes or fails depending on which test ran first."""
    if key is None:
        _logged_once.clear()
    else:
        _logged_once.discard(key)


def enable_nan_checks(enabled: bool = True) -> None:
    """Turn on jax_debug_nans: any NaN-producing jitted op re-runs op-by-op
    and raises with the originating primitive — the debug-mode default for
    this framework's tests and repro runs."""
    jax.config.update("jax_debug_nans", enabled)


def check_finite(tree, name: str = "tree") -> None:
    """Host-side finite assertion over a pytree (checkpoint/debug guard)."""
    bad = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        arr = np.asarray(jax.device_get(leaf))
        if arr.dtype.kind == "f" and not np.isfinite(arr).all():
            bad.append(jax.tree_util.keystr(path))
    if bad:
        raise FloatingPointError(
            f"non-finite values in {name}: {', '.join(bad[:8])}")

"""Utilization gauges: device memory polling and MFU.

Answers "is HBM creeping" and "how much of the chip are we using" on a
LIVE run without attaching a debugger:

  - ``DeviceMonitor``: a daemon thread polling ``device.memory_stats()``
    for every local device on a period (``obs.device_poll_s``), feeding
    ``nvs3d_device_bytes_in_use / _device_peak_bytes / _device_bytes_limit``
    gauges (labeled per device) plus ``nvs3d_host_rss_bytes``. Backends
    whose devices report no memory stats (CPU) fall back to host RSS
    under a ``source="host_rss"`` label so the gauge family — and any
    dashboard built on it — exists on every platform. Each poll also
    mirrors to the JSONL sink so `tools/summarize_bench.py` can report
    peak HBM after the fact.
  - ``device_peak_flops()``: dense-bf16 peak per chip from public spec
    sheets, keyed on ``device_kind`` (the one home for this table —
    bench.py and the trainer's MFU gauge both read it).
  - ``mfu(...)``: model-FLOPs-utilization from a one-time
    ``jax.jit(...).lower().cost_analysis()`` FLOPs estimate and the
    observed step rate. cost_analysis() reports whole-program FLOPs on
    SPMD executables in the pinned JAX, so MFU normalizes by
    peak × n_chips; on one chip the conventions coincide.
"""

from __future__ import annotations

import threading
from typing import Callable, List, Optional

# Per-chip peaks from public spec sheets, keyed on `device_kind`: dense
# bf16 FLOP/s (v5e/v5litepod: 197 TF — 394 is its int8 TOPS figure; v4:
# 275 TF; v6e/trillium: 918 TF) and HBM bytes/s (819 / 1228 / 1640 GB/s).
# The one home of these numbers: bench.py, the trainer's MFU gauge and the
# roofline join (obs/roofline.py) all read them here. An accelerator kind
# that is not in the table is an error — a utilization silently computed
# against the wrong peak, or silently absent, is worse than none.
_PEAKS_BY_KIND = (("v5lite", 197e12, 819e9), ("v5e", 197e12, 819e9),
                  ("v6", 918e12, 1640e9), ("v4", 275e12, 1228e9))


def _peaks(device) -> Optional[tuple]:
    """(peak FLOP/s, peak bytes/s) of one chip; None on CPU."""
    import jax

    if device is None:
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower().replace(" ", "")
    for key, flops, byts in _PEAKS_BY_KIND:
        if key in kind:
            return flops, byts
    raise KeyError(
        f"device_kind {device.device_kind!r} ({device.platform}) is not "
        "in the peak table (obs/devmon._PEAKS_BY_KIND): add its spec-"
        "sheet peaks before reporting utilization on it")


def device_peak_flops(device=None) -> Optional[float]:
    """Dense bf16 peak FLOPs/s for one chip; None on CPU, KeyError for
    an accelerator kind the table does not know."""
    peaks = _peaks(device)
    return peaks and peaks[0]


def device_peak_bytes_per_s(device=None) -> Optional[float]:
    """Peak HBM bytes/s for one chip; None on CPU, KeyError for an
    accelerator kind the table does not know."""
    peaks = _peaks(device)
    return peaks and peaks[1]


def mfu(flops_per_step: float, steps_per_sec: float,
        n_chips: Optional[int] = None) -> Optional[float]:
    """Model-FLOPs utilization in [0, 1], or None on CPU (no peak)."""
    import jax

    peak = device_peak_flops()
    if not peak or not flops_per_step or steps_per_sec <= 0:
        return None
    if n_chips is None:
        n_chips = max(1, len(jax.devices()))
    return flops_per_step * steps_per_sec / (peak * n_chips)


def host_rss_bytes() -> Optional[int]:
    """Resident set size of this process, Linux-first with a stdlib
    fallback (ru_maxrss is a PEAK, labeled as such by the caller)."""
    try:
        with open("/proc/self/statm") as fh:
            pages = int(fh.read().split()[1])
        import os

        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        try:
            import resource

            return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        except Exception:
            return None


def read_device_memory() -> List[dict]:
    """One sample per local device that answers memory_stats():
    {device, bytes_in_use, peak_bytes_in_use, bytes_limit} (absent keys
    omitted). Empty on backends without the API (CPU)."""
    import jax

    out = []
    for d in jax.local_devices():
        stats = getattr(d, "memory_stats", lambda: None)()
        if not stats:
            continue
        sample = {"device": str(d.id)}
        for key, stat in (("bytes_in_use", "bytes_in_use"),
                          ("peak_bytes_in_use", "peak_bytes_in_use"),
                          ("bytes_limit", "bytes_limit")):
            if stat in stats:
                sample[key] = int(stats[stat])
        out.append(sample)
    return out


class DeviceMonitor:
    """Periodic device-memory poller feeding the registry (and JSONL).

    `poll()` is also callable directly (bench snapshots, tests). The
    thread is a daemon sleeping on an Event — stop() is prompt, and a
    wedged backend can't block interpreter exit. Polling cost is one
    memory_stats() call per device per period (a local PJRT query, no
    device sync); the default 10 s period is invisible next to a step.
    """

    def __init__(self, registry, *, poll_s: float = 10.0,
                 jsonl_cb: Optional[Callable[..., None]] = None):
        self.registry = registry
        self.poll_s = poll_s
        self._jsonl_cb = jsonl_cb
        self._in_use = registry.gauge(
            "nvs3d_device_bytes_in_use",
            "device memory currently allocated, per local device "
            "(host RSS under source=\"host_rss\" when the backend "
            "reports no device stats)")
        self._peak = registry.gauge(
            "nvs3d_device_peak_bytes",
            "high-water device memory since process start, per device")
        self._limit = registry.gauge(
            "nvs3d_device_bytes_limit",
            "allocatable device memory, per device")
        self._rss = registry.gauge(
            "nvs3d_host_rss_bytes", "host process resident set size")
        self.peak_bytes = 0  # run-level high water across devices
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def poll(self) -> List[dict]:
        samples = read_device_memory()
        for s in samples:
            dev = s["device"]
            if "bytes_in_use" in s:
                self._in_use.set(s["bytes_in_use"], device=dev)
                self.peak_bytes = max(self.peak_bytes, s["bytes_in_use"])
            if "peak_bytes_in_use" in s:
                self._peak.set(s["peak_bytes_in_use"], device=dev)
                self.peak_bytes = max(self.peak_bytes,
                                      s["peak_bytes_in_use"])
            if "bytes_limit" in s:
                self._limit.set(s["bytes_limit"], device=dev)
        rss = host_rss_bytes()
        if rss is not None:
            self._rss.set(rss)
            if not samples:
                # CPU (or any backend without memory_stats): keep the
                # device gauge family alive with the host number, loudly
                # labeled — dashboards stay wired, nobody mistakes it for
                # HBM.
                self._in_use.set(rss, device="host", source="host_rss")
                self.peak_bytes = max(self.peak_bytes, rss)
        if self._jsonl_cb is not None and (samples or rss is not None):
            self._jsonl_cb("nvs3d_device_peak_bytes", self.peak_bytes,
                           scope="run_max")
        return samples

    def snapshot(self) -> dict:
        """Point-in-time summary for bench JSON embedding."""
        samples = self.poll()
        out: dict = {"peak_bytes": self.peak_bytes}
        if samples:
            out["devices"] = samples
        rss = host_rss_bytes()
        if rss is not None:
            out["host_rss_bytes"] = rss
        return out

    # -- lifecycle -----------------------------------------------------
    def start(self) -> "DeviceMonitor":
        if self._thread is None and self.poll_s > 0:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="obs-devmon")
            self._thread.start()
        return self

    def _run(self) -> None:
        # Immediate first sample: a run shorter than one period still
        # reports memory.
        try:
            self.poll()
        except Exception:
            pass
        while not self._stop.wait(self.poll_s):
            try:
                self.poll()
            except Exception:
                pass  # a flaky backend query must never kill telemetry

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

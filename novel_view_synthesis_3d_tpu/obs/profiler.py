"""Continuous per-op-group profiling (docs/DESIGN.md "Performance
observatory").

PR 4's ``XProfWindow`` captures ONE jax.profiler window per run and
leaves the trace for a human with TensorBoard. This module closes the
loop: ``ContinuousProfiler`` re-arms bounded windows on a cadence
(``obs.profile.every_steps`` / ``window_steps``, on by default), parses
each captured trace host-side into per-``op_group`` device-time totals,
and lands the result where the rest of the observatory already looks —
a ``profile_window`` row in telemetry.jsonl (via the EventBus, the one
write path) plus ``nvs3d_group_device_time_seconds{group}`` gauges.

Attribution vocabulary: the SAME ordered op-group list the cost map,
numerics observatory, and pipeline staging share
(``models/xunet.op_groups``). Trace events are matched against each
group's module label and param names (the XUNet op loop additionally
tags each op with a ``jax.named_scope("og.<label>")`` so HLO op
metadata carries the group name verbatim); device time no pattern
claims is binned LOUDLY as ``other`` — a big ``other`` bucket is a
finding, not a rounding error. Cross-device collective time gets its
own synthetic ``comm`` group so the roofline can classify comm-bound
groups without guessing.

Overhead contract (tier-1 asserted): arming/parsing happens strictly
host-side between dispatches — no jitted code changes, zero new
recompiles, bitwise-identical training outputs profiler on vs off.
Window-armed steps are excluded from the step-rate gauges (the trainer
checks ``armed_steps_total`` across each log interval), and each
``profile_window`` row carries its own measured ``overhead_s`` so the
amortized cost (overhead per window / cadence × step time) is
measurable from artifacts alone. The acceptance test holds the rows to
that shape; the ratio itself (≤ 1 % at the default cadence) is a chip
reading that has not been taken yet (PERF.md).

Trace-format note: jax.profiler writes a Chrome trace-event JSON
(``*.trace.json.gz``) next to the xplane proto. On TPU (looked at on a
v5e under jax 0.9.0: PERF.md) the process ``/device:TPU:<n>`` has a lane
``XLA Ops`` with one slice per executed HLO instruction; the slice's
``name`` is the instruction's short name (``fusion.15``,
``flash_fwd.543``) and holds no scope, the named_scope path is in
``args.tf_op`` (``jit(f)/.../og.<label>/.../mul:``), the instruction's
text in ``args.long_name``. A group's ``og.<label>`` is therefore matched
as a whole segment of ``args.tf_op``, and only then the patterns against
the name. On CPU the trace holds only compile passes and
``*Executable::Execute`` host slices — those Execute slices are treated as (unattributable)
device time so a CPU-lane window loudly reports ``other`` rather than
an empty window. Parsing tolerates gzip/plain, torn files, and empty
windows: a window that cannot be parsed emits a row with
``error`` set instead of raising — profiling must never fault the run.

No jax at module load (supervisor constraint); jax.profiler is imported
inside the arm/disarm paths only.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

PROFILE_KIND = "profile_window"
OTHER_GROUP = "other"
COMM_GROUP = "comm"
GROUP_TIME_GAUGE = "nvs3d_group_device_time_seconds"
# Subdirectory of the run folder that holds the rolling window captures
# (distinct from the one-shot XProfWindow's "xprof" dir).
PROFILE_DIR = "profile_cont"
# Consecutive arm/disarm failures before the profiler turns itself off
# for the rest of the run (loudly, via a profile_window error row).
MAX_FAILURES = 3

# Substrings that mark a trace lane (process or thread) as device-side.
_DEVICE_LANE_RE = re.compile(
    r"/device:|TensorCore|TPU|XLA Op|Steps|GPU", re.IGNORECASE)
# Host slices that stand in for device execution on backends whose
# traces carry no device lanes (CPU): the executable dispatch itself.
_EXECUTE_RE = re.compile(r"Executable::Execute|XlaModule:")
# A named_scope path's separators, transform wrappers included
# (`transpose(jvp(XUNet))/og.final/...`; `;` joins merged instructions).
_SCOPE_SPLIT_RE = re.compile(r"[/();:]")
# Collective-op names across HLO spellings and jax primitive names.
_COMM_RE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|psum|all_gather|ppermute|send|recv", re.IGNORECASE)


def group_patterns(
        groups: Sequence[Tuple[str, Sequence[str]]]) -> List[Tuple[str, List[str]]]:
    """Ordered (label, [substring patterns]) used to claim trace events.

    Per group: the explicit ``og.<label>`` named-scope tag first (exact
    vocabulary match), then the flax module / param names (HLO op
    metadata carries them as ``.../ModuleName_k/...`` path segments).
    First match wins in group order, mirroring group_assignment."""
    out: List[Tuple[str, List[str]]] = []
    for label, names in groups:
        pats = [f"og.{label}"]
        for name in names:
            if name not in pats:
                pats.append(name)
        if label not in pats:
            pats.append(label)
        out.append((label, pats))
    return out


def find_trace_file(log_dir: str) -> Optional[str]:
    """Newest Chrome-trace JSON under a jax.profiler log dir (the
    ``plugins/profile/<ts>/<host>.trace.json.gz`` layout), or None."""
    hits: List[str] = []
    for pat in ("**/*.trace.json.gz", "**/*.trace.json"):
        hits.extend(glob.glob(os.path.join(log_dir, pat), recursive=True))
    if not hits:
        return None
    return max(hits, key=lambda p: (os.path.getmtime(p), p))


def load_chrome_trace(path: str) -> Optional[dict]:
    """Parse a (possibly gzipped) Chrome-trace JSON; None on torn or
    unreadable files — the caller bins the window as an error row."""
    try:
        if path.endswith(".gz"):
            with gzip.open(path, "rt") as fh:
                return json.load(fh)
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError, EOFError):
        return None


def _self_times(evs: List[dict]) -> List[float]:
    """Self time (dur minus immediate children) per complete event of one
    (pid, tid) lane, in the event's own time unit."""
    order = sorted(range(len(evs)),
                   key=lambda i: (evs[i]["ts"], -evs[i]["dur"]))
    self_dur = [0.0] * len(evs)
    stack: List[Tuple[float, int]] = []  # (end_ts, index)
    for i in order:
        ts = evs[i]["ts"]
        dur = evs[i]["dur"]
        while stack and stack[-1][0] <= ts:
            stack.pop()
        if stack:
            self_dur[stack[-1][1]] -= dur
        self_dur[i] += dur
        stack.append((ts + dur, i))
    return self_dur


def attribute_device_time(doc: Optional[dict],
                          patterns: Sequence[Tuple[str, Sequence[str]]]
                          ) -> dict:
    """Per-group device-time totals from one Chrome-trace document.

    Returns {"groups": {label: seconds}, "comm_s", "other_s", "total_s",
    "events", "device_lanes"}. Device lanes are identified by their
    process/thread metadata names; on lanes-free traces (CPU) the host
    ``*Executable::Execute`` slices substitute, which by construction
    land in ``other`` unless a named scope leaked into the slice name —
    the loud-``other`` contract, not a parse failure."""
    out = {"groups": {label: 0.0 for label, _ in patterns},
           "comm_s": 0.0, "other_s": 0.0, "total_s": 0.0,
           "events": 0, "device_lanes": 0}
    if not doc:
        return out
    events = doc.get("traceEvents") or []
    if not isinstance(events, list):
        return out
    # Lane naming: metadata events carry process/thread display names.
    proc_names: Dict[object, str] = {}
    thread_names: Dict[Tuple[object, object], str] = {}
    for ev in events:
        if not isinstance(ev, dict) or ev.get("ph") != "M":
            continue
        name = (ev.get("args") or {}).get("name", "")
        if ev.get("name") == "process_name":
            proc_names[ev.get("pid")] = str(name)
        elif ev.get("name") == "thread_name":
            thread_names[(ev.get("pid"), ev.get("tid"))] = str(name)

    def lane_is_device(pid, tid) -> bool:
        label = (proc_names.get(pid, "") + " "
                 + thread_names.get((pid, tid), ""))
        return bool(_DEVICE_LANE_RE.search(label))

    lanes: Dict[Tuple[object, object], List[dict]] = {}
    exec_lanes: Dict[Tuple[object, object], List[dict]] = {}
    for ev in events:
        if not isinstance(ev, dict) or ev.get("ph") != "X":
            continue
        try:
            ts = float(ev.get("ts", 0.0))
            dur = float(ev.get("dur", 0.0))
        except (TypeError, ValueError):
            continue
        if dur <= 0:
            continue
        key = (ev.get("pid"), ev.get("tid"))
        args = ev.get("args")
        slim = {"ts": ts, "dur": dur, "name": str(ev.get("name", "")),
                "scope": str(args.get("tf_op", ""))
                if isinstance(args, dict) else ""}
        if lane_is_device(*key):
            lanes.setdefault(key, []).append(slim)
        elif _EXECUTE_RE.search(slim["name"]):
            exec_lanes.setdefault(key, []).append(slim)
    # Prefer real device lanes; fall back to host Execute slices only
    # when the trace has none (CPU backend).
    chosen = lanes or exec_lanes
    out["device_lanes"] = len(lanes)
    for key, evs in chosen.items():
        selfs = _self_times(evs)
        for ev, self_us in zip(evs, selfs):
            if self_us <= 0:
                continue
            s = self_us / 1e6  # Chrome trace ts/dur are microseconds
            out["events"] += 1
            out["total_s"] += s
            name = ev["name"]
            # Whole segments of the scope path first: as a substring
            # `og.XUNetBlock_3` would also claim XUNetBlock_30's time.
            segs = set(_SCOPE_SPLIT_RE.split(ev["scope"]))
            label = next((lab for lab, pats in patterns if pats[0] in segs),
                         None) or next(
                (lab for lab, pats in patterns
                 if any(p in name for p in pats)), None)
            if label is not None:
                out["groups"][label] += s
            elif _COMM_RE.search(name):
                out["comm_s"] += s
            else:
                out["other_s"] += s
    for k in ("comm_s", "other_s", "total_s"):
        out[k] = round(out[k], 6)
    out["groups"] = {k: round(v, 6) for k, v in out["groups"].items()}
    return out


class ContinuousProfiler:
    """Re-arming jax.profiler windows with per-group attribution.

    ``on_step(step)`` is called once per loop iteration with the current
    step (training) or dispatch (serving) count, exactly like
    ``XProfWindow.on_step`` — sync-free, host-side. A window arms when
    ``step`` hits the cadence and closes ``window`` units later; closing
    stops the trace, attributes it, emits the ``profile_window`` row and
    per-group gauges, and removes nothing (captures stay on disk under
    ``<results>/profile_cont/window_<step>`` for XProf deep dives).

    ``armed_steps_total`` counts loop iterations observed while a window
    was open (including the closing iteration, which pays the parse):
    the trainer compares it across a log interval and skips the
    step-rate gauges for intervals that overlapped a window. Failures
    never propagate; after MAX_FAILURES consecutive ones the profiler
    disables itself and says so in a final error row.

    ``start_cb``/``stop_cb`` are injectable for tests; the defaults bind
    jax.profiler lazily.
    """

    def __init__(self, log_root: str,
                 groups: Sequence[Tuple[str, Sequence[str]]],
                 bus, registry=None, *,
                 every: int = 500, window: int = 2, unit: str = "step",
                 start_cb: Optional[Callable[[str], None]] = None,
                 stop_cb: Optional[Callable[[], None]] = None):
        self.log_root = log_root
        self.patterns = group_patterns(groups)
        self.bus = bus
        self.every = max(1, int(every))
        self.window = max(1, int(window))
        self.unit = unit
        self.active = False
        self.enabled = True
        self.windows: List[dict] = []
        self.armed_steps_total = 0
        self.overhead_s = 0.0  # cumulative host time arming/parsing
        self.failures = 0
        self._start_step = 0
        self._end_step = 0
        self._last_step: Optional[int] = None
        self._window_dir = ""
        self._start_cb = start_cb
        self._stop_cb = stop_cb
        self._gauge = None
        if registry is not None:
            self._gauge = registry.gauge(
                GROUP_TIME_GAUGE,
                "measured device seconds per op group in the latest "
                "profile window (obs.profile; 'other' = unattributed, "
                "'comm' = collectives)")

    # -- profiler backend ---------------------------------------------
    def _start_trace(self, log_dir: str) -> None:
        if self._start_cb is not None:
            self._start_cb(log_dir)
            return
        import jax

        jax.profiler.start_trace(log_dir)

    def _stop_trace(self) -> None:
        if self._stop_cb is not None:
            self._stop_cb()
            return
        import jax

        jax.profiler.stop_trace()

    # -- window lifecycle ---------------------------------------------
    def on_step(self, step: int) -> None:
        """Advance the window state machine; call every loop iteration."""
        if not self.enabled:
            return
        self._last_step = step
        if self.active:
            self.armed_steps_total += 1
            if step >= self._end_step:
                self._close_window(step)
        elif step > 0 and step % self.every == 0:
            self._arm(step)

    def _arm(self, step: int) -> None:
        t0 = time.perf_counter()
        self._window_dir = os.path.join(self.log_root,
                                        f"window_{step:08d}")
        try:
            os.makedirs(self._window_dir, exist_ok=True)
            self._start_trace(self._window_dir)
        except Exception as exc:  # profiling must never fault the run
            self._fail(step, f"start_trace: {exc!r}")
            return
        self.failures = 0
        self.active = True
        self._start_step = step
        self._end_step = step + self.window
        self.armed_steps_total += 1
        self.overhead_s += time.perf_counter() - t0

    def _close_window(self, step: int) -> None:
        t0 = time.perf_counter()
        self.active = False
        try:
            self._stop_trace()
        except Exception as exc:
            self._fail(step, f"stop_trace: {exc!r}")
            return
        row = {"kind": PROFILE_KIND, "unit": self.unit,
               "step_start": self._start_step, "step_end": step,
               "trace_dir": self._window_dir}
        path = find_trace_file(self._window_dir)
        doc = load_chrome_trace(path) if path else None
        attr = attribute_device_time(doc, self.patterns)
        row.update(attr)
        if path is None:
            row["error"] = "no trace file captured"
        elif doc is None:
            row["error"] = "trace file unreadable (torn or truncated)"
        dt = time.perf_counter() - t0
        self.overhead_s += dt
        row["overhead_s"] = round(dt, 6)
        self.windows.append(row)
        if self.bus is not None:
            self.bus.jsonl_row(row)
        if self._gauge is not None:
            for label, secs in attr["groups"].items():
                self._gauge.set(secs, group=label)
            self._gauge.set(attr["other_s"], group=OTHER_GROUP)
            self._gauge.set(attr["comm_s"], group=COMM_GROUP)

    def _fail(self, step: int, detail: str) -> None:
        self.active = False
        self.failures += 1
        row = {"kind": PROFILE_KIND, "unit": self.unit,
               "step_start": self._start_step, "step_end": step,
               "error": detail}
        if self.failures >= MAX_FAILURES:
            self.enabled = False
            row["disabled"] = True
            print(f"obs: continuous profiler disabled after "
                  f"{self.failures} consecutive failures ({detail})",
                  flush=True)
        self.windows.append(row)
        if self.bus is not None:
            self.bus.jsonl_row(row)

    def close(self) -> None:
        """Finalize an open window (run ended mid-capture); idempotent."""
        if self.active:
            self._close_window(self._last_step
                               if self._last_step is not None
                               else self._end_step)

    # -- overhead accounting ------------------------------------------
    def amortized_overhead(self, step_s: float) -> Optional[float]:
        """Measured profiler overhead as a fraction of run time at the
        configured cadence: (host overhead per window) / (every × step
        wall time). None before the first closed window."""
        if not self.windows or step_s <= 0:
            return None
        per_window = self.overhead_s / len(self.windows)
        return per_window / (self.every * step_s)


def make_profiler(pcfg, results_folder: str, model_cfg, bus,
                  registry=None, *, unit: str = "step"
                  ) -> Optional[ContinuousProfiler]:
    """Build the run's ContinuousProfiler from ObsProfileConfig, or None
    when disabled. `unit` picks the training (steps) vs serving
    (dispatches) cadence fields. Imports models.xunet lazily — obs stays
    jax-free at module load."""
    if pcfg is None or not getattr(pcfg, "enabled", False):
        return None
    if unit == "dispatch":
        every = int(getattr(pcfg, "serve_every_dispatches", 0))
        window = int(getattr(pcfg, "serve_window_dispatches", 0))
    else:
        every = int(getattr(pcfg, "every_steps", 0))
        window = int(getattr(pcfg, "window_steps", 0))
    if every <= 0 or window <= 0:
        return None
    from novel_view_synthesis_3d_tpu.models.xunet import op_groups

    return ContinuousProfiler(
        os.path.join(results_folder, PROFILE_DIR),
        op_groups(model_cfg), bus, registry,
        every=every, window=window, unit=unit)


def profile_rows(results_folder: str) -> List[dict]:
    """All profile_window rows a run has landed in telemetry.jsonl,
    in file order; [] when the file or rows are absent. Torn trailing
    lines are skipped (crash-tolerant, same policy as load_ledger)."""
    from novel_view_synthesis_3d_tpu.obs.bus import jsonl_path

    path = jsonl_path(results_folder)
    if not os.path.exists(path):
        return []
    out: List[dict] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if isinstance(row, dict) and row.get("kind") == PROFILE_KIND:
                out.append(row)
    return out

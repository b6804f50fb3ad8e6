"""Roofline attribution: measured group time × analytic cost map
(docs/DESIGN.md "Performance observatory").

The cost map (obs/compiles.xunet_costmap) knows each op group's analytic
FLOPs and bytes; the continuous profiler (obs/profiler) knows its
MEASURED device seconds; devmon knows the chip's peak FLOPs/s and HBM
bytes/s. This module joins the three into per-group roofline rows:

    mfu        = flops / (time × peak_flops)
    bw_util    = bytes / (time × peak_bytes_per_s)
    ideal_s    = max(flops / peak_flops, bytes / peak_bytes_per_s)
    headroom_s = time − ideal_s          (what an optimal kernel saves)
    bound      = comm | compute | memory | unknown

``bound`` is the roofline verdict: compute when MFU dominates bandwidth
utilization, memory when the reverse, comm for the synthetic collective
group, unknown when the chip's peaks aren't tabulated (CPU) or the
group was never measured. The top-k-by-headroom list is the target list
for the ROADMAP perf arcs — it names where an optimization pays before
anyone writes one.

Pure host-side joins over dicts; no jax at module load. Peaks are
optional arguments so tests (and `nvs3d obs roofline` on a machine that
didn't run the job) can supply them explicitly.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence

from novel_view_synthesis_3d_tpu.obs.profiler import (
    COMM_GROUP,
    OTHER_GROUP,
    profile_rows,
)

BOUND_COMM = "comm"
BOUND_COMPUTE = "compute"
BOUND_MEMORY = "memory"
BOUND_UNKNOWN = "unknown"


def costmap_by_group(costmap_rows: Sequence[dict]) -> Dict[str, dict]:
    """Aggregate costmap rows (one per op) into per-group FLOPs/bytes.
    Groups and ops are 1:1 today, but the join sums defensively."""
    out: Dict[str, dict] = {}
    for row in costmap_rows or []:
        group = row.get("group") or row.get("op") or ""
        if not group:
            continue
        agg = out.setdefault(group, {"flops": 0.0, "bytes": 0.0})
        agg["flops"] += float(row.get("flops") or 0.0)
        agg["bytes"] += float(row.get("bytes") or 0.0)
    return out


def _classify(mfu: Optional[float], bw: Optional[float]) -> str:
    if mfu is None and bw is None:
        return BOUND_UNKNOWN
    if mfu is not None and (bw is None or mfu >= bw):
        return BOUND_COMPUTE
    return BOUND_MEMORY


def roofline_rows(costmap_rows: Sequence[dict],
                  group_seconds: Dict[str, float], *,
                  comm_s: float = 0.0, other_s: float = 0.0,
                  peak_flops: Optional[float] = None,
                  peak_bytes_per_s: Optional[float] = None) -> List[dict]:
    """Join per-group measured seconds with analytic cost; one row per
    group, sorted by measured time (descending, unmeasured last). The
    synthetic ``comm``/``other`` buckets ride along so the rendered
    table always accounts for ALL measured device time."""
    cost = costmap_by_group(costmap_rows)
    labels = list(dict.fromkeys(list(group_seconds) + list(cost)))
    rows: List[dict] = []
    for label in labels:
        t = group_seconds.get(label)
        flops = cost.get(label, {}).get("flops", 0.0)
        byts = cost.get(label, {}).get("bytes", 0.0)
        row: dict = {"group": label, "time_s": t,
                     "flops": flops, "bytes": byts}
        mfu = bw = None
        if t and t > 0:
            if flops and peak_flops:
                mfu = flops / (t * peak_flops)
                row["mfu"] = round(mfu, 4)
            if flops:
                row["achieved_flops_per_s"] = flops / t
            if byts and peak_bytes_per_s:
                bw = byts / (t * peak_bytes_per_s)
                row["bw_util"] = round(bw, 4)
            if byts:
                row["achieved_bytes_per_s"] = byts / t
        ideal = 0.0
        if peak_flops and flops:
            ideal = max(ideal, flops / peak_flops)
        if peak_bytes_per_s and byts:
            ideal = max(ideal, byts / peak_bytes_per_s)
        if ideal > 0:
            row["ideal_s"] = round(ideal, 6)
            if t and t > 0:
                row["headroom_s"] = round(t - ideal, 6)
                row["headroom_x"] = round(t / ideal, 2) if ideal else None
        row["bound"] = _classify(mfu, bw)
        rows.append(row)
    if comm_s:
        rows.append({"group": COMM_GROUP, "time_s": comm_s,
                     "flops": 0.0, "bytes": 0.0, "bound": BOUND_COMM})
    if other_s:
        rows.append({"group": OTHER_GROUP, "time_s": other_s,
                     "flops": 0.0, "bytes": 0.0,
                     "bound": BOUND_UNKNOWN})
    rows.sort(key=lambda r: (-(r.get("time_s") or 0.0), r["group"]))
    return rows


def top_headroom(rows: Sequence[dict], k: int = 3) -> List[dict]:
    """The k groups with the most recoverable seconds — the aim list."""
    cands = [r for r in rows if (r.get("headroom_s") or 0.0) > 0.0]
    cands.sort(key=lambda r: -r["headroom_s"])
    return cands[:k]


def analyze_run(run_dir: str, *, peak_flops: Optional[float] = None,
                peak_bytes_per_s: Optional[float] = None,
                window_index: int = -1) -> dict:
    """Roofline a results folder from its artifacts: latest (or indexed)
    profile_window row + costmap.json. Peaks default to the CURRENT
    process's devices (lazily; None on CPU → bound stays unknown with a
    loud note). Returns {"rows", "top", "notes", "window"}."""
    from novel_view_synthesis_3d_tpu.obs.compiles import load_costmap

    notes: List[str] = []
    cost_rows = load_costmap(run_dir)
    if not cost_rows:
        # bench banks the costmap next to, not inside, the run folder.
        cost_rows = load_costmap(os.path.dirname(run_dir) or ".")
    if not cost_rows:
        notes.append("no costmap.json found — analytic FLOPs/bytes "
                     "unavailable, rows carry measured time only")
    rows_all = profile_rows(run_dir)
    windows = [r for r in rows_all if not r.get("error")]
    window: Optional[dict] = None
    if windows:
        window = windows[window_index]
    else:
        notes.append("no profile_window rows in telemetry.jsonl — "
                     "analytic-only roofline (ideal times, no measured "
                     "time; run with obs.profile.enabled to measure)")
    group_seconds = dict((window or {}).get("groups") or {})
    if peak_flops is None or peak_bytes_per_s is None:
        from novel_view_synthesis_3d_tpu.obs.devmon import (
            device_peak_bytes_per_s,
            device_peak_flops,
        )

        if peak_flops is None:
            peak_flops = device_peak_flops()
        if peak_bytes_per_s is None:
            peak_bytes_per_s = device_peak_bytes_per_s()
    if not peak_flops and not peak_bytes_per_s:
        notes.append("no chip peaks on CPU — bound classification "
                     "degraded to 'unknown'")
    if window and window.get("other_s", 0.0) > 0.5 * max(
            window.get("total_s") or 1e-12, 1e-12):
        notes.append(
            f"{window['other_s']:.3f}s of {window.get('total_s', 0.0):.3f}s "
            "device time is unattributed ('other') — group tagging did "
            "not reach this trace (CPU lane, or named scopes stripped)")
    rows = roofline_rows(
        cost_rows, group_seconds,
        comm_s=float((window or {}).get("comm_s") or 0.0),
        other_s=float((window or {}).get("other_s") or 0.0),
        peak_flops=peak_flops, peak_bytes_per_s=peak_bytes_per_s)
    return {"rows": rows, "top": top_headroom(rows), "notes": notes,
            "window": window}


def render(report: dict, k: int = 3) -> str:
    """Human table for `nvs3d obs roofline` — fixed-width, stdlib only."""
    lines: List[str] = []
    win = report.get("window")
    if win:
        lines.append(
            f"profile window [{win.get('step_start')}, "
            f"{win.get('step_end')}) unit={win.get('unit', 'step')} "
            f"measured {win.get('total_s', 0.0):.4f}s device time")
    hdr = (f"{'group':<22} {'time_s':>10} {'mfu':>7} {'bw_util':>8} "
           f"{'ideal_s':>10} {'headroom':>9} {'bound':<8}")
    lines.append(hdr)
    lines.append("-" * len(hdr))
    for r in report.get("rows", []):
        def fmt(key, spec):
            v = r.get(key)
            return format(v, spec) if isinstance(v, (int, float)) else "-"

        lines.append(
            f"{r['group']:<22} {fmt('time_s', '10.5f')} "
            f"{fmt('mfu', '7.3f')} {fmt('bw_util', '8.3f')} "
            f"{fmt('ideal_s', '10.6f')} {fmt('headroom_s', '9.5f')} "
            f"{r.get('bound', BOUND_UNKNOWN):<8}")
    top = top_headroom(report.get("rows", []), k)
    if top:
        names = ", ".join(
            f"{r['group']} ({r['headroom_s']:.4f}s, {r['bound']})"
            for r in top)
        lines.append(f"top-{len(top)} headroom: {names}")
    for note in report.get("notes", []):
        lines.append(f"note: {note}")
    return "\n".join(lines)

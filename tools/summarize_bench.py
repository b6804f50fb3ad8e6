"""Summarize a results/tpu_r* directory into one markdown table.

Usage: python tools/summarize_bench.py [results/tpu_r04] [--write out.md]

Reads every {name}.json the watcher persisted (platform-tagged judged-format
lines), plus quality summaries if present, and prints a compact table —
the round-results narrative's data section, generated instead of
hand-copied.
"""

from __future__ import annotations

import json
import os
import sys


def load_rows(out_dir: str):
    rows = []
    for fn in sorted(os.listdir(out_dir)):
        if not fn.endswith(".json"):
            continue
        try:
            with open(os.path.join(out_dir, fn)) as fh:
                d = json.loads(fh.read().strip() or "{}")
        except (OSError, json.JSONDecodeError):
            continue
        if "metric" not in d:
            continue
        rows.append((fn[:-5], d))
    return rows


def fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:,.3f}".rstrip("0").rstrip(".")
    return str(v)


def recovery_rows(search_dirs):
    """(path, anomalies, rollbacks, restarts) per metrics.csv with
    recovery events.

    The trainer logs cumulative anomaly-guard skips, checkpoint rollbacks,
    and supervised restarts as metrics.csv columns (train/metrics.py,
    train/supervisor.py) — a bench or quality number produced by a run
    that silently recovered from faults must say so next to the number.
    Pre-fault-tolerance CSVs (no such columns) read as zero.
    """
    import csv
    import glob

    rows = []
    seen = set()
    for d in search_dirs:
        for path in sorted(glob.glob(os.path.join(d, "**", "metrics.csv"),
                                     recursive=True)):
            if path in seen:
                continue
            seen.add(path)
            anomalies = rollbacks = restarts = 0
            try:
                with open(path, newline="") as fh:
                    for row in csv.DictReader(fh):
                        anomalies = max(anomalies,
                                        int(float(row.get("anomalies") or 0)))
                        rollbacks = max(rollbacks,
                                        int(float(row.get("rollbacks") or 0)))
                        restarts = max(restarts,
                                       int(float(row.get("restarts") or 0)))
            except (OSError, ValueError):
                continue
            if anomalies or rollbacks or restarts:
                rows.append((path, anomalies, rollbacks, restarts))
    return rows


def _pctl(sorted_vals, q):
    """Nearest-rank percentile over a pre-sorted list (stdlib-only)."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def telemetry_rows(search_dirs):
    """Per telemetry.jsonl (the obs/ JSONL sink): span p50/p90/p99 per
    phase plus the peak device-memory gauge — the same numbers the live
    /metrics endpoint exposes, recovered after the fact from the run's
    results folder."""
    import glob

    rows = []
    seen = set()
    for d in search_dirs:
        for path in sorted(glob.glob(
                os.path.join(d, "**", "telemetry.jsonl"), recursive=True)):
            if path in seen:
                continue
            seen.add(path)
            spans = {}
            peak_bytes = 0.0
            versions = []  # ordered-unique model_version timeline
            swaps = 0
            try:
                with open(path) as fh:
                    for line in fh:
                        try:
                            rec = json.loads(line)
                        except json.JSONDecodeError:
                            continue  # torn tail line of a crashed run
                        mv = rec.get("model_version")
                        if mv and (not versions or versions[-1] != mv):
                            versions.append(mv)
                        if (rec.get("kind") == "event"
                                and rec.get("event") == "model_swap"):
                            swaps += 1
                        if rec.get("kind") == "span":
                            spans.setdefault(rec.get("name", "?"),
                                             []).append(
                                float(rec.get("dur_s", 0.0)))
                        elif (rec.get("kind") == "gauge"
                              and "bytes" in rec.get("name", "")):
                            peak_bytes = max(peak_bytes,
                                             float(rec.get("value", 0.0)))
            except OSError:
                continue
            phases = {}
            for name, durs in sorted(spans.items()):
                total = sum(durs)
                durs.sort()
                phases[name] = (len(durs), _pctl(durs, 0.5),
                                _pctl(durs, 0.9), _pctl(durs, 0.99),
                                total)
            if phases or peak_bytes or versions:
                rows.append((path, phases, peak_bytes, versions, swaps))
    return rows


def input_pipeline_lines(telem):
    """Input-pipeline health per run: data_fetch percentiles against
    train_step, plus the overlap ratio — the fraction of total fetch time
    hidden behind device compute (1.0 = the loader never sat on the step
    loop's critical path; the packed-backend acceptance target is
    data_fetch p99 < 10% of train_step p50). data_fetch spans run on the
    prefetcher thread, so fetch/step = producer duty cycle, and
    overlap = 1 − Σfetch/Σstep clamped to [0, 1]."""
    lines = ["", "## Input pipeline (data_fetch vs train_step, "
                 "from telemetry.jsonl)", ""]
    rows = []
    for path, phases, _peak, _versions, _swaps in telem:
        fetch = phases.get("data_fetch")
        step = phases.get("train_step")
        if not fetch or not step or step[1] <= 0:
            continue
        ratio = fetch[3] / step[1]  # fetch p99 / step p50
        overlap = max(0.0, 1.0 - fetch[4] / step[4]) if step[4] else 0.0
        rows.append((path, fetch, step, ratio, overlap))
    if not rows:
        return []
    lines += ["| run | fetch p50 | fetch p99 | step p50 | "
              "p99(fetch)/p50(step) | overlap |",
              "|---|---|---|---|---|---|"]
    for path, fetch, step, ratio, overlap in rows:
        lines.append(
            "| `{}` | {:.1f}ms | {:.1f}ms | {:.1f}ms | {:.1%} | {:.1%} |"
            .format(path, fetch[1] * 1e3, fetch[3] * 1e3, step[1] * 1e3,
                    ratio, overlap))
    return lines


def continuous_lines(rows):
    """Per-step-class latency tables for serve_bench --continuous rows
    (the step-level continuous-batching scenario): one table per entry,
    covering the stepper, the same-trace whole-request A/B, and the
    PR 3 teacher-ladder deployment baseline."""
    lines = []
    for name, d in rows:
        cont = d.get("continuous")
        if not isinstance(cont, dict):
            continue
        lines += ["", f"## Continuous batching — {name}", ""]
        tr = cont.get("trace", {})
        lines.append(
            f"- trace: {tr.get('requests')} req @ "
            f"{tr.get('rate_per_s')}/s, mix {tr.get('mix')}, "
            f"teacher {tr.get('teacher_steps')} steps")
        lines.append(
            f"- few-step serving vs PR 3 deployment: "
            f"**{cont.get('vs_pr3_few_step_serving')}×**; scheduler-only "
            f"(same trace): {cont.get('vs_whole_request_same_trace')}×; "
            f"few-step p99 {cont.get('p99_few_step_s')}s "
            f"(bounded={cont.get('p99_few_step_bounded')})")
        lines += ["",
                  "| lane | class | n | ok | late | expired | p50 (s) | "
                  "p99 (s) |", "|---|---|---|---|---|---|---|---|"]
        for lane in ("stepper", "scheduler_ab", "pr3_teacher_steps"):
            summ = cont.get(lane)
            if not summ:
                continue
            for cls, c in sorted(summ.get("classes", {}).items(),
                                 key=lambda kv: int(kv[0])):
                lines.append(
                    "| {} | {} | {} | {} | {} | {} | {} | {} |".format(
                        lane, cls, c.get("n"), c.get("ok"),
                        c.get("late"), c.get("expired"),
                        fmt(c.get("p50_s", 0.0)), fmt(c.get("p99_s", 0.0))))
        delta = cont.get("stepper", {}).get("programs_built_delta")
        lines.append("")
        lines.append(
            f"- stepper programs built during the mixed trace: {delta} "
            "(zero-recompile contract)"
            + (f"; whole-request built "
               f"{cont.get('scheduler_ab', {}).get('programs_built_delta')}"
               " (per-(steps,bucket) cache key)" if cont.get("scheduler_ab")
               else ""))
    return lines


def cpu_lane_lines(repo_root: str):
    """The restored CPU-lane trajectory: every BENCH_r*.json archive at
    the repo root, with its lane/platform/value — four rc=3 rounds with
    'parsed: null' (BENCH_r03-r05) is the blindness this replaces.

    Bad rounds (rc!=0, parsed null, malformed JSON) are SKIPPED LOUDLY:
    they appear in the table and in the skip note, but never silence the
    value trajectory line — earlier builds rendered an empty trajectory
    whenever the glob hit only rc=3 archives."""
    import glob

    lines = ["", "## Bench-lane trajectory (BENCH_r*.json)", ""]
    rows = []
    good = []   # (round name, lane, metric, value) — plottable points
    skipped = []  # (round name, reason) — named, not silenced
    for path in sorted(glob.glob(os.path.join(repo_root, "BENCH_r*.json"))):
        name = os.path.basename(path)
        try:
            with open(path) as fh:
                d = json.load(fh)
        except (OSError, json.JSONDecodeError) as e:
            rows.append((name, "?", "-", "(malformed archive)",
                         None, None, "-", "-"))
            skipped.append((name, f"malformed: {type(e).__name__}"))
            continue
        parsed = d.get("parsed")
        if isinstance(parsed, dict) and parsed.get("value") is not None:
            lane = parsed.get("lane", parsed.get("platform", "?"))
            rows.append((name, d.get("rc"), lane,
                         parsed.get("metric"), parsed.get("value"),
                         parsed.get("vs_baseline"),
                         parsed.get("precision", "-"),
                         parsed.get("fused_step", "-"),
                         parsed.get("update_sharding", "-"),
                         parsed.get("pipeline_stages", "-")))
            good.append((name, lane, parsed.get("metric"),
                         parsed.get("value"), parsed.get("vs_baseline")))
        else:
            rows.append((name, d.get("rc"), "-",
                         "(no parsed datapoint)", None, None, "-", "-",
                         "-", "-"))
            skipped.append((name, f"rc={d.get('rc')}, no parsed "
                                  "datapoint"))
    if not rows:
        return []
    # precision / fused_step columns (PR 8) and update-sharding / stage
    # columns (PR 13): the trajectory must record what was measured — a
    # bf16+fused or zero-sharded number next to an f32/replicated one is
    # a different deployment, not a regression/improvement of the same.
    lines += ["| round | rc | lane | metric | value | vs_baseline | "
              "precision | fused_step | sharding | stages |",
              "|---|---|---|---|---|---|---|---|---|---|"]
    for (name, rc, lane, metric, value, vsb, prec, fused, shard,
         stages) in rows:
        lines.append(
            "| {} | {} | {} | {} | {} | {} | {} | {} | {} | {} |".format(
                name, rc, lane, metric,
                fmt(value) if value is not None else "null",
                fmt(vsb) if vsb is not None else "", prec, fused, shard,
                stages))
    lines.append("")
    if good:
        by_lane = {}
        regressions = []  # sub-1.0x rounds — named LOUDLY, not buried
        for name, lane, metric, value, vsb in good:
            short = name.replace("BENCH_", "").replace(".json", "")
            flag = ""
            if isinstance(vsb, (int, float)) and vsb < 1.0:
                flag = " [REGRESSION]"
                regressions.append(f"{short} (vs_baseline={fmt(vsb)})")
            by_lane.setdefault(lane, []).append(
                f"{short} {fmt(value)}{flag}")
        for lane, pts in sorted(by_lane.items()):
            lines.append(f"- {lane} lane trajectory: "
                         + " -> ".join(pts))
        # BENCH_r09 landed 0.973x with rc=0 and nobody noticed — a
        # sub-1.0x round now gets its own line (and tools/
        # bench_sentry.py gets its own rc).
        if regressions:
            lines.append("- **REGRESSION: sub-1.0x vs_baseline round(s): "
                         + "; ".join(regressions)
                         + "** (see tools/bench_sentry.py)")
    else:
        lines.append("- lane trajectory: NO parsed datapoints in any "
                     "round")
    if skipped:
        lines.append("- skipped rounds (no datapoint): "
                     + "; ".join(f"{n} ({r})" for n, r in skipped))
    return lines


def multichip_lines(repo_root: str):
    """The MULTICHIP_r*.json trajectory: mesh dry-run contract rounds
    (ok/skipped/n_devices + the mesh line from the tail) — previously
    banked at the repo root but rendered nowhere."""
    import glob

    paths = sorted(glob.glob(os.path.join(repo_root, "MULTICHIP_r*.json")))
    if not paths:
        return []
    lines = ["", "## Multichip trajectory (MULTICHIP_r*.json)", "",
             "| round | rc | ok | skipped | n_devices | tail |",
             "|---|---|---|---|---|---|"]
    problems = []
    for path in paths:
        name = os.path.basename(path)
        try:
            with open(path) as fh:
                d = json.load(fh)
        except (OSError, json.JSONDecodeError):
            lines.append(f"| {name} | ? | | | | (malformed archive) |")
            problems.append(f"{name} (malformed)")
            continue
        tail = " ".join(str(d.get("tail", "")).split())[:80]
        lines.append("| {} | {} | {} | {} | {} | {} |".format(
            name, d.get("rc"), d.get("ok"), d.get("skipped"),
            d.get("n_devices"), tail))
        if d.get("rc") != 0 or not d.get("ok") or d.get("skipped"):
            problems.append(f"{name} (rc={d.get('rc')} ok={d.get('ok')} "
                            f"skipped={d.get('skipped')})")
    lines.append("")
    if problems:
        lines.append("- **PROBLEM round(s): " + "; ".join(problems) + "**")
    else:
        lines.append(f"- all {len(paths)} rounds ok (dry-run mesh "
                     "contract held)")
    return lines


def trajectory_serving_lines(rows):
    """Tables for serve_bench --trajectory artifacts: ring-native orbit
    generation vs the naive per-frame client loop, with the delivery /
    zero-recompile contract columns."""
    lines = []
    for name, d in rows:
        traj = d.get("trajectory")
        if not isinstance(traj, dict):
            continue
        lines += ["", f"## Trajectory serving — {name}", ""]
        tr = traj.get("trace", {})
        lines.append(
            f"- trace: {tr.get('orbits')} orbit(s) × "
            f"{tr.get('frames_per_orbit')} frames × "
            f"{tr.get('reps')} rep(s) at {tr.get('steps_per_frame')} "
            f"step(s)/frame, k_max {tr.get('k_max')}, flush "
            f"{tr.get('flush_timeout_ms')}ms")
        lines.append(
            f"- ring-native vs naive per-frame loop: "
            f"**{traj.get('ring_vs_naive')}×** "
            f"({traj.get('fps_ring')} vs {traj.get('fps_naive')} "
            "frames/s)")
        ring = traj.get("ring", {})
        lines += ["",
                  "| lane | frames | window (s) | frames/s | built | "
                  "jit Δ | commit Δ | delivery |",
                  "|---|---|---|---|---|---|---|---|"]
        lines.append("| ring | {} | {} | {} | {} | {} | {} | {} |".format(
            ring.get("frames_delivered"), fmt(ring.get("window_s", 0.0)),
            fmt(ring.get("frames_per_sec", 0.0)),
            ring.get("programs_built_delta"),
            ring.get("jit_cache_entries_delta"),
            ring.get("commit_jit_entries_delta"),
            "ok" if ring.get("delivery_ok") else "INCOMPLETE"))
        naive = traj.get("naive", {})
        lines.append("| naive | {} | {} | {} | | | | |".format(
            naive.get("frames_delivered"),
            fmt(naive.get("window_s", 0.0)),
            fmt(naive.get("frames_per_sec", 0.0))))
    return lines


def cond_cache_lines(rows):
    """Tables for serve_bench --cond-cache artifacts: the cached vs
    re-encode-every-step lanes with the cache-hit attribution
    (hits/misses/resident bytes from the service's cond_cache summary)."""
    lines = []
    for name, d in rows:
        cc = d.get("cond_cache")
        if not isinstance(cc, dict) or "off" not in cc:
            continue
        lines += ["", f"## Conditioning cache — {name}", ""]
        tr = cc.get("trace", {})
        lines.append(
            f"- trace: {tr.get('requests')} arrivals @ "
            f"{tr.get('rate_per_s')}/s ({tr.get('util_target')}× the "
            f"cache-off lane's solo capacity), {tr.get('orbits')} "
            f"orbit(s) × {tr.get('frames_per_orbit')} frames, "
            f"{tr.get('steps')} steps/request, emb_ch "
            f"{tr.get('emb_ch')}")
        lines.append(
            f"- cached vs re-encode-every-step: **{cc.get('speedup')}×** "
            f"({cc.get('on', {}).get('row_steps_per_sec')} vs "
            f"{cc.get('off', {}).get('row_steps_per_sec')} row-steps/s)")
        stats = cc.get("on", {}).get("cond_cache") or {}
        if stats:
            lines.append(
                f"- cache hits: {stats.get('hits')} / misses "
                f"{stats.get('misses')} (hit rate "
                f"{fmt(100 * stats.get('hit_rate', 0.0))}%), "
                f"{stats.get('uncond_entries')} uncond entr(y/ies), "
                f"resident {stats.get('resident_bytes', 0) / 1e6:.1f} MB")
        lines += ["",
                  "| lane | row-steps | window (s) | row-steps/s | "
                  "built | jit Δ | encode Δ | delivery |",
                  "|---|---|---|---|---|---|---|---|"]
        for lane in ("off", "on"):
            ln = cc.get(lane, {})
            deltas = ln.get("deltas", {})
            lines.append(
                "| {} | {} | {} | {} | {} | {} | {} | {} |".format(
                    lane, ln.get("row_steps_delivered"),
                    fmt(ln.get("window_s", 0.0)),
                    fmt(ln.get("row_steps_per_sec", 0.0)),
                    deltas.get("programs_built"),
                    deltas.get("jit_cache_entries"),
                    deltas.get("encode_jit_entries"),
                    "ok" if ln.get("delivery_ok") else "INCOMPLETE"))
    return lines


def precision_sweep_lines(rows):
    """Per-lane tables for serve_bench --precision-sweep artifacts:
    precision/fused-step delivery + the per-precision PSNR probe deltas
    the promotion gate would charge each deployment."""
    lines = []
    for name, d in rows:
        sweep = d.get("precision_sweep")
        if not isinstance(sweep, dict):
            continue
        lines += ["", f"## Precision sweep — {name}", ""]
        tr = sweep.get("trace", {})
        lines.append(
            f"- trace: {tr.get('requests')} req @ "
            f"{tr.get('rate_per_s')}/s, mix {tr.get('mix')}; gate "
            f"margin {sweep.get('gate_margin_db')} dB")
        lines.append(
            f"- headline: bf16+fused {sweep.get('rps_bf16_fused')} req/s "
            f"vs f32-unfused {sweep.get('rps_f32_unfused')} req/s "
            f"({sweep.get('bf16_vs_f32_rps')}×), probe delta "
            f"{sweep.get('bf16_psnr_delta_db')} dB")
        lines += ["",
                  "| precision | fused | rps | goodput | expired | "
                  "built | probe PSNR (dB) | Δ vs f32 (dB) |",
                  "|---|---|---|---|---|---|---|---|"]
        for lane in sweep.get("lanes", []):
            lines.append(
                "| {} | {} | {} | {} | {} | {} | {} | {} |".format(
                    lane.get("precision"), lane.get("fused_step"),
                    fmt(lane.get("rps_served", 0.0)),
                    fmt(lane.get("rps_goodput", 0.0)),
                    lane.get("expired", 0),
                    lane.get("programs_built_delta", 0),
                    fmt(lane.get("probe_psnr_db", 0.0)),
                    fmt(lane.get("probe_delta_db", 0.0))))
    return lines


def state_memory_lines(rows):
    """Per-device train-state footprint from the judged train-bench
    records (bench.py `state_device_bytes`): params / opt_state / EMA in
    MB next to the sharding mode that produced them. With
    train.update_sharding=zero, opt+EMA should read ~1/data_shards of
    the replicated lane's numbers — this table is where BENCH_r* rounds
    check the memory claim without a device profiler."""
    lines = []
    body = []
    for name, d in rows:
        sb = d.get("state_device_bytes")
        if not isinstance(sb, dict):
            continue
        mb = {k: sb.get(k, 0) / 1e6 for k in
              ("params", "opt_state", "ema_params")}
        body.append(
            "| {} | {} | {} | {:.1f} | {:.1f} | {:.1f} | {:.1f} |".format(
                name, d.get("update_sharding", "?"),
                d.get("pipeline_stages", "?"), mb["params"],
                mb["opt_state"], mb["ema_params"],
                mb["params"] + mb["opt_state"] + mb["ema_params"]))
    if body:
        lines += ["", "## Train-state device memory (MB/device)", "",
                  "| entry | sharding | stages | params | opt_state | "
                  "ema | total |",
                  "|---|---|---|---|---|---|---|"] + body
    return lines


def chaos_lines(rows):
    """Per-phase tables for serve_bench --chaos artifacts: each injected
    fault against the requests it poisoned vs the requests it was NOT
    allowed to touch, and the fault-phase p99 against the same trace's
    steady-state — the latency cost of surviving."""
    lines = []
    for name, d in rows:
        chaos = d.get("chaos")
        if not isinstance(chaos, dict):
            continue
        lines += ["", f"## Chaos drills — {name}", ""]
        tr = chaos.get("trace", {})
        lines.append(
            f"- trace: {tr.get('requests_per_phase')} req/phase @ "
            f"{tr.get('rate_per_s')}/s (target "
            f"{tr.get('utilization_target')} utilization), mix "
            f"{tr.get('mix')}, max_batch {tr.get('max_batch')}")
        lines.append(
            f"- worst fault-phase p99 {chaos.get('p99_worst_fault_s')}s "
            f"vs steady {chaos.get('p99_steady_s')}s; anomalies "
            f"{chaos.get('anomalies_total')}, worker restarts "
            f"{chaos.get('worker_restarts_total')}, recompiles "
            f"{chaos.get('programs_built_delta')}")
        lines += ["",
                  "| phase | injected | ok | late | expired | rejected "
                  "| failed | p50 (s) | p99 (s) |",
                  "|---|---|---|---|---|---|---|---|---|"]
        for phase in ("steady", "nan", "worker_die", "swap_fail"):
            p = chaos.get("phases", {}).get(phase)
            if not p:
                continue
            lines.append(
                "| {} | {} | {} | {} | {} | {} | {} | {} | {} |".format(
                    phase, p.get("injected", "—"), p.get("ok", 0),
                    p.get("late", 0), p.get("expired", 0),
                    p.get("rejected", 0), p.get("failed", 0),
                    fmt(p.get("p50_s", 0.0)), fmt(p.get("p99_s", 0.0))))
        sw = chaos.get("phases", {}).get("swap_fail", {})
        if sw:
            lines.append(
                f"- swap breaker: {sw.get('swap_failures')} failure(s) "
                f"opened it, half-open probe recovered to v2="
                f"{sw.get('recovered_to_v2')} "
                f"({sw.get('swaps')} swap(s))")
    return lines


def mixed_res_lines(rows):
    """Per-resolution tables for serve_bench --mixed-res artifacts: the
    ladder's serving counterpart (one param tree, one service per rung
    resolution) with each lane's warm compile-counter deltas — the
    zero-recompile contract, per resolution."""
    lines = []
    for name, d in rows:
        mr = d.get("mixed_res")
        if not isinstance(mr, dict):
            continue
        lines += ["", f"## Mixed-resolution serving — {name}", ""]
        lines.append(
            f"- {mr.get('requests')} interleaved requests across "
            f"{mr.get('sidelengths')} px at {mr.get('sample_steps')} "
            f"step(s), buckets {mr.get('buckets')}: "
            f"{fmt(mr.get('rps', 0.0))} req/s")
        lines += ["",
                  "| resolution | requests | built Δ | jit Δ | "
                  "programs |", "|---|---|---|---|---|"]
        violated = []
        for res, lane in sorted(mr.get("per_resolution", {}).items(),
                                key=lambda kv: int(kv[0])):
            lines.append("| {}px | {} | {} | {} | {} |".format(
                res, lane.get("requests"),
                lane.get("programs_built_delta"),
                lane.get("jit_cache_entries_delta"),
                lane.get("programs_built_total")))
            if (lane.get("programs_built_delta")
                    or lane.get("jit_cache_entries_delta")):
                violated.append(res)
        lines.append("")
        if violated:
            lines.append("- **VIOLATION: warm mixed traffic recompiled "
                         f"in lane(s) {violated}px**")
        else:
            lines.append("- zero warm recompiles in every resolution "
                         "lane (contract held)")
    return lines


def gate_matrix_lines(search_dirs):
    """The promotion gate's corpus × resolution eval matrix
    (gate_matrix.json, written by `nvs3d registry promote` when the run
    trains a corpus mix or a resolution ladder): candidate vs incumbent
    PSNR per cell against the margin. Rounds without the artifact are
    named as skipped — 'no matrix' must read as 'the gate never probed a
    matrix', never as 'all cells passed'."""
    import glob

    lines = ["", "## Gate eval matrix (corpus × resolution, from "
                 "gate_matrix.json)", ""]
    found = []
    seen = set()
    for d in search_dirs:
        for path in sorted(glob.glob(
                os.path.join(d, "**", "gate_matrix.json"),
                recursive=True)):
            if path in seen:
                continue
            seen.add(path)
            try:
                with open(path) as fh:
                    doc = json.load(fh)
            except (OSError, json.JSONDecodeError):
                lines.append(f"- `{path}`: SKIPPED (malformed)")
                continue
            found.append((path, doc))
    if not found:
        lines.append("- none recorded — SKIPPED: no gate_matrix.json "
                     "under the scanned dirs (flat single-corpus run, or "
                     "the registry gate never ran)")
        return lines
    for path, doc in found:
        lines.append(
            f"- `{path}`: candidate {doc.get('candidate')} vs incumbent "
            f"{doc.get('incumbent')}, margin {doc.get('margin_db')} dB — "
            + ("**PASSED**" if doc.get("passed") else "**FAILED**"))
        lines += ["",
                  "| corpus | resolution | candidate (dB) | incumbent "
                  "(dB) | Δ (dB) | verdict |",
                  "|---|---|---|---|---|---|"]
        for cell in doc.get("cells", []):
            lines.append(
                "| {} | {}px | {} | {} | {} | {} |".format(
                    cell.get("corpus"), cell.get("resolution"),
                    fmt(cell.get("candidate_psnr", 0.0)),
                    fmt(cell.get("incumbent_psnr"))
                    if cell.get("incumbent_psnr") is not None else "—",
                    fmt(cell.get("delta_db", 0.0)),
                    "pass" if cell.get("passed")
                    else f"FAIL ({cell.get('reason')})"))
        lines.append("")
    return lines


def corpus_lines(search_dirs):
    """Per-corpus health + loss attribution from telemetry.jsonl
    `corpus_stats` rows (the mixer publishes one row per corpus per log
    interval): last-seen records/quarantine/decode-error counters next
    to the per-corpus training loss. Single-corpus runs are skipped
    LOUDLY, not silently."""
    import glob

    lines = ["", "## Corpus mix (per-corpus quarantine / loss, from "
                 "telemetry.jsonl corpus_stats rows)", ""]
    found = []
    for d in search_dirs:
        for path in sorted(glob.glob(
                os.path.join(d, "**", "telemetry.jsonl"),
                recursive=True)):
            last = {}   # corpus -> latest corpus_stats row
            steps = 0
            try:
                with open(path) as fh:
                    for line in fh:
                        try:
                            rec = json.loads(line)
                        except json.JSONDecodeError:
                            continue  # torn tail line
                        if rec.get("kind") != "corpus_stats":
                            continue
                        steps = max(steps, int(rec.get("step") or 0))
                        last[rec.get("corpus", "?")] = rec
            except OSError:
                continue
            if last:
                found.append((path, steps, last))
    if not found:
        lines.append("- none recorded — SKIPPED: no corpus_stats rows in "
                     "any scanned telemetry.jsonl (single-corpus run, or "
                     "a pre-mixer round)")
        return lines
    for path, steps, last in found:
        lines.append(f"- `{path}` (through step {steps}):")
        lines += ["",
                  "| corpus | weight | records | quarantined | decode "
                  "errs | draws | loss | samples |",
                  "|---|---|---|---|---|---|---|---|"]
        for name, rec in sorted(last.items()):
            loss = rec.get("loss")
            lines.append(
                "| {} | {} | {} | {} | {} | {} | {} | {} |".format(
                    name, fmt(rec.get("weight", 0.0)),
                    rec.get("records"), rec.get("quarantined"),
                    rec.get("decode_errors"),
                    rec.get("draws") if rec.get("draws") is not None
                    else "—",
                    fmt(loss) if isinstance(loss, (int, float))
                    and loss == loss else "—",
                    fmt(rec.get("samples", 0.0))))
        lines.append("")
    return lines


def numerics_lines(search_dirs):
    """Numerics-observatory digest per numerics.jsonl (obs/numerics.py):
    row/spike counts, the worst spike (group + z), and any anomaly
    events whose detail names a first_bad_layer — the per-layer-group
    NaN provenance next to the numbers it poisoned. Runs recorded
    before the observatory (or with train.numerics.enabled=false) are
    skipped LOUDLY, not silently."""
    import csv
    import glob

    lines = ["", "## Numerics (grad/param norms per layer group, "
                 "from numerics.jsonl)", ""]
    found = []
    for d in search_dirs:
        for path in sorted(glob.glob(
                os.path.join(d, "**", "numerics.jsonl"), recursive=True)):
            rows = spikes = 0
            worst = None  # (z, group, step)
            groups = set()
            try:
                with open(path) as fh:
                    for line in fh:
                        try:
                            rec = json.loads(line)
                        except json.JSONDecodeError:
                            continue  # torn tail line
                        if rec.get("kind") == "numerics":
                            rows += 1
                            groups.update(rec.get("groups") or {})
                        elif rec.get("kind") == "numerics_spike":
                            spikes += 1
                            z = float(rec.get("z", 0.0))
                            if worst is None or z > worst[0]:
                                worst = (z, rec.get("group", "?"),
                                         rec.get("step"))
            except OSError:
                continue
            found.append((path, rows, len(groups), spikes, worst))
    if not found:
        lines.append("- none recorded — SKIPPED: no numerics.jsonl under "
                     "the scanned dirs (pre-observatory round, or the run "
                     "trained with train.numerics.enabled=false)")
        return lines
    for path, rows, n_groups, spikes, worst in found:
        spike_txt = f" spikes={spikes}"
        if worst is not None:
            spike_txt += (f" (worst z={worst[0]:.1f} group={worst[1]}"
                          f" step={worst[2]})")
        lines.append(f"- `{path}`: rows={rows} groups={n_groups}"
                     + spike_txt)
    # Anomaly provenance: the guard stamps first_bad_layer=<group> into
    # the anomaly event detail; a NaN with a named layer group belongs
    # in the same digest as the spike that preceded it.
    for d in search_dirs:
        for path in sorted(glob.glob(
                os.path.join(d, "**", "events.csv"), recursive=True)):
            try:
                with open(path, newline="") as fh:
                    for row in csv.DictReader(fh):
                        if (row.get("event") == "anomaly"
                                and "first_bad_layer="
                                in (row.get("detail") or "")):
                            lines.append(
                                f"- anomaly `{path}` step="
                                f"{row.get('step')}: {row.get('detail')}")
            except (OSError, csv.Error):
                continue
    return lines


def costmap_lines(search_dirs, rows):
    """Per-op FLOPs attribution: the top ops from each costmap.json
    (obs/compiles.xunet_costmap) plus any cost map embedded in a judged
    bench record. Rounds banked before the cost map existed are named
    as skipped so 'no table' never reads as 'no cost'."""
    import glob

    lines = ["", "## Cost map (per-op FLOPs/bytes, from costmap.json)", ""]
    maps = []  # (origin, rows)
    seen = set()
    for d in search_dirs:
        for path in sorted(glob.glob(
                os.path.join(d, "**", "costmap.json"), recursive=True)):
            if path in seen:
                continue
            seen.add(path)
            try:
                with open(path) as fh:
                    doc = json.load(fh)
            except (OSError, json.JSONDecodeError):
                lines.append(f"- `{path}`: SKIPPED (malformed)")
                continue
            maps.append((path, doc.get("ops", [])))
    for name, d in rows:
        cm = d.get("costmap")
        if isinstance(cm, list) and cm:
            maps.append((f"{name} (embedded)", cm))
    if not maps:
        lines.append("- none recorded — SKIPPED: no costmap.json and no "
                     "embedded costmap in any judged record (pre-cost-map "
                     "round, or bench ran with NVS3D_BENCH_COST=0)")
        return lines
    for origin, ops in maps:
        costed = [r for r in ops
                  if isinstance(r.get("flops"), (int, float))]
        total = sum(r["flops"] for r in costed)
        lines.append(f"- `{origin}`: {len(ops)} ops, "
                     f"total {total / 1e9:.2f} GFLOP")
        if not costed:
            lines.append("  - SKIPPED: no per-op flops (cost_analysis "
                         "returned the legacy list form)")
            continue
        top = sorted(costed, key=lambda r: r["flops"], reverse=True)[:5]
        lines += ["", "  | op | group | GFLOP | share | MB |",
                  "  |---|---|---|---|---|"]
        for r in top:
            byts = r.get("bytes")
            lines.append(
                "  | {} {} | {} | {:.2f} | {:.1%} | {} |".format(
                    r.get("op"), r.get("name", r.get("kind", "?")),
                    r.get("group"), r["flops"] / 1e9,
                    r["flops"] / total if total else 0.0,
                    f"{byts / 1e6:.1f}"
                    if isinstance(byts, (int, float)) else "-"))
        lines.append("")
    return lines


def doctor_lines(search_dirs, repo_root):
    """Performance-observatory digest: the top ranked findings from any
    banked doctor.json (obs/doctor.py's cross-run regression doctor)
    plus the roofline top-k headroom table for runs that captured
    continuous-profiler windows. Both joins are loud about absence —
    'no doctor verdict' must read as 'doctor never ran', never as
    'nothing wrong'."""
    import glob

    lines = ["", "## Doctor (ranked cross-run diagnosis + roofline "
                 "headroom, from doctor.json / profile windows)", ""]
    docs = []  # (path, findings)
    seen = set()
    for d in search_dirs:
        for path in sorted(glob.glob(
                os.path.join(d, "**", "doctor.json"), recursive=True)):
            if path in seen:
                continue
            seen.add(path)
            try:
                with open(path) as fh:
                    doc = json.load(fh)
            except (OSError, json.JSONDecodeError):
                lines.append(f"- `{path}`: SKIPPED (malformed)")
                continue
            docs.append((path, doc.get("findings") or []))
    if docs:
        for path, findings in docs:
            lines.append(f"- `{path}`: {len(findings)} finding(s)")
            for f in findings[:3]:
                lines.append(
                    "  - [{}] {}{}".format(
                        str(f.get("severity", "?")).upper(),
                        f.get("title", ""),
                        f" — {f['detail']}" if f.get("detail") else ""))
    else:
        lines.append("- none recorded — SKIPPED: no doctor.json under "
                     "the scanned dirs (run `nvs3d obs doctor "
                     "--trajectory --out RUN/doctor.json` to bank a "
                     "verdict)")
    # Roofline: measured per-group device time (continuous-profiler
    # windows in telemetry.jsonl) joined against costmap FLOPs/bytes.
    # Needs the package importable — summarize_bench is otherwise
    # stdlib-only, so the join degrades to a named skip, not a crash.
    lines += ["", "### Roofline (measured group time vs costmap "
                  "FLOPs/bytes)", ""]
    try:
        if repo_root not in sys.path:
            sys.path.insert(0, repo_root)
        from novel_view_synthesis_3d_tpu.obs import roofline
    except ImportError:
        lines.append("- SKIPPED: novel_view_synthesis_3d_tpu not "
                     "importable from this checkout — no roofline join")
        return lines
    run_dirs = []
    for d in search_dirs:
        for path in sorted(glob.glob(
                os.path.join(d, "**", "telemetry.jsonl"),
                recursive=True)):
            run_dirs.append(os.path.dirname(path))
    if not run_dirs:
        lines.append("- SKIPPED: no telemetry.jsonl under the scanned "
                     "dirs — no profile windows to attribute")
        return lines
    reported = False
    for rd in run_dirs:
        try:
            report = roofline.analyze_run(rd)
        except Exception as exc:  # noqa: BLE001 — digest must not crash
            lines.append(f"- `{rd}`: SKIPPED (roofline failed: {exc})")
            continue
        if not report.get("rows"):
            continue  # no profile windows in this run; note below
        reported = True
        lines.append(f"- `{rd}`:")
        for note in report.get("notes") or []:
            lines.append(f"  - note: {note}")
        # Headroom needs chip peaks (TPU); on peak-less runs fall back
        # to the biggest measured time sinks so the table never empties.
        top = (roofline.top_headroom(report["rows"], k=3)
               or report["rows"][:3])
        for r in top:
            mfu = r.get("mfu")
            lines.append(
                "  - {}: {:.1f}ms {}{}".format(
                    r.get("group"), 1e3 * float(r.get("time_s") or 0.0),
                    r.get("bound", "?"),
                    f" mfu={mfu:.1%}" if isinstance(mfu, float) else ""))
    if not reported:
        lines.append("- SKIPPED: no profile_window rows in any scanned "
                     "telemetry.jsonl (obs.profile.enabled=false, or a "
                     "pre-observatory round)")
    return lines


def main() -> int:
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    out_dir = args[0] if args else os.path.join("results", "tpu_r04")
    lines = [
        f"# Bench summary — {out_dir}", "",
        "| entry | metric | value | unit | vs_baseline | platform | mfu "
        "| precision | fused_step | sharding | stages |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    rows = load_rows(out_dir)
    for name, d in rows:
        lines.append(
            "| {} | {} | {} | {} | {} | {} | {} | {} | {} | {} | {} |"
            .format(
                name, d.get("metric", "?"), fmt(d.get("value", "?")),
                d.get("unit", ""), fmt(d.get("vs_baseline", "")),
                d.get("platform", "?"),
                fmt(d.get("mfu", "")) if d.get("mfu") else "",
                d.get("precision", ""), d.get("fused_step", ""),
                d.get("update_sharding", ""),
                d.get("pipeline_stages", "")))
    if not rows:
        lines.append("| (no artifacts yet) | | | | | | | | | | |")
    # Per-device train-state footprint (PR 13): rows that carry the
    # measured params/opt/EMA byte breakdown — the number the zero
    # update-sharding lane exists to shrink.
    lines += state_memory_lines(rows)
    # Quality summaries live in sibling dirs; pull their headline if there.
    for qdir in sorted(d for d in os.listdir("results")
                       if d.startswith("quality_tpu")):
        summary = os.path.join("results", qdir, "summary.json")
        if os.path.exists(summary):
            with open(summary) as fh:
                s = json.load(fh)
            lines.append(
                "| {} | {} | {} | {} | | {} | |".format(
                    qdir, s.get("metric"), fmt(s.get("value")),
                    s.get("unit"), s.get("platform")))
    # Per-step-class latency tables for any serve_bench --continuous
    # artifacts in the dir (the step-level continuous-batching scenario).
    lines += continuous_lines(rows)
    # Precision/fused-step lanes for any --precision-sweep artifacts.
    lines += precision_sweep_lines(rows)
    # Ring-native vs naive orbit serving for --trajectory artifacts.
    lines += trajectory_serving_lines(rows)
    # Conditioning-cache A/B for --cond-cache artifacts.
    lines += cond_cache_lines(rows)
    # Survivability drill tables for any --chaos artifacts.
    lines += chaos_lines(rows)
    # Per-resolution zero-recompile lanes for --mixed-res artifacts.
    lines += mixed_res_lines(rows)
    # The restored CPU-lane trajectory from the repo-root BENCH archives,
    # and the multichip dry-run contract trajectory next to it.
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lines += cpu_lane_lines(repo_root)
    lines += multichip_lines(repo_root)
    # Recovery events: every training metrics.csv under the bench dir (and
    # the quality sibling dirs) that recorded anomaly-guard skips or
    # checkpoint rollbacks. "none" is an explicit claim, not silence.
    quality_dirs = ([os.path.join("results", d) for d in os.listdir("results")
                     if d.startswith("quality_tpu")]
                    if os.path.isdir("results") else [])
    recov = recovery_rows([out_dir] + quality_dirs)
    lines += ["", "## Recovery events (anomaly guard / rollbacks / "
                  "supervised restarts)", ""]
    if recov:
        for path, anomalies, rollbacks, restarts in recov:
            lines.append(f"- `{path}`: anomalies={anomalies} "
                         f"rollbacks={rollbacks} restarts={restarts}")
    else:
        lines.append("- none recorded")
    # Telemetry: span percentiles + peak device memory from each run's
    # JSONL sink (obs/bus.py) — where did step time go, and did HBM creep.
    telem = telemetry_rows([out_dir] + quality_dirs)
    lines += ["", "## Telemetry (span percentiles / peak device memory, "
                  "from telemetry.jsonl)", ""]
    if telem:
        for path, phases, peak_bytes, versions, swaps in telem:
            peak = (f" peak_device_bytes={peak_bytes / 1e9:.2f}G"
                    if peak_bytes else "")
            lines.append(f"- `{path}`:{peak}")
            for name, (n, p50, p90, p99, _total) in phases.items():
                lines.append(
                    f"  - {name}: n={n} p50={p50 * 1e3:.1f}ms "
                    f"p90={p90 * 1e3:.1f}ms p99={p99 * 1e3:.1f}ms")
            if versions:
                # Model lifecycle: which registry versions served this
                # run, in order, and how many hot swaps landed.
                lines.append(
                    f"  - model versions: {' -> '.join(versions)} "
                    f"(swaps={swaps})")
    else:
        lines.append("- none recorded")
    # Input-pipeline health: did the loader ever sit on the step loop's
    # critical path (data_fetch vs train_step, overlap ratio)?
    lines += input_pipeline_lines(telem)
    # Numerics observatory + per-op cost attribution: spike/anomaly
    # digest from numerics.jsonl and the top-FLOPs ops from each
    # costmap.json (or the copy embedded in a judged bench record).
    lines += numerics_lines([out_dir] + quality_dirs)
    lines += costmap_lines([out_dir] + quality_dirs, rows)
    # Corpus mixer + ladder observability: per-corpus quarantine/loss
    # tables from telemetry and the promotion gate's corpus × resolution
    # eval matrix. Both are loud about absence.
    lines += corpus_lines([out_dir] + quality_dirs)
    lines += gate_matrix_lines([out_dir] + quality_dirs)
    # Performance observatory: ranked doctor findings + roofline
    # headroom for runs that captured continuous-profiler windows.
    lines += doctor_lines([out_dir] + quality_dirs, repo_root)
    text = "\n".join(lines) + "\n"
    print(text)
    if "--write" in sys.argv:
        out = sys.argv[sys.argv.index("--write") + 1]
        with open(out, "w") as fh:
            fh.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())

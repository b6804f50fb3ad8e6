"""Perf-regression sentry over the banked BENCH_r*/MULTICHIP_r* rounds.

The bench archives are append-only JSON snapshots the round driver
banks at the repo root; until now nothing READ them adversarially —
BENCH_r09 landed at vs_baseline=0.973 (a 2.7% regression against the
CPU-lane trajectory) with rc=0 and nobody noticed. This tool judges the
NEWEST judgeable round of each trajectory against the rolling median of
its predecessors and exits loudly on a regression:

  - rc 0: newest round of every trajectory is healthy (or nothing is
    judgeable yet — an empty archive is not a regression);
  - rc ``REGRESSION_RC`` (4): the newest judgeable round regressed.
    DISTINCT from bench.py's rc=3 (infra refusal: backend probe failed,
    nothing was measured) — a sentry trip means the bench RAN and the
    number got worse, which is a different on-call page.

Judging rules:

  - BENCH_r*: a round is judgeable when rc==0 and ``parsed`` carries a
    numeric ``vs_baseline`` (rc=3/124 probe/timeout rounds with
    ``parsed: null`` are infra, skipped with a note). The newest
    judgeable round regresses when vs_baseline < 1.0 (slower than its
    own baseline — absolute) OR vs_baseline < median(prior judgeable
    rounds) * (1 - tolerance) (drifting below its own trajectory).
  - MULTICHIP_r*: no parsed metric to compare, so the contract is
    judged instead: rc==0 rounds regress when ok!=true, skipped==true,
    or n_devices shrank below the largest previously demonstrated mesh.

Usage:
    python tools/bench_sentry.py                  # judge repo-root archives
    python tools/bench_sentry.py --dir DIR --json
    python tools/bench_sentry.py --fresh-vs 0.98  # judge an un-banked
                                                  # datapoint as round +1

bench.py runs this in-process after emitting its judged line (exits 4
only under NVS3D_BENCH_SENTRY=1 so archived trajectories keep their rc
semantics). tests/test_bench_sentry.py pins the rc contract
against synthetic trajectories and the real r01–r09 archive.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
from typing import List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# rc=3 is bench.py's "infra refused to measure"; the sentry's "measured
# and got slower" must never be conflated with it.
REGRESSION_RC = 4
DEFAULT_TOLERANCE_PCT = 2.0

_ROUND_RE = re.compile(r"_r(\d+)\.json$")


def load_rounds(dirpath: str, prefix: str) -> List[dict]:
    """[{round, path, doc}] for ``{prefix}_r*.json``, oldest first.
    Unreadable/torn files become unjudgeable rounds, not crashes."""
    out = []
    for path in glob.glob(os.path.join(dirpath, f"{prefix}_r*.json")):
        m = _ROUND_RE.search(path)
        if not m:
            continue
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            doc = None
        out.append({"round": int(m.group(1)), "path": path, "doc": doc})
    out.sort(key=lambda r: r["round"])
    return out


def bench_verdicts(rounds: List[dict],
                   tolerance_pct: float = DEFAULT_TOLERANCE_PCT,
                   fresh_vs: Optional[float] = None) -> List[dict]:
    """Per-round verdicts over a BENCH trajectory. ``fresh_vs`` judges
    an un-banked datapoint (the round bench.py just measured) as the
    newest round without writing it anywhere."""
    points = []
    for r in rounds:
        doc = r["doc"] or {}
        parsed = doc.get("parsed") or {}
        vs = parsed.get("vs_baseline")
        if doc.get("rc") != 0 or not isinstance(vs, (int, float)):
            points.append({
                "round": r["round"], "judged": False,
                "note": (f"rc={doc.get('rc')}"
                         + ("" if parsed else ", parsed=null")
                         + " — infra, not judged")})
            continue
        points.append({"round": r["round"], "judged": True,
                       "vs_baseline": float(vs),
                       "lane": parsed.get("lane")
                       or parsed.get("platform")})
    if fresh_vs is not None:
        last = points[-1]["round"] if points else 0
        points.append({"round": last + 1, "judged": True,
                       "vs_baseline": float(fresh_vs), "lane": "fresh"})
    prior: List[float] = []
    for p in points:
        if not p["judged"]:
            continue
        vs = p["vs_baseline"]
        floor = None
        if prior:
            floor = statistics.median(prior) * (1.0
                                                - tolerance_pct / 100.0)
        p["median_prior"] = (round(statistics.median(prior), 3)
                             if prior else None)
        p["regressed"] = bool(vs < 1.0
                              or (floor is not None and vs < floor))
        why = []
        if vs < 1.0:
            why.append(f"vs_baseline {vs} < 1.0")
        if floor is not None and vs < floor:
            why.append(f"{vs} < median({p['median_prior']}) "
                       f"- {tolerance_pct:g}%")
        p["note"] = "; ".join(why) if why else "ok"
        prior.append(vs)
    return points


def multichip_verdicts(rounds: List[dict]) -> List[dict]:
    """MULTICHIP rounds carry no parsed metric; the judged contract is
    ok/skipped/n_devices (the mesh must not silently shrink)."""
    points = []
    best_devices = 0
    for r in rounds:
        doc = r["doc"] or {}
        if doc.get("rc") != 0:
            points.append({"round": r["round"], "judged": False,
                           "note": f"rc={doc.get('rc')} — infra, "
                                   "not judged"})
            continue
        n_dev = int(doc.get("n_devices") or 0)
        ok = bool(doc.get("ok"))
        skipped = bool(doc.get("skipped"))
        why = []
        if not ok:
            why.append("ok=false")
        if skipped:
            why.append("skipped=true")
        if best_devices and n_dev < best_devices:
            why.append(f"n_devices shrank {best_devices} -> {n_dev}")
        points.append({"round": r["round"], "judged": True,
                       "n_devices": n_dev, "ok": ok, "skipped": skipped,
                       "regressed": bool(why),
                       "note": "; ".join(why) if why else "ok"})
        best_devices = max(best_devices, n_dev)
    return points


def doctor_attribution(prior_docs: List[dict],
                       newest_doc: Optional[dict]) -> dict:
    """Trip attribution, delegated to the obs regression doctor
    (novel_view_synthesis_3d_tpu/obs/doctor.py — the ranked diagnosis
    engine this tool's ad-hoc attribute_regression grew into). Returns
    {"summary": one-liner or None, "findings": ranked list} so the
    rc=4 page can embed the doctor's top findings, not just one line."""
    if not newest_doc:
        return {"summary": None, "findings": []}
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    try:
        from novel_view_synthesis_3d_tpu.obs import doctor as doctor_lib
    except ImportError:
        return {"summary": ("obs.doctor unavailable (package not "
                            "importable from this checkout) — no "
                            "attribution"), "findings": []}
    return doctor_lib.attribute_fresh(prior_docs, newest_doc)


def judge(dirpath: str,
          tolerance_pct: float = DEFAULT_TOLERANCE_PCT,
          fresh_vs: Optional[float] = None,
          fresh_doc: Optional[dict] = None) -> dict:
    """Whole-archive verdict: ``regressed`` is True iff the NEWEST
    judgeable round of either trajectory regressed (older regressions
    are history — they already had their round to page). ``fresh_doc``
    (the judged record bench.py just built, when judging ``fresh_vs``)
    feeds the trip attribution its span/costmap telemetry."""
    rounds = load_rounds(dirpath, "BENCH")
    bench = bench_verdicts(rounds, tolerance_pct, fresh_vs=fresh_vs)
    multichip = multichip_verdicts(load_rounds(dirpath, "MULTICHIP"))

    def newest(points):
        judged = [p for p in points if p["judged"]]
        return judged[-1] if judged else None

    nb, nm = newest(bench), newest(multichip)
    attribution = None
    doctor: List[dict] = []
    if nb and nb["regressed"]:
        judged_docs = [(r["doc"] or {}).get("parsed") or {}
                       for r in rounds
                       if (r["doc"] or {}).get("rc") == 0]
        if fresh_vs is not None:
            diag = doctor_attribution(judged_docs, fresh_doc)
        elif judged_docs:
            diag = doctor_attribution(judged_docs[:-1], judged_docs[-1])
        else:
            diag = {"summary": None, "findings": []}
        attribution = diag["summary"]
        doctor = diag["findings"]
    return {
        "bench": bench,
        "multichip": multichip,
        "newest_bench": nb,
        "newest_multichip": nm,
        "regressed": bool((nb and nb["regressed"])
                          or (nm and nm["regressed"])),
        "attribution": attribution,
        "doctor": doctor,
        "tolerance_pct": tolerance_pct,
    }


def _print_points(label: str, points: List[dict]) -> None:
    print(f"{label}:")
    if not points:
        print("  (no rounds)")
    for p in points:
        if not p["judged"]:
            print(f"  r{p['round']:02d}  -        SKIP   {p['note']}")
            continue
        flag = "REGRESS" if p["regressed"] else "ok"
        val = (f"{p['vs_baseline']:.3f}" if "vs_baseline" in p
               else f"{p['n_devices']}dev")
        print(f"  r{p['round']:02d}  {val:<8s} {flag:<6s} {p['note']}")


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--dir", default=REPO,
                        help="archive dir holding BENCH_r*.json / "
                             "MULTICHIP_r*.json (default: repo root)")
    parser.add_argument("--tolerance-pct", type=float,
                        default=float(os.environ.get(
                            "NVS3D_SENTRY_TOLERANCE_PCT",
                            DEFAULT_TOLERANCE_PCT)),
                        help="allowed drift below the rolling median "
                             "before flagging (default 2)")
    parser.add_argument("--fresh-vs", type=float, default=None,
                        help="judge this un-banked vs_baseline as the "
                             "newest BENCH round")
    parser.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)
    verdict = judge(args.dir, args.tolerance_pct,
                    fresh_vs=args.fresh_vs)
    if args.json:
        print(json.dumps(verdict))
    else:
        _print_points("BENCH", verdict["bench"])
        _print_points("MULTICHIP", verdict["multichip"])
        print("verdict: "
              + ("REGRESSION (newest round below trajectory)"
                 if verdict["regressed"] else "healthy"))
        if verdict["regressed"] and verdict.get("attribution"):
            print(f"attribution: {verdict['attribution']}")
        # Doctor embedding: the rc=4 page carries the top ranked
        # findings, so the on-call reads WHAT moved without re-running
        # anything.
        for i, f in enumerate(verdict.get("doctor") or [], 1):
            if i > 3:
                break
            print(f"doctor {i}. [{f.get('severity', '?').upper()}] "
                  f"{f.get('title', '')}")
    return REGRESSION_RC if verdict["regressed"] else 0


if __name__ == "__main__":
    sys.exit(main())

"""Several runs of one cell in one chip call, each a process of its own
(as the driver runs them), results gathered as JSON lines.

    chiprun -- python benchmarks/tools/runs.py --workload <cell> \
        --seeds 1,2,3,4,5,6 --seconds 40 [--trace 0] [--sets 2]

This launcher never touches JAX, so each child gets the chip. With
--sets 2 the seeds are run twice over (two sets of the same seeds).
Every child's last line is appended to <out-dir>/runs_<cell>.jsonl with
its seed, set and wall seconds; spreads (quartile distance over the
median, statistics.quantiles n=4) are printed at the end per set.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--out-dir", default="chiprun_out")
    args = ap.parse_args()
    os.makedirs(args.out_dir, exist_ok=True)
    log = os.path.join(args.out_dir, f"runs_{args.workload}.jsonl")
    sets = []
    for k in range(args.sets):
        rows = []
        for seed in args.seeds.split(","):
            t0 = time.time()
            p = subprocess.run(
                [sys.executable, "benchmarks/run.py", "--workload",
                 args.workload, "--seed", seed, "--seconds",
                 f"{args.seconds:g}", "--trace", str(args.trace)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            last = (p.stdout.strip().splitlines() or [""])[-1]
            try:
                out = json.loads(last)
            except ValueError:
                out = {"error": p.stderr[-2000:]}
            row = {"workload": args.workload, "seed": int(seed), "set": k,
                   "trace": args.trace, "rc": p.returncode,
                   "wall_s": time.time() - t0, **out,
                   "log": [ln for ln in p.stderr.splitlines()
                           if ln.startswith("[bench ")],
                   "compares": [ln for ln in p.stdout.splitlines()
                                if ln.startswith("compare")]}
            rows.append(row)
            with open(log, "a") as fh:
                fh.write(json.dumps(row) + "\n")
            print(json.dumps({k_: row.get(k_) for k_ in (
                "seed", "set", "rc", "wall_s", "correct", "failed",
                "metrics", "error")}), flush=True)
            for ln in row["compares"]:
                print("   ", ln, flush=True)
            if p.returncode != 0:
                # The rest would fail the same way: keep the chip time.
                print(p.stderr[-3000:], flush=True)
                return 1
        sets.append(rows)
    for k, rows in enumerate(sets):
        names = sorted({n for r in rows for n in r.get("metrics", {})})
        for n in names:
            vals = [r["metrics"][n]["value"] for r in rows
                    if n in r.get("metrics", {})]
            if len(vals) >= 2:
                print(f"set {k} {n}: median {statistics.median(vals):.6g} "
                      f"spread {spread(vals):.5f} n={len(vals)} "
                      f"min {min(vals):.6g} max {max(vals):.6g}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

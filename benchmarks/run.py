"""The benchmark's entry.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no children. Resolves the cell from files (harness.load_cell),
refuses to measure without the chips the cell asks for, runs the cell's
kind, and prints ONE JSON object as its last line of standard output: the
cell's end-to-end metrics (`--trace 0`) or its per-layer metrics
(`--trace 1`, which also opens the benchmark's own profiler capture over
the first seconds of the window). Everything else goes to stderr or to
earlier lines, and to benchmarks/out/.

`--rehearse` (CPU, tiny sizes from benchmarks/rehearse.json) drives the
same code path for the tests; it never prints a result line and exits 4.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

EXIT_NO_CHIP = 3
EXIT_REHEARSAL = 4


def measure(cell, seed, seconds, trace_on, env):
    """Run the cell's kind and reduce what it hands back to the metrics of
    this run. → (result dict of the last line, the kind's raw result)."""
    import harness
    import trace_reduce

    res = cell["kind"].run(cell, seed, seconds, trace_on, env)
    t0, t1 = res["window"]
    in_window = env["compiles"].in_window
    correct = bool(res["correct"])
    if in_window:
        harness.compare("compilations_in_window", in_window, 0,
                        res["numbers"])
        correct = False
    device = harness.device_info(cell["chips"], res["memory"])
    values = dict(res["end_to_end"], setup_s=t0 - env["t_start"])
    metrics = {}
    out = {"correct": correct, "attempted": int(res["attempted"]),
           "failed": int(res["failed"])}
    if not trace_on:
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        spans = [s for s in res["spans"] if t0 <= s["end"] <= t1]
        tw = res["trace"]
        trace = None
        if tw.xplane():
            raw = trace_reduce.read(tw.xplane())
            trace = trace_reduce.reduce(raw, owners=res.get("owners", ()))
        counters = dict(res["counters"], window_s=t1 - t0,
                        peaks=harness.read_json(HERE, "peaks.json"),
                        device_kind=device["kind"],
                        reserved_peak_bytes=res["memory"]["reserved"],
                        end_to_end=values)
        for m in cell["per_layer"]:
            try:
                v = harness.layer_reader(m["name"], cell["bench_dir"])(
                    spans, trace, counters)
            except KeyError:
                if not env.get("rehearse"):
                    raise
                v = None  # a CPU has no row in the table of peaks
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if trace is not None:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            out["breakdown"] = {"device_ops": trace["device_ops"],
                                "idle_gaps": trace["idle_gaps"]}
    out["metrics"] = metrics
    out["device"] = device
    return out, res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import harness

    if not os.path.isdir(os.path.join(ROOT, "novel_view_synthesis_3d_tpu")):
        print("benchmark: the system under test is not in this checkout",
              file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    rehearse = harness.read_json(HERE, "rehearse.json") if args.rehearse \
        else None
    if rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        cell["traffic"] = dict(cell["traffic"],
                               **rehearse["traffic"].get(
                                   cell["traffic"]["kind"], {}))

    import jax

    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu"
                         or len(devs) < cell["chips"]):
        print(f"benchmark: {args.workload} needs {cell['chips']} TPU chip(s);"
              f" JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return EXIT_NO_CHIP
    # The program's one cache helper: JAX_COMPILATION_CACHE_DIR if set,
    # else <checkout>/.jax_cache — a fixed path inside the checkout.
    from novel_view_synthesis_3d_tpu.utils.xla_cache import (
        setup_compilation_cache)

    cache_dir = setup_compilation_cache()
    out_dir = os.path.join(HERE, "out", args.workload)
    env = {"t_start": T_START, "compiles": harness.CompileCounter(),
           "rehearse": rehearse, "out_dir": out_dir}
    harness.log(f"{args.workload} seed {args.seed} {args.seconds:g} s "
                f"trace {args.trace}; compile cache {cache_dir}")
    out, res = measure(cell, args.seed, args.seconds, bool(args.trace), env)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"last_trace{args.trace}.json"),
              "w") as fh:
        json.dump({"result": out, "numbers": res["numbers"],
                   "counters": res["counters"],
                   "programs_built": env["compiles"].total,
                   "compile_s": env["compiles"].total_s,
                   "total_s": time.perf_counter() - T_START}, fh, indent=1)
    sys.stdout.flush()
    if rehearse:
        print("rehearsal " + json.dumps(out), file=sys.stderr)
        return EXIT_REHEARSAL
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Device milliseconds per sampler call, by the layer kind the program
stamps (`layer_ms_per_call.<kind>`; the variant is the kind): the self
time of that kind's instructions in the traced window over the times the
window's heaviest program ran, averaged over the chips used. The kinds of
one run add up to the device's busy time per call, and the reader refuses
a capture whose kinds do not add up to the busy time run.py read. Layers:
Model (conv, gn, attn, emb), Samplers (pose, update), Device (other,
unattributed).

Reads the run's capture the way `harness.TraceWindow.xplane()` finds it
(the newest `*.xplane.pb` under `out/<--workload>/trace`), reduces it once
per process (`scope_reduce.reduce` keeps its result) and leaves the whole
reduction — kinds, blocks by `og.<label>`, the instructions no kind
reaches — beside it as `out/<--workload>/layers.json`. None on a run
without a capture. None, with a line on stderr, on a program that has no
vocabulary function: the driver lays these files over the parent commit's
checkout for its traced runs, and a reader may not fail there.
"""

import glob
import json
import os
import sys
import time

import scope_reduce

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = ("other", "unattributed")  # beside the program's LAYER_KINDS


def _capture():
    """(out/<workload>, its newest capture) or None."""
    argv = sys.argv
    if "--workload" not in argv[:-1]:
        return None
    out_dir = os.path.join(HERE, "out", argv[argv.index("--workload") + 1])
    files = sorted(glob.glob(os.path.join(
        out_dir, "trace", "plugins", "profile", "*", "*.xplane.pb")))
    return (out_dir, files[-1]) if files else None


def compute(spans, trace, counters):
    found = _capture() if trace is not None else None
    if found is None:
        return None
    try:
        from novel_view_synthesis_3d_tpu.models.xunet import (
            LAYER_KINDS, layer_of)
    except ImportError:
        print("layer_ms_per_call: this program stamps no layer kinds "
              "(models/xunet.layer_of is not there)", file=sys.stderr)
        return None
    kind = counters["variant"]
    if kind not in LAYER_KINDS + KINDS:
        raise ValueError(f"layer_ms_per_call: no layer kind {kind!r}")
    out_dir, path = found
    t0 = time.perf_counter()
    misses = scope_reduce.reduce.cache_info().misses
    red = scope_reduce.reduce(path, layer_of)
    if not red or not red["module_runs"]:
        return None
    if abs(red["total_s"] - trace["busy_s"]) > 0.01 * trace["busy_s"]:
        raise ValueError(
            f"layer_ms_per_call: the kinds of {path} add up to "
            f"{red['total_s']:.6f} s, the run's device was busy "
            f"{trace['busy_s']:.6f} s: not this run's capture?")
    if scope_reduce.reduce.cache_info().misses > misses:
        # The first of a run's readers: this call did the reducing.
        calls = sorted((s["ts"], s["end"]) for s in spans)
        with open(os.path.join(out_dir, "layers.json"), "w") as fh:
            json.dump(dict(
                red, reduce_s=time.perf_counter() - t0,
                capture_bytes=os.path.getsize(path),
                # Where a traced run writes its capture out: the longest
                # host gap between two calls of the window.
                longest_gap_between_calls_s=max(
                    (b[0] - a[1] for a, b in zip(calls, calls[1:])),
                    default=0.0)), fh, indent=1)
    return 1e3 * red["by_kind_s"].get(kind, 0.0) / red["module_runs"]

"""Device milliseconds per sampler call under one part of a layer kind
(`part_ms_per_call.<kind>.<part>`; the variant is `<kind>.<part>`): the
self time of the instructions the program stamps `pt.<part>` inside that
`lk.<kind>`, over the runs of the window's heaviest program. Parts are
the program's four (models/vocab.py: `kernel` — a Pallas call itself —,
`layout` — what its wrapper does to feed it and hand its result back —,
`gather` — rows between token and expert order —, `matmul` — a dense
product); what a kind holds outside every part is its remainder,
`layer_ms_per_call.<kind>` − Σ parts. Layers: Kernels (kernel, layout),
Model (gather, matmul).

The same reduction as `layer_ms_per_call` (`scope_reduce.reduce` over the
run's capture) with the program's second vocabulary function,
`layer_part_of`; its keys must add up to the busy time run.py read, like
the kinds'. The first of a run's part readers leaves the whole of it —
every `<kind>` remainder and `<kind>.<part>` — beside `layers.json` as
`out/<--workload>/parts.json`. None on a run without a capture; None on a
program without `layer_part_of`: the driver lays these files over the
parent commit's checkout for its traced runs, and a reader may not fail
there.
"""

import json
import os
import time

import scope_reduce
import stamped_time


def compute(spans, trace, counters):
    try:
        from novel_view_synthesis_3d_tpu.models.xunet import (
            LAYER_KINDS, LAYER_PARTS, layer_part_of)
    except ImportError:
        return None
    kind, _, part = counters["variant"].rpartition(".")
    if kind not in LAYER_KINDS or part not in LAYER_PARTS:
        raise ValueError("part_ms_per_call: no part "
                         f"{counters['variant']!r} of a layer kind")
    t0 = time.perf_counter()
    misses = scope_reduce.reduce.cache_info().misses
    red = stamped_time.reduction(trace, layer_of=layer_part_of)
    if red is None:
        return None
    if abs(red["total_s"] - trace["busy_s"]) > 0.01 * trace["busy_s"]:
        raise ValueError(
            f"part_ms_per_call: the parts add up to {red['total_s']:.6f} s,"
            f" the run's device was busy {trace['busy_s']:.6f} s: not this "
            "run's capture?")
    if scope_reduce.reduce.cache_info().misses > misses:
        # The first of a run's part readers: this call did the reducing.
        out_dir = stamped_time._capture()[0]
        with open(os.path.join(out_dir, "parts.json"), "w") as fh:
            json.dump({"ms_per_call": {
                k: 1e3 * v / red["module_runs"]
                for k, v in sorted(red["by_kind_s"].items())},
                "total_s": red["total_s"],
                "module_runs": red["module_runs"],
                "reduce_s": time.perf_counter() - t0}, fh, indent=1)
    return 1e3 * red["by_kind_s"].get(counters["variant"], 0.0) \
        / red["module_runs"]

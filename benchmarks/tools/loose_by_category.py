"""What a traced run's `other` and `unattributed` are made of: device
milliseconds a call by `hlo_category`, with each category's heaviest
instruction names (a name's trailing number dropped, so nine
`subtract_convert_fusion.N` are one line) — the whole list, where
`layers.json`'s `loose` keeps the 20 heaviest instructions.

    python benchmarks/tools/loose_by_category.py --workload <cell>

Reads the newest capture under `benchmarks/out/<cell>/trace` (a `--trace 1`
run's, found as the readers find it), by `scope_reduce.reduce`'s rules (self times; a `while`'s or a
`conditional`'s own time is `unattributed`) and the program's `layer_of`;
writes `benchmarks/out/<cell>/loose.json` and prints it. No chip needed.
"""
import argparse
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.dirname(HERE), HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import scope_reduce  # noqa: E402
import stamped_time  # noqa: E402
import trace_reduce  # noqa: E402


def split(path, layer_of, top=6):
    """{kind: {category: {"ms_per_call", "instructions": [[stem, count,
    ms_per_call]]}}} for the kinds `other` and `unattributed`."""
    devs = {k: v for k, v in trace_reduce.read(path)["devices"].items() if v}
    meta = scope_reduce.event_metadata(path)
    runs = scope_reduce.module_runs(path)
    scale = 1e-6 / (len(devs) * runs)
    out = {}
    for chip, events in devs.items():
        stats = meta.get(chip, {})
        for name, t in trace_reduce.self_times(events):
            st = stats.get(name, {})
            category = st.get("hlo_category", "")
            kind = "unattributed" if category in scope_reduce.CONTROL else \
                layer_of(scope_reduce.scope_path(st.get("tf_op", "")))[1]
            if kind not in ("other", "unattributed"):
                continue
            cat = out.setdefault(kind, {}).setdefault(
                category, {"ms_per_call": 0.0, "instructions": {}})
            cat["ms_per_call"] += t * scale
            stem = re.sub(r"[.\d]+$", "", trace_reduce.op_name(name))
            row = cat["instructions"].setdefault(stem, [set(), 0.0])
            row[0].add(name)
            row[1] += t * scale
    for cats in out.values():
        for cat in cats.values():
            cat["instructions"] = [
                [stem, len(which), ms] for stem, (which, ms) in sorted(
                    cat["instructions"].items(),
                    key=lambda kv: -kv[1][1])[:top]]
    return {k: dict(sorted(v.items(), key=lambda kv: -kv[1]["ms_per_call"]))
            for k, v in out.items()}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    from novel_view_synthesis_3d_tpu.models.xunet import layer_of

    found = stamped_time._capture()   # by this process's own --workload
    if found is None:
        raise SystemExit(f"no capture under benchmarks/out/{args.workload}")
    out_dir, capture = found
    res = split(capture, layer_of)
    with open(os.path.join(out_dir, "loose.json"), "w") as fh:
        json.dump(res, fh, indent=1)
    print(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()

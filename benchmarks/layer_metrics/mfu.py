"""Model FLOP/s utilisation: operations the model needs per unit of the
cell's rate (benchmarks/flops.py, from shapes; recomputation and bucket
padding not counted) × units per second ÷ (chips × peak). The variant
names the kind it is read in. Layer: Model."""
import flops


def peak(counters, key):
    kind = counters["device_kind"].lower().replace(" ", "")
    for name, row in counters["peaks"]["kinds"].items():
        if name in kind:
            return row[key]
    raise KeyError(f"device_kind {counters['device_kind']!r} is not in "
                   "benchmarks/peaks.json")


def compute(spans, trace, counters):
    if counters.get("variant") != counters.get("kind"):
        return None
    need = flops.per_unit(counters["sizes"], counters["flops_mode"])
    return 100.0 * need * counters["units_per_s"] / (
        counters["chips"] * peak(counters, "flops_per_s"))

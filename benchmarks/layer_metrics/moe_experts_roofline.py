"""The grouped expert products' share of their roofline: the least time
the chip could take for a call's local-expert work — max(operations / peak
FLOP/s, bytes / peak bytes/s), from shapes and from the run's own routing
counts (flops_tokens.py) — over the device time under the `lk.moe_experts`
stamp per call. The stamp also covers the combine (mask, un-sort, weighted
sum), which the count leaves out: it lowers the share, never raises it.
Layer: Kernels."""
import flops_tokens
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if "routing_counts" not in counters or "hidden_size" not in m:
        return None
    rows, steps = 2 * counters["views"], counters["steps"]
    # Expert passes of a call: the once-a-call frame and every step's, each
    # over `rows` rows, in every layer. The routing counts are of the
    # checked steps' target pass; the mean load per row-layer stands for
    # every pass.
    counted = counters["routing_counts"]        # (layers, held), summed
    checked_rows = counters["counted_rows"]
    per_row_layer = sum(map(sum, counted)) / (len(counted) * checked_rows)
    hit = sum(1 for layer in counted for c in layer if c) / len(counted)
    passes = m["num_hidden_layers"] * (steps + 1)
    flops = passes * flops_tokens.moe_experts_flops(m, rows * per_row_layer)
    nbytes = passes * flops_tokens.moe_experts_bytes(
        m, rows * per_row_layer, round(hit))
    return roofline_share(trace, counters, "moe_experts", flops, nbytes)

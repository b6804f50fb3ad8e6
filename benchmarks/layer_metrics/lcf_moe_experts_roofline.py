"""The grouped expert products' share of their roofline on LongCat-Flash's
stack: as kl_moe_experts_roofline.py — max(operations / peak FLOP/s, bytes
/ peak bytes/s) of a call's held-expert work from the run's own routing
counts over the device time under `lk.moe_experts` (which also covers the
combine, twelve choices a token of which a quarter of one is held) — with
the passes counted as this trunk runs them
(flops_tokens_scmoe.expert_passes). The rows are the seed's: a run whose
tokens chose no held expert reads 0. Layer: Kernels."""
import flops_tokens_scmoe
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    counted = counters.get("routing_counts")   # (layers, held)
    if "zero_expert_num" not in m or not counted:
        return None
    rows, steps = 2 * counters["views"], counters["steps"]
    per_row_layer = sum(map(sum, counted)) / (
        len(counted) * counters["counted_rows"])
    hit = sum(1 for layer in counted for c in layer if c) / len(counted)
    passes = flops_tokens_scmoe.expert_passes(m, steps)
    flops = passes * flops_tokens_scmoe.moe_experts_flops(
        m, rows * per_row_layer)
    nbytes = passes * flops_tokens_scmoe.moe_experts_bytes(
        m, rows * per_row_layer, round(hit))
    return roofline_share(trace, counters, "moe_experts", flops, nbytes)

"""The grouped expert products' share of their roofline on Laguna's stack:
as kl_moe_experts_roofline.py — max(operations / peak FLOP/s, bytes / peak
bytes/s) of a call's held-expert work from the run's own routing counts
over the device time under `lk.moe_experts` (which also covers the
combine, ten choices a token of which five are held) — with the passes
counted from the layers that HAVE experts
(flops_tokens_headmix.expert_passes; the leading dense layer has none,
and moe_experts_roofline.py multiplies one layer by the depth). Layer:
Kernels."""
import flops_tokens_headmix
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    counted = counters.get("routing_counts")   # (expert layers, held)
    if "num_attention_heads_per_layer" not in m or not counted:
        return None
    rows, steps = 2 * counters["views"], counters["steps"]
    per_row_layer = sum(map(sum, counted)) / (
        len(counted) * counters["counted_rows"])
    hit = sum(1 for layer in counted for c in layer if c) / len(counted)
    passes = flops_tokens_headmix.expert_passes(m, steps)
    flops = passes * flops_tokens_headmix.moe_experts_flops(
        m, rows * per_row_layer)
    nbytes = passes * flops_tokens_headmix.moe_experts_bytes(
        m, rows * per_row_layer, round(hit))
    return roofline_share(trace, counters, "moe_experts", flops, nbytes)

"""The KDA scan's share of its roofline: max(operations / peak FLOP/s,
bytes / peak bytes/s) of a call's gated delta rule in its chunked form
(flops_tokens_kda.py: triangles as triangles, one MXU pass, every KDA
layer's every step and the once-a-call pass) over the device time under
the `lk.kda_core` stamp per call — the same count whatever implements the
scan, so an implementation that does more reads less. Layer: Kernels."""
import flops_tokens_kda
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if "linear_attn_config" not in m:
        return None
    flops, nbytes = flops_tokens_kda.kda_core_call_work(
        m, counters["steps"], 2 * counters["views"])
    return roofline_share(trace, counters, "kda_core", flops, nbytes)

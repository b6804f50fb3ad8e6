"""The windowed attention's share of its roofline: max(operations / peak
FLOP/s, bytes / peak bytes/s) of a call's VISIBLE query-key pairs in the
passes where a layer's window binds (flops_tokens_gqa.py: every step's
target queries against the part of [cache ; own] the band lets through)
over the device time under the `lk.attn_window` stamp per call — the
kernel and whatever layout work its wrapper adds. Layer: Kernels."""
import flops_tokens_gqa
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if "sliding_window_layout" not in m:
        return None
    flops, nbytes = flops_tokens_gqa.attn_call_work(
        m, counters["steps"], 2 * counters["views"], window=True)
    return roofline_share(trace, counters, "attn_window", flops, nbytes)

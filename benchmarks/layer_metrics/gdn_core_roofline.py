"""The gated delta rule's share of its roofline: max(operations / peak
FLOP/s, bytes / peak bytes/s) of a call's scalar-decay scan in its chunked
form (flops_tokens_gdn.py: triangles as triangles, one MXU pass, keys 96
wide on values 192 wide, every delta-rule layer's every step and the
once-a-call pass) over the device time under the `lk.gdn_core` stamp per
call — the same count whatever implements the scan, so an implementation
that does more reads less. None on a program without the stamp or on
another trunk. Layer: Kernels."""
import flops_tokens_gdn
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if "linear_key_head_dim" not in m:
        return None
    flops, nbytes = flops_tokens_gdn.gdn_core_call_work(
        m, counters["steps"], 2 * counters["views"])
    return roofline_share(trace, counters, "gdn_core", flops, nbytes)

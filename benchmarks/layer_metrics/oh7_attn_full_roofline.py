"""Full attention's share of its roofline on Olmo-Hybrid's stack:
max(operations / peak FLOP/s, bytes / peak bytes/s) of a call's visible
query-key pairs, 30 query heads each on its own key/value head of 128
(flops_tokens_gdn.attn_call_work: every full layer's every step on 8192
keys, the once-a-call pass's on 4096 less the last layer's), over the
device time under the `lk.attn_full` stamp per call — the kernel and
whatever layout work its wrapper adds. (`attn_full_roofline` counts
SmallThinker's grouped heads and stays off this trunk.) None on a program
without the stamp or on another trunk. Layer: Kernels."""
import flops_tokens_gdn
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if "linear_key_head_dim" not in m:
        return None
    flops, nbytes = flops_tokens_gdn.attn_call_work(
        m, counters["steps"], 2 * counters["views"])
    return roofline_share(trace, counters, "attn_full", flops, nbytes)

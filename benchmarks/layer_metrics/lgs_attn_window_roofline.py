"""The windowed attention's share of its roofline on Laguna's stack:
max(operations / peak FLOP/s, bytes / peak bytes/s) of a call's VISIBLE
query-key pairs at the window layers' 72 heads on 8 key/value heads, in
the passes where the window binds — every step's target queries against
[the window's tail ; own] and the once-a-call frame against itself
(flops_tokens_headmix.attn_call_work) — over the device time under the
`lk.attn_window` stamp per call: the kernel and whatever layout work its
wrapper adds. attn_window_roofline.py counts one head count for every
layer and would misread this trunk. Layer: Kernels."""
import flops_tokens_headmix
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if "num_attention_heads_per_layer" not in m:
        return None
    flops, nbytes = flops_tokens_headmix.attn_call_work(
        m, counters["steps"], 2 * counters["views"], window=True)
    return roofline_share(trace, counters, "attn_window", flops, nbytes)

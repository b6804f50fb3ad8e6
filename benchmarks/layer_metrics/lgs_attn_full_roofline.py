"""The unwindowed attention's share of its roofline on Laguna's stack:
max(operations / peak FLOP/s, bytes / peak bytes/s) of a call's query-key
pairs at the full layers' 48 heads on 8 key/value heads — every step's
target queries against both frames, and the once-a-call frame against
itself in the full layers before the last
(flops_tokens_headmix.attn_call_work) — over the device time under the
`lk.attn_full` stamp per call: the kernel and whatever layout work its
wrapper adds. Layer: Kernels."""
import flops_tokens_headmix
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if "num_attention_heads_per_layer" not in m:
        return None
    flops, nbytes = flops_tokens_headmix.attn_call_work(
        m, counters["steps"], 2 * counters["views"], window=False)
    return roofline_share(trace, counters, "attn_full", flops, nbytes)

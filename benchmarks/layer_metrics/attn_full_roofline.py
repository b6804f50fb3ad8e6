"""The unwindowed attention's share of its roofline: max(operations / peak
FLOP/s, bytes / peak bytes/s) of a call's query-key pairs in the passes
where no window binds — the layers without one, and the once-a-call pass
of every layer (one frame is no longer than the window;
flops_tokens_gqa.py) — over the device time under the `lk.attn_full`
stamp per call: the kernel and whatever layout work its wrapper adds.
Layer: Kernels."""
import flops_tokens_gqa
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if "sliding_window_layout" not in m:
        return None
    flops, nbytes = flops_tokens_gqa.attn_call_work(
        m, counters["steps"], 2 * counters["views"], window=False)
    return roofline_share(trace, counters, "attn_full", flops, nbytes)

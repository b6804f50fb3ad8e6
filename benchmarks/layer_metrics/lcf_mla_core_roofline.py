"""The latent attention core's share of its roofline on LongCat-Flash's
stack: max(operations / peak FLOP/s, bytes / peak bytes/s) of a call's
score and value products — 64 heads, keys 192 wide, values 128, TWO
attentions a layer, the once-a-call pass's last one left out
(flops_tokens_scmoe.mla_core_call_work) — over the device time under the
`lk.mla_core` stamp per call: the kernel and its wrapper's layout work.
Layer: Kernels."""
import flops_tokens_scmoe
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if "zero_expert_num" not in m:
        return None
    flops, nbytes = flops_tokens_scmoe.mla_core_call_work(
        m, counters["steps"], 2 * counters["views"])
    return roofline_share(trace, counters, "mla_core", flops, nbytes)

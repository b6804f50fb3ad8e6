"""The latent attention core's share of its roofline on Kimi-Linear's
stack: max(operations / peak FLOP/s, bytes / peak bytes/s) of a call's
score and value products — keys 192 wide, values 128, the stack's latent
layers counted as often as it has them (flops_tokens_kda.py; one at the
cell's depth, where mla_core_roofline.py would count every layer) — over
the device time under the `lk.mla_core` stamp per call: the kernel and its
wrapper's layout work. Layer: Kernels."""
import flops_tokens_kda
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if "linear_attn_config" not in m:
        return None
    flops, nbytes = flops_tokens_kda.mla_core_call_work(
        m, counters["steps"], 2 * counters["views"])
    return roofline_share(trace, counters, "mla_core", flops, nbytes)

"""The selective scan's share of its roofline: max(operations / peak FLOP/s,
bytes / peak bytes/s) of a call's Mamba recurrences (flops_tokens_ssm.py:
9 operations a (channel, state) element a token, an exponential counted as
one; u, Δ, m, B, C and the state once each; every Mamba layer's every step
and the once-a-call pass) over the device time under the `lk.ssm_core`
stamp per call — the same count whatever implements the scan. The table of
peaks has no VPU or EUP peak, so this reads the distance to the HBM bound.
None on a program without the stamp. Layer: Kernels."""
import flops_tokens_ssm
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if "mamba_d_state" not in m:
        return None
    flops, nbytes = flops_tokens_ssm.ssm_core_call_work(
        m, counters["steps"], 2 * counters["views"])
    return roofline_share(trace, counters, "ssm_core", flops, nbytes)

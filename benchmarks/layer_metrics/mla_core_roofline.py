"""The attention core's share of its roofline: max(operations / peak
FLOP/s, bytes / peak bytes/s) of a call's score and value products
(flops_tokens.py: every step's target queries against [cache ; own] keys,
the once-a-call frame against its own) over the device time under the
`lk.mla_core` stamp per call. Layer: Kernels."""
import flops_tokens
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if "hidden_size" not in m:
        return None
    rows, steps = 2 * counters["views"], counters["steps"]
    L = flops_tokens.tokens_per_frame(m)
    layers = m["num_hidden_layers"]
    flops = rows * layers * (steps * flops_tokens.mla_core_flops(m, L, 2 * L)
                             + flops_tokens.mla_core_flops(m, L, L))
    nbytes = rows * layers * (
        steps * flops_tokens.mla_core_bytes(m, L, 2 * L)
        + flops_tokens.mla_core_bytes(m, L, L))
    return roofline_share(trace, counters, "mla_core", flops, nbytes)

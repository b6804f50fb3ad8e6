"""Differential attention's share of its roofline, by what the layer reads
(`p4f_attn_roofline.<variant>`: `window`, `full`, `cross`): max(operations
/ peak FLOP/s, bytes / peak bytes/s) of a call's VISIBLE query-key pairs,
both softmax maps, keys 64 wide against a 128-wide value pair
(flops_tokens_ssm.attn_call_work) over the device time under the
`lk.attn_<variant>` stamp per call — the kernel and whatever layout work
its wrapper adds. None on a program without the stamp or on another trunk.
Layer: Kernels."""
import flops_tokens_ssm
from stamped_time import roofline_share


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    stamp = "attn_" + counters.get("variant", "")
    if "mb_per_layer" not in m or stamp not in (
            "attn_window", "attn_full", "attn_cross"):
        return None
    flops, nbytes = flops_tokens_ssm.attn_call_work(
        m, counters["steps"], 2 * counters["views"], stamp)
    return roofline_share(trace, counters, stamp, flops, nbytes)

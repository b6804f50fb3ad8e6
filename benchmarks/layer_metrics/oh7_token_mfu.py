"""Model FLOP/s utilisation of a token-denoiser cell on Olmo-Hybrid's
stack: operations per view-step (benchmarks/flops_tokens_gdn.py, from
shapes; layers counted by kind, the delta rule in its chunked form in one
pass, every query-key pair of 30 heads, the once-a-call pass as the
program runs it) × view-steps per second of the median call ÷ (chips ×
peak). The variant names the kind it is read in. Layer: Model."""
import flops_tokens_gdn
from stamped_time import peak


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if counters.get("variant") != counters.get("kind") \
            or "linear_key_head_dim" not in m:
        return None
    need = flops_tokens_gdn.per_view_step(m, counters["steps"])
    return 100.0 * need * counters["units_per_s"] / (
        counters["chips"] * peak(counters, "flops_per_s"))

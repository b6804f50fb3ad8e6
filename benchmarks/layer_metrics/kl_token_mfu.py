"""Model FLOP/s utilisation of a token-denoiser cell on Kimi-Linear's
stack: operations per view-step (benchmarks/flops_tokens_kda.py, from
shapes; layers counted by kind, KDA's scan in its chunked form, visible
query-key pairs, the run's own held assignments a token) × view-steps per
second of the median call ÷ (chips × peak). The variant names the kind it
is read in. Layer: Model."""
import flops_tokens_kda
from stamped_time import peak


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if counters.get("variant") != counters.get("kind") \
            or "linear_attn_config" not in m:
        return None
    held = None
    counted = counters.get("routing_counts")
    if counted:
        held = sum(map(sum, counted)) / (
            len(counted) * counters["counted_rows"]
            * flops_tokens_kda.tokens_per_frame(m))
    need = flops_tokens_kda.per_view_step(m, counters["steps"], held)
    return 100.0 * need * counters["units_per_s"] / (
        counters["chips"] * peak(counters, "flops_per_s"))

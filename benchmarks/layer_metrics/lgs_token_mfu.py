"""Model FLOP/s utilisation of a token-denoiser cell on Laguna's stack:
operations per view-step (benchmarks/flops_tokens_headmix.py, from shapes
by layer kind; visible query-key pairs at each layer's own head count, the
run's own held assignments a token, the once-a-call pass as the program
runs it) × view-steps per second of the median call ÷ (chips × peak). The
variant names the kind it is read in. Layer: Model."""
import flops_tokens_headmix
from stamped_time import peak


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if counters.get("variant") != counters.get("kind") \
            or "num_attention_heads_per_layer" not in m:
        return None
    held = None
    counted = counters.get("routing_counts")
    if counted:
        held = sum(map(sum, counted)) / (
            len(counted) * counters["counted_rows"]
            * flops_tokens_headmix.tokens_per_frame(m))
    need = flops_tokens_headmix.per_view_step(m, counters["steps"], held)
    return 100.0 * need * counters["units_per_s"] / (
        counters["chips"] * peak(counters, "flops_per_s"))

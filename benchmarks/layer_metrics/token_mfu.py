"""Model FLOP/s utilisation of a token-denoiser cell: operations per
view-step (benchmarks/flops_tokens.py, from shapes; local experts only,
even routing) × view-steps per second of the median call ÷ (chips × peak).
The variant names the kind it is read in. Layer: Model."""
import flops_tokens
from stamped_time import peak


def compute(spans, trace, counters):
    if counters.get("variant") != counters.get("kind") \
            or "hidden_size" not in counters.get("sizes", {}):
        return None
    need = flops_tokens.per_view_step(counters["sizes"], counters["steps"])
    return 100.0 * need * counters["units_per_s"] / (
        counters["chips"] * peak(counters, "flops_per_s"))

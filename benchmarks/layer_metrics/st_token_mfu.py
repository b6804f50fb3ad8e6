"""Model FLOP/s utilisation of a token-denoiser cell on the grouped-query,
windowed trunk: operations per view-step (benchmarks/flops_tokens_gqa.py,
from shapes; visible query-key pairs and held assignments only) ×
view-steps per second of the median call ÷ (chips × peak). The variant
names the kind it is read in. Layer: Model."""
import flops_tokens_gqa
from stamped_time import peak


def compute(spans, trace, counters):
    if counters.get("variant") != counters.get("kind") \
            or "sliding_window_layout" not in counters.get("sizes", {}):
        return None
    need = flops_tokens_gqa.per_view_step(counters["sizes"],
                                          counters["steps"])
    return 100.0 * need * counters["units_per_s"] / (
        counters["chips"] * peak(counters, "flops_per_s"))

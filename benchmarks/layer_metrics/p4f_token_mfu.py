"""Model FLOP/s utilisation of a token-denoiser cell on Phi-4-mini-flash's
stack: operations per view-step (benchmarks/flops_tokens_ssm.py, from
shapes; layers counted by kind, the scan as its recurrence, visible
query-key pairs of both maps, the once-a-call pass through the layers that
keep a cache entry) × view-steps per second of the median call ÷ (chips ×
peak). The variant names the kind it is read in. Layer: Model."""
import flops_tokens_ssm
from stamped_time import peak


def compute(spans, trace, counters):
    m = counters.get("sizes", {})
    if counters.get("variant") != counters.get("kind") \
            or "mb_per_layer" not in m:
        return None
    need = flops_tokens_ssm.per_view_step(m, counters["steps"])
    return 100.0 * need * counters["units_per_s"] / (
        counters["chips"] * peak(counters, "flops_per_s"))

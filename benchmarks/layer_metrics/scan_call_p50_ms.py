"""Median host time of one `make_sampler` call ending in
block_until_ready. Layer: Samplers."""
import statistics


def compute(spans, trace, counters):
    d = [s["dur"] for s in spans if s["name"] == "scan_call"]
    return statistics.median(d) * 1e3 if d else None

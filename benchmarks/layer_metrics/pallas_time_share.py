"""Device time of Pallas kernels (`custom-call(` instructions) over all
device op time in the traced window. Layer: Kernels."""


def compute(spans, trace, counters):
    if trace is None or counters.get("variant") != counters.get("kind"):
        return None
    return 100.0 * trace["custom_call_share"]

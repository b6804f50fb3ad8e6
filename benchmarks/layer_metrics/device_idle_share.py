"""1 − union of device-op intervals over the traced window, averaged over
the chips used. Layer: Device."""


def compute(spans, trace, counters):
    if trace is None or counters.get("variant") != counters.get("kind"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

"""memory_stats()["peak_bytes_reserved"] of the fullest chip after the
window (holds a program's temporaries, which peak_bytes_in_use omits on
this runtime). Layer: Device."""


def compute(spans, trace, counters):
    if counters.get("variant") != counters.get("kind"):
        return None
    return counters["reserved_peak_bytes"] / 1e9 or None

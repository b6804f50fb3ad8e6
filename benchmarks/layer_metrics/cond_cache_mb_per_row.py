"""Megabytes one row of the doubled guidance batch keeps of the
conditioning frame between the once-a-call pass and the steps, summed over
the layers and the kinds of cache entry: the program's own read-out of
what `precompute` returns, from shapes (`TokenDenoiser.cond_cache_bytes`;
the split by kind — recurrent state, latent — is in the run's counters).
Layer: Samplers."""


def compute(spans, trace, counters):
    by_kind = counters.get("cond_cache_bytes")
    if not by_kind:
        return None
    return sum(by_kind.values()) / 1e6

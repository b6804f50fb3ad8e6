"""Device time of the sampler's once-a-call pass (everything the program
runs under its `precompute` scope: the conditioning frame through the
trunk, leaving its latent cache) over the device's busy time, in the
traced window. Layer: Samplers."""
import re

from stamped_time import reduction


def _once_or_steps(path):
    """A vocabulary function for scope_reduce: is the instruction part of
    the once-a-call pass?"""
    segs = re.split(r"[/()]", path.split(";", 1)[0])
    return "", "precompute" if "precompute" in segs else "steps"


def compute(spans, trace, counters):
    red = reduction(trace, _once_or_steps)
    if red is None or "precompute" not in red["by_kind_s"]:
        return None
    return 100.0 * red["by_kind_s"]["precompute"] / red["total_s"]

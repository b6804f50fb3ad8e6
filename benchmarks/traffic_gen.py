"""The arithmetic of a cell's rate. A traffic mix is a data file under
benchmarks/traffic/, read by its kind; what the kinds share is here."""

from __future__ import annotations


def first_to_last_rate(units_per_completion: float, done_times) -> float:
    """units × (N − 1) / (t_N − t_1) over the completion times of whole
    steps or calls: a rate that does not round to whole completions and
    never divides by the nominal window."""
    t = sorted(done_times)
    if len(t) < 2 or t[-1] <= t[0]:
        raise ValueError("a rate needs two completions apart in time; "
                         f"got {len(t)}")
    return units_per_completion * (len(t) - 1) / (t[-1] - t[0])

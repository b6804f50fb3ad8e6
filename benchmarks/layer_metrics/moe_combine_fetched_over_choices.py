"""Rows the expert layer's combine reads over the choices a step's tokens
make: Σ over the expert layers of `rows_fetched(counts)` ÷ (tokens a step ×
top-k × expert layers) — the program's own account of what its combine
kernel fetches from the down product (`ops/expert_combine.rows_fetched`:
the held assignments, no row of a choice that is not held here) on the
run's own `routing_counts`. It is the traffic's share of held choices:
0.25 where one choice in four is held, 1 where every choice is; it says
why the cells gain differently from the skip and judges no PR. The counts
are of the checked steps' rows in ONE pass (`counted_rows`); a step of the
timed program routes 2 × `views` rows of (side ÷ patch)² tokens, so each
expert's count is scaled to a step's rows (rounded) as
moe_rows_visited_over_held.py scales. None where the run counted nothing,
and on a program without the function. Layer: Kernels."""
import numpy as np


def compute(spans, trace, counters):
    counted = counters.get("routing_counts")
    sizes = counters.get("sizes") or {}
    if not (counted and counters.get("counted_rows")
            and counters.get("views") and sizes.get("num_experts_per_tok")):
        return None
    try:
        from novel_view_synthesis_3d_tpu.ops.expert_combine import (
            rows_fetched)
    except ImportError:
        return None
    rows = 2 * counters["views"]
    scale = rows / counters["counted_rows"]
    tokens = rows * (sizes["side"] // sizes["patch_size"]) ** 2
    choices = tokens * sizes["num_experts_per_tok"] * len(counted)
    return sum(rows_fetched(np.rint(np.asarray(layer) * scale).astype(
        np.int64)) for layer in counted) / choices

"""Rows the grouped expert products multiply over rows that are a token's:
Σ over the expert layers of `rows_visited(counts)` ÷ Σ counts — the
program's own account of its tile-aligned spans
(`ops/grouped_matmul.rows_visited`: every held expert's rows rounded up
to whole 128-row tiles) on the run's own `routing_counts`. 1 = no pad row
multiplied. The counts are of the checked steps' rows in ONE pass
(`counted_rows`); a step of the timed program multiplies 2 × `views` rows,
so each expert's count is scaled to a step's rows (rounded) before the
tiles are counted — a fuller pass pads less. None where the run counted
nothing, and on a program without the function. Layer: Kernels."""
import numpy as np


def compute(spans, trace, counters):
    counted = counters.get("routing_counts")
    if not counted:
        return None
    try:
        from novel_view_synthesis_3d_tpu.ops.grouped_matmul import (
            rows_visited)
    except ImportError:
        return None
    scale = 1.0
    if counters.get("counted_rows") and counters.get("views"):
        scale = 2 * counters["views"] / counters["counted_rows"]
    layers = [np.rint(np.asarray(layer) * scale).astype(np.int64)
              for layer in counted]
    held = sum(int(layer.sum()) for layer in layers)
    if not held:
        return None
    return sum(rows_visited(layer) for layer in layers) / held

"""What the token cell's device-trace readers share: the device seconds a
call spends under one `lk.<kind>` stamp (scope_reduce, the program's own
vocabulary function), the chip's peaks, and the roofline share made of
them. None wherever something is missing — on a run without a capture, on
a program without the vocabulary or the stamp (the driver lays these files
over the parent commit's checkout for its traced runs)."""
import os

import harness
import scope_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
# The table of peaks and the run's capture each have one reader already.
peak = harness.load_module(os.path.join(HERE, "layer_metrics", "mfu.py"),
                           "layer_mfu").peak
_capture = harness.load_module(
    os.path.join(HERE, "layer_metrics", "layer_ms_per_call.py"),
    "layer_ms_per_call")._capture


def reduction(trace, layer_of=None):
    """scope_reduce's reduction of this run's capture, or None."""
    found = _capture() if trace is not None else None
    if found is None:
        return None
    path = found[1]
    if layer_of is None:
        try:
            from novel_view_synthesis_3d_tpu.models.xunet import layer_of
        except ImportError:
            return None
    red = scope_reduce.reduce(path, layer_of)
    return red if red and red["module_runs"] else None


def seconds_per_call(trace, kind):
    red = reduction(trace)
    if red is None or not red["by_kind_s"].get(kind):
        return None
    return red["by_kind_s"][kind] / red["module_runs"]


def roofline_share(trace, counters, kind, flops_per_call, bytes_per_call):
    """100 × max(ops / peak ops, bytes / peak bytes) ÷ the device seconds
    under the stamp, per call."""
    t = seconds_per_call(trace, kind)
    if t is None or "hidden_size" not in counters.get("sizes", {}):
        return None
    least = max(flops_per_call / peak(counters, "flops_per_s"),
                bytes_per_call / peak(counters, "bytes_per_s"))
    return 100.0 * least / t

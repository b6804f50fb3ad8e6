"""Reduction of a jax.profiler capture (`*.xplane.pb`) to device metrics.

Read with jax.profiler.ProfileData alone. On the TPU runtime here
(jax 0.9.0, libtpu 0.0.34) each chip is a plane `/device:TPU:<n>` whose
line `XLA Ops` holds one event per executed HLO instruction, named by the
instruction's text (`%fusion.12 = bf16[...] fusion(...)`); a Pallas kernel
is a `custom-call(` instruction whose name is the kernel's. Host threads
are lines of the plane `/host:CPU`; `jax.profiler.TraceAnnotation` names
appear there as events on the calling thread. Device and host timestamps
share one axis to about a millisecond (the fixture's device module starts
1.0 ms before the host annotation that dispatched it), so a gap is given
to the host span that covers most of it, not to an instant.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_INSTR = re.compile(r"^%([^ ]+) = ")


def op_name(event_name: str) -> str:
    """`%fusion.12 = bf16[..] fusion(...)` → `fusion.12`."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0]


def is_custom_call(event_name: str) -> bool:
    return " custom-call(" in event_name


def union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def read(path: str) -> dict:
    """{"devices": {ordinal: [(name, start_ns, end_ns), ...]},
        "host": [(name, start_ns, end_ns), ...]} from an xplane file."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    return {"devices": devices, "host": host}


def self_times(events):
    """[(name, self_ns)]: each event's duration less the events nested in
    it on the same line (a `while` or `conditional` holds its body's
    instructions), so that the self times of a line add up to its busy
    time."""
    out, stack = [], []  # stack of [end, index into out]
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(b, stack[-1][0]) - a
        out.append([name, b - a])
        stack.append([b, len(out) - 1])
    return [(n, max(0.0, t)) for n, t in out]


def reduce(raw: dict, owners=(), top: int = 10, min_gap_ns: float = 20e3):
    """Device metrics of a capture.

    `owners`: host annotation names, in the order of preference, that may
    own an idle gap. The window is the span from the first to the last
    device event over all chips. Returns busy_s / window_s (busy averaged
    over the chips), the share of busy time in custom calls, device
    seconds by instruction (self time: a loop or a conditional without the
    instructions of its body), and idle seconds by owner (gaps of chip 0
    longer than min_gap_ns; a gap no owner overlaps is `unannotated`)."""
    devs = {k: v for k, v in raw["devices"].items() if v}
    if not devs:
        return None
    lo = min(e[1] for v in devs.values() for e in v)
    hi = max(e[2] for v in devs.values() for e in v)
    busy, custom, by_op = [], 0.0, {}
    for events in devs.values():
        merged = union((a, b) for _, a, b in events)
        busy.append(sum(b - a for a, b in merged))
        for name, t in self_times(events):
            by_op[op_name(name)] = by_op.get(op_name(name), 0.0) + t
            if is_custom_call(name):
                custom += t
    n = len(devs)
    op_total = sum(by_op.values())
    first = devs[min(devs)]
    merged = union((a, b) for _, a, b in first)
    gaps = [(a_end, b_start) for (_, a_end), (b_start, _)
            in zip(merged, merged[1:]) if b_start - a_end >= min_gap_ns]
    spans = {o: union((a, b) for nm, a, b in raw["host"] if nm == o)
             for o in owners}
    idle_by = {}
    for g0, g1 in gaps:
        best, best_cover = "unannotated", 0.0
        for o in owners:
            cover = sum(max(0.0, min(g1, b) - max(g0, a))
                        for a, b in spans[o])
            if cover > best_cover:
                best, best_cover = o, cover
        idle_by[best] = idle_by.get(best, 0.0) + (g1 - g0)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / n * ns,
        "chips": n,
        "custom_call_share": custom / op_total if op_total else 0.0,
        "custom_call_s": custom / n * ns,
        "by_op_s": {k: v / n * ns for k, v in by_op.items()},
        "device_ops": [[k, v / n * ns] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in sorted(
            idle_by.items(), key=lambda kv: -kv[1])[:top]],
    }

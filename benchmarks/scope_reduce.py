"""Device time by model layer, out of the capture a `--trace 1` run takes.

The program stamps `jax.named_scope`s (`og.<block>`, `lk.<kind>`); XLA
carries the scope path of every instruction as its HLO `op_name`, and the
TPU runtime (jax 0.9.0, libtpu 0.0.34) writes it into the capture as the
stat `tf_op` (`jit(sample)/lk.update/while/body/.../XUNet/og.final/
GroupNorm_0/lk.gn/...:`) of the *event metadata* of each `XLA Ops` event,
beside `hlo_category` (`convolution fusion`, `while`, `custom-call`, ...).
`jax.profiler.ProfileData` shows an event's own stats only, so the
metadata is read off the protobuf's wire format here (no protobuf module:
TensorFlow's costs seconds to import and `setup_s` is an end-to-end
metric): `XSpace.planes=1`; `XPlane{name=2, event_metadata=4,
lines=3, stat_metadata=5}`, the two `*_metadata` maps of `{key=1,
value=2}`; `XEventMetadata{name=2, stats=5}`; `XStatMetadata{name=2}`;
`XStat{metadata_id=1, str_value=5, ref_value=7}` (a `ref_value` names a
`stat_metadata` entry whose name is the string); `XLine{name=2, events=4}`;
`XEvent{metadata_id=1, duration_ps=3}`.

Times are the self times of `trace_reduce` (a loop without its body), so a
reduction's kinds add up to the device's busy time. The program's
vocabulary function says which `(block, kind)` a path is; a `while`'s or a
`conditional`'s own self time is control flow and goes to `unattributed`
whatever scope the loop was opened in.
"""

from __future__ import annotations

import functools

import trace_reduce

MODULES_LINE = "XLA Modules"
CONTROL = ("while", "conditional")


def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width fields
    are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt in (1, 5):
            v = None
            i += 8 if wt == 1 else 4
        else:
            raise ValueError(f"xplane: wire type {wt} at byte {i}")
        yield num, wt, v


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entries(plane, field):
    for num, wt, v in _fields(plane):
        if num == field and wt == 2:
            key = value = None
            for n2, w2, v2 in _fields(v):
                if n2 == 1 and w2 == 0:
                    key = v2
                elif n2 == 2 and w2 == 2:
                    value = v2
            if value is not None:
                yield key, value


def _first(msg, field, wt):
    for num, w, v in _fields(msg):
        if num == field and w == wt:
            return v
    return None


def _stat(msg, stat_names):
    """(stat name, string value) of one XStat; the value is None where
    the stat holds a number."""
    key = val = None
    for num, wt, v in _fields(msg):
        if num == 1 and wt == 0:
            key = stat_names.get(v)
        elif num == 5 and wt == 2:
            val = _text(v)
        elif num == 7 and wt == 0:
            val = stat_names.get(v, "")
    return key, val


def _module_runs(plane) -> int:
    """How often the plane's heaviest program ran: the events of its `XLA
    Modules` line that bear the metadata with the most device time (the
    sampler's, beside the few microseconds of a key's fold-in)."""
    total, count = {}, {}
    for num, wt, line in _fields(plane):
        if num != 3 or wt != 2:
            continue
        name = _first(line, 2, 2)
        if name is None or _text(name) != MODULES_LINE:
            continue
        for n2, w2, ev in _fields(line):
            if n2 == 4 and w2 == 2:
                fields = {n3: v3 for n3, w3, v3 in _fields(ev) if w3 == 0}
                key = fields.get(1)
                total[key] = total.get(key, 0) + fields.get(3, 0)
                count[key] = count.get(key, 0) + 1
    return count[max(total, key=total.get)] if total else 0


@functools.lru_cache(maxsize=4)
def _device_planes(path: str) -> dict:
    """{chip ordinal: (event metadata, module runs)}: one walk of the file."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for num, wt, plane in _fields(space):
        if num != 1 or wt != 2:
            continue
        name = _first(plane, 2, 2)
        m = trace_reduce.DEVICE_PLANE.match(_text(name)) if name else None
        if not m:
            continue
        stat_names = {k: _text(_first(v, 2, 2) or b"")
                      for k, v in _map_entries(plane, 5)}
        events = {}
        for _, em in _map_entries(plane, 4):
            ev_name, stats = "", {}
            for n2, w2, v2 in _fields(em):
                if n2 == 2 and w2 == 2:
                    ev_name = _text(v2)
                elif n2 == 5 and w2 == 2:
                    key, val = _stat(v2, stat_names)
                    if key in ("tf_op", "hlo_category") and val is not None:
                        stats[key] = val
            if stats:
                events.setdefault(ev_name, stats)
        out[int(m.group(1))] = (events, _module_runs(plane))
    return out


def event_metadata(path: str) -> dict:
    """{chip ordinal: {event name: {"tf_op": ..., "hlo_category": ...}}}
    for the device planes of an xplane file. The event name is the
    instruction's text, as `trace_reduce.read` hands it on."""
    return {chip: ev for chip, (ev, _) in _device_planes(path).items()}


def module_runs(path: str) -> int:
    """Runs of the capture's heaviest program on its first chip."""
    planes = _device_planes(path)
    return planes[min(planes)][1] if planes else 0


def scope_path(tf_op: str) -> str:
    """`jit(work)/og.a/dot_general:` → `jit(work)/og.a/dot_general` (the
    stat is `<op_name>:<op_type>`; a scope path holds no colon)."""
    return tf_op.split(":", 1)[0]


@functools.lru_cache(maxsize=4)
def reduce(path: str, layer_of) -> dict | None:
    """Device seconds by kind and by block (averaged over the chips of the
    capture, self times), the seconds they add up to, and the module runs.
    `layer_of(scope path) -> (block, kind)` is the program's vocabulary
    function. None where the capture holds no device event. Kept per
    (file, function): one run's eight readers share one reduction."""
    raw = trace_reduce.read(path)
    devs = {k: v for k, v in raw["devices"].items() if v}
    if not devs:
        return None
    meta = event_metadata(path)
    by_kind, by_block, loose = {}, {}, {}
    for chip, events in devs.items():
        stats, layers = meta.get(chip, {}), {}
        for name, t in trace_reduce.self_times(events):
            if name not in layers:  # a few thousand names, many events
                st = stats.get(name, {})
                category = st.get("hlo_category", "")
                block, kind = ("", "unattributed") if category in CONTROL \
                    else layer_of(scope_path(st.get("tf_op", "")))
                layers[name] = (block, kind, category)
            block, kind, category = layers[name]
            by_kind[kind] = by_kind.get(kind, 0.0) + t
            if block:
                by_block[block] = by_block.get(block, 0.0) + t
            if kind in ("other", "unattributed"):
                key = (trace_reduce.op_name(name), category, kind)
                loose[key] = loose.get(key, 0.0) + t
    scale = 1e-9 / len(devs)
    return {
        "by_kind_s": {k: v * scale for k, v in by_kind.items()},
        "by_block_s": {k: v * scale for k, v in sorted(
            by_block.items(), key=lambda kv: -kv[1])},
        # The instructions no kind reaches, heaviest first: what PERF.md
        # has to explain when `other` + `unattributed` grow.
        "loose": [[*k, v * scale] for k, v in sorted(
            loose.items(), key=lambda kv: -kv[1])[:20]],
        "total_s": sum(by_kind.values()) * scale,
        "chips": len(devs),
        "module_runs": module_runs(path),
    }

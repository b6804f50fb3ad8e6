"""Performance observatory (obs/profiler, obs/roofline, obs/doctor,
obs/runindex): Chrome-trace attribution against the shared op-group
vocabulary (golden fixture, loud-`other` binning, empty/torn windows),
the ContinuousProfiler window state machine + overhead-exclusion
contract, roofline bound classification, the regression doctor's pair
and trajectory diagnoses (the real banked archive must name r09 and the
r16→r18 recovery), the run index, the bench_sentry doctor embedding,
the summarize_bench Doctor section, and the end-to-end acceptance run
(profile rows land, bitwise-identical training, zero recompiles,
every window row carrying its measured overhead)."""

import gzip
import json
import math
import os
import statistics

import pytest

from novel_view_synthesis_3d_tpu import obs
from novel_view_synthesis_3d_tpu.obs import doctor, profiler, roofline
from novel_view_synthesis_3d_tpu.obs.runindex import RunIndex

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO_ROOT, "tools")

pytestmark = pytest.mark.smoke

GROUPS = [("prelude", ["dense_emb", "conv_in"]),
          ("resnet_0", ["ResnetBlock_0"]),
          ("attn_16", ["AttnLayer_0"])]


# ---------------------------------------------------------------------------
# Chrome-trace fixtures
# ---------------------------------------------------------------------------
def _meta(pid, pname, tid=1, tname="main"):
    return [
        {"ph": "M", "pid": pid, "name": "process_name",
         "args": {"name": pname}},
        {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
         "args": {"name": tname}},
    ]


def _x(pid, tid, name, ts, dur):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name,
            "ts": ts, "dur": dur}


def golden_trace():
    """One device lane (TPU-looking metadata) + one host lane. Times in
    microseconds; total device self time = 1000us."""
    events = _meta(1, "/device:TPU:0", tid=7, tname="TensorCore")
    events += _meta(2, "python", tid=1, tname="main")
    # Device lane: named-scope tagged ops, a collective, and a stranger.
    events += [
        _x(1, 7, "fusion.1 og.prelude/conv_general", 0, 400),
        _x(1, 7, "custom-call og.attn_16/softmax", 400, 250),
        _x(1, 7, "all-reduce.3", 650, 150),
        _x(1, 7, "mystery-op.42", 800, 200),
        # Host lane noise that must NOT count once device lanes exist.
        _x(2, 1, "TfrtCpuExecutable::Execute", 0, 99999),
    ]
    return {"traceEvents": events}


def test_attribution_golden_device_lanes():
    out = profiler.attribute_device_time(golden_trace(),
                                         profiler.group_patterns(GROUPS))
    assert out["device_lanes"] == 1
    assert out["groups"]["prelude"] == pytest.approx(400e-6)
    assert out["groups"]["attn_16"] == pytest.approx(250e-6)
    assert out["groups"]["resnet_0"] == 0.0
    assert out["comm_s"] == pytest.approx(150e-6)
    # The stranger bins LOUDLY as other, and the host Execute slice is
    # excluded because a real device lane exists.
    assert out["other_s"] == pytest.approx(200e-6)
    assert out["total_s"] == pytest.approx(1000e-6)
    assert out["events"] == 4


def chip_trace_events():
    """Three device slices and their lane metadata as a v5e's
    `*.trace.json.gz` holds them (recorded with
    benchmarks/tools/record_scoped_fixture.py under jax 0.9.0; `args`
    trimmed to the fields that matter): the name is the instruction's
    short name, the scope path is `args.tf_op`."""
    loop = "jit(work)/lk.update/while/body/closed_call/"
    return [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 45988.910078,
         "dur": 15.123828, "name": "convert_reduce_fusion.2",
         "args": {"hlo_category": "convolution fusion",
                  "long_name": "%convert_reduce_fusion.2 = (f32[1024]{0:"
                               "T(1024)S(1)}, bf16[1024,1024]{1,0:T(8,128)"
                               "(2,1)S(1)}) fusion(...), kind=kOutput",
                  "tf_op": loop + "og.block_a/lk.conv/dot_general:"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 46004.042578,
         "dur": 1.001172, "name": "fusion.15",
         "args": {"hlo_category": "loop fusion",
                  "long_name": "%fusion.15 = f32[1024]{0:T(1024)S(1)} "
                               "fusion(...), kind=kLoop",
                  "tf_op": loop + "og.block_b/lk.gn/reduce_sum:"}},
        {"ph": "X", "pid": 3, "tid": 3, "ts": 46024.801406,
         "dur": 12.6225, "name": "convolution_tanh_fusion",
         "args": {"hlo_category": "convolution fusion",
                  "long_name": "%convolution_tanh_fusion = bf16[1024,1024]"
                               "{1,0:T(8,128)(2,1)} fusion(...)",
                  "tf_op": "jit(work)/dot_general:"}},
    ]


def test_attribution_reads_the_scope_from_tf_op_of_a_chip_trace():
    """On the chip no event NAME holds an `og.` label; `args.tf_op` does.
    A label claims only whole path segments: `block_a` is not `block_a1`,
    as XUNetBlock_3 is not XUNetBlock_30. (Seconds come back rounded to
    the microsecond.)"""
    groups = [("block_a", ["Dense_7"]), ("block_a1", ["Dense_8"]),
              ("block_b", ["Dense_9"])]
    events = chip_trace_events()
    out = profiler.attribute_device_time(
        {"traceEvents": events}, profiler.group_patterns(groups))
    assert out["device_lanes"] == 1 and out["events"] == 3
    assert out["groups"] == {"block_a": pytest.approx(15.123828e-6, abs=6e-7),
                             "block_a1": 0.0,
                             "block_b": pytest.approx(1.001172e-6, abs=6e-7)}
    # The unscoped matmul after the loop: loudly `other`.
    assert out["other_s"] == pytest.approx(12.6225e-6, abs=6e-7)
    events[2]["args"]["tf_op"] = events[2]["args"]["tf_op"].replace(
        "og.block_a/", "og.block_a1/")
    out = profiler.attribute_device_time(
        {"traceEvents": events}, profiler.group_patterns(groups))
    assert out["groups"]["block_a"] == 0.0
    assert out["groups"]["block_a1"] == pytest.approx(15.123828e-6,
                                                      abs=6e-7)


def test_attribution_self_time_nesting():
    """A parent slice containing a tagged child: the child's duration is
    the child's, and only the parent's SELF time bins elsewhere."""
    doc = {"traceEvents": _meta(1, "/device:TPU:0") + [
        _x(1, 1, "outer-untagged", 0, 100),
        _x(1, 1, "og.prelude/inner", 20, 40),
    ]}
    out = profiler.attribute_device_time(
        doc, profiler.group_patterns(GROUPS))
    assert out["groups"]["prelude"] == pytest.approx(40e-6)
    assert out["other_s"] == pytest.approx(60e-6)
    assert out["total_s"] == pytest.approx(100e-6)


def test_attribution_host_execute_fallback_is_loud_other():
    """CPU-backend traces carry no device lanes; the Execute slices
    substitute and (being scope-free) land in `other` — the loud-other
    contract, not an empty window."""
    doc = {"traceEvents": _meta(5, "python") + [
        _x(5, 1, "TfrtCpuExecutable::Execute", 0, 300),
        _x(5, 1, "irrelevant_host_fn", 300, 400),
    ]}
    out = profiler.attribute_device_time(
        doc, profiler.group_patterns(GROUPS))
    assert out["device_lanes"] == 0
    assert out["total_s"] == pytest.approx(300e-6)
    assert out["other_s"] == pytest.approx(300e-6)
    assert all(v == 0.0 for v in out["groups"].values())


def test_attribution_empty_window_and_none():
    pats = profiler.group_patterns(GROUPS)
    for doc in (None, {}, {"traceEvents": []},
                {"traceEvents": "not-a-list"}):
        out = profiler.attribute_device_time(doc, pats)
        assert out["total_s"] == 0.0 and out["events"] == 0


def test_load_chrome_trace_gzip_plain_and_torn(tmp_path):
    doc = golden_trace()
    gz = str(tmp_path / "t.trace.json.gz")
    with gzip.open(gz, "wt") as fh:
        json.dump(doc, fh)
    assert profiler.load_chrome_trace(gz)["traceEvents"]
    plain = str(tmp_path / "t.trace.json")
    with open(plain, "w") as fh:
        json.dump(doc, fh)
    assert profiler.load_chrome_trace(plain)["traceEvents"]
    # Torn gzip (truncated mid-stream) → None, never a raise.
    with open(gz, "rb") as fh:
        blob = fh.read()
    torn = str(tmp_path / "torn.trace.json.gz")
    with open(torn, "wb") as fh:
        fh.write(blob[: len(blob) // 2])
    assert profiler.load_chrome_trace(torn) is None
    assert profiler.load_chrome_trace(
        str(tmp_path / "missing.trace.json.gz")) is None


def test_find_trace_file_newest_in_profiler_layout(tmp_path):
    assert profiler.find_trace_file(str(tmp_path)) is None
    old = tmp_path / "plugins" / "profile" / "2026_01_01" / "h.trace.json.gz"
    new = tmp_path / "plugins" / "profile" / "2026_01_02" / "h.trace.json.gz"
    for i, p in enumerate((old, new)):
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(b"x")
        os.utime(str(p), (1000 + i, 1000 + i))
    assert profiler.find_trace_file(str(tmp_path)) == str(new)


def test_group_patterns_scope_tag_first():
    pats = dict(profiler.group_patterns(GROUPS))
    assert pats["prelude"][0] == "og.prelude"
    assert "dense_emb" in pats["prelude"]
    assert "prelude" in pats["prelude"]


# ---------------------------------------------------------------------------
# ContinuousProfiler window state machine
# ---------------------------------------------------------------------------
class FakeBus:
    def __init__(self):
        self.rows = []

    def jsonl_row(self, obj):
        self.rows.append(dict(obj))


def _cbs(write_trace=True):
    """start/stop callbacks that fake jax.profiler: stop writes a golden
    trace into the armed window dir (the plugins/profile layout)."""
    state = {"dir": None, "starts": 0, "stops": 0}

    def start(log_dir):
        state["dir"] = log_dir
        state["starts"] += 1

    def stop():
        state["stops"] += 1
        if not write_trace:
            return
        d = os.path.join(state["dir"], "plugins", "profile", "x")
        os.makedirs(d, exist_ok=True)
        with gzip.open(os.path.join(d, "h.trace.json.gz"), "wt") as fh:
            json.dump(golden_trace(), fh)

    return start, stop, state


def test_profiler_cadence_rows_and_gauges(tmp_path):
    start, stop, state = _cbs()
    bus = FakeBus()
    reg = obs.MetricsRegistry()
    p = profiler.ContinuousProfiler(
        str(tmp_path), GROUPS, bus, reg, every=5, window=2,
        start_cb=start, stop_cb=stop)
    for step in range(1, 13):
        p.on_step(step)
    # Windows: armed at 5 (closed at 7) and 10 (closed at 12).
    assert state["starts"] == 2 and state["stops"] == 2
    assert len(bus.rows) == 2
    # armed_steps_total counts every iteration a window overlapped,
    # including the arming and closing ones: {5,6,7} + {10,11,12}.
    assert p.armed_steps_total == 6
    row = bus.rows[0]
    assert row["kind"] == "profile_window" and row["unit"] == "step"
    assert row["step_start"] == 5 and row["step_end"] == 7
    assert "error" not in row
    assert row["groups"]["prelude"] == pytest.approx(400e-6)
    assert row["comm_s"] == pytest.approx(150e-6)
    assert row["overhead_s"] >= 0.0
    # Captures stay on disk for deep dives.
    assert os.path.isdir(os.path.join(str(tmp_path), "window_00000005"))
    text = reg.render_prometheus()
    assert 'nvs3d_group_device_time_seconds{group="prelude"} 0.0004' \
        in text
    assert 'group="other"' in text and 'group="comm"' in text


def test_profiler_missing_trace_is_error_row_not_raise(tmp_path):
    start, stop, _ = _cbs(write_trace=False)
    bus = FakeBus()
    p = profiler.ContinuousProfiler(str(tmp_path), GROUPS, bus,
                                    every=2, window=1,
                                    start_cb=start, stop_cb=stop)
    for step in range(1, 4):
        p.on_step(step)
    assert bus.rows and bus.rows[0]["error"] == "no trace file captured"
    assert p.enabled  # a parse miss is not an arm/disarm failure


def test_profiler_disables_after_consecutive_failures(tmp_path):
    def bad_start(log_dir):
        raise RuntimeError("backend says no")

    bus = FakeBus()
    p = profiler.ContinuousProfiler(str(tmp_path), GROUPS, bus,
                                    every=2, window=1,
                                    start_cb=bad_start, stop_cb=lambda: None)
    for step in range(1, 20):
        p.on_step(step)
    assert not p.enabled
    assert len(bus.rows) == profiler.MAX_FAILURES
    assert bus.rows[-1]["disabled"] is True
    assert all("start_trace" in r["error"] for r in bus.rows)


def test_profiler_close_finalizes_open_window(tmp_path):
    start, stop, state = _cbs()
    bus = FakeBus()
    p = profiler.ContinuousProfiler(str(tmp_path), GROUPS, bus,
                                    every=4, window=50,
                                    start_cb=start, stop_cb=stop)
    for step in range(1, 6):
        p.on_step(step)  # window armed at 4, far from closing
    assert p.active and not bus.rows
    p.close()
    p.close()  # idempotent
    assert not p.active and len(bus.rows) == 1
    assert state["stops"] == 1
    assert bus.rows[0]["step_end"] == 5


def test_make_profiler_gating(tmp_path):
    from novel_view_synthesis_3d_tpu.config import get_preset

    cfg = get_preset("tiny64")
    bus = FakeBus()
    p = obs.make_profiler(cfg.obs.profile, str(tmp_path), cfg.model, bus)
    assert p is not None and p.every == cfg.obs.profile.every_steps
    assert p.unit == "step"
    ps = obs.make_profiler(cfg.obs.profile, str(tmp_path), cfg.model,
                           bus, unit="dispatch")
    assert ps.every == cfg.obs.profile.serve_every_dispatches
    assert ps.unit == "dispatch"
    off = cfg.override(**{"obs.profile.enabled": False})
    assert obs.make_profiler(off.obs.profile, str(tmp_path),
                             cfg.model, bus) is None
    zero = cfg.override(**{"obs.profile.every_steps": 0})
    assert obs.make_profiler(zero.obs.profile, str(tmp_path),
                             cfg.model, bus) is None
    # The vocabulary is the shared op-group list.
    from novel_view_synthesis_3d_tpu.models.xunet import op_groups

    assert [lab for lab, _ in p.patterns] == [
        lab for lab, _ in op_groups(cfg.model)]


def test_profile_rows_roundtrip_through_bus(tmp_path):
    from novel_view_synthesis_3d_tpu.obs.bus import EventBus

    bus = EventBus(str(tmp_path))
    start, stop, _ = _cbs()
    p = profiler.ContinuousProfiler(str(tmp_path), GROUPS, bus,
                                    every=2, window=1,
                                    start_cb=start, stop_cb=stop)
    for step in range(1, 4):
        p.on_step(step)
    bus.jsonl_row({"kind": "span", "name": "train_step", "dur_s": 0.1})
    rows = profiler.profile_rows(str(tmp_path))
    assert len(rows) == 1 and rows[0]["kind"] == "profile_window"
    assert rows[0]["groups"]["prelude"] == pytest.approx(400e-6)
    # Torn tail tolerated.
    with open(os.path.join(str(tmp_path), "telemetry.jsonl"), "a") as fh:
        fh.write('{"kind": "profile_window", "trunc')
    assert len(profiler.profile_rows(str(tmp_path))) == 1
    assert profiler.profile_rows(str(tmp_path / "nope")) == []


def test_amortized_overhead_formula(tmp_path):
    start, stop, _ = _cbs()
    p = profiler.ContinuousProfiler(str(tmp_path), GROUPS, FakeBus(),
                                    every=100, window=1,
                                    start_cb=start, stop_cb=stop)
    assert p.amortized_overhead(0.1) is None  # no windows yet
    for step in range(1, 102):
        p.on_step(step)
    assert len(p.windows) == 1
    frac = p.amortized_overhead(0.1)
    assert frac == pytest.approx(
        (p.overhead_s / 1) / (100 * 0.1))


# ---------------------------------------------------------------------------
# Overhead-exclusion contract: armed intervals keep rate gauges clean
# ---------------------------------------------------------------------------
def test_update_gauges_excludes_rates_when_window_overlapped():
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    class Stub:
        pass

    reg = obs.MetricsRegistry()
    s = Stub()
    s._gauge_steps_per_sec = reg.gauge("nvs3d_steps_per_sec", "t")
    s._gauge_imgs_per_sec = reg.gauge("nvs3d_imgs_per_sec", "t")
    s._gauge_mfu = reg.gauge("nvs3d_mfu", "t")
    s._gauge_loss = reg.gauge("nvs3d_loss", "t")
    logged = {"steps_per_sec": 4.0, "imgs_per_sec_per_chip": 32.0,
              "loss": 0.5}
    Trainer._update_gauges(s, logged, {"mfu": 0.33})
    text = reg.render_prometheus()
    assert "nvs3d_steps_per_sec 4\n" in text
    assert "nvs3d_mfu 0.33" in text
    # A window overlapped this interval: rate gauges keep the last clean
    # sample; loss (not a rate) still updates.
    logged2 = {"steps_per_sec": 0.1, "imgs_per_sec_per_chip": 0.8,
               "loss": 0.25}
    Trainer._update_gauges(s, logged2, {"mfu": 0.01}, exclude_rates=True)
    text = reg.render_prometheus()
    assert "nvs3d_steps_per_sec 4\n" in text
    assert "nvs3d_mfu 0.33" in text
    assert "nvs3d_loss 0.25" in text


# ---------------------------------------------------------------------------
# Roofline
# ---------------------------------------------------------------------------
COST = [
    {"op": 0, "kind": "conv", "name": "prelude", "group": "prelude",
     "flops": 100e9, "bytes": 10e6},
    {"op": 1, "kind": "attn", "name": "attn_16", "group": "attn_16",
     "flops": 1e9, "bytes": 400e6},
]


def test_roofline_rows_bound_classification():
    rows = roofline.roofline_rows(
        COST, {"prelude": 1e-3, "attn_16": 2e-3},
        comm_s=0.5e-3, other_s=0.1e-3,
        peak_flops=200e12, peak_bytes_per_s=800e9)
    by = {r["group"]: r for r in rows}
    # prelude: flops-limited ideal (100e9/200e12=0.5ms) dominates bytes
    # (10e6/800e9=12.5us) → compute-bound; mfu = 100e9/(1e-3*200e12).
    assert by["prelude"]["bound"] == roofline.BOUND_COMPUTE
    assert by["prelude"]["mfu"] == pytest.approx(0.5)
    assert by["prelude"]["ideal_s"] == pytest.approx(0.5e-3)
    assert by["prelude"]["headroom_s"] == pytest.approx(0.5e-3)
    # attn_16: bytes-limited (400e6/800e9=0.5ms >> flops 5us).
    assert by["attn_16"]["bound"] == roofline.BOUND_MEMORY
    assert by["attn_16"]["bw_util"] == pytest.approx(
        (400e6 / 2e-3) / 800e9)
    # Synthetic comm/other rows ride along; rows sorted by time desc.
    assert by["comm"]["bound"] == roofline.BOUND_COMM
    assert "other" in by
    assert [r["time_s"] for r in rows] == sorted(
        (r["time_s"] for r in rows), reverse=True)


def test_roofline_unknown_without_peaks_and_top_headroom():
    rows = roofline.roofline_rows(COST, {"prelude": 1e-3, "attn_16": 2e-3})
    by = {r["group"]: r for r in rows}
    assert by["prelude"]["bound"] == roofline.BOUND_UNKNOWN
    assert by["prelude"].get("mfu") is None
    assert roofline.top_headroom(rows) == []
    rows = roofline.roofline_rows(
        COST, {"prelude": 1e-3, "attn_16": 2e-3},
        peak_flops=200e12, peak_bytes_per_s=800e9)
    top = roofline.top_headroom(rows, k=1)
    assert len(top) == 1
    # attn_16 recovers 1.5ms (2ms vs 0.5ms ideal) > prelude's 0.5ms.
    assert top[0]["group"] == "attn_16"


def test_roofline_analyze_run_from_artifacts(tmp_path):
    from novel_view_synthesis_3d_tpu.obs.bus import EventBus
    from novel_view_synthesis_3d_tpu.obs.compiles import write_costmap

    run = str(tmp_path / "run")
    os.makedirs(run)
    write_costmap(run, COST)
    bus = EventBus(run)
    bus.jsonl_row({"kind": "profile_window", "step_start": 500,
                   "step_end": 502, "unit": "step",
                   "groups": {"prelude": 1e-3, "attn_16": 2e-3},
                   "comm_s": 0.0, "other_s": 1e-4, "total_s": 3.1e-3})
    report = roofline.analyze_run(run, peak_flops=200e12,
                                  peak_bytes_per_s=800e9)
    by = {r["group"]: r for r in report["rows"]}
    assert by["prelude"]["bound"] == roofline.BOUND_COMPUTE
    assert report["window"]["step_start"] == 500
    # Missing pieces are loud notes, not silence.
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    report = roofline.analyze_run(empty)
    assert any("no costmap" in n for n in report["notes"])
    assert any("no profile_window" in n for n in report["notes"])


# ---------------------------------------------------------------------------
# Doctor: pairwise
# ---------------------------------------------------------------------------
def _mk_run(tmp, name, step_p50, fetch=0.01, recompile=False,
            spike=False, flops_scale=1.0, group_s=None):
    from novel_view_synthesis_3d_tpu.obs.bus import EventBus
    from novel_view_synthesis_3d_tpu.obs.compiles import (
        CompileLedger,
        write_costmap,
    )

    run = str(tmp / name)
    os.makedirs(run, exist_ok=True)
    bus = EventBus(run)
    for _ in range(4):
        bus.jsonl_row({"kind": "span", "name": "train_step",
                       "dur_s": step_p50})
        bus.jsonl_row({"kind": "span", "name": "data_fetch",
                       "dur_s": fetch})
    write_costmap(run, [dict(r, flops=r["flops"] * flops_scale)
                        for r in COST])
    if group_s:
        bus.jsonl_row({"kind": "profile_window", "unit": "step",
                       "step_start": 1, "step_end": 2,
                       "groups": dict(group_s), "comm_s": 0.0,
                       "other_s": 0.0,
                       "total_s": sum(group_s.values())})
    led = CompileLedger(run)
    led.record("train_step", {"donated": 1})
    if recompile:
        led.record("train_step", {"donated": 2})
    if spike:
        bus.event(3, "numerics_spike", "group=attn_16 z=9.1")
    return run


def test_diagnose_pair_names_the_regression(tmp_path):
    a = _mk_run(tmp_path, "a", step_p50=0.100)
    b = _mk_run(tmp_path, "b", step_p50=0.120, recompile=True,
                spike=True)
    doc = doctor.diagnose_pair(a, b)
    kinds = {f["kind"]: f for f in doc["findings"]}
    # A recompile in B pages, and pages rank first.
    assert doc["findings"][0]["kind"] == "recompile"
    assert doc["findings"][0]["severity"] == "page"
    assert "changed" in doc["findings"][0]["detail"]
    sd = next(f for f in doc["findings"]
              if f["kind"] == "span_drift"
              and "train_step" in f["title"])
    assert sd["severity"] == "warn" and "+20.0%" in sd["title"]
    assert kinds["numerics"]["severity"] == "warn"
    assert "z=9.1" in kinds["numerics"]["detail"]


def test_diagnose_pair_memory_bound_join(tmp_path):
    """Group device time up while its costmap FLOPs stayed flat → the
    doctor names a memory-bound regression, the tentpole join."""
    a = _mk_run(tmp_path, "ma", step_p50=0.1,
                group_s={"prelude": 1e-3, "attn_16": 1e-3})
    b = _mk_run(tmp_path, "mb", step_p50=0.1,
                group_s={"prelude": 1e-3, "attn_16": 2e-3})
    doc = doctor.diagnose_pair(a, b)
    gt = [f for f in doc["findings"] if f["kind"] == "group_time_drift"]
    assert gt and gt[0]["severity"] == "warn"
    assert "attn_16" in gt[0]["title"]
    assert "memory-bound regression" in gt[0]["title"]


def test_diagnose_pair_healthy_is_quiet_but_explicit(tmp_path):
    a = _mk_run(tmp_path, "ha", step_p50=0.100)
    b = _mk_run(tmp_path, "hb", step_p50=0.101)
    doc = doctor.diagnose_pair(a, b)
    assert not [f for f in doc["findings"] if f["severity"] == "page"]
    # "0 recompiles" is an explicit claim, not silence.
    assert any(f["kind"] == "recompile"
               and "0 recompiles" in f["title"]
               for f in doc["findings"])


def test_overlap_drop_is_flagged(tmp_path):
    a = _mk_run(tmp_path, "oa", step_p50=0.1, fetch=0.001)
    b = _mk_run(tmp_path, "ob", step_p50=0.1, fetch=0.05)
    doc = doctor.diagnose_pair(a, b)
    ov = [f for f in doc["findings"] if f["kind"] == "pipeline_overlap"]
    assert ov and ov[0]["severity"] == "warn"


# ---------------------------------------------------------------------------
# Doctor: the real banked trajectory (the golden acceptance claim)
# ---------------------------------------------------------------------------
def test_doctor_trajectory_names_r09_and_the_recovery():
    doc = doctor.diagnose_trajectory(REPO_ROOT)
    titles = [f["title"] for f in doc["findings"]]
    # The motivating miss: BENCH_r09 landed 0.973x with rc=0.
    assert "r09 regressed: vs_baseline 0.973×" in titles
    # And the recovery arc the later rounds won back.
    assert any(t.startswith("recovery r16→r18: vs_baseline "
                            "1.026→1.372") for t in titles)
    # r09 is history, not the newest round: it warns, it does not page.
    r09 = next(f for f in doc["findings"]
               if f["title"].startswith("r09 regressed"))
    assert r09["severity"] == "warn"
    assert not [f for f in doc["findings"] if f["severity"] == "page"]
    # Infra rounds (r02 timeout, r03-r05 refusals) are accounted for.
    assert any(f["kind"] == "infra_gap" for f in doc["findings"])
    assert any(f["kind"] == "multichip" for f in doc["findings"])


def test_doctor_trajectory_pages_when_newest_regressed(tmp_path):
    for n, vs in ((1, 1.05), (2, 1.04), (3, 0.91)):
        with open(str(tmp_path / f"BENCH_r{n:02d}.json"), "w") as fh:
            json.dump({"rc": 0, "parsed": {"vs_baseline": vs,
                                           "lane": "cpu"}}, fh)
    doc = doctor.diagnose_trajectory(str(tmp_path))
    top = doc["findings"][0]
    assert top["severity"] == "page"
    assert top["title"] == "r03 regressed: vs_baseline 0.910×"


def test_doctor_write_load_render_roundtrip(tmp_path):
    doc = doctor.diagnose_trajectory(REPO_ROOT)
    path = doctor.write_doctor(str(tmp_path), doc)
    assert os.path.basename(path) == "doctor.json"
    loaded = doctor.load_doctor(str(tmp_path))
    assert loaded["mode"] == "trajectory"
    assert loaded["findings"] == doc["findings"]
    text = doctor.render(loaded, limit=3)
    assert "doctor (trajectory)" in text
    assert text.count("\n") <= 8  # limit respected (title+detail lines)
    assert doctor.load_doctor(str(tmp_path / "missing")) is None


def test_doctor_cli_trajectory_and_pair(tmp_path):
    from novel_view_synthesis_3d_tpu.cli import main

    assert main(["obs", "doctor", "--trajectory", REPO_ROOT,
                 "--out", str(tmp_path)]) == 0
    assert doctor.load_doctor(str(tmp_path)) is not None
    a = _mk_run(tmp_path, "ca", step_p50=0.1)
    b = _mk_run(tmp_path, "cb", step_p50=0.1, recompile=True)
    # A page finding → rc 1 (the pair-mode alarm).
    assert main(["obs", "doctor", a, b]) == 1


def test_roofline_cli(tmp_path):
    from novel_view_synthesis_3d_tpu.cli import main

    run = _mk_run(tmp_path, "rl", step_p50=0.1,
                  group_s={"prelude": 1e-3, "attn_16": 2e-3})
    assert main(["obs", "roofline", run, "--peak-flops", "2e14",
                 "--peak-bytes", "8e11"]) == 0
    with pytest.raises(SystemExit):
        empty = str(tmp_path / "rl_empty")
        os.makedirs(empty)
        main(["obs", "roofline", empty])


# ---------------------------------------------------------------------------
# RunIndex
# ---------------------------------------------------------------------------
def test_runindex_scan_append_and_reindex(tmp_path):
    root = str(tmp_path)
    with open(os.path.join(root, "BENCH_r02.json"), "w") as fh:
        json.dump({"rc": 0, "parsed": {"vs_baseline": 1.1}}, fh)
    with open(os.path.join(root, "BENCH_r01.json"), "w") as fh:
        json.dump({"rc": 3, "parsed": None}, fh)
    with open(os.path.join(root, "BENCH_r03.json"), "w") as fh:
        fh.write('{"torn":')  # torn bank: indexed, flagged
    run = os.path.join(root, "results", "bench_tiny64")
    os.makedirs(run)
    with open(os.path.join(run, "telemetry.jsonl"), "w") as fh:
        fh.write("{}\n")
    idx = RunIndex(root)
    rounds = idx.rounds("BENCH")
    assert [e["round"] for e in rounds] == [1, 2, 3]
    assert rounds[2].get("torn") is True
    assert rounds[1]["rc"] == 0
    assert idx.load_doc(rounds[1])["parsed"]["vs_baseline"] == 1.1
    assert idx.load_doc(rounds[2]) is None
    assert any(e["path"].endswith("bench_tiny64")
               for e in idx.run_dirs())
    # Append-only: a second refresh with nothing changed adds no lines.
    with open(idx.path) as fh:
        n1 = len(fh.readlines())
    idx.refresh()
    with open(idx.path) as fh:
        assert len(fh.readlines()) == n1
    # A re-banked round (size change) re-indexes.
    with open(os.path.join(root, "BENCH_r02.json"), "w") as fh:
        json.dump({"rc": 0, "parsed": {"vs_baseline": 1.25,
                                       "lane": "cpu"}}, fh)
    idx.refresh()
    with open(idx.path) as fh:
        assert len(fh.readlines()) > n1


# ---------------------------------------------------------------------------
# bench_sentry embeds the doctor on its rc=4 page
# ---------------------------------------------------------------------------
@pytest.fixture()
def sentry(monkeypatch):
    monkeypatch.syspath_prepend(TOOLS)
    import bench_sentry

    return bench_sentry


def _parsed(vs, step_p50):
    return {"vs_baseline": vs, "lane": "cpu",
            "telemetry": {"spans": {"train_step": {"p50_s": step_p50}}}}


def test_sentry_regression_page_embeds_doctor(tmp_path, sentry, capsys):
    for n, vs in ((1, 1.10), (2, 1.08)):
        with open(str(tmp_path / f"BENCH_r{n:02d}.json"), "w") as fh:
            json.dump({"rc": 0, "parsed": _parsed(vs, 0.100)}, fh)
    fresh = _parsed(0.90, 0.140)
    verdict = sentry.judge(str(tmp_path), fresh_vs=0.90, fresh_doc=fresh)
    assert verdict["regressed"]
    assert verdict["doctor"], "rc=4 page must carry doctor findings"
    assert "train_step" in verdict["attribution"]
    assert "+40.0%" in verdict["attribution"]
    # Healthy archives carry no doctor noise.
    healthy = sentry.judge(str(tmp_path))
    assert not healthy["regressed"] and healthy["doctor"] == []


def test_sentry_real_archive_doctor_quiet(sentry):
    verdict = sentry.judge(REPO_ROOT)
    assert not verdict["regressed"]
    assert verdict["doctor"] == [] and verdict["attribution"] is None


# ---------------------------------------------------------------------------
# summarize_bench Doctor section
# ---------------------------------------------------------------------------
@pytest.fixture()
def summarize(monkeypatch):
    monkeypatch.syspath_prepend(TOOLS)
    import summarize_bench

    return summarize_bench


def test_summarize_doctor_section_skips_loudly(tmp_path, summarize):
    lines = summarize.doctor_lines([str(tmp_path)], REPO_ROOT)
    text = "\n".join(lines)
    assert "## Doctor" in text
    assert "SKIPPED: no doctor.json" in text
    assert "SKIPPED: no telemetry.jsonl" in text


def test_summarize_doctor_section_renders_findings(tmp_path, summarize):
    run = _mk_run(tmp_path, "sr", step_p50=0.1,
                  group_s={"prelude": 1e-3, "attn_16": 2e-3})
    doctor.write_doctor(run, doctor.diagnose_trajectory(REPO_ROOT))
    text = "\n".join(summarize.doctor_lines([str(tmp_path)], REPO_ROOT))
    assert "r09 regressed: vs_baseline 0.973×" in text
    assert "### Roofline" in text
    assert "prelude" in text and "attn_16" in text


# ---------------------------------------------------------------------------
# Acceptance: short real training run, profiler on vs off
# ---------------------------------------------------------------------------
def test_acceptance_profiler_on_train_run(tmp_path):
    """The tentpole contract, end to end on the CPU backend: profile
    rows land in telemetry.jsonl with the op-group vocabulary; training
    outputs are BITWISE identical profiler on vs off; the warm step
    never recompiles; and every window row carries its measured
    overhead."""
    import jax
    import numpy as np

    from novel_view_synthesis_3d_tpu.config import (
        Config, DataConfig, DiffusionConfig, MeshConfig, ModelConfig,
        TrainConfig,
    )
    from novel_view_synthesis_3d_tpu.data.synthetic import (
        write_synthetic_srn)
    from novel_view_synthesis_3d_tpu.models.xunet import op_groups
    from novel_view_synthesis_3d_tpu.obs.compiles import load_ledger
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    srn = str(tmp_path / "srn")
    write_synthetic_srn(srn, num_instances=2, views_per_instance=4,
                        image_size=16)

    def run(sub, profile_enabled):
        cfg = Config(
            model=ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32,
                              num_res_blocks=1, attn_resolutions=(8,),
                              dropout=0.0),
            diffusion=DiffusionConfig(timesteps=8, sample_timesteps=4),
            data=DataConfig(root_dir=srn, img_sidelength=16,
                            num_workers=0),
            train=TrainConfig(batch_size=8, lr=1e-3, num_steps=4,
                              save_every=0, log_every=1, seed=0,
                              resume=False,
                              checkpoint_dir=str(tmp_path / sub / "ck"),
                              results_folder=str(tmp_path / sub / "res")),
            mesh=MeshConfig(data=-1),
        ).override(**{"obs.profile.enabled": profile_enabled,
                      "obs.profile.every_steps": 2,
                      "obs.profile.window_steps": 1})
        t = Trainer(config=cfg.validate(), use_grain=False)
        t.train()
        params = jax.device_get(t.state.params)
        t.ckpt.close()
        return cfg.train.results_folder, params, t

    res_on, params_on, t_on = run("on", True)
    res_off, params_off, _ = run("off", False)

    # Profile rows landed, attributed over the shared vocabulary.
    rows = [r for r in profiler.profile_rows(res_on)
            if not r.get("error")]
    assert rows, "no profile_window rows from the instrumented run"
    labels = {lab for lab, _ in op_groups(t_on.config.model)}
    assert set(rows[0]["groups"]) == labels
    # CPU traces carry no device lanes: ALL attributed time must sit in
    # `other` (the loud-other contract), none invented for groups.
    assert all(v == 0.0 for r in rows for v in r["groups"].values())
    assert profiler.profile_rows(res_off) == []

    # Bitwise-identical outputs profiler on vs off.
    leaves_on = jax.tree.leaves(params_on)
    leaves_off = jax.tree.leaves(params_off)
    assert len(leaves_on) == len(leaves_off)
    for a, b in zip(leaves_on, leaves_off):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # Zero warm recompiles with the profiler armed.
    recompiles = [e for e in load_ledger(res_on)
                  if e.get("kind") == "recompile"]
    assert recompiles == []

    # Overhead contract, structurally: every window row carries its own
    # measured host cost beside the step spans it is amortized over. The
    # ratio itself (<= 1 % at the default cadence of 500 steps) is a
    # statement about a chip's step time; a 16 px step on a shared CPU
    # lasts 0.14 s against 1.5 s of writing and parsing a capture (2.1 %),
    # so it is a chip reading (PERF.md: not measured yet) and is not
    # asserted here.
    step_p50 = statistics.median(
        r["dur_s"] for r in _span_rows(res_on, "train_step"))
    assert step_p50 > 0
    assert all(math.isfinite(r["overhead_s"]) and r["overhead_s"] > 0
               for r in rows)
    # And the armed-interval bookkeeping the gauge exclusion keys on.
    assert t_on._profiler is not None
    assert t_on._profiler.armed_steps_total > 0


def _span_rows(run_dir, name):
    out = []
    with open(os.path.join(run_dir, "telemetry.jsonl")) as fh:
        for line in fh:
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if row.get("kind") == "span" and row.get("name") == name:
                out.append(row)
    return out

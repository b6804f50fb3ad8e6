"""Device time by layer kind, against two captures recorded on the chip
(TPU v5 lite, jax 0.9.0): `chip_trace_scoped.xplane.pb`
(tools/record_scoped_fixture.py: three dispatches of one program, a
two-iteration scan under `lk.update` holding a matmul under
`og.block_a`/`lk.conv`, a normalisation under `og.block_b`/`lk.gn` and a
named Pallas kernel under `og.block_b`/`lk.attn`, then an unscoped
matmul + tanh), and PR 23's `chip_trace.xplane.pb`, which has no stamp in
it at all."""
import importlib.util
import json
import os
import shutil
import sys

import pytest

import scope_reduce as sr
import trace_reduce as tr
from novel_view_synthesis_3d_tpu.models.xunet import layer_of

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPED = os.path.join(HERE, "fixtures", "chip_trace_scoped.xplane.pb")
PLAIN = os.path.join(HERE, "fixtures", "chip_trace.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return sr.reduce(SCOPED, layer_of)


def test_event_metadata_is_read_off_the_wire():
    meta = sr.event_metadata(PLAIN)
    assert list(meta) == [0]
    by_op = {tr.op_name(n): s for n, s in meta[0].items()}
    assert by_op["convolution_tanh_fusion"] == {
        "hlo_category": "convolution fusion",
        "tf_op": "jit(work)/dot_general:"}
    assert by_op["bench_fixture_scale.1"] == {
        "hlo_category": "custom-call",
        "tf_op": "jit(work)/bench_fixture_scale/pallas_call:"}
    assert by_op["copy-start"] == {"hlo_category": "copy-start"}
    assert sr.scope_path("jit(work)/og.a/dot_general:") == \
        "jit(work)/og.a/dot_general"
    assert sr.scope_path("") == ""


def test_scoped_capture_carries_the_stamps():
    paths = {tr.op_name(n): sr.scope_path(s.get("tf_op", ""))
             for n, s in sr.event_metadata(SCOPED)[0].items()}
    kernel = [p for n, p in paths.items()
              if n.startswith("scoped_fixture_scale")]
    assert kernel == ["jit(work)/lk.update/while/body/closed_call/"
                      "og.block_b/lk.attn/scoped_fixture_scale/pallas_call"]
    assert layer_of(kernel[0]) == ("block_b", "attn")
    assert paths["convolution_tanh_fusion"] == "jit(work)/dot_general"
    assert any("/og.block_a/lk.conv/" in p for p in paths.values())
    assert any("/og.block_b/lk.gn/" in p for p in paths.values())


def test_module_runs():
    assert sr.module_runs(SCOPED) == 3
    assert sr.module_runs(PLAIN) == 4


def test_kinds_add_up_to_busy_time(red):
    base = tr.reduce(tr.read(SCOPED))
    assert red["chips"] == 1 and red["module_runs"] == 3
    assert sum(red["by_kind_s"].values()) == pytest.approx(red["total_s"])
    # Self times of one line add up to the union of its intervals.
    assert red["total_s"] == pytest.approx(base["busy_s"], rel=1e-6)
    assert red["total_s"] == pytest.approx(155.57e-6, rel=1e-3)


def test_seconds_by_kind_and_block(red):
    """Three runs of: 2 x (matmul+reduce fusion 15.2 us under lk.conv;
    three norm fusions 2.2 us under lk.gn; the kernel 0.62 us under
    lk.attn), then the unscoped matmul+tanh 12.6 us."""
    kinds = red["by_kind_s"]
    assert set(kinds) == {"conv", "gn", "attn", "unattributed"}
    assert kinds["conv"] == pytest.approx(91.05e-6, rel=1e-3)
    assert kinds["gn"] == pytest.approx(13.20e-6, rel=1e-3)
    assert kinds["attn"] == pytest.approx(3.693e-6, rel=1e-3)
    assert kinds["unattributed"] == pytest.approx(47.62e-6, rel=1e-3)
    by_op = tr.reduce(tr.read(SCOPED))["by_op_s"]
    assert kinds["attn"] == pytest.approx(by_op["scoped_fixture_scale.3"])
    assert red["by_block_s"] == {
        "block_a": pytest.approx(kinds["conv"]),
        "block_b": pytest.approx(kinds["gn"] + kinds["attn"])}


def test_unscoped_work_and_loop_control_are_unattributed(red):
    loose = {name: (cat, kind, t) for name, cat, kind, t in red["loose"]}
    # The matmul after the loop carries a path, but no scope of the
    # program's: `jit(work)/dot_general`.
    assert loose["convolution_tanh_fusion"] == (
        "convolution fusion", "unattributed",
        pytest.approx(37.86e-6, rel=1e-3))
    # The loop itself: 6 iterations' worth of control, none of its body
    # (it was opened under lk.update, and is not `update` for that).
    assert loose["while"][:2] == ("while", "unattributed")
    assert 0 < loose["while"][2] < 1e-6
    events = tr.read(SCOPED)["devices"][0]
    whole = sum(b - a for n, a, b in events if tr.op_name(n) == "while")
    assert whole * 1e-9 > 100 * loose["while"][2]
    assert all(kind == "unattributed" for _, kind, _ in loose.values())
    assert sum(t for _, _, t in loose.values()) == pytest.approx(
        red["by_kind_s"]["unattributed"])


def test_a_capture_without_stamps_is_all_unattributed():
    red = sr.reduce(PLAIN, layer_of)
    assert set(red["by_kind_s"]) == {"unattributed"}
    assert red["by_block_s"] == {}
    assert red["total_s"] == pytest.approx(
        tr.reduce(tr.read(PLAIN))["busy_s"], rel=1e-3)
    assert red["loose"][0][:3] == ["convolution_tanh_fusion",
                                   "convolution fusion", "unattributed"]


def _reader(tmp_path, monkeypatch, workload="cell.fixture", capture=SCOPED):
    spec = importlib.util.spec_from_file_location(
        "layer_ms_per_call", os.path.join(
            os.path.dirname(HERE), "layer_metrics", "layer_ms_per_call.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "HERE", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", workload,
                                      "--seed", "1"])
    if capture:
        run = tmp_path / "out" / workload / "trace" / "plugins" / \
            "profile" / "2026_09_27"
        run.mkdir(parents=True)
        shutil.copy(capture, run / "vm.xplane.pb")
    return mod


def test_reader_gives_ms_per_call_and_keeps_the_reduction(
        tmp_path, monkeypatch, red):
    mod = _reader(tmp_path, monkeypatch)
    spans = [{"name": "scan_call", "ts": 0.0, "dur": 1.0, "end": 1.0},
             {"name": "scan_call", "ts": 1.5, "dur": 1.0, "end": 2.5}]
    trace = {"busy_s": red["total_s"]}
    got = {k: mod.compute(spans, trace, {"variant": k})
           for k in ("conv", "gn", "attn", "emb", "pose", "update", "other",
                     "unattributed")}
    assert got["emb"] == got["pose"] == got["other"] == got["update"] == 0.0
    for k in ("conv", "gn", "attn", "unattributed"):
        assert got[k] == pytest.approx(1e3 * red["by_kind_s"][k] / 3)
    assert sum(got.values()) == pytest.approx(
        1e3 * trace["busy_s"] / 3, rel=1e-3)
    kept = json.load(open(tmp_path / "out" / "cell.fixture"
                          / "layers.json"))
    assert kept["by_kind_s"] == red["by_kind_s"]
    assert kept["longest_gap_between_calls_s"] == pytest.approx(0.5)
    assert kept["capture_bytes"] == os.path.getsize(SCOPED)


def test_reader_without_a_capture_or_a_vocabulary_reads_nothing(
        tmp_path, monkeypatch):
    mod = _reader(tmp_path, monkeypatch, capture=None)
    assert mod.compute([], {"busy_s": 1.0}, {"variant": "conv"}) is None
    mod = _reader(tmp_path, monkeypatch, workload="cell.other")
    assert mod.compute([], None, {"variant": "conv"}) is None  # --trace 0
    # A program from before the stamps: no vocabulary function to import.
    import novel_view_synthesis_3d_tpu.models.xunet as xunet

    monkeypatch.delattr(xunet, "layer_of")
    assert mod.compute([], {"busy_s": 1.0}, {"variant": "conv"}) is None


def test_reader_refuses_a_misspelt_kind_and_a_foreign_capture(
        tmp_path, monkeypatch, red):
    mod = _reader(tmp_path, monkeypatch)
    with pytest.raises(ValueError, match="no layer kind 'gnn'"):
        mod.compute([], {"busy_s": red["total_s"]}, {"variant": "gnn"})
    # A capture whose kinds do not add up to what run.py read of this run.
    with pytest.raises(ValueError, match="not this run's capture"):
        mod.compute([], {"busy_s": 2 * red["total_s"]}, {"variant": "gn"})

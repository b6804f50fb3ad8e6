"""The harness is driven by data: a configuration, a traffic mix, a
per-layer metric, a kind and a four-chip cell of it are each added as new
files plus an entry, in a copy of the benchmark, with no file edited."""
import json
import os
import shutil

import pytest

import harness
import traffic_gen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"), root)
    return root


def digest(root):
    return {str(p.relative_to(root)): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()
            and p.name != "BENCHMARK.json"}


def test_new_cells_are_files_and_an_entry(copy):
    before = digest(copy)
    bench = copy / "benchmarks"
    spec = json.loads((copy / "BENCHMARK.json").read_text())
    # a configuration
    cfg = json.loads((bench / "configs" / "paper256.json").read_text())
    cfg["name"], cfg["overrides"] = "paper256_deep", {
        "model.num_res_blocks": 4}
    (bench / "configs" / "paper256_deep.json").write_text(json.dumps(cfg))
    # a traffic mix of a kind that is there
    mix = json.loads((bench / "traffic" / "sample_scan.json").read_text())
    mix["views_per_call"] = 4
    (bench / "traffic" / "sample_scan_v4.json").write_text(json.dumps(mix))
    # a kind (a new way of driving the program) and a four-chip mix of it
    (bench / "kinds" / "train.py").write_text(
        "def run(cell, seed, seconds, trace_on, env):\n"
        "    raise NotImplementedError\n")
    (bench / "traffic" / "train_dp4.json").write_text(json.dumps(
        {"kind": "train", "chips": 4, "batch_per_chip": 8}))
    # a per-layer metric
    (bench / "layer_metrics" / "calls_counted.py").write_text(
        "def compute(spans, trace, counters):\n"
        "    return float(len(spans)) or None\n")
    spec["configs"].append({"name": "paper256_deep", "source": "test",
                            "file": "benchmarks/configs/paper256_deep.json",
                            "reduced": [], "why": "test"})
    spec["workloads"] += [
        {"name": "paper256_deep.sample_scan_v4", "config": "paper256_deep",
         "traffic": "sample_scan_v4", "chips": 1, "why": "test"},
        {"name": "paper256.train_dp4", "config": "paper256",
         "traffic": "train_dp4", "chips": 4, "why": "test"}]
    spec["end_to_end"].append({
        "name": "train_imgs_per_s_chip", "unit": "img/s/chip",
        "better": "higher", "bound": 0.02, "source": "host_clock",
        "workloads": ["paper256.train_dp4"]})
    spec["per_layer"].append({
        "name": "calls_counted", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "Samplers",
        "moves": "view_steps_per_s",
        "workloads": ["paper256_deep.sample_scan_v4"]})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("view_steps_per_s", "mfu.scan"):
            m["workloads"].append("paper256_deep.sample_scan_v4")
    (copy / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("paper256_deep.sample_scan_v4", str(bench))
    assert cell["chips"] == 1 and cell["traffic"]["kind"] == "scan"
    assert cell["traffic"]["views_per_call"] == 4
    assert cell["config"]["overrides"] == {"model.num_res_blocks": 4}
    assert hasattr(cell["kind"], "run")
    assert {m["name"] for m in cell["end_to_end"]} == {
        "view_steps_per_s", "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == {
        "mfu.scan", "calls_counted"}
    read = harness.layer_reader("calls_counted", str(bench))
    assert read([{"name": "a"}, {"name": "b"}], None, {}) == 2.0
    # a family reader gets its variant, and is silent in another kind
    mfu = harness.layer_reader("mfu.scan", str(bench))
    assert mfu([], None, {"kind": "train"}) is None
    # the four-chip cell of the new kind
    dp4 = harness.load_cell("paper256.train_dp4", str(bench))
    assert dp4["chips"] == 4 and dp4["traffic"]["batch_per_chip"] == 8
    assert [m["name"] for m in dp4["end_to_end"]] == [
        "setup_s", "train_imgs_per_s_chip"]
    assert dp4["per_layer"] == []
    # the traffic file and the entry have to agree on the chips
    spec["workloads"][-1]["chips"] = 1
    (copy / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(SystemExit):
        harness.load_cell("paper256.train_dp4", str(bench))
    # nothing that was there was edited
    after = digest(copy)
    assert {k: v for k, v in after.items() if k in before} == before
    # the old cell still resolves
    assert harness.load_cell("paper256.sample_scan", str(bench))["chips"] == 1


def test_unknown_names_are_refused(copy):
    with pytest.raises(SystemExit):
        harness.load_cell("nope.nothing", str(copy / "benchmarks"))
    with pytest.raises(SystemExit):
        harness.layer_reader("no_such_metric", str(copy / "benchmarks"))


def test_mfu_refuses_an_unknown_device():
    mfu = harness.layer_reader("mfu.scan")
    peaks = harness.read_json(BENCH, "peaks.json")
    sizes = dict(ch=256, ch_mult=[1, 2, 2, 4, 4], emb_ch=1024,
                 num_res_blocks=3, attn_resolutions=[8, 16, 32], side=256)
    c = dict(kind="scan", chips=1, units_per_s=5.332, flops_mode="denoise",
             sizes=sizes, peaks=peaks, device_kind="TPU v5 lite")
    assert 65 < mfu([], None, c) < 66
    with pytest.raises(KeyError):
        mfu([], None, dict(c, device_kind="TPU v9"))


def test_first_to_last_rate():
    # 141 steps of batch 8, one every 212 ms: N−1 intervals, never N/window.
    t = [3.0 + 0.212 * i for i in range(141)]
    assert traffic_gen.first_to_last_rate(8, t) == pytest.approx(8 / 0.212)
    # One step more or fewer in the window does not move the rate.
    assert traffic_gen.first_to_last_rate(8, t[:-1]) == pytest.approx(
        8 / 0.212)
    with pytest.raises(ValueError):
        traffic_gen.first_to_last_rate(8, [1.0])

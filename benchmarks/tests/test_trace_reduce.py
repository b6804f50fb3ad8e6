"""The trace reduction against the small trace recorded on the chip
(benchmarks/tools/record_fixture.py, TPU v5 lite, jax 0.9.0): four
dispatches of one program (two fusions and one Pallas kernel), a host
sleep of 4 ms after each."""
import os

import pytest

import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "chip_trace.xplane.pb")


@pytest.fixture(scope="module")
def raw():
    return tr.read(FIXTURE)


def test_planes_and_names(raw):
    assert list(raw["devices"]) == [0]
    names = {tr.op_name(n) for n, _, _ in raw["devices"][0]}
    assert {"convolution_tanh_fusion", "convolution_reduce_fusion",
            "bench_fixture_scale.1"} <= names
    custom = {tr.op_name(n) for n, _, _ in raw["devices"][0]
              if tr.is_custom_call(n)}
    assert custom == {"bench_fixture_scale.1"}
    assert sum(n == "fixture_sleep" for n, _, _ in raw["host"]) == 4


def test_busy_idle_and_custom_call_share(raw):
    r = tr.reduce(raw, owners=("fixture_sleep", "fixture_dispatch"))
    # Four runs of ~184 us (the XLA Modules line of the same capture).
    assert r["busy_s"] == pytest.approx(4 * 183.7e-6, rel=0.01)
    assert r["window_s"] == pytest.approx(17.02e-3, rel=0.01)
    assert 0.95 < 1 - r["busy_s"] / r["window_s"] < 0.96
    # The kernel: 4 x 2.636 us of 4 x ~183.7 us of op time.
    assert r["custom_call_share"] == pytest.approx(2.636 / 183.7, rel=0.02)
    assert r["device_ops"][0][0] == "convolution_tanh_fusion"
    assert r["by_op_s"]["bench_fixture_scale.1"] == pytest.approx(
        4 * 2.636e-6, rel=0.01)


def test_gaps_go_to_the_span_that_covers_them(raw):
    r = tr.reduce(raw, owners=("fixture_sleep", "fixture_dispatch"))
    gaps = dict(r["idle_gaps"])
    # Three gaps between four runs, each mostly under the host's sleep.
    assert set(gaps) == {"fixture_sleep"}
    assert gaps["fixture_sleep"] == pytest.approx(
        r["window_s"] - r["busy_s"], rel=0.01)
    none = tr.reduce(raw, owners=())
    assert dict(none["idle_gaps"]).keys() == {"unannotated"}


def test_self_times_of_nested_instructions():
    ev = [("%while.1 = f32[] while(...)", 0.0, 100.0),
          ("%fusion.2 = f32[] fusion(...)", 10.0, 40.0),
          ("%k.3 = f32[] custom-call(...)", 40.0, 60.0),
          ("%fusion.4 = f32[] fusion(...)", 120.0, 130.0)]
    got = dict((tr.op_name(n), t) for n, t in tr.self_times(ev))
    assert got == {"while.1": 50.0, "fusion.2": 30.0, "k.3": 20.0,
                   "fusion.4": 10.0}
    r = tr.reduce({"devices": {0: ev}, "host": []})
    assert r["busy_s"] == pytest.approx(110e-9)
    assert r["custom_call_share"] == pytest.approx(20.0 / 110.0)


def test_union_and_an_empty_capture():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert tr.reduce({"devices": {}, "host": []}) is None

"""Sampling-service tests: bucketing/padding correctness vs single-request
reference images, request ordering, flush-timeout and backpressure paths,
zero-recompile-after-warmup (jit cache-size counters), shard-aware
dispatch over the 8-device test mesh, and the trainer's device
prefetcher."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.config import (
    Config,
    DiffusionConfig,
    ModelConfig,
    ServeConfig,
)
from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
from novel_view_synthesis_3d_tpu.diffusion import make_schedule
from novel_view_synthesis_3d_tpu.models.xunet import XUNet
from novel_view_synthesis_3d_tpu.sample.ddpm import make_request_sampler
from novel_view_synthesis_3d_tpu.sample.service import (
    DeadlineExceeded,
    Rejected,
    SamplingService,
    bucket_for,
    request_cond_from_batch,
)

pytestmark = pytest.mark.smoke

TINY = ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                   attn_resolutions=(8,), dropout=0.0)
T = 3  # reverse-process steps: enough to exercise the scan, fast on CPU
S = 16


@pytest.fixture(scope="module")
def setup():
    dcfg = DiffusionConfig(timesteps=T, sample_timesteps=T)
    model = XUNet(TINY)
    batch = make_example_batch(batch_size=8, sidelength=S, seed=0)
    mb = {
        "x": jnp.asarray(batch["x"]), "z": jnp.asarray(batch["target"]),
        "logsnr": jnp.zeros((8,)), "R1": jnp.asarray(batch["R1"]),
        "t1": jnp.asarray(batch["t1"]), "R2": jnp.asarray(batch["R2"]),
        "t2": jnp.asarray(batch["t2"]), "K": jnp.asarray(batch["K"]),
    }
    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        mb, cond_mask=jnp.ones((8,)), train=False)["params"]
    conds = [request_cond_from_batch(mb, i) for i in range(8)]
    return model, params, dcfg, conds


@pytest.fixture(scope="module")
def ref_sampler(setup):
    """Bucket-1 reference program: the solo image every coalesced request
    must reproduce."""
    model, params, dcfg, _ = setup
    sampler = make_request_sampler(model, make_schedule(dcfg), dcfg)

    def solo(cond, seed):
        keys = jnp.asarray(jax.random.PRNGKey(seed))[None]
        c1 = {k: jnp.asarray(v)[None] for k, v in cond.items()}
        return np.asarray(jax.device_get(sampler(params, keys, c1)))[0]

    return solo


@pytest.fixture(scope="module")
def service(setup, tmp_path_factory):
    """Shared warmed service: buckets 1, 2, 4 compiled once per module."""
    model, params, dcfg, conds = setup
    svc = SamplingService(
        model, params, dcfg,
        ServeConfig(max_batch=4, flush_timeout_ms=30.0, queue_depth=16),
        results_folder=str(tmp_path_factory.mktemp("serve_events")))
    seed = 900
    for b in (1, 2, 4):
        tickets = [svc.submit(conds[j % len(conds)], seed=seed + j)
                   for j in range(b)]
        seed += b
        for t in tickets:
            t.result(timeout=300)
    yield svc
    svc.stop()


def test_bucket_for():
    assert [bucket_for(n, 8) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 8]
    with pytest.raises(ValueError):
        bucket_for(0, 8)


def test_serve_config_validation():
    with pytest.raises(ValueError, match="power of two"):
        Config(serve=ServeConfig(max_batch=3)).validate()
    with pytest.raises(ValueError, match="queue_depth"):
        Config(serve=ServeConfig(queue_depth=0)).validate()
    with pytest.raises(ValueError, match="flush_timeout_ms"):
        Config(serve=ServeConfig(flush_timeout_ms=-1.0)).validate()
    with pytest.raises(ValueError, match="sample_steps"):
        Config(serve=ServeConfig(sample_steps=2000)).validate()
    Config(serve=ServeConfig(max_batch=16)).validate()


def test_coalesced_batch_matches_single_and_preserves_order(
        service, ref_sampler, setup):
    """Three concurrent requests coalesce into one padded bucket-4 batch;
    every ticket gets ITS OWN request's image, equal to the solo
    bucket-1 reference (padding/batch-composition invariance)."""
    _, _, _, conds = setup
    seeds = [11, 22, 33]
    tickets = [service.submit(conds[i], seed=seeds[i]) for i in range(3)]
    imgs = [t.result(timeout=300) for t in tickets]
    for i, (img, t) in enumerate(zip(imgs, tickets)):
        ref = ref_sampler(conds[i], seeds[i])
        np.testing.assert_allclose(img, ref, rtol=1e-5, atol=1e-5)
        assert t.timing["queue_wait_s"] >= 0.0
        assert "device_s" in t.timing or "compile_s" in t.timing
    # The three were coalesced (one padded bucket-4 dispatch), not served
    # one by one. (Submission is fast next to the 30 ms flush window.)
    assert tickets[0].timing["bucket"] == 4
    assert tickets[0].timing["batch_n"] == 3
    # Distinct requests produced distinct images (ordering is observable).
    assert np.abs(imgs[0] - imgs[1]).max() > 1e-4


def test_flush_timeout_dispatches_partial_bucket(service, setup):
    """A lone pair must not wait for max_batch riders: the flush window
    closes and a bucket-2 batch dispatches."""
    _, _, _, conds = setup
    t0 = time.perf_counter()
    tickets = [service.submit(conds[i], seed=300 + i) for i in range(2)]
    for t in tickets:
        t.result(timeout=300)
    assert tickets[0].timing["bucket"] == 2
    assert tickets[0].timing["batch_n"] == 2
    # Served promptly after the 30 ms window — not stuck waiting for 4.
    assert time.perf_counter() - t0 < 60


def test_backpressure_rejects_with_reason(setup, tmp_path):
    """Submits past serve.queue_depth are rejected immediately with a
    reason, and the rejection lands in events.csv (the trainer's fault
    convention)."""
    model, params, dcfg, conds = setup
    svc = SamplingService(
        model, params, dcfg,
        ServeConfig(max_batch=8, flush_timeout_ms=5000.0, queue_depth=2),
        results_folder=str(tmp_path))
    try:
        svc.submit(conds[0], seed=1)
        svc.submit(conds[1], seed=2)
        with pytest.raises(Rejected, match="queue full"):
            svc.submit(conds[2], seed=3)
        events = (tmp_path / "events.csv").read_text()
        assert "reject" in events and "queue full" in events
    finally:
        svc.stop()


def test_deadline_exceeded_rejected_not_served(setup, tmp_path):
    """A request whose queue wait blows its deadline is expired at
    dispatch time instead of burning device compute."""
    model, params, dcfg, conds = setup
    svc = SamplingService(
        model, params, dcfg,
        ServeConfig(max_batch=8, flush_timeout_ms=300.0, queue_depth=8),
        results_folder=str(tmp_path))
    try:
        ticket = svc.submit(conds[0], seed=1, deadline_ms=1.0)
        with pytest.raises(DeadlineExceeded):
            ticket.result(timeout=300)
        events = (tmp_path / "events.csv").read_text()
        assert "deadline" in events
    finally:
        svc.stop()


def test_zero_recompile_after_warmup(service, setup):
    """Warm mixed-size sweep over all three buckets (1, 2, 4 — group of
    3 pads up to 4) triggers ZERO new sampler compilations, asserted
    from the program cache's jit cache-size counters."""
    _, _, _, conds = setup
    before = service.compile_counters()
    assert before["programs_built"] == 3  # buckets 1, 2, 4 from warmup
    seed = 5000
    for n in (1, 2, 3, 4, 1, 3):
        tickets = [service.submit(conds[(seed + j) % len(conds)],
                                  seed=seed + j) for j in range(n)]
        seed += n
        for t in tickets:
            t.result(timeout=300)
    after = service.compile_counters()
    assert after["programs_built"] == before["programs_built"]
    assert after["jit_cache_entries"] == before["jit_cache_entries"]
    assert after["cache_hits"] > before["cache_hits"]
    # Throughput accounting saw every request exactly once.
    summary = service.summary()
    assert summary["requests"] >= 14
    assert summary["queue_wait"]["count"] == summary["requests"]


def test_mesh_sharded_dispatch_matches_single(setup, ref_sampler, tmp_path):
    """A full bucket over the 8-device test mesh dispatches data-parallel
    through shard_batch and still reproduces every solo image."""
    from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib

    model, params, dcfg, conds = setup
    mesh = mesh_lib.make_mesh()
    assert mesh_lib.num_data_shards(mesh) == 8
    assert mesh_lib.divides_data_axis(mesh, 8)
    assert not mesh_lib.divides_data_axis(mesh, 4)
    assert not mesh_lib.divides_data_axis(None, 8)
    svc = SamplingService(
        model, params, dcfg,
        ServeConfig(max_batch=8, flush_timeout_ms=500.0, queue_depth=16),
        mesh=mesh, results_folder=str(tmp_path))
    try:
        seeds = list(range(40, 48))
        tickets = [svc.submit(conds[i], seed=seeds[i]) for i in range(8)]
        imgs = [t.result(timeout=600) for t in tickets]
        assert tickets[0].timing["bucket"] == 8
        for i in (0, 3, 7):  # spot-check across shards
            ref = ref_sampler(conds[i], seeds[i])
            np.testing.assert_allclose(imgs[i], ref, rtol=1e-5, atol=1e-5)
        # Ragged bucket (1 request on an 8-shard data axis — the common
        # low-concurrency case): must SERVE via mesh-replicated dispatch,
        # not crash on params/batch device-set mismatch.
        lone = svc.submit(conds[2], seed=99)
        img = lone.result(timeout=600)
        assert lone.timing["bucket"] == 1
        np.testing.assert_allclose(img, ref_sampler(conds[2], 99),
                                   rtol=1e-5, atol=1e-5)
    finally:
        svc.stop()


def test_service_stop_fails_queued_requests(setup, tmp_path):
    model, params, dcfg, conds = setup
    svc = SamplingService(
        model, params, dcfg,
        ServeConfig(max_batch=8, flush_timeout_ms=5000.0, queue_depth=8),
        results_folder=str(tmp_path))
    ticket = svc.submit(conds[0], seed=1)
    svc.stop()
    with pytest.raises(Rejected, match="service stopped"):
        ticket.result(timeout=10)
    with pytest.raises(Rejected, match="service stopped"):
        svc.submit(conds[0], seed=2)


# ---------------------------------------------------------------------------
# trainer device prefetcher (data.prefetch depth satellite)
# ---------------------------------------------------------------------------
def test_device_prefetcher_orders_bounds_and_terminates():
    from novel_view_synthesis_3d_tpu.train.trainer import _DevicePrefetcher

    produced = []

    def make(n=[0]):  # noqa: B006 - deliberate shared counter
        if n[0] >= 5:
            raise StopIteration
        n[0] += 1
        produced.append(n[0])
        return n[0]

    pf = _DevicePrefetcher(make, depth=2)
    time.sleep(0.3)
    # Bounded: at most depth in the queue + one in-flight fetch.
    assert len(produced) <= 3
    got = [pf.get() for _ in range(5)]
    assert got == [1, 2, 3, 4, 5]  # order preserved
    with pytest.raises(StopIteration):
        pf.get()
    with pytest.raises(StopIteration):  # terminal state is sticky
        pf.get()
    pf.stop()


def test_device_prefetcher_propagates_errors_and_flushes():
    from novel_view_synthesis_3d_tpu.train.trainer import _DevicePrefetcher

    def boom(n=[0]):  # noqa: B006
        n[0] += 1
        if n[0] >= 3:
            raise RuntimeError("loader died")
        return n[0]

    pf = _DevicePrefetcher(boom, depth=4)
    time.sleep(0.3)
    pf.flush()  # rollback path: staged batches dropped, terminal kept
    with pytest.raises(RuntimeError, match="loader died"):
        pf.get()
    pf.stop()


def test_device_prefetcher_flush_discards_in_flight_batch():
    """A batch INSIDE make_batch when flush() fires is enqueued after
    flush returns; the generation counter must still discard it — a
    pre-rollback 'suspect' batch may never reach the consumer."""
    import threading

    from novel_view_synthesis_3d_tpu.train.trainer import _DevicePrefetcher

    in_fetch_2 = threading.Event()
    release = threading.Event()

    def make(n=[0]):  # noqa: B006 - deliberate shared counter
        n[0] += 1
        if n[0] == 2:
            in_fetch_2.set()
            assert release.wait(10)
        return n[0]

    pf = _DevicePrefetcher(make, depth=4)
    assert in_fetch_2.wait(10)  # batch 1 queued, batch 2 mid-fetch
    pf.flush()  # drops batch 1; batch 2 is in-flight and must die too
    release.set()
    assert pf.get() == 3  # batch 2 (stale generation) was discarded
    pf.stop()


def test_trainer_honors_prefetch_depth_and_completes(tmp_path):
    """End-to-end: a Trainer with data.prefetch=3 trains to completion on
    an injected finite iterator with EXACTLY num_steps batches — the
    background uploader must neither skip nor double-consume batches."""
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    import dataclasses

    num_steps = 4
    batches = [make_example_batch(batch_size=2, sidelength=16, seed=i)
               for i in range(num_steps + 1)]  # +1 proves no over-consume
    cfg = Config.from_dict({
        "model": dataclasses.asdict(TINY),
        "diffusion": {"timesteps": 4, "sample_timesteps": 4},
        "data": {"img_sidelength": 16, "prefetch": 3},
        "mesh": {"data": 1},  # batch of 2 on one of the 8 test devices
        "train": {"batch_size": 2, "num_steps": num_steps,
                  "save_every": 0, "log_every": 1,
                  "results_folder": str(tmp_path / "results"),
                  "checkpoint_dir": str(tmp_path / "ckpt"),
                  "watchdog": {"enabled": False}},
    })
    trainer = Trainer(config=cfg, data_iter=iter(batches))
    trainer.train()
    assert trainer.step == num_steps


def test_log_once_dedups():
    from novel_view_synthesis_3d_tpu.utils.profiling import log_once

    key = ("test_log_once", time.time())
    assert log_once(key, "first") is True
    assert log_once(key, "second") is False


def test_service_stats_summary():
    from novel_view_synthesis_3d_tpu.utils.profiling import ServiceStats

    st = ServiceStats()
    assert st.summary() == {"requests": 0}
    for v in (0.1, 0.2, 0.3):
        st.record_span("queue_wait", v)
    st.count_requests(3)
    s = st.summary()
    assert s["requests"] == 3
    assert "requests_per_sec" in s
    assert s["queue_wait"]["count"] == 3
    assert abs(s["queue_wait"]["p50_s"] - 0.2) < 1e-9


def test_service_stats_window_bounds_memory():
    """Span storage must not grow with total requests served (long-lived
    service): only the newest `window` records back the percentiles,
    while `count` stays the total ever recorded."""
    from novel_view_synthesis_3d_tpu.utils.profiling import ServiceStats

    st = ServiceStats(window=8)
    for i in range(100):
        st.record_span("device", float(i))
    assert len(st._spans["device"]) == 8  # bounded
    s = st.span_summary("device")
    assert s["count"] == 100  # totals survive the window
    # Percentiles reflect the sliding window (last 8 records: 92..99).
    assert s["p50_s"] >= 92.0

"""Model lifecycle registry (novel_view_synthesis_3d_tpu/registry/):
manifest round-trip + sha256 tamper detection, atomic publish under a
concurrent reader, channel promote/rollback, gate pass/fail on a
synthetic PSNR delta, publisher integrity/coalescing, the CPU end-to-end
zero-downtime hot-swap through a live SamplingService, and the `nvs3d
registry` CLI verb round-trip."""

import dataclasses
import os
import threading
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
from novel_view_synthesis_3d_tpu.registry import (
    GateResult,
    IntegrityError,
    RegistryError,
    RegistryPublisher,
    RegistryStore,
    RegistryWatcher,
    VersionManifest,
    decide,
    make_psnr_probe,
    promote,
    rollback,
    run_gate,
)
from novel_view_synthesis_3d_tpu.sample.ddpm import make_request_sampler
from novel_view_synthesis_3d_tpu.sample.service import (
    SamplingService,
    request_cond_from_batch,
)

pytestmark = pytest.mark.smoke

TINY = ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                   attn_resolutions=(8,), dropout=0.0)
T = 3  # reverse-process steps (enough to exercise the scan, fast on CPU)
S = 16


def small_tree(scale: float = 1.0) -> dict:
    return {"w": {"kernel": np.full((2, 3), scale, np.float32)},
            "b": np.arange(4, dtype=np.float32)}


# ---------------------------------------------------------------------------
# manifest + store
# ---------------------------------------------------------------------------
def test_manifest_roundtrip_and_tamper(tmp_path):
    store = RegistryStore(str(tmp_path))
    m = store.publish_params(small_tree(), step=120, ema=True,
                             config_digest="abc", notes="n1")
    # Round-trip: the manifest on disk reconstructs the published one.
    again = VersionManifest.from_json(m.to_json())
    assert again == m
    assert store.manifest(m.version) == m
    assert m.step == 120 and m.ema and m.version.startswith("00000120-")
    assert store.verify(m.version) == m  # hashes check out

    # Unknown fields (written by a newer build) are refused, not guessed.
    with pytest.raises(ValueError, match="unknown fields"):
        VersionManifest.from_json(
            m.to_json()[:-2] + ', "future_field": 1}')

    # sha256 tamper detection: one flipped payload byte is an
    # IntegrityError at verify AND at load (tampered weights can never
    # reach the mesh).
    payload = os.path.join(store.versions_dir, m.version, "params.msgpack")
    blob = bytearray(open(payload, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(payload, "wb").write(bytes(blob))
    with pytest.raises(IntegrityError, match="sha256"):
        store.verify(m.version)
    with pytest.raises(IntegrityError):
        store.load_params(m.version)

    # A hand-renamed version directory is caught by the self-naming check.
    good = store.publish_params(small_tree(2.0), step=121, ema=False)
    import shutil

    shutil.copytree(os.path.join(store.versions_dir, good.version),
                    os.path.join(store.versions_dir, "99999999-deadbeef"))
    with pytest.raises(IntegrityError, match="renamed"):
        store.manifest("99999999-deadbeef")


def test_publish_is_idempotent_and_content_addressed(tmp_path):
    store = RegistryStore(str(tmp_path))
    m1 = store.publish_params(small_tree(), step=5, ema=False)
    m2 = store.publish_params(small_tree(), step=5, ema=False)
    assert m1.version == m2.version  # identical bytes+step: same version
    m3 = store.publish_params(small_tree(3.0), step=5, ema=False)
    assert m3.version != m1.version  # different content never collides
    assert len(store.list_versions()) == 2


def test_atomic_publish_under_concurrent_reader(tmp_path):
    """A reader polling list/verify/read_channel while a writer publishes
    N versions must never observe a partially-visible version (torn
    manifest, missing payload, pointer at a half-written dir)."""
    store = RegistryStore(str(tmp_path))
    reader_errors = []
    verified = [0]
    stop = threading.Event()

    def reader():
        rstore = RegistryStore(str(tmp_path))  # own handle, like a server
        while not stop.is_set():
            try:
                for m in rstore.list_versions():
                    rstore.verify(m.version)
                    verified[0] += 1
                vid = rstore.read_channel("latest")
                if vid is not None:
                    rstore.verify(vid)
            except Exception as exc:  # any tear is a failure
                reader_errors.append(exc)
                return

    t = threading.Thread(target=reader)
    t.start()
    try:
        for i in range(15):
            store.publish_params(small_tree(float(i + 1)), step=i, ema=False)
    finally:
        time.sleep(0.05)
        stop.set()
        t.join(timeout=30)
    assert not reader_errors, f"reader saw a torn version: {reader_errors[0]!r}"
    assert verified[0] > 0  # the reader actually raced the writer
    assert len(store.list_versions()) == 15


def test_channel_promote_rollback_and_gc(tmp_path):
    store = RegistryStore(str(tmp_path))
    ms = [store.publish_params(small_tree(float(i + 1)), step=i, ema=False)
          for i in range(4)]
    events = []

    def cb(step, kind, detail, version=""):
        events.append((step, kind, version))

    # Channel pointers survive a reader race trivially; promote/rollback
    # walk the history.
    promote(store, ms[1].version, channel="stable", event_cb=cb)
    promote(store, ms[3].version, channel="stable", event_cb=cb)
    assert store.read_channel("stable") == ms[3].version
    restored = rollback(store, channel="stable", event_cb=cb)
    assert restored == ms[1].version
    assert store.read_channel("stable") == ms[1].version
    assert [k for _, k, _ in events] == ["promote", "promote", "rollback"]
    # Unknown version: pointer moves are validated.
    with pytest.raises(RegistryError, match="unknown version"):
        store.set_channel("stable", "00000042-cafecafecafe")
    # gc keeps the newest K plus anything a channel pins. latest points
    # at ms[3], stable at ms[1]; keep=1 keeps ms[3] (newest) — ms[0] and
    # ms[2] are deleted.
    deleted = store.gc(keep=1)
    assert set(deleted) == {ms[0].version, ms[2].version}
    left = {m.version for m in store.list_versions()}
    assert left == {ms[1].version, ms[3].version}
    # Rolling back with no distinct prior version is a loud error.
    fresh = RegistryStore(str(tmp_path / "fresh"))
    fresh.publish_params(small_tree(), step=0, ema=False)
    with pytest.raises(RegistryError, match="no previous"):
        fresh.rollback("latest")


# ---------------------------------------------------------------------------
# quality gate
# ---------------------------------------------------------------------------
def test_gate_decide_synthetic_deltas():
    assert decide(20.0, None, 0.5) == (True, "no incumbent: bootstrap "
                                             "promotion")
    passed, _ = decide(19.6, 20.0, 0.5)
    assert passed  # -0.4 dB within the 0.5 dB margin
    passed, reason = decide(19.0, 20.0, 0.5)
    assert not passed and "regression" in reason  # -1.0 dB beyond margin
    passed, _ = decide(21.0, 20.0, 0.0)
    assert passed  # improvements always pass
    passed, reason = decide(float("nan"), 20.0, 0.5)
    assert not passed and "non-finite" in reason  # broken payload signature


def test_run_gate_pass_fail_and_autoreject(tmp_path):
    """Gate verdicts over a registry with a deterministic probe: the
    'PSNR' is read off a published leaf, so pass/fail is a synthetic,
    controlled delta."""
    store = RegistryStore(str(tmp_path))
    good = store.publish_params(small_tree(20.0), step=1, ema=False)
    bad = store.publish_params(small_tree(10.0), step=2, ema=False)
    events = []

    def cb(step, kind, detail, version=""):
        events.append((kind, version))

    def probe(params) -> float:
        return float(np.mean(params["w"]["kernel"]))

    # Bootstrap: no incumbent on 'stable' yet -> pass, promote.
    g = run_gate(store, good.version, channel="stable", probe_fn=probe,
                 margin_db=0.5, event_cb=cb)
    assert g.passed and g.incumbent is None
    promote(store, good.version, channel="stable", gate=g, event_cb=cb)
    # Candidate regresses 10 dB -> gate_fail, and promote() auto-rejects:
    # the stable pointer must not move.
    g2 = run_gate(store, bad.version, channel="stable", probe_fn=probe,
                  margin_db=0.5, event_cb=cb)
    assert not g2.passed and g2.delta_db == pytest.approx(-10.0)
    with pytest.raises(RegistryError, match="refusing to promote"):
        promote(store, bad.version, channel="stable", gate=g2)
    assert store.read_channel("stable") == good.version
    assert [k for k, _ in events] == ["gate_pass", "promote", "gate_fail"]
    # A tampered candidate fails at hash verification, before any PSNR.
    payload = os.path.join(store.versions_dir, bad.version,
                           "params.msgpack")
    blob = bytearray(open(payload, "rb").read())
    blob[0] ^= 0xFF
    open(payload, "wb").write(bytes(blob))
    with pytest.raises(IntegrityError):
        run_gate(store, bad.version, channel="stable", probe_fn=probe,
                 margin_db=0.5)


# ---------------------------------------------------------------------------
# publisher
# ---------------------------------------------------------------------------
def test_publisher_rejects_nonfinite_and_coalesces(tmp_path):
    store = RegistryStore(str(tmp_path))
    events = []
    pub = RegistryPublisher(
        store, ema=False,
        event_cb=lambda s, k, d, v="": events.append((s, k)))
    try:
        poisoned = small_tree()
        poisoned["b"] = np.array([1.0, np.nan, 3.0, 4.0], np.float32)
        assert pub.publish(1, poisoned) is None  # checkpoint-grade verify
        assert pub.rejected == 1
        assert store.list_versions() == []
        vid = pub.publish(2, small_tree())
        assert vid is not None
        assert store.read_channel("latest") == vid
        # Async path: snapshots land without blocking the caller, and the
        # publish shows up after a drain.
        pub.publish_async(3, small_tree(3.0))
        assert pub.drain(timeout=30)
        assert store.read_channel("latest").startswith("00000003-")
    finally:
        pub.stop()
    kinds = [k for _, k in events]
    assert "publish_reject" in kinds and kinds.count("model_publish") == 2


# ---------------------------------------------------------------------------
# end-to-end: publish -> gate -> promote -> zero-downtime hot swap
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served_model():
    dcfg = DiffusionConfig(timesteps=T, sample_timesteps=T)
    model = XUNet(TINY)
    batch = make_example_batch(batch_size=4, sidelength=S, seed=0)
    mb = {
        "x": jnp.asarray(batch["x"]), "z": jnp.asarray(batch["target"]),
        "logsnr": jnp.zeros((4,)), "R1": jnp.asarray(batch["R1"]),
        "t1": jnp.asarray(batch["t1"]), "R2": jnp.asarray(batch["R2"]),
        "t2": jnp.asarray(batch["t2"]), "K": jnp.asarray(batch["K"]),
    }

    def init_params(seed: int):
        return model.init(
            {"params": jax.random.PRNGKey(seed),
             "dropout": jax.random.PRNGKey(seed + 1)},
            mb, cond_mask=jnp.ones((4,)), train=False)["params"]

    params_v1 = jax.tree.map(np.asarray, init_params(0))
    params_v2 = jax.tree.map(np.asarray, init_params(7))
    conds = [request_cond_from_batch(mb, i) for i in range(4)]
    sampler = make_request_sampler(model, make_schedule(dcfg), dcfg)

    def solo(params, cond, seed):
        keys = jnp.asarray(jax.random.PRNGKey(seed))[None]
        c1 = {k: jnp.asarray(v)[None] for k, v in cond.items()}
        return np.asarray(jax.device_get(sampler(params, keys, c1)))[0]

    return model, dcfg, params_v1, params_v2, conds, solo


def test_e2e_hot_swap_under_live_submits(served_model, tmp_path):
    """The acceptance path: publish -> gate -> promote -> swap on a LIVE
    service. Zero dropped requests, zero new sampler-program compilations
    after warmup, every response attributed to the version it ran on, and
    requests pinned to the old version reproduce its exact images."""
    model, dcfg, params_v1, params_v2, conds, solo = served_model
    store = RegistryStore(str(tmp_path / "registry"))
    probe = make_psnr_probe(
        model, dcfg,
        make_example_batch(batch_size=2, sidelength=S, seed=3),
        sample_steps=T, seed=0)
    # publish v1 -> gate (bootstrap) -> promote to stable.
    m1 = store.publish_params(params_v1, step=1, ema=False)
    g1 = run_gate(store, m1.version, channel="stable", probe_fn=probe,
                  margin_db=0.5)
    assert g1.passed
    promote(store, m1.version, channel="stable", gate=g1)

    events_dir = str(tmp_path / "serve")
    svc = SamplingService(
        model, store.load_params(m1.version), dcfg,
        ServeConfig(max_batch=4, flush_timeout_ms=20.0, queue_depth=64),
        results_folder=events_dir, model_version=m1.version)
    watcher = RegistryWatcher(svc, store, "stable", poll_s=0.05)
    results = []  # (seed, ticket)
    errors = []
    try:
        # Warm the full bucket ladder (1, 2, 4) on v1.
        for b in (1, 2, 4):
            for t in [svc.submit(conds[j], seed=800 + b + j)
                      for j in range(b)]:
                t.result(timeout=300)
        warm = svc.compile_counters()

        # Live submit stream on a client thread while the promotion lands.
        # The stream stays open until the flip: its last three requests
        # wait for it (bounded), so that "some ride v1, some v2" does not
        # hang on how fast this host serves eleven requests beside a gate
        # (it failed once on a loaded host with all 14 served by v1).
        def client():
            for j in range(14):
                if j == 11:
                    deadline = time.monotonic() + 120
                    while svc.model_version == m1.version \
                            and time.monotonic() < deadline:
                        time.sleep(0.02)
                try:
                    results.append(
                        (j, svc.submit(conds[j % len(conds)], seed=j)))
                except Exception as exc:
                    errors.append(exc)
                time.sleep(0.01)

        t = threading.Thread(target=client)
        t.start()
        time.sleep(0.03)  # a few requests ride v1 first
        # publish v2 -> gate vs incumbent v1 (wide margin: two random
        # inits probe within noise of each other) -> promote -> the
        # watcher hot-swaps it under the live stream.
        m2 = store.publish_params(params_v2, step=2, ema=False)
        g2 = run_gate(store, m2.version, channel="stable", probe_fn=probe,
                      margin_db=1000.0)
        assert g2.passed and g2.incumbent == m1.version
        promote(store, m2.version, channel="stable", gate=g2)
        watcher.poke()
        t.join(timeout=300)
        images = [(seed, tk.result(timeout=300), tk) for seed, tk in results]

        # Post-swap traffic serves v2 (wait for the flip, then submit).
        deadline = time.monotonic() + 60
        while svc.model_version != m2.version and time.monotonic() < deadline:
            time.sleep(0.02)
        assert svc.model_version == m2.version
        tail = svc.submit(conds[0], seed=99)
        tail_img = tail.result(timeout=300)

        # Zero dropped/failed requests across the swap.
        assert not errors
        assert len(images) == 14
        # Zero new compilations after warmup, across the swap: warm
        # programs survive because the cache is keyed on shapes/config.
        after = svc.compile_counters()
        assert after["programs_built"] == warm["programs_built"]
        assert after["jit_cache_entries"] == warm["jit_cache_entries"]
        # Every response attributed AND bit-matching the version it
        # claims: v1-pinned requests reproduce v1's solo images even
        # though v2 was live by the time they resolved.
        by_version = {m1.version: params_v1, m2.version: params_v2}
        seen = set()
        for seed, img, tk in images:
            assert tk.model_version in by_version
            assert tk.timing["model_version"] == tk.model_version
            seen.add(tk.model_version)
            ref = solo(by_version[tk.model_version],
                       conds[seed % len(conds)], seed)
            np.testing.assert_allclose(img, ref, rtol=1e-5, atol=1e-5)
        assert tail.model_version == m2.version
        np.testing.assert_allclose(tail_img, solo(params_v2, conds[0], 99),
                                   rtol=1e-5, atol=1e-5)
        assert m2.version in seen  # the swap really landed mid-stream
        assert watcher.swaps == 1
        summary = svc.summary()
        assert summary["model_version"] == m2.version
        assert summary["model_swaps"] == 1
    finally:
        watcher.stop()
        svc.stop()

    # events.csv: the swap row carries the new version in the
    # model_version column (the bus threads it end to end).
    import csv

    with open(os.path.join(events_dir, "events.csv")) as fh:
        rows = list(csv.DictReader(fh))
    swap_rows = [r for r in rows if r["event"] == "model_swap"]
    assert swap_rows and swap_rows[-1]["model_version"] == m2.version
    assert m1.version in swap_rows[-1]["detail"]


def test_watcher_blacklists_bad_version_and_recovers(served_model,
                                                     tmp_path):
    """A tampered promoted version must NOT take down serving: the
    watcher logs swap_fail, keeps the old weights live, and doesn't
    retry-storm; a subsequent good promotion swaps normally."""
    model, dcfg, params_v1, params_v2, conds, solo = served_model
    store = RegistryStore(str(tmp_path / "registry"))
    m1 = store.publish_params(params_v1, step=1, ema=False,
                              channel="stable")
    svc = SamplingService(
        model, store.load_params(m1.version), dcfg,
        ServeConfig(max_batch=4, flush_timeout_ms=10.0),
        results_folder=str(tmp_path / "serve"), model_version=m1.version)
    events = []
    watcher = RegistryWatcher(
        svc, store, "stable", poll_s=30.0, start=False,
        event_cb=lambda s, k, d, v="": events.append(k))
    try:
        m2 = store.publish_params(params_v2, step=2, ema=False,
                                  channel="stable")
        payload = os.path.join(store.versions_dir, m2.version,
                               "params.msgpack")
        blob = bytearray(open(payload, "rb").read())
        blob[-1] ^= 0xFF
        open(payload, "wb").write(bytes(blob))
        assert watcher.poll_once() is None
        assert watcher.failures == 1 and events == ["swap_fail"]
        assert svc.model_version == m1.version  # still serving v1
        assert watcher.poll_once() is None  # blacklisted: no retry storm
        assert watcher.failures == 1
        # Re-publishing intact bytes lands on a DIFFERENT content hash?
        # No — same bytes, same version id, which is blacklisted; a real
        # operator rolls back or publishes a fixed snapshot. Do the
        # latter: v2' with a different step -> new id -> swap succeeds.
        m3 = store.publish_params(params_v2, step=3, ema=False,
                                  channel="stable")
        assert watcher.poll_once() == m3.version
        assert svc.model_version == m3.version
        img = svc.submit(conds[1], seed=5).result(timeout=300)
        np.testing.assert_allclose(img, solo(params_v2, conds[1], 5),
                                   rtol=1e-5, atol=1e-5)
    finally:
        watcher.stop()
        svc.stop()


# ---------------------------------------------------------------------------
# CLI verb round-trip
# ---------------------------------------------------------------------------
def test_registry_cli_roundtrip(tmp_path, capsys):
    """publish -> list -> promote (gated) -> rollback -> gc over a tmpdir
    registry, driven through the real CLI, against a real checkpoint."""
    import json

    from novel_view_synthesis_3d_tpu.cli import main
    from novel_view_synthesis_3d_tpu.train.checkpoint import (
        CheckpointManager)
    from novel_view_synthesis_3d_tpu.train.state import create_train_state
    from novel_view_synthesis_3d_tpu.train.trainer import (
        _sample_model_batch)

    reg = str(tmp_path / "registry")
    ckpt_dir = str(tmp_path / "ckpt")
    cfg = Config.from_dict({
        "model": dataclasses.asdict(TINY),
        "diffusion": {"timesteps": T, "sample_timesteps": T},
        "data": {"img_sidelength": S,
                 "root_dir": str(tmp_path / "no_such_dataset")},
        "train": {"checkpoint_dir": ckpt_dir},
        "registry": {"dir": reg, "gate_sample_steps": 2, "gate_batch": 2},
    })
    cfg_path = str(tmp_path / "cfg.json")
    with open(cfg_path, "w") as fh:
        fh.write(cfg.to_json())
    model = XUNet(cfg.model)
    state = create_train_state(
        cfg.train, model,
        _sample_model_batch(make_example_batch(batch_size=1,
                                               sidelength=S)))
    ckpt = CheckpointManager(ckpt_dir)
    assert ckpt.save(0, state, force=True)
    ckpt.wait()
    ckpt.close()

    # publish: checkpoint (via the integrity walk-back default) -> latest.
    assert main(["registry", "publish", "--dir", reg,
                 "--config", cfg_path]) == 0
    out = capsys.readouterr().out
    assert "published 00000000-" in out

    # list --json: one native version, latest pointing at it.
    assert main(["registry", "list", "--dir", reg, "--json"]) == 0
    listing = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert len(listing["versions"]) == 1
    vid = listing["versions"][0]["version"]
    assert listing["channels"]["latest"] == vid
    assert listing["versions"][0]["fmt"] == "native"

    # promote: runs the real PSNR gate (bootstrap: no incumbent) on the
    # synthetic probe batch, then moves stable.
    assert main(["registry", "promote", "--dir", reg,
                 "--config", cfg_path]) == 0
    out = capsys.readouterr().out
    assert '"passed": true' in out
    store = RegistryStore(reg)
    assert store.read_channel("stable") == vid

    # A second (distinct) version promoted --force, then rollback.
    m2 = store.publish_params(small_tree(), step=9, ema=False,
                              channel="latest")
    assert main(["registry", "promote", "--dir", reg, "--force",
                 "--version", m2.version, "--config", cfg_path]) == 0
    capsys.readouterr()
    assert store.read_channel("stable") == m2.version
    assert main(["registry", "rollback", "--dir", reg,
                 "--channel", "stable"]) == 0
    assert f"rolled back to {vid}" in capsys.readouterr().out
    assert store.read_channel("stable") == vid

    # gc: both surviving versions are channel-pinned -> nothing deleted.
    assert main(["registry", "gc", "--dir", reg, "--keep", "1"]) == 0
    gc_out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert gc_out["deleted"] == []
    assert set(gc_out["kept"]) == {vid, m2.version}

    # Tampered candidate: the gated promote refuses with a loud error.
    payload = os.path.join(store.versions_dir, m2.version,
                           "params.msgpack")
    blob = bytearray(open(payload, "rb").read())
    blob[3] ^= 0xFF
    open(payload, "wb").write(bytes(blob))
    with pytest.raises(SystemExit, match="gate error"):
        main(["registry", "promote", "--dir", reg,
              "--version", m2.version, "--config", cfg_path])

    # The registry kept an EventBus audit trail of all of it.
    events = open(os.path.join(reg, "events.csv")).read()
    for kind in ("model_publish", "gate_pass", "promote", "rollback"):
        assert kind in events


def test_trainer_publishes_to_registry(tmp_path):
    """End-to-end trainer hook: every registry.publish_every steps the
    snapshot is published to the `latest` channel off the step loop, and
    the model_publish events ride the run's EventBus."""
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    reg = str(tmp_path / "registry")
    num_steps = 4
    batches = [make_example_batch(batch_size=2, sidelength=S, seed=i)
               for i in range(num_steps)]
    cfg = Config.from_dict({
        "model": dataclasses.asdict(TINY),
        "diffusion": {"timesteps": 4, "sample_timesteps": 4},
        "data": {"img_sidelength": S},
        "mesh": {"data": 1},
        "train": {"batch_size": 2, "num_steps": num_steps,
                  "save_every": 0, "log_every": 2, "ema_decay": 0.99,
                  "results_folder": str(tmp_path / "results"),
                  "checkpoint_dir": str(tmp_path / "ckpt"),
                  "watchdog": {"enabled": False}},
        "registry": {"dir": reg, "publish_every": 2,
                     "gate_sample_steps": 2},
    })
    trainer = Trainer(config=cfg, data_iter=iter(batches))
    trainer.train()
    store = RegistryStore(reg)
    versions = store.list_versions()
    assert [m.step for m in versions] == [2, 4]
    assert all(m.ema for m in versions)  # EMA run publishes the EMA tree
    latest = store.read_channel("latest")
    assert latest == versions[-1].version
    store.verify(latest)
    # Published weights are servable as-is.
    tree = store.load_params(latest)
    assert jax.tree.leaves(tree)
    events = open(os.path.join(str(tmp_path / "results"),
                               "events.csv")).read()
    assert events.count("model_publish") == 2
    assert latest in events


def test_gate_probe_deterministic(served_model):
    """The fixed-seed probe is exactly reproducible — candidate and
    incumbent comparisons isolate the weights, not the noise."""
    model, dcfg, params_v1, _, _, _ = served_model
    probe = make_psnr_probe(
        model, dcfg, make_example_batch(batch_size=2, sidelength=S,
                                        seed=11),
        sample_steps=T, seed=4)
    a = probe(params_v1)
    b = probe(params_v1)
    assert np.isfinite(a) and a == b

"""Packed-record data plane tests (data/records.py + PipelinedLoader).

The contract under test (ISSUE 7 acceptance):
  - pack/read round-trip: `backend='packed'` batches are BIT-identical to
    `backend='files'` for the same (seed, epoch, index) — k>1 draws,
    instance-grouped sampling, and per-host shard slicing included;
  - integrity: a flipped byte or torn shard tail is caught by the
    open-time re-hash and quarantined BY ID (run continues), both from
    on-disk corruption and the NVS3D_FI_*_SHARD_AT env points;
  - overlap: a CPU train run with the packed loader reports data_fetch
    span p99 < 10% of train_step p50 in telemetry.jsonl.
"""

import json
import os

import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.data import records
from novel_view_synthesis_3d_tpu.data.pipeline import (
    iter_batches,
    make_dataset,
    make_packed_loader,
)
from novel_view_synthesis_3d_tpu.data.srn import FlatViewDataset, SRNDataset
from novel_view_synthesis_3d_tpu.data.synthetic import write_synthetic_srn

pytestmark = pytest.mark.smoke


@pytest.fixture(scope="module")
def srn_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("srn_packed_src")
    write_synthetic_srn(str(root), num_instances=4, views_per_instance=6,
                        image_size=32)
    return str(root)


@pytest.fixture(scope="module")
def packed_root(tmp_path_factory, srn_root):
    out = tmp_path_factory.mktemp("packed")
    # Tiny target shard size → one scene per shard (4 shards): exercises
    # multi-shard reads and gives per-host slicing something to slice.
    records.pack_srn(srn_root, str(out), shard_mb=0.001)
    return str(out)


def _pack_fresh(tmp_path, srn_root, **kw):
    out = str(tmp_path / "packed")
    records.pack_srn(srn_root, out, shard_mb=kw.pop("shard_mb", 0.001),
                     **kw)
    return out


# ---------------------------------------------------------------------------
# Format + index contract
# ---------------------------------------------------------------------------
def test_index_and_shard_contract(packed_root):
    with open(os.path.join(packed_root, records.INDEX_NAME)) as fh:
        index = json.load(fh)
    assert index["format"] == records.FORMAT_NAME
    assert index["num_instances"] == 4 and index["num_views"] == 24
    assert len(index["shards"]) >= 2  # sharded by scene at the target size
    for meta in index["shards"]:
        path = os.path.join(packed_root, meta["file"])
        assert os.path.getsize(path) == meta["bytes"]
    # (instance, view) -> (shard, offset): every entry names a shard and a
    # byte range, and the shard's own footer agrees (self-describing).
    for ordinal, meta in enumerate(index["shards"]):
        footer = records.read_shard_footer(
            os.path.join(packed_root, meta["file"]), ordinal)
        footer_map = {e[0]: tuple(e[1:]) for e in footer["instances"]}
        for e in index["instances"]:
            if e["shard"] == ordinal:
                assert footer_map[e["name"]] == (
                    e["offset"], e["length"], e["views"])
    assert records.verify_packed(packed_root, decode="all") == []


def test_locate_is_shared_binary_search(srn_root, packed_root):
    # One cumulative-views + searchsorted implementation serves BOTH
    # backends (the reference's per-fetch linear scan over instances,
    # data_loader.py:153-161, is gone for good).
    assert SRNDataset.locate is FlatViewDataset.locate
    assert records.PackedDataset.locate is FlatViewDataset.locate
    packed = records.PackedDataset(packed_root, img_sidelength=16)
    files = SRNDataset(srn_root, img_sidelength=16)
    for flat in (0, 5, 6, 17, 23):
        assert packed.locate(flat) == files.locate(flat)


# ---------------------------------------------------------------------------
# Bit-identity: packed vs files
# ---------------------------------------------------------------------------
def test_pair_and_samples_bit_identical(srn_root, packed_root):
    files = SRNDataset(srn_root, img_sidelength=16, samples_per_instance=2)
    packed = records.PackedDataset(packed_root, img_sidelength=16,
                                   samples_per_instance=2)
    assert len(files) == len(packed)
    for flat in (0, 7, 23):
        for nc in (1, 2):
            a = files.pair(flat, np.random.default_rng(3), num_cond=nc)
            b = packed.pair(flat, np.random.default_rng(3), num_cond=nc)
            for k in a:
                np.testing.assert_array_equal(a[k], b[k], err_msg=k)
        ga = files.samples(flat, np.random.default_rng(5))
        gb = packed.samples(flat, np.random.default_rng(5))
        for ra, rb in zip(ga, gb, strict=True):
            for k in ra:
                np.testing.assert_array_equal(ra[k], rb[k], err_msg=k)


@pytest.mark.parametrize("spi,num_cond,bs", [(1, 1, 4), (1, 2, 4),
                                             (3, 1, 6), (2, 2, 4)])
def test_batches_bit_identical_across_epochs(srn_root, packed_root,
                                             spi, num_cond, bs):
    # The acceptance contract: same (seed, epoch, index) → bit-identical
    # batches from the compute-overlapped packed loader and the files
    # iterator, including k>1 draws and instance-grouped sampling. 12
    # batches at bs 4-6 over 24 records span multiple epochs.
    files = SRNDataset(srn_root, img_sidelength=16,
                       samples_per_instance=spi)
    packed = records.PackedDataset(packed_root, img_sidelength=16,
                                   samples_per_instance=spi)
    a = iter_batches(files, bs, seed=7, num_cond=num_cond)
    b = make_packed_loader(packed, bs, seed=7, num_cond=num_cond,
                           workers=3, depth=3)
    try:
        for i in range(12):
            ba, bb = next(a), next(b)
            assert set(ba) == set(bb)
            for k in ba:
                np.testing.assert_array_equal(
                    ba[k], bb[k], err_msg=f"batch {i} key {k}")
    finally:
        b.stop()


def test_per_host_shard_slicing(packed_root, srn_root):
    # Faked process_count: shard-granular slices partition the corpus
    # (disjoint, union = everything), and each host's loader feeds
    # correctly-shaped batches from its slice alone.
    full = records.PackedDataset(packed_root, img_sidelength=16)
    slices = [records.PackedDataset(packed_root, img_sidelength=16,
                                    shard_index=i, shard_count=2)
              for i in range(2)]
    names = [{inst.instance_dir for inst in s.instances} for s in slices]
    assert not (names[0] & names[1])
    assert names[0] | names[1] == {i.instance_dir for i in full.instances}
    assert sum(len(s) for s in slices) == len(full)
    for i, s in enumerate(slices):
        loader = make_packed_loader(s, 4, seed=0, shard_index=i,
                                    workers=2, depth=2)
        try:
            batch = next(loader)
            assert batch["x"].shape == (4, 16, 16, 3)
        finally:
            loader.stop()
    # More hosts than shards → a loud error naming the fix, not a silent
    # empty dataset.
    with open(os.path.join(packed_root, records.INDEX_NAME)) as fh:
        n_shards = len(json.load(fh)["shards"])
    with pytest.raises(ValueError, match="shard-mb"):
        records.PackedDataset(packed_root, img_sidelength=16,
                              shard_index=n_shards, shard_count=n_shards + 1)


def test_make_dataset_dispatch_and_config_validation(srn_root, packed_root):
    import dataclasses

    from novel_view_synthesis_3d_tpu.config import Config, DataConfig

    ds = make_dataset(DataConfig(root_dir=packed_root, backend="packed",
                                 img_sidelength=16))
    assert isinstance(ds, records.PackedDataset)
    ds = make_dataset(DataConfig(root_dir=srn_root, img_sidelength=16))
    assert isinstance(ds, SRNDataset)
    with pytest.raises(ValueError, match="data.backend"):
        dataclasses.replace(
            Config(), data=DataConfig(backend="arrayrecord")).validate()
    # Pointing the packed backend at a plain SRN tree → actionable error.
    with pytest.raises(FileNotFoundError, match="nvs3d pack"):
        make_dataset(DataConfig(root_dir=srn_root, backend="packed"))


# ---------------------------------------------------------------------------
# Integrity: corruption quarantined by id, run continues
# ---------------------------------------------------------------------------
def _flip_byte(path, offset=None):
    size = os.path.getsize(path)
    offset = size // 2 if offset is None else offset
    with open(path, "r+b") as fh:
        fh.seek(offset)
        b = fh.read(1)
        fh.seek(offset)
        fh.write(bytes([b[0] ^ 0xFF]))


def test_flipped_byte_shard_quarantined(tmp_path, srn_root):
    out = _pack_fresh(tmp_path, srn_root)
    with open(os.path.join(out, records.INDEX_NAME)) as fh:
        index = json.load(fh)
    _flip_byte(os.path.join(out, index["shards"][0]["file"]))
    ds = records.PackedDataset(out, img_sidelength=16)
    assert ds.shards_quarantined == 1
    bad = {e["name"] for e in index["instances"] if e["shard"] == 0}
    bad_views = sum(e["views"] for e in index["instances"]
                    if e["shard"] == 0)
    assert len(ds.quarantined) == bad_views  # that shard's records, by id
    assert any("sha256" in r["error"] for r in ds.fault_reports)
    # The run continues on the surviving shards: full batches, and no
    # quarantined instance's views ever appear.
    loader = make_packed_loader(ds, 4, seed=0, workers=2, depth=2)
    try:
        for _ in range(6):
            assert next(loader)["x"].shape == (4, 16, 16, 3)
    finally:
        loader.stop()
    live_instances = {ds.instances[ds.locate(int(i))[0]].instance_dir
                      for i in ds.live_indices()}
    assert not (live_instances & bad)


def test_torn_tail_shard_quarantined(tmp_path, srn_root):
    out = _pack_fresh(tmp_path, srn_root)
    with open(os.path.join(out, records.INDEX_NAME)) as fh:
        index = json.load(fh)
    path = os.path.join(out, index["shards"][1]["file"])
    with open(path, "r+b") as fh:
        fh.truncate(os.path.getsize(path) // 2)  # a mid-write crash
    ds = records.PackedDataset(out, img_sidelength=16)
    assert ds.shards_quarantined == 1
    assert any("torn tail" in r["error"] or "truncated" in r["error"]
               for r in ds.fault_reports)
    problems = records.verify_packed(out)
    assert problems and any(index["shards"][1]["file"] in p
                            for p in problems)


def test_all_shards_corrupt_aborts_loudly(tmp_path, srn_root):
    out = _pack_fresh(tmp_path, srn_root)
    with open(os.path.join(out, records.INDEX_NAME)) as fh:
        index = json.load(fh)
    for meta in index["shards"]:
        _flip_byte(os.path.join(out, meta["file"]))
    with pytest.raises(RuntimeError, match="every local shard"):
        records.PackedDataset(out, img_sidelength=16)


def test_fi_env_points_quarantine_without_touching_disk(tmp_path, srn_root,
                                                        monkeypatch):
    out = _pack_fresh(tmp_path, srn_root)
    monkeypatch.setenv("NVS3D_FI_CORRUPT_SHARD_AT", "0")
    monkeypatch.setenv("NVS3D_FI_TRUNCATE_SHARD_AT", "2")
    ds = records.PackedDataset(out, img_sidelength=16)
    assert ds.shards_quarantined == 2
    errors = " ".join(r["error"] for r in ds.fault_reports)
    assert "sha256" in errors  # flipped byte lane
    assert "torn tail" in errors or "truncated" in errors  # torn lane
    monkeypatch.delenv("NVS3D_FI_CORRUPT_SHARD_AT")
    monkeypatch.delenv("NVS3D_FI_TRUNCATE_SHARD_AT")
    # In-memory only: the on-disk corpus is still pristine.
    assert records.verify_packed(out) == []
    clean = records.PackedDataset(out, img_sidelength=16)
    assert clean.shards_quarantined == 0 and not clean.quarantined


def test_decode_fault_mid_pipeline_substitutes_and_quarantines(
        tmp_path, srn_root):
    # A record that fails to DECODE despite a clean shard hash (bit rot
    # in an encoded PNG, bad offset) must cost one record, not the run:
    # the loader quarantines the exact flat id and substitutes a redrawn
    # group inline, bounded by max_record_retries.
    out = _pack_fresh(tmp_path, srn_root)
    ds = records.PackedDataset(out, img_sidelength=16)
    orig = ds._decode_view
    poisoned = {"obj": 2, "idx": 1, "fired": 0}

    def flaky(obj, idx):
        if obj == poisoned["obj"] and idx == poisoned["idx"]:
            poisoned["fired"] += 1
            flat = int(ds._offsets[obj]) + idx
            raise records.PackedRecordError("synthetic bit rot",
                                            flat_index=flat)
        return orig(obj, idx)

    ds._decode_view = flaky
    loader = make_packed_loader(ds, 4, seed=1, workers=2, depth=2)
    try:
        for _ in range(10):  # enough epochs to hit the poisoned view
            assert next(loader)["x"].shape == (4, 16, 16, 3)
    finally:
        loader.stop()
    assert poisoned["fired"] >= 1
    flat = int(ds._offsets[poisoned["obj"]]) + poisoned["idx"]
    assert flat in ds.quarantined  # by id, sibling draws included


# ---------------------------------------------------------------------------
# CLI: nvs3d pack / pack --verify
# ---------------------------------------------------------------------------
def test_cli_pack_and_verify_roundtrip(tmp_path, srn_root, capsys):
    from novel_view_synthesis_3d_tpu.cli import main

    out = str(tmp_path / "corpus")
    rc = main(["pack", srn_root, "--out", out, "--shard-mb", "0.002",
               "--verify"])
    assert rc == 0
    printed = [json.loads(ln) for ln in
               capsys.readouterr().out.strip().splitlines()]
    assert printed[0]["instances"] == 4 and printed[0]["shards"] >= 2
    assert printed[1]["verified"] is True
    # Verify-only mode on an existing corpus; rc=1 once a shard is bad.
    assert main(["pack", out, "--verify"]) == 0
    with open(os.path.join(out, records.INDEX_NAME)) as fh:
        index = json.load(fh)
    _flip_byte(os.path.join(out, index["shards"][0]["file"]))
    assert main(["pack", out, "--verify"]) == 1


# ---------------------------------------------------------------------------
# Train e2e: fault drill + the decode/compute-overlap acceptance target
# ---------------------------------------------------------------------------
def _train_config(packed_dir, tmp, **train_kw):
    from novel_view_synthesis_3d_tpu.config import (
        Config, DataConfig, DiffusionConfig, MeshConfig, ModelConfig,
        TrainConfig)

    kw = dict(batch_size=8, lr=1e-3, num_steps=8, save_every=0,
              log_every=4, seed=0, resume=False,
              checkpoint_dir=os.path.join(str(tmp), "ckpt"),
              results_folder=os.path.join(str(tmp), "results"))
    kw.update(train_kw)
    return Config(
        model=ModelConfig(ch=32, ch_mult=(1,), num_res_blocks=1,
                          attn_resolutions=(), dropout=0.0),
        diffusion=DiffusionConfig(timesteps=8, sample_timesteps=4),
        data=DataConfig(root_dir=packed_dir, backend="packed",
                        img_sidelength=16, num_workers=4, prefetch=2),
        train=TrainConfig(**kw),
        mesh=MeshConfig(data=-1),
    ).validate()


@pytest.mark.faultinject
def test_train_packed_corrupt_shard_drill(tmp_path, srn_root, monkeypatch):
    # Tier-1 drill: training over a packed corpus with a flipped-byte
    # shard AND a torn-tail shard (FI env points) quarantines both at
    # open and runs to completion — no stall, watchdog budgets honored,
    # batches drawn from the surviving shards only.
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    out = _pack_fresh(tmp_path, srn_root)
    monkeypatch.setenv("NVS3D_FI_CORRUPT_SHARD_AT", "0")
    monkeypatch.setenv("NVS3D_FI_TRUNCATE_SHARD_AT", "3")
    cfg = _train_config(out, tmp_path, num_steps=4)
    tr = Trainer(config=cfg, use_grain=False)
    assert tr.dataset.shards_quarantined == 2
    assert len(tr.dataset.quarantined) == 12
    tr.train()
    assert tr.step == 4
    assert tr.stalled is False
    tr.ckpt.close()


def test_train_packed_overlap_acceptance(tmp_path, srn_root):
    # What a CPU run can hold of the packed loader's overlap: the run
    # reaches its end, and every step leaves a data_fetch and a
    # train_step span in telemetry.jsonl for the ratio to be read from
    # (data_fetch p99 against train_step p50: host decode and upload
    # hidden behind device compute). The ratio itself is a device
    # number, not measured on the chip yet: on shared CPU cores under
    # xdist it is the scheduler's, and is not asserted here.
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    out = _pack_fresh(tmp_path, srn_root)
    cfg = _train_config(out, tmp_path, num_steps=72, log_every=36)
    tr = Trainer(config=cfg, use_grain=False)
    tr.train()
    assert tr.step == 72
    tr.ckpt.close()

    spans = {}
    with open(os.path.join(str(tmp_path), "results",
                           "telemetry.jsonl")) as fh:
        for line in fh:
            rec = json.loads(line)
            if rec.get("kind") == "span":
                spans.setdefault(rec["name"], []).append(
                    float(rec["dur_s"]))

    fetch, step = spans["data_fetch"], spans["train_step"]
    assert len(fetch) >= 70 and len(step) >= 70

    # The summarize_bench input-pipeline section renders this run.
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import summarize_bench

    telem = summarize_bench.telemetry_rows([str(tmp_path)])
    lines = summarize_bench.input_pipeline_lines(telem)
    assert any("data_fetch" in ln or "fetch p99" in ln for ln in lines)
    assert any("telemetry.jsonl" in ln for ln in lines if "|" in ln)

"""Trainer: the end-to-end training driver.

API-compatible with the reference's `Trainer(folder, *, train_batch_size,
train_lr, train_num_steps, save_every, img_sidelength, results_folder)`
(train.py:78-126) but TPU-native throughout: mesh + sharded batches instead
of pmap replication, on-device noising, Orbax checkpoints with auto-resume
(the reference cannot resume — SURVEY.md §5.4), real metrics, periodic
sample dumps, and optional jax.profiler traces.
"""

from __future__ import annotations

import os
import queue
import signal
import threading
import time
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from novel_view_synthesis_3d_tpu import obs
from novel_view_synthesis_3d_tpu.config import Config
from novel_view_synthesis_3d_tpu.data.pipeline import (
    cycle,
    iter_batches,
    make_dataset,
    make_grain_loader,
)
from novel_view_synthesis_3d_tpu.diffusion.schedules import (
    make_schedule,
    sampling_schedule,
)
from novel_view_synthesis_3d_tpu.models import build_denoiser, require_family
from novel_view_synthesis_3d_tpu.parallel import dist, mesh as mesh_lib
from novel_view_synthesis_3d_tpu.parallel import pipeline as pipeline_lib
from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler
from novel_view_synthesis_3d_tpu.train.checkpoint import CheckpointManager
from novel_view_synthesis_3d_tpu.train.guard import init_guard_state
from novel_view_synthesis_3d_tpu.train.metrics import MetricsLogger
from novel_view_synthesis_3d_tpu.train.state import (
    create_train_state,
    pack_train_state,
    unpack_ema,
    unpack_train_state,
)
from novel_view_synthesis_3d_tpu.train.step import (
    effective_accum_steps,
    make_train_step,
)
from novel_view_synthesis_3d_tpu.utils import faultinject, watchdog
from novel_view_synthesis_3d_tpu.utils.images import save_image_grid
from novel_view_synthesis_3d_tpu.utils.profiling import (
    StepTimer,
    enable_nan_checks,
)


def _sample_model_batch(batch: dict) -> dict:
    """Shape-template batch for model.init from a clean data batch."""
    target = batch["target"]
    return {
        "x": jnp.asarray(batch["x"]),
        "z": jnp.asarray(target),
        "logsnr": jnp.zeros((target.shape[0],)),
        "R1": jnp.asarray(batch["R1"]),
        "t1": jnp.asarray(batch["t1"]),
        "R2": jnp.asarray(batch["R2"]),
        "t2": jnp.asarray(batch["t2"]),
        "K": jnp.asarray(batch["K"]),
    }


class _DevicePrefetcher:
    """Bounded background uploader: runs `make_batch` (host fetch + async
    device_put) up to `depth` batches ahead of the consumer.

    Replaces the hardcoded depth-1 prefetch slot: with depth > 1 a slow
    fetch (cold page cache, contended loader workers) is absorbed by the
    buffered batches instead of stalling the very next step. `data.prefetch`
    sets the depth — the same knob that sizes the loaders' host-side
    prefetch, so one number describes the whole feed pipeline.

    Terminal conditions ride the queue in-band: StopIteration from the
    data iterator parks the prefetcher in an 'ended' state (get() raises
    StopIteration — only fatal if the trainer actually needs another
    batch, preserving the finite-injected-iterator contract), and any
    other exception re-raises in the consumer. The producer thread is a
    daemon: a fetch wedged in uninterruptible IO can't block interpreter
    exit (the run watchdog catches the stall itself — the consumer blocks
    inside its armed `data_fetch` phase once the buffer drains)."""

    _END = "end"
    _ERROR = "error"
    _BATCH = "batch"

    def __init__(self, make_batch: Callable[[], object], depth: int):
        self._make_batch = make_batch
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._terminal = None  # sticky ("end"|"error", exc) once popped
        self._gen = 0  # bumped by flush(); stale-generation batches drop
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="device-prefetch")
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            gen = self._gen  # read BEFORE the fetch: a flush() during
            # make_batch leaves this item stale, and get() discards it
            try:
                item = (self._BATCH, self._make_batch(), gen)
            except StopIteration:
                item = (self._END, None, gen)
            except BaseException as exc:  # propagate to the consumer
                item = (self._ERROR, exc, gen)
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.2)
                    break
                except queue.Full:
                    continue
            if item[0] != self._BATCH:
                return

    def get(self):
        """Next device batch; raises StopIteration at stream end, or the
        producer's exception. Blocks while the buffer is empty — callers
        arm the watchdog's data_fetch phase around this."""
        if self._terminal is not None:
            kind, exc = self._terminal
            raise StopIteration if kind == self._END else exc
        while True:
            kind, val, gen = self._q.get()
            if kind == self._BATCH:
                if gen != self._gen:
                    continue  # fetched before a flush(): suspect, drop
                return val
            self._terminal = (kind, val)
            if kind == self._END:
                raise StopIteration
            raise val

    def flush(self) -> None:
        """Drop buffered batches (rollback: the staged data is suspect) —
        including one currently inside make_batch on the producer thread,
        which lands in the queue AFTER this returns but carries the old
        generation and is discarded by get(). Terminal items stay sticky;
        the producer simply refills."""
        self._gen += 1  # before the drain: an in-flight fetch stays stale
        while True:
            try:
                kind, val, _gen = self._q.get_nowait()
            except queue.Empty:
                return
            if kind != self._BATCH:
                self._terminal = (kind, val)
                return

    def stop(self) -> None:
        self._stop.set()
        # Drain so a producer blocked on a full queue can observe _stop.
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)


class Trainer:
    def __init__(
        self,
        folder: Optional[str] = None,
        *,
        train_batch_size: int = 2,
        train_lr: float = 1e-4,
        train_num_steps: int = 100_000,
        save_every: int = 1000,
        img_sidelength: int = 64,
        results_folder: str = "./results",
        config: Optional[Config] = None,
        data_iter: Optional[Iterator[dict]] = None,
        use_grain: bool = True,
        skip_batches: int = 0,
    ):
        if config is None:
            config = Config()
        if folder is not None:
            config = config.override(**{
                "data.root_dir": folder,
                "train.batch_size": train_batch_size,
                "train.lr": train_lr,
                "train.num_steps": train_num_steps,
                "train.save_every": save_every,
                "data.img_sidelength": img_sidelength,
                "train.results_folder": results_folder,
            })
        self.config = config.validate()
        require_family(
            config.model, "xunet", "train.Trainer",
            "a router balance loss, expert gradients through the grouped "
            "product and the optimizer's share of an expert-parallel layer "
            "(train/step.py)")
        tcfg = config.train

        dist.initialize_distributed()
        self.mesh = mesh_lib.make_mesh(config.mesh)
        mesh_lib.validate_global_batch(self.mesh, tcfg.batch_size)

        # --- data ---
        self._native_loader = None
        self._packed_loader = None
        # skip_batches: mid-rung ladder resume (train/ladder.py) — the
        # loader replays that many batches' PLANNING before yielding, so
        # a resumed rung consumes the exact batches the uninterrupted
        # run would have. Only the pipelined packed loader implements it.
        if skip_batches and (data_iter is not None
                             or config.data.backend != "packed"):
            raise ValueError(
                "skip_batches (ladder mid-rung resume) requires "
                "data.backend='packed' with no injected data_iter — the "
                "other backends have no deterministic plan stream to "
                "fast-forward")
        if data_iter is not None:
            self.data_iter = data_iter
            self.dataset = None
        elif config.data.mix:
            # Corpus mixer (data/corpus.py): N packed corpora behind one
            # FlatViewDataset-shaped surface; validate() already pinned
            # backend='packed' for mixes.
            from novel_view_synthesis_3d_tpu.data.corpus import (
                make_mixed_dataset)

            self.dataset = make_mixed_dataset(
                config.data,
                shard_index=jax.process_index(),
                shard_count=jax.process_count())
        else:
            self.dataset = make_dataset(
                config.data,
                # Packed backend: per-host reads at shard granularity —
                # this process opens only its 1/process_count() slice of
                # the shard set (files backend ignores the kwargs; its
                # sharding happens at the index-sampler level).
                shard_index=jax.process_index(),
                shard_count=jax.process_count())
        if self.dataset is not None:
            assert len(self.dataset) > 0
            local_bs = dist.local_batch_size(tcfg.batch_size)
            num_cond = config.model.num_cond_frames
            spi = config.data.samples_per_instance
            if spi > 1 and local_bs % spi != 0:
                # Config.validate checks the GLOBAL batch (it has no process
                # topology); the per-host slice must divide too.
                raise ValueError(
                    f"per-host batch {local_bs} (train.batch_size="
                    f"{tcfg.batch_size} over {jax.process_count()} "
                    f"processes) is not divisible by "
                    f"data.samples_per_instance={spi}")
            # Instance-grouped sampling (samples_per_instance > 1) is
            # implemented by all backends: in-process iterator, Grain
            # (grouped transform + flatten), the native loader (grouped
            # claims in C++), and the packed pipelined loader (grouped
            # plans) — no fallback needed.
            backend = config.data.loader if use_grain else "python"
            if config.data.backend == "packed":
                # Compute-overlapped pipelined loader (decode worker pool
                # feeding the _DevicePrefetcher below); `loader`/use_grain
                # govern the files backend only. A data.mix runs the
                # weighted mixer variant over the MixedDataset built above.
                if config.data.mix:
                    from novel_view_synthesis_3d_tpu.data.corpus import (
                        make_mixed_loader)

                    self._packed_loader = make_mixed_loader(
                        self.dataset, local_bs,
                        seed=config.data.shuffle_seed,
                        shard_index=jax.process_index(),
                        num_cond=num_cond,
                        workers=config.data.num_workers,
                        depth=config.data.prefetch,
                        skip_batches=skip_batches)
                else:
                    from novel_view_synthesis_3d_tpu.data.pipeline import (
                        make_packed_loader)

                    self._packed_loader = make_packed_loader(
                        self.dataset, local_bs,
                        seed=config.data.shuffle_seed,
                        shard_index=jax.process_index(),
                        num_cond=num_cond,
                        workers=config.data.num_workers,
                        depth=config.data.prefetch,
                        skip_batches=skip_batches)
                self.data_iter = iter(self._packed_loader)
            elif backend == "native":
                from novel_view_synthesis_3d_tpu.data import native_io
                if native_io.available():
                    self._native_loader = native_io.make_native_loader(
                        self.dataset, local_bs, num_cond=num_cond,
                        n_threads=config.data.num_workers,
                        prefetch_depth=config.data.prefetch,
                        seed=config.data.shuffle_seed,
                        shard_index=jax.process_index(),
                        shard_count=jax.process_count(),
                        max_record_retries=config.data.max_record_retries)
                    self.data_iter = iter(self._native_loader)
                else:
                    # The documented fallback, said out loud: a run that
                    # quietly lost its C++ loader looks like a slow chip.
                    print("note: native IO library unavailable (`make -C "
                          "native` failed or ABI mismatch): data.loader="
                          "'native' falls back to the Grain loader")
                    backend = "grain"
            if self._packed_loader is not None:
                pass  # data_iter already set above
            elif backend == "grain" and config.data.num_workers > 0:
                loader = make_grain_loader(
                    self.dataset, local_bs,
                    seed=config.data.shuffle_seed,
                    num_workers=config.data.num_workers,
                    num_cond=num_cond)
                self.data_iter = cycle(loader)
            elif self._native_loader is None:
                self.data_iter = iter_batches(
                    self.dataset, local_bs, seed=config.data.shuffle_seed,
                    shard_index=jax.process_index(),
                    shard_count=jax.process_count(),
                    num_cond=num_cond)

        # --- model / schedule / state ---
        self.schedule = make_schedule(config.diffusion)
        # train.remat overrides the checkpoint policy for the TRAINING
        # build only ('' = inherit model.remat): the param tree layout is
        # remat-independent (models/xunet._named_remat), so checkpoints
        # stay portable to samplers built without it.
        model_cfg = config.model
        if config.train.remat != "":
            import dataclasses as _dc
            model_cfg = _dc.replace(model_cfg, remat=config.train.remat)
        self.model = build_denoiser(model_cfg, mesh=self.mesh)
        first_batch = next(self.data_iter)
        self._held_batch = first_batch
        self._device_batch = None  # staged batch for the NEXT dispatch
        # Background device prefetcher (train()): fetches + uploads up to
        # data.prefetch batches ahead. The lock serializes its data_iter
        # access against main-thread peeks (eval probe, dump_samples).
        self._prefetcher: Optional[_DevicePrefetcher] = None
        self._data_lock = threading.Lock()
        # Fixed probe batch for eval_every: scoring the SAME views every
        # time makes the PSNR/SSIM curve comparable across steps (a fresh
        # random batch per eval would swing several dB on content alone).
        # Only copied when the probe is on — it pins a full batch in host
        # RAM for the Trainer's lifetime. With train.eval_folder set, the
        # probe batch is drawn from that HELD-OUT tree instead of the first
        # training batch, turning eval.csv into a true validation curve.
        self._eval_batch = None
        if tcfg.eval_every:
            if tcfg.eval_folder:
                self._eval_batch = jax.tree.map(
                    np.array, self._held_out_probe_batch(tcfg.eval_folder))
            else:
                self._eval_batch = jax.tree.map(np.array, first_batch)
        self._samplers = {}  # sample_steps -> jitted sampler (_sample_cond)
        self._cond_sens_fn = None  # lazily-built jitted probe (eval_step)
        self.state = create_train_state(
            tcfg, self.model, _sample_model_batch(first_batch))
        # ZeRO update sharding (train.update_sharding='zero'): between
        # steps the state carries opt_state/EMA in the packed row-sharded
        # layout of parallel/zero.py — 1/data_shards of those bytes per
        # device. Every host boundary (checkpoint save/restore, registry
        # publish, probes) converts through pack/unpack below so the rest
        # of the trainer only ever sees the canonical layout.
        self._zero = tcfg.update_sharding == "zero"
        if self._zero:
            self.state, self._state_sharding = pack_train_state(
                tcfg, self.mesh, self.state)
        else:
            self._state_sharding = mesh_lib.state_shardings(
                self.mesh, self.state, tcfg.fsdp, tp=tcfg.tp)
        self.state = jax.device_put(self.state, self._state_sharding)
        self.train_step = make_train_step(
            config, self.model, self.schedule, self.mesh,
            state_sharding=self._state_sharding)

        # --- host-side EMA (train.ema_host) ---
        # The EMA buffer lives in host RAM (f32 numpy) instead of HBM —
        # 4 bytes/param of chip memory back, the paper256-on-16G margin
        # (config.py preset comment). Folded in every ema_host_every steps
        # with the decay^k correction; rides in the checkpoint as the
        # state's ema_params leaves.
        self._host_ema = None
        self._host_ema_step = 0
        self._host_ema_pending = False  # seed from params at first fold
        ema_host_on = tcfg.ema_host and tcfg.ema_decay > 0
        if ema_host_on:
            # Structure-only template (the restore path just needs matching
            # tree structure/shapes). Seeding from the live params is
            # DEFERRED to the first fold: a pull here would be (a) a full
            # param transfer discarded on every resume and (b) on pods an
            # un-barriered replication collective inside __init__, where
            # per-host init-compile stagger can blow the communicator
            # rendezvous window — the first fold instead runs at a point
            # where every host is in lock-step.
            self._host_ema = jax.tree.map(
                lambda p: np.zeros(p.shape, np.float32), self.state.params)
            self._host_ema_pending = True

        # --- telemetry (obs/: spans + registry + sinks + gauges) ---
        # Created BEFORE the MetricsLogger so both share one EventBus —
        # the single write path for metrics.csv/events.csv/telemetry.jsonl.
        # The /metrics endpoint starts here iff obs.metrics_port is set.
        self.telemetry = obs.RunTelemetry.create(
            config.obs, tcfg.results_folder)
        self.tracer = self.telemetry.tracer
        reg = self.telemetry.registry
        self._steps_total = reg.counter(
            "nvs3d_steps_total", "optimizer steps completed this process")
        self._gauge_steps_per_sec = reg.gauge(
            "nvs3d_steps_per_sec", "training steps per second")
        self._gauge_imgs_per_sec = reg.gauge(
            "nvs3d_imgs_per_sec_per_chip",
            "training images per second per chip")
        self._gauge_mfu = reg.gauge(
            "nvs3d_mfu", "model-FLOPs utilization of the train step")
        self._gauge_loss = reg.gauge("nvs3d_loss", "last logged train loss")
        # Static memory/topology gauges: set once at init. The *_bytes
        # gauges report PER-DEVICE bytes (local shard shapes), so a ZeRO
        # run shows opt/EMA at ~1/data_shards of the replicated numbers —
        # the measured half of the ISSUE's memory claim, also asserted in
        # tests/test_zero.py.
        self._gauge_params_bytes = reg.gauge(
            "nvs3d_params_bytes", "per-device bytes of the param tree")
        self._gauge_opt_state_bytes = reg.gauge(
            "nvs3d_opt_state_bytes",
            "per-device bytes of the optimizer state")
        self._gauge_ema_bytes = reg.gauge(
            "nvs3d_ema_bytes", "per-device bytes of the EMA tree")
        self._gauge_pipeline_bubble = reg.gauge(
            "nvs3d_pipeline_bubble_frac",
            "GPipe fill/drain bubble fraction of the pipelined step")
        self._gauge_params_bytes.set(
            float(mesh_lib.tree_device_bytes(self.state.params)))
        self._gauge_opt_state_bytes.set(
            float(mesh_lib.tree_device_bytes(self.state.opt_state)))
        self._gauge_ema_bytes.set(
            float(mesh_lib.tree_device_bytes(self.state.ema_params)))
        stages = config.mesh.stages
        self._gauge_pipeline_bubble.set(
            pipeline_lib.bubble_fraction(
                effective_accum_steps(
                    tcfg.batch_size, mesh_lib.num_data_shards(self.mesh),
                    tcfg.grad_accum_steps), stages)
            if stages > 1 else 0.0)
        # One-time FLOPs estimate for MFU (obs.cost_analysis): filled at
        # the first dispatch via train_step.lower(...).cost_analysis().
        self._flops_per_step: Optional[float] = None
        # Compile ledger (obs/compiles.py): every jit build this process
        # makes lands in compiles.jsonl with a fingerprint, so a recompile
        # can name the argument that changed. The train step's entry is
        # recorded at its first dispatch (where the wall time is known).
        self._compile_ledger = obs.CompileLedger(tcfg.results_folder,
                                                 registry=reg)
        self._train_step_hlo = ""
        # Numerics observatory (train.numerics): host half of the in-jit
        # per-layer-group stats — numerics.jsonl rows, grad-norm gauges,
        # EWMA spike detection. The labels are kept even with the monitor
        # off: the step always emits the stats, so NaN provenance
        # (first_bad_layer on anomaly events/flight dumps) works without
        # opting into the full observatory.
        from novel_view_synthesis_3d_tpu.models.xunet import op_groups

        self._numerics_labels = obs.group_labels(op_groups(config.model))
        self._numerics: Optional[obs.NumericsMonitor] = None
        if tcfg.numerics.enabled:
            self._numerics = obs.NumericsMonitor(
                self._numerics_labels,
                self.telemetry.bus, reg,
                every=tcfg.numerics.every,
                spike_z=tcfg.numerics.spike_z,
                ewma_decay=tcfg.numerics.ewma_decay)
        # Continuous profiler (obs.profile): re-arming jax.profiler
        # windows attributed to the same op-group vocabulary. Host-side
        # only; the loop hook sits next to the one-shot xprof window's.
        self._profiler = obs.make_profiler(
            config.obs.profile, tcfg.results_folder, config.model,
            self.telemetry.bus, reg) if config.obs.enabled else None
        # armed_steps_total snapshot at the last metrics log: a log
        # interval that overlapped a profile window skips the step-rate
        # gauges (the overhead-exclusion contract).
        self._profiler_armed_mark = 0
        # /healthz progress facts: an external probe distinguishes
        # wedged-but-listening from healthy by last_step_age_s.
        self._last_step_t = time.time()
        if self.telemetry.server is not None:
            self.telemetry.server.set_health_provider(self._health_snapshot)

        # --- checkpointing / metrics ---
        self.ckpt = CheckpointManager(tcfg.checkpoint_dir)
        # Fault-tolerance bookkeeping (docs/DESIGN.md "Fault tolerance"):
        # rollback budget consumed + last anomaly total observed (to log
        # each new anomaly exactly once).
        self._rollbacks = 0
        self._anomalies_seen = 0
        if tcfg.resume:
            # restore_with_growth (train/ladder.py): a checkpoint saved
            # before model.num_classes grew the category table restores
            # with the table's zero-init spliced in (asserted neutral);
            # same-version checkpoints take the plain path inside.
            from novel_view_synthesis_3d_tpu.train.ladder import (
                restore_with_growth)

            restored = restore_with_growth(self.ckpt, self._ckpt_state())
            if restored is not None:
                restored = self._adopt_restored_state(restored)
                # Restore provenance line: which step actually resumed, and
                # whether corrupt newer steps were walked past.
                prov = self.ckpt.last_restore or {}
                rejected = prov.get("rejected", [])
                fallback = (f" (fell back past corrupt step(s) "
                            f"{[s for s, _ in rejected]})" if rejected
                            else "")
                print(f"resumed from checkpoint at step "
                      f"{int(self.state.step)}{fallback}")
        self.metrics = MetricsLogger(tcfg.results_folder,
                                     bus=self.telemetry.bus)
        prov = self.ckpt.last_restore or {}
        for bad_step, reason in prov.get("rejected", []):
            self.metrics.log_event(
                int(prov["step"]), "restore_fallback",
                f"step {bad_step} rejected: {reason.splitlines()[0][:160]}")
        self.results_folder = tcfg.results_folder
        os.makedirs(self.results_folder, exist_ok=True)

        # --- registry publisher (registry.publish_every; docs/DESIGN.md
        # "Model lifecycle") ---
        # Every publish_every steps the EMA snapshot is published to the
        # registry's `latest` channel as a content-hashed version. The
        # hand-off is a reference; serialization/hashing/fsync run on the
        # publisher's worker thread, so the step loop never blocks on
        # registry IO. Process 0 only — the snapshot gather below is the
        # collective part every host joins.
        self._publisher = None
        rcfg = config.registry
        if rcfg.publish_every > 0 and jax.process_index() == 0:
            from novel_view_synthesis_3d_tpu.registry import (
                RegistryPublisher, RegistryStore)
            from novel_view_synthesis_3d_tpu.registry.manifest import (
                config_digest)

            bus = self.telemetry.bus
            self._publisher = RegistryPublisher(
                RegistryStore(rcfg.dir),
                ema=rcfg.publish_ema and tcfg.ema_decay > 0,
                config_digest=config_digest(config),
                event_cb=lambda step, kind, detail, version="": bus.event(
                    step, kind, detail, model_version=version,
                    echo="[registry]"))
        # units_per_measure: each measured region covers one dispatch, i.e.
        # steps_per_dispatch training steps — normalize so the end-of-run
        # summary reports true per-step times at any dispatch width.
        self.timer = StepTimer(units_per_measure=tcfg.steps_per_dispatch)
        if tcfg.debug_nans:
            enable_nan_checks()

        # Preemption handling (SURVEY.md §5.3 — the reference has none):
        # TPU VMs receive SIGTERM on maintenance/preemption. Flag it and let
        # the step loop checkpoint + exit cleanly; combined with
        # resume=True the run continues from the last step after reschedule.
        self._preempted = False
        if tcfg.handle_preemption:
            try:
                signal.signal(signal.SIGTERM, self._on_preempt)
            except ValueError:
                pass  # not the main thread (e.g. under some test runners)

        # Hang/stall watchdog (utils/watchdog.py; docs/DESIGN.md "Stall
        # recovery"). The monitor thread starts with train() and feeds on
        # the loop's phase markers; _on_stall below runs ON THE MONITOR
        # THREAD, so it only writes (events.csv row, flag) — escalation is
        # observed by the main loop at the next cross-host agreement
        # point, exactly like preemption.
        self._stalled = False  # set by the watchdog; observed by the loop
        self._fetches = 0  # host-batch fetch ordinal (data-stall drills)
        self._step_host = self.step  # sync-free step estimate (watchdog)
        # Supervised-restart generation (train/supervisor.py): rides into
        # metrics.csv so a curve produced across restarts says so.
        from novel_view_synthesis_3d_tpu.train.supervisor import RESTART_ENV
        self._restarts = int(os.environ.get(RESTART_ENV, "0") or 0)
        if self._restarts:
            self.metrics.log_event(
                self.step, "supervised_resume",
                f"restart generation {self._restarts} resumed at step "
                f"{self.step}")
        self.watchdog = watchdog.from_config(
            tcfg.watchdog, on_stall=self._on_stall,
            diagnosis_dir=tcfg.results_folder,
            # Device memory queries can themselves hang on a wedged
            # backend; the bundle helper bounds them, but skip entirely in
            # multi-process runs where a straggling query could collide
            # with collectives.
            query_device=jax.process_count() == 1)

    def _on_preempt(self, signum, frame) -> None:
        self._preempted = True

    def _on_stall(self, phase: str, diagnosis_path: str) -> None:
        """Watchdog escalation (monitor thread — flags only, no JAX calls).

        Per-phase policy: a stalled checkpoint_save DEGRADES (diagnosis +
        events.csv row; training continues — exiting through a save that
        is itself stuck would be circular, and the save path already has
        retry/degrade semantics); every other phase flags a cross-host-
        agreed checkpoint-and-exit, the same escalation lane preemption
        uses, so one stuck host can't wedge the slice."""
        degrade = phase == "checkpoint_save"
        self.metrics.log_event(
            self.step_host_estimate, "stall",
            f"phase {phase} exceeded its watchdog budget; diagnosis in "
            f"{diagnosis_path}"
            + ("; degrading (save retries continue)" if degrade
               else "; checkpoint-and-exit requested"))
        # Flight-recorder dump next to the watchdog's stall bundle: the
        # last ~512 spans/events/gauges BEFORE the stall (no JAX calls —
        # safe on the monitor thread).
        if self.telemetry.flight is not None:
            self.telemetry.flight.dump(
                "stall", phase=phase, step=self.step_host_estimate,
                degrade=degrade)
        if not degrade:
            self._stalled = True

    @property
    def step_host_estimate(self) -> int:
        """Last step count observed WITHOUT a device sync — safe to read
        from the watchdog thread while the main thread is stuck inside a
        dispatch (self.step would join it in the hang)."""
        return self._step_host

    def _stop_agreed(self) -> int:
        """Cross-host agreement on the exit flags (0 none, 1 preempted,
        2 watchdog stall — max over hosts wins).

        SIGTERM (or a stall) can land at different step boundaries on
        different hosts; if one host broke into the (collective)
        checkpoint save while another entered the next train step's psum,
        the mismatched collectives would hang the slice. Every host
        therefore joins an allgather each step and all of them break
        together iff any host flagged. The per-step allgather is a few µs
        over ICI — negligible next to a train step.
        """
        local = 2 if self._stalled else (1 if self._preempted else 0)
        if jax.process_count() == 1:
            return local
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(np.asarray(local))
        return int(np.max(flags))

    @property
    def stalled(self) -> bool:
        """True once the watchdog escalated a stall (cli.cmd_train exits
        with watchdog.EXIT_STALL so a supervisor restarts the run)."""
        return self._stalled

    # ------------------------------------------------------------------
    @property
    def step(self) -> int:
        return int(jax.device_get(self.state.step))

    def _next_batch(self) -> dict:
        with self._data_lock:
            if self._held_batch is not None:
                batch, self._held_batch = self._held_batch, None
                return batch
            return next(self.data_iter)

    def _peek_batch(self) -> dict:
        """Look at the next batch without consuming it from the loop."""
        with self._data_lock:
            if self._held_batch is None:
                self._held_batch = next(self.data_iter)
            return self._held_batch

    # ------------------------------------------------------------------
    def _host_params(self):
        """Full host numpy copy of the live params. On multi-process runs
        EVERY host joins a replication collective first (FSDP shards →
        fully replicated), so all hosts see — and host-EMA over — the same
        tree; call at the same step on every host."""
        params = self.state.params
        if jax.process_count() > 1:
            params = mesh_lib.replicate(self.mesh, params)
        return jax.device_get(params)

    def _ckpt_state(self):
        """State handed to Orbax: with host EMA on, the numpy EMA tree
        rides in ema_params (StandardSave/Restore handle mixed
        device/numpy leaves), so the checkpoint format is identical to a
        device-EMA run's."""
        state = self.state
        if self._zero:
            # Gather-on-save: checkpoints always hold the CANONICAL
            # layout, so a run can resume under either update_sharding
            # setting (tests/test_zero.py round-trips both ways). The
            # device_get is the same full-state fetch Orbax would do.
            state = unpack_train_state(
                self.config.train, self.mesh, jax.device_get(state))
        if self._host_ema is None:
            return state
        return state.replace(ema_params=self._host_ema)

    def _adopt_restored_state(self, restored):
        """Install a checkpoint-restored TrainState (resume or rollback):
        peel the host-EMA tree back into host RAM, shard the rest onto the
        mesh, and re-anchor the sparse-EMA step counter.

        The restored leaves are explicitly COPIED before the donating train
        step may consume them: on the CPU backend Orbax/tensorstore can
        hand back arrays aliasing its own restore buffers, and jit
        donation then writes outputs into that shared memory — observed as
        garbage step counters right after a rollback (fault-injection
        suite). jnp.copy is cheap next to the restore IO and guarantees
        the state owns its buffers on every backend."""
        if self._host_ema is not None:
            self._host_ema = jax.tree.map(np.asarray, restored.ema_params)
            self._host_ema_pending = False
            restored = restored.replace(ema_params=None)
        owned = jax.tree.map(
            lambda a: jnp.copy(a) if isinstance(a, jax.Array) else a,
            restored)
        if self._zero:
            # Checkpoints are canonical (gather-on-save above); re-pack
            # into the row-sharded between-steps layout before device_put.
            owned, _ = pack_train_state(self.config.train, self.mesh, owned)
        self.state = jax.device_put(owned, self._state_sharding)
        self._host_ema_step = int(jax.device_get(restored.step))
        return restored

    def _rollback(self, step_now: int) -> None:
        """Anomaly-guard escalation: restore the last intact checkpoint.

        Fired when `max_anomaly_strikes` consecutive steps were anomalous —
        the skipped-update guard alone isn't recovering, so the optimizer
        state (or the data window) is presumed poisoned. The restored state
        gets a RESEEDED rng (same rng + same step would replay the exact
        t/ε/dropout draws that blew up) and a cleared guard; the data
        stream simply continues — the replayed steps see fresh batches.
        Bounded by `max_rollbacks`, then abort: past that point the fault
        is systematic and retrying only burns pod-hours."""
        tcfg = self.config.train
        self._rollbacks += 1
        self.metrics.log_event(
            step_now, "rollback",
            f"{tcfg.max_anomaly_strikes} consecutive anomalies; attempt "
            f"{self._rollbacks}/{tcfg.max_rollbacks}")
        if self._rollbacks > tcfg.max_rollbacks:
            raise RuntimeError(
                f"anomaly guard: {tcfg.max_anomaly_strikes} consecutive "
                f"anomalous steps at step {step_now} and the rollback "
                f"budget (train.max_rollbacks={tcfg.max_rollbacks}) is "
                "exhausted — aborting. Inspect metrics.csv/events.csv; "
                "likely a systematic fault (bad data shard, lr blow-up), "
                "not a transient.")
        self.ckpt.wait()
        with self.tracer.span("checkpoint_restore", step=step_now):
            restored = self.ckpt.restore(self._ckpt_state())
        if restored is None:
            raise RuntimeError(
                f"anomaly guard: rollback requested at step {step_now} but "
                "no checkpoint exists yet (train.save_every="
                f"{tcfg.save_every}) — aborting before the anomaly "
                "propagates")
        restored = restored.replace(
            rng=jax.random.fold_in(restored.rng, 0x5EED + self._rollbacks),
            guard=(init_guard_state() if restored.guard is not None
                   else None))
        self._adopt_restored_state(restored)
        self._anomalies_seen = 0
        self._device_batch = None  # drop the staged (suspect) batch
        if self._prefetcher is not None:
            self._prefetcher.flush()  # ...and the buffered ones behind it
        self.metrics.log_event(
            self.step, "rollback_restored",
            f"resumed at step {self.step} with reseeded rng")

    def _check_guard(self, step_now: int, step_metrics: dict) -> bool:
        """Host-side half of the anomaly guard: log new anomalies, roll
        back when strikes exceed the budget. Returns True if a rollback
        happened (the loop should restart its iteration)."""
        tcfg = self.config.train
        if not tcfg.anomaly_guard or "strikes" not in step_metrics:
            return False
        strikes, anomalies = (int(v) for v in jax.device_get(
            [step_metrics["strikes"], step_metrics["anomalies"]]))
        if anomalies > self._anomalies_seen:
            # NaN provenance (obs/numerics.py): the per-group non-finite
            # counts name the first bad layer group, so the anomaly event
            # (and the flight dump) carry their root cause.
            first_bad = ""
            if "numerics" in step_metrics:
                first_bad = obs.first_bad_group(
                    self._numerics_labels,
                    jax.device_get(step_metrics["numerics"]["nonfinite"]))
            detail = (f"non-finite/spike step skipped (strikes={strikes}, "
                      f"total={anomalies})")
            if first_bad:
                detail += f" first_bad_layer={first_bad}"
            self.metrics.log_event(step_now, "anomaly", detail)
            if (self.telemetry.flight is not None
                    and strikes <= tcfg.steps_per_dispatch):
                # One forensics dump per strike streak (its first
                # anomalous dispatch), not per anomaly — a poisoned-run
                # drill must not carpet the results folder.
                self.telemetry.flight.dump(
                    "anomaly", step=step_now, strikes=strikes,
                    anomalies=anomalies, first_bad_layer=first_bad)
            self._anomalies_seen = anomalies
        if strikes >= tcfg.max_anomaly_strikes:
            self._rollback(step_now)
            return True
        return False

    def _maybe_update_host_ema(self, step_now: int,
                               force: bool = False) -> None:
        """Fold the live params into the host EMA buffer if due.

        Sparse EMA: k elapsed steps fold in with decay^k —
        ema ← d^k·ema + (1−d^k)·params — exact for k=1 and the standard
        approximation for k>1 (one params→host transfer per
        ema_host_every steps instead of per step). `force` (checkpoint
        saves, probes) flushes regardless of the interval."""
        if self._host_ema is None:
            return
        if self._host_ema_pending:
            # First touch of a fresh (non-resumed) run: seed EMA = params.
            # On pods every host reaches here at the same step (the fold
            # sites are symmetric), so the replicate inside _host_params
            # rendezvouses in lock-step.
            self._host_ema = jax.tree.map(
                lambda a: np.asarray(a, np.float32), self._host_params())
            self._host_ema_pending = False
            self._host_ema_step = step_now
            return
        k = step_now - self._host_ema_step
        if k <= 0 or (not force and k < self.config.train.ema_host_every):
            return
        d = self.config.train.ema_decay ** k
        params = self._host_params()
        self._host_ema = jax.tree.map(
            lambda e, p: d * e + (1.0 - d) * np.asarray(p, np.float32),
            self._host_ema, params)
        self._host_ema_step = step_now

    def _make_device_batch(self):
        """One dispatch's worth of data: host fetch + async device upload.

        Runs on the prefetcher thread (train()) up to data.prefetch
        batches ahead of the consumer; the device_put inside shard_batch
        is async, so buffered batches are in flight to HBM while the
        device executes earlier steps. The stall drill keys on the fetch
        ordinal — deterministic regardless of how far ahead the
        prefetcher runs.

        With train.steps_per_dispatch = K > 1, K consecutive batches are
        stacked on a leading step axis and consumed by one fused-scan
        dispatch (train/step.py multi_step) — fresh data every step, K-1
        fewer dispatch round trips."""
        spd = self.config.train.steps_per_dispatch

        def clean(b):
            return {k: v for k, v in b.items() if k != "noise"}

        faultinject.maybe_stall("data", self._fetches)
        fetch = self._fetches
        self._fetches += 1
        # Two spans per staged batch: data_fetch is the HOST half (loader
        # wait + decode), h2d the device upload — on the trace timeline
        # these sit on the prefetcher thread's row, overlapping train_step
        # spans on the main thread when the pipeline is healthy.
        with self.tracer.span("data_fetch", fetch=fetch):
            if spd <= 1:
                host = clean(self._next_batch())
            else:
                hosts = [clean(self._next_batch()) for _ in range(spd)]
                host = jax.tree.map(lambda *xs: np.stack(xs), *hosts)
        with self.tracer.span("h2d", fetch=fetch):
            return mesh_lib.shard_batch(self.mesh, host, stacked=spd > 1)

    def _staged_batch(self):
        """The next device batch, blocking under the armed data_fetch
        phase: when the prefetch buffer is drained by a stalled loader,
        the consumer blocks HERE and the watchdog sees the stall exactly
        as it did when the fetch was inline."""
        with self.watchdog.phase("data_fetch"):
            if self._prefetcher is not None:
                return self._prefetcher.get()
            return self._make_device_batch()

    def train(self) -> None:
        tcfg = self.config.train
        last_metrics = None
        profiling = False
        self.watchdog.start()
        # Device prefetch honoring data.prefetch (was a hardcoded depth-1
        # slot): the background thread keeps up to `depth` staged batches
        # uploading while the device runs, so a fetch hiccup shorter than
        # depth × step-time never stalls a dispatch.
        self._prefetcher = _DevicePrefetcher(
            self._make_device_batch, depth=self.config.data.prefetch)
        try:
            self._train_loop(tcfg, last_metrics, profiling)
        except BaseException as exc:
            # Fatal exit (incl. KeyboardInterrupt/SystemExit): dump the
            # flight ring BEFORE the telemetry teardown below, so the
            # postmortem has the last spans/events leading into the
            # fault even when the process is about to die.
            if self.telemetry.flight is not None:
                self.telemetry.flight.dump(
                    "fatal", error=repr(exc)[:200],
                    step=self.step_host_estimate)
            raise
        finally:
            self._prefetcher.stop()
            self._prefetcher = None
            self.watchdog.stop()
            if self._publisher is not None:
                # Drain, don't drop: the final snapshot is usually the
                # one an operator wants to promote.
                self._publisher.stop(drain=True)
            # A window open at exit (run ended mid-capture) still stops,
            # parses, and lands its row — before the bus closes.
            if self._profiler is not None:
                self._profiler.close()
            # Export trace.json, stop the device monitor, close the bus
            # and endpoint. Idempotent; a crashed run still gets its
            # trace up to the fault.
            self.telemetry.finalize()

    def _train_loop(self, tcfg, last_metrics, profiling) -> None:
        # The first dispatch of the jitted train step runs under the
        # separate (long) compile budget; every later one under the
        # steady-state step budget.
        first_dispatch = True
        while self.step < tcfg.num_steps:
            if tcfg.profile_steps:
                at = self.step
                end = tcfg.profile_from + tcfg.profile_steps
                if profiling and at >= end:
                    jax.profiler.stop_trace()
                    profiling = False
                elif not profiling and tcfg.profile_from <= at < end:
                    # Range check (not equality) so the window still fires
                    # when resuming into or past profile_from.
                    jax.profiler.start_trace(
                        os.path.join(self.results_folder, "profile"))
                    profiling = True
            # Device batches come from the background prefetcher (up to
            # data.prefetch staged uploads in flight); a StopIteration is
            # only fatal when a step actually needs the missing batch.
            if self.telemetry.xprof is not None:
                # Sync-free step estimate: the xprof window tolerates a
                # ±1-dispatch skew; a device_get here would add a sync to
                # EVERY iteration just to arm a rarely-used capture.
                self.telemetry.xprof.on_step(self._step_host)
            if self._profiler is not None:
                # Continuous profiling window (same sync-free estimate).
                self._profiler.on_step(self._step_host)
            if self._device_batch is None:
                try:
                    self._device_batch = self._staged_batch()
                except StopIteration:
                    raise RuntimeError(
                        "data_iter exhausted before train.num_steps="
                        f"{tcfg.num_steps} (at step {self.step}). Injected "
                        "finite iterators must supply ceil(remaining_steps /"
                        f" steps_per_dispatch={tcfg.steps_per_dispatch}) * "
                        "steps_per_dispatch batches; with "
                        "steps_per_dispatch>1 a partial trailing group "
                        "cannot be dispatched.") from None
            if first_dispatch:
                # One-time FLOPs estimate for the MFU gauge, BEFORE the
                # donating dispatch deletes the state's buffers. lower()
                # only traces — no XLA compile, no device time.
                self._maybe_cost_analysis(self._device_batch)
                # Ledger fingerprint is taken BEFORE the donating dispatch
                # too — it reads the arg tree's shapes/dtypes.
                compile_fp = obs.fingerprint_args(
                    self.state, self._device_batch,
                    static=(self.config.model, self.config.diffusion,
                            self.config.train, self.config.mesh))
                compile_t0 = time.perf_counter()
            phase = "compile" if first_dispatch else "train_step"
            was_first = first_dispatch
            with self.timer.measure(), self.watchdog.phase(phase), \
                    self.tracer.span(phase) as sp:
                first_dispatch = False
                self.state, step_metrics = self.train_step(
                    self.state, self._device_batch)
                self._device_batch = None  # consumed (donated) by the step
                # Dispatch is async; the step read below device_gets
                # state.step, which syncs on the whole step — keep it inside
                # the timed region so timings reflect real device time.
                # (The NEXT batch's fetch + upload overlaps this step on
                # the prefetcher thread.)
                step_now = self.step
                self._step_host = step_now
                sp.set(step=step_now)
                # Deterministic hang drill: the injected sleep sits inside
                # the armed train_step phase, exactly where a wedged
                # dispatch would stall.
                faultinject.maybe_stall("step", step_now)
            if was_first:
                # Compile-ledger entry for the train step: the first
                # dispatch's wall time IS compile + first step (the same
                # definition the compile span/watchdog budget uses).
                self._compile_ledger.record(
                    "train_step", compile_fp,
                    wall_s=time.perf_counter() - compile_t0,
                    hlo=self._train_step_hlo,
                    backend=jax.default_backend())
            # /healthz heartbeat: a dispatch completed; last_step_age_s
            # restarts from zero.
            self._last_step_t = time.time()
            # Counter semantics: steps EXECUTED — each dispatch runs
            # steps_per_dispatch optimizer steps; a rolled-back window
            # that re-runs counts again (a Prometheus counter is monotone,
            # the step column in metrics.csv carries the logical step).
            self._steps_total.inc(self.config.train.steps_per_dispatch)

            # Numerics observatory: decimated host publish of the in-jit
            # per-group stats. BEFORE the guard check so an anomalous
            # window's stats (and its non-finite provenance) are on disk
            # even when the guard rolls back and restarts the loop.
            if self._numerics is not None and "numerics" in step_metrics:
                self._numerics.observe(step_now, step_metrics["numerics"])

            if self._check_guard(step_now, step_metrics):
                continue  # rolled back: restart the loop from the restore

            self._maybe_update_host_ema(step_now)

            # First-iteration log: step_now is 1 normally, K under fused
            # multi-step dispatch (both only at a fresh, non-resumed start).
            if (step_now % tcfg.log_every == 0
                    or step_now == tcfg.steps_per_dispatch):
                with self.tracer.span("d2h", step=step_now):
                    host_metrics = jax.device_get(step_metrics)
                util = self._utilization_metrics()
                corpus_cols = self._publish_corpus_stats(step_now,
                                                         host_metrics)
                logged = self.metrics.log(
                    step_now,
                    dict(host_metrics,
                         rollbacks=self._rollbacks,
                         restarts=self._restarts, **util),
                    tcfg.batch_size, extra=corpus_cols)
                # Overhead-exclusion contract (obs.profile): a log
                # interval that overlapped a profile window carries the
                # window's arm/parse host time in its wall clock, so its
                # step-rate samples are excluded from the rate gauges
                # (metrics.csv keeps every row — the gauges feed alerts).
                armed = (self._profiler.armed_steps_total
                         if self._profiler is not None else 0)
                self._update_gauges(
                    logged, util,
                    exclude_rates=armed != self._profiler_armed_mark)
                self._profiler_armed_mark = armed
                print(f"{step_now}: loss={logged['loss']:.5f} "
                      f"imgs/s/chip={logged['imgs_per_sec_per_chip']:.2f}")
                last_metrics = logged

            if tcfg.save_every and step_now % tcfg.save_every == 0:
                # Pass the (possibly FSDP-sharded) device state directly:
                # Orbax gathers per-shard across hosts; device_get would
                # crash on non-fully-addressable arrays in multi-host runs.
                self._maybe_update_host_ema(step_now, force=True)
                with self.watchdog.phase("checkpoint_save"), \
                        self.tracer.span("checkpoint_save", step=step_now):
                    faultinject.maybe_stall("save", step_now)
                    self.ckpt.save(step_now, self._ckpt_state())

            rcfg = self.config.registry
            if rcfg.publish_every and step_now % rcfg.publish_every == 0:
                # Collective on pods (every host joins the snapshot
                # gather); only process 0 holds a publisher. The slow
                # half (serialize + hash + fsync + rename) runs on the
                # publisher's worker thread.
                with self.tracer.span("registry_publish", step=step_now):
                    snap = self._registry_snapshot(step_now)
                    if self._publisher is not None and snap is not None:
                        self._publisher.publish_async(step_now, snap)

            sample_due = (tcfg.sample_every
                          and step_now % tcfg.sample_every == 0)
            eval_due = tcfg.eval_every and step_now % tcfg.eval_every == 0
            if sample_due or eval_due:
                self._maybe_update_host_ema(step_now, force=True)
                # Called on EVERY host: non-reporting hosts join the param
                # replication collective and get None back. Gathered ONCE
                # even when both probes fire (on a pod each gather is a
                # full cross-host all-gather of the param tree).
                with self.watchdog.phase("eval"), \
                        self.tracer.span("eval", step=step_now):
                    probe_params = self._probe_host_params()
                    try:
                        if sample_due:
                            self.dump_samples(step_now, params=probe_params)
                        if eval_due:
                            logged = self.eval_step(step_now,
                                                    params=probe_params)
                            if logged is not None:
                                print(f"{step_now}: "
                                      f"eval psnr={logged['psnr']:.2f} "
                                      f"ssim={logged['ssim']:.4f}")
                    finally:
                        # Free the pinned probe copy promptly — at paper256
                        # it is the difference between the next step fitting
                        # HBM and an OOM (VERDICT r4 item 8).
                        self._release_probe_params(probe_params)

            # Fault-injection SIGTERM drill (env-gated, inert otherwise):
            # fires here so the flag is observed by the agreement check
            # below within the same iteration.
            faultinject.maybe_sigterm(step_now)

            stop = self._stop_agreed()
            if stop:
                print(("preemption signal received" if stop == 1 else
                       "watchdog stall escalation") + f" at step {step_now}"
                      ": checkpointing and exiting")
                break

        if profiling:
            jax.profiler.stop_trace()
        # Release the dead prefetched batch's HBM before post-training use
        # of this Trainer (sampling/eval on large configs wants the room).
        self._device_batch = None
        self._maybe_update_host_ema(self.step, force=True)
        with self.watchdog.phase("checkpoint_save"), \
                self.tracer.span("checkpoint_save", step=self.step):
            self.ckpt.save(self.step, self._ckpt_state(), force=True)
            self.ckpt.wait()
        print("training completed" if not self._stalled else
              f"training STALLED at step {self.step}; state checkpointed "
              "for a supervised restart")
        if last_metrics is not None:
            print(f"final: {last_metrics}")
        timing = self.timer.summary()
        if timing:
            print(f"step timing: {timing}")

    # -- telemetry helpers (obs/) --------------------------------------
    def _publish_corpus_stats(self, step_now: int,
                              host_metrics: dict) -> Optional[dict]:
        """Per-corpus attribution at log time (data/corpus.py mixes).

        Consumes the step's (C,) corpus_loss_sum/corpus_count aux (popped
        so the scalar logger never sees array values) and joins it with
        the MixedDataset's quarantine/decode stats and the MixedLoader's
        draw counts: one telemetry.jsonl row per corpus via the bus, a
        per-corpus loss gauge, and the `loss_<corpus>` extra columns for
        metrics.csv. Returns None on unmixed runs."""
        sums = host_metrics.pop("corpus_loss_sum", None)
        counts = host_metrics.pop("corpus_count", None)
        stats_fn = getattr(self.dataset, "corpus_stats", None)
        if sums is None or stats_fn is None:
            return None
        draws = getattr(self._packed_loader, "corpus_draws", None)
        cols: dict = {}
        reg = self.telemetry.registry
        for i, row in enumerate(stats_fn()):
            name = row["corpus"]
            n = float(counts[i])
            mean_loss = float(sums[i]) / n if n else float("nan")
            cols[f"loss_{name}"] = mean_loss
            if not np.isnan(mean_loss):
                reg.gauge(
                    f"nvs3d_corpus_{name}_loss",
                    f"last logged train loss attributed to corpus "
                    f"{name!r}").set(mean_loss)
            self.telemetry.bus.jsonl_row(dict(
                row, kind="corpus_stats", step=step_now,
                loss=mean_loss, samples=n,
                draws=(int(draws[i]) if draws is not None else None)))
        return cols

    def _health_snapshot(self) -> dict:
        """/healthz body (obs/server.py health provider): progress facts
        an external probe can alarm on — a wedged trainer keeps /metrics
        up while last_step_age_s grows without bound."""
        return {
            "status": "ok",
            "role": "train",
            "step": int(getattr(self, "_step_host", 0)),
            "last_step_age_s": round(time.time() - self._last_step_t, 3),
        }

    def _maybe_cost_analysis(self, device_batch) -> None:
        """One-time FLOPs estimate of the train step for the MFU gauge
        (obs.cost_analysis): jit(...).lower(...).cost_analysis() on the
        unoptimized HLO — a trace, not an XLA compile, so it neither
        touches the jit cache nor adds steady-state dispatches."""
        if not self.config.obs.cost_analysis \
                or self._flops_per_step is not None:
            return
        try:
            with self.tracer.span("cost_analysis"):
                lowered = self.train_step.lower(self.state, device_batch)
                # Piggyback the compile ledger's HLO module hash on the
                # lowering we already paid for.
                self._train_step_hlo = obs.hlo_hash(lowered)
                ca = lowered.cost_analysis()
            flops = (float(ca.get("flops", 0.0))
                     if isinstance(ca, dict) else 0.0)
        except Exception as e:  # bonus context, never fatal
            print(f"note: obs cost analysis unavailable ({e})")
            flops = 0.0
        # 0.0 = tried and unavailable (don't retry every dispatch). The
        # fused multi-step program's FLOPs cover steps_per_dispatch steps.
        self._flops_per_step = flops / max(
            1, self.config.train.steps_per_dispatch)
        if self._flops_per_step:
            self.telemetry.registry.gauge(
                "nvs3d_flops_per_step",
                "XLA cost-model FLOPs per optimizer step").set(
                    self._flops_per_step)

    def _utilization_metrics(self) -> dict:
        """device_mem_gb / mfu for the metrics.csv row (NaN = unknown)."""
        out = {}
        devmon = self.telemetry.devmon
        if devmon is not None and devmon.peak_bytes:
            out["device_mem_gb"] = devmon.peak_bytes / 1e9
        step_s = self.timer.last_s
        if self._flops_per_step and step_s:
            from novel_view_synthesis_3d_tpu.obs import devmon as obs_devmon

            m = obs_devmon.mfu(self._flops_per_step, 1.0 / step_s)
            if m is not None:
                out["mfu"] = m
        return out

    def _update_gauges(self, logged: dict, util: dict,
                       exclude_rates: bool = False) -> None:
        # exclude_rates: this log interval overlapped a continuous-
        # profiler window, so its wall clock includes arm/parse host
        # time — rate gauges (and the rate-derived MFU) keep their last
        # clean sample rather than alerting on profiler overhead.
        if not exclude_rates:
            self._gauge_steps_per_sec.set(logged["steps_per_sec"])
            self._gauge_imgs_per_sec.set(logged["imgs_per_sec_per_chip"])
            if "mfu" in util:
                self._gauge_mfu.set(util["mfu"])
        self._gauge_loss.set(logged["loss"])

    def _registry_snapshot(self, step_now: int):
        """Host numpy copy of the publishable tree: the EMA when the run
        trains one (and registry.publish_ema), else live params.

        Collective on pods — EVERY host must call at the same step (the
        replicate below rides ICI/DCN); non-reporting hosts get None.
        Returns a tree the publisher worker may hold past this step: the
        host-EMA fold REPLACES its tree (never mutates in place), and
        device_get materializes fresh host arrays, so the snapshot can't
        be overwritten under the async publish."""
        use_ema = (self.config.registry.publish_ema
                   and self.config.train.ema_decay > 0)
        if use_ema and self._host_ema is not None:
            self._maybe_update_host_ema(step_now, force=True)
            if jax.process_index() != 0:
                return None
            return self._host_ema
        device_ema = use_ema and self.state.ema_params is not None
        tree = (self.state.ema_params if device_ema else self.state.params)
        if jax.process_count() > 1:
            tree = mesh_lib.replicate(self.mesh, tree)
            jax.block_until_ready(tree)
            if jax.process_index() != 0:
                return None
        host = jax.tree.map(np.asarray, jax.device_get(tree))
        if device_ema and self._zero:
            # The device EMA rides in the packed 1/N row-sharded layout;
            # gather it back to canonical leaves exactly once per publish,
            # off the step loop (tests/test_zero.py asserts the published
            # tree hashes identical to a replicated run's).
            host = unpack_ema(self.config.train, self.mesh,
                              self.state.params, host)
        return host

    def _probe_host_params(self):
        """Sampling params for the in-loop probes, pod-safe.

        Single-process: returns the live (possibly device-sharded) params.
        Multi-process (pods): the naive probe would feed per-host batches
        into a collective program and device_get non-addressable outputs —
        a mid-training crash or hang. Instead EVERY host joins one
        replication collective here (FSDP shards → fully-replicated,
        riding ICI/DCN — so the train loop must call the probe on every
        host at the same step), then process 0 alone fetches the now
        host-addressable copy and samples on its own devices with zero
        collectives inside the sampler; other hosts get None and return
        early — no multi-writer eval.csv, no mismatched collectives."""
        self._maybe_update_host_ema(self.step, force=True)
        pd = self.config.train.probe_dtype or None
        if self._host_ema is not None:
            # Host EMA is already fully replicated host-side (every host
            # folds the same values) — no collective needed; process 0
            # pins it on a local device for the probe samplers. probe_dtype
            # (paper256: bf16) halves the pin — the f32 copy is ~2.6G the
            # 16G chip doesn't have mid-training (VERDICT r4 item 8).
            if jax.process_index() != 0:
                return None
            tree = self._host_ema
            if pd:
                tree = jax.tree.map(lambda a: np.asarray(a, pd), tree)
            return jax.device_put(tree, jax.local_devices()[0])
        params = (self.state.ema_params if self.state.ema_params is not None
                  else self.state.params)
        if self._zero and self.state.ema_params is not None:
            # Packed EMA → canonical, one gather per probe (the sampler
            # can't consume (N, c) rows); then pin on one local device
            # like the pod path below.
            packed = self.state.ema_params
            if jax.process_count() > 1:
                packed = mesh_lib.replicate(self.mesh, packed)
                jax.block_until_ready(packed)
                if jax.process_index() != 0:
                    return None
            host = unpack_ema(self.config.train, self.mesh,
                              self.state.params, jax.device_get(packed))
            if pd:
                host = jax.tree.map(lambda a: np.asarray(a, pd), host)
            return jax.device_put(host, jax.local_devices()[0])
        if jax.process_count() == 1:
            if pd and pd != self.config.model.param_dtype:
                return jax.tree.map(lambda a: jnp.asarray(a, pd), params)
            return params
        replicated = mesh_lib.replicate(self.mesh, params)
        jax.block_until_ready(replicated)
        if jax.process_index() != 0:
            return None
        # Pin the gathered copy on ONE local device: the probe samplers are
        # single-device programs, and handing them host numpy would re-pay
        # the host→device transfer per sampler call (2× when sample and
        # eval probes coincide).
        host = jax.device_get(replicated)
        if pd:
            host = jax.tree.map(lambda a: np.asarray(a, pd), host)
        return jax.device_put(host, jax.local_devices()[0])

    def _release_probe_params(self, probe_params) -> None:
        """Free the probe's pinned device copy (paper256 HBM margin).

        No-op when the probe handed out the live state trees themselves
        (single-process, probe_dtype unset) — only a distinct pinned copy
        is deleted. Guarded PER LEAF, not just per tree (ADVICE r5):
        jnp.asarray(a, dtype) is a no-copy alias when a leaf already has
        the target dtype, so a future mixed-dtype param tree could hand
        out a tree that fails the tree-level 'is' check while some of its
        leaves ARE the live training buffers — deleting those would kill
        the run."""
        if probe_params is None:
            return
        if (probe_params is self.state.params
                or probe_params is self.state.ema_params):
            return
        live = set()
        for tree in (self.state.params, self.state.ema_params):
            if tree is not None:
                live.update(id(leaf) for leaf in jax.tree.leaves(tree))
        for leaf in jax.tree.leaves(probe_params):
            if id(leaf) not in live and hasattr(leaf, "delete"):
                leaf.delete()

    def _held_out_probe_batch(self, folder: str):
        """Fixed probe batch from a held-out SRN tree (train.eval_folder).

        Drawn once, deterministically (seed 0), sized to the smaller of the
        train batch and what the tree holds — small val splits must not
        trip the loader's records>=batch contract."""
        import dataclasses

        ds = make_dataset(dataclasses.replace(
            self.config.data, root_dir=folder))
        if len(ds) == 0:
            raise ValueError(f"train.eval_folder={folder!r} has no records")
        bs = min(dist.local_batch_size(self.config.train.batch_size),
                 len(ds))
        spi = self.config.data.samples_per_instance
        if spi > 1:
            bs = (bs // spi) * spi  # iter_batches needs bs % spi == 0
            if bs == 0:
                raise ValueError(
                    f"train.eval_folder={folder!r} holds {len(ds)} records "
                    f"— fewer than data.samples_per_instance={spi}")
        return next(iter_batches(
            ds, bs, seed=0,
            num_cond=self.config.model.num_cond_frames))

    # ------------------------------------------------------------------
    _UNSET = object()  # "gather the probe params yourself" sentinel

    def eval_step(self, step: int, num: int = 4,
                  params=_UNSET) -> Optional[dict]:
        """In-loop quality probe on a FIXED batch of views.

        Samples the probe batch's target poses and scores PSNR/SSIM against
        the ground-truth targets — same views every call, so the eval.csv
        curve is comparable across steps. The batch comes from
        `train.eval_folder` (held-out views — a true validation curve) when
        set, else from the first TRAINING batch (reconstruction-progress
        signal only; the `eval` CLI does held-out). Uses EMA params
        when available, a respaced `eval_sample_steps` ladder, and logs to
        eval.csv — the reference has no quality signal at all during
        training (SURVEY.md §5.5)."""
        from novel_view_synthesis_3d_tpu.eval.metrics import psnr, ssim

        if params is Trainer._UNSET:
            params = self._probe_host_params()  # collective: all hosts call
        if params is None:
            return None  # non-reporting host of a multi-process run
        if self._eval_batch is None:  # direct eval_step call, eval_every=0
            tcfg = self.config.train
            self._eval_batch = jax.tree.map(
                np.array,
                self._held_out_probe_batch(tcfg.eval_folder)
                if tcfg.eval_folder else self._peek_batch())
        batch = self._eval_batch
        num = min(num, batch["target"].shape[0])
        imgs = self._sample_cond(
            {k: jnp.asarray(batch[k][:num])
             for k in ("x", "R1", "t1", "R2", "t2", "K")},
            seed=step, sample_steps=self.config.train.eval_sample_steps,
            params=params)
        truth = np.asarray(batch["target"][:num])
        logged = {
            "psnr": float(np.mean(psnr(imgs, truth))),
            "ssim": float(np.mean(ssim(imgs, truth))),
        }
        # Standing conditioning-sensitivity probe (VERDICT r3 item 3): the
        # r2/r3 inert-attention failure class trains an unconditional
        # pose-memorizer whose seen-pose PSNR looks healthy — this logs
        # 0.00000 in eval.csv the first time that happens instead of
        # requiring a manual postmortem. One cheap forward pair; absent
        # (not 0.0) while the probe is degenerate (e.g. zero-init output).
        from novel_view_synthesis_3d_tpu.eval.evaluate import (
            cond_sensitivity,
            make_cond_sensitivity_fn,
        )

        if self._cond_sens_fn is None:
            self._cond_sens_fn = make_cond_sensitivity_fn(self._probe_model())
        sens = cond_sensitivity(
            None, params,
            {k: jnp.asarray(batch[k][:num])
             for k in ("x", "R1", "t1", "R2", "t2", "K", "target")},
            key=jax.random.PRNGKey(step), fn=self._cond_sens_fn)
        # NaN (not a missing key) when the probe declines: the eval.csv
        # schema must be stable across a run — a step-0 eval (zero-init
        # output → probe degenerate) would otherwise log a different
        # column set than later evals and trigger the header rotation
        # mid-run, truncating the curve.
        logged["cond_sens"] = float("nan") if sens is None else sens
        self.metrics.log_eval(step, logged)
        return logged

    def _probe_model(self):
        """The model the in-loop probes run: dense (non-sequence-parallel)
        attention — identical math and identical params, but free of the
        batch/'data'-axis divisibility constraint the ring path imposes (a
        4-view probe need not divide the mesh)."""
        if self.config.model.sequence_parallel:
            import dataclasses
            return build_denoiser(dataclasses.replace(
                self.config.model, sequence_parallel=False))
        return self.model

    def _sample_cond(self, cond: dict, seed: int, *, params,
                     sample_steps: Optional[int] = None) -> np.ndarray:
        """Sample novel views for a conditioning dict with current params.

        Samplers are cached per sample_steps — a fresh make_sampler closure
        would recompile its scan on every call.

        `params` comes from `_probe_host_params` (host-local on pods, so
        the sampler never emits a cross-host collective)."""
        key = (self.config.diffusion.sample_timesteps
               if sample_steps is None else sample_steps)
        sampler = self._samplers.get(key)
        if sampler is None:
            dcfg = self.config.diffusion
            sampler = make_sampler(self._probe_model(),
                                   sampling_schedule(dcfg, sample_steps),
                                   dcfg)
            self._samplers[key] = sampler
        imgs = sampler(params, jax.random.PRNGKey(seed), cond)
        return np.asarray(jax.device_get(imgs))

    def dump_samples(self, step: int, num: int = 4,
                     sample_steps: Optional[int] = None,
                     params=_UNSET) -> Optional[str]:
        """Sample novel views for the first records and write a PNG grid.

        Call on every host (the param gather inside is collective); only
        process 0 writes and returns a path."""
        if params is Trainer._UNSET:
            params = self._probe_host_params()
        if params is None:
            return None
        batch = self._peek_batch()
        cond = {k: jnp.asarray(batch[k][:num])
                for k in ("x", "R1", "t1", "R2", "t2", "K")}
        imgs = self._sample_cond(cond, seed=step, sample_steps=sample_steps,
                                 params=params)
        path = os.path.join(self.results_folder, f"samples_{step:07d}.png")
        save_image_grid(imgs, path)
        return path

"""Command-line entry points: train / sample / serve / eval / prep / pack
/ config.

The reference's entry points are two hardwired scripts with zero flags
(`/root/reference/train.py:174-176` — dataset path literal 'cars_train_val';
`/root/reference/sampling.py` — a flat script with an infinite cv2.imshow
loop). Here every capability is a subcommand of

    python -m novel_view_synthesis_3d_tpu <command> [options] [key=value ...]

with config presets (BASELINE.json ladder) + dotted-key overrides, PNG output
instead of GUI display, and checkpoint restore that actually matches what
training saves (the reference's prefixes don't — SURVEY.md §3.5).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional, Sequence

import numpy as np

from novel_view_synthesis_3d_tpu.config import (
    Config, PRESET_NAMES, get_preset)
from novel_view_synthesis_3d_tpu.utils.xla_cache import (
    setup_compilation_cache)


def build_config(args, overrides: Sequence[str]) -> Config:
    """preset → optional JSON file → dotted CLI overrides, later wins."""
    if getattr(args, "config", None):
        with open(args.config) as fh:
            cfg = Config.from_json(fh.read())
        if getattr(args, "preset", None):
            raise SystemExit("--preset and --config are mutually exclusive")
    else:
        cfg = get_preset(args.preset or "tiny64")
    if overrides:
        try:
            cfg = cfg.apply_cli(overrides)
        except KeyError as e:
            raise SystemExit(f"config error: {e.args[0]}") from e
    try:
        return cfg.validate()
    except ValueError as e:
        raise SystemExit(str(e)) from e


def _split_overrides(rest: List[str]) -> List[str]:
    bad = [a for a in rest if "=" not in a]
    if bad:
        raise SystemExit(f"unrecognized arguments: {' '.join(bad)} "
                         "(overrides look like model.ch=64)")
    return rest


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def cmd_train(args, overrides: List[str]) -> int:
    from novel_view_synthesis_3d_tpu.utils import faultinject

    armed = faultinject.armed()
    if armed:
        # Loud, not fatal: chaos drills on real hardware are legitimate,
        # but a production run must never discover injected faults only by
        # dying — and injected anomalies in metrics.csv must be
        # distinguishable from real ones.
        print(f"warning: FAULT INJECTION ARMED ({', '.join(armed)}) — this "
              "run will experience deliberate failures; unset NVS3D_FI_* "
              "for production training")
    cfg = build_config(args, overrides)
    if args.folder:
        cfg = cfg.override(**{"data.root_dir": args.folder})

    if getattr(args, "supervise", False):
        # Supervisor mode: hold no JAX state in THIS process (it must stay
        # responsive while a child wedges); the child runs the same train
        # command minus --supervise and is restarted on crash or stall.
        from novel_view_synthesis_3d_tpu.train.supervisor import (
            supervise, train_child_argv)

        return supervise(
            train_child_argv(args, overrides),
            results_folder=cfg.train.results_folder,
            max_restarts=cfg.train.max_restarts)

    # Device-or-fail, in this process: the platform that answers must be
    # the one asked for, else exit code 3 + a reason line (no child
    # probes the chip first, nothing falls back to the CPU by itself).
    from novel_view_synthesis_3d_tpu.parallel import dist
    from novel_view_synthesis_3d_tpu.utils.watchdog import EXIT_STALL

    dist.require_platform()
    # Persistent compilation cache BEFORE the first jitted dispatch:
    # until this call only bench/tests/tools had it wired, so every CLI
    # train run paid the full XLA compile (utils/xla_cache.py).
    setup_compilation_cache()

    if cfg.train.ladder:
        # Resolution ladder (train/ladder.py): consecutive rung runs over
        # one checkpoint_dir; rung selection and mid-rung fast-forward
        # both derive from the restored step, so plain re-invocation
        # resumes exactly where the last run stopped.
        from novel_view_synthesis_3d_tpu.train.ladder import run_ladder

        last = run_ladder(cfg, use_grain=not args.no_grain)
        return (EXIT_STALL if last is not None and last.stalled else 0)

    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    trainer = Trainer(config=cfg, use_grain=not args.no_grain)
    trainer.train()
    if trainer.stalled:
        # Distinct exit code: the supervisor (or any operator tooling)
        # can tell "completed" from "checkpointed and bailed on a stall".
        return EXIT_STALL
    return 0


# ---------------------------------------------------------------------------
# sample
# ---------------------------------------------------------------------------
def _restore_params(cfg: Config, model, sample_batch: dict, step: Optional[int],
                    reference_ckpt: Optional[str] = None):
    """Latest (or `step`) checkpoint → params (EMA if trained with EMA).

    `reference_ckpt`: path to a reference-format flax msgpack file (e.g.
    the published pretrained model) — imported via compat/reference_ckpt.py
    instead of reading this repo's Orbax checkpoints. Use with
    `--preset reference` so the model carries the quirks the weights were
    trained under.
    """
    import jax

    if reference_ckpt is not None:
        # Before the Orbax/optax imports below — this path needs neither.
        from novel_view_synthesis_3d_tpu.compat.reference_ckpt import (
            load_reference_checkpoint)
        if cfg.model.groupnorm_per_frame or cfg.model.attn_out_proj:
            print("warning: --reference-ckpt weights were trained under the "
                  "reference quirks (shared-frame GroupNorm stats, no attn "
                  "out-projection) but the active config disables them — "
                  "outputs will differ from the reference; use "
                  "--preset reference")
        return load_reference_checkpoint(reference_ckpt), 0

    from novel_view_synthesis_3d_tpu.train.checkpoint import CheckpointManager
    from novel_view_synthesis_3d_tpu.train.state import create_train_state

    template = create_train_state(cfg.train, model, sample_batch)
    if cfg.train.ema_host and cfg.train.ema_decay > 0:
        # Host-EMA checkpoints carry the (host f32) EMA tree in ema_params
        # even though the live TrainState keeps it None — mirror that
        # structure or StandardRestore rejects the tree.
        template = template.replace(ema_params=jax.tree.map(
            lambda p: np.zeros(p.shape, np.float32), template.params))
    ckpt = CheckpointManager(cfg.train.checkpoint_dir)
    if ckpt.latest_step() is None:
        raise FileNotFoundError(
            f"no checkpoint under {cfg.train.checkpoint_dir!r} — train first "
            "(the reference fails the same way: sampling.py:111-112)")
    # Growth-compat restore (train/ladder.py): a pre-num_classes
    # checkpoint loads into the grown template with the category table's
    # zero-init spliced in (asserted neutral).
    from novel_view_synthesis_3d_tpu.train.ladder import restore_with_growth

    state = restore_with_growth(ckpt, template, step=step)
    ckpt.close()
    params = state.ema_params if state.ema_params is not None else state.params
    return jax.device_get(params), int(jax.device_get(state.step))


def cmd_sample(args, overrides: List[str]) -> int:
    from novel_view_synthesis_3d_tpu.parallel import dist

    dist.require_platform()  # device-or-fail: exit 3 + a reason line
    setup_compilation_cache()  # warm repeat samples skip the XLA compile

    import jax
    import jax.numpy as jnp

    from novel_view_synthesis_3d_tpu.data.srn import SRNDataset
    from novel_view_synthesis_3d_tpu.diffusion.schedules import sampling_schedule
    from novel_view_synthesis_3d_tpu.models import build_denoiser
    from novel_view_synthesis_3d_tpu.sample.ddpm import (
        autoregressive_generate, make_sampler)
    from novel_view_synthesis_3d_tpu.train.trainer import _sample_model_batch
    from novel_view_synthesis_3d_tpu.utils.geometry import (
        interpolate_poses, orbit_poses)
    from novel_view_synthesis_3d_tpu.utils.images import (
        save_animation, save_image, save_image_grid)

    if args.stochastic and args.denoise_gif:
        # Fail fast — before dataset IO and checkpoint restore.
        raise SystemExit("--denoise-gif is not supported with --stochastic")
    if args.trajectory and (args.stochastic or args.denoise_gif):
        raise SystemExit(
            "--trajectory is the serving-grade device-resident orbit "
            "path (stepper ring + frame bank); it does not combine with "
            "--stochastic (the offline autoregressive sampler) or "
            "--denoise-gif")
    if args.pool_views < 1:
        # Unconditional: with --stochastic, 0/negative would silently
        # behave as 1 (the seeding branch only fires for pool_views > 1).
        raise SystemExit("--pool-views must be >= 1")
    if args.pool_views != 1 and not args.stochastic:
        raise SystemExit("--pool-views requires --stochastic (it seeds the "
                         "stochastic-conditioning pool)")
    cfg = build_config(args, overrides)
    dcfg = cfg.diffusion
    if args.trajectory:
        args.num_views = args.trajectory
    ds = SRNDataset(args.folder or cfg.data.root_dir,
                    img_sidelength=cfg.data.img_sidelength)
    inst = ds.instances[args.instance % ds.num_instances]
    x, pose1 = inst.view(args.cond_view % len(inst))

    # Target poses: dataset ground-truth poses, a synthetic orbit, or a
    # smooth slerp path through the instance's dataset poses.
    if args.poses == "dataset":
        idcs = [v for v in range(len(inst))
                if v != args.cond_view % len(inst)][:args.num_views]
        poses2 = np.stack([inst.view(v)[1] for v in idcs])
    elif args.poses == "interp":
        # Poses only — inst.view() would decode every RGB just to drop it.
        from novel_view_synthesis_3d_tpu.data.srn import load_pose
        keyframes = np.stack([load_pose(p) for p in inst.pose_paths])
        poses2 = interpolate_poses(keyframes, args.num_views)
    else:
        radius = float(np.linalg.norm(pose1[:3, 3]))
        poses2 = orbit_poses(args.num_views, radius=radius,
                             elevation=args.elevation)

    model = build_denoiser(cfg.model)
    first_view = {
        "x": jnp.asarray(x)[None],
        "R1": jnp.asarray(pose1[:3, :3])[None],
        "t1": jnp.asarray(pose1[:3, 3])[None],
        "K": jnp.asarray(inst.K)[None],
    }
    sample_batch = _sample_model_batch({
        "x": x[None], "target": x[None],
        "R1": pose1[None, :3, :3], "t1": pose1[None, :3, 3],
        "R2": poses2[0][None, :3, :3], "t2": poses2[0][None, :3, 3],
        "K": inst.K[None],
    })
    params, step = _restore_params(cfg, model, sample_batch, args.step,
                                   reference_ckpt=args.reference_ckpt)
    print(f"restored checkpoint at step {step}")

    schedule = sampling_schedule(dcfg, args.sample_steps)
    key = jax.random.PRNGKey(args.seed)

    if args.trajectory:
        # Serving-grade orbit: ONE TrajectoryRequest through the stepper
        # ring — the frame bank stays device-resident, each denoise step
        # conditions stochastically on it, frames stream back as they
        # finish (docs/DESIGN.md "Trajectory serving & stochastic
        # conditioning"). The offline twin of `nvs3d serve --trajectory`.
        import dataclasses

        from novel_view_synthesis_3d_tpu.sample.service import (
            SamplingService)
        from novel_view_synthesis_3d_tpu.utils.images import (
            save_image_strip)

        scfg = cfg.serve
        if scfg.scheduler != "step" or scfg.k_max < 1:
            scfg = dataclasses.replace(scfg, scheduler="step",
                                       k_max=max(8, scfg.k_max))
            print(f"note: --trajectory enables serve.scheduler='step', "
                  f"serve.k_max={scfg.k_max} (set serve.k_max to size "
                  "the conditioning window)")
        os.makedirs(args.out, exist_ok=True)
        service = SamplingService(model, params, dcfg, scfg,
                                  results_folder=args.out,
                                  model_version=f"ckpt:{step}")
        try:
            ticket = service.submit_trajectory(
                {"x": x, "R1": pose1[:3, :3], "t1": pose1[:3, 3],
                 "K": inst.K},
                poses=poses2, seed=args.seed,
                sample_steps=args.sample_steps)
            frames = []
            for i, img in ticket.frames(timeout=600):
                frames.append(img)
                print(json.dumps({"frame_index": i,
                                  "model_version": ticket.model_version}))
            imgs = np.stack(frames)
        finally:
            service.stop()
        save_image_strip(imgs, os.path.join(args.out, "orbit_strip.png"))
    elif args.stochastic:
        # Autoregressive 3DiM sampling: each generated view joins the
        # conditioning pool for the next (sample/ddpm.py). --pool-views
        # seeds the pool with that many REAL dataset views (cond_view
        # first, then views that are not sampling targets).
        if args.pool_views > 1:
            cand = [args.cond_view % len(inst)]
            targets = set(idcs) if args.poses == "dataset" else set()
            cand += [v for v in range(len(inst))
                     if v not in cand and v not in targets]
            if len(cand) < args.pool_views:
                print(f"note: only {len(cand)} non-target views available "
                      f"for --pool-views {args.pool_views}")
            pool_views = [inst.view(v) for v in cand[:args.pool_views]]
            first_view = {
                "x": jnp.asarray(np.stack([x for x, _ in pool_views]))[None],
                "R1": jnp.asarray(np.stack(
                    [p[:3, :3] for _, p in pool_views]))[None],
                "t1": jnp.asarray(np.stack(
                    [p[:3, 3] for _, p in pool_views]))[None],
                "K": first_view["K"],
            }
        target_poses = {
            "R2": jnp.asarray(poses2[None, :, :3, :3]),
            "t2": jnp.asarray(poses2[None, :, :3, 3]),
        }
        imgs = autoregressive_generate(
            model, schedule, dcfg, params, key, first_view, target_poses)
        imgs = np.asarray(jax.device_get(imgs))[0]  # (N, H, W, 3)
    else:
        # One batched reverse process: the conditioning view broadcasts over
        # all N target poses (same pattern as eval/evaluate.py).
        traj_every = 0
        if args.denoise_gif:
            # Aim for ~32 frames of the reverse process. The sampler accepts
            # any stride (remainder steps are flat-scanned and the final
            # state appended), so a near-uniform stride works for prime step
            # counts too — no divisor hunt, never a single-frame "animation".
            T = schedule.num_timesteps
            traj_every = max(1, round(T / 32))
        sampler = make_sampler(model, schedule, dcfg,
                               trajectory_every=traj_every,
                               trajectory_views=1)
        N = len(poses2)
        cond = {k: jnp.broadcast_to(v, (N,) + v.shape[1:])
                for k, v in first_view.items()}
        cond["R2"] = jnp.asarray(poses2[:, :3, :3])
        cond["t2"] = jnp.asarray(poses2[:, :3, 3])
        out = sampler(params, key, cond)
        if traj_every:
            out, traj = out  # traj is (frames, 1, H, W, 3): view 0 only
            save_animation(
                np.asarray(jax.device_get(traj))[:, 0],
                os.path.join(args.out, "denoise.gif"), fps=args.gif_fps)
        imgs = np.asarray(jax.device_get(out))

    if not np.isfinite(imgs).all():
        # PNG conversion clips, so NaN pixels would land as valid-looking
        # images: refuse instead.
        raise SystemExit("error: the sampler produced non-finite pixels "
                         f"({int((~np.isfinite(imgs)).sum())} of "
                         f"{imgs.size}); nothing written")
    os.makedirs(args.out, exist_ok=True)
    for i, img in enumerate(imgs):
        save_image(img, os.path.join(args.out, f"view_{i:03d}.png"))
    save_image_grid(imgs, os.path.join(args.out, "grid.png"))
    save_image(x, os.path.join(args.out, "cond.png"))
    if args.gif:
        save_animation(imgs, os.path.join(args.out, "orbit.gif"),
                       fps=args.gif_fps)
    print(f"wrote {len(imgs)} views to {args.out} "
          f"(pixel range [{imgs.min():.4f}, {imgs.max():.4f}])")
    return 0


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
# Canonical implementation lives in sample/client.py so the CLI client
# and the fleet router (serve/router.py) share one retry/backoff/jitter
# loop; re-exported here because tests and external callers import it
# from cli.
from novel_view_synthesis_3d_tpu.sample.client import (  # noqa: F401
    submit_with_retry)


def cmd_serve(args, overrides: List[str]) -> int:
    """Micro-batched sampling service (sample/service.py).

    Requests come from --requests (a JSON-lines file; each line selects a
    conditioning view and a target pose by dataset index and may override
    seed / sample_steps / guidance_weight / deadline_ms) or, with no
    file, a --num-requests demo sweep over the instance's poses. Every
    request's image lands in --out; a JSON summary line (requests/sec,
    queue-wait and device-time percentiles, program-cache counters)
    closes the run — the serving twin of eval's result line.
    """
    from novel_view_synthesis_3d_tpu.parallel import dist

    dist.require_platform()  # device-or-fail: exit 3 + a reason line
    setup_compilation_cache()  # the warm-traffic contract starts on disk

    import jax

    from novel_view_synthesis_3d_tpu.data.srn import SRNDataset
    from novel_view_synthesis_3d_tpu.models import build_denoiser
    from novel_view_synthesis_3d_tpu.sample.service import (
        Rejected, SamplingService)
    from novel_view_synthesis_3d_tpu.train.trainer import _sample_model_batch
    from novel_view_synthesis_3d_tpu.utils.images import save_image

    cfg = build_config(args, overrides)
    ds = SRNDataset(args.folder or cfg.data.root_dir,
                    img_sidelength=cfg.data.img_sidelength)
    model = build_denoiser(cfg.model)
    inst0 = ds.instances[0]
    x0, pose0 = inst0.view(0)
    sample_batch = _sample_model_batch({
        "x": x0[None], "target": x0[None],
        "R1": pose0[None, :3, :3], "t1": pose0[None, :3, 3],
        "R2": pose0[None, :3, :3], "t2": pose0[None, :3, 3],
        "K": inst0.K[None],
    })
    # int8-requires-registry-staging: a quantized deployment serves
    # gate-probed registry versions only (the PSNR gate scores candidates
    # AT the serving precision, so quantization loss is part of what the
    # gate_margin_db admitted) — a raw checkpoint has no such lineage.
    if cfg.serve.precision == "int8" and not args.registry:
        raise SystemExit(
            "serve.precision='int8' requires --registry: quantized "
            "serving only deploys versions whose promotion gate probed "
            "them at int8 (registry/gate.py) — serve a checkpoint at "
            "'float32'/'bfloat16', or publish + promote it first "
            "(nvs3d registry publish/promote)")
    # Weights: either a checkpoint (the pre-registry path) or a registry
    # channel subscription — the service then HOT-RELOADS whenever the
    # channel pointer moves (registry/watcher.py), with zero downtime.
    store = watcher = None
    if args.registry:
        from novel_view_synthesis_3d_tpu.registry import RegistryStore

        store = RegistryStore(args.registry)
        channel = args.channel or cfg.registry.channel
        vid = store.read_channel(channel)
        if vid is None:
            raise SystemExit(
                f"registry {args.registry!r} channel {channel!r} points at "
                "no version — publish and promote first (nvs3d registry "
                "publish/promote)")
        manifest = store.verify(vid)
        params, step = store.load_params(vid, verify=False), manifest.step
        model_version = vid
        print(f"serving registry version {vid} (step {step}, channel "
              f"{channel})")
    else:
        params, step = _restore_params(cfg, model, sample_batch, args.step,
                                       reference_ckpt=args.reference_ckpt)
        model_version = f"ckpt:{step}"
        print(f"restored checkpoint at step {step}")

    # Multi-chip: one coalesced batch serves data-parallel through the
    # mesh (buckets that divide the data axis shard via shard_batch).
    mesh = None
    if len(jax.devices()) > 1:
        from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.fit_local_mesh(cfg.mesh)

    def build_request(spec: dict) -> tuple:
        """(cond, poses): poses is None for single-frame specs, an
        (N, 4, 4) stack for trajectory specs (`poses` = explicit pose
        matrices, `orbit` = N synthetic orbit poses at the conditioning
        camera's radius)."""
        from novel_view_synthesis_3d_tpu.utils.geometry import orbit_poses

        inst = ds.instances[int(spec.get("instance", 0)) % ds.num_instances]
        cx, cpose = inst.view(int(spec.get("cond_view", 0)) % len(inst))
        poses = None
        if spec.get("poses") is not None:
            poses = np.asarray(spec["poses"], np.float32)
        elif spec.get("orbit"):
            poses = orbit_poses(
                int(spec["orbit"]),
                radius=float(np.linalg.norm(cpose[:3, 3])),
                elevation=float(spec.get("elevation", 0.3)))
        if poses is not None:
            return {"x": cx, "R1": cpose[:3, :3], "t1": cpose[:3, 3],
                    "K": inst.K}, poses
        _, tpose = inst.view(int(spec.get("target_view", 1)) % len(inst))
        return {
            "x": cx, "R1": cpose[:3, :3], "t1": cpose[:3, 3],
            "R2": tpose[:3, :3], "t2": tpose[:3, 3], "K": inst.K,
        }, None

    if args.requests:
        with open(args.requests) as fh:
            specs = [json.loads(ln) for ln in fh if ln.strip()]
    elif args.trajectory:
        # Trajectory demo sweep: each request is an N-frame orbit; the
        # frames stream back per request and land as an orbit PNG strip.
        specs = [{"instance": args.instance + i,
                  "cond_view": args.cond_view, "orbit": args.trajectory,
                  "seed": args.seed + i}
                 for i in range(args.num_requests)]
    else:
        specs = [{"instance": args.instance, "cond_view": args.cond_view,
                  "target_view": i + 1, "seed": args.seed + i}
                 for i in range(args.num_requests)]
    if not specs:
        raise SystemExit("no requests (empty --requests file)")
    wants_traj = any(s.get("poses") is not None or s.get("orbit")
                     for s in specs)
    if wants_traj and (cfg.serve.k_max < 1
                       or cfg.serve.scheduler != "step"):
        import dataclasses

        cfg = dataclasses.replace(cfg, serve=dataclasses.replace(
            cfg.serve, scheduler="step",
            k_max=max(8, cfg.serve.k_max)))
        print(f"note: trajectory requests enable serve.scheduler='step',"
              f" serve.k_max={cfg.serve.k_max} (set serve.k_max to size "
              "the frame bank)")

    os.makedirs(args.out, exist_ok=True)
    # Unified telemetry (obs/): the service's pipeline spans (queue_wait →
    # batch_form → compile/device → respond) land in trace.json next to
    # the request PNGs, and the /metrics endpoint — when obs.metrics_port
    # is set — exposes the same registry the spans' histograms feed.
    from novel_view_synthesis_3d_tpu import obs

    telemetry = obs.RunTelemetry.create(cfg.obs, args.out)
    profiler = (obs.make_profiler(cfg.obs.profile, args.out, cfg.model,
                                  telemetry.bus, telemetry.registry,
                                  unit="dispatch")
                if cfg.obs.enabled else None)
    service = SamplingService(model, params, cfg.diffusion, cfg.serve,
                              mesh=mesh, results_folder=args.out,
                              tracer=telemetry.tracer,
                              flight=telemetry.flight,
                              profiler=profiler,
                              model_version=model_version)
    if telemetry.server is not None:
        # /healthz progress facts: last_dispatch_age_s + the live
        # model_version, so a probe (or the registry rollback runbook)
        # reads the serving plane's heartbeat without scraping.
        telemetry.server.set_health_provider(service.health_snapshot)
    if store is not None:
        from novel_view_synthesis_3d_tpu.registry import RegistryWatcher

        bus = telemetry.bus
        watcher = RegistryWatcher(
            service, store, args.channel or cfg.registry.channel,
            poll_s=cfg.registry.poll_s,
            event_cb=lambda s, kind, detail, version: bus.event(
                s, kind, detail, model_version=version,
                echo="[registry]"))
    # Rolling-restart contract: SIGTERM/SIGINT flips the service into
    # drain mode — new admissions get a retryable reject (clients fail
    # over to a peer), in-flight and queued work finishes, telemetry
    # flushes, and the process exits 0 so the orchestrator's restart
    # counts as clean.
    import signal
    import threading

    drain_requested = threading.Event()

    def _on_term(signum, frame):
        drain_requested.set()
        service.begin_drain(reason=signal.Signals(signum).name)

    prev_handlers = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            prev_handlers[sig] = signal.signal(sig, _on_term)
        except ValueError:
            pass  # non-main thread (embedded use): no signal hooks
    try:
        from novel_view_synthesis_3d_tpu.utils.images import (
            save_image_strip)

        tickets = []
        for i, spec in enumerate(specs):
            if drain_requested.is_set():
                print(f"draining: requests {i}..{len(specs) - 1} not "
                      "submitted")
                break
            try:
                cond, poses = build_request(spec)
                if poses is not None:
                    def _submit(cond=cond, poses=poses, spec=spec, i=i):
                        return service.submit_trajectory(
                            cond, poses=poses,
                            seed=int(spec.get("seed", args.seed + i)),
                            sample_steps=spec.get("sample_steps",
                                                  args.sample_steps),
                            guidance_weight=spec.get("guidance_weight"),
                            deadline_ms=spec.get("deadline_ms"),
                            k_max=spec.get("k_max"),
                            trace_id=spec.get("trace_id"))
                else:
                    def _submit(cond=cond, spec=spec, i=i):
                        return service.submit(
                            cond,
                            seed=int(spec.get("seed", args.seed + i)),
                            sample_steps=spec.get("sample_steps",
                                                  args.sample_steps),
                            guidance_weight=spec.get("guidance_weight"),
                            deadline_ms=spec.get("deadline_ms"),
                            trace_id=spec.get("trace_id"))
                # Brownout/queue-full rejects are retryable with a
                # server-suggested retry_after_s; honor it before giving
                # up on the request.
                tickets.append((i, submit_with_retry(_submit)))
            except Rejected as e:
                print(f"request {i}: rejected ({e})")
        served = 0
        orbits = 0
        for i, ticket in tickets:
            if hasattr(ticket, "frames"):  # TrajectoryTicket: stream
                frames = []
                try:
                    # Per-frame streaming: each response line carries
                    # frame_index + model_version the moment the frame
                    # finishes — clients render while the rest of the
                    # orbit is still on device.
                    for j, img in ticket.frames(timeout=args.timeout):
                        save_image(img, os.path.join(
                            args.out, f"request_{i:04d}_frame_{j:03d}.png"))
                        print(json.dumps({
                            "request": i, "frame_index": j,
                            "model_version": ticket.model_version}))
                        frames.append(img)
                        served += 1
                except Exception as e:
                    print(f"request {i}: failed after {len(frames)} "
                          f"frame(s) ({e})")
                if frames:
                    save_image_strip(np.stack(frames), os.path.join(
                        args.out, f"request_{i:04d}_orbit.png"))
                if len(frames) == ticket.num_frames:
                    orbits += 1
                continue
            try:
                # Bounded wait: a dispatch wedged on the device must
                # surface as a per-request TimeoutError, not an eternal
                # hang (the serving-side analog of the run watchdog).
                img = ticket.result(timeout=args.timeout)
            except Exception as e:
                print(f"request {i}: failed ({e})")
                continue
            save_image(img, os.path.join(args.out, f"request_{i:04d}.png"))
            served += 1
    finally:
        if watcher is not None:
            watcher.stop()
        if drain_requested.is_set():
            # Drain already rejected new admissions; wait (bounded by
            # serve.drain_timeout_s) for the in-flight tail, then stop.
            clean = service.drain(reason="signal")
            print(f"drain {'complete' if clean else 'TIMED OUT'}; "
                  "exiting 0")
        else:
            service.stop()
        for sig, handler in prev_handlers.items():
            signal.signal(sig, handler)
        telemetry.finalize()  # trace.json + gauges flushed into --out
    summary = dict(service.summary(), served=served,
                   submitted=len(specs), checkpoint_step=step)
    if wants_traj:
        summary["orbits_completed"] = orbits
    print(json.dumps(summary))
    return 0


# ---------------------------------------------------------------------------
# eval
# ---------------------------------------------------------------------------
def cmd_eval(args, overrides: List[str]) -> int:
    from novel_view_synthesis_3d_tpu.parallel import dist

    dist.require_platform()  # device-or-fail: exit 3 + a reason line
    setup_compilation_cache()  # repeat evals skip the XLA compile

    import jax

    from novel_view_synthesis_3d_tpu.data.srn import SRNDataset
    from novel_view_synthesis_3d_tpu.eval.evaluate import evaluate_dataset
    from novel_view_synthesis_3d_tpu.models import build_denoiser
    from novel_view_synthesis_3d_tpu.train.trainer import _sample_model_batch

    cfg = build_config(args, overrides)
    ds = SRNDataset(args.folder or cfg.data.root_dir,
                    img_sidelength=cfg.data.img_sidelength)
    model = build_denoiser(cfg.model)

    rec = ds.pair(0, np.random.default_rng(0))
    sample_batch = _sample_model_batch(
        {k: v[None] for k, v in rec.items()})
    params, step = _restore_params(cfg, model, sample_batch, args.step,
                                   reference_ckpt=args.reference_ckpt)
    print(f"restored checkpoint at step {step}")

    # Multi-chip: shard the sampling batch over the mesh 'data' axis; the
    # data axis is refit to the LOCAL device count so a training config's
    # mesh (e.g. mesh.data=32) doesn't crash an eval on a smaller host.
    mesh = None
    batch_size = args.batch_size
    if len(jax.devices()) > 1:
        from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.fit_local_mesh(cfg.mesh)
        if mesh is None:
            print(f"note: {len(jax.devices())} devices not divisible by "
                  f"mesh.model×mesh.seq claims; evaluating on the default "
                  "device")
        else:
            shards = mesh_lib.num_data_shards(mesh)
            batch_size = ((batch_size + shards - 1) // shards) * shards
            if batch_size != args.batch_size:
                print(f"note: rounding eval batch {args.batch_size} -> "
                      f"{batch_size} (multiple of data axis {shards})")

    fid_feature_fn = None
    if args.inception_npz:
        from novel_view_synthesis_3d_tpu.eval.inception import (
            load_inception_features)
        fid_feature_fn = load_inception_features(args.inception_npz)

    result = evaluate_dataset(
        cfg, model, params, ds,
        key=jax.random.PRNGKey(args.seed),
        num_instances=args.num_instances,
        views_per_instance=args.views_per_instance,
        cond_view=args.cond_view,
        sample_steps=args.sample_steps,
        batch_size=batch_size,
        compute_fid=args.fid or fid_feature_fn is not None,
        fid_feature_fn=fid_feature_fn,
        protocol=args.protocol,
        mesh=mesh,
        dump_comparisons=args.dump_comparisons,
    )
    print(json.dumps(dict(result.to_dict(), checkpoint_step=step)))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            # The eval protocol parameters ride along so downstream
            # analysis (tools/pose_generalization.py) can reconstruct the
            # exact (instance, view) pairing of per_view_psnr instead of
            # guessing it from counts.
            json.dump(dict(result.to_dict(), checkpoint_step=step,
                           cond_view=args.cond_view,
                           num_instances=args.num_instances,
                           views_per_instance=args.views_per_instance,
                           per_view_psnr=result.per_view_psnr.tolist(),
                           per_view_ssim=result.per_view_ssim.tolist()), fh)
    return 0


# ---------------------------------------------------------------------------
# prep / config
# ---------------------------------------------------------------------------
def cmd_prep(args, overrides: List[str]) -> int:
    del overrides
    from novel_view_synthesis_3d_tpu.data import prep

    if args.prep_command == "split-object":
        n_train, n_val = prep.train_val_split(
            args.object_dir, args.train_dir, args.val_dir,
            symlink=args.symlink, invert=args.invert)
        print(f"{n_train} train / {n_val} val views")
    elif args.prep_command == "shapenet":
        placed = prep.shapenet_train_test_split(
            args.shapenet_path, args.synset_id, args.name, args.csv_path,
            symlink=args.symlink)
        print(json.dumps({k: len(v) for k, v in placed.items()}))
    else:
        raise SystemExit(f"unknown prep command {args.prep_command!r}")
    return 0


def cmd_pack(args, overrides: List[str]) -> int:
    """Pack an SRN tree into sharded records, or verify a packed corpus.

    Two modes:
      nvs3d pack SRN_DIR --out PACKED_DIR [--shard-mb N] [--verify]
        walks the SRN layout once, writes shard-*.nvsrec + index.json
        (sharded by scene at a target shard size), optionally verifying
        the result before reporting;
      nvs3d pack PACKED_DIR --verify
        integrity sweep over an existing corpus: re-hash every shard,
        cross-check footers against index.json, unpack every record,
        decode a probe view per scene. rc=1 if anything fails — the
        pre-flight for pointing data.backend='packed' at a corpus.
    """
    del overrides
    from novel_view_synthesis_3d_tpu.data import records

    def run_verify(root: str) -> int:
        problems = records.verify_packed(
            root, decode="all" if args.deep else "first")
        print(json.dumps({
            "verified": not problems, "dir": root,
            "problems": problems[:50],
            "num_problems": len(problems)}))
        if problems:
            print(f"verification FAILED: {len(problems)} problem(s)",
                  file=sys.stderr)
            return 1
        return 0

    if os.path.exists(os.path.join(args.src, records.INDEX_NAME)) \
            and not args.out:
        if not args.verify:
            raise SystemExit(
                f"{args.src!r} is already a packed corpus; pass --verify "
                "to check it, or --out DIR to re-pack somewhere else")
        return run_verify(args.src)
    if not args.out:
        raise SystemExit("--out DIR is required when packing")
    index = records.pack_srn(
        args.src, args.out, shard_mb=args.shard_mb,
        max_num_instances=args.max_instances,
        name=args.name, classes=args.classes,
        progress=((lambda name, views, shard: print(
            f"  packed {name} ({views} views) -> shard {shard}"))
            if args.progress else None))
    print(json.dumps({
        "packed": args.out,
        "shards": len(index["shards"]),
        "instances": index["num_instances"],
        "views": index["num_views"],
        "bytes": sum(s["bytes"] for s in index["shards"]),
        "meta": index.get("meta"),
    }))
    if args.verify:
        return run_verify(args.out)
    return 0


def cmd_config(args, overrides: List[str]) -> int:
    print(build_config(args, overrides).to_json())
    return 0


# ---------------------------------------------------------------------------
# export (checkpoint → reference format)
# ---------------------------------------------------------------------------
def cmd_export(args, overrides: List[str]) -> int:
    """Write a trained checkpoint as a reference-format flax msgpack file.

    The inverse of --reference-ckpt: a file the reference codebase's
    restore path (sampling.py:104-114) can consume — bare param dict,
    3-D (1,3,3) conv kernels, reference module naming. EMA params are
    exported when present (they are what you sample with).

    Default-step selection rides the checkpoint integrity walk-back
    (train/checkpoint.restore with step=None): after a torn save the
    export picks the newest VERIFIED checkpoint, never blindly the
    latest step. With --registry the converted snapshot is also
    published as a registry version (fmt='reference' in the manifest —
    inspectable and gc-able, but never servable by mistake).
    """
    import jax
    import numpy as np

    from flax import serialization

    from novel_view_synthesis_3d_tpu.compat.reference_ckpt import (
        export_reference_params)
    from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
    from novel_view_synthesis_3d_tpu.models import build_denoiser
    from novel_view_synthesis_3d_tpu.train.trainer import _sample_model_batch

    cfg = build_config(args, overrides)
    if cfg.model.num_cond_frames != 1:
        raise SystemExit(
            "export: the reference format is strictly two-frame (k=1); "
            f"model.num_cond_frames={cfg.model.num_cond_frames}")
    model = build_denoiser(cfg.model)
    sample_batch = _sample_model_batch(make_example_batch(
        batch_size=1, sidelength=cfg.data.img_sidelength))
    params, step = _restore_params(cfg, model, sample_batch, args.step)
    ref_tree = export_reference_params(jax.tree.map(np.asarray, params))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "wb") as fh:
        fh.write(serialization.msgpack_serialize(ref_tree))
    n = sum(np.asarray(leaf).size
            for leaf in jax.tree.leaves(ref_tree))
    print(f"exported step-{step} params ({n:,} values) to {args.out} "
          "(reference flax msgpack layout)")
    if args.registry:
        from novel_view_synthesis_3d_tpu.registry import RegistryStore
        from novel_view_synthesis_3d_tpu.registry.manifest import (
            config_digest)

        with open(args.out, "rb") as fh:
            payload = fh.read()
        m = RegistryStore(args.registry).publish_bytes(
            payload, step=step, ema=cfg.train.ema_decay > 0,
            fmt="reference", config_digest=config_digest(cfg),
            notes=f"nvs3d export of {args.out}",
            channel=args.channel)
        print(f"published as registry version {m.version} "
              f"(fmt=reference, channel {args.channel})")
    return 0


# ---------------------------------------------------------------------------
# distill (progressive distillation: teacher -> few-step student)
# ---------------------------------------------------------------------------
def cmd_distill(args, overrides: List[str]) -> int:
    """Progressive distillation rounds against a registry teacher.

    Reads the teacher from --teacher-version (or the --teacher-channel
    pointer), runs config.distill step-halving rounds
    (train/distill.run_distill), publishes each student generation as a
    registry version on --channel, and — with --promote-channel — runs
    the existing fixed-seed PSNR gate (registry/gate.py) on the FINAL
    student and advances that channel on a pass. The gate probes at the
    student's final step count: the comparison is "serving at N steps
    with the candidate vs the incumbent", the few-step serving regime
    the distillation exists for. Prints one JSON line per round and a
    closing summary line.
    """
    from novel_view_synthesis_3d_tpu.parallel import dist

    dist.require_platform()  # device-or-fail: exit 3 + a reason line
    setup_compilation_cache()

    import jax

    from novel_view_synthesis_3d_tpu.models import build_denoiser
    from novel_view_synthesis_3d_tpu.registry import (
        RegistryError, RegistryStore, promote)
    from novel_view_synthesis_3d_tpu.train.distill import run_distill

    cfg = build_config(args, overrides)
    store = RegistryStore(args.registry)
    vid = args.teacher_version or store.read_channel(args.teacher_channel)
    if vid is None:
        raise SystemExit(
            f"registry {args.registry!r} channel "
            f"{args.teacher_channel!r} points at no version — publish "
            "and promote a teacher first (nvs3d registry publish)")
    manifest = store.verify(vid)
    teacher_params = store.load_params(vid, verify=False)
    print(f"teacher: {vid} (step {manifest.step}, channel "
          f"{args.teacher_channel})")
    model = build_denoiser(cfg.model)
    event_cb = _registry_event_cb(args.registry)

    data_iter = None
    root = args.folder or cfg.data.root_dir
    if root and os.path.isdir(root):
        try:
            import dataclasses

            from novel_view_synthesis_3d_tpu.data.pipeline import (
                iter_batches, make_dataset)

            ds = make_dataset(dataclasses.replace(cfg.data, root_dir=root))
            if len(ds) > 0:
                data_iter = iter_batches(ds, cfg.distill.batch_size,
                                         seed=cfg.distill.seed)
                print(f"distilling on {root} ({len(ds)} records)")
        except Exception as e:
            print(f"note: falling back to synthetic distill batches ({e})")
    try:
        results = run_distill(
            cfg, model, teacher_params, data_iter=data_iter, store=store,
            publish_channel=args.channel, base_step=manifest.step,
            event_cb=event_cb)
    except (ValueError, FloatingPointError) as e:
        raise SystemExit(f"distill error: {e}")
    for r in results:
        print(json.dumps(dict(r.to_dict(), teacher=vid)))
    final = results[-1]
    if args.promote_channel:
        # The gate probes AT the student's serving step count; with
        # registry.gate_trajectory_frames set, the multi-view
        # consistency gate ALSO runs — a few-step student whose orbit
        # drifts is refused even when its single frames gate clean.
        try:
            passed, gate = _run_gates(
                cfg, model, store, final.version, args.promote_channel,
                _gate_probe_batch(cfg, args.folder),
                psnr_sample_steps=final.student_steps,
                event_cb=event_cb)
        except RegistryError as e:
            raise SystemExit(f"gate error: {e}")
        if not passed:
            print(f"promotion REFUSED: {gate.reason} (channel "
                  f"{args.promote_channel} untouched)")
            return 1
        promote(store, final.version, channel=args.promote_channel,
                gate=gate, event_cb=event_cb)
        print(f"promoted {final.version} -> channel "
              f"{args.promote_channel}")
    print(f"distilled {cfg.distill.start_steps} -> "
          f"{final.student_steps} steps over {len(results)} round(s); "
          f"serve with sample_steps={final.student_steps}")
    return 0


# ---------------------------------------------------------------------------
# registry (model lifecycle: publish / promote / rollback / gc)
# ---------------------------------------------------------------------------
def _registry_event_cb(registry_dir: str):
    """EventBus-routed audit log in the registry root: every lifecycle
    decision (publish, gate verdicts, promote, rollback, gc) is a row in
    <dir>/events.csv + telemetry.jsonl — same single write path as the
    trainer and the service."""
    from novel_view_synthesis_3d_tpu import obs

    bus = obs.EventBus(registry_dir, jsonl=True)
    return lambda step, kind, detail, version="": bus.event(
        step, kind, detail, model_version=version, echo="[registry]")


def _gate_probe_batch(cfg, folder: Optional[str]) -> dict:
    """Fixed-seed conditioning batch for the promotion gate: real SRN
    views when a dataset is reachable (the honest probe), else the
    synthetic harness (still a valid candidate-vs-incumbent comparator —
    both versions see identical conditioning and noise)."""
    rcfg = cfg.registry
    root = folder or cfg.data.root_dir
    if root and os.path.isdir(root):
        try:
            from novel_view_synthesis_3d_tpu.data.pipeline import (
                iter_batches, make_dataset)

            import dataclasses

            ds = make_dataset(dataclasses.replace(cfg.data, root_dir=root))
            if len(ds) > 0:
                bs = min(rcfg.gate_batch, len(ds))
                return next(iter_batches(ds, bs, seed=rcfg.gate_seed,
                                         num_cond=cfg.model.num_cond_frames))
        except Exception as e:
            print(f"note: gate falling back to synthetic probe data ({e})")
    from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch

    return make_example_batch(batch_size=rcfg.gate_batch,
                              sidelength=cfg.data.img_sidelength,
                              seed=rcfg.gate_seed)


def _gate_matrix_cells(cfg, model, folder, *, psnr_sample_steps: int):
    """Probe cells for the (corpus × rung-resolution) gate matrix.

    One PSNR probe per corpus of `data.mix` (or the single training
    root) at EVERY resolution the run trains at (train/ladder.py
    `ladder_resolutions`) — a candidate that regressed at the 64px rung
    must not ship on the strength of its 128px cells, and vice versa.
    Each cell's batch is drawn fixed-seed from that corpus at that
    resolution, falling back to the synthetic harness per cell."""
    from novel_view_synthesis_3d_tpu.registry import make_psnr_probe
    from novel_view_synthesis_3d_tpu.train.ladder import ladder_resolutions

    rcfg = cfg.registry
    if cfg.data.mix:
        from novel_view_synthesis_3d_tpu.data.corpus import parse_mix_spec

        corpora = [(s.name, s.path) for s in parse_mix_spec(cfg.data.mix)]
    else:
        corpora = [("train", folder or cfg.data.root_dir)]
    cells = []
    for name, root in corpora:
        for res in ladder_resolutions(cfg):
            ccfg = cfg.override(**{
                "data.root_dir": root or "",
                "data.img_sidelength": res,
                "data.mix": "",
            })
            cells.append({
                "corpus": name,
                "resolution": res,
                "metric": "psnr",
                "probe_fn": make_psnr_probe(
                    model, cfg.diffusion, _gate_probe_batch(ccfg, None),
                    sample_steps=psnr_sample_steps, seed=rcfg.gate_seed,
                    precision=cfg.serve.precision),
            })
    return cells


def _run_gates(cfg, model, store, vid: str, channel: str, batch: dict,
               *, psnr_sample_steps: int, event_cb, folder=None):
    """Run every configured promotion gate for one candidate.

    Always the fixed-seed single-frame PSNR probe; additionally, when
    registry.gate_trajectory_frames > 0, the multi-view CONSISTENCY
    probe (adjacent-frame PSNR over a fixed stochastic-conditioning
    orbit, registry/gate.make_trajectory_probe) under the SAME
    gate_margin_db — so distilled/quantized candidates are judged on
    trajectory quality, not just single-frame fidelity. A `data.mix` or
    `train.ladder` run additionally gates on the per-corpus ×
    per-rung-resolution PSNR MATRIX (registry/gate.run_gate_matrix; one
    regressed cell refuses the promotion), with the matrix landed as
    gate_matrix.json in the registry root for summarize_bench. Prints
    one JSON line per gate; returns (all_passed,
    gate_result_for_promote)."""
    from novel_view_synthesis_3d_tpu.registry import (
        GateResult, make_psnr_probe, make_trajectory_probe, run_gate,
        run_gate_matrix)

    rcfg = cfg.registry
    probes = [("psnr", make_psnr_probe(
        model, cfg.diffusion, batch, sample_steps=psnr_sample_steps,
        seed=rcfg.gate_seed, precision=cfg.serve.precision))]
    if rcfg.gate_trajectory_frames:
        probes.append(("trajectory_consistency", make_trajectory_probe(
            model, cfg.diffusion, batch,
            frames=rcfg.gate_trajectory_frames,
            sample_steps=rcfg.gate_sample_steps, seed=rcfg.gate_seed,
            precision=cfg.serve.precision,
            k_max=cfg.serve.k_max or None)))
    last = None
    for metric, probe in probes:
        gate = run_gate(store, vid, channel=channel, probe_fn=probe,
                        margin_db=rcfg.gate_margin_db,
                        event_cb=event_cb, metric=metric)
        print(json.dumps({
            "metric": metric,
            "candidate": gate.candidate, "incumbent": gate.incumbent,
            "candidate_psnr": round(gate.candidate_psnr, 3),
            "incumbent_psnr": (None if gate.incumbent_psnr is None
                               else round(gate.incumbent_psnr, 3)),
            "margin_db": gate.margin_db,
            "passed": gate.passed, "reason": gate.reason}))
        last = gate
        if not gate.passed:
            return False, gate
    if cfg.data.mix or cfg.train.ladder:
        matrix = run_gate_matrix(
            store, vid, channel=channel,
            cells=_gate_matrix_cells(cfg, model, folder,
                                     psnr_sample_steps=psnr_sample_steps),
            margin_db=cfg.registry.gate_margin_db, event_cb=event_cb)
        artifact = os.path.join(store.root, "gate_matrix.json")
        with open(artifact, "w") as fh:
            json.dump({
                "candidate": matrix.candidate,
                "incumbent": matrix.incumbent,
                "margin_db": matrix.margin_db,
                "passed": matrix.passed,
                "cells": list(matrix.cells),
            }, fh, indent=2)
        print(json.dumps({
            "metric": "matrix", "passed": matrix.passed,
            "cells": len(matrix.cells),
            "failed": sum(1 for c in matrix.cells if not c["passed"]),
            "artifact": artifact}))
        if not matrix.passed:
            worst = min((c for c in matrix.cells if not c["passed"]),
                        key=lambda c: (c["delta_db"]
                                       if c["delta_db"] is not None
                                       else 0.0))
            return False, GateResult(
                passed=False, candidate=vid, incumbent=matrix.incumbent,
                candidate_psnr=worst["candidate_psnr"],
                incumbent_psnr=worst["incumbent_psnr"],
                margin_db=matrix.margin_db,
                reason=(f"matrix cell {worst['corpus']}@"
                        f"{worst['resolution']}px: {worst['reason']}"))
    return True, last


def cmd_registry(args, overrides: List[str]) -> int:
    """Model lifecycle verbs over a registry directory.

    publish: newest VERIFIED checkpoint (integrity walk-back) → a
    content-hashed version on the `latest` channel. promote: fixed-seed
    PSNR gate vs the incumbent, then advance the stable channel —
    auto-reject (rc=1, pointer untouched) on regression beyond
    registry.gate_margin_db. rollback: previous stable version (a
    subscribed service hot-reloads it on the next poll). gc: keep the
    newest registry.keep versions; channel-pinned versions survive.
    """
    from novel_view_synthesis_3d_tpu.registry import (
        RegistryError, RegistryStore)

    store = RegistryStore(args.dir)
    sub = args.registry_command

    if sub == "list":
        versions = store.list_versions()
        channels = store.channels()
        if args.json:
            import dataclasses

            print(json.dumps({
                "versions": [dataclasses.asdict(m) for m in versions],
                "channels": channels}))
            return 0
        if not versions:
            print(f"(empty registry at {store.root})")
        by_version = {}
        for name, vid in channels.items():
            by_version.setdefault(vid, []).append(name)
        for m in versions:
            tags = ",".join(sorted(by_version.get(m.version, []))) or "-"
            print(f"{m.version}  step={m.step:<8d} ema={int(m.ema)} "
                  f"fmt={m.fmt:<9s} channels={tags}")
        for name, vid in sorted(channels.items()):
            print(f"channel {name} -> {vid}")
        return 0

    event_cb = _registry_event_cb(args.dir)

    if sub == "publish":
        from novel_view_synthesis_3d_tpu.data.synthetic import (
            make_example_batch)
        from novel_view_synthesis_3d_tpu.models import build_denoiser
        from novel_view_synthesis_3d_tpu.registry.manifest import (
            config_digest)
        from novel_view_synthesis_3d_tpu.train.trainer import (
            _sample_model_batch)

        cfg = build_config(args, overrides)
        model = build_denoiser(cfg.model)
        sample_batch = _sample_model_batch(make_example_batch(
            batch_size=1, sidelength=cfg.data.img_sidelength))
        # step=None rides the checkpoint integrity walk-back: a torn
        # newest save publishes the newest VERIFIED step instead.
        params, step = _restore_params(cfg, model, sample_batch, args.step)
        m = store.publish_params(
            params, step=step, ema=cfg.train.ema_decay > 0,
            config_digest=config_digest(cfg), channel=args.channel,
            notes=args.notes)
        event_cb(step, "model_publish",
                 f"channel {args.channel} <- {m.version} (cli)", m.version)
        print(f"published {m.version} (step {step}, "
              f"channel {args.channel})")
        return 0

    if sub == "promote":
        from novel_view_synthesis_3d_tpu.registry import promote

        cfg = build_config(args, overrides)
        channel = args.channel or cfg.registry.channel
        vid = args.version or store.read_channel(args.from_channel)
        if vid is None:
            raise SystemExit(
                f"nothing to promote: channel {args.from_channel!r} is "
                "empty and no --version was given")
        gate_result = None
        if not args.force:
            from novel_view_synthesis_3d_tpu.models import build_denoiser

            # Probe AT the serving precision (serve.precision): a
            # version promoted into a bf16/int8 deployment is gated on
            # what that deployment actually computes with. With
            # registry.gate_trajectory_frames set, the multi-view
            # consistency gate runs too (same margin).
            try:
                passed, gate_result = _run_gates(
                    cfg, build_denoiser(cfg.model), store, vid, channel,
                    _gate_probe_batch(cfg, args.folder),
                    psnr_sample_steps=cfg.registry.gate_sample_steps,
                    event_cb=event_cb)
            except RegistryError as e:
                raise SystemExit(f"gate error: {e}")
            if not passed:
                print(f"promotion REFUSED: {gate_result.reason} "
                      f"(channel {channel} still -> "
                      f"{store.read_channel(channel)})")
                return 1
        try:
            promote(store, vid, channel=channel, gate=gate_result,
                    event_cb=event_cb)
        except RegistryError as e:
            raise SystemExit(str(e))
        print(f"promoted {vid} -> channel {channel}")
        return 0

    if sub == "rollback":
        from novel_view_synthesis_3d_tpu.registry import rollback

        try:
            restored = rollback(store, channel=args.channel,
                                event_cb=event_cb)
        except RegistryError as e:
            raise SystemExit(str(e))
        print(f"channel {args.channel} rolled back to {restored}")
        return 0

    if sub == "gc":
        from novel_view_synthesis_3d_tpu.config import RegistryConfig

        keep = args.keep if args.keep is not None else RegistryConfig().keep
        try:
            deleted = store.gc(keep)
        except ValueError as e:
            raise SystemExit(str(e))
        for vid in deleted:
            event_cb(0, "gc", f"deleted version {vid} (keep={keep})", vid)
        print(json.dumps({"deleted": deleted, "keep": keep,
                          "kept": [m.version
                                   for m in store.list_versions()]}))
        return 0

    raise SystemExit(f"unknown registry command {sub!r}")


# ---------------------------------------------------------------------------
# obs (offline observability: trace reconstruction, run diff, SLO score)
# ---------------------------------------------------------------------------
def cmd_obs(args, overrides: List[str]) -> int:
    """Postmortem tooling over a finished run's telemetry.jsonl.

    `trace`: reconstruct per-request causal timelines (which dispatches
    a request rode, co-rider counts, step debt, swap drains) and verify
    the trace invariants; `diff`: span-percentile drift between two
    runs; `slo`: whole-run SLO attainment per step class; `numerics`:
    per-layer-group training stats + spike/anomaly triage from
    numerics.jsonl; `compiles`: the jit build ledger with recompile
    culprits from compiles.jsonl. No JAX, no device — these read what
    obs/ defines and the run emitted, so they work on a laptop against
    rsync'd artifacts.
    """
    from novel_view_synthesis_3d_tpu.obs import reqtrace

    sub = args.obs_command

    if sub == "trace":
        # Fleet layout (<run>/router/ + <run>/replica_<name>/ — the
        # `nvs3d route` / serve_bench --fleet convention): reconstruct
        # cross-replica timelines keyed by the trace_id the router
        # threaded through every hop, then verify the fleet invariants
        # (hop/failover accounting, replica-side joins).
        per_source = reqtrace.load_fleet_rows(args.run)
        if per_source.get("router"):
            fleet = reqtrace.reconstruct_fleet(per_source)
            problems = reqtrace.verify_fleet(fleet, per_source)
            if args.trace_id:
                fleet = {t: tl for t, tl in fleet.items()
                         if t == args.trace_id}
                if not fleet:
                    raise SystemExit(
                        f"trace {args.trace_id!r} not found in fleet "
                        f"dir {args.run!r}")
            if args.json:
                print(json.dumps({"fleet": True,
                                  "timelines": list(fleet.values()),
                                  "problems": problems}))
            else:
                for tid in sorted(fleet):
                    print(reqtrace.format_fleet_timeline(fleet[tid]))
                    print()
                for p in problems:
                    print(f"PROBLEM: {p}")
            return 1 if problems else 0
        rows = reqtrace.load_rows(args.run)
        if not rows:
            raise SystemExit(
                f"no telemetry rows under {args.run!r} — was the run "
                "recorded with obs.jsonl=true?")
        timelines = reqtrace.reconstruct(rows)
        if not timelines:
            raise SystemExit(
                f"{len(rows)} telemetry rows but no request_submit "
                "spans — not a serving run, or pre-tracing telemetry")
        problems = reqtrace.verify_timelines(timelines, rows)
        if args.trace_id:
            sel = {t: tl for t, tl in timelines.items()
                   if t == args.trace_id}
            if not sel:
                raise SystemExit(
                    f"trace {args.trace_id!r} not found (known: "
                    f"{', '.join(sorted(timelines)[:10])}...)")
        else:
            sel = timelines
        if args.json:
            print(json.dumps({"timelines": list(sel.values()),
                              "problems": problems}))
        else:
            for tid in sorted(sel):
                print(reqtrace.format_timeline(sel[tid]))
                print()
            for p in problems:
                print(f"PROBLEM: {p}")
        if args.perfetto:
            if args.trace_id:
                out = reqtrace.export_perfetto(
                    sel[args.trace_id], args.perfetto)
                print(f"wrote {out}")
            else:
                os.makedirs(args.perfetto, exist_ok=True)
                for tid, tl in sorted(sel.items()):
                    reqtrace.export_perfetto(tl, os.path.join(
                        args.perfetto, f"request_{tid}.json"))
                print(f"wrote {len(sel)} per-request tracks under "
                      f"{args.perfetto}")
        return 1 if problems else 0

    if sub == "diff":
        pa = reqtrace.span_percentiles(reqtrace.load_rows(args.a))
        pb = reqtrace.span_percentiles(reqtrace.load_rows(args.b))
        if not pa or not pb:
            raise SystemExit("no span rows in "
                             + (args.a if not pa else args.b))
        diff = reqtrace.diff_percentiles(
            pa, pb, threshold_pct=args.threshold_pct)
        drifted = [d for d in diff if d["drift"]]
        if args.json:
            print(json.dumps({"diff": diff,
                              "drifted": [d["name"] for d in drifted]}))
        else:
            for d in diff:
                flag = "DRIFT" if d["drift"] else "     "
                deltas = " ".join(
                    f"{k.split('_')[0]}{v:+.1f}%"
                    for k, v in d["deltas_pct"].items()) or d.get(
                        "note", "")
                print(f"{flag} {d['name']:<24s} {deltas}")
            print(f"{len(drifted)}/{len(diff)} span names drifted "
                  f">{args.threshold_pct:.0f}% (B vs A)")
        return 1 if drifted else 0

    if sub == "slo":
        from novel_view_synthesis_3d_tpu.obs import slo as slo_lib

        spec = args.targets
        if spec is None:
            cfg = build_config(args, overrides)
            spec = cfg.serve.slo.targets
        targets = slo_lib.parse_targets(spec)
        if not targets:
            raise SystemExit(
                "no SLO targets: pass --targets '4:500,64:2000' or set "
                "serve.slo.targets")
        rows = reqtrace.load_rows(args.run)
        snap = slo_lib.attainment_from_rows(rows, targets)
        print(json.dumps({"run": args.run, "slo": snap}))
        missed = [c for c, s in snap.items()
                  if s["total"] and s["attainment"] < s["objective"]]
        return 1 if missed else 0

    if sub == "numerics":
        return _obs_numerics(args)

    if sub == "compiles":
        return _obs_compiles(args)

    if sub == "roofline":
        return _obs_roofline(args)

    if sub == "doctor":
        return _obs_doctor(args)

    raise SystemExit(f"unknown obs command {sub!r}")


def _obs_roofline(args) -> int:
    """Roofline a run: measured per-group device time (profile_window
    rows) × analytic costmap FLOPs/bytes × chip peaks → per-group MFU,
    bandwidth utilization, bound class, and the top-k headroom list
    (the aim list for the ROADMAP perf arcs)."""
    from novel_view_synthesis_3d_tpu.obs import roofline as roofline_lib

    report = roofline_lib.analyze_run(
        args.run, peak_flops=args.peak_flops,
        peak_bytes_per_s=args.peak_bytes)
    if not report["rows"]:
        raise SystemExit(
            f"nothing to roofline under {args.run!r}: no costmap.json "
            "and no profile_window rows in telemetry.jsonl (run with "
            "obs.profile.enabled and obs.cost_analysis)")
    if args.json:
        print(json.dumps(report))
    else:
        print(roofline_lib.render(report, k=args.top))
    return 0


def _obs_doctor(args) -> int:
    """The regression doctor: rank every artifact-backed finding. Two
    modes — `doctor RUN_A RUN_B` diffs two results folders; `doctor
    --trajectory [ROOT]` reads the banked BENCH_r*/MULTICHIP_r* archive
    via the run index. rc=1 when any page-severity finding lands (the
    sentry's embedding reads the same ranked list)."""
    from novel_view_synthesis_3d_tpu.obs import doctor as doctor_lib

    if args.trajectory:
        root = args.run_a or "."
        doc = doctor_lib.diagnose_trajectory(
            root, tolerance_pct=args.tolerance_pct)
    else:
        if not args.run_a or not args.run_b:
            raise SystemExit(
                "doctor needs RUN_A RUN_B (pair mode) or --trajectory "
                "[ROOT] (archive mode)")
        doc = doctor_lib.diagnose_pair(args.run_a, args.run_b)
    if args.out:
        path = doctor_lib.write_doctor(args.out, doc)
        print(f"wrote {path}")
    if args.json:
        print(json.dumps(doc))
    else:
        print(doctor_lib.render(doc, limit=args.limit))
    pages = [f for f in doc.get("findings", [])
             if f.get("severity") == "page"]
    return 1 if pages else 0


def _obs_numerics(args) -> int:
    """Render a run's numerics.jsonl: per-group latest stats, the spike
    timeline, and anomaly provenance from events.csv. rc=1 when a spike
    or anomaly is UNRESOLVED — the loss-spike triage runbook's exit code
    (docs/TPU_VM_SETUP.md)."""
    from novel_view_synthesis_3d_tpu import obs

    path = obs.numerics_path(args.run)
    rows, spikes = [], []
    if os.path.exists(path):
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn trailing line
                if rec.get("kind") == "numerics":
                    rows.append(rec)
                elif rec.get("kind") == "numerics_spike":
                    spikes.append(rec)
    if not rows:
        raise SystemExit(
            f"no numerics rows under {args.run!r} — was the run trained "
            "with train.numerics.enabled=true?")
    anomalies = [ev for ev in obs.read_events(args.run)
                 if ev.get("event") == "anomaly"]

    latest = rows[-1]
    # A spike is RESOLVED once any later row shows that group's grad
    # norm back below the spiking sample; otherwise it is still burning.
    def resolved(spike) -> bool:
        for row in rows:
            if row["step"] <= spike["step"]:
                continue
            g = row["groups"].get(spike["group"], {})
            gn = g.get("grad_norm")
            if gn is not None and gn < spike["grad_norm"]:
                return True
        return False

    unresolved_spikes = [s for s in spikes if not resolved(s)]
    # An anomaly is resolved once a LATER numerics row is clean (every
    # group finite) — i.e. training demonstrably recovered after it.
    def clean_after(step: int) -> bool:
        for row in rows:
            if row["step"] <= step:
                continue
            if all((g.get("nonfinite") or 0) == 0
                   for g in row["groups"].values()):
                return True
        return False

    def anomaly_step(ev) -> int:
        try:
            return int(ev.get("step", -1))
        except (TypeError, ValueError):
            return -1

    unresolved_anoms = [e for e in anomalies
                        if not clean_after(anomaly_step(e))]

    if args.json:
        print(json.dumps({
            "run": args.run, "rows": len(rows),
            "last_step": latest["step"], "groups": latest["groups"],
            "spikes": spikes,
            "unresolved_spikes": unresolved_spikes,
            "anomalies": [dict(e) for e in anomalies],
            "unresolved_anomalies": [dict(e) for e in unresolved_anoms],
        }))
        return 1 if unresolved_spikes or unresolved_anoms else 0

    print(f"numerics: {len(rows)} rows, last step {latest['step']} "
          f"({len(latest['groups'])} layer groups)")
    print(f"{'group':<16s} {'grad_norm':>10s} {'param_norm':>10s} "
          f"{'upd_ratio':>10s} {'grad_max':>10s} {'nonfin':>6s}")
    for label, g in latest["groups"].items():
        print(f"{label:<16s} {g.get('grad_norm', 0.0):>10.3e} "
              f"{g.get('param_norm', 0.0):>10.3e} "
              f"{g.get('update_ratio', 0.0):>10.3e} "
              f"{g.get('grad_max', 0.0):>10.3e} "
              f"{int(g.get('nonfinite') or 0):>6d}")
    if spikes:
        print(f"\nspike timeline ({len(spikes)}):")
        for s in spikes:
            state = ("resolved" if s not in unresolved_spikes
                     else "UNRESOLVED")
            print(f"  step {s['step']:>8d} {s['group']:<16s} "
                  f"z={s['z']:.1f} grad_norm={s['grad_norm']:.3e} "
                  f"[{state}]")
    if anomalies:
        print(f"\nanomaly events ({len(anomalies)}):")
        for e in anomalies:
            state = ("resolved" if e not in unresolved_anoms
                     else "UNRESOLVED")
            print(f"  step {e.get('step', '?'):>8s} "
                  f"{e.get('detail', '')} [{state}]")
    if unresolved_spikes or unresolved_anoms:
        print(f"\nUNRESOLVED: {len(unresolved_spikes)} spike(s), "
              f"{len(unresolved_anoms)} anomaly(ies) — triage per "
              "docs/TPU_VM_SETUP.md 'Loss-spike triage'")
        return 1
    return 0


def _obs_compiles(args) -> int:
    """Render a run's compile ledger (compiles.jsonl): every jit build
    with its wall time and HLO hash, recompiles with the argument that
    changed. rc=1 when the ledger records any recompile."""
    from novel_view_synthesis_3d_tpu import obs

    entries = obs.load_ledger(args.run)
    if not entries:
        raise SystemExit(
            f"no compile ledger under {args.run!r} — nothing jit-built "
            "there, or a pre-ledger run")
    recompiles = [e for e in entries if e.get("kind") == "recompile"]

    if args.why is not None:
        if not 1 <= args.why <= len(recompiles):
            raise SystemExit(
                f"--why {args.why}: run has {len(recompiles)} "
                "recompile(s)")
        e = recompiles[args.why - 1]
        print(f"recompile {args.why}/{len(recompiles)}: {e['name']}")
        for line in e.get("diff", []):
            print(f"  {line}")
        return 1

    if args.json:
        print(json.dumps({"run": args.run, "entries": entries,
                          "recompiles": len(recompiles)}))
        return 1 if recompiles else 0

    print(f"{'#':>3s} {'kind':<10s} {'name':<18s} {'wall_s':>8s} "
          f"{'hlo':<12s} changed")
    for i, e in enumerate(entries):
        wall = e.get("wall_s")
        print(f"{i:>3d} {e.get('kind', '?'):<10s} "
              f"{e.get('name', '?'):<18s} "
              f"{wall if wall is not None else '':>8} "
              f"{e.get('hlo_hash', ''):<12s} {e.get('changed', '')}")
    print(f"{len(entries)} build(s), {len(recompiles)} recompile(s)"
          + (" — `--why N` shows the Nth recompile's full diff"
             if recompiles else ""))
    return 1 if recompiles else 0


# ---------------------------------------------------------------------------
def cmd_route(args, overrides: List[str]) -> int:
    """Fleet front-end operations against running replica processes
    (serve/replica_main.py, or any ReplicaServer).

    `status`: poll every replica's /healthz through a FleetRouter and
    print the aggregated fleet snapshot (dispatch eligibility, step
    debt, breaker states, live SLO burn); rc=1 unless every replica is
    dispatchable. `deploy`: zero-downtime rolling deploy — move the
    registry channel, then per replica quiesce → drain-to-idle → poke
    the watcher → verify the swap → readmit → SLO-burn probation, with
    fleet-wide auto-rollback on any gate failure (serve/deploy.py);
    rc=0 only when the report says 'deployed'. Replicas are named
    `--replica name=http://host:port` (bare URLs get r0, r1, ...).
    """
    from novel_view_synthesis_3d_tpu.serve import (
        FleetRouter,
        HttpReplica,
        rolling_deploy,
    )

    cfg = build_config(args, overrides)
    handles = []
    for i, spec in enumerate(args.replica or []):
        name, sep, url = spec.partition("=")
        if not sep:
            name, url = f"r{i}", spec
        handles.append(HttpReplica(name, url))
    if not handles:
        raise SystemExit("no replicas: pass --replica name=URL "
                         "(repeatable)")
    journal = getattr(args, "journal", None)
    router = FleetRouter(handles, rcfg=cfg.router, journal=journal)
    sub = args.route_command

    if sub == "status":
        router.poll_health()
        snap = router.fleet_snapshot()
        snap["slo"] = router.fleet_slo()
        print(json.dumps(snap, indent=None if args.json else 2,
                         sort_keys=True))
        rec = snap.get("recovery")
        if rec:
            rc = rec.get("recovered_steps") or {}
            print(f"# journal {rec['journal']}: {rec['records']} "
                  f"record(s), {rec['pins_restored']} override pin(s) "
                  f"restored, {sum(rc.values())} pre-poll step(s) over "
                  f"{len(rc)} replica(s), "
                  f"{len(rec.get('reconciled') or {})} reconciled "
                  f"against live /healthz"
                  + (f", {rec['torn']} torn line(s)"
                     if rec.get("torn") else ""),
                  file=sys.stderr)
        return 0 if snap["healthy"] == snap["total"] else 1

    if sub == "deploy":
        from novel_view_synthesis_3d_tpu.registry import RegistryStore

        store = RegistryStore(args.dir)
        version = args.version or store.read_channel(args.from_channel)
        if not version:
            raise SystemExit(
                f"no deploy target: --version not given and channel "
                f"{args.from_channel!r} points at no version")
        router.poll_health()
        report = rolling_deploy(router, store, args.channel, version,
                                rcfg=cfg.router)
        print(json.dumps(report))
        return 0 if report["status"] == "deployed" else 1

    raise SystemExit(f"unknown route command {sub!r}")


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------
def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--preset", default=None, choices=PRESET_NAMES,
                   help="config preset")
    p.add_argument("--config", default=None, help="config JSON file")


def make_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="python -m novel_view_synthesis_3d_tpu",
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("train", help="train the X-UNet (reference train.py)")
    _add_common(p)
    p.add_argument("folder", nargs="?", default=None,
                   help="SRN dataset root (overrides data.root_dir)")
    p.add_argument("--no-grain", action="store_true",
                   help="in-process data loading (no worker processes)")
    p.add_argument("--supervise", action="store_true",
                   help="run training in a supervised child process: "
                        "restart on crash or watchdog-declared stall with "
                        "exponential backoff (train.max_restarts), "
                        "resuming from the newest intact checkpoint")

    p = sub.add_parser("sample",
                       help="sample novel views (reference sampling.py, PNGs "
                            "instead of cv2 windows)")
    _add_common(p)
    p.add_argument("folder", nargs="?", default=None)
    p.add_argument("--out", default="./samples")
    p.add_argument("--instance", type=int, default=0)
    p.add_argument("--cond-view", type=int, default=0)
    p.add_argument("--num-views", type=int, default=8)
    p.add_argument("--poses", choices=("dataset", "orbit", "interp"),
                   default="dataset",
                   help="targets: dataset ground-truth poses, a synthetic "
                        "orbit, or a smooth slerp path through the "
                        "instance's poses")
    p.add_argument("--pool-views", type=int, default=1,
                   help="with --stochastic: seed the conditioning pool "
                        "with this many REAL dataset views (default 1, "
                        "the 3DiM paper protocol)")
    p.add_argument("--elevation", type=float, default=0.3,
                   help="orbit elevation (radians), --poses orbit only")
    p.add_argument("--stochastic", action="store_true",
                   help="3DiM autoregressive stochastic conditioning")
    p.add_argument("--trajectory", type=int, default=0, metavar="N",
                   help="serving-grade N-frame orbit: one "
                        "TrajectoryRequest through the stepper ring — "
                        "device-resident frame bank, stochastic "
                        "conditioning per step, frames streamed as they "
                        "finish; writes orbit_strip.png beside the views")
    p.add_argument("--sample-steps", type=int, default=None,
                   help="respaced DDPM steps (default: config)")
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step (default: latest)")
    p.add_argument("--reference-ckpt", default=None,
                   help="load a reference-format flax msgpack checkpoint "
                        "(e.g. the published pretrained model) instead of "
                        "this repo's checkpoints; pair with "
                        "--preset reference")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--gif", action="store_true",
                   help="also write a looping orbit.gif of the views")
    p.add_argument("--gif-fps", type=float, default=8.0)
    p.add_argument("--denoise-gif", action="store_true",
                   help="also write denoise.gif showing the reverse "
                        "diffusion of the first view (not with --stochastic)")

    p = sub.add_parser("serve",
                       help="micro-batched sampling service: coalesce "
                            "concurrent requests into padded power-of-two "
                            "buckets served from a compiled-program cache")
    _add_common(p)
    p.add_argument("folder", nargs="?", default=None)
    p.add_argument("--out", default="./serve",
                   help="request PNGs + the service events.csv land here")
    p.add_argument("--requests", default=None, metavar="JSONL",
                   help="JSON-lines request file (fields: instance, "
                        "cond_view, target_view, seed, sample_steps, "
                        "guidance_weight, deadline_ms, trace_id "
                        "(client-chosen id for nvs3d obs trace); "
                        "trajectory "
                        "requests add poses=[[4x4],...] or orbit=N plus "
                        "optional k_max — responses then stream one "
                        "line per frame with frame_index/model_version);"
                        " default: a --num-requests demo sweep")
    p.add_argument("--trajectory", type=int, default=0, metavar="N",
                   help="demo sweep serves N-frame ORBITS instead of "
                        "single views: --num-requests trajectories "
                        "stream per-frame responses and write per-"
                        "request orbit PNG strips")
    p.add_argument("--num-requests", type=int, default=8)
    p.add_argument("--instance", type=int, default=0)
    p.add_argument("--cond-view", type=int, default=0)
    p.add_argument("--sample-steps", type=int, default=None,
                   help="respaced steps (default: serve.sample_steps or "
                        "diffusion.sample_timesteps)")
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step (default: latest)")
    p.add_argument("--reference-ckpt", default=None,
                   help="serve a reference-format flax msgpack checkpoint; "
                        "pair with --preset reference")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--timeout", type=float, default=600.0,
                   help="per-request wall-clock budget in seconds "
                        "(queue wait + compile + device); a wedged "
                        "dispatch reports TimeoutError per request "
                        "instead of hanging the CLI forever")
    p.add_argument("--registry", default=None, metavar="DIR",
                   help="serve from a model registry instead of a "
                        "checkpoint: load the subscribed channel's "
                        "version and HOT-RELOAD (zero downtime) whenever "
                        "the pointer moves")
    p.add_argument("--channel", default=None,
                   help="registry channel to subscribe "
                        "(default: registry.channel, i.e. 'stable')")

    p = sub.add_parser("eval", help="PSNR/SSIM/FID over held-out views")
    _add_common(p)
    p.add_argument("folder", nargs="?", default=None)
    p.add_argument("--out", default=None, help="write result JSON here")
    p.add_argument("--num-instances", type=int, default=None)
    p.add_argument("--views-per-instance", type=int, default=1)
    p.add_argument("--cond-view", type=int, default=0)
    p.add_argument("--sample-steps", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--step", type=int, default=None)
    p.add_argument("--reference-ckpt", default=None,
                   help="load a reference-format flax msgpack checkpoint; "
                        "pair with --preset reference")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--protocol", choices=("single", "autoregressive"),
                   default="single",
                   help="'single': every target conditioned on the fixed "
                        "view; 'autoregressive': 3DiM stochastic "
                        "conditioning over the growing view pool")
    p.add_argument("--fid", action="store_true",
                   help="also compute Fréchet distance — reported as "
                        "'fid_random' (deterministic random-conv features, "
                        "NOT comparable to published Inception-FID; see "
                        "eval/metrics.py)")
    p.add_argument("--inception-npz", default=None,
                   help="InceptionV3 weights (.npz from "
                        "tools/convert_inception.py): compute the Fréchet "
                        "distance over pool3 features and report it as the "
                        "paper-comparable 'fid' (implies --fid)")
    p.add_argument("--dump-comparisons", default=None, metavar="PNG",
                   help="write a [conditioning | ground truth | synthesis] "
                        "row per scored pair (first 8) — the human-legible "
                        "form of the PSNR table")

    p = sub.add_parser("prep", help="offline dataset preparation")
    prep_sub = p.add_subparsers(dest="prep_command", required=True)
    q = prep_sub.add_parser("split-object",
                            help="SRN per-object 1-in-3 train/val split")
    q.add_argument("object_dir")
    q.add_argument("train_dir")
    q.add_argument("val_dir")
    q.add_argument("--symlink", action="store_true")
    q.add_argument("--invert", action="store_true",
                   help="train on the 2-in-3 slice, hold out 1-in-3 "
                        "(default mirrors the reference: train on the "
                        "sparse third)")
    q = prep_sub.add_parser("shapenet", help="CSV-driven ShapeNet split")
    q.add_argument("shapenet_path")
    q.add_argument("synset_id")
    q.add_argument("name")
    q.add_argument("csv_path")
    q.add_argument("--symlink", action="store_true")

    p = sub.add_parser(
        "pack",
        help="pack an SRN tree into sharded records (data.backend="
             "'packed'), or --verify an existing packed corpus")
    p.add_argument("src",
                   help="SRN dataset root to pack, or a packed corpus "
                        "dir with --verify and no --out")
    p.add_argument("--out", default=None, metavar="DIR",
                   help="output corpus dir (shard-*.nvsrec + index.json)")
    p.add_argument("--shard-mb", type=float, default=64.0,
                   help="target shard size in MB; shards close at the "
                        "scene boundary past this (default 64). Pack "
                        "with at least as many shards as training hosts "
                        "— per-host reads slice at shard granularity")
    p.add_argument("--max-instances", type=int, default=-1,
                   help="pack only the first N instances (-1 = all)")
    p.add_argument("--name", default=None,
                   help="corpus name recorded in index.json meta (default: "
                        "the source dir's basename); the mixer's stats and "
                        "gauges use it")
    p.add_argument("--class", dest="classes", action="append", default=None,
                   metavar="NAME",
                   help="scene-class vocab entry for index.json meta "
                        "(repeatable; default: the corpus name)")
    p.add_argument("--verify", action="store_true",
                   help="after packing (or on an existing corpus with no "
                        "--out): re-hash every shard, cross-check "
                        "footers vs index.json, unpack every record, "
                        "decode a probe view per scene; rc=1 on failure")
    p.add_argument("--deep", action="store_true",
                   help="with --verify: decode EVERY view, not one per "
                        "scene")
    p.add_argument("--progress", action="store_true",
                   help="print one line per packed instance")

    p = sub.add_parser("config", help="print the resolved config JSON")
    _add_common(p)

    p = sub.add_parser("export",
                       help="write a checkpoint as a reference-format flax "
                            "msgpack file (inverse of --reference-ckpt)")
    _add_common(p)
    p.add_argument("--out", required=True,
                   help="output path (e.g. checkpoints_ref/model50000)")
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step (default: newest VERIFIED step — "
                        "the checkpoint integrity walk-back skips torn "
                        "saves)")
    p.add_argument("--registry", default=None, metavar="DIR",
                   help="also publish the converted snapshot as a "
                        "registry version (manifest fmt=reference)")
    p.add_argument("--channel", default="latest",
                   help="registry channel for --registry (default latest)")

    p = sub.add_parser(
        "distill",
        help="progressive distillation: halve the teacher's sampling "
             "steps per round (registry teacher -> published few-step "
             "students, optional PSNR-gated promotion)")
    _add_common(p)
    p.add_argument("folder", nargs="?", default=None,
                   help="SRN tree for distillation batches (default "
                        "data.root_dir; synthetic fallback)")
    p.add_argument("--registry", required=True, metavar="DIR",
                   help="registry holding the teacher; students are "
                        "published here")
    p.add_argument("--teacher-channel", default="stable",
                   help="channel supplying the teacher (default stable)")
    p.add_argument("--teacher-version", default=None,
                   help="explicit teacher version id (overrides "
                        "--teacher-channel)")
    p.add_argument("--channel", default="distill",
                   help="channel each student generation is published to "
                        "(default 'distill')")
    p.add_argument("--promote-channel", default=None,
                   help="after the final round, run the PSNR gate and "
                        "advance this channel to the few-step student "
                        "(rc=1 + pointer untouched on a gate fail)")

    p = sub.add_parser(
        "registry",
        help="model lifecycle: versioned publish, quality-gated promote, "
             "rollback, gc over a registry directory")
    reg_sub = p.add_subparsers(dest="registry_command", required=True)
    q = reg_sub.add_parser("list", help="versions + channel pointers")
    q.add_argument("--dir", required=True, help="registry root directory")
    q.add_argument("--json", action="store_true")
    q = reg_sub.add_parser(
        "publish", help="newest verified checkpoint -> a registry version")
    _add_common(q)
    q.add_argument("--dir", required=True)
    q.add_argument("--step", type=int, default=None,
                   help="checkpoint step (default: newest VERIFIED step)")
    q.add_argument("--channel", default="latest")
    q.add_argument("--notes", default="")
    q = reg_sub.add_parser(
        "promote",
        help="run the PSNR gate vs the incumbent, then advance the "
             "stable channel (auto-reject on regression)")
    _add_common(q)
    q.add_argument("--dir", required=True)
    q.add_argument("--version", default=None,
                   help="candidate version id (default: the latest "
                        "channel's pointer)")
    q.add_argument("--from-channel", default="latest",
                   help="channel supplying the candidate when no "
                        "--version is given")
    q.add_argument("--channel", default=None,
                   help="destination channel (default registry.channel)")
    q.add_argument("--folder", default=None,
                   help="SRN tree for the gate probe (default "
                        "data.root_dir, synthetic fallback)")
    q.add_argument("--force", action="store_true",
                   help="skip the gate (operator override; the promote "
                        "event still lands in the audit log)")
    q = reg_sub.add_parser(
        "rollback", help="point the channel back at its previous version")
    q.add_argument("--dir", required=True)
    q.add_argument("--channel", default="stable")
    q = reg_sub.add_parser(
        "gc", help="delete all but the newest K versions "
                   "(channel-pinned versions always survive)")
    q.add_argument("--dir", required=True)
    q.add_argument("--keep", type=int, default=None,
                   help="versions to keep (default registry.keep)")

    p = sub.add_parser(
        "obs",
        help="postmortem tooling over a run's telemetry.jsonl: "
             "per-request trace reconstruction, cross-run span-"
             "percentile diff, whole-run SLO attainment")
    obs_sub = p.add_subparsers(dest="obs_command", required=True)
    q = obs_sub.add_parser(
        "trace",
        help="reconstruct per-request causal timelines (dispatches "
             "ridden, co-riders, step debt, swap drains) and verify "
             "the trace invariants; rc=1 on a broken trace")
    q.add_argument("run", help="run dir holding telemetry.jsonl")
    q.add_argument("--trace-id", default=None,
                   help="show one request (default: all)")
    q.add_argument("--json", action="store_true")
    q.add_argument("--perfetto", default=None, metavar="PATH",
                   help="export Perfetto/Chrome-trace track(s): a file "
                        "with --trace-id, else a directory of "
                        "per-request tracks")
    q = obs_sub.add_parser(
        "diff",
        help="span-percentile drift between two runs (p50/p90/p99 per "
             "span name); rc=1 when any span drifted past the "
             "threshold")
    q.add_argument("a", help="baseline run dir")
    q.add_argument("b", help="candidate run dir")
    q.add_argument("--threshold-pct", type=float, default=20.0)
    q.add_argument("--json", action="store_true")
    q = obs_sub.add_parser(
        "slo",
        help="whole-run SLO attainment per step class from the "
             "request_respond spans; rc=1 when a class missed its "
             "objective")
    _add_common(q)
    q.add_argument("run", help="run dir holding telemetry.jsonl")
    q.add_argument("--targets", default=None,
                   help="step-class targets, e.g. '4:500,64:2000' "
                        "(default: serve.slo.targets from config)")

    q = obs_sub.add_parser(
        "numerics",
        help="per-layer-group training numerics from numerics.jsonl: "
             "latest stats, spike timeline, anomaly provenance; rc=1 "
             "when a spike/anomaly is unresolved")
    q.add_argument("run", help="run dir holding numerics.jsonl")
    q.add_argument("--json", action="store_true",
                   help="machine-readable output")

    q = obs_sub.add_parser(
        "compiles",
        help="compile ledger from compiles.jsonl: every jit build with "
             "wall time + HLO hash, recompiles diffed to the argument "
             "that changed; rc=1 when any recompile is recorded")
    q.add_argument("run", help="run dir holding compiles.jsonl")
    q.add_argument("--json", action="store_true",
                   help="machine-readable output")
    q.add_argument("--why", type=int, default=None, metavar="N",
                   help="show the Nth recompile's full fingerprint diff")

    q = obs_sub.add_parser(
        "roofline",
        help="per-op-group roofline: measured device time (profile "
             "windows) × costmap FLOPs/bytes × chip peaks → MFU, "
             "bandwidth utilization, compute/memory/comm-bound class, "
             "top-k headroom")
    q.add_argument("run", help="run dir holding telemetry.jsonl "
                               "(+ costmap.json)")
    q.add_argument("--top", type=int, default=3,
                   help="top-k groups by headroom (default 3)")
    q.add_argument("--peak-flops", type=float, default=None,
                   help="override chip peak FLOPs/s (default: this "
                        "process's devices via obs.devmon)")
    q.add_argument("--peak-bytes", type=float, default=None,
                   help="override chip peak HBM bytes/s")
    q.add_argument("--json", action="store_true")

    q = obs_sub.add_parser(
        "doctor",
        help="ranked cross-run diagnosis: span drift, recompiles, "
             "numerics spikes, costmap drift, profile-window group "
             "drift (pair mode), or the whole banked BENCH_r* archive "
             "(--trajectory); rc=1 on a page-severity finding")
    q.add_argument("run_a", nargs="?", default=None,
                   help="baseline run dir (pair mode) or archive root "
                        "(--trajectory; default '.')")
    q.add_argument("run_b", nargs="?", default=None,
                   help="candidate run dir (pair mode)")
    q.add_argument("--trajectory", action="store_true",
                   help="diagnose the banked BENCH_r*/MULTICHIP_r* "
                        "archive instead of a run pair")
    q.add_argument("--tolerance-pct", type=float, default=2.0,
                   help="bench_sentry's rolling-median tolerance "
                        "(trajectory mode, default 2)")
    q.add_argument("--out", default=None, metavar="DIR",
                   help="also land the diagnosis as doctor.json in DIR")
    q.add_argument("--limit", type=int, default=0,
                   help="show at most N findings (0 = all)")
    q.add_argument("--json", action="store_true")

    p = sub.add_parser(
        "route",
        help="fleet front-end: aggregated replica health/SLO status "
             "and zero-downtime registry-channel rolling deploys "
             "with SLO-gated auto-rollback")
    route_sub = p.add_subparsers(dest="route_command", required=True)
    q = route_sub.add_parser(
        "status",
        help="poll every replica's /healthz and print the fleet "
             "snapshot (eligibility, step debt, breaker, SLO burn); "
             "rc=1 unless every replica is dispatchable")
    _add_common(q)
    q.add_argument("--replica", action="append", default=[],
                   metavar="NAME=URL",
                   help="replica endpoint (repeatable); bare URLs get "
                        "names r0, r1, ...")
    q.add_argument("--json", action="store_true",
                   help="single-line JSON (default: indented)")
    q.add_argument("--journal", default=None, metavar="PATH",
                   help="router journal to replay first: the snapshot "
                        "then carries the crash-restart reconstruction "
                        "provenance (records replayed, pins restored, "
                        "ledger steps reconciled against live /healthz)")
    q = route_sub.add_parser(
        "deploy",
        help="rolling deploy: move the registry channel, then per "
             "replica quiesce -> drain -> swap -> SLO-burn probation; "
             "auto-rollback on any gate failure; rc=0 only on "
             "'deployed'")
    _add_common(q)
    q.add_argument("--replica", action="append", default=[],
                   metavar="NAME=URL")
    q.add_argument("--dir", required=True, help="registry root directory")
    q.add_argument("--channel", default="stable",
                   help="channel the fleet subscribes to")
    q.add_argument("--version", default=None,
                   help="target version id (default: head of "
                        "--from-channel)")
    q.add_argument("--from-channel", default="latest",
                   help="channel supplying the target when no "
                        "--version is given")

    return parser


_COMMANDS = {
    "train": cmd_train,
    "sample": cmd_sample,
    "serve": cmd_serve,
    "eval": cmd_eval,
    "prep": cmd_prep,
    "pack": cmd_pack,
    "config": cmd_config,
    "export": cmd_export,
    "registry": cmd_registry,
    "distill": cmd_distill,
    "obs": cmd_obs,
    "route": cmd_route,
}


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    parser = make_parser()
    args, rest = parser.parse_known_args(argv)
    # The optional positional `folder` would otherwise swallow the first
    # key=value override when no folder is given.
    if getattr(args, "folder", None) and "=" in args.folder:
        rest.insert(0, args.folder)
        args.folder = None
    overrides = _split_overrides(rest)
    return _COMMANDS[args.command](args, overrides)


if __name__ == "__main__":
    sys.exit(main())

"""Chip smoke: train → sample → serve at a preset's full width, on the TPU.

    python chip_smoke.py                  # base128, one chip (the driver's run)
    python chip_smoke.py --chips 4        # only the data-parallel step check
    python chip_smoke.py --preset paper256

ONE process, no children (a chip belongs to one process at a time). Each
phase goes through the entry point a user would type — `cli.main([...])`,
the `train`, `sample` and `serve` verbs — on a synthetic SRN directory
written from --seed, at the preset's own widths. Before the phases, and
outside anything timed, every Pallas kernel runs once at a base128 shape
against its jnp twin; a mismatch fails the run.

stdout is JSON lines, one per phase (free-form: versions, device kind,
compile seconds vs steady seconds, peak bytes, compile-cache hits, kernel
evidence). The LAST line is the contract's:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Exit code 0 only with that line. No accelerator → exit 3 and no result.
Trailing key=value config overrides make a REHEARSAL (tiny sizes on the
CPU, tests/test_chip_smoke.py): the phases run, and the script still
exits non-zero without "ok": true — only the unmodified preset on
platform 'tpu' passes. These are smoke timings, not a benchmark.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import glob
import importlib.metadata
import io
import json
import re
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import jaxlib
import numpy as np

from novel_view_synthesis_3d_tpu import cli
from novel_view_synthesis_3d_tpu.config import get_preset
from novel_view_synthesis_3d_tpu.parallel import dist
from novel_view_synthesis_3d_tpu.utils.xla_cache import (
    setup_compilation_cache)

EXIT_NOT_THE_CHIP = 4  # every phase ran, but this was a rehearsal

# Stated tolerances of the on-chip kernel-vs-twin checks. The fused step is
# f32 elementwise math in the twin's operation order; GroupNorm/epilogue/
# attention compare a bf16 (or f32-accumulated) kernel against XLA's own
# fusion of the same math, so a few bf16 ulps (2^-7 relative) of slack.
TOL_F32 = dict(rtol=1e-5, atol=1e-5)
TOL_BF16 = dict(rtol=2e-2, atol=2e-2)
# --chips 4: same program, same seed and batch, two layouts. The first
# loss is mean(noise²) (the output head is zero-initialised) and must
# agree almost exactly; grad_norm and the second loss run the whole
# bf16 network under a different reduction order.
TOL_DP_LOSS = 1e-3
TOL_DP_GRAD = 2e-2


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring): per-phase compile seconds, backend
# compiles, persistent-cache hits
# ---------------------------------------------------------------------------
class CompileMeter:
    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _REQ = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        self._totals = {"compile_s": 0.0, "backend_compiles": 0,
                        "compile_cache_hits": 0,
                        "compile_cache_requests": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event: str, secs: float, **_) -> None:
        if event in self._DURATIONS:
            self._totals["compile_s"] += secs
        if event == self._BACKEND:
            self._totals["backend_compiles"] += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self._HIT:
            self._totals["compile_cache_hits"] += 1
        elif event == self._REQ:
            self._totals["compile_cache_requests"] += 1

    def snapshot(self) -> dict:
        return dict(self._totals)


class Tee(io.TextIOBase):
    """stdout that also keeps what was written (the CLI verbs report
    through print; the smoke reads their result lines back)."""

    def __init__(self, stream):
        self._stream = stream
        self._buf = io.StringIO()

    def write(self, s: str) -> int:
        self._buf.write(s)
        return self._stream.write(s)

    def flush(self) -> None:
        self._stream.flush()

    def getvalue(self) -> str:
        return self._buf.getvalue()


def run_cli(meter: CompileMeter, phase: str, argv: list) -> tuple:
    """One CLI verb in-process → (its stdout, the phase's timing dict).
    A non-zero return code or an exception fails the whole script."""
    before = meter.snapshot()
    tee = Tee(sys.stdout)
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(tee):
        rc = cli.main(argv)
    # The verbs end on host fetches of their results; this closes any
    # dispatch still in flight before the clock stops.
    jax.effects_barrier()
    wall = time.perf_counter() - t0
    if rc != 0:
        raise SystemExit(f"chip_smoke: `{' '.join(argv[:1])}` returned {rc}")
    spent = {k: v - before[k] for k, v in meter.snapshot().items()}
    timing = {
        "phase": phase,
        "wall_s": round(wall, 3),
        **spent,
        "compile_s": round(spent["compile_s"], 3),
        "steady_s": round(wall - spent["compile_s"], 3),
        **memory_line(),
    }
    # Drop the phase's buffers and programs (the verb's objects are
    # garbage now): the next verb is a separate process in real use and
    # must find the chip's memory free. bytes_in_use of the next line
    # shows whether it did.
    gc.collect()
    return tee.getvalue(), timing


def memory_line() -> dict:
    """Device 0's allocator counters (peaks are cumulative per process)."""
    stats = jax.devices()[0].memory_stats() or {}
    return {k: int(stats[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
             "peak_bytes_reserved", "largest_alloc_size", "bytes_limit")
            if k in stats}


# ---------------------------------------------------------------------------
# kernels vs their jnp twins (set-up, untimed)
# ---------------------------------------------------------------------------
def check_kernels(seed: int) -> dict:
    """Each Pallas kernel once at a base128 shape against its jnp twin.
    On 'tpu' the kernels are compiled (ops/_pallas.use_interpret); on the
    CPU rehearsal the same code runs through the interpreter."""
    import flax.linen as nn

    from novel_view_synthesis_3d_tpu.config import DiffusionConfig
    from novel_view_synthesis_3d_tpu.ops.flash_attention import (
        flash_attention)
    from novel_view_synthesis_3d_tpu.ops.fused_step import (
        fused_denoise_step, unfused_reference_step)
    from novel_view_synthesis_3d_tpu.sample.stepper import ScheduleBank

    rng = np.random.default_rng(seed)
    report = {}

    def close(name, got, want, tol):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if not np.isfinite(got).all():
            raise SystemExit(f"chip_smoke: kernel {name} is not finite")
        np.testing.assert_allclose(got, want, err_msg=name, **tol)
        report[name] = float(np.max(np.abs(got - want)))

    def normal(shape, dtype=jnp.float32):
        return jnp.asarray(rng.normal(size=shape), dtype)

    # Fused denoise step: ring bucket 4 at 128 px, both samplers.
    bank = ScheduleBank(DiffusionConfig(timesteps=64, sample_timesteps=64))
    B, px = 4, 128
    coefs = jnp.asarray(bank.get(64).table[rng.integers(1, 64, size=B)])
    w = jnp.asarray(rng.uniform(0.0, 8.0, size=B), jnp.float32)
    z, ec, eu, nz = (normal((B, px, px, 3)) for _ in range(4))
    for sampler, eta in (("ddpm", 0.0), ("ddim", 0.5)):
        kw = dict(sampler=sampler, objective="eps", eta=eta)
        close(f"fused_step_{sampler}",
              jax.jit(lambda *a: fused_denoise_step(*a, **kw))(
                  z, ec, eu, nz, coefs, w),
              jax.jit(lambda *a: unfused_reference_step(*a, **kw))(
                  z, ec, eu, nz, coefs, w),
              TOL_F32)

    # Attention at base128's two attention levels: 32² tokens at head
    # dim 64, 16² tokens at head dim 128 (4 heads) — flash forward and
    # backward against XLA.
    for name, L, hd in (("L1024_d64", 1024, 64), ("L256_d128", 256, 128)):
        q, k, v = (normal((4, L, 4, hd), jnp.bfloat16) for _ in range(3))
        want = jax.jit(nn.dot_product_attention)(q, k, v)
        close(f"flash_fwd_{name}", jax.jit(flash_attention)(q, k, v),
              want, TOL_BF16)
        cot = normal(want.shape, jnp.bfloat16)

        def grads(attn):
            return jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(
                    attn(q, k, v).astype(jnp.float32) * cot),
                argnums=(0, 1, 2)))(q, k, v)

        for gname, got, ref in zip(
                "qkv", grads(flash_attention),
                grads(nn.dot_product_attention)):
            close(f"flash_bwd_d{gname}_{name}", got, ref, TOL_BF16)
    return report


# ---------------------------------------------------------------------------
# evidence that the kernels are in the programs that run
# ---------------------------------------------------------------------------
def custom_call_counts(cfg) -> dict:
    """`tpu_custom_call` occurrences in the lowered train step and ring
    step, built by the factories the trainer (train/step.make_train_step)
    and the service (sample/ddpm.make_ring_step_fn) use. Traced on
    shapes only: nothing is compiled or run."""
    from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
    from novel_view_synthesis_3d_tpu.diffusion import make_schedule
    from novel_view_synthesis_3d_tpu.models import build_denoiser
    from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib
    from novel_view_synthesis_3d_tpu.sample.ddpm import (
        STEP_COEF_KEYS, make_ring_step_fn)
    from novel_view_synthesis_3d_tpu.train.state import create_train_state
    from novel_view_synthesis_3d_tpu.train.step import make_train_step
    from novel_view_synthesis_3d_tpu.train.trainer import _sample_model_batch

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    model = build_denoiser(cfg.model)
    side = cfg.data.img_sidelength
    batch = make_example_batch(batch_size=cfg.train.batch_size,
                               sidelength=side)
    state = jax.eval_shape(
        lambda: create_train_state(cfg.train, model,
                                   _sample_model_batch(batch)))
    mesh = mesh_lib.make_mesh(cfg.mesh, jax.devices()[:1])
    train_step = make_train_step(cfg, model, make_schedule(cfg.diffusion),
                                 mesh)
    train_text = train_step.lower(state, shapes(batch)).as_text()

    B = 2
    f32 = jnp.float32
    cond = {k: jax.ShapeDtypeStruct((B,) + np.shape(batch[k])[1:], f32)
            for k in ("x", "R1", "t1", "R2", "t2", "K")}
    ring_step = make_ring_step_fn(model, cfg.diffusion)
    ring_text = ring_step.lower(
        state.params,
        jax.ShapeDtypeStruct((B, side, side, 3), f32),
        jax.ShapeDtypeStruct((B, 2), jnp.uint32),
        jax.ShapeDtypeStruct((B,), jnp.bool_), cond,
        jax.ShapeDtypeStruct((B, len(STEP_COEF_KEYS)), f32),
        jax.ShapeDtypeStruct((B,), f32)).as_text()
    return {"train_step": train_text.count("tpu_custom_call"),
            "ring_step": ring_text.count("tpu_custom_call")}


# ---------------------------------------------------------------------------
# the three phases
# ---------------------------------------------------------------------------
def phase_train(meter, root, work, preset, seed, overrides, steps=5):
    out, timing = run_cli(meter, "train", [
        "train", root, "--preset", preset,
        f"train.num_steps={steps}", "train.log_every=1",
        f"train.seed={seed}", f"data.shuffle_seed={seed}",
        f"train.checkpoint_dir={work}/ckpt",
        f"train.results_folder={work}/train"] + overrides)
    losses = [float(m) for m in re.findall(
        r"^\d+: loss=(\S+) imgs/s/chip=", out, re.M)]
    if len(losses) != steps or not np.isfinite(losses).all():
        raise SystemExit(f"chip_smoke: want {steps} finite losses, "
                         f"got {losses}")
    # One checkpoint saved, and verified by the manager's own restore
    # (Orbax errors and non-finite leaves both reject a step); the
    # sample and serve phases then restore it through the CLI.
    from novel_view_synthesis_3d_tpu.train.checkpoint import (
        CheckpointManager)

    ckpt = CheckpointManager(f"{work}/ckpt")
    saved = ckpt.all_steps()
    ckpt.close()
    if saved != [steps]:
        raise SystemExit(f"chip_smoke: want one checkpoint at step "
                         f"{steps}, found {saved}")
    from novel_view_synthesis_3d_tpu.data import native_io

    emit(dict(timing, losses=losses, checkpoint_steps=saved,
              native_io=native_io.available()))


def phase_sample(meter, root, work, preset, seed, overrides, side):
    views, steps = 2, 8
    out, timing = run_cli(meter, "sample", [
        "sample", root, "--preset", preset, "--out", f"{work}/sample",
        "--num-views", str(views), "--sample-steps", str(steps),
        "--seed", str(seed), f"train.checkpoint_dir={work}/ckpt"]
        + overrides)
    # `nvs3d sample` itself refuses non-finite pixels; its closing line
    # carries the float range of what it wrote.
    m = re.search(r"wrote (\d+) views .*pixel range \[(\S+), (\S+)\]", out)
    if not m or int(m.group(1)) != views:
        raise SystemExit("chip_smoke: sample did not report its views")
    lo, hi = float(m.group(2)), float(m.group(3))
    if not (-1.0 <= lo <= hi <= 1.0):
        raise SystemExit(f"chip_smoke: sample range [{lo}, {hi}] leaves "
                         "[-1, 1]")
    check_pngs(glob.glob(f"{work}/sample/view_*.png"), views, side)
    guidance = get_preset(preset).diffusion.guidance_weight
    if guidance <= 0:
        raise SystemExit("chip_smoke: the preset samples without CFG")
    emit(dict(timing, views=views, sample_steps=steps,
              guidance_weight=guidance, pixel_range=[lo, hi]))


def phase_serve(meter, root, work, preset, seed, overrides, side):
    # Four requests, one ring: max_batch=4 closes the first bucket as soon
    # as all four are queued (the long flush timeout only guards a slow
    # submit loop), three leave after 4 steps and the fourth runs on
    # alone — exactly two bucket shapes, 4 and 1, each one more compile.
    reqs = f"{work}/requests.jsonl"
    with open(reqs, "w") as fh:
        for i, steps in enumerate((4, 4, 4, 8)):
            fh.write(json.dumps({"instance": i % 2, "cond_view": 0,
                                 "target_view": 1 + i, "seed": seed + i,
                                 "sample_steps": steps}) + "\n")
    out, timing = run_cli(meter, "serve", [
        "serve", root, "--preset", preset, "--out", f"{work}/serve",
        "--requests", reqs, "serve.max_batch=4",
        "serve.flush_timeout_ms=30000",
        f"train.checkpoint_dir={work}/ckpt"] + overrides)
    summary = json.loads(
        [ln for ln in out.splitlines() if ln.startswith("{")][-1])
    want = {"served": 4, "submitted": 4, "anomalies": 0,
            # Zero compilations after warm-up: one program per bucket
            # shape, each compiled exactly once, every other ring step a
            # cache hit.
            "programs_built": 2, "jit_cache_entries": 2}
    got = {k: summary.get(k) for k in want}
    if got != want:
        raise SystemExit(f"chip_smoke: serve summary {got}, want {want}")
    check_pngs(glob.glob(f"{work}/serve/request_*.png"), 4, side)
    emit(dict(timing, **got, cache_hits=summary.get("cache_hits"),
              fused_step=summary.get("fused_step")))


def check_pngs(paths, count, side):
    from PIL import Image

    if len(paths) != count:
        raise SystemExit(f"chip_smoke: want {count} images, got "
                         f"{sorted(paths)}")
    for p in paths:
        img = np.asarray(Image.open(p))
        if img.shape != (side, side, 3):
            raise SystemExit(f"chip_smoke: {p} has shape {img.shape}")


# ---------------------------------------------------------------------------
# --chips 4: one data-parallel step against the same step on one device
# ---------------------------------------------------------------------------
def check_data_parallel(preset, seed, overrides, n_chips=4) -> None:
    from novel_view_synthesis_3d_tpu.config import MeshConfig
    from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
    from novel_view_synthesis_3d_tpu.diffusion import make_schedule
    from novel_view_synthesis_3d_tpu.models import build_denoiser
    from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib
    from novel_view_synthesis_3d_tpu.train.state import create_train_state
    from novel_view_synthesis_3d_tpu.train.step import make_train_step
    from novel_view_synthesis_3d_tpu.train.trainer import _sample_model_batch

    if len(jax.devices()) != n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} on a host with "
                         f"{len(jax.devices())} device(s)")
    cfg = get_preset(preset).apply_cli(
        [f"train.seed={seed}"] + overrides).validate()
    schedule = make_schedule(cfg.diffusion)
    batch = make_example_batch(batch_size=cfg.train.batch_size,
                               sidelength=cfg.data.img_sidelength, seed=seed)

    def two_steps(data: int) -> dict:
        mesh = mesh_lib.make_mesh(MeshConfig(data=data))
        model = build_denoiser(cfg.model, mesh=mesh)  # as train/trainer.py builds it
        state = mesh_lib.replicate(mesh, create_train_state(
            cfg.train, model, _sample_model_batch(batch)))
        step = make_train_step(cfg, model, schedule, mesh)
        device_batch = mesh_lib.shard_batch(mesh, batch)
        shard_devices = sorted(
            s.device.id for s in device_batch["target"].addressable_shards)
        t0 = time.perf_counter()
        out = []
        for _ in range(2):
            state, m = step(state, device_batch)
            out.append({k: float(jax.device_get(m[k]))
                        for k in ("loss", "grad_norm")})
        return {"metrics": out, "shard_devices": shard_devices,
                "wall_s": round(time.perf_counter() - t0, 3),
                "bytes_in_use": [
                    int((d.memory_stats() or {}).get("bytes_in_use", 0))
                    for d in jax.devices()]}

    # One device first: its whole-batch step is the larger program, and
    # it should find device 0 empty.
    one = two_steps(1)
    gc.collect()
    dp = two_steps(n_chips)
    emit({"phase": "data_parallel", "dp": dp, "one_device": one,
          "tolerance": {"loss": TOL_DP_LOSS, "grad_norm": TOL_DP_GRAD}})
    if dp["shard_devices"] != sorted(d.id for d in jax.devices()):
        raise SystemExit("chip_smoke: batch shards sit on "
                         f"{dp['shard_devices']}, not on all devices")
    if jax.devices()[0].platform != "cpu" and \
            min(dp["bytes_in_use"]) < 2 ** 20:
        raise SystemExit("chip_smoke: a device holds no live bytes under "
                         f"data parallelism: {dp['bytes_in_use']}")
    for a, b in zip(dp["metrics"], one["metrics"]):
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=TOL_DP_LOSS)
        np.testing.assert_allclose(a["grad_norm"], b["grad_norm"],
                                   rtol=TOL_DP_GRAD)


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--preset", default="base128",
                    choices=("base128", "paper256"))
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = only the data-parallel step and its "
                         "one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("overrides", nargs="*", metavar="key=value",
                    help="config overrides: a rehearsal, never 'ok'")
    args = ap.parse_args(argv)
    rehearsal = bool(args.overrides)

    # Device-or-fail, in this process: exit 3 before anything is built
    # when the platform asked for does not answer; a CPU has nothing to
    # say about the full-width run.
    platform = dist.require_platform()
    if platform != "tpu" and not rehearsal:
        print(f"error: chip_smoke needs a TPU; {platform!r} answered",
              file=sys.stderr)
        return dist.EXIT_BACKEND_UNREACHABLE
    cache_dir = setup_compilation_cache()
    meter = CompileMeter()

    from novel_view_synthesis_3d_tpu.obs import devmon

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    emit({"phase": "environment", "device": device, "jax": jax.__version__,
          "jaxlib": jaxlib.__version__, "libtpu": libtpu,
          "compile_cache_dir": cache_dir,
          # Raises for an accelerator kind the peak table lacks.
          "peak_flops": devmon.device_peak_flops(),
          "peak_bytes_per_s": devmon.device_peak_bytes_per_s(),
          "preset": args.preset, "rehearsal": rehearsal, **memory_line()})

    if args.chips == 4:
        check_data_parallel(args.preset, args.seed, args.overrides)
    else:
        emit({"phase": "kernels_vs_twins", "max_abs_err":
              check_kernels(args.seed),
              "tolerance": {"f32": TOL_F32, "bf16": TOL_BF16}})
        cfg = get_preset(args.preset).apply_cli(args.overrides).validate()
        counts = custom_call_counts(cfg)
        emit({"phase": "kernel_evidence", "tpu_custom_call": counts})
        if platform == "tpu" and min(counts.values()) == 0:
            raise SystemExit("chip_smoke: no Pallas kernel in a program "
                             f"that runs on the chip: {counts}")
        side = cfg.data.img_sidelength
        from novel_view_synthesis_3d_tpu.data.synthetic import (
            write_synthetic_srn)

        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
            root = write_synthetic_srn(
                f"{work}/srn", num_instances=2, views_per_instance=8,
                image_size=side, seed=args.seed)
            common = (meter, root, work, args.preset, args.seed,
                      args.overrides)
            phase_train(*common)
            phase_sample(*common, side)
            phase_serve(*common, side)

    ok = platform == "tpu" and not rehearsal
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else EXIT_NOT_THE_CHIP


if __name__ == "__main__":
    sys.exit(main())

"""Compare samplers (ddpm / ddim / dpm++) on one trained checkpoint.

Evaluates each (sampler, step-count) pair on the SAME held-out views with
the SAME PRNG seed and reports PSNR/SSIM plus wall-clock sec/view, so the
"dpm++ at ~1/8 the steps matches many-step ancestral quality" claim is a
measured table instead of a citation. The reference repo has nothing like
this (its sampling.py displays images and computes nothing).

Usage:
  python tools/sampler_comparison.py DATA_ROOT OUT.json \
      [--preset tiny64] [--num-instances 8] [--views-per-instance 2] \
      [key=value config overrides ...]

The checkpoint is read from the preset's train.checkpoint_dir (override
with train.checkpoint_dir=...). The sweep is fixed: ddpm@256, ddpm@64,
ddim@64, ddim@32, dpm++@32, dpm++@16, dpm++@8 (clamped to
diffusion.timesteps when the training schedule is shorter).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SWEEP = [
    ("ddpm", 256),
    ("ddpm", 64),
    ("ddim", 64),
    ("ddim", 32),
    ("dpm++", 32),
    ("dpm++", 16),
    ("dpm++", 8),
]


def clamped_sweep(sweep, timesteps: int):
    """Clamp step counts to the training schedule and drop the duplicate
    (sampler, steps) pairs clamping creates, preserving order."""
    out = []
    for sampler, steps in sweep:
        pair = (sampler, min(steps, timesteps))
        if pair not in out:
            out.append(pair)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("folder")
    ap.add_argument("out")
    ap.add_argument("--preset", default="tiny64")
    ap.add_argument("--config", default=None,
                    help="path to a resolved Config JSON (e.g. the "
                         "work/config.json a quality run writes); "
                         "takes precedence over --preset")
    ap.add_argument("--num-instances", type=int, default=8)
    ap.add_argument("--views-per-instance", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args, rest = ap.parse_known_args()
    overrides = [a for a in rest if "=" in a]
    bad = [a for a in rest if "=" not in a]
    if bad:
        ap.error(f"unrecognized arguments: {bad}")

    from _common import init_jax_env
    init_jax_env()
    import jax
    import numpy as np

    from novel_view_synthesis_3d_tpu.config import Config, get_preset
    from novel_view_synthesis_3d_tpu.data.srn import SRNDataset
    from novel_view_synthesis_3d_tpu.eval.evaluate import evaluate_dataset
    from novel_view_synthesis_3d_tpu.models import build_denoiser
    from novel_view_synthesis_3d_tpu.train.checkpoint import CheckpointManager
    from novel_view_synthesis_3d_tpu.train.state import create_train_state
    from novel_view_synthesis_3d_tpu.train.trainer import _sample_model_batch

    if args.config:
        cfg = Config.from_json(open(args.config).read())
    else:
        cfg = get_preset(args.preset)
    if overrides:
        cfg = cfg.apply_cli(overrides)
    # The sweep passes explicit step counts; the preset's default
    # sample_timesteps (e.g. 1000) may exceed a short training schedule.
    cfg = dataclasses.replace(
        cfg, diffusion=dataclasses.replace(
            cfg.diffusion,
            sample_timesteps=min(cfg.diffusion.sample_timesteps,
                                 cfg.diffusion.timesteps)))
    cfg.validate()

    ds = SRNDataset(args.folder, img_sidelength=cfg.data.img_sidelength)
    model = build_denoiser(cfg.model)
    rec = ds.pair(0, np.random.default_rng(0))
    template = create_train_state(
        cfg.train, model, _sample_model_batch({k: v[None]
                                               for k, v in rec.items()}))
    ckpt = CheckpointManager(cfg.train.checkpoint_dir)
    step = ckpt.latest_step()
    if step is None:
        raise SystemExit(
            f"no checkpoint under {cfg.train.checkpoint_dir!r} — train first")
    state = ckpt.restore(template, step=step)
    ckpt.close()
    params = state.ema_params if getattr(state, "ema_params",
                                         None) is not None else state.params
    print(f"restored checkpoint at step {step}", flush=True)

    rows = []
    for sampler, steps in clamped_sweep(SWEEP, cfg.diffusion.timesteps):
        run_cfg = dataclasses.replace(
            cfg, diffusion=dataclasses.replace(cfg.diffusion, sampler=sampler))
        t0 = time.perf_counter()
        result = evaluate_dataset(
            run_cfg, model, params, ds,
            key=jax.random.PRNGKey(args.seed),
            num_instances=args.num_instances,
            views_per_instance=args.views_per_instance,
            sample_steps=steps,
        )
        wall = time.perf_counter() - t0
        row = {
            "sampler": sampler,
            "steps": steps,
            "psnr": round(result.psnr, 4),
            "ssim": round(result.ssim, 4),
            "num_views": result.num_views,
            # Includes this config's compile; relative timing only.
            "wall_sec_per_view": round(wall / result.num_views, 4),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)

    out = {
        "checkpoint_step": step,
        "preset": args.preset,
        "platform": jax.default_backend(),
        "timing_note": "wall_sec_per_view includes each config's jit "
                       "compile — compare rows relatively, not as "
                       "deployment latency (bench.py sample measures that)",
        "rows": rows,
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"wrote {args.out}", flush=True)
    # Single platform-tagged JSON line LAST (the bench watcher parses the
    # last {-line and refuses CPU-fallback output as TPU evidence). Value:
    # PSNR cost of the cheapest dpm++ config vs the most expensive ddpm.
    dpmpp = [r for r in rows if r["sampler"] == "dpm++"]
    print(json.dumps({
        "metric": "sampler_comparison_psnr_delta_fastest_dpmpp_vs_ddpm",
        "value": (round(dpmpp[-1]["psnr"] - rows[0]["psnr"], 4)
                  if dpmpp else None),
        "unit": "dB",
        "platform": jax.default_backend(),
    }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Real-geometry quality run: train on raytraced multi-view scenes, eval
held-out views, commit the evidence (VERDICT r1 item 5).

SRN ShapeNet cars (the external target, BASELINE.md) is not fetchable in
this environment (no network egress), so the run uses data/raytrace.py —
true 3-D scenes rendered through the framework's exact camera model, where
held-out-view PSNR/SSIM genuinely measures novel-view synthesis (the model
must map pose → appearance of a consistent scene, not recall a pattern).

Scope note: with a handful of training instances the model fits the scenes
it saw; the held-out VIEWS (1-in-3 split, data/prep.py) measure viewpoint
generalization — the same protocol as eval on seen-instance SRN splits.

Writes results/quality_r02/: eval_single.json, eval_autoregressive.json,
samples_*.png grids, eval.csv (the in-training probe curve), summary.json.

Usage: python tools/quality_run.py [out_dir] [steps] [size] [overrides...]
       (defaults: results/quality_r02 3000 32; honors JAX_PLATFORMS).
       Trailing key=value args are config overrides appended AFTER the
       built-in list (so they win), applied to the persisted config.json
       and every train/eval/sample invocation alike — e.g.
       `model.num_cond_frames=2` for the k=2 ablation.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> None:
    out_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        REPO, "results", "quality_r02")
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 3000
    size = int(sys.argv[3]) if len(sys.argv) > 3 else 32
    extra_overrides = sys.argv[4:]
    for ov in extra_overrides:  # fail fast, not 3h into a TPU run
        if "=" not in ov:
            raise SystemExit(f"override {ov!r} is not key=value")

    from _common import init_jax_env
    init_jax_env()
    import jax

    from novel_view_synthesis_3d_tpu.cli import main as cli
    from novel_view_synthesis_3d_tpu.data.prep import train_val_split
    from novel_view_synthesis_3d_tpu.data.raytrace import write_raytraced_srn

    # Under out_dir (not a tempdir) and retained after exit — see the note
    # at the end of main(). A stale workdir from a previous run is cleared.
    work = os.path.join(out_dir, "work")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work, exist_ok=True)
    # 50 views/instance — SRN-cars trainset density (the real benchmark
    # renders 50 views per car). The r4 CPU hedge at 24 views showed the
    # held-out curve pinned near the mean-image floor: with a 1-in-3 split
    # the pose-interpolation gaps were ~2x the real protocol's. Density is
    # a property of the DATASET generator, not a metric knob — held-out
    # views remain fully unseen.
    full = write_raytraced_srn(os.path.join(work, "full"), num_instances=6,
                               views_per_instance=50, image_size=size,
                               seed=7)
    # Dense-train / sparse-holdout: train on 2/3 of each scene's views,
    # evaluate on the unseen 1-in-3 slice (invert=True — the REFERENCE
    # split semantics train on the sparse third, data_util.py:75-98, which
    # r4's CPU hedges showed starves pose coverage: 8 train views per
    # 24-view instance pinned held-out PSNR at the mean-image floor).
    train_root = os.path.join(work, "train")
    val_root = os.path.join(work, "val")
    for inst in sorted(os.listdir(full)):
        train_val_split(os.path.join(full, inst),
                        os.path.join(train_root, inst),
                        os.path.join(val_root, inst), invert=True)

    # Model capacity scales with the run size: the CPU smoke stays tiny,
    # while the 64px TPU run (minutes of chip time at ~150 imgs/s) affords
    # a base-width net whose samples actually show novel-view synthesis.
    ch = 32 if size < 64 else 64
    # attn at size//2 — the BOTTLENECK of this 2-level UNet (levels run at
    # {size, size//2}). Round 2/3 postmortem: size//4 matched NO level, so
    # cross-frame attention never fired and the conditioning image could
    # not reach the target frame at all — the model trained as a
    # pose-memorizer and held-out eval sat at the mean-image floor while
    # the seen-pose probe hit 20 dB. Config.validate() now rejects such
    # configs outright.
    overrides = [
        f"model.ch={ch}", "model.ch_mult=[1,2]", f"model.emb_ch={2 * ch}",
        "model.num_res_blocks=2", f"model.attn_resolutions=[{size // 2}]",
        f"data.img_sidelength={size}",
        "train.batch_size=8", f"train.num_steps={steps}",
        f"train.save_every={max(steps // 4, 1)}", "train.log_every=50",
        f"train.eval_every={max(steps // 10, 1)}",
        f"train.eval_folder={val_root}",  # eval.csv = true held-out curve
        "train.eval_sample_steps=32",
        # Fused 10-step dispatch: ~10x fewer host->device round trips.
        # All cadences above are multiples of 10 for every steps value this tool is
        # invoked with (200 smoke, 8000..20000 quality; validate() rejects
        # misalignment loudly rather than silently skipping a probe).
        "train.steps_per_dispatch=10",
        f"train.sample_every={max(steps // 4, 1)}",
        "diffusion.sample_timesteps=64",
        f"train.checkpoint_dir={work}/ckpt",
        f"train.results_folder={out_dir}",
    ] + extra_overrides  # caller overrides win (applied last)
    os.makedirs(out_dir, exist_ok=True)
    # Persist the RESOLVED config next to the checkpoint so follow-up tools
    # (tools/sampler_comparison.py --config) reload exactly this model
    # shape instead of hand-mirroring the override list.
    from novel_view_synthesis_3d_tpu.config import get_preset
    preset = "tiny64"  # single source of truth: the SAME preset feeds the
    # persisted config.json AND the train invocation below, so the saved
    # shape cannot drift from the trained shape if cli defaults change.
    with open(os.path.join(work, "config.json"), "w") as fh:
        fh.write(get_preset(preset).apply_cli(overrides).to_json())
    print(f"training {steps} steps at {size}px on {train_root}", flush=True)
    rc = cli(["train", train_root, "--preset", preset] + overrides)
    if rc != 0:
        raise SystemExit(f"train failed with rc={rc}")

    results = {}
    for protocol in ("single", "autoregressive"):
        out_json = os.path.join(out_dir, f"eval_{protocol}.json")
        rc = cli(["eval", val_root, "--out", out_json,
                  "--protocol", protocol, "--views-per-instance", "4",
                  "--sample-steps", "64", "--batch-size", "6", "--fid",
                  "--dump-comparisons",
                  os.path.join(out_dir, f"comparisons_{protocol}.png")]
                 + overrides)
        if rc != 0:
            raise SystemExit(f"eval ({protocol}) failed with rc={rc}")
        results[protocol] = json.load(open(out_json))
        print(f"{protocol}: {results[protocol]}", flush=True)

    # A sample grid from held-out conditioning for the eye.
    rc = cli(["sample", val_root,
              "--out", os.path.join(out_dir, "samples_val"),
              "--num-views", "6", "--sample-steps", "64", "--gif"]
             + overrides)
    if rc != 0:
        raise SystemExit(f"sample failed with rc={rc}")

    summary = {
        "metric": "quality_heldout_psnr",
        "value": results["single"]["psnr"],
        "unit": "dB",
        "platform": jax.devices()[0].platform,
        "dataset": "raytraced spheres+plane (data/raytrace.py), "
                   "6 instances x 50 views, 1-in-3 held-out view split",
        "img_size": size, "train_steps": steps,
        "eval": results,
    }
    with open(os.path.join(out_dir, "summary.json"), "w") as fh:
        json.dump(summary, fh, indent=2)
    # The workdir (dataset splits + checkpoint) is RETAINED under out_dir
    # so follow-up tools can reuse the trained model — in particular
    # tools/sampler_comparison.py, which must run as a SEPARATE process
    # AFTER this one exits (a chip belongs to one process at a time: a
    # child spawned here could never initialize the TPU while this
    # process holds it).
    # Single JSON line LAST, with the platform tag.
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()

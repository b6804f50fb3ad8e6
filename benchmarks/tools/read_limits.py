"""Read the numbers a sampling cell's limits are set from, on the chip, at
the cell's own size, over many seeds in ONE process (the sampler compiles
once): for every seed one timed-path call and its comparison with the
reference (sampling_check.step_gaps), and for the control seeds the
reference in each of the configuration's `control_precisions` put in the
program's place at the same inputs.

    chiprun -- python benchmarks/tools/read_limits.py \
        --workload paper256.sample_scan --seeds 11,12,13 --control-seeds 11,12

`--all-steps` seeds also read the steps that a run does not judge (those
whose timestep the stated precision cannot represent). One JSON line per
seed goes to chiprun_out/limits_<cell>.jsonl; a summary is printed last.
This tool sets nothing: the limits are written by hand into the traffic
file, above the sound runs' largest and below the control's smallest.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def ints(text):
    return [int(s) for s in text.split(",") if s]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, required=True)
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--all-steps", type=ints, default=[])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import harness

    cell = harness.load_cell(args.workload)
    rehearse = None
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        rehearse = harness.read_json(HERE, "rehearse.json")
        cell["traffic"] = dict(cell["traffic"], **rehearse["traffic"].get(
            cell["traffic"]["kind"], {}))

    import jax
    import jax.numpy as jnp
    import numpy as np

    import sampling_check
    import synth_data
    import weights
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler
    from novel_view_synthesis_3d_tpu.utils.xla_cache import (
        setup_compilation_cache)

    if not rehearse and jax.devices()[0].platform != "tpu":
        print("read_limits: needs a TPU chip", file=sys.stderr)
        return 3
    setup_compilation_cache()
    tr, conf = cell["traffic"], cell["config"]
    views = int(tr["views_per_call"])
    cfg = harness.build_config(
        cell, {"diffusion.sample_timesteps": int(tr["steps"]),
               "diffusion.sampler": tr["sampler"],
               "diffusion.guidance_weight": float(tr["guidance_weight"])},
        rehearse)
    n, side = cfg.diffusion.sample_timesteps, cfg.data.img_sidelength
    ref = harness.load_module(os.path.join(HERE, conf["reference"]),
                              "xunet_ref")
    m = harness.model_sizes(cfg)
    T, w = cfg.diffusion.timesteps, cfg.diffusion.guidance_weight
    tab = ref.cosine_tables(T, n)
    lams = [float(ref.logsnr_cosine(tab["t_orig"][t], T))
            for t in range(n - 1, -1, -1)]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = os.path.join(out_dir, f"limits_{args.workload}.jsonl")

    model = sampler = None
    summary = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        if sampler is None:
            model, shapes, params = sampling_check.program_model(cfg, seed)
            sampler = make_sampler(model, sampling_schedule(cfg.diffusion, n),
                                   cfg.diffusion, trajectory_every=1)
        else:
            params = weights.make_weights(seed, shapes)
        rng = np.random.default_rng(seed)
        ci, v = int(rng.integers(8)), int(rng.integers(views))
        host = synth_data.cond_views(views * int(tr["cond_pool"]), side, seed)
        pick = ci % int(tr["cond_pool"])
        cond = {k: jnp.asarray(a[pick * views:(pick + 1) * views])
                for k, a in host.items()}
        key = jax.random.fold_in(weights.seed_key(seed), ci)
        final, traj = jax.block_until_ready(sampler(params, key, cond))
        sample = {"label": f"call{ci}.view{v}", "key": key, "row": v,
                  "final": np.asarray(final[v]),
                  "traj": np.asarray(traj[:, v]),
                  "cond": {k: np.asarray(a[v]) for k, a in cond.items()},
                  "draw_shape": (views, side, side, 3)}
        del final, traj
        steps = sampling_check.pick_steps(
            lams, conf["stated_precision"], float(tr["check"]["timestep_tol"]),
            int(tr["check"]["steps"]), rng)
        precs = tuple(conf["control_precisions"]) \
            if seed in args.control_seeds else ()
        rows = sampling_check.step_gaps(ref, params, m, tab, T, w, sample,
                                        steps, precs)
        size = sum(r["size"] for r in rows)
        line = {
            "seed": seed, "label": sample["label"], "steps": steps,
            "eps_rel_rms": sampling_check.pooled(rows, "program"),
            "per_step": {r["step"]: sampling_check.pooled([r], "program")
                         for r in rows},
            "uncompared_pixel_share": 1 - sum(r["pixels"] for r in rows)
            / size,
            "clipped_share_gap": abs(sum(r["clipped_prog"] for r in rows) - sum(
                r["clipped_ref"] for r in rows)) / size,
            "final_is_last_state": float(np.max(np.abs(
                sample["final"] - sample["traj"][-1]))),
            "control": {p: sampling_check.pooled(rows, p) for p in precs},
            "control_per_step": {p: {r["step"]: sampling_check.pooled([r], p)
                                     for r in rows} for p in precs}}
        if seed in args.all_steps:
            others = [i for i in range(n) if i not in steps]
            line["unjudged_per_step"] = {
                r["step"]: [sampling_check.pooled([r], "program"),
                            r["pixels"] / r["size"]]
                for r in sampling_check.step_gaps(
                    ref, params, m, tab, T, w, sample, others)}
        line["seconds"] = time.perf_counter() - t0
        summary.append(line)
        with open(log, "a") as fh:
            fh.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)
        del params

    sound = [s["eps_rel_rms"] for s in summary]
    print(f"sound eps_rel_rms over {len(sound)} seeds: min {min(sound):.6g} "
          f"max {max(sound):.6g}")
    for name in ("uncompared_pixel_share", "clipped_share_gap",
                 "final_is_last_state"):
        print(f"sound {name}: max {max(s[name] for s in summary):.6g}")
    for p in conf["control_precisions"]:
        c = [s["control"][p] for s in summary if p in s["control"]]
        if c:
            print(f"control {p} eps_rel_rms over {len(c)} seeds: "
                  f"min {min(c):.6g} max {max(c):.6g}; smallest control / "
                  f"largest sound {min(c) / max(sound):.3g}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Read the numbers the limits of a `scan_tokens_gdn` cell are set from, on
the chip, at the cell's own size, over many seeds in ONE process (the
sampler compiles once) — tools/read_limits_tokens_ssm.py's twin for
token_check_gdn.py: for every seed one timed-path call and its comparison
with the reference, and for the control seeds the reference in each of the
configuration's `control_precisions` and under each of the three controls
of the delta rule (token_check_gdn.CONTROLS: every state zeroed at the
target frame's first token, β without its factor 2, the decay switched
off), each put in the program's place at the same inputs with the same
weights.

    chiprun -- python benchmarks/tools/read_limits_tokens_gdn.py \
        --workload oh7_denoiser256.sample_scan_gdn --seeds 11,12,13 \
        --control-seeds 11,12

`--controls a,b` runs only those of the controls. Every number goes
through harness.compare against the traffic file's limits, a control's
under the name of what it stands in for; the exit code is 0 only if every
sound run reads correct and every control incorrect. Per seed the median
head half-life of each delta-rule layer (the reference's own −g) is printed
and logged with its quantiles. One JSON line per seed goes to
chiprun_out/limits_<cell>.jsonl; a summary is printed last. This tool sets
nothing: the limits are written by hand into the traffic file, between
the sound runs' largest and the controls' smallest.
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
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--controls", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import harness

    cell = harness.load_cell(args.workload)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    import gdn_weights
    import synth_data
    import token_check_gdn as check
    import weights
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler
    from novel_view_synthesis_3d_tpu.utils.xla_cache import (
        setup_compilation_cache)

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("read_limits_tokens_gdn: needs a TPU chip", file=sys.stderr)
        return 3
    setup_compilation_cache()
    cfg, tr = cell["kind"].build(cell, {"rehearse": args.rehearse})
    conf, limits = cell["config"], tr["limits"]
    views = int(tr["views_per_call"])
    n, side = cfg.diffusion.sample_timesteps, cfg.data.img_sidelength
    ref, tables = check.load_refs(cell)
    m = check.model_sizes(cfg)
    T, w = cfg.diffusion.timesteps, cfg.diffusion.guidance_weight
    tab = tables.cosine_tables(T, n)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = os.path.join(out_dir, f"limits_{args.workload}.jsonl")
    model, shapes = check.program_shapes(cfg)
    wargs = check.weight_args(cell)
    controls = tuple(conf["control_precisions"]) + check.CONTROLS
    if args.controls:
        controls = tuple(c for c in controls
                         if c in args.controls.split(","))
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, n),
                           cfg.diffusion, trajectory_every=1)
    summary = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        params = gdn_weights.make_weights(seed, shapes, **wargs)
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
        del final, traj, params
        steps = check.pick(cell, tables, tab, T, n, seed)
        batch, mask, z_ins, noises = check.step_inputs(
            tables, tab, T, sample, steps)
        precs = controls if seed in args.control_seeds else ()
        got = check.reference_pass(ref, m, seed, shapes, batch, mask, precs,
                                   wargs)
        rows = check.step_rows(m, tab, w, sample, steps, z_ins, noises, got,
                               0.0)
        line = {"seed": seed, "label": sample["label"], "steps": steps,
                "final_is_last_state": float(np.max(np.abs(
                    sample["final"] - sample["traj"][-1]))),
                "per_step": {r["step"]: check.sampling_check.pooled(
                    [r], "program") for r in rows},
                "control": {p: check.sampling_check.pooled(rows, p)
                            for p in precs},
                "half_life": got["half_life"]}
        line.update(check.pooled_numbers(rows))
        print(f"-- seed {seed}", flush=True)
        numbers = []
        line["correct"] = all([
            harness.compare(k, line[k], limits.get(k, 0.0), numbers)
            for k in list(limits) + ["final_is_last_state"]])
        line["control_correct"] = {
            p: harness.compare(f"eps_rel_rms[reference: {p}]", c,
                               limits["eps_rel_rms"], numbers)
            for p, c in line["control"].items()}
        line["seconds"] = time.perf_counter() - t0
        summary.append(line)
        with open(log, "a") as fh:
            fh.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)

    if summary:
        e = [s["eps_rel_rms"] for s in summary]
        print(f"sound eps_rel_rms over {len(e)} seeds min {min(e):.6g} max "
              f"{max(e):.6g}")
    for p in controls:
        c = [s["control"][p] for s in summary if p in s["control"]]
        if c:
            print(f"control {p}: min {min(c):.6g} max {max(c):.6g}; smallest "
                  f"control / largest sound "
                  f"{min(c) / max(s['eps_rel_rms'] for s in summary):.3g}")
    for i in sorted({i for s in summary for i in s["half_life"]}):
        q = [s["half_life"][i] for s in summary if i in s["half_life"]]
        print(f"delta-rule layer {i}: median head half-life over "
              f"{len(q)} seeds {min(x[2] for x in q):.4g}-"
              f"{max(x[2] for x in q):.4g} tokens; 5 % under "
              f"{min(x[4] for x in q):.4g}, 5 % over "
              f"{max(x[0] for x in q):.4g}")
    for name in ("uncompared_pixel_share", "clipped_share_gap",
                 "final_is_last_state", "seconds"):
        v = [s[name] for s in summary]
        print(f"{name}: min {min(v):.6g} max {max(v):.6g}")
    as_expected = all(s["correct"] and not any(s["control_correct"].values())
                      for s in summary)
    print("every sound run correct, every control incorrect: "
          f"{as_expected}")
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())

"""Read the numbers the limits of a `scan_tokens_headmix` cell are set from,
on the chip, at the cell's own size, over many seeds in ONE process (the
sampler compiles once) — tools/read_limits_tokens_scmoe.py's twin for
token_check_headmix.py: for every seed one timed-path call and its
comparison with the reference, and for the control seeds the reference in
each of the configuration's `control_precisions` AND with each of this
mechanism's four faults planted (lgs_ref.CONTROLS: the head gate left out,
the two rotary laws swapped between the layer kinds, the x 2.5 on the gates
left out, the window layers at the full layers' visibility), each put in
the program's place at the same inputs with the same weights.

    chiprun -- python benchmarks/tools/read_limits_tokens_headmix.py \
        --workload lgs_denoiser256.sample_scan_headmix --seeds 11,12,13 \
        --control-seeds 11,12 --margins 0,0.0025

`--margins` reads every number at several `check.router_margin` beside the
traffic file's own (0: nothing adopted, every flip shows; the reference
runs once a margin). `--independent-seeds` runs with `router_replicas` 1:
independent router columns, so tokens have 0 to 10 held choices (the cell
ties them: exactly five). `--wrong-choice-seeds` hands the reference a
choice that is wrong by construction (every chosen expert's id plus one):
no near tie can be adopted, so `excluded_token_share` reads the share of
tokens with a near tie anywhere — its control. `--fault-seeds` with
`--faults group,row` builds the sampler again with token_check.rows_lost
open: held_rows_lost's control. Every number goes through harness.compare
against the traffic file's limits, a control's under the name of what it
stands in for, and EVERY control is reported as it reads, also where it
passes the limit; the exit code is 0 only if every sound run reads correct,
every fault incorrect and the lower precision incorrect. Per seed the
program's own routing is logged: the held rows of an expert layer in a
step and their spread over the held experts. One JSON line per seed goes to
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


def floats(text):
    return [float(s) for s in text.split(",") if s]


def ints(text):
    return [int(s) for s in text.split(",") if s]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--independent-seeds", type=ints, default=[])
    ap.add_argument("--wrong-choice-seeds", type=ints, default=[])
    ap.add_argument("--fault-seeds", type=ints, default=[])
    ap.add_argument("--faults", default="group,row")
    ap.add_argument("--margins", type=floats, default=[])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import harness

    cell = harness.load_cell(args.workload)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    import synth_data
    import token_check_headmix as check
    import token_weights
    import weights
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler
    from novel_view_synthesis_3d_tpu.utils.xla_cache import (
        setup_compilation_cache)

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("read_limits_tokens_headmix: needs a TPU chip", file=sys.stderr)
        return 3
    setup_compilation_cache()
    cfg, tr = cell["kind"].build(cell, {"rehearse": args.rehearse})
    conf, limits, chk = cell["config"], tr["limits"], tr["check"]
    views = int(tr["views_per_call"])
    n, side = cfg.diffusion.sample_timesteps, cfg.data.img_sidelength
    ref, tables = check.load_refs(cell)
    m = check.model_sizes(cfg)
    T, w = cfg.diffusion.timesteps, cfg.diffusion.guidance_weight
    tab = tables.cosine_tables(T, n)
    own = float(chk["router_margin"])
    margins = sorted(set(args.margins) | {own})
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = os.path.join(out_dir, f"limits_{args.workload}.jsonl")
    model, shapes = check.program_shapes(cfg)
    controls = tuple(conf["control_precisions"]) + check.CONTROLS
    summary = []

    def read(phase, seed, sampler, experts):
        """One timed-path call of `seed`'s weights and its comparison."""
        t0 = time.perf_counter()
        r = 1 if phase == "independent" else check.replicas(cell)
        params = token_weights.make_weights(seed, shapes, router_replicas=r)
        rng = np.random.default_rng(seed)
        ci, v = int(rng.integers(8)), int(rng.integers(views))
        host = synth_data.cond_views(views * int(tr["cond_pool"]), side, seed)
        pick = ci % int(tr["cond_pool"])
        cond = {k: jnp.asarray(a[pick * views:(pick + 1) * views])
                for k, a in host.items()}
        key = jax.random.fold_in(weights.seed_key(seed), ci)
        t_call = time.perf_counter()
        final, traj = jax.block_until_ready(sampler(params, key, cond))
        call_s = time.perf_counter() - t_call
        sample = {"label": f"call{ci}.view{v}", "key": key, "row": v,
                  "final": np.asarray(final[v]),
                  "traj": np.asarray(traj[:, v]),
                  "cond": {k: np.asarray(a[v]) for k, a in cond.items()},
                  "draw_shape": (views, side, side, 3)}
        del final, traj
        steps = check.pick(cell, tables, tab, T, n, seed)
        batch, mask, z_ins, noises = check.step_inputs(
            tables, tab, T, sample, steps)
        counts = check.program_counts(model, params, batch, mask)
        choice = check.program_choices(model, params, batch, mask)
        if phase == "fault:choice":
            choice = (choice + 1) % m["num_experts"]
        del params
        line = {"phase": phase, "seed": seed, "label": sample["label"],
                "steps": steps, "call_s": call_s,
                "held_per_token": float(counts.sum() / (
                    counts.shape[0] * batch["z"].shape[0]
                    * (side // m["patch_size"]) ** 2)),
                "load_max_over_mean": float(np.mean(
                    counts.max(axis=1) * counts.shape[1]
                    / np.maximum(counts.sum(axis=1), 1))),
                "held_rows_by_layer": (counts.sum(axis=1)
                                       / len(steps)).tolist(),
                "experts_hit_by_layer": (counts > 0).sum(axis=1).tolist(),
                "final_is_last_state": float(np.max(np.abs(
                    sample["final"] - sample["traj"][-1]))),
                "by_margin": {}}
        for thr in margins:
            precs = controls if (
                phase == "sound" and seed in args.control_seeds
                and thr == own) else ()
            got = check.reference_pass(
                ref, m, seed, shapes, batch, mask, choice, thr, precs, r,
                experts if thr == own else None, 2 * views)
            rows = check.step_rows(m, tab, w, sample, steps, z_ins, noises,
                                   got, thr)
            entry = check.pooled_numbers(rows)
            entry["adopted"] = got["adopted"]
            entry["per_step"] = {r["step"]: check.sampling_check.pooled(
                [r], "program") for r in rows}
            if thr == own:
                entry["held_rows_lost"] = check.held_rows_lost(
                    got, float(chk["lost_row_ratio"]))
                entry["routed_miss_max"] = float(got["routed_miss"].max())
                entry["control"] = {
                    p: check.sampling_check.pooled(rows, p) for p in precs}
            line["by_margin"][str(thr)] = entry
        line.update(line["by_margin"][str(own)])
        print(f"-- {phase} seed {seed}", flush=True)
        numbers = []
        line["correct"] = all([
            harness.compare(k, line[k], limits.get(k, 0.0), numbers)
            for k in list(limits) + ["final_is_last_state"]])
        line["control_correct"] = {
            p: harness.compare(f"eps_rel_rms[reference with {p}]", c,
                               limits["eps_rel_rms"], numbers)
            for p, c in line["control"].items()}
        line["seconds"] = time.perf_counter() - t0
        summary.append(line)
        with open(log, "a") as fh:
            fh.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)

    def build():
        return (make_sampler(model, sampling_schedule(cfg.diffusion, n),
                             cfg.diffusion, trajectory_every=1),
                check.expert_layer(cfg))

    if args.seeds or args.wrong_choice_seeds or args.independent_seeds:
        sampler, experts = build()
        for seed in args.seeds:
            read("sound", seed, sampler, experts)
        for seed in args.independent_seeds:
            read("independent", seed, sampler, experts)
        for seed in args.wrong_choice_seeds:
            read("fault:choice", seed, sampler, experts)
    for which in [f for f in args.faults.split(",") if f] \
            if args.fault_seeds else []:
        with check.rows_lost(which):
            sampler, experts = build()
            for seed in args.fault_seeds:
                read("fault:" + which, seed, sampler, experts)

    sounds = [s for s in summary if s["phase"] == "sound"]
    for thr in margins if sounds else []:
        e = [s["by_margin"][str(thr)] for s in sounds]
        print(f"margin {thr:g}: sound eps_rel_rms over {len(e)} seeds min "
              f"{min(x['eps_rel_rms'] for x in e):.6g} max "
              f"{max(x['eps_rel_rms'] for x in e):.6g}; excluded tokens max "
              f"{max(x['excluded_token_share'] for x in e):.4g}; adopted "
              f"token-layers max {max(x['adopted'] for x in e):.4g}")
    for p in controls:
        c = [s["control"][p] for s in sounds if p in s["control"]]
        if c:
            print(f"control {p}: min {min(c):.6g} max {max(c):.6g}; smallest "
                  f"control / largest sound "
                  f"{min(c) / max(s['eps_rel_rms'] for s in sounds):.3g}; "
                  + ("FAILS the limit on every seed"
                     if min(c) > limits["eps_rel_rms"] else
                     "PASSES the limit on some seed"))
    for name in ("uncompared_pixel_share", "clipped_share_gap",
                 "held_rows_lost", "routed_miss_max", "final_is_last_state",
                 "held_per_token", "load_max_over_mean", "call_s",
                 "seconds"):
        for phase in sorted({s["phase"] for s in summary}):
            v = [s[name] for s in summary if s["phase"] == phase]
            print(f"{phase} {name}: min {min(v):.6g} max {max(v):.6g}")
    lower = tuple(conf["control_precisions"])
    as_expected = all(
        s["correct"] == (s["phase"] in ("sound", "independent"))
        and not any(s["control_correct"].get(p) for p in lower)
        for s in summary)
    print("every sound run correct, every fault and the lower precision "
          f"incorrect: {as_expected}; a planted control that passed the "
          "limit on some seed: " + (", ".join(sorted(
              {p for s in summary for p, ok in s["control_correct"].items()
               if ok})) or "none"))
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())

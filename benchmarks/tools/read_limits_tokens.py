"""Read the numbers a token-denoiser sampling cell's limits are set from,
on the chip, at the cell's own size, over many seeds in ONE process (the
sampler compiles once) — tools/read_limits.py's twin for kind
`scan_tokens`: for every seed one timed-path call and its comparison with
the reference (token_check), and for the control seeds the reference in
each of the configuration's `control_precisions` put in the program's
place at the same inputs with the same weights.

    chiprun -- python benchmarks/tools/read_limits_tokens.py \
        --workload ms4_denoiser128.sample_scan_tokens --seeds 11,12,13 \
        --control-seeds 11,12 --margins 0,0.02,0.05,0.1

`--margins` reads every number at several router-margin thresholds beside
the traffic file's own, to choose that threshold from.
`--independent-seeds` reads the same program on weights whose router has
128 independent columns (`assumed.router_replicas` 1: 0 to 4 held choices
a token, unequal gate weights). `--fault-seeds` with `--faults group,row`
builds the sampler again with token_check.rows_lost open: held_rows_lost's
control, planted in the timed path and in the expert layer run alone.
Every number goes through harness.compare against the traffic file's
limits, a control's under the name of what it stands in for; the exit code
is 0 only if every sound run reads correct and every control and fault
incorrect (an independent-columns run is reported, not judged: the limits
are the committed weights'). One JSON line per seed goes to chiprun_out/limits_<cell>.jsonl;
a summary is printed last. This tool sets nothing: the limits are written
by hand into the traffic file, above the sound runs' largest and below the
control's smallest.
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
    import token_check
    import token_weights
    import weights
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler
    from novel_view_synthesis_3d_tpu.utils.xla_cache import (
        setup_compilation_cache)

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("read_limits_tokens: needs a TPU chip", file=sys.stderr)
        return 3
    setup_compilation_cache()
    kind = cell["kind"]
    cfg, tr = kind.build(cell, {"rehearse": args.rehearse})
    conf, limits, check = cell["config"], tr["limits"], tr["check"]
    views = int(tr["views_per_call"])
    n, side = cfg.diffusion.sample_timesteps, cfg.data.img_sidelength
    ref, tables = token_check.load_refs(cell)
    m = token_check.model_sizes(cfg)
    T, w = cfg.diffusion.timesteps, cfg.diffusion.guidance_weight
    tab = tables.cosine_tables(T, n)
    own = float(check["router_margin"])
    margins = sorted(set(args.margins) | {own})
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    log = os.path.join(out_dir, f"limits_{args.workload}.jsonl")
    model, shapes = token_check.program_shapes(cfg)
    summary = []

    def read(phase, seed, sampler, experts, replicas):
        """One timed-path call of `seed`'s weights and its comparison."""
        t0 = time.perf_counter()
        params = token_weights.make_weights(seed, shapes,
                                            router_replicas=replicas)
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
        steps = token_check.pick(cell, tables, tab, T, n, seed)
        batch, mask, z_ins, noises = token_check.step_inputs(
            tables, tab, T, sample, steps)
        counts = token_check.program_counts(model, params, batch, mask)
        del params
        precs = tuple(conf["control_precisions"]) \
            if phase == "sound" and seed in args.control_seeds else ()
        got = token_check.reference_pass(ref, m, seed, shapes, batch, mask,
                                         precs, replicas, experts, 2 * views)
        safe = got["routed_miss"][got["layer_margin"] >= own]
        line = {"phase": phase, "seed": seed, "label": sample["label"],
                "steps": steps, "routed_miss_max": float(safe.max()),
                "load_max_over_mean": float(np.mean(
                    counts.max(axis=1) * counts.shape[1]
                    / np.maximum(counts.sum(axis=1), 1))),
                "final_is_last_state": float(np.max(np.abs(
                    sample["final"] - sample["traj"][-1]))),
                "by_margin": {}}
        for thr in margins:
            rows = token_check.step_rows(m, tab, w, sample, steps, z_ins,
                                         noises, got, thr)
            entry = token_check.pooled_numbers(rows)
            entry["held_rows_lost"] = token_check.held_rows_lost(
                got, thr, float(check["lost_row_ratio"]))
            entry["per_step"] = {r["step"]: token_check.sampling_check.pooled(
                [r], "program") for r in rows}
            entry["control"] = {p: token_check.sampling_check.pooled(rows, p)
                                for p in precs}
            line["by_margin"][str(thr)] = entry
        line.update(line["by_margin"][str(own)])
        print(f"-- {phase} seed {seed}", flush=True)
        numbers = []
        line["correct"] = all([harness.compare(k, line[k], limits.get(k, 0.0),
                                               numbers)
                               for k in list(limits) + ["final_is_last_state"]])
        line["control_correct"] = {
            p: harness.compare(f"eps_rel_rms[reference in {p}]", c,
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
                token_check.expert_layer(cfg))

    if args.seeds or args.independent_seeds:
        sampler, experts = build()
        for seed in args.seeds:
            read("sound", seed, sampler, experts, token_check.replicas(cell))
        for seed in args.independent_seeds:
            read("independent", seed, sampler, experts, 1)
    for which in [f for f in args.faults.split(",") if f] \
            if args.fault_seeds else []:
        with token_check.rows_lost(which):
            sampler, experts = build()
            for seed in args.fault_seeds:
                read("fault:" + which, seed, sampler, experts,
                     token_check.replicas(cell))

    sounds = [s for s in summary if s["phase"] in ("sound", "independent")]
    for thr in margins if sounds else []:
        key = str(thr)
        sound = [s["by_margin"][key]["eps_rel_rms"] for s in sounds]
        excl = [s["by_margin"][key]["excluded_token_share"] for s in sounds]
        text = (f"margin {thr:g}: sound eps_rel_rms over {len(sound)} seeds "
                f"min {min(sound):.6g} max {max(sound):.6g}; excluded tokens "
                f"max {max(excl):.4g}")
        for p in conf["control_precisions"]:
            c = [s["by_margin"][key]["control"][p] for s in sounds
                 if p in s["by_margin"][key]["control"]]
            if c:
                text += (f"; control {p} min {min(c):.6g} max {max(c):.6g}, "
                         f"smallest control / largest sound "
                         f"{min(c) / max(sound):.3g}")
        print(text)
    for name in ("uncompared_pixel_share", "clipped_share_gap",
                 "held_rows_lost", "routed_miss_max", "final_is_last_state",
                 "load_max_over_mean"):
        for phase in sorted({s["phase"] for s in summary}):
            v = [s[name] for s in summary if s["phase"] == phase]
            print(f"{phase} {name}: min {min(v):.6g} max {max(v):.6g}")
    for s in summary:
        if s["phase"] == "independent":
            print(f"independent seed {s['seed']}: correct {s['correct']} "
                  "(held to the committed weights' limits; reported only)")
    as_expected = all(
        s["correct"] == (s["phase"] == "sound")
        and not any(s["control_correct"].values())
        for s in summary if s["phase"] != "independent")
    print("every sound run correct, every control and fault incorrect: "
          f"{as_expected}")
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())

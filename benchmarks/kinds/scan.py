"""kind: scan — the program's `make_sampler` (one XLA program for the
whole reverse process of a call), called back to back for the window. The
sampler is built with the program's `trajectory_every=1`, so every call
also returns the latent after each reverse step: that is what `correct`
reads (sampling_check.py)."""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import harness
import sampling_check
import synth_data
import traffic_gen
import weights


def run(cell, seed, seconds, trace_on, env):
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler

    tr = cell["traffic"]
    views, steps = int(tr["views_per_call"]), int(tr["steps"])
    cfg = harness.build_config(
        cell, {"diffusion.sample_timesteps": steps,
               "diffusion.sampler": tr["sampler"],
               "diffusion.guidance_weight": float(tr["guidance_weight"])},
        env.get("rehearse"))
    steps = cfg.diffusion.sample_timesteps
    side = cfg.data.img_sidelength
    model, shapes, params = sampling_check.program_model(cfg, seed)
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, steps),
                           cfg.diffusion, trajectory_every=1)
    pool = int(tr["cond_pool"])
    host = synth_data.cond_views(views * pool, side, seed)
    conds = [{k: jnp.asarray(v[i * views:(i + 1) * views])
              for k, v in host.items()} for i in range(pool)]
    base = weights.seed_key(seed)

    def call(i):
        with jax.profiler.TraceAnnotation("scan_call_dispatch"):
            out = sampler(params, jax.random.fold_in(base, i),
                          conds[i % pool])
        with jax.profiler.TraceAnnotation("scan_call_wait"):
            jax.block_until_ready(out)
        return out

    harness.log("warming the sampler up")
    for i in range(int(tr["warm_calls"])):
        call(10 ** 6 + i)

    tracewin = harness.TraceWindow(
        trace_on, os.path.join(env["out_dir"], "trace"),
        float(tr["trace_seconds"]))
    env["compiles"].armed = True
    t0 = time.perf_counter()
    tracewin.start()
    done, outs, spans, i = [], [], [], 0
    while True:
        a = time.perf_counter()
        if a >= t0 + seconds:
            break
        out = call(i)
        b = time.perf_counter()
        tracewin.poll()
        if b <= t0 + seconds:
            done.append(b)
            outs.append((i, out))
        spans.append({"name": "scan_call", "ts": a, "dur": b - a, "end": b,
                      "attrs": {"call": i}})
        i += 1
    env["compiles"].armed = False
    tracewin.poll(force=True)
    rate = traffic_gen.first_to_last_rate(views * steps, done)
    harness.log(f"window: {len(done)} calls, {rate:.4f} view-steps/s")

    memory = harness.memory_peaks(1)
    # One finished call and one of its views, drawn from the seed.
    rng = np.random.default_rng(seed)
    ci, (final, traj) = outs[int(rng.integers(len(outs)))]
    v = int(rng.integers(views))
    sample = {"label": f"call{ci}.view{v}",
              "final": np.asarray(jax.device_get(final[v])),
              "traj": np.asarray(jax.device_get(traj[:, v])),
              "cond": {k: np.asarray(a[v])
                       for k, a in conds[ci % pool].items()},
              "key": jax.random.fold_in(base, ci),
              "draw_shape": (views, side, side, 3), "row": v}
    # The program's state is freed before the reference's is made.
    del outs, out, final, traj, params, conds, sampler, model
    numbers = []
    ok = sampling_check.judge_steps(cell, cfg, seed, shapes, sample, numbers)
    return {
        "end_to_end": {"view_steps_per_s": rate},
        "window": (t0, t0 + seconds), "spans": spans, "trace": tracewin,
        "owners": ("scan_call_wait", "scan_call_dispatch"),
        # Utilisation from the median call alone: a traced run stalls
        # between calls where the capture is written out.
        "counters": {"kind": "scan", "chips": 1, "units_per_s": views * steps
                     / float(np.median([s["dur"] for s in spans])),
                     "flops_mode": "denoise",
                     "sizes": harness.model_sizes(cfg),
                     "calls_in_window": len(done)},
        "attempted": len(done), "failed": 0,
        "numbers": numbers, "correct": ok, "memory": memory,
    }

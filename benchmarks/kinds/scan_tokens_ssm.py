"""kind: scan_tokens_ssm — kind `scan_tokens_kda` for a token denoiser whose
trunk has NO expert layer and whose layers hand state to later layers
(Phi-4-mini-flash's stack: Mamba layers with a recurrent state, differential
attention under a window and full, gated memory units and cross layers that
read what two layers publish): the program's `make_sampler` called back to
back for the window, one XLA program a call (the conditioning frame's
once-a-call pass through the layers that keep a cache entry, then every
step over the target's tokens through all of them, each Mamba layer's scan
entered with the cached state), built with `trajectory_every=1` so that
every call returns the latent after each reverse step, which `correct`
reads.

What differs from `scan_tokens_kda`, and why it is a file of its own:
`correct` is decided by token_check_ssm.py (a reference that carries
published state between its layer-at-a-time calls; no router, so no
routing counts or choices are asked of the program), the weights are
ssm_weights.py's (the Mamba decay's leaves as the public implementation
draws them), and the run carries both program counters of the earlier
kinds: the bytes a row keeps of the conditioning frame by kind of cache
entry (`cond_cache_bytes`) and the key columns the windowed kernel walks
over those its band lets through (`window_key_columns`). The window, the
timing and the result are `scan_tokens`'s line for line."""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import harness
import synth_data
import token_check_ssm as check
import traffic_gen
import weights


def build(cell, env):
    """(cfg, traffic) of this run; a rehearsal takes the traffic file's
    own tiny sizes."""
    tr = cell["traffic"]
    extra = {"diffusion.sample_timesteps": int(tr["steps"]),
             "diffusion.sampler": tr["sampler"],
             "diffusion.guidance_weight": float(tr["guidance_weight"])}
    rehearse = env.get("rehearse")
    if rehearse:
        tr = dict(tr, **tr["rehearse"]["traffic"])
        rehearse = {"overrides": tr["rehearse"]["overrides"]}
    return harness.build_config(cell, extra, rehearse), tr


def run(cell, seed, seconds, trace_on, env):
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler

    cfg, tr = build(cell, env)
    cell = dict(cell, traffic=tr)  # a rehearsal's own sizes and limits
    views, steps = int(tr["views_per_call"]), cfg.diffusion.sample_timesteps
    side = cfg.data.img_sidelength
    model, shapes, params = check.program_model(cfg, seed,
                                                check.weight_args(cell))
    cache_bytes = model.cond_cache_bytes(side)
    key_columns = model.window_key_columns(side)
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
    del outs, out, final, traj, conds, sampler
    _, tables = check.load_refs(cell)
    T = cfg.diffusion.timesteps
    tab = tables.cosine_tables(T, steps)
    sample["steps"] = check.pick(cell, tables, tab, T, steps, seed)
    (sample["batch"], sample["mask"], sample["z_ins"],
     sample["noises"]) = check.step_inputs(tables, tab, T, sample,
                                           sample["steps"])
    # The program's state is freed before the reference's is made.
    del params, model
    numbers = []
    ok = check.judge_steps(cell, cfg, seed, shapes, sample, numbers)
    return {
        "end_to_end": {"view_steps_per_s": rate},
        "window": (t0, t0 + seconds), "spans": spans, "trace": tracewin,
        "owners": ("scan_call_wait", "scan_call_dispatch"),
        # Utilisation from the median call alone: a traced run stalls
        # between calls where the capture is written out.
        "counters": {"kind": "scan", "chips": 1, "units_per_s": views * steps
                     / float(np.median([s["dur"] for s in spans])),
                     "views": views, "steps": steps,
                     "sizes": check.model_sizes(cfg),
                     "attn_key_columns": [int(c) for c in key_columns],
                     "cond_cache_bytes": {k: int(v) for k, v
                                          in cache_bytes.items()},
                     "calls_in_window": len(done)},
        "attempted": len(done), "failed": 0,
        "numbers": numbers, "correct": ok, "memory": memory,
    }

"""How a sampling cell's `correct` is decided: the states the timed sampler
wrote, step by step, against the plain reference.

The timed program returns the latent after every reverse step (the
program's own `trajectory_every=1`). For a sample of steps the reference is
handed the program's state BEFORE the step and computes the guided ε̂ in
float32; the program's ε̂ is read back from the state AFTER the step by
inverting the ancestral update (the noise of every step is redrawn from
the request key). No error of an earlier step reaches a later comparison,
and nothing is compared through the clip of x̂₀, which turns an error into
a count of flipped pixels.

Compared, each beside its limit:
  eps_rel_rms         pooled over the checked steps, on the pixels whose x̂₀
                      the reference leaves unclipped by a margin:
                      rms(ε̂_program − ε̂_reference) / rms(ε̂_reference)
  uncompared_pixel_share  the share of pixels left out of it (held
                      against a comparison of next to nothing)
  clipped_share_gap   |share of pixels the program clipped − share the
                      reference clips| over the same steps (held against a
                      sampler that does not clip)
  final_is_last_state the returned image against the last state (exact)

Steps are judged where the configuration's stated precision can represent
the step's timestep. The program embeds 1000·u(logsnr) in its compute type
(`timesteps.astype(bfloat16)`), and bfloat16 resolves only 2–4 units near
1000: where XLA performs that rounding (its CPU backend does) the
sinusoid's fast components turn by a radian or more at half the steps of a
16-step schedule, and the program is as far from float32 as an fp8 model.
On the chip XLA keeps the excess precision and those steps read like the
others (PERF.md, Findings), but that is the compiler's choice, not the
program's, so a run does not judge them (tools/read_limits.py reads them
with --all-steps).
"""

from __future__ import annotations

import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import harness
import weights

UNCLIPPED_BELOW = 0.9   # |x̂₀| of the reference under which a pixel is compared
# |x̂₀| of the program from which a pixel counts as clipped: its ±1 comes
# back through the inverted update, with that arithmetic's rounding.
CLIPPED_FROM = 0.999


def program_model(cfg, seed):
    """The program's XUNet and the benchmark's seeded weights for it (only
    the tree's shapes come from the program)."""
    from novel_view_synthesis_3d_tpu.models.xunet import XUNet

    model = XUNet(cfg.model)
    side = cfg.data.img_sidelength
    f32 = jnp.float32
    batch = {"x": jnp.zeros((1, side, side, 3), f32),
             "z": jnp.zeros((1, side, side, 3), f32),
             "logsnr": jnp.zeros((1,), f32),
             "R1": jnp.zeros((1, 3, 3), f32), "t1": jnp.zeros((1, 3), f32),
             "R2": jnp.zeros((1, 3, 3), f32), "t2": jnp.zeros((1, 3), f32),
             "K": jnp.zeros((1, 3, 3), f32)}
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, batch,
                           cond_mask=jnp.ones((1,)), train=False))["params"]
    return model, shapes, weights.make_weights(seed, shapes)


def chain_noise(key, steps, draw_shape, row):
    """Row `row` of z0 and of the per-step noises as the program's sampler
    draws them from one request key: split → (carry, k_init), z0 ~ N(0,1)
    from k_init; every step splits the carry again → (carry, k_step)."""
    key, k_init = jax.random.split(key)
    z0 = np.asarray(jax.random.normal(k_init, draw_shape)[row], np.float64)
    noises = []
    for _ in range(steps):
        key, k_step = jax.random.split(key)
        noises.append(np.asarray(jax.random.normal(k_step, draw_shape)[row],
                                 np.float64))
    return z0, noises


def timestep_1000(lam):
    """1000·u(logsnr), the argument of the model's sinusoidal embedding."""
    lam = min(max(float(lam), -20.0), 20.0)
    return 2000.0 * math.atan(math.exp(-lam / 2.0)) / math.pi


def representable(value, dtype, tol):
    """Whether `dtype` holds `value` to within `tol`."""
    return abs(float(jnp.asarray(value, jnp.float32).astype(dtype)) - value) \
        <= tol


def pick_steps(lams, stated, tol, count, rng):
    """Indices (in sampling order) of the steps to judge: those whose
    timestep the stated precision represents, the last always among them,
    at most `count`, the rest drawn by `rng`."""
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[stated]
    ok = [i for i, lam in enumerate(lams)
          if representable(timestep_1000(lam), dtype, tol)]
    last = len(lams) - 1
    if last not in ok:
        raise RuntimeError("the last step's timestep is not representable")
    rest = [i for i in ok if i != last]
    if len(rest) > count - 1:
        rest = sorted(int(i) for i in rng.choice(rest, size=count - 1,
                                                 replace=False))
    return rest + [last]


def step_gaps(ref, params, m, tab, T, w, sample, steps, precs=()):
    """The teacher-forced readings of one sampled view.

    `sample`: {"traj" (n, H, W, 3) the program's state after every step,
    "cond" (unbatched dict), "key", "draw_shape", "row"}. → one dict per
    step of `steps` with the sums that the pooled numbers are made of; for
    every precision in `precs` the reference in that precision stands in
    the program's place at the same inputs (the control)."""
    traj = np.asarray(sample["traj"], np.float64)
    n = traj.shape[0]
    z0, noises = chain_noise(sample["key"], n, sample["draw_shape"],
                             sample["row"])
    cond = {k: jnp.asarray(v)[None] for k, v in sample["cond"].items()}
    doubled = {k: jnp.concatenate([v, v], axis=0) for k, v in cond.items()}
    mask2 = jnp.asarray([1.0, 0.0])
    fwd = {p: ref.guided_eps_fn(m, w, p) for p in ("f32",) + tuple(precs)}
    rows = []
    for i in steps:
        t = n - 1 - i
        z_in = z0 if i == 0 else traj[i - 1]
        z32 = jnp.asarray(z_in, jnp.float32)
        batch = dict(doubled, z=jnp.stack([z32, z32]),
                     logsnr=jnp.full((2,), ref.logsnr_cosine(
                         tab["t_orig"][t], T)))
        eps = {p: np.asarray(f(params, batch, mask2), np.float64)
               for p, f in fwd.items()}
        a0, a1, c1, c2 = (float(tab[k][t]) for k in (
            "sqrt_recip", "sqrt_recipm1", "c1", "c2"))
        sigma = math.exp(0.5 * float(tab["log_var"][t])) if t > 0 else 0.0
        # The program's clipped x̂₀ and, where it is not clipped, its ε̂.
        x0_prog = (traj[i] - c2 * z_in - sigma * noises[i]) / c1
        x0_ref = a0 * z_in - a1 * eps["f32"]
        eps["program"] = (a0 * z_in - x0_prog) / a1
        keep = (np.abs(x0_ref) < UNCLIPPED_BELOW) \
            & (np.abs(x0_prog) < CLIPPED_FROM)
        row = {"step": i, "t": t, "pixels": int(keep.sum()),
               "size": keep.size,
               "ref_sq": float(np.sum(eps["f32"][keep] ** 2)),
               "clipped_prog": int((np.abs(x0_prog) >= CLIPPED_FROM).sum()),
               "clipped_ref": int((np.abs(x0_ref) >= 1.0).sum())}
        for p in ("program",) + tuple(precs):
            row["err_sq." + p] = float(np.sum(
                (eps[p] - eps["f32"])[keep] ** 2))
        rows.append(row)
    return rows


def pooled(rows, who):
    return math.sqrt(sum(r["err_sq." + who] for r in rows)
                     / max(sum(r["ref_sq"] for r in rows), 1e-300))


def judge_steps(cell, cfg, seed, shapes, sample, numbers):
    """One sampled view of one finished call against the reference, as the
    module's head says. `sample` also holds "final" (H, W, 3), the image
    the call returned."""
    ref = harness.load_module(os.path.join(
        cell["bench_dir"], cell["config"]["reference"]), "xunet_ref")
    check = cell["traffic"]["check"]
    limits = cell["traffic"]["limits"]
    m = harness.model_sizes(cfg)
    T, w = cfg.diffusion.timesteps, cfg.diffusion.guidance_weight
    n = sample["traj"].shape[0]
    tab = ref.cosine_tables(T, n)
    lams = [float(ref.logsnr_cosine(tab["t_orig"][t], T))
            for t in range(n - 1, -1, -1)]
    steps = pick_steps(lams, cell["config"]["stated_precision"],
                       float(check["timestep_tol"]), int(check["steps"]),
                       np.random.default_rng(seed))
    params = weights.make_weights(seed, shapes)
    t_ref = time.perf_counter()
    rows = step_gaps(ref, params, m, tab, T, w, sample, steps)
    harness.log(f"reference: {time.perf_counter() - t_ref:.1f} s for "
                f"{len(rows)} step(s) of {sample['label']}")
    for r in rows:
        print(f"compare-detail {sample['label']} step {r['step']}: eps gap "
              f"{pooled([r], 'program'):.6g} on {r['pixels']} of "
              f"{r['size']} pixels", flush=True)
    size = sum(r["size"] for r in rows)
    ok = harness.compare("eps_rel_rms", pooled(rows, "program"),
                         limits["eps_rel_rms"], numbers)
    ok &= harness.compare("uncompared_pixel_share",
                          1.0 - sum(r["pixels"] for r in rows) / size,
                          limits["uncompared_pixel_share"], numbers)
    ok &= harness.compare(
        "clipped_share_gap",
        abs(sum(r["clipped_prog"] for r in rows)
            - sum(r["clipped_ref"] for r in rows)) / size,
        limits["clipped_share_gap"], numbers)
    ok &= harness.compare(
        "final_is_last_state",
        float(np.max(np.abs(np.asarray(sample["final"], np.float64)
                            - np.asarray(sample["traj"][-1], np.float64)))),
        0.0, numbers)
    return ok

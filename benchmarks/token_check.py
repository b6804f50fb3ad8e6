"""How a token-denoiser sampling cell's `correct` is decided: the states
the timed sampler wrote against the plain reference's full forward.

As sampling_check.py does for the X-UNet: the timed program returns the
latent after every reverse step; for a sample of steps the reference
(reference/ms4_ref.py: both frames in ONE full forward under the frame
mask, float32, no cache, a dense loop over the held experts) is handed the
program's state BEFORE the step and computes the guided ε̂; the program's
ε̂ is read back from the state AFTER the step by inverting the ancestral
update. The program made that ε̂ from a latent cache computed once a call
and the target's tokens alone, through sorted assignments and a grouped
product — so prefill-then-decode through the cache is held to the full
forward.

One thing is particular to sparse experts. A top-k choice flips on
rounding where the k-th and (k+1)-th probabilities are close: a token whose
reference margin ln p_(k) − ln p_(k+1) is under `check.router_margin` in
any layer, in either guidance row, may take another expert in bfloat16
than in float32, and its ε̂ then differs by a whole expert's output, which
says nothing of the arithmetic. Such tokens' pixels are left out of
eps_rel_rms, and their share is itself compared.

Compared, each beside its limit:
  eps_rel_rms             pooled over the checked steps on the compared
                          pixels: rms(ε̂_program − ε̂_reference) / rms(ε̂_ref)
  excluded_token_share    the share of target tokens left out for a close
                          router margin
  uncompared_pixel_share  the share of pixels left out in all (those, and
                          the pixels whose x̂₀ is at the clip)
  clipped_share_gap       |pixels the program clipped − pixels the
                          reference clips| over the same steps
  held_rows_lost          target tokens, per layer, whose routed part from
                          the program's expert layer misses the
                          reference's by more than `check.lost_row_ratio`
                          of its norm (below): a count, limit 0
  final_is_last_state     the returned image against the last state

**What ε̂ cannot see.** A token's held experts add about a sixteenth of
what a layer adds, so one assignment that the grouped product or the
combine loses moves eps_rel_rms by less than bfloat16 does. The program's
expert layer (`token_denoiser.route` + `held_expert_part`: the sort, the
three grouped products, the combine — the functions a timed step traces)
is therefore also run ALONE, jitted at a timed step's shapes, on the
program's weights of each layer and the reference's float32 input to that
layer's experts, and its output is held to the reference's dense loop
token by token: a lost or misplaced row is that token's whole routed part.
This is a second program, not the timed one; a count taken before the
product (the program's `routing_counts`) could not fail and is a per-layer
metric only.

The reference runs a layer at a time (one float32 layer is 3.4 GB at the
cell's size) with the checked steps batched, after the window and after
the program's state is freed; each layer's weights are made again from the
seed (token_weights.make_group).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import harness
import sampling_check
import token_weights
from sampling_check import CLIPPED_FROM, UNCLIPPED_BELOW

SMALL_GROUPS = ("patch_in", "ray_in", "emb", "final_norm", "out")


def model_sizes(cfg) -> dict:
    """The sizes the reference and flops_tokens.py need, read off the
    program's config object (inputs, nothing computed)."""
    m = dataclasses.asdict(cfg.model.tokens)
    m["held_experts"] = list(m["held_experts"])
    m["side"] = cfg.data.img_sidelength
    return m


def replicas(cell) -> int:
    """The configuration's `assumed.router_replicas` (token_weights.py)."""
    return int(cell["config"]["assumed"].get("router_replicas", 1))


def program_shapes(cfg):
    """The program's denoiser and the shapes of its parameter tree."""
    from novel_view_synthesis_3d_tpu.models import build_denoiser

    model = build_denoiser(cfg.model)
    return model, jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}))["params"]


def program_model(cfg, seed, router_replicas=1):
    """The program's denoiser and the benchmark's seeded weights for it
    (only the tree's shapes come from the program)."""
    model, shapes = program_shapes(cfg)
    return model, shapes, token_weights.make_weights(
        seed, shapes, router_replicas=router_replicas)


def load_refs(cell):
    """(the model's reference, the module holding the schedule's tables)."""
    ref = harness.load_module(os.path.join(
        cell["bench_dir"], cell["config"]["reference"]), "ms4_ref")
    tables = harness.load_module(os.path.join(
        cell["bench_dir"], "reference", "xunet_ref.py"), "xunet_ref")
    return ref, tables


def step_inputs(tables, tab, T, sample, steps):
    """The doubled batch of the checked steps, rows [step₀ conditional,
    step₀ unconditional, step₁ …], and their mask; plus z_in and the
    per-step noises (float64) for the inversion."""
    traj = np.asarray(sample["traj"], np.float64)
    n = traj.shape[0]
    z0, noises = sampling_check.chain_noise(
        sample["key"], n, sample["draw_shape"], sample["row"])
    z_ins = [z0 if i == 0 else traj[i - 1] for i in steps]
    rows = 2 * len(steps)
    batch = {k: jnp.broadcast_to(jnp.asarray(v)[None],
                                 (rows,) + np.shape(v))
             for k, v in sample["cond"].items()}
    batch["z"] = jnp.asarray(np.repeat(np.stack(z_ins), 2, axis=0),
                             jnp.float32)
    batch["logsnr"] = jnp.asarray(np.repeat([float(tables.logsnr_cosine(
        tab["t_orig"][n - 1 - i], T)) for i in steps], 2), jnp.float32)
    mask = jnp.asarray([1.0, 0.0] * len(steps))
    return batch, mask, z_ins, noises


def expert_layer(cfg):
    """The program's expert layer alone: (a layer's parameters, normalised
    tokens (T, hidden) float32) → its routed part (T, hidden)."""
    from novel_view_synthesis_3d_tpu.models import token_denoiser as td

    k, dt = cfg.model.tokens, jnp.dtype(cfg.model.dtype)

    def run(p_layer, b32):
        top_p, top_i = td.route(b32, p_layer["router"], k)
        return td.held_expert_part(b32.astype(dt), top_p, top_i,
                                   p_layer["experts"], k)[0]

    return jax.jit(run)


@contextlib.contextmanager
def rows_lost(which):
    """held_rows_lost's control, for the tests and the limits tool: while
    open, the grouped product that models/token_denoiser.py traces zeroes,
    after the product, the rows of its fullest group ("group") or that
    group's last row ("row")."""
    from novel_view_synthesis_3d_tpu.models import token_denoiser as td

    real = td.grouped_matmul

    def faulty(lhs, rhs, group_sizes):
        g = jnp.argmax(group_sizes)
        end = jnp.cumsum(group_sizes)[g]
        start = end - group_sizes[g] if which == "group" else end - 1
        r = jnp.arange(lhs.shape[0])
        return jnp.where(((r >= start) & (r < end))[:, None], 0,
                         real(lhs, rhs, group_sizes))

    td.grouped_matmul = faulty
    try:
        yield
    finally:
        td.grouped_matmul = real


def routed_miss(experts, p_layer, b, want, rows_a_step):
    """|program's routed part − reference's| / |reference's| per target
    token, (rows, L); the program's layer is fed `rows_a_step` rows at a
    time, a timed step's batch. Where the reference's is 0 (no held
    choice), anything the program adds reads inf."""
    rows, L, H = b.shape
    got = jnp.concatenate([experts(
        p_layer, b[r:r + rows_a_step].reshape(-1, H)).astype(jnp.float32)
        for r in range(0, rows, rows_a_step)]).reshape(rows, L, H)
    err = jnp.linalg.norm(got - want, axis=-1)
    nrm = jnp.linalg.norm(want, axis=-1)
    return np.asarray(jnp.where(nrm > 0, err / jnp.maximum(nrm, 1e-30),
                                jnp.where(err > 0, jnp.inf, 0.0)))


def reference_pass(ref, m, seed, shapes, batch, mask, precs=(),
                   router_replicas=1, experts=None, rows_a_step=None):
    """The reference over the batch, a layer at a time. → {"eps": {prec:
    (rows, H, W, 3)}, "layer_margin": (layers, rows, L) the target tokens'
    router margins, and with `experts` (from `expert_layer`) "routed_miss":
    (layers, rows, L)}; the controls `precs` stand in at the same inputs
    with the same weights."""
    side = batch["z"].shape[1]
    small = token_weights.make_weights(seed, shapes, SMALL_GROUPS)
    allp = ("f32",) + tuple(precs)
    h = {p: ref.jitted("embed", m, p)(small, batch, mask) for p in allp}
    L = h["f32"].shape[1] // 2
    margins, miss = [], []
    for i in range(m["num_hidden_layers"]):
        p_layer = token_weights.make_group(seed, shapes, f"layer_{i}",
                                           router_replicas)
        for p in allp:
            if p == "f32":
                h[p], aux = ref.jitted("layer", m, p, "up_projected", None,
                                       True)(p_layer, h[p])
                margins.append(np.asarray(aux["margin"])[:, L:])
                if experts is not None:
                    miss.append(routed_miss(
                        experts, p_layer, aux["b"][:, L:],
                        aux["routed"][:, L:], rows_a_step))
                del aux
            else:
                h[p], _ = ref.jitted("layer", m, p)(p_layer, h[p])
        del p_layer
    eps = {p: np.asarray(ref.jitted("head", m, side, p)(small, h[p]),
                         np.float64) for p in allp}
    return {"eps": eps, "layer_margin": np.stack(margins),
            "routed_miss": np.stack(miss) if miss else None}


def held_rows_lost(got, threshold, ratio):
    """Target tokens, per layer, whose routed part the program's expert
    layer lost (tokens at a close margin in that layer left out)."""
    return int(np.sum((got["routed_miss"] > ratio)
                      & (got["layer_margin"] >= threshold)))


def step_rows(m, tab, w, sample, steps, z_ins, noises, got, threshold):
    """Per checked step, the sums the pooled numbers are made of."""
    traj = np.asarray(sample["traj"], np.float64)
    n = traj.shape[0]
    p, side = m["patch_size"], m["side"]
    rows = []
    for j, i in enumerate(steps):
        t = n - 1 - i
        eps = {k: (1.0 + w) * v[2 * j] - w * v[2 * j + 1]
               for k, v in got["eps"].items()}
        close = got["layer_margin"][:, 2 * j:2 * j + 2].min(axis=(0, 1)) \
            < threshold                                       # (L,)
        close_px = np.repeat(np.repeat(
            close.reshape(side // p, side // p), p, axis=0), p, axis=1)
        a0, a1, c1, c2 = (float(tab[k][t]) for k in (
            "sqrt_recip", "sqrt_recipm1", "c1", "c2"))
        sigma = math.exp(0.5 * float(tab["log_var"][t])) if t > 0 else 0.0
        z_in = z_ins[j]
        x0_prog = (traj[i] - c2 * z_in - sigma * noises[i]) / c1
        x0_ref = a0 * z_in - a1 * eps["f32"]
        eps["program"] = (a0 * z_in - x0_prog) / a1
        keep = (np.abs(x0_ref) < UNCLIPPED_BELOW) \
            & (np.abs(x0_prog) < CLIPPED_FROM) & ~close_px[..., None]
        row = {"step": i, "t": t, "pixels": int(keep.sum()),
               "size": keep.size, "tokens": close.size,
               "close_tokens": int(close.sum()),
               "ref_sq": float(np.sum(eps["f32"][keep] ** 2)),
               "clipped_prog": int((np.abs(x0_prog) >= CLIPPED_FROM).sum()),
               "clipped_ref": int((np.abs(x0_ref) >= 1.0).sum())}
        for k in eps:
            if k != "f32":
                row["err_sq." + k] = float(np.sum(
                    (eps[k] - eps["f32"])[keep] ** 2))
        rows.append(row)
    return rows


def pooled_numbers(rows, who="program"):
    size = sum(r["size"] for r in rows)
    return {
        "eps_rel_rms": sampling_check.pooled(rows, who),
        "excluded_token_share": sum(r["close_tokens"] for r in rows)
        / sum(r["tokens"] for r in rows),
        "uncompared_pixel_share": 1.0 - sum(r["pixels"] for r in rows) / size,
        "clipped_share_gap": abs(sum(r["clipped_prog"] for r in rows)
                                 - sum(r["clipped_ref"] for r in rows)) / size,
    }


def pick(cell, tables, tab, T, n, seed):
    check = cell["traffic"]["check"]
    lams = [float(tables.logsnr_cosine(tab["t_orig"][t], T))
            for t in range(n - 1, -1, -1)]
    # The token denoiser embeds its timestep in float32: every step's is
    # representable, so all are candidates.
    return sampling_check.pick_steps(lams, "float32",
                                     float(check["timestep_tol"]),
                                     int(check["steps"]),
                                     np.random.default_rng(seed))


def program_counts(model, params, batch, mask):
    """(layers, held) tokens per held expert that the PROGRAM routes in
    its pass over the target's tokens of the checked steps (its own pure
    function; called while its weights are still on the device). Counted
    before the product: `moe_load_max_over_mean` reads it, `correct` does
    not."""
    return np.asarray(jax.jit(model.routing_counts)(params, batch, mask))


def judge_steps(cell, cfg, seed, shapes, sample, numbers):
    """One sampled view of one finished call against the reference.
    `sample` as sampling_check.judge_steps takes it, with "steps", "batch",
    "mask", "z_ins", "noises" from `step_inputs`."""
    ref, tables = load_refs(cell)
    limits, check = cell["traffic"]["limits"], cell["traffic"]["check"]
    m = model_sizes(cfg)
    T, w = cfg.diffusion.timesteps, cfg.diffusion.guidance_weight
    tab = tables.cosine_tables(T, sample["traj"].shape[0])
    t_ref = time.perf_counter()
    got = reference_pass(ref, m, seed, shapes, sample["batch"],
                         sample["mask"], router_replicas=replicas(cell),
                         experts=expert_layer(cfg),
                         rows_a_step=2 * sample["draw_shape"][0])
    rows = step_rows(m, tab, w, sample, sample["steps"], sample["z_ins"],
                     sample["noises"], got, float(check["router_margin"]))
    harness.log(f"reference: {time.perf_counter() - t_ref:.1f} s for "
                f"{len(rows)} step(s) of {sample['label']}")
    for r in rows:
        print(f"compare-detail {sample['label']} step {r['step']}: eps gap "
              f"{sampling_check.pooled([r], 'program'):.6g} on "
              f"{r['pixels']} of {r['size']} pixels, {r['close_tokens']} of "
              f"{r['tokens']} tokens at a close margin", flush=True)
    ok = True
    for name, value in pooled_numbers(rows).items():
        ok &= harness.compare(name, value, limits[name], numbers)
    miss = got["routed_miss"][got["layer_margin"]
                              >= float(check["router_margin"])]
    print(f"compare-detail expert layer alone: routed part off the "
          f"reference's by median {np.median(miss):.3g}, at most "
          f"{miss.max():.3g} of its norm over {miss.size} token-layers",
          flush=True)
    ok &= harness.compare(
        "held_rows_lost", held_rows_lost(
            got, float(check["router_margin"]),
            float(check["lost_row_ratio"])),
        limits["held_rows_lost"], numbers)
    ok &= harness.compare(
        "final_is_last_state",
        float(np.max(np.abs(np.asarray(sample["final"], np.float64)
                            - np.asarray(sample["traj"][-1], np.float64)))),
        0.0, numbers)
    return ok

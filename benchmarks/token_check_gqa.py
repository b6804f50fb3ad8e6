"""How `correct` is decided for a token-denoiser cell whose trunk routes
BEFORE attention over independent router columns (SmallThinker's layer,
reference/st21_ref.py): token_check.py's comparison — the states the timed
sampler wrote against the plain reference's full forward, ε̂ read back by
inverting the update, pooled over the checked steps — with two things
done differently, and everything else (`step_inputs`, `step_rows`,
`pooled_numbers`, `pick`, the planted fault `rows_lost`) token_check's own
functions.

**Near ties are adopted, not left out.** With 64 independent columns, top-6
and 12 layers × 2 guidance rows, a token has a sixth and seventh logit
within 0.01 of each other somewhere in nine cases of ten: leaving those
tokens out (token_check.py) would leave next to nothing to compare. The
program therefore reports the experts it chose for every token of the
checked steps (`routing_choices`, its own pure function, run while its
weights are on the device), and the reference takes the program's set
where its own margin is under `check.router_margin` AND every chosen
expert lies, by the reference's own logits, within that margin of the
reference's sixth (st21_ref.router). A choice outside is not adopted: the
token is left out and counted (`excluded_token_share`, limit in the
traffic file), so a router that picks wrongly shows there; a token at a
clear margin is always held to the reference's own choice.

**The expert layer alone takes the reference's gates and choice.** The
program's `held_expert_part` (sort, three grouped products, combine — the
function a timed step traces) is jitted alone at a timed step's shapes and
fed, per layer, the program's weights, the reference's float32 input to
that layer's experts and the reference's (gates, chosen experts): every
target token's routed part is then held to the reference's dense loop,
with no margin to leave out — six live choices a token at unequal gates.

Compared, each beside its limit: eps_rel_rms, excluded_token_share,
uncompared_pixel_share, clipped_share_gap, held_rows_lost,
final_is_last_state (token_check.py's head says what each is).
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

import harness
import sampling_check
import token_weights
from token_check import (  # noqa: F401 — the kind and the tools take them here
    SMALL_GROUPS, pick, pooled_numbers, program_counts, program_model,
    program_shapes, replicas, rows_lost, step_inputs, step_rows)


def model_sizes(cfg) -> dict:
    """The trunk's sizes under the source's key names (the reference's and
    flops_tokens_gqa.py's), plus the names the expert layer's readers know
    them by (moe_experts_roofline, flops_tokens.py), which the program's
    config gives as properties."""
    k = cfg.model.tokens
    m = {name: list(v) if isinstance(v, tuple) else v
         for name, v in dataclasses.asdict(k).items()}
    m["side"] = cfg.data.img_sidelength
    m.update(n_routed_experts=k.n_routed_experts,
             num_experts_per_tok=k.num_experts_per_tok,
             moe_intermediate_size=k.moe_ffn_hidden_size)
    return m


def load_refs(cell):
    """(the model's reference, the module holding the schedule's tables)."""
    ref = harness.load_module(os.path.join(
        cell["bench_dir"], cell["config"]["reference"]), "st21_ref")
    tables = harness.load_module(os.path.join(
        cell["bench_dir"], "reference", "xunet_ref.py"), "xunet_ref")
    return ref, tables


def program_choices(model, params, batch, mask):
    """(layers, rows, 2L, k) the experts the PROGRAM sends each token of
    both frames to on the checked steps' inputs (its own pure function;
    called while its weights are still on the device)."""
    return np.asarray(jax.jit(model.routing_choices)(params, batch, mask))


def expert_layer(cfg):
    """The program's expert layer alone: (a layer's parameters, normalised
    tokens (T, hidden) float32, gates (T, k), chosen experts (T, k)) →
    its routed part (T, hidden)."""
    from novel_view_synthesis_3d_tpu.models import token_denoiser as td

    k, dt = cfg.model.tokens, jnp.dtype(cfg.model.dtype)

    def run(p_layer, b32, gates, chosen):
        return td.held_expert_part(b32.astype(dt), gates, chosen,
                                   p_layer["experts"], k)[0]

    return jax.jit(run)


def routed_miss(experts, p_layer, aux, L, rows_a_step):
    """|program's routed part − reference's| / |reference's| per target
    token, (rows, L); the program's layer is fed `rows_a_step` rows at a
    time, a timed step's batch."""
    b, want = aux["b"][:, L:], aux["routed"][:, L:]
    gates, chosen = aux["gates"][:, L:], aux["chosen"][:, L:]
    rows, _, H = b.shape
    got = jnp.concatenate([experts(
        p_layer, b[r:r + rows_a_step].reshape(-1, H),
        gates[r:r + rows_a_step].reshape(-1, gates.shape[-1]),
        chosen[r:r + rows_a_step].reshape(-1, chosen.shape[-1])
    ).astype(jnp.float32) for r in range(0, rows, rows_a_step)]
    ).reshape(rows, L, H)
    err = jnp.linalg.norm(got - want, axis=-1)
    return np.asarray(err / jnp.maximum(jnp.linalg.norm(want, axis=-1),
                                        1e-30))


def reference_pass(ref, m, seed, shapes, batch, mask, choice, margin,
                   precs=(), router_replicas=1, experts=None,
                   rows_a_step=None):
    """The reference over the batch, a layer at a time, adopting the
    program's `choice` (layers, rows, 2L, k) at near ties under `margin`.
    → {"eps": {prec: (rows, H, W, 3)}, "layer_margin": (layers, rows, L)
    the target tokens' router margins with an adopted token's set to inf
    (so that token_check.step_rows leaves out exactly the tokens whose
    near tie was NOT adopted), "adopted": their share, and with `experts`
    (from `expert_layer`) "routed_miss": (layers, rows, L)}. The controls
    `precs` stand in at the same inputs with the same weights, on their
    own routing."""
    side = batch["z"].shape[1]
    small = token_weights.make_weights(seed, shapes, SMALL_GROUPS)
    allp = ("f32",) + tuple(precs)
    h = {p: ref.jitted("embed", m, p)(small, batch, mask) for p in allp}
    L = h["f32"].shape[1] // 2
    margins, miss, adopted = [], [], []
    for i in range(m["num_hidden_layers"]):
        p_layer = token_weights.make_group(seed, shapes, f"layer_{i}",
                                           router_replicas)
        for p in allp:
            if p != "f32":
                h[p], _ = ref.jitted_layer(m, i, p)(p_layer, h[p])
                continue
            h[p], aux = ref.jitted_layer(m, i, p, True, float(margin))(
                p_layer, h[p], jnp.asarray(choice[i]))
            took = np.asarray(aux["adopted"])[:, L:]
            margins.append(np.where(took, np.inf,
                                    np.asarray(aux["margin"])[:, L:]))
            adopted.append(took.mean())
            if experts is not None:
                miss.append(routed_miss(experts, p_layer, aux, L,
                                        rows_a_step))
            del aux
        del p_layer
    eps = {p: np.asarray(ref.jitted("head", m, side, p)(small, h[p]),
                         np.float64) for p in allp}
    return {"eps": eps, "layer_margin": np.stack(margins),
            "adopted": float(np.mean(adopted)),
            "routed_miss": np.stack(miss) if miss else None}


def held_rows_lost(got, ratio):
    """Target tokens, per layer, whose routed part the program's expert
    layer lost: every token-layer counts, none is left out."""
    return int(np.sum(got["routed_miss"] > ratio))


def judge_steps(cell, cfg, seed, shapes, sample, numbers):
    """One sampled view of one finished call against the reference.
    `sample` as token_check.judge_steps takes it, with "choice" from
    `program_choices`."""
    ref, tables = load_refs(cell)
    limits, check = cell["traffic"]["limits"], cell["traffic"]["check"]
    m = model_sizes(cfg)
    T, w = cfg.diffusion.timesteps, cfg.diffusion.guidance_weight
    tab = tables.cosine_tables(T, sample["traj"].shape[0])
    margin = float(check["router_margin"])
    t_ref = time.perf_counter()
    got = reference_pass(ref, m, seed, shapes, sample["batch"],
                         sample["mask"], sample["choice"], margin,
                         router_replicas=replicas(cell),
                         experts=expert_layer(cfg),
                         rows_a_step=2 * sample["draw_shape"][0])
    rows = step_rows(m, tab, w, sample, sample["steps"], sample["z_ins"],
                     sample["noises"], got, margin)
    harness.log(f"reference: {time.perf_counter() - t_ref:.1f} s for "
                f"{len(rows)} step(s) of {sample['label']}")
    for r in rows:
        print(f"compare-detail {sample['label']} step {r['step']}: eps gap "
              f"{sampling_check.pooled([r], 'program'):.6g} on "
              f"{r['pixels']} of {r['size']} pixels, {r['close_tokens']} of "
              f"{r['tokens']} tokens at a near tie not adopted", flush=True)
    print(f"compare-detail the program's choice adopted at a near tie in "
          f"{got['adopted']:.4g} of the target token-layers", flush=True)
    ok = True
    for name, value in pooled_numbers(rows).items():
        ok &= harness.compare(name, value, limits[name], numbers)
    miss = got["routed_miss"]
    print(f"compare-detail expert layer alone: routed part off the "
          f"reference's by median {np.median(miss):.3g}, at most "
          f"{miss.max():.3g} of its norm over {miss.size} token-layers",
          flush=True)
    ok &= harness.compare(
        "held_rows_lost", held_rows_lost(got, float(check["lost_row_ratio"])),
        limits["held_rows_lost"], numbers)
    ok &= harness.compare(
        "final_is_last_state",
        float(np.max(np.abs(np.asarray(sample["final"], np.float64)
                            - np.asarray(sample["traj"][-1], np.float64)))),
        0.0, numbers)
    return ok

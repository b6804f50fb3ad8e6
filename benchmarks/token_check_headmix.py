"""How `correct` is decided for a token-denoiser cell on Laguna's stack
(reference/lgs_ref.py): token_check_gqa.py's comparison — the states the
timed sampler wrote against the plain reference's full forward, ε̂ read
back by inverting the update, pooled over the checked steps; a near tie of
the 10th and 11th logit adopted from the program inside
`check.router_margin`, else the token left out and counted; the expert
layer alone fed the reference's gates and choice (`held_rows_lost`) — for
a trunk whose layers differ by index in head count, rotary law, mask and
feed-forward. What is this file's own:

  - only the layers WITH experts have a router (token_check_kda.py's
    rule): the program's choices and counts, `layer_margin` and
    `routed_miss` are (expert layers, …), and the leading dense layer is
    held by ε̂ alone;
  - the program made its ε̂ from keys rotated by TWO laws and cached in
    two lengths (a full layer's whole frame, a window layer's last 511
    rows): prefill into both, then 72- and 48-head queries against
    [cache ; own], is held to the reference's one pass over both frames
    under its dense predicate;
  - four more controls than the lower precision, each a fault of this
    mechanism planted in the reference (lgs_ref.CONTROLS): the head gate
    left out, the two rotary laws swapped between the layer kinds, the
    × 2.5 on the gates left out, the window layers run at the full
    layers' visibility. Each stands in the program's place at the same
    inputs with the same weights (tools/read_limits_tokens_headmix.py
    reads them and reports each as it reads);
  - the program's own account of its routing is taken a checked step's two
    rows at a time (token_check_scmoe.py's: 10.6 GB of weights leave a
    timed step's temporaries and little more), and the reference runs one
    name at a time, the layers' weights made again in each pass;
  - weights are token_weights.py's, the router tied in the configuration's
    `assumed.router_replicas`.

Compared, each beside its limit: eps_rel_rms, excluded_token_share,
uncompared_pixel_share, clipped_share_gap, held_rows_lost,
final_is_last_state (token_check.py's head says what each is).
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax.numpy as jnp
import numpy as np

import harness
import sampling_check
import token_weights
from token_check import (  # noqa: F401 — the kind and the tools take them here
    SMALL_GROUPS, pick, pooled_numbers, program_model, program_shapes,
    replicas, rows_lost, step_inputs, step_rows)
from token_check_gqa import (  # noqa: F401
    expert_layer, held_rows_lost, routed_miss)
from token_check_kda import _plain
from token_check_scmoe import program_choices, program_counts  # noqa: F401

CONTROLS = ("no_head_gate", "swapped_rope", "no_routed_scale",
            "full_visibility")


def model_sizes(cfg) -> dict:
    """The trunk's sizes under the source's key names (the reference's and
    flops_tokens_headmix.py's), plus the name the expert layer's readers
    know the router's width by, which the program's config gives as a
    property."""
    k = cfg.model.tokens
    m = _plain(dataclasses.asdict(k))
    m["side"] = cfg.data.img_sidelength
    m["n_routed_experts"] = k.n_routed_experts
    return m


def expert_layers(m) -> list:
    """The layers that have a router and experts, in order."""
    return [i for i in range(m["num_hidden_layers"])
            if m["mlp_layer_types"][i] != "dense"]


def load_refs(cell):
    """(the model's reference, the module holding the schedule's tables)."""
    ref = harness.load_module(os.path.join(
        cell["bench_dir"], cell["config"]["reference"]), "lgs_ref")
    tables = harness.load_module(os.path.join(
        cell["bench_dir"], "reference", "xunet_ref.py"), "xunet_ref")
    return ref, tables


def reference_pass(ref, m, seed, shapes, batch, mask, choice, margin,
                   controls=(), router_replicas=1, experts=None,
                   rows_a_step=None):
    """The reference over the batch, a layer at a time, adopting the
    program's `choice` (expert layers, rows, 2L, k) at near ties under
    `margin`. → {"eps": {name: (rows, H, W, 3)}, "layer_margin" (expert
    layers, rows, L) the target tokens' router margins with an adopted
    token's set to inf, "adopted": their share, and with `experts` (from
    `expert_layer`) "routed_miss" (expert layers, rows, L)}. The `controls`
    stand in at the same inputs with the same weights, on their own
    routing: a lower precision of lgs_ref.py, or one of CONTROLS. One pass
    a name, the layers' weights made again in each: a hidden state a name
    beside a layer's temporaries is more than the chip needs to hold."""
    side = batch["z"].shape[1]
    small = token_weights.make_weights(seed, shapes, SMALL_GROUPS)
    L = (side // m["patch_size"]) ** 2
    with_experts = expert_layers(m)
    margins, miss, adopted, eps = [], [], [], {}
    for n in ("f32",) + tuple(controls):
        prec, control = ("f32", n) if n in CONTROLS else (n, None)
        h = ref.jitted("embed", m, prec)(small, batch, mask)
        for i in range(m["num_hidden_layers"]):
            p_layer = token_weights.make_group(seed, shapes, f"layer_{i}",
                                               router_replicas)
            if n != "f32":
                h, _ = ref.jitted_layer(m, i, prec, control=control)(
                    p_layer, h)
            elif i not in with_experts:
                h, _ = ref.jitted_layer(m, i, "f32")(p_layer, h)
            else:
                h, aux = ref.jitted_layer(m, i, "f32", True, float(margin))(
                    p_layer, h, jnp.asarray(choice[with_experts.index(i)]))
                took = np.asarray(aux["adopted"])[:, L:]
                margins.append(np.where(took, np.inf,
                                        np.asarray(aux["margin"])[:, L:]))
                adopted.append(took.mean())
                if experts is not None:
                    miss.append(routed_miss(experts, p_layer, aux, L,
                                            rows_a_step))
                del aux
            del p_layer
        eps[n] = np.asarray(ref.jitted("head", m, side, prec)(small, h),
                            np.float64)
        del h
    return {"eps": eps, "layer_margin": np.stack(margins),
            "adopted": float(np.mean(adopted)),
            "routed_miss": np.stack(miss) if miss else None}


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

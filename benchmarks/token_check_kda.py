"""How `correct` is decided for a token-denoiser cell on Kimi-Linear's stack
(reference/kl48_ref.py): token_check_gqa.py's comparison — the states the
timed sampler wrote against the plain reference's full forward, ε̂ read
back by inverting the update, pooled over the checked steps; a near tie of
the 8th and 9th of (score + bias) adopted from the program inside
`check.router_margin`, else the token left out and counted; the expert
layer alone fed the reference's gates and choice (`held_rows_lost`) — for
a trunk whose layers differ by index. What is this file's own:

  - the reference runs a layer at a time whatever the layer's kind; only
    the layers WITH experts have a router, so the program's choices and
    counts, `layer_margin` and `routed_miss` are (expert layers, …), and
    the leading dense layer is held by ε̂ alone;
  - the program made its ε̂ from a KDA state computed once a call, entered
    anew by every step's chunked scan, and a latent beside it: so prefill
    into TWO kinds of cache, then decode from them, is held to the
    reference's one token-by-token pass over both frames;
  - one more control than the lower precision: `zeroed_state`, the
    reference with every KDA layer's state set to zero at the target
    frame's first token (what a step reads if the cached state is lost or
    never handed on), in the program's place. It must read past the
    `eps_rel_rms` limit as the fp8 reference must — else the comparison
    could not tell a cache that holds the conditioning frame from one that
    holds nothing (tools/read_limits_tokens_kda.py reads both);
  - weights from kda_weights.py (the decay's leaves as the public
    implementation draws them), and with `parts` the reference's own
    read-out of how fast the state forgets (`decay_rate_quantiles`).

Compared, each beside its limit: eps_rel_rms, excluded_token_share,
uncompared_pixel_share, clipped_share_gap, held_rows_lost,
final_is_last_state (token_check.py's head says what each is).
"""

from __future__ import annotations

import dataclasses
import math
import os
import time

import jax.numpy as jnp
import numpy as np

import harness
import kda_weights
import sampling_check
from token_check import (  # noqa: F401 — the kind and the tools take them here
    SMALL_GROUPS, pick, pooled_numbers, program_counts, program_shapes,
    rows_lost, step_inputs, step_rows)
from token_check_gqa import (  # noqa: F401
    expert_layer, held_rows_lost, program_choices, routed_miss)

ZEROED_STATE = "zeroed_state"


def _plain(v):
    if isinstance(v, dict):
        return {k: _plain(x) for k, x in v.items()}
    return list(v) if isinstance(v, tuple) else v


def model_sizes(cfg) -> dict:
    """The trunk's sizes under the source's key names (the reference's and
    flops_tokens_kda.py's), plus the names the expert layer's readers know
    them by, which the program's config gives as properties."""
    k = cfg.model.tokens
    m = _plain(dataclasses.asdict(k))
    m["side"] = cfg.data.img_sidelength
    m.update(n_routed_experts=k.n_routed_experts,
             num_experts_per_tok=k.num_experts_per_tok)
    return m


def expert_layers(m) -> list:
    """The layers that have a router and experts, in order."""
    return [i for i in range(m["num_hidden_layers"])
            if i >= m["first_k_dense_replace"]]


def load_refs(cell):
    """(the model's reference, the module holding the schedule's tables)."""
    ref = harness.load_module(os.path.join(
        cell["bench_dir"], cell["config"]["reference"]), "kl48_ref")
    tables = harness.load_module(os.path.join(
        cell["bench_dir"], "reference", "xunet_ref.py"), "xunet_ref")
    return ref, tables


def weight_args(cell) -> dict:
    """What kda_weights.make_group takes from the configuration file."""
    return kda_weights.decay_args(cell["config"])


def program_model(cfg, seed, wargs):
    """The program's denoiser and the benchmark's seeded weights for it
    (only the tree's shapes come from the program)."""
    model, shapes = program_shapes(cfg)
    return model, shapes, kda_weights.make_weights(seed, shapes, **wargs)


def reference_pass(ref, m, seed, shapes, batch, mask, choice, margin,
                   controls=(), wargs=None, experts=None, rows_a_step=None):
    """The reference over the batch, a layer at a time, adopting the
    program's `choice` (expert layers, rows, 2L, k) at near ties under
    `margin`. → {"eps": {name: (rows, H, W, 3)}, "layer_margin" (expert
    layers, rows, L) the target tokens' router margins with an adopted
    token's set to inf, "adopted": their share, "half_life": per KDA
    layer the (95, 75, 50, 25, 5 %) quantiles of ln 2 / |g| in tokens,
    and with `experts` (from `expert_layer`) "routed_miss" (expert
    layers, rows, L)}. The `controls` stand in at the same inputs with the
    same weights, on their own routing: a lower precision of
    kl48_ref.py, or ZEROED_STATE."""
    wargs = wargs or {}
    side = batch["z"].shape[1]
    small = kda_weights.make_weights(seed, shapes, SMALL_GROUPS, **wargs)
    names = ("f32",) + tuple(controls)
    prec = {n: "f32" if n == ZEROED_STATE else n for n in names}
    h = {n: ref.jitted("embed", m, prec[n])(small, batch, mask)
         for n in names}
    L = h["f32"].shape[1] // 2
    with_experts = expert_layers(m)
    margins, miss, adopted, half_life = [], [], [], {}
    for i in range(m["num_hidden_layers"]):
        p_layer = kda_weights.make_group(seed, shapes, f"layer_{i}", **wargs)
        for n in names:
            if n != "f32":
                h[n], _ = ref.jitted_layer(
                    m, i, prec[n],
                    zero_state_at=L if n == ZEROED_STATE else None)(
                    p_layer, h[n])
                continue
            run = ref.jitted_layer(m, i, "f32", True, float(margin))
            if i not in with_experts:
                h[n], aux = run(p_layer, h[n])
            else:
                h[n], aux = run(p_layer, h[n], jnp.asarray(
                    choice[with_experts.index(i)]))
                took = np.asarray(aux["adopted"])[:, L:]
                margins.append(np.where(took, np.inf,
                                        np.asarray(aux["margin"])[:, L:]))
                adopted.append(took.mean())
                if experts is not None:
                    miss.append(routed_miss(experts, p_layer, aux, L,
                                            rows_a_step))
            if "decay_rate_quantiles" in aux:
                half_life[i] = (math.log(2.0) / np.maximum(np.asarray(
                    aux["decay_rate_quantiles"], np.float64), 1e-30)
                ).tolist()
            del aux
        del p_layer
    eps = {n: np.asarray(ref.jitted("head", m, side, prec[n])(small, h[n]),
                         np.float64) for n in names}
    return {"eps": eps, "layer_margin": np.stack(margins),
            "adopted": float(np.mean(adopted)), "half_life": half_life,
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
                         wargs=weight_args(cell), experts=expert_layer(cfg),
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
          f"{got['adopted']:.4g} of the target token-layers; median channel "
          "half-life in tokens, by KDA layer: " + ", ".join(
              f"{i}: {q[2]:.4g}" for i, q in got["half_life"].items()),
          flush=True)
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

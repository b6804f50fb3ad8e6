"""How `correct` is decided for a token-denoiser cell on LongCat-Flash's
stack (reference/lcf_ref.py): token_check_kda.py's comparison — the states
the timed sampler wrote against the plain reference's full forward, ε̂ read
back by inverting the update, pooled over the checked steps; a near tie of
the 12th and 13th of (score + bias) adopted from the program inside
`check.router_margin`, else the token left out and counted; the expert
branch alone fed the reference's gates and choice (`held_rows_lost`) — for
a trunk whose layer is two sublayers with the branch across them. What is
this file's own:

  - the program made its ε̂ from TWO latents a layer computed once a call
    (one per attention of the double layer): prefill into both, then
    decode from both, is held to the reference's one pass over both
    frames;
  - the router is wider than the experts: of a token's twelve choices 0 to
    12 are real experts, of which those in `held_experts` have a row here,
    and the rest are identities. The program's branch — `held_expert_part`
    AND `identity_part`, summed as the layer sums them — is run alone on
    the reference's input, gates and choice, and every target token's m is
    held to the reference's: tokens with NO held choice (most of them, on
    independent router columns) and tokens all of whose choices are
    identities included. A token whose m the reference reads 0 may not
    read anything else;
  - three more controls than the lower precision, each a fault of this
    mechanism planted in the reference (lcf_ref.CONTROLS): the identity
    part left out, the branch joined one sublayer early, the latent
    scales left out. Each should read past the `eps_rel_rms` limit as the
    fp8 reference must (tools/read_limits_tokens_scmoe.py reads them);
  - the program's own account of its routing, and the reference itself,
    are taken a checked step's two rows at a time (at the cell's size one
    pass's expert buffer is 1.2 GB a step's two rows, and a float32 hidden
    state of all checked steps 1.2 GB of which a layer holds eight; six
    rows at once beside the weights do not fit), and the
    choices' shares — identities among a token's choices, tokens without
    a held choice — are read off them (`choice_shares`) for the run's
    counters;
  - weights from scmoe_weights.py (the router's bias on the scores'
    scale).

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
import scmoe_weights
from token_check import (  # noqa: F401 — the kind and the tools take them here
    SMALL_GROUPS, pick, pooled_numbers, program_shapes, rows_lost,
    step_inputs, step_rows)
from token_check_gqa import held_rows_lost  # noqa: F401

CONTROLS = ("no_identity", "early_join", "no_latent_scale")


def model_sizes(cfg) -> dict:
    """The trunk's sizes under the source's key names (the reference's and
    flops_tokens_scmoe.py's), plus the names the frame's and the expert
    layer's readers know them by, which the program's config gives as
    properties."""
    k = cfg.model.tokens
    m = {name: list(v) if isinstance(v, tuple) else v
         for name, v in dataclasses.asdict(k).items()}
    m["side"] = cfg.data.img_sidelength
    m.update(num_hidden_layers=k.num_hidden_layers,
             num_experts_per_tok=k.num_experts_per_tok,
             moe_intermediate_size=k.expert_ffn_hidden_size)
    return m


def load_refs(cell):
    """(the model's reference, the module holding the schedule's tables)."""
    ref = harness.load_module(os.path.join(
        cell["bench_dir"], cell["config"]["reference"]), "lcf_ref")
    tables = harness.load_module(os.path.join(
        cell["bench_dir"], "reference", "xunet_ref.py"), "xunet_ref")
    return ref, tables


def weight_args(cell) -> dict:
    """What scmoe_weights.make_group takes from the configuration file."""
    return scmoe_weights.router_args(cell["config"])


def program_model(cfg, seed, wargs):
    """The program's denoiser and the benchmark's seeded weights for it
    (only the tree's shapes come from the program)."""
    model, shapes = program_shapes(cfg)
    return model, shapes, scmoe_weights.make_weights(seed, shapes, **wargs)


def _by_step(fn, batch, mask, axis):
    """`fn` over the batch a checked step's two rows at a time, its
    results joined along `axis`."""
    rows = mask.shape[0]
    return np.concatenate([np.asarray(fn(
        {k: v[r:r + 2] for k, v in batch.items()}, mask[r:r + 2]))
        for r in range(0, rows, 2)], axis=axis)


def program_counts(model, params, batch, mask):
    """(layers, held) tokens per held expert that the PROGRAM routes in
    its pass over the target's tokens of the checked steps, summed over
    the steps (its own pure function, a step's rows at a time; called
    while its weights are still on the device)."""
    run = jax.jit(model.routing_counts)
    return _by_step(lambda b, m: np.asarray(run(params, b, m))[None], batch,
                    mask, 0).sum(axis=0)


def program_choices(model, params, batch, mask):
    """(layers, rows, 2L, k) the router outputs the PROGRAM sends each
    token of both frames to on the checked steps' inputs (ids up to
    router_width − 1; its own pure function, a step's rows at a time)."""
    run = jax.jit(model.routing_choices)
    return _by_step(lambda b, m: run(params, b, m), batch, mask, 1)


def choice_shares(choice, m) -> dict:
    """Of `program_choices`' (layers, rows, 2L, k), over the target tokens
    of the checked steps and the layers: the share of a token's choices
    that are identities, the share of tokens with no held choice, and the
    held rows of one layer in one step (two rows). `m` = `model_sizes`."""
    first, count = m["held_experts"]
    own = np.asarray(choice)[:, :, choice.shape[2] // 2:]
    held = (own >= first) & (own < first + count)
    return {
        "zero_choice_share": float(np.mean(own >= m["n_routed_experts"])),
        "tokens_without_held_share": float(np.mean(~held.any(axis=-1))),
        "held_rows_per_layer_step": float(held.sum() / (
            own.shape[0] * own.shape[1] / 2))}


def expert_layer(cfg):
    """The program's expert branch alone: (a layer's parameters, normalised
    tokens (T, hidden) float32, gates (T, k), chosen outputs (T, k)) → its
    two parts (T, hidden) each: the held experts' (`held_expert_part`) and
    the identities' (`identity_part`), which `LongcatFlashLayer` sums."""
    from novel_view_synthesis_3d_tpu.models import token_denoiser as td

    k, dt = cfg.model.tokens, jnp.dtype(cfg.model.dtype)

    def run(p_layer, b32, gates, chosen):
        b = b32.astype(dt)
        return (td.held_expert_part(b, gates, chosen, p_layer["experts"],
                                    k)[0],
                td.identity_part(b, gates, chosen, k))

    return jax.jit(run)


def routed_miss(experts, p_layer, aux, L, rows_a_step):
    """Per target token (rows, L), the larger of |program's − reference's|
    / |reference's| over the branch's two parts, the held experts' and the
    identities'; the program's branch is fed `rows_a_step` rows at a time,
    a timed step's batch. Each part is held on its own: beside an identity
    part of half the token's norm one lost expert row would not show in
    their sum. Where the reference's part is 0 (no held choice; no
    identity among the choices), anything the program adds reads huge."""
    b, gates, chosen = (aux[n][:, L:] for n in ("b", "gates", "chosen"))
    rows, n, H = b.shape
    got = [experts(p_layer, b[r:r + rows_a_step].reshape(-1, H),
                   gates[r:r + rows_a_step].reshape(-1, gates.shape[-1]),
                   chosen[r:r + rows_a_step].reshape(-1, chosen.shape[-1]))
           for r in range(0, rows, rows_a_step)]
    miss = []
    for j, name in enumerate(("routed", "zero")):
        part = jnp.concatenate([g[j].astype(jnp.float32)
                                for g in got]).reshape(rows, n, H)
        want = aux[name][:, L:]
        miss.append(jnp.linalg.norm(part - want, axis=-1) / jnp.maximum(
            jnp.linalg.norm(want, axis=-1), 1e-30))
    return np.asarray(jnp.maximum(*miss))


def reference_pass(ref, m, seed, shapes, batch, mask, choice, margin,
                   controls=(), wargs=None, experts=None, rows_a_step=None):
    """The reference over the batch, a layer at a time, adopting the
    program's `choice` (layers, rows, 2L, k) at near ties under `margin`.
    → {"eps": {name: (rows, H, W, 3)}, "layer_margin" (layers, rows, L)
    the target tokens' router margins with an adopted token's set to inf,
    "adopted": their share, and with `experts` (from `expert_layer`)
    "routed_miss" (layers, rows, L)}. The `controls` stand in at the same
    inputs with the same weights, on their own routing: a lower precision
    of lcf_ref.py, or one of CONTROLS."""
    wargs = wargs or {}
    side = batch["z"].shape[1]
    small = scmoe_weights.make_weights(seed, shapes, SMALL_GROUPS, **wargs)
    L = (side // m["patch_size"]) ** 2
    steps = range(0, mask.shape[0], 2)
    margins, miss, adopted, eps = [], [], [], {}
    # One pass a name, a checked step's two rows at a time, the layers'
    # weights made again in each pass: at the cell's size a hidden state of
    # all the checked steps is 1.2 GB and a layer holds eight of them
    # beside 2.5 GB of weights — all steps at once, or one state a control
    # beside the reference's own, do not fit.
    for n in ("f32",) + tuple(controls):
        prec, control = ("f32", n) if n in CONTROLS else (n, None)
        h = [ref.jitted("embed", m, prec)(
            small, {k: v[r:r + 2] for k, v in batch.items()}, mask[r:r + 2])
            for r in steps]
        for i in range(m["num_layers"]):
            p_layer = scmoe_weights.make_group(seed, shapes, f"layer_{i}",
                                               **wargs)
            if n != "f32":
                run = ref.jitted_layer(m, prec, control=control)
                h = [run(p_layer, x)[0] for x in h]
                continue
            run = ref.jitted_layer(m, "f32", True, float(margin))
            took, gaps, missed = [], [], []
            for j, r in enumerate(steps):
                h[j], aux = run(p_layer, h[j], jnp.asarray(
                    choice[i][r:r + 2]))
                took.append(np.asarray(aux["adopted"])[:, L:])
                gaps.append(np.asarray(aux["margin"])[:, L:])
                if experts is not None:
                    missed.append(routed_miss(experts, p_layer, aux, L,
                                              rows_a_step))
                del aux
            took = np.concatenate(took)
            margins.append(np.where(took, np.inf, np.concatenate(gaps)))
            adopted.append(took.mean())
            if missed:
                miss.append(np.concatenate(missed))
            del p_layer
        eps[n] = np.concatenate([np.asarray(
            ref.jitted("head", m, side, prec)(small, x), np.float64)
            for x in h])
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
    shares = choice_shares(sample["choice"], m)
    print(f"compare-detail the program's choice adopted at a near tie in "
          f"{got['adopted']:.4g} of the target token-layers; of the target "
          f"tokens' choices {shares['zero_choice_share']:.4g} are "
          f"identities, {shares['tokens_without_held_share']:.4g} of "
          "the token-layers have no held choice", flush=True)
    ok = True
    for name, value in pooled_numbers(rows).items():
        ok &= harness.compare(name, value, limits[name], numbers)
    miss = got["routed_miss"]
    print(f"compare-detail expert branch alone: its worse part off the "
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

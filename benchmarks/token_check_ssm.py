"""How `correct` is decided for a token-denoiser cell on Phi-4-mini-flash's
stack (reference/p4f_ref.py): token_check.py's comparison — the states the
timed sampler wrote against the plain reference's full forward, ε̂ read
back by inverting the update, pooled over the checked steps — for a trunk
WITHOUT a router: no token is left out for a near tie, no expert layer is
run alone, and `excluded_token_share` and `held_rows_lost` have nothing to
count and are not compared. What is this file's own:

  - the reference runs a layer at a time whatever the layer's kind, and
    what a layer publishes for later layers (layer N/2's scan output,
    layer N/2 + 1's keys and values) travels beside h between the calls;
  - the program made its ε̂ from a Mamba state, a window's tail and ONE
    shared key/value cache computed once a call by a pass that stops at
    layer N/2 + 1, every step's scan entered anew from the cached state
    and fourteen later layers reading what two layers published: so
    prefill into THREE kinds of cache entry, then decode from them, is held
    to the reference's one token-by-token pass over both frames through
    every layer;
  - two controls of this mechanism beside the lower precision, each of
    which must read past the `eps_rel_rms` limit as the fp8 reference must:
    `zeroed_state`, the reference with every Mamba layer's state set to
    zero at the target frame's first token (what a step reads if the cached
    state is lost or never handed on), and `lost_shared_cache`, the
    reference whose cross layers see layer N/2 + 1's keys and values of
    their OWN frame alone (the conditioning frame's shared cache lost) —
    else the comparison could not tell a cache that holds the conditioning
    frame from one that holds nothing (tools/read_limits_tokens_ssm.py
    reads all three);
  - weights from ssm_weights.py (the decay's leaves as the public
    implementation draws them), and with `parts` the reference's own
    read-out of how fast the state forgets (`decay_rate_quantiles`).

Compared, each beside its limit: eps_rel_rms, uncompared_pixel_share,
clipped_share_gap, final_is_last_state (token_check.py's head says what
each is).
"""

from __future__ import annotations

import dataclasses
import math
import os
import time

import numpy as np

import harness
import sampling_check
import ssm_weights
from token_check import (  # noqa: F401 — the kind and the tools take them here
    SMALL_GROUPS, pick, pooled_numbers, program_shapes, step_inputs,
    step_rows)

ZEROED_STATE = "zeroed_state"
LOST_SHARED_CACHE = "lost_shared_cache"
COMPARED = ("eps_rel_rms", "uncompared_pixel_share", "clipped_share_gap")


def model_sizes(cfg) -> dict:
    """The trunk's sizes under the source's key names (the reference's and
    flops_tokens_ssm.py's)."""
    m = dataclasses.asdict(cfg.model.tokens)
    m["side"] = cfg.data.img_sidelength
    return m


def load_refs(cell):
    """(the model's reference, the module holding the schedule's tables)."""
    ref = harness.load_module(os.path.join(
        cell["bench_dir"], cell["config"]["reference"]), "p4f_ref")
    tables = harness.load_module(os.path.join(
        cell["bench_dir"], "reference", "xunet_ref.py"), "xunet_ref")
    return ref, tables


def weight_args(cell) -> dict:
    """What ssm_weights.make_group takes from the configuration file."""
    return ssm_weights.decay_args(cell["config"])


def program_model(cfg, seed, wargs):
    """The program's denoiser and the benchmark's seeded weights for it
    (only the tree's shapes come from the program)."""
    model, shapes = program_shapes(cfg)
    return model, shapes, ssm_weights.make_weights(seed, shapes, **wargs)


def _layer_args(name):
    """p4f_ref.jitted_layer's (prec, zero_state_at given L, lost cache)."""
    return ("f32" if name in (ZEROED_STATE, LOST_SHARED_CACHE) else name,
            name == ZEROED_STATE, name == LOST_SHARED_CACHE)


def reference_pass(ref, m, seed, shapes, batch, mask, controls=(),
                   wargs=None):
    """The reference over the batch, a layer at a time; then each of the
    `controls` in its place, at the same inputs with the same weights (a
    lower precision of p4f_ref.py, ZEROED_STATE or LOST_SHARED_CACHE), one
    after the other so that one pass's state is on the device at a time.
    → {"eps": {name: (rows, H, W, 3)}, "layer_margin": (1, rows, L) of inf
    (no router: `step_rows` leaves no token out), "half_life": per Mamba
    layer the (95, 75, 50, 25, 5 %) quantiles of ln 2 / (Δ·|A|) in
    tokens}."""
    wargs = wargs or {}
    side = batch["z"].shape[1]
    small = ssm_weights.make_weights(seed, shapes, SMALL_GROUPS, **wargs)
    eps, half_life = {}, {}
    for name in ("f32",) + tuple(controls):
        prec, zeroed, lost = _layer_args(name)
        h = ref.jitted("embed", m, prec)(small, batch, mask)
        L = h.shape[1] // 2
        pub = {}
        for i in range(m["num_hidden_layers"]):
            p_layer = ssm_weights.make_group(seed, shapes, f"layer_{i}",
                                             **wargs)
            h, new, aux = ref.jitted_layer(
                m, i, prec, name == "f32", L if zeroed else None, lost)(
                p_layer, h, pub)
            pub = {**pub, **new}
            if "decay_rate_quantiles" in aux:
                half_life[i] = (math.log(2.0) / np.maximum(np.asarray(
                    aux["decay_rate_quantiles"], np.float64), 1e-30)
                ).tolist()
            del p_layer, new, aux
        eps[name] = np.asarray(ref.jitted("head", m, side, prec)(small, h),
                               np.float64)
        del h, pub
    rows = batch["z"].shape[0]
    return {"eps": eps, "layer_margin": np.full((1, rows, L), np.inf),
            "half_life": half_life}


def judge_steps(cell, cfg, seed, shapes, sample, numbers):
    """One sampled view of one finished call against the reference.
    `sample` as token_check.judge_steps takes it."""
    ref, tables = load_refs(cell)
    limits = cell["traffic"]["limits"]
    m = model_sizes(cfg)
    T, w = cfg.diffusion.timesteps, cfg.diffusion.guidance_weight
    tab = tables.cosine_tables(T, sample["traj"].shape[0])
    t_ref = time.perf_counter()
    got = reference_pass(ref, m, seed, shapes, sample["batch"],
                         sample["mask"], wargs=weight_args(cell))
    rows = step_rows(m, tab, w, sample, sample["steps"], sample["z_ins"],
                     sample["noises"], got, 0.0)
    harness.log(f"reference: {time.perf_counter() - t_ref:.1f} s for "
                f"{len(rows)} step(s) of {sample['label']}")
    for r in rows:
        print(f"compare-detail {sample['label']} step {r['step']}: eps gap "
              f"{sampling_check.pooled([r], 'program'):.6g} on "
              f"{r['pixels']} of {r['size']} pixels", flush=True)
    print("compare-detail median (channel, state) half-life in tokens, by "
          "Mamba layer: " + ", ".join(
              f"{i}: {q[2]:.4g}" for i, q in got["half_life"].items()),
          flush=True)
    ok = True
    pooled = pooled_numbers(rows)
    for name in COMPARED:
        ok &= harness.compare(name, pooled[name], limits[name], numbers)
    ok &= harness.compare(
        "final_is_last_state",
        float(np.max(np.abs(np.asarray(sample["final"], np.float64)
                            - np.asarray(sample["traj"][-1], np.float64)))),
        0.0, numbers)
    return ok

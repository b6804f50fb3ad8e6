"""How `correct` is decided for a token-denoiser cell on Olmo-Hybrid's
stack (reference/oh7_ref.py): token_check_ssm.py's comparison — the states
the timed sampler wrote against the plain reference's full forward, ε̂ read
back by inverting the update, pooled over the checked steps, for a trunk
without a router: no token is left out, every pixel whose x̂₀ is unclipped
is compared. What is this file's own:

  - the program made its ε̂ from a delta-rule state with its convolution's
    tail and from keys and values computed once a call, every step's
    chunked scan entered anew from the cached state: so prefill into TWO
    kinds of cache entry, then decode from them, is held to the reference's
    one token-by-token pass over both frames;
  - three controls of this mechanism beside the lower precision
    (oh7_ref.CONTROLS), each of which must read past the `eps_rel_rms`
    limit as the fp8 reference must: `zeroed_state`, the reference with
    every delta-rule layer's state set to zero at the target frame's first
    token (what a step reads if the cached state is lost or never handed
    on); `beta_unscaled`, β without the factor 2 that
    `linear_allow_neg_eigval` gives it; `no_decay`, g = 0, nothing ever
    forgotten — else the comparison could not tell this model's delta rule
    from a plainer one (tools/read_limits_tokens_gdn.py reads all four);
  - weights from gdn_weights.py (the decay's leaves as the public
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

import gdn_weights
import harness
import sampling_check
from token_check import (  # noqa: F401 — the kind and the tools take them here
    SMALL_GROUPS, pick, pooled_numbers, program_shapes, step_inputs,
    step_rows)

COMPARED = ("eps_rel_rms", "uncompared_pixel_share", "clipped_share_gap")
CONTROLS = ("zeroed_state", "beta_unscaled", "no_decay")


def model_sizes(cfg) -> dict:
    """The trunk's sizes under the source's key names (the reference's and
    flops_tokens_gdn.py's)."""
    m = dataclasses.asdict(cfg.model.tokens)
    m["side"] = cfg.data.img_sidelength
    return m


def load_refs(cell):
    """(the model's reference, the module holding the schedule's tables)."""
    ref = harness.load_module(os.path.join(
        cell["bench_dir"], cell["config"]["reference"]), "oh7_ref")
    tables = harness.load_module(os.path.join(
        cell["bench_dir"], "reference", "xunet_ref.py"), "xunet_ref")
    return ref, tables


def weight_args(cell) -> dict:
    """What gdn_weights.make_group takes from the configuration file."""
    return gdn_weights.decay_args(cell["config"])


def program_model(cfg, seed, wargs):
    """The program's denoiser and the benchmark's seeded weights for it
    (only the tree's shapes come from the program)."""
    model, shapes = program_shapes(cfg)
    return model, shapes, gdn_weights.make_weights(seed, shapes, **wargs)


def reference_pass(ref, m, seed, shapes, batch, mask, controls=(),
                   wargs=None):
    """The reference over the batch, a layer at a time; then each of the
    `controls` in its place, at the same inputs with the same weights (a
    lower precision of oh7_ref.py or one of CONTROLS), one after the other
    so that one pass's state is on the device at a time. → {"eps": {name:
    (rows, H, W, 3)}, "layer_margin": (1, rows, L) of inf (no router:
    `step_rows` leaves no token out), "half_life": per delta-rule layer the
    (95, 75, 50, 25, 5 %) quantiles of ln 2 / −g in tokens}."""
    wargs = wargs or {}
    side = batch["z"].shape[1]
    small = gdn_weights.make_weights(seed, shapes, SMALL_GROUPS, **wargs)
    eps, half_life = {}, {}
    for name in ("f32",) + tuple(controls):
        prec, control = ("f32", name) if name in CONTROLS else (name, None)
        h = ref.jitted("embed", m, prec)(small, batch, mask)
        for i in range(m["num_hidden_layers"]):
            p_layer = gdn_weights.make_group(seed, shapes, f"layer_{i}",
                                             **wargs)
            h, aux = ref.jitted_layer(m, i, prec, name == "f32", control)(
                p_layer, h)
            if "decay_rate_quantiles" in aux:
                half_life[i] = (math.log(2.0) / np.maximum(np.asarray(
                    aux["decay_rate_quantiles"], np.float64), 1e-30)
                ).tolist()
            del p_layer, aux
        eps[name] = np.asarray(ref.jitted("head", m, side, prec)(small, h),
                               np.float64)
        del h
    rows, L = batch["z"].shape[0], (side // m["patch_size"]) ** 2
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
    print("compare-detail median head half-life in tokens, by delta-rule "
          "layer: " + ", ".join(
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

"""kind: scan_tokens_headmix — kind `scan_tokens_gqa` for a token denoiser
on Laguna's stack (grouped-query heads of two counts on one set of
key/value heads, two rotary laws, a window layer's cache entry its tail, a
gate a head, a leading dense layer, experts of which half are held): the
program's `make_sampler` called back to back for the window, one XLA
program a call (the conditioning frame's once-a-call pass into each layer's
cache entry, then every step over the target's tokens), built with
`trajectory_every=1` so that every call returns the latent after each
reverse step, which `correct` reads.

What differs from `scan_tokens_gqa` is who decides `correct` —
token_check_headmix.py (lgs_ref.py's one pass; routing counts and choices
of the layers that have experts only, read a step's rows at a time) — and
one more program counter: the bytes a row of the doubled batch keeps of
the conditioning frame, by kind of cache entry (`cond_cache_bytes`, from
the shapes `precompute` returns: a full layer's whole frame, a window
layer's 511 rows). The window, the timing and the result are that kind's
line for line: this file is that kind's code under another comparison (it
loads a copy of the module of its own and gives it this trunk's `check`,
the one name through which `build` and `run` reach the comparison and the
weights) and adds the counter to what it returns."""

from __future__ import annotations

import os

import harness
import token_check_headmix as check

_gqa = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "scan_tokens_gqa.py"), "kind_scan_tokens_headmix_body")
_gqa.check = check
build = _gqa.build


def run(cell, seed, seconds, trace_on, env):
    out = _gqa.run(cell, seed, seconds, trace_on, env)
    from novel_view_synthesis_3d_tpu.models import build_denoiser

    cfg, _ = build(cell, env)
    out["counters"]["cond_cache_bytes"] = {
        k: int(v) for k, v in build_denoiser(cfg.model).cond_cache_bytes(
            cfg.data.img_sidelength).items()}    # from shapes: no weight
    return out

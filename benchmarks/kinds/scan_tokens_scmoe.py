"""kind: scan_tokens_scmoe — kind `scan_tokens_kda` for a token denoiser on
LongCat-Flash's stack (the shortcut-connected double layer: two latents a
layer in the cache, an expert branch across two sublayers over a router
whose last outputs are identity experts): the program's `make_sampler`
called back to back for the window, one XLA program a call (the
conditioning frame's once-a-call pass into both latents of each layer, then
every step over the target's tokens), built with `trajectory_every=1` so
that every call returns the latent after each reverse step, which `correct`
reads.

What differs from `scan_tokens_kda` is who decides `correct` and who makes
the weights — token_check_scmoe.py (lcf_ref.py's one pass; scmoe_weights.py's
router bias on the scores' scale; the program's routing read a step's rows
at a time) — and three more program counters, read off the program's own
`routing_choices` of the checked steps: the share of a token's choices
that are identities, the share of token-layers without a held choice, the
held rows of a layer in a step. The window, the timing and the result are
that kind's line for line: this file is that kind's code under another
comparison (it loads a copy of the module of its own and gives it this
trunk's `check`, the one name through which `build` and `run` reach the
comparison and the weights) and adds the counters to what it returns: `run`
hands that kind a `check` that keeps the choices it read, and the shares are
`choice_shares` of them — a run that read none raises."""

from __future__ import annotations

import os

import harness
import token_check_scmoe as check

_kda = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "scan_tokens_kda.py"), "kind_scan_tokens_scmoe_body")
_kda.check = check
build = _kda.build


class _KeepsChoices:
    """token_check_scmoe.py, with what `program_choices` returned kept."""

    choice = None

    def __getattr__(self, name):
        return getattr(check, name)

    def program_choices(self, *args):
        self.choice = check.program_choices(*args)
        return self.choice


def run(cell, seed, seconds, trace_on, env):
    _kda.check = keeps = _KeepsChoices()       # a fresh one a run
    out = _kda.run(cell, seed, seconds, trace_on, env)
    if keeps.choice is None:
        raise RuntimeError("scan_tokens_scmoe: the run read no routing "
                           "choices, so its counters would lack the shares")
    out["counters"]["routing_choice_shares"] = check.choice_shares(
        keeps.choice, out["counters"]["sizes"])
    return out

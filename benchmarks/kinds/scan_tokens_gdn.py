"""kind: scan_tokens_gdn — kind `scan_tokens_ssm` for a token denoiser on
Olmo-Hybrid's stack (Gated DeltaNet layers with a recurrent state, full
attention under a QK norm, no expert layer, nothing published between
layers): the program's `make_sampler` called back to back for the window,
one XLA program a call (the conditioning frame's once-a-call pass, then
every step over the target's tokens, each delta-rule layer's scan entered
with the cached state), built with `trajectory_every=1` so that every call
returns the latent after each reverse step, which `correct` reads.

What differs from `scan_tokens_ssm` is who decides `correct` and who makes
the weights — token_check_gdn.py (oh7_ref.py's one token-by-token pass;
gdn_weights.py's decay as the public implementation draws it) —, and
nothing else: the window, the timing, the counters (`cond_cache_bytes` by
kind of cache entry; `attn_key_columns`, (0, 0) on a trunk without windows)
and the result are that kind's line for line. So this file is that kind's
code under another comparison: it loads a copy of the module of its own
and gives it this trunk's `check`, the one name through which `build` and
`run` reach the comparison and the weights."""

from __future__ import annotations

import os

import harness
import token_check_gdn as check

_ssm = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "scan_tokens_ssm.py"), "kind_scan_tokens_gdn_body")
_ssm.check = check
build, run = _ssm.build, _ssm.run

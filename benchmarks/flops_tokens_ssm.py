"""Operations and bytes the token denoiser needs on Phi-4-mini-flash's stack
(SambaY) — Mamba layers, differential attention under a window and full,
gated memory units, differential cross-attention on one shared cache, a
dense MLP in every layer — counted from shapes (multiply-add = 2). `m`: the
sizes token_check_ssm.model_sizes gives (the source's key names).

flops_tokens.py's twin for this trunk, and the same conventions:
everything is per ROW of the doubled guidance batch (one row = one frame's
L tokens through the trunk) unless it says otherwise; layers are counted
BY KIND (`layer_kind`, the source's rule), each as often as the stack has
it; norms, softmax, activations, λ and the sub-layer norm are not counted.

**Differential attention counts the VISIBLE query-key pairs, both maps**:
a pair of heads is two score products at the keys' width (head_dim) and
two value products at the value pair's (2 × head_dim) — (A¹ − λA²)·V as
A¹V − λ·A²V, the form a kernel that keeps one softmax map at a time
computes; an implementation that pads a 64-wide key to 128 lanes, or
multiplies masked pairs, does more than this and reads a lower share;
nothing can pass 100 %. Bytes: q in and both maps' outputs out for every
query pair, k and v in once a key/value pair, the keys some query sees.

**The selective scan is counted as the recurrence, whatever implements
it**, a (channel, state) element of one token: Δ·A, its exponential
(counted as ONE operation), exp·s, Δ·u (shared by the states: counted with
them all the same), ·B, the add, ·C, the sum over states, and D·u's
share: 9 operations. Bytes: u in at the compute type, Δ in and m out in
float32, B and C in float32, once each a token; the state in and out and A
once a row-layer. The table of peaks has no VPU or EUP peak: the share is
the distance to the HBM bound, and is expected low.

**The once-a-call pass stops at layer N/2 + 1**, and of that layer it runs
what its cache entry needs (the key and value projections): 18 layers at
the published depth, the last a sliver.
"""

from __future__ import annotations

RAY_CHANNELS = 144
SCAN_OPS_PER_ELEMENT = 9
KINDS = ("mamba", "attn_window", "attn_full", "gmu", "attn_cross")


def tokens_per_frame(m) -> int:
    return (m["side"] // m["patch_size"]) ** 2


def layer_kind(m, i: int) -> str:
    """Layer i's kind by the source's rule in num_hidden_layers and
    mb_per_layer (reference/p4f_ref.py's head)."""
    half = m["num_hidden_layers"] // 2
    slot = i % m["mb_per_layer"] == 0
    if i >= half + 2:
        return "gmu" if slot else "attn_cross"
    if slot:
        return "mamba"
    return "attn_full" if i == half + 1 else "attn_window"


def layers_of(m, kind: str):
    return [i for i in range(m["num_hidden_layers"])
            if layer_kind(m, i) == kind]


def last_cached_layer(m) -> int:
    """The layer the once-a-call pass stops at: the one whose keys and
    values are kept."""
    return m["num_hidden_layers"] // 2 + 1


def head_dim(m) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


# -- Mamba --------------------------------------------------------------------
def ssm_widths(m):
    """(channels C, states N, the step's rank R, taps K)."""
    return (m["mamba_expand"] * m["hidden_size"], m["mamba_d_state"],
            -(-m["hidden_size"] // 16), m["mamba_d_conv"])


def ssm_proj_params(m) -> int:
    """in (u and z), x (δ, B, C), dt, out."""
    H = m["hidden_size"]
    C, N, R, _ = ssm_widths(m)
    return H * 2 * C + C * (R + 2 * N) + R * C + C * H


def ssm_conv_flops(m, tokens: int) -> int:
    C, _, _, K = ssm_widths(m)
    return 2 * tokens * C * K


def ssm_core_flops(m, tokens: int) -> int:
    C, N, _, _ = ssm_widths(m)
    return SCAN_OPS_PER_ELEMENT * tokens * C * N


def ssm_core_bytes(m, tokens: int, itemsize=2) -> int:
    C, N, _, _ = ssm_widths(m)
    return tokens * (C * (itemsize + 4 + 4) + 2 * 4 * N) \
        + 3 * 4 * C * N + 4 * C


# -- differential attention ---------------------------------------------------
def visible_pairs(m, kind: str, k_frames: int) -> int:
    """(query, key) pairs one map of a layer of `kind` lets through, for
    one frame's L queries against k_frames × L keys (1: the conditioning
    frame's own pass; 2: a step): every key of the frames up to the
    query's own, less, in a window layer, those a window or more behind
    the query."""
    L = tokens_per_frame(m)
    keys, first = k_frames * L, (k_frames - 1) * L
    if kind != "attn_window":
        return L * keys
    W = m["sliding_window"]
    return sum(keys - max(first + r - W + 1, 0) for r in range(L))


def visible_keys(m, kind: str, k_frames: int) -> int:
    """Keys that at least one of the frame's queries sees."""
    L = tokens_per_frame(m)
    keys, first = k_frames * L, (k_frames - 1) * L
    if kind != "attn_window":
        return keys
    return keys - max(first - m["sliding_window"] + 1, 0)


def attn_proj_params(m, kind: str, cache_only=False) -> int:
    """qkv (q alone in a cross layer) and o; `cache_only`: k and v."""
    H, D = m["hidden_size"], head_dim(m)
    NH, NKV = m["num_attention_heads"], m["num_key_value_heads"]
    if cache_only:
        return H * 2 * NKV * D
    kv = 0 if kind == "attn_cross" else 2 * NKV * D
    return H * (NH * D + kv) + NH * D * H


def attn_flops(m, kind: str, k_frames: int) -> int:
    """Both maps of every query pair over the visible pairs: scores at
    head_dim, values at 2 × head_dim."""
    D = head_dim(m)
    return 2 * (m["num_attention_heads"] // 2) * 2 \
        * visible_pairs(m, kind, k_frames) * (D + 2 * D)


def attn_bytes(m, kind: str, k_frames: int, itemsize=2) -> int:
    L, D = tokens_per_frame(m), head_dim(m)
    NH, NKV = m["num_attention_heads"], m["num_key_value_heads"]
    return itemsize * D * (3 * NH * L
                           + 2 * NKV * visible_keys(m, kind, k_frames))


# -- the other halves ---------------------------------------------------------
def gmu_params(m) -> int:
    return 2 * m["hidden_size"] * ssm_widths(m)[0]


def dense_mlp_params(m) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


# -- a pass, a step, a call ---------------------------------------------------
def mixer_flops(m, i: int, k_frames: int, cache_only=False) -> float:
    """One row's L tokens through layer i's mixer against k_frames × L
    keys; `cache_only`: only what the layer's cache entry needs."""
    L, kind = tokens_per_frame(m), layer_kind(m, i)
    if kind == "mamba":
        return 2 * L * ssm_proj_params(m) + ssm_conv_flops(m, L) \
            + ssm_core_flops(m, L)
    if kind == "gmu":
        return 2 * L * gmu_params(m)
    if cache_only:
        return 2 * L * attn_proj_params(m, kind, True)
    return 2 * L * attn_proj_params(m, kind) + attn_flops(m, kind, k_frames)


def frame_pass_flops(m, k_frames: int) -> float:
    """One row's L tokens through the stack against k_frames × L keys,
    with the adapters and the logsnr MLP. The once-a-call pass (k_frames
    1) stops at the last layer that keeps a cache entry and runs of it
    what that entry needs."""
    L, H = tokens_per_frame(m), m["hidden_size"]
    n = m["num_hidden_layers"] if k_frames > 1 else last_cached_layer(m) + 1
    stop = k_frames == 1
    trunk = sum(mixer_flops(m, i, k_frames, stop and i == n - 1)
                for i in range(n)) \
        + (n - int(stop)) * 2 * L * dense_mlp_params(m)
    pix = 3 * m["patch_size"] ** 2
    adapters = 2 * L * H * (pix + RAY_CHANNELS * m["patch_size"] ** 2) \
        + 2 * 2 * H * H
    return trunk + adapters


def per_view_step(m, steps: int, views: int = 1) -> float:
    """Operations per view-step of a sampler call: a guided step is two
    rows over the target's tokens against two frames, plus the output
    adapter; the once-a-call pass of the conditioning frame (two rows) is
    spread over the call's steps."""
    L, H = tokens_per_frame(m), m["hidden_size"]
    out = 2 * L * H * 3 * m["patch_size"] ** 2
    step = 2 * (frame_pass_flops(m, 2) + out)
    once = 2 * frame_pass_flops(m, 1)
    return step + once / steps


def ssm_core_call_work(m, steps: int, rows: int):
    """(operations, bytes) of a sampler call under `lk.ssm_core`: every
    step's scan of every Mamba layer, and the once-a-call pass's."""
    L = tokens_per_frame(m)
    n = len(layers_of(m, "mamba")) * (steps + 1)
    return rows * n * ssm_core_flops(m, L), rows * n * ssm_core_bytes(m, L)


def attn_call_work(m, steps: int, rows: int, stamp: str):
    """(operations, bytes) of a sampler call's differential attention
    under one stamp: `attn_window` covers the passes in which a window
    layer's window binds (at the cell's size every step's and the
    once-a-call pass's, a frame being longer than the window),
    `attn_full` layer N/2 + 1's steps (its once-a-call attention feeds
    nothing) and any window layer's pass whose window does not bind,
    `attn_cross` the cross layers' steps."""
    L = tokens_per_frame(m)
    flops = nbytes = 0
    for i in range(m["num_hidden_layers"]):
        kind = layer_kind(m, i)
        if not kind.startswith("attn"):
            continue
        once = int(i < last_cached_layer(m))
        for k_frames, times in ((1, once), (2, steps)):
            binds = visible_pairs(m, kind, k_frames) < L * k_frames * L
            under = kind if kind == "attn_cross" else \
                "attn_window" if binds else "attn_full"
            if under == stamp:
                flops += times * attn_flops(m, kind, k_frames)
                nbytes += times * attn_bytes(m, kind, k_frames)
    return rows * flops, rows * nbytes

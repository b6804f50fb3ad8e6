"""Operations and bytes the token denoiser needs on Olmo-Hybrid's stack —
Gated DeltaNet layers, full attention under a QK norm, a dense MLP in every
layer — counted from shapes (multiply-add = 2). `m`: the sizes
token_check_gdn.model_sizes gives (the source's key names).

flops_tokens.py's twin for this trunk, and the same conventions:
everything is per ROW of the doubled guidance batch (one row = one frame's
L tokens through the trunk) unless it says otherwise; layers are counted
BY KIND (`layer_types`, as published), each as often as the stack has it;
norms, softmax, activations, the decay's exponentials and the gates are not
counted. Attention counts the visible query-key pairs (the frame rule
hides none of a step's: target queries see both frames), every one of the
30 key/value heads its own.

**The delta rule's scan is counted in its chunked scalar-decay form,
whatever implements it** (ops/gdn.py's head has the equations), a chunk of
C tokens of one head, keys d_k wide on values d_v wide, multiply-adds:

    K̄·S_0, Q̄·S_0, K̂ᵀ·U                      3 · C·d_k·d_v
    strict_tril(K Kᵀ), tril(Q Kᵀ)             2 · C²/2 · d_k
    the solve (I + A)·U = …, tril(·)·U        2 · C²/2 · d_v

— triangles as triangles, ONE pass: an implementation that multiplies
whole squares, inverts (I + A) outright, makes a chunk's transition matrix,
or takes several MXU passes for float32 does more than this and reads a
lower share; nothing can pass 100 %. Bytes: q, k, v in and o out at the
compute type, g and β in float32, the state in and out in float32, once
each.

**The once-a-call pass runs what its cache entries need**: every layer
whole up to the last, and of the last — a full layer, whose attention of
the conditioning frame feeds nothing — the key and value projections.
"""

from __future__ import annotations

RAY_CHANNELS = 144
CHUNK = 64


def tokens_per_frame(m) -> int:
    return (m["side"] // m["patch_size"]) ** 2


def is_full_attention(m, i: int) -> bool:
    return m["layer_types"][i] == "full_attention"


def layers_of(m, full: bool):
    return [i for i in range(m["num_hidden_layers"])
            if is_full_attention(m, i) == full]


def head_dim(m) -> int:
    return m["hidden_size"] // m["num_attention_heads"]


# -- Gated DeltaNet -----------------------------------------------------------
def gdn_widths(m):
    """(heads, a key head's width, a value head's, taps)."""
    return (m["linear_num_value_heads"], m["linear_key_head_dim"],
            m["linear_value_head_dim"], m["linear_conv_kernel_dim"])


def gdn_proj_params(m) -> int:
    """q, k; v, the output gate, o; the decay's and β's a number a head."""
    H = m["hidden_size"]
    NH, dk, dv, _ = gdn_widths(m)
    return H * (2 * NH * dk + 3 * NH * dv + 2 * NH)


def gdn_conv_flops(m, tokens: int) -> int:
    NH, dk, dv, K = gdn_widths(m)
    return 2 * tokens * NH * (2 * dk + dv) * K


def gdn_core_flops(m, tokens: int) -> int:
    """The chunked scan over `tokens` of one row, every head."""
    NH, dk, dv, _ = gdn_widths(m)
    C = CHUNK
    chunks = -(-tokens // C)
    macs = 3 * C * dk * dv + 2 * (C * C // 2) * dk + 2 * (C * C // 2) * dv
    return 2 * NH * chunks * macs


def gdn_core_bytes(m, tokens: int, itemsize=2) -> int:
    NH, dk, dv, _ = gdn_widths(m)
    per_token = 2 * (dk + dv) * itemsize + 4 + 4
    return NH * (tokens * per_token + 2 * 4 * dk * dv)


# -- full attention -----------------------------------------------------------
def attn_proj_params(m, cache_only=False) -> int:
    """q, k, v and o; `cache_only`: k and v."""
    H, D = m["hidden_size"], head_dim(m)
    kv = 2 * H * m["num_key_value_heads"] * D
    return kv if cache_only else kv + 2 * H * m["num_attention_heads"] * D


def attn_flops(m, k_frames: int) -> int:
    """One frame's L queries on k_frames × L keys, every pair visible:
    scores and weighted values at head_dim."""
    L = tokens_per_frame(m)
    return 2 * m["num_attention_heads"] * L * k_frames * L * 2 * head_dim(m)


def attn_bytes(m, k_frames: int, itemsize=2) -> int:
    """q in and o out, k and v in, once each."""
    L, D = tokens_per_frame(m), head_dim(m)
    return itemsize * D * (2 * m["num_attention_heads"] * L
                           + 2 * m["num_key_value_heads"] * k_frames * L)


def dense_mlp_params(m) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


# -- a pass, a step, a call ---------------------------------------------------
def mixer_flops(m, i: int, k_frames: int, cache_only=False) -> float:
    """One row's L tokens through layer i's mixer against k_frames × L
    keys; `cache_only`: only what a full layer's cache entry needs."""
    L = tokens_per_frame(m)
    if not is_full_attention(m, i):
        return 2 * L * gdn_proj_params(m) + gdn_conv_flops(m, L) \
            + gdn_core_flops(m, L)
    if cache_only:
        return 2 * L * attn_proj_params(m, True)
    return 2 * L * attn_proj_params(m) + attn_flops(m, k_frames)


def cut_short(m, i: int, k_frames: int) -> bool:
    """Layer i of the once-a-call pass runs only what its cache needs: the
    last layer, where it is a full one."""
    return k_frames == 1 and i == m["num_hidden_layers"] - 1 \
        and is_full_attention(m, i)


def frame_pass_flops(m, k_frames: int) -> float:
    """One row's L tokens through the stack against k_frames × L keys (1:
    the once-a-call pass; 2: a step), with the adapters and the logsnr
    MLP."""
    L, H = tokens_per_frame(m), m["hidden_size"]
    trunk = 0.0
    for i in range(m["num_hidden_layers"]):
        short = cut_short(m, i, k_frames)
        trunk += mixer_flops(m, i, k_frames, short)
        if not short:
            trunk += 2 * L * dense_mlp_params(m)
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


def gdn_core_call_work(m, steps: int, rows: int):
    """(operations, bytes) of a sampler call under `lk.gdn_core`: every
    step's scan of every delta-rule layer, and the once-a-call pass's."""
    L = tokens_per_frame(m)
    n = len(layers_of(m, False)) * (steps + 1)
    return rows * n * gdn_core_flops(m, L), rows * n * gdn_core_bytes(m, L)


def attn_call_work(m, steps: int, rows: int):
    """(operations, bytes) of a sampler call under `lk.attn_full`: every
    full layer's every step on both frames' keys, and the once-a-call
    pass's on the conditioning frame's own — less the last layer's, which
    feeds nothing."""
    flops = nbytes = 0
    for i in layers_of(m, True):
        once = int(not cut_short(m, i, 1))
        flops += steps * attn_flops(m, 2) + once * attn_flops(m, 1)
        nbytes += steps * attn_bytes(m, 2) + once * attn_bytes(m, 1)
    return rows * flops, rows * nbytes

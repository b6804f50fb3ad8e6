"""Operations and bytes the token denoiser needs on a trunk with
grouped-query attention, per-layer windows and an all-held ReGLU expert
layer (SmallThinker's layer), counted from shapes (multiply-add = 2).
`m`: the sizes token_check_gqa.model_sizes gives (the source's key names).

flops_tokens.py's twin for this trunk, and the same conventions:
everything is per ROW of the doubled guidance batch (one row = one
frame's L tokens through the trunk) unless it says otherwise; norms,
softmax, rotary, activations, sorting and gathers are not counted.
Attention counts the VISIBLE query-key pairs only — what the frame rule
and a layer's window let through, whatever implements it: a kernel that
skips a masked block does the same work in less time and reads a higher
share, a kernel that multiplies masked pairs reads a lower one, and
nothing can pass 100 %. The expert layer counts the assignments to held
experts (all of them in this configuration: top-k a token).
"""

from __future__ import annotations

RAY_CHANNELS = 144


def tokens_per_frame(m) -> int:
    return (m["side"] // m["patch_size"]) ** 2


def windowed(m, i: int) -> bool:
    return bool(m["sliding_window_layout"][i])


def visible_pairs(m, i: int, k_frames: int) -> int:
    """(query, key) pairs one head of layer i lets through, for one frame's
    L queries against k_frames × L keys (1: the conditioning frame's own
    pass; 2: a step, [cache ; own]): every key of the frames up to the
    query's own, less, in a window layer, those a window or more behind
    the query."""
    L = tokens_per_frame(m)
    keys, first = k_frames * L, (k_frames - 1) * L
    if not windowed(m, i):
        return L * keys
    W = m["sliding_window_size"]
    return sum(keys - max(first + r - W + 1, 0) for r in range(L))


def visible_keys(m, i: int, k_frames: int) -> int:
    """Keys of layer i that at least one of the frame's queries sees."""
    L = tokens_per_frame(m)
    keys, first = k_frames * L, (k_frames - 1) * L
    if not windowed(m, i):
        return keys
    return keys - max(first - m["sliding_window_size"] + 1, 0)


def attn_flops(m, i: int, k_frames: int) -> int:
    """Scores and weighted values of the visible pairs, all query heads."""
    return 2 * m["num_attention_heads"] * visible_pairs(m, i, k_frames) \
        * 2 * m["head_dim"]


def attn_bytes(m, i: int, k_frames: int, itemsize=2) -> int:
    """q in and o out for every query head; k and v in once a key/value
    head (a group's query heads share them), the keys some query sees."""
    L, D = tokens_per_frame(m), m["head_dim"]
    return itemsize * D * (2 * m["num_attention_heads"] * L
                           + 2 * m["num_key_value_heads"]
                           * visible_keys(m, i, k_frames))


def layer_dense_params(m) -> int:
    """Parameters a token passes in one layer outside the experts and the
    attention products: q, k, v, o and the router."""
    H, D = m["hidden_size"], m["head_dim"]
    return H * D * (2 * m["num_attention_heads"]
                    + 2 * m["num_key_value_heads"]) \
        + H * m["moe_num_primary_experts"]


def expert_params(m) -> int:
    return 3 * m["hidden_size"] * m["moe_ffn_hidden_size"]


def held_assignments(m, tokens: int) -> float:
    return tokens * m["moe_num_active_primary_experts"] \
        * m["held_experts"][1] / m["moe_num_primary_experts"]


def frame_pass_flops(m, k_frames: int) -> float:
    """One row's L tokens through all layers against k_frames × L keys,
    with the adapters and the logsnr MLP. The once-a-call pass (k_frames
    1) leaves a cache and nothing else: of the last layer it runs the key
    and value projections only."""
    L, H = tokens_per_frame(m), m["hidden_size"]
    n = m["num_hidden_layers"]
    whole = n if k_frames > 1 else n - 1
    kv_only = 2 * L * H * 2 * m["num_key_value_heads"] * m["head_dim"]
    trunk = sum(attn_flops(m, i, k_frames) for i in range(whole)) \
        + whole * (2 * L * layer_dense_params(m)
                   + 2.0 * held_assignments(m, L) * expert_params(m)) \
        + (n - whole) * kv_only
    pix = 3 * m["patch_size"] ** 2
    adapters = 2 * L * H * (pix + RAY_CHANNELS * m["patch_size"] ** 2) \
        + 2 * 2 * H * H
    return trunk + adapters


def per_view_step(m, steps: int) -> float:
    """Operations per view-step of a sampler call: a guided step is two
    rows over the target's tokens against two frames of keys, plus the
    output adapter; the once-a-call pass of the conditioning frame (two
    rows) is spread over the call's steps."""
    L, H = tokens_per_frame(m), m["hidden_size"]
    out = 2 * L * H * 3 * m["patch_size"] ** 2
    step = 2 * (frame_pass_flops(m, 2) + out)
    once = 2 * frame_pass_flops(m, 1)
    return step + once / steps


def attn_call_work(m, steps: int, rows: int, window: bool):
    """(operations, bytes) of a sampler call's attention under one stamp:
    `lk.attn_window` covers the passes in which a layer's window binds (at
    the cell's size every step's pass over the window layers), and
    `lk.attn_full` the others (every step's pass over the layers without
    a window, and the once-a-call pass of all layers but the last, whose
    attention feeds nothing, where one frame is no longer than the
    window)."""
    L = tokens_per_frame(m)
    flops = nbytes = 0
    last = m["num_hidden_layers"] - 1
    for i in range(m["num_hidden_layers"]):
        # the once-a-call pass stops at the last layer's keys and values
        for k_frames, times in ((1, int(i < last)), (2, steps)):
            binds = visible_pairs(m, i, k_frames) < L * k_frames * L
            if binds == window:
                flops += times * attn_flops(m, i, k_frames)
                nbytes += times * attn_bytes(m, i, k_frames)
    return rows * flops, rows * nbytes

"""Operations and bytes the token denoiser needs on Laguna's stack —
grouped-query attention whose query-head count, mask and feed-forward
depend on the layer — counted from shapes (multiply-add = 2). `m`: the
sizes token_check_headmix.model_sizes gives (the source's key names).

flops_tokens_gqa.py's twin for this trunk, and the same conventions:
everything is per ROW of the doubled guidance batch (one row = one frame's
L tokens through the trunk) unless it says otherwise; norms, softmax,
rotary, the head gate's sigmoid and product, activations, sorting and
gathers are not counted. Attention counts the VISIBLE query-key pairs only
— what the frame rule and a layer's window let through — at THAT layer's
head count: 72 under the window, 48 without. The expert layers count the
assignments to held experts: the run's own where a reader has them, top-k
× held ÷ the router's width otherwise.

**The once-a-call pass** leaves a cache and nothing else: of the last layer
it runs the key and value projections only.
"""

from __future__ import annotations

from flops_tokens import (  # noqa: F401 — one expert layer, one count
    RAY_CHANNELS, expert_params, moe_experts_bytes, moe_experts_flops)


def tokens_per_frame(m) -> int:
    return (m["side"] // m["patch_size"]) ** 2


def heads(m, i: int) -> int:
    return m["num_attention_heads_per_layer"][i]


def windowed(m, i: int) -> bool:
    return m["layer_types"][i] == "sliding_attention"


def is_dense(m, i: int) -> bool:
    return m["mlp_layer_types"][i] == "dense"


def expert_layers(m) -> list:
    return [i for i in range(m["num_hidden_layers"]) if not is_dense(m, i)]


def visible_pairs(m, i: int, k_frames: int) -> int:
    """(query, key) pairs one head of layer i lets through, for one frame's
    L queries against k_frames × L keys (1: the conditioning frame's own
    pass; 2: a step, [cache ; own]): every key of the frames up to the
    query's own, less, in a window layer, those a window or more behind
    the query — in its own frame too, the window being shorter than a
    frame."""
    L = tokens_per_frame(m)
    keys, first = k_frames * L, (k_frames - 1) * L
    if not windowed(m, i):
        return L * keys
    W = m["sliding_window"]
    return sum(keys - max(first + r - W + 1, 0) for r in range(L))


def visible_keys(m, i: int, k_frames: int) -> int:
    """Keys of layer i that at least one of the frame's queries sees."""
    L = tokens_per_frame(m)
    keys, first = k_frames * L, (k_frames - 1) * L
    if not windowed(m, i):
        return keys
    return keys - max(first - m["sliding_window"] + 1, 0)


def attn_flops(m, i: int, k_frames: int) -> int:
    """Scores and weighted values of the visible pairs, layer i's heads."""
    return 2 * heads(m, i) * visible_pairs(m, i, k_frames) \
        * 2 * m["head_dim"]


def attn_bytes(m, i: int, k_frames: int, itemsize=2) -> int:
    """q in and o out for every query head of layer i; k and v in once a
    key/value head (a group's query heads share them), the keys some query
    sees."""
    L, D = tokens_per_frame(m), m["head_dim"]
    return itemsize * D * (2 * heads(m, i) * L
                           + 2 * m["num_key_value_heads"]
                           * visible_keys(m, i, k_frames))


def kv_params(m) -> int:
    return 2 * m["hidden_size"] * m["num_key_value_heads"] * m["head_dim"]


def attn_proj_params(m, i: int) -> int:
    """q, o and the head gate at layer i's head count, k and v."""
    H, N = m["hidden_size"], heads(m, i)
    return 2 * H * N * m["head_dim"] + H * N + kv_params(m)


def dense_mlp_params(m) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def shared_params(m) -> int:
    return 3 * m["hidden_size"] * m["shared_expert_intermediate_size"]


def router_params(m) -> int:
    return m["hidden_size"] * m["num_experts"]


def expected_held_per_token(m) -> float:
    """Under even routing: top-k × held ÷ the router's width."""
    return m["num_experts_per_tok"] * m["held_experts"][1] / m["num_experts"]


def layer_flops(m, i: int, k_frames: int, held_per_token: float) -> float:
    """One row's L tokens through layer i against k_frames × L keys."""
    L = tokens_per_frame(m)
    flops = 2 * L * attn_proj_params(m, i) + attn_flops(m, i, k_frames)
    if is_dense(m, i):
        return flops + 2 * L * dense_mlp_params(m)
    return flops + 2 * L * (router_params(m) + shared_params(m)) \
        + moe_experts_flops(m, L * held_per_token)


def frame_pass_flops(m, k_frames: int, held_per_token=None) -> float:
    """One row's L tokens through the stack against k_frames × L keys,
    with the adapters and the logsnr MLP. The once-a-call pass (k_frames
    1) runs of the last layer its key and value projections alone."""
    if held_per_token is None:
        held_per_token = expected_held_per_token(m)
    L, H = tokens_per_frame(m), m["hidden_size"]
    n = m["num_hidden_layers"]
    whole = n if k_frames > 1 else n - 1
    trunk = sum(layer_flops(m, i, k_frames, held_per_token)
                for i in range(whole)) + (n - whole) * 2 * L * kv_params(m)
    pix = 3 * m["patch_size"] ** 2
    adapters = 2 * L * H * (pix + RAY_CHANNELS * m["patch_size"] ** 2) \
        + 2 * 2 * H * H
    return trunk + adapters


def per_view_step(m, steps: int, held_per_token=None) -> float:
    """Operations per view-step of a sampler call: a guided step is two
    rows over the target's tokens against two frames of keys, plus the
    output adapter; the once-a-call pass of the conditioning frame (two
    rows) is spread over the call's steps."""
    L, H = tokens_per_frame(m), m["hidden_size"]
    out = 2 * L * H * 3 * m["patch_size"] ** 2
    step = 2 * (frame_pass_flops(m, 2, held_per_token) + out)
    once = 2 * frame_pass_flops(m, 1, held_per_token)
    return step + once / steps


def attn_call_work(m, steps: int, rows: int, window: bool):
    """(operations, bytes) of a sampler call's attention under one stamp:
    `lk.attn_window` covers the passes in which a layer's window binds —
    the window layers' in every step AND in the once-a-call pass, the
    window being shorter than a frame — and `lk.attn_full` the full
    layers'; the once-a-call pass stops at the last layer's keys and
    values, so that layer's attention is counted in the steps alone."""
    L = tokens_per_frame(m)
    flops = nbytes = 0
    last = m["num_hidden_layers"] - 1
    for i in range(m["num_hidden_layers"]):
        for k_frames, times in ((1, int(i < last)), (2, steps)):
            binds = visible_pairs(m, i, k_frames) < L * k_frames * L
            if binds == window:
                flops += times * attn_flops(m, i, k_frames)
                nbytes += times * attn_bytes(m, i, k_frames)
    return rows * flops, rows * nbytes


def expert_passes(m, steps: int) -> int:
    """Expert-layer passes of a call, each over every row: every step's,
    and the once-a-call pass's of the expert layers before the last
    layer."""
    with_experts = expert_layers(m)
    n = m["num_hidden_layers"]
    return steps * len(with_experts) + sum(i < n - 1 for i in with_experts)

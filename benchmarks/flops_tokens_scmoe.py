"""Operations and bytes the token denoiser needs on LongCat-Flash's stack —
the shortcut-connected double layer: two latent attentions with rotary, two
dense MLPs, one expert branch over a router wider than its experts —
counted from shapes (multiply-add = 2). `m`: the sizes
token_check_scmoe.model_sizes gives (the source's key names).

flops_tokens.py's twin for this trunk, and the same conventions:
everything is per ROW of the doubled guidance batch (one row = one frame's
L tokens through the trunk) unless it says otherwise; norms, softmax,
rotary, activations, sorting and gathers are not counted — nor is the
identity experts' part, which is a product a token's element and no
matmul. W_qb is counted ONCE a query token (the program multiplies against
it twice to rotate q where its product writes it; that is the program's
way, not the algorithm's need). Attention counts the visible query-key
pairs (the frame rule hides none of a step's: target queries see both
frames). The expert branch counts the assignments to HELD real experts
only: a choice of an absent expert or of an identity costs nothing here.

**The once-a-call pass** leaves two latents a layer and nothing else: of
the LAST layer it runs the first attention and the first MLP whole (the
second attention's input), the second attention's down-projection alone,
and neither the router, the experts nor the second MLP — the program
builds them and the compiler drops what nothing reads.
"""

from __future__ import annotations

from flops_tokens import (  # noqa: F401 — one expert layer, one count
    RAY_CHANNELS, expert_params, kv_up_params, mla_core_bytes,
    mla_core_flops, moe_experts_bytes, moe_experts_flops)


def tokens_per_frame(m) -> int:
    return (m["side"] // m["patch_size"]) ** 2


def router_width(m) -> int:
    return m["n_routed_experts"] + m["zero_expert_num"]


def mla_proj_params(m, cache_only=False) -> int:
    """q_a, q_b, kv_a and o of ONE attention (every QUERY token); kv_b is
    counted per KEY token (`kv_up_params`). `cache_only`: kv_a alone."""
    H, NH = m["hidden_size"], m["num_attention_heads"]
    kv_a = H * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
    if cache_only:
        return kv_a
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return H * m["q_lora_rank"] + m["q_lora_rank"] * NH * qk + kv_a \
        + NH * m["v_head_dim"] * H


def dense_mlp_params(m) -> int:
    return 3 * m["hidden_size"] * m["ffn_hidden_size"]


def router_params(m) -> int:
    return m["hidden_size"] * router_width(m)


def expected_held_per_token(m) -> float:
    """Under uniform choices: top-k × held ÷ the router's width."""
    return m["moe_topk"] * m["held_experts"][1] / router_width(m)


def attention_flops(m, k_frames: int) -> float:
    """One attention sublayer over one row's L tokens against k_frames × L
    keys: its projections, the keys' and values' up-projection, the
    pairs."""
    L = tokens_per_frame(m)
    return 2 * L * mla_proj_params(m) + 2 * k_frames * L * kv_up_params(m) \
        + mla_core_flops(m, L, k_frames * L)


def layer_flops(m, k_frames: int, held_per_token: float,
                cache_only=False) -> float:
    """One row's L tokens through one double layer against k_frames × L
    keys; `cache_only`: only what the layer's two latents need (the
    module's head)."""
    L = tokens_per_frame(m)
    mlp = 2 * L * dense_mlp_params(m)
    if cache_only:
        return attention_flops(m, k_frames) + mlp \
            + 2 * L * mla_proj_params(m, cache_only=True)
    return 2 * attention_flops(m, k_frames) + 2 * mlp \
        + 2 * L * router_params(m) \
        + moe_experts_flops(m, L * held_per_token)


def frame_pass_flops(m, k_frames: int, held_per_token=None) -> float:
    """One row's L tokens through the stack against k_frames × L keys,
    with the adapters and the logsnr MLP. The once-a-call pass (k_frames
    1) runs of the last layer what its latents need."""
    if held_per_token is None:
        held_per_token = expected_held_per_token(m)
    n = m["num_layers"]
    trunk = sum(layer_flops(m, k_frames, held_per_token,
                            cache_only=k_frames == 1 and i == n - 1)
                for i in range(n))
    L, H = tokens_per_frame(m), m["hidden_size"]
    pix = 3 * m["patch_size"] ** 2
    adapters = 2 * L * H * (pix + RAY_CHANNELS * m["patch_size"] ** 2) \
        + 2 * 2 * H * H
    return trunk + adapters


def per_view_step(m, steps: int, held_per_token=None) -> float:
    """Operations per view-step of a sampler call: a guided step is two
    rows over the target's tokens against two frames, plus the output
    adapter; the once-a-call pass of the conditioning frame (two rows) is
    spread over the call's steps."""
    L, H = tokens_per_frame(m), m["hidden_size"]
    out = 2 * L * H * 3 * m["patch_size"] ** 2
    step = 2 * (frame_pass_flops(m, 2, held_per_token) + out)
    once = 2 * frame_pass_flops(m, 1, held_per_token)
    return step + once / steps


def mla_core_call_work(m, steps: int, rows: int):
    """(operations, bytes) of a sampler call under `lk.mla_core`: every
    step's target queries against [cache ; own] in both attentions of
    every layer, and the once-a-call frame against itself in both — of the
    last layer in the first alone (the second's would feed nothing)."""
    L, n = tokens_per_frame(m), m["num_layers"]
    once = 2 * n - 1
    flops = steps * 2 * n * mla_core_flops(m, L, 2 * L) \
        + once * mla_core_flops(m, L, L)
    nbytes = steps * 2 * n * mla_core_bytes(m, L, 2 * L) \
        + once * mla_core_bytes(m, L, L)
    return rows * flops, rows * nbytes


def expert_passes(m, steps: int) -> int:
    """Expert-branch passes of a call, each over every row: every step's
    of every layer, and the once-a-call pass's of the layers before the
    last."""
    return (steps + 1) * m["num_layers"] - 1

"""Operations and bytes the token denoiser needs, counted from shapes
(multiply-add = 2). `m`: the sizes token_check.model_sizes gives.

Everything is per ROW of the doubled guidance batch (one row = one frame's
L tokens through the trunk) unless it says otherwise. Counted: every dense
layer, the attention products, the expert products of the LOCAL experts
only — an assignment to an expert that is not held here costs nothing here.
Each of a token's top-k choices lands on a held expert with probability
held / n_routed_experts under even routing, which is what the counts
assume (`expected_held_assignments`); the readers that have the run's own
routing counts use those instead. Not counted: norms, softmax, rotary,
activations, sorting and gathers (no matmul).
"""

from __future__ import annotations

RAY_CHANNELS = 144


def tokens_per_frame(m) -> int:
    return (m["side"] // m["patch_size"]) ** 2


def layer_dense_params(m) -> int:
    """Parameters a token passes in one layer outside the routed experts
    and the attention products: the five MLA projections, the router, the
    shared expert."""
    H, NH = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    own = (H * m["q_lora_rank"] + m["q_lora_rank"] * NH * qk
           + H * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
           + NH * m["v_head_dim"] * H
           + H * m["n_routed_experts"]
           + 3 * H * m["moe_intermediate_size"] * m["n_shared_experts"])
    return own


def kv_up_params(m) -> int:
    """kv_b: applied to every KEY token's latent (cached ones included)."""
    return m["kv_lora_rank"] * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["v_head_dim"])


def expert_params(m) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def expected_held_assignments(m, tokens: int) -> float:
    return tokens * m["num_experts_per_tok"] * m["held_experts"][1] \
        / m["n_routed_experts"]


def mla_core_flops(m, q_tokens: int, k_tokens: int) -> int:
    """scores (qk_head_dim) and weighted values (v_head_dim), all heads."""
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return 2 * m["num_attention_heads"] * q_tokens * k_tokens * (
        qk + m["v_head_dim"])


def mla_core_bytes(m, q_tokens: int, k_tokens: int, itemsize=2) -> int:
    """q in, k and v in, o out, once each."""
    NH = m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return itemsize * NH * (q_tokens * (qk + m["v_head_dim"])
                            + k_tokens * (qk + m["v_head_dim"]))


def moe_experts_flops(m, held_assignments: float) -> float:
    return 2.0 * held_assignments * expert_params(m)


def moe_experts_bytes(m, held_assignments: float, experts_hit: int,
                      itemsize=2) -> float:
    """The weights of the experts that got a token, read once; each
    assignment's row in (hidden), the two hidden-width intermediates out
    and in again, the result out (hidden)."""
    H, I = m["hidden_size"], m["moe_intermediate_size"]
    return itemsize * (experts_hit * expert_params(m)
                       + held_assignments * (2 * H + 4 * I))


def frame_pass_flops(m, k_frames: int, held_assignments=None) -> float:
    """One row's L tokens through all layers against k_frames × L keys
    (1: the conditioning frame's own pass; 2: a step, [cache ; own]),
    with the adapters and the logsnr MLP."""
    L = tokens_per_frame(m)
    if held_assignments is None:
        held_assignments = expected_held_assignments(m, L)
    per_layer = (2 * L * layer_dense_params(m)
                 + 2 * k_frames * L * kv_up_params(m)
                 + mla_core_flops(m, L, k_frames * L)
                 + moe_experts_flops(m, held_assignments))
    pix = 3 * m["patch_size"] ** 2
    H = m["hidden_size"]
    adapters = 2 * L * H * (pix + RAY_CHANNELS * m["patch_size"] ** 2) \
        + 2 * 2 * H * H
    return m["num_hidden_layers"] * per_layer + adapters


def per_view_step(m, steps: int) -> float:
    """Operations per view-step of a sampler call: a guided step is two
    rows (conditional, unconditional) over the target's tokens against
    two frames of keys, plus the output adapter; the once-a-call pass of
    the conditioning frame (two rows) is spread over the call's steps."""
    L, H = tokens_per_frame(m), m["hidden_size"]
    out = 2 * L * H * 3 * m["patch_size"] ** 2
    step = 2 * (frame_pass_flops(m, 2) + out)
    once = 2 * frame_pass_flops(m, 1)
    return step + once / steps

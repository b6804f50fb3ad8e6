"""Operations and bytes the token denoiser needs on Kimi-Linear's stack —
KDA layers, latent attention without a positional term, a leading dense
MLP, sigmoid-routed experts with one shared — counted from shapes
(multiply-add = 2). `m`: the sizes token_check_kda.model_sizes gives (the
source's key names).

flops_tokens.py's twin for this trunk, and the same conventions:
everything is per ROW of the doubled guidance batch (one row = one frame's
L tokens through the trunk) unless it says otherwise; layers are counted
BY KIND, each as often as the stack has it; norms, softmax, activations,
the decay's exponentials, sorting and gathers are not counted. Attention
counts the visible query-key pairs (the frame rule hides none of a step's:
target queries see both frames). The expert layers count the assignments
to HELD experts only.

**KDA's scan is counted in its chunked form, whatever implements it**
(ops/kda.py's head has the equations), a chunk of C tokens of one head,
multiply-adds:

    K̄·S_0, Q̄·S_0, (Γ_C ⊙ K̃)ᵀ·U             3 · C·d_k·d_v
    strict_tril(K̄K̃ᵀ), tril(Q̄K̃ᵀ)            2 · C²/2 · d_k
    the solve (I + A)·U = …, tril(·)·U        2 · C²/2 · d_v

— triangles as triangles: an implementation that multiplies whole squares,
inverts (I + A) outright, or takes several MXU passes for float32 does
more than this and reads a lower share; nothing can pass 100 %. Bytes: q,
k, v in and o out at the compute type, g and β in float32, the state in
and out in float32, once each.
"""

from __future__ import annotations

from flops_tokens import (  # noqa: F401 — one expert layer, one count
    RAY_CHANNELS, expert_params, moe_experts_bytes, moe_experts_flops)

CHUNK = 64


def tokens_per_frame(m) -> int:
    return (m["side"] // m["patch_size"]) ** 2


def is_full_attention(m, i: int) -> bool:
    return i + 1 in m["linear_attn_config"]["full_attn_layers"]


def is_dense(m, i: int) -> bool:
    return i < m["first_k_dense_replace"]


def layers_of(m, full: bool):
    return [i for i in range(m["num_hidden_layers"])
            if is_full_attention(m, i) == full]


def expert_layers(m):
    return [i for i in range(m["num_hidden_layers"]) if not is_dense(m, i)]


# -- KDA ----------------------------------------------------------------------
def kda_widths(m):
    lin = m["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"]


def kda_proj_params(m, cache_only=False) -> int:
    """Parameters a token passes in a KDA layer's projections: q, k, v,
    the decay's low-rank pair, β — and, unless only the cache is wanted
    (the once-a-call pass of the last layer), the gate's pair and o."""
    H = m["hidden_size"]
    NH, D = kda_widths(m)
    need = 3 * H * NH * D + H * D + D * NH * D + H * NH
    if not cache_only:
        need += H * D + D * NH * D + NH * D * H
    return need


def kda_conv_flops(m, tokens: int) -> int:
    NH, D = kda_widths(m)
    K = m["linear_attn_config"]["short_conv_kernel_size"]
    return 2 * tokens * 3 * NH * D * K


def kda_core_flops(m, tokens: int, output=True) -> int:
    """The chunked scan over `tokens` of one row, every head; without
    `output` only what the final state needs (the module's head)."""
    NH, D = kda_widths(m)
    C = CHUNK
    chunks = -(-tokens // C)
    macs = (3 if output else 2) * C * D * D \
        + (2 if output else 1) * (C * C // 2) * 2 * D
    return 2 * NH * chunks * macs


def kda_core_bytes(m, tokens: int, itemsize=2, output=True) -> int:
    NH, D = kda_widths(m)
    per_token = (3 + int(output)) * D * itemsize + 4 * D + 4
    return NH * (tokens * per_token + 2 * 4 * D * D)


# -- latent attention ---------------------------------------------------------
def mla_proj_params(m, cache_only=False) -> int:
    """q, kv_a and o (every QUERY token); kv_b is counted per KEY token."""
    H, NH = m["hidden_size"], m["num_attention_heads"]
    kv_a = H * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
    if cache_only:
        return kv_a
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return H * NH * qk + kv_a + NH * m["v_head_dim"] * H


def kv_up_params(m) -> int:
    return m["kv_lora_rank"] * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["v_head_dim"])


def mla_core_flops(m, q_tokens: int, k_tokens: int) -> int:
    """Scores at the keys' width, weighted values at the values'."""
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return 2 * m["num_attention_heads"] * q_tokens * k_tokens * (
        qk + m["v_head_dim"])


def mla_core_bytes(m, q_tokens: int, k_tokens: int, itemsize=2) -> int:
    """q in and o out, k and v in, once each."""
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return itemsize * m["num_attention_heads"] * (
        (q_tokens + k_tokens) * (qk + m["v_head_dim"]))


# -- the feed-forward layers --------------------------------------------------
def dense_mlp_params(m) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def moe_dense_params(m) -> int:
    """The router and the shared expert(s): every token passes them."""
    return m["hidden_size"] * m["num_experts"] \
        + expert_params(m) * m["num_shared_experts"]


def expected_held_per_token(m) -> float:
    return m["num_experts_per_token"] * m["held_experts"][1] \
        / m["num_experts"]


# -- a pass, a step, a call ---------------------------------------------------
def layer_flops(m, i: int, k_frames: int, held_per_token: float,
                cache_only=False) -> float:
    """One row's L tokens through layer i against k_frames × L keys;
    `cache_only`: only what the layer's cache entry needs."""
    L = tokens_per_frame(m)
    if is_full_attention(m, i):
        mix = 2 * L * mla_proj_params(m, cache_only)
        if not cache_only:
            mix += 2 * k_frames * L * kv_up_params(m) \
                + mla_core_flops(m, L, k_frames * L)
    else:
        mix = 2 * L * kda_proj_params(m, cache_only) \
            + kda_conv_flops(m, L) \
            + kda_core_flops(m, L, output=not cache_only)
    if cache_only:
        return mix
    if is_dense(m, i):
        return mix + 2 * L * dense_mlp_params(m)
    return mix + 2 * L * moe_dense_params(m) \
        + moe_experts_flops(m, L * held_per_token)


def frame_pass_flops(m, k_frames: int, held_per_token=None) -> float:
    """One row's L tokens through the stack against k_frames × L keys,
    with the adapters and the logsnr MLP. The once-a-call pass (k_frames
    1) leaves the caches and nothing else: of the last layer it runs what
    its cache entry needs."""
    if held_per_token is None:
        held_per_token = expected_held_per_token(m)
    n = m["num_hidden_layers"]
    trunk = sum(layer_flops(m, i, k_frames, held_per_token,
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


def kda_core_call_work(m, steps: int, rows: int):
    """(operations, bytes) of a sampler call under `lk.kda_core`: every
    step's scan of every KDA layer, and the once-a-call pass's (of the
    last layer, where it is KDA, the state alone)."""
    L, n = tokens_per_frame(m), m["num_hidden_layers"]
    flops = nbytes = 0
    for i in layers_of(m, full=False):
        out = i < n - 1
        flops += steps * kda_core_flops(m, L) \
            + kda_core_flops(m, L, output=out)
        nbytes += steps * kda_core_bytes(m, L) \
            + kda_core_bytes(m, L, output=out)
    return rows * flops, rows * nbytes


def mla_core_call_work(m, steps: int, rows: int):
    """(operations, bytes) of a sampler call under `lk.mla_core`: every
    step's target queries against [cache ; own] in every latent layer, and
    the once-a-call frame against itself where that layer is not the last
    (whose attention would feed nothing)."""
    L, n = tokens_per_frame(m), m["num_hidden_layers"]
    flops = nbytes = 0
    for i in layers_of(m, full=True):
        once = int(i < n - 1)
        flops += steps * mla_core_flops(m, L, 2 * L) \
            + once * mla_core_flops(m, L, L)
        nbytes += steps * mla_core_bytes(m, L, 2 * L) \
            + once * mla_core_bytes(m, L, L)
    return rows * flops, rows * nbytes


def expert_passes(m, steps: int) -> int:
    """Expert-layer passes of a call, each over every row: every step's,
    and the once-a-call pass's of the expert layers before the last
    layer."""
    with_experts = expert_layers(m)
    n = m["num_hidden_layers"]
    return steps * len(with_experts) + sum(i < n - 1 for i in with_experts)

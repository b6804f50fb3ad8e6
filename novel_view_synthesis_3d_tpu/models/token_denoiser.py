"""A token denoiser: patch tokens of both frames through a decoder trunk
of a published language model, ε̂ of the target frame out.

**Seven trunks, one frame.** `config.tokens` is one of config.TOKEN_TRUNKS
and names the layers; the frame asks the layer object for layer i's
parameter tree and takes back layer i's cache entry — or None, from a layer
that keeps nothing of a frame — so a trunk's layers may differ by index,
and it carries beside `h` what a layer PUBLISHES for later layers of the
same pass (one trunk's; the others' calls do not take it):

  - `Mistral4Layer` (config.TokenTrunkConfig; Mistral-Small-4-119B-2603,
    `mistral4` config.json): RMSNorm → low-rank queries and a compressed
    key/value latent with one shared rotary key head (yarn) → softmax
    attention → RMSNorm → a router over ALL `n_routed_experts`, top-k,
    renormalised → gated-SiLU experts plus one shared expert. Its cache
    of a frame is the latent: (normalised c_kv, rotated shared key).
  - `SmallThinkerLayer` (config.SmallThinkerTrunkConfig;
    SmallThinker-21BA3B-Instruct): RMSNorm → the ROUTER's logits, taken
    from the attention's normalised input, so a token's experts are known
    before attention runs → grouped-query attention (28 query heads on 4
    key/value heads), per layer rotary or no positional term at all
    (`rope_layout`) and a one-sided window or none
    (`sliding_window_layout`) → RMSNorm → ReGLU experts, top-k of the
    router's softmax renormalised, no shared expert. Its cache of a frame
    is that frame's keys (rotated where the layer rotates) and values.
  - `KimiLinearLayer` (config.KimiLinearTrunkConfig;
    Kimi-Linear-48B-A3B-Instruct): by index (`linear_attn_config`) KDA —
    q, k, v through a causal depthwise convolution of 4 taps and SiLU,
    L2-normalised, a decay per head AND channel, a write strength β, the
    gated delta rule S_t = (I − β k kᵀ) Diag(α) S_{t−1} + β k vᵀ in
    SEQUENCE order (ops/kda.py, chunked), a head-wise RMSNorm under a
    sigmoid gate (ops/head_norm.py) — or latent attention with no
    positional term at all and no low-rank query path (192-wide keys
    against 128-wide values); by
    index a dense gated-SiLU MLP (the leading layers) or experts scored by
    a SIGMOID, chosen on score + a per-expert bias, gated by the score
    alone, renormalised and scaled, plus one shared expert. A KDA layer's
    cache of a frame is the state after its last token (float32) and the
    last three pre-convolution rows; a latent layer's is (c_kv, the shared
    key part). A KDA layer has no frame rule: the target frame's scan is
    entered with the conditioning frame's state, every step anew.
  - `Phi4FlashLayer` (config.Phi4FlashTrunkConfig;
    Phi-4-mini-flash-reasoning, SambaY): ALL 32 layers, five kinds by
    index — Mamba-1 (a short convolution with a bias, an input-dependent
    step, the selective scan s_t = exp(Δ_t ⊙ A) ⊙ s_{t−1} + (Δ_t ⊙ u_t)
    B_tᵀ in SEQUENCE order, ops/ssm.py; its cache the state and the
    convolution's tail), differential attention (adjacent head pairs, two
    softmax maps over one 128-wide value pair, subtracted with a learned
    λ, a pair-wise RMSNorm) under a one-sided 512 window (its cache the
    window's TAIL, 511 rows of keys and values) or over everything (layer
    17, the ONLY layer whose keys and values are kept whole), and past it
    gated memory units that read layer 16's scan output token for token
    and cross layers that project queries only and read layer 17's keys
    and values: fourteen layers without a cache entry. LayerNorm with
    weight and bias, biases on the attention projections and the
    convolution, a dense gated-SiLU MLP in every layer, NO expert layer
    (`routing_counts` and `routing_choices` refuse it by name).
  - `OlmoHybridLayer` (config.OlmoHybridTrunkConfig; Olmo-Hybrid-7B): by
    index (`layer_types`) Gated DeltaNet — q, k, v through a causal
    depthwise convolution of 4 taps and SiLU, q and k L2-normalised, ONE
    decay a head, keys of 96 on values of 192, a write strength β up to
    2, the gated delta rule in SEQUENCE order (ops/gdn.py, chunked), a
    head-wise RMSNorm under a SiLU gate (ops/head_norm.py, the op KDA's
    layer calls with the other activation); its cache the state after the
    frame's last token (float32) and the last three pre-convolution rows —
    or full attention with as many key/value heads as query heads, q and
    k RMS-normalised over the WHOLE projection before the head split, no
    positional term; its cache the frame's keys and values. A dense
    gated-SiLU MLP in every layer, no expert layer. What no other trunk
    here does: NO norm on a sublayer's input — its OUTPUT is normalised
    inside the residual, h + Norm(Mixer(h)), h + Norm(MLP(h)).

  - `LongcatFlashLayer` (config.LongcatFlashTrunkConfig; LongCat-Flash-
    Omni's language model): the shortcut-connected DOUBLE layer — latent
    attention with rotary (64 heads, a 1536-wide query latent, 192-wide
    keys on 128-wide values, both latents scaled after their norms), a
    12288-wide dense MLP, a second latent attention, a second dense MLP,
    and one expert branch ACROSS them: it reads the first attention's
    normalised output and joins the residual after the second MLP. Its
    router is wider than its experts: 512 + 256 outputs, the last 256
    IDENTITY experts that return the router's input; softmax scores, the
    choice on score + a correction bias, the gate 6 × the score, not
    renormalised — a token's twelve choices hold 0 to 12 real experts.
    Its cache of a frame is TWO latents a layer.

  - `LagunaLayer` (config.LagunaTrunkConfig; Laguna-S-2.1): grouped-query
    attention whose query-head COUNT depends on the layer — 48 heads in a
    full layer, 72 under a one-sided 512 window, on the same 8 keys and
    values of 128 — with a rotary law a layer kind (yarn on a head's first
    64 lanes, cos and sin scaled, in full layers; plain on all 128 under
    the window) and a sigmoid gate a head, read from the layer's
    normalised input, on the attention's output before W_o; a dense
    gated-SiLU MLP in the leading layer, then a softmax router over 256,
    top-10 renormalised × 2.5, beside one shared expert. Its cache of a
    frame is the keys (rotated by the layer's law) and values — under a
    window their last 511 rows alone.

`route` and `held_expert_part` are one function each for all that route
(the scoring function, the choice's bias, top-k, the renormalisation and
the activation come from the trunk's config and its router's parameters),
as are the grouped product and the attention kernel under them. What is this
repo's and not a source's is the frame around the trunk:

  - both frames are cut into `patch_size`² patches, one token each:
    token = Dense(patch) + Dense(posenc of the patch's rays)·cond_mask +
    the logsnr embedding (the X-UNet's two-layer MLP on `posenc_ddpm`);
    the conditioning frame's tokens take it at logsnr 20, the clean frame;
  - the sequence is [conditioning frame, target frame], a token's
    position its index in it, and a token sees its own frame and the
    frames before it (in a window layer: those of them less than a
    window behind it) — so the conditioning frame's tokens never depend
    on z_t or the step;
  - the last RMSNorm is followed by Dense(hidden → patch pixels) on the
    target's tokens, un-patched to ε̂ (B, H, W, 3). No vocabulary.

**The once-a-call pass.** Because of that mask, everything a step needs of
the conditioning frame is its per-layer cache. `precompute` runs the
conditioning frame once (prefill) through the layers UP TO THE LAST THAT
KEEPS A CACHE ENTRY — all of them in six trunks, layers 0–17 of the
fourth's 32: nothing its cross-decoder computes of that frame is ever
read, and the pass is built without it, not left to the compiler to cut —
and every denoise step runs the target's tokens alone against [cache ;
own], or from the cached state (decode through the cache). `apply`
without a cache does exactly the two in a row, so there is one set of
equations. What a trunk makes of its PARAMETERS alone (`derive`: the three
latent trunks' kernels that write q, keys and values where the attention
kernel reads them) is made in the same pass and handed on beside the
cache, under `derived`; `apply` without that entry makes it itself.

**The expert layer is told which experts it holds** (`held_experts`, a
(first, count) range: this chip's share of an expert-parallel deployment).
It routes over all experts, computes the part of the result its own give
and nothing of the others'. Assignments to held experts are sorted by
expert, each expert's rows laid into a span of whole row tiles (pad rows
at the span's head, live rows at its end, the spans end to end in a static
buffer sized for every assignment landing here) and multiplied as ONE
grouped product over stacked weights (ops/grouped_matmul.py, whose
contract the aligned spans are); there is no capacity and no token is
dropped, whatever the imbalance. On one chip it runs without its exchange.

Not a flax module: parameters are a plain nested dict, `init`/`apply`
keep flax's calling convention so that every caller of the X-UNet's can
call this one's (the denoiser contract, models/__init__.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from novel_view_synthesis_3d_tpu.config import (
    KimiLinearTrunkConfig, LagunaTrunkConfig, LongcatFlashTrunkConfig,
    ModelConfig, OlmoHybridTrunkConfig, Phi4FlashTrunkConfig,
    SmallThinkerTrunkConfig, TokenTrunkConfig)
from novel_view_synthesis_3d_tpu.models.rays import camera_rays
from novel_view_synthesis_3d_tpu.ops.expert_combine import combine
from novel_view_synthesis_3d_tpu.ops.flash_attention import (
    band_key_columns, flash_attention, resolve_flash, shared_part_fits,
    window_binds)
from novel_view_synthesis_3d_tpu.ops.grouped_matmul import (
    ROW_TILE, buffer_rows, grouped_matmul, span_sizes)
from novel_view_synthesis_3d_tpu.ops.gdn import gated_delta_chunked
from novel_view_synthesis_3d_tpu.ops.head_norm import gated_head_norm
from novel_view_synthesis_3d_tpu.ops.kda import kda_chunked
from novel_view_synthesis_3d_tpu.ops.posenc import posenc_ddpm, posenc_nerf
from novel_view_synthesis_3d_tpu.ops.short_conv import short_conv
from novel_view_synthesis_3d_tpu.ops.ssm import selective_scan

LOGSNR_CLEAN = 20.0   # the conditioning frame's logsnr: 3DiM's clean frame
RAY_CHANNELS = 144    # posenc_nerf(origin, 15) 93 + posenc_nerf(dir, 8) 51
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Rotary embedding (yarn) and the scales that go with it — static numpy.
# ---------------------------------------------------------------------------
def yarn_inv_freq(rope, dim: int) -> np.ndarray:
    """(dim/2,) rotary frequencies: θ^(−2i/dim) where a pair turns often
    inside the original context (extrapolated as it is), the same ÷ factor
    where it turns seldom (interpolated), blended by a linear ramp between
    the two correction dimensions."""
    base, factor = float(rope.rope_theta), float(rope.factor)
    orig = float(rope.original_max_position_embeddings)
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(rope.beta_fast)), 0)
    high = min(math.ceil(correction_dim(rope.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / factor * ramp


def rope_tables(positions, k: TokenTrunkConfig):
    """cos, sin (L, rope/2) float32 of the given positions, and the
    queries' position scale (L,) float32:
    1 + β·ln(1 + ⌊pos / original_max_position_embeddings⌋)."""
    rope = k.rope_parameters
    pos = np.asarray(positions, np.float64)
    ang = pos[:, None] * yarn_inv_freq(rope, k.qk_rope_head_dim)[None]
    qscale = 1.0 + float(rope.llama_4_scaling_beta) * np.log1p(
        np.floor(pos / float(rope.original_max_position_embeddings)))
    return (np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32),
            qscale.astype(np.float32))


def apply_rope(x, cos, sin, interleave: bool):
    """Rotate the pairs of x (..., L, [heads,] rope) by the tables (L,
    rope/2): interleaved pairs (2i, 2i+1) as `rope_interleave` says,
    otherwise halves (i, i + rope/2). float32 inside."""
    x32 = x.astype(jnp.float32)
    if x.ndim == 4:  # (B, L, heads, rope)
        cos, sin = cos[:, None], sin[:, None]
    if interleave:
        a, b = x32[..., 0::2], x32[..., 1::2]
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    a, b = jnp.split(x32, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def softmax_scale(k: TokenTrunkConfig) -> float:
    """qk_head_dim^(−1/2)·m², m = 0.1·mscale_all_dim·ln(factor) + 1 (the
    DeepSeek-V2 convention for a yarn-scaled model)."""
    rope = k.rope_parameters
    m = 1.0
    if float(rope.factor) > 1.0 and rope.mscale_all_dim:
        m = 0.1 * float(rope.mscale_all_dim) * math.log(float(rope.factor)) \
            + 1.0
    return k.qk_head_dim ** -0.5 * m * m


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------
def layer_label(i: int) -> str:
    return f"layer_{i}"


def op_groups(cfg: ModelConfig):
    """Ordered (label, top-level param names) groups: the `og.<label>`
    blocks of this family, as models/xunet.op_groups gives the X-UNet's."""
    layers = [(layer_label(i), (layer_label(i),))
              for i in range(cfg.tokens.num_hidden_layers)]
    return [("prelude", ("patch_in", "ray_in", "emb"))] + layers + [
        ("final", ("final_norm", "out"))]


def param_shapes(cfg: ModelConfig) -> dict:
    """The parameter tree as ShapeDtypeStructs. 2-D kernels are (in, out);
    an expert stack is (held, in, out). Six trunks have no bias at all
    (a router's correction bias apart); Phi4FlashLayer's has LayerNorm
    weights AND biases, and biases on its attention projections, its
    convolution and its step projection."""
    k = cfg.tokens
    dt = jnp.dtype(cfg.param_dtype)
    H = k.hidden_size
    pix = 3 * k.patch_size ** 2

    def w(*shape):
        return jax.ShapeDtypeStruct(shape, dt)

    layer = trunk_layer(cfg)
    tree = {
        "patch_in": {"kernel": w(pix, H)},
        "ray_in": {"kernel": w(RAY_CHANNELS * k.patch_size ** 2, H)},
        "emb": {"dense_0": {"kernel": w(H, H), "bias": w(H)},
                "dense_1": {"kernel": w(H, H), "bias": w(H)}},
        "final_norm": {"scale": w(H)},
        "out": {"kernel": w(H, pix)},
    }
    for i in range(k.num_hidden_layers):
        tree[layer_label(i)] = layer.param_shapes(w, i)
    return tree


def _mlp_shapes(w, hidden, width, *lead):
    return {"gate": {"kernel": w(*lead, hidden, width)},
            "up": {"kernel": w(*lead, hidden, width)},
            "down": {"kernel": w(*lead, width, hidden)}}


def _init_leaf(key, path, s):
    name = path[-1]
    if "mamba" in path and (name in ("A_log", "D") or path[-2] == "dt"):
        # Mamba-1 as its public implementation starts it: a channel's
        # rates A = 1..N, the skip D = 1, the step's projection from
        # U(±rank^(−1/2)) and its bias the inverse softplus of a step
        # from log-U(1e-3, 1e-1).
        if name == "A_log":
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, s.shape[1] + 1, dtype=jnp.float32)), s.shape).astype(
                    s.dtype)
        if name == "D":
            return jnp.ones(s.shape, s.dtype)
        u = jax.random.uniform(key, s.shape, jnp.float32)
        if name == "kernel":
            return ((2.0 * u - 1.0) / math.sqrt(s.shape[0])).astype(s.dtype)
        dt = jnp.exp(math.log(1e-3) + u * math.log(1e2))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(s.dtype)
    if name.startswith("lambda_"):   # differential attention's: N(0, 0.1)
        return (0.1 * jax.random.normal(key, s.shape, jnp.float32)).astype(
            s.dtype)
    if name == "scale":
        return jnp.ones(s.shape, s.dtype)
    if name == "bias" or path[0] == "out":
        return jnp.zeros(s.shape, s.dtype)  # ε̂ = 0 at init, as the X-UNet
    if name in ("A_log", "dt_bias"):
        # KDA's and Gated DeltaNet's decay as their public implementations
        # start it: a head's rate A from U(1, 16) (KDA) or U(0, 16)
        # (Gated DeltaNet; floored at 1e-3 of it, so that its logarithm
        # exists), a channel's or head's step from log-U(1e-3, 1e-1)
        # through the inverse of the softplus it goes through.
        u = jax.random.uniform(key, s.shape, jnp.float32)
        if name == "A_log" and "gdn" in path:
            return jnp.log(16.0 * jnp.maximum(u, 1e-3)).astype(s.dtype)
        if name == "A_log":
            return jnp.log(1.0 + 15.0 * u).astype(s.dtype)
        dt = jnp.exp(math.log(1e-3) + u * math.log(1e2))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(s.dtype)
    fan_in = s.shape[-2]
    return (jax.random.normal(key, s.shape, jnp.float32)
            / math.sqrt(fan_in)).astype(s.dtype)


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------
def rms_norm(x, scale, eps):
    """float32 in, float32 out."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def bfloat16_terms(a):
    """The three bfloat16 arrays that hold a float32's 24 bits: a = t₀ +
    t₁ + t₂ exactly, each the rounding of what the ones before it left.
    `reduce_precision`, not a cast and back: a round trip through bfloat16
    is one XLA may drop (xla_allow_excess_precision)."""
    terms = []
    for _ in range(3):
        t = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        terms.append(t.astype(jnp.bfloat16))
        a = a - t
    return terms


def rms_norm_lane_groups(x, scale, width: int, eps):
    """RMSNorm of x (…, groups·width) float32 over each run of `width`
    lanes, times `scale` (width,) — `rms_norm` of x seen as (…, groups,
    width), without that view: a group's mean of squares, and its way back
    to the group's lanes, are products with the groups' 0/1 indicator, so
    everything stays where x lies. In float32: a value goes through the
    MXU as its `bfloat16_terms`, every product with 0 or 1 is exact and
    the sums are float32's — the mean differs from `jnp.mean`'s by the
    order of its sum alone, and the way back (one product, the terms side
    by side against the indicator three times over) returns the statistic
    to float32's last bit."""
    groups = x.shape[-1] // width
    member = np.repeat(np.eye(groups), width, axis=0)  # (groups·width, groups)
    mean = sum(jnp.dot(t, jnp.asarray(member, jnp.bfloat16),
                       preferred_element_type=jnp.float32)
               for t in bfloat16_terms(x * x)) / width
    inv = jnp.dot(jnp.concatenate(bfloat16_terms(jax.lax.rsqrt(mean + eps)),
                                  axis=-1),
                  jnp.asarray(np.tile(member.T, (3, 1)), jnp.bfloat16),
                  preferred_element_type=jnp.float32)
    return x * inv * jnp.tile(scale.astype(jnp.float32), groups)


def layer_norm(x, p, eps):
    """LayerNorm with weight and bias; float32 in, float32 out."""
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _dense(x, p):
    """x · kernel: every dense product of the trunks, stamped `pt.matmul`
    (models/vocab.py) so that a kind's matmul time is told from its norms,
    rotary and slices."""
    with jax.named_scope("pt.matmul"):
        return jnp.dot(x, p["kernel"].astype(x.dtype))


def pair_swapped_kernel(q_b, heads: int, nope: int, interleave: bool):
    """q_b's kernel (rank, heads·(nope + rope)) with every rotary column
    swapped with its pair's — the lane `apply_rope` rotates it with — and
    zero under the lanes that do not rotate: x against it puts each lane's
    pair under it (`rotated_queries`)."""
    kernel = q_b["kernel"].reshape(q_b["kernel"].shape[0], heads, -1)
    lane = np.arange(kernel.shape[-1] - nope)
    pair = lane ^ 1 if interleave else (lane + lane.size // 2) % lane.size
    swapped = jnp.pad(jnp.take(kernel, nope + pair, axis=-1),
                      ((0, 0), (0, 0), (nope, 0)))
    return {"kernel": swapped.reshape(q_b["kernel"].shape)}


def rotated_queries(x, q_b, q_b_pair, heads: int, cos, sin,
                    interleave: bool):
    """x · q_b (B, L, heads·(nope + rope)), the last `rope` lanes of every
    head rotated by the tables (L, rope/2) as `apply_rope` rotates them and
    the first `nope` as they are — written where the product writes it,
    the heads side by side, no head sliced apart (XLA rotates a slice
    token-minor, through 5-D arrays, and re-lays q for the attention
    kernel: PERF.md §6, PR 41). A second product, against `q_b_pair`
    (`pair_swapped_kernel` of q_b), puts each lane's pair under it; the
    rotation is then x·c + x'·s a lane, c = [1…, cos…], s = [0…, ∓sin…]:
    `apply_rope`'s float32 products and sum."""
    L, half = cos.shape
    nope = q_b["kernel"].shape[-1] // heads - 2 * half
    if interleave:
        c = np.repeat(cos, 2, axis=1)
        s = np.stack([-sin, sin], axis=-1).reshape(L, -1)
    else:
        c = np.concatenate([cos, cos], axis=1)
        s = np.concatenate([-sin, sin], axis=1)
    c = np.concatenate([np.ones((L, nope), np.float32), c], axis=1)
    s = np.concatenate([np.zeros((L, nope), np.float32), s], axis=1)
    return (_dense(x, q_b).astype(jnp.float32) * jnp.tile(c, (1, heads))
            + _dense(x, q_b_pair).astype(jnp.float32)
            * jnp.tile(s, (1, heads))).astype(x.dtype)


def latent_kernels(kv_b, heads: int, dn: int, dr: int):
    """`kv_b` (rank, heads·(dn + dv), a head's dn key columns before its
    dv value columns) as the two kernels `latent_keys_values` multiplies:
    `k_b` (rank + dr, heads·(dn + dr)), the keys' columns over an identity
    block that carries the dr shared lanes under every head's last dr; and
    `v_b` (rank, heads·dv), the values' columns."""
    w = kv_b["kernel"].reshape(kv_b["kernel"].shape[0], heads, -1)
    carry = jnp.pad(jnp.eye(dr, dtype=w.dtype), ((0, 0), (dn, 0)))
    k_b = jnp.concatenate(
        [jnp.pad(w[..., :dn], ((0, 0), (0, 0), (0, dr))),
         jnp.broadcast_to(carry[:, None], (dr, heads, dn + dr))], axis=0)
    return {"k_b": {"kernel": k_b.reshape(-1, heads * (dn + dr))},
            "v_b": {"kernel": w[..., dn:].reshape(w.shape[0], -1)}}


def latent_keys_values(c_kv, k_shared, k_b, v_b, heads: int):
    """Keys (B, Lk, heads, dn + dr) and values (B, Lk, heads, dv)
    up-projected from the latent c_kv (B, Lk, rank), every head's key
    ending in the part all heads share, k_shared (B, Lk, dr). Each is a
    product of its own — the values' against `v_b`, the keys' against
    `k_b` (`latent_kernels` of the layer's `kv_b`) — so that each leaves
    its product as (B, Lk, heads·width), where the attention kernel reads
    it, and no head is sliced apart or put together lane by lane
    afterwards (XLA does that token-minor and re-lays both arrays for the
    kernel: PERF.md §6, PR 41). The values of slicing `kv_b`'s whole
    product and concatenating: a column's sum has the same terms, and the
    identity's products are exact."""
    B, Lk = c_kv.shape[:2]
    keys = _dense(jnp.concatenate([c_kv, k_shared], axis=-1), k_b)
    values = _dense(c_kv, v_b)
    return keys.reshape(B, Lk, heads, -1), values.reshape(B, Lk, heads, -1)


def shares_key_part(k) -> bool:
    """Whether trunk `k`'s latent attention hands the attention kernel the
    heads' own lanes and the rotary lanes all heads share as operands
    apart (`flash_attention`'s `shared`): where the own width fills whole
    lane blocks and the sum does not — 128 + 64, Kimi-Linear's and
    LongCat-Flash's heads. At 64 + 64 (Mistral-Small-4's) a head IS a lane
    block and takes the one-operand form without a pad. Widths alone."""
    return shared_part_fits(k.num_attention_heads, k.qk_nope_head_dim,
                            k.qk_rope_head_dim)


def split_columns(kernel, heads: int, dn: int):
    """A kernel (rank, heads·(dn + d)) as every head's first dn columns
    (rank, heads·dn) and its last d (rank, heads·d), each side by side."""
    w = kernel["kernel"].reshape(kernel["kernel"].shape[0], heads, -1)
    return ({"kernel": w[..., :dn].reshape(w.shape[0], -1)},
            {"kernel": w[..., dn:].reshape(w.shape[0], -1)})


def attention_kernels(k, p, q: str, rotary: bool) -> dict:
    """The kernels one latent attention reads beside its parameters `p`,
    derived from them once a call, so that every operand of the attention
    kernel leaves a product where the kernel reads it. `q` names the
    queries' kernel (`q_b` behind a query latent, `q` at full rank).

    Where a head is whole lane blocks (`shares_key_part` false):
    `latent_kernels`' `k_b` and `v_b`, and for a trunk that rotates
    `<q>_pair`, `pair_swapped_kernel` of the whole kernel. Where it is
    not, the two-operand form: `<q>_nope` and `<q>_rope`, the queries'
    columns apart, `<q>_rope_pair` for a trunk that rotates — the
    pair-swapped kernel of the rotary columns alone, no zero column —,
    `k_nope`, the keys' columns of `kv_b` with no identity block under
    them, and `v_b`."""
    NH, dn, dr = k.num_attention_heads, k.qk_nope_head_dim, \
        k.qk_rope_head_dim
    if not shares_key_part(k):
        pair = {q + "_pair": pair_swapped_kernel(
            p[q], NH, dn, k.rope_interleave)} if rotary else {}
        return {**pair, **latent_kernels(p["kv_b"], NH, dn, dr)}
    nope, rope = split_columns(p[q], NH, dn)
    pair = {q + "_rope_pair": pair_swapped_kernel(
        rope, NH, 0, k.rope_interleave)} if rotary else {}
    k_nope, v_b = split_columns(p["kv_b"], NH, dn)
    return {q + "_nope": nope, q + "_rope": rope, **pair,
            "k_nope": k_nope, "v_b": v_b}


def low_rank_queries(cfg, p, a, cos, sin, scale=1.0):
    """Queries of normalised tokens `a` through the query latent: c_q =
    RMSNorm(a·q_a) — times `scale` where the trunk scales the latent after
    its norm —, then `rotated_queries` of it: (B, L, heads·(dn + dr)), or
    where `shares_key_part` the pair (c_q·q_b's nope columns (B, L,
    heads·dn), its rotary columns rotated (B, L, heads·dr)) — the rotation
    and its second product on a third of the lanes."""
    k = cfg.tokens
    NH = k.num_attention_heads
    c_q = rms_norm(_dense(a, p["q_a"]), p["q_norm"]["scale"], k.rms_norm_eps)
    if scale != 1.0:
        c_q = c_q * scale
    c_q = c_q.astype(a.dtype)
    if shares_key_part(k):
        return _dense(c_q, p["q_b_nope"]), rotated_queries(
            c_q, p["q_b_rope"], p["q_b_rope_pair"], NH, cos, sin,
            k.rope_interleave)
    return rotated_queries(c_q, p["q_b"], p["q_b_pair"], NH, cos, sin,
                           k.rope_interleave)


def latent_attention(cfg, p, h, a, q, cache, scale, rope=None,
                     kv_scale=1.0):
    """h + W_o · attention of the queries `q` (B, L, heads·(dn + dr), made
    by the caller from `a` its trunk's way; where `shares_key_part`, the
    pair of their nope lanes (B, L, heads·dn) and their rotary lanes (B, L,
    heads·dr)) over keys and values
    up-projected at use from the latent of the normalised tokens `a` (the
    form a chip run chose over absorbed weights; PERF.md, PR 26): c_kv =
    RMSNorm of a·kv_a's first `kv_lora_rank` lanes, times `kv_scale` where
    the trunk scales the latent after its norm; the lanes past them are
    the key part all heads share, rotated by `rope` = (cos, sin) where the
    trunk has a positional term and never scaled. `cache` = (c_kv, shared
    key part) of the frames before, as this returns them for this frame:
    → (h, (c_kv, shared key part)). Every trunk with a latent cache runs
    this one function, under the stamps `lk.mla_proj` and `lk.mla_core`.

    Two forms by the heads' widths. A head of whole lane blocks: keys (B,
    Lk, heads·(dn + dr)) by `latent_keys_values`, the shared part copied
    under every head by the product itself. Otherwise (`shares_key_part`)
    nothing is copied or padded: the keys' nope lanes are c_kv · `k_nope`,
    and the shared key part goes to the attention kernel as it is, ONE
    (B, Lk, dr) operand beside the queries' rotary lanes — a score is the
    sum of the two products."""
    k = cfg.tokens
    dt, eps = jnp.dtype(cfg.dtype), k.rms_norm_eps
    B, L, _ = h.shape
    NH = k.num_attention_heads
    with jax.named_scope("lk.mla_proj"):
        q = jax.tree.map(lambda x: x.reshape(B, L, NH, -1), q)
        kv_a = _dense(a, p["kv_a"])
        c_kv = rms_norm(kv_a[..., :k.kv_lora_rank], p["kv_norm"]["scale"],
                        eps)
        if kv_scale != 1.0:
            c_kv = c_kv * kv_scale
        c_kv = c_kv.astype(dt)
        k_shared = kv_a[..., k.kv_lora_rank:]
        if rope is not None:
            k_shared = apply_rope(k_shared, *rope, k.rope_interleave)
        own = (c_kv, k_shared)
        if cache is not None:
            c_kv = jnp.concatenate([cache[0].astype(dt), c_kv], axis=1)
            k_shared = jnp.concatenate([cache[1].astype(dt), k_shared],
                                       axis=1)
        if shares_key_part(k):
            q, q_rope = q
            shared = (q_rope, k_shared)
            keys, values = (
                _dense(c_kv, p[n]).reshape(B, c_kv.shape[1], NH, -1)
                for n in ("k_nope", "v_b"))
        else:
            shared = None
            keys, values = latent_keys_values(c_kv, k_shared, p["k_b"],
                                              p["v_b"], NH)
    with jax.named_scope("lk.mla_core"):
        o = _attention(q, keys, values, scale,
                       resolve_flash(cfg.use_flash_attention), shared=shared)
    with jax.named_scope("lk.mla_proj"):
        return h + _dense(o.reshape(B, L, -1), p["o"]), own


def _attention(q, k, v, scale, use_flash, window=None, shared=None):
    """softmax(q·kᵀ·scale)·v, softmax in float32. q (B, Lq, N, D), k (B,
    Lk, Nkv, D), v (B, Lk, Nkv, Dv), query head n on key/value head n //
    (N // Nkv). The
    queries are the last Lq positions of the key axis. No mask but the
    window's (the caller hands each frame's queries the keys of the frames
    they may see): with `window`, a query at p sees key j iff j > p −
    window. `shared` = (qs (B, Lq, N, w), ks (B, Lk, w)): w more lanes of
    every query, against ONE key part under every head — the kernel adds
    the two products; without it they are put together here."""
    if use_flash:
        return flash_attention(q, k, v, scale=scale, window=window,
                               shared=shared)
    if shared is not None:
        q = jnp.concatenate([q, shared[0]], axis=-1)
        k = jnp.concatenate([k, jnp.broadcast_to(
            shared[1][:, :, None], k.shape[:3] + shared[1].shape[-1:])],
            axis=-1)
    B, Lq, N, D = q.shape
    Lk, Nkv = k.shape[1], k.shape[2]
    binds = window_binds(Lq, window, Lk - Lq)
    if N == Nkv and not binds:
        s = jnp.einsum("bqnd,bknd->bnqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("bnqk,bknd->bqnd", p, v)
    qg = q.reshape(B, Lq, Nkv, N // Nkv, D)
    s = jnp.einsum("bqngd,bknd->bngqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if binds:
        seen = np.arange(Lk)[None] > (Lk - Lq + np.arange(Lq))[:, None] \
            - window
        s = jnp.where(jnp.asarray(seen), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bngqk,bknd->bqngd", p, v).reshape(B, Lq, N,
                                                         v.shape[-1])


_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def route(b32, p_router, k):
    """(top-k gates (T, k) float32, ids (T, k) int32) of normalised tokens
    b32 (T, H) float32, scored in float32 over ALL the router's outputs —
    as many as its kernel has columns: a trunk's router may be wider than
    its experts (ids past `n_routed_experts` are that trunk's to read;
    `held_expert_part` gives them no row). Three things, each on its own:
    the SCORES are the trunk's `router_activation` of the logits, "softmax"
    over all outputs or "sigmoid", each output on its own; the CHOICE is
    the top-k of score + the router's per-output correction bias where it
    has one (`p_router["bias"]`), of the score where it has none; the GATE
    is the chosen's score without the bias, renormalised to sum 1 where
    `norm_topk_prob` says so (for a softmax without a bias the same
    numbers as a softmax over the chosen logits alone), times
    `routed_scaling_factor`. `k` is any trunk's config."""
    with jax.named_scope("pt.matmul"):
        logits = jnp.dot(b32, p_router["kernel"].astype(jnp.float32),
                         precision=HIGHEST)
    scores = jax.nn.sigmoid(logits) if k.router_activation == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    if "bias" in p_router:
        _, top_i = jax.lax.top_k(
            scores + p_router["bias"].astype(jnp.float32),
            k.num_experts_per_tok)
        top_p = jnp.take_along_axis(scores, top_i, axis=-1)
    else:
        top_p, top_i = jax.lax.top_k(scores, k.num_experts_per_tok)
    if k.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p * float(k.routed_scaling_factor), top_i.astype(jnp.int32)


def held_expert_part(b, top_p, top_i, p_experts, k):
    """Σ over a token's top-k experts THAT ARE HELD HERE of p_e·expert_e(b),
    and the tokens each held expert was given (held,) int32 — the true
    counts, not the spans'. expert_e(b) = down(act(gate·b) ⊙ up·b), `act`
    the trunk's `expert_activation`.

    Each held expert's rows get a span of whole row tiles in a static
    buffer (ops/grouped_matmul.py's contract: no tile belongs to two
    experts), the spans end to end, a span's pad rows at its head and its
    live rows at its end; the span sizes are what the products are handed,
    one grouped product each for gate, up and down. ONE stable sort lays
    it out: the (T·k) assignments keyed by expert (absent experts last)
    behind the buffer's spare rows, of which each held expert's first
    span − size are keyed to it as pad and the rest to the absent. Every
    assignment to a held expert has exactly one row (no capacity: the
    buffer covers all T·k landing here); assignments to absent experts —
    and to ids past the real experts, a wider router's — lie past the last
    span. Pad rows hold token 0's copy, rows past the last
    span are never written; the combine reads neither."""
    with jax.named_scope("lk.moe_route"):
        first, count = k.held_experts
        T, K = top_i.shape
        local = top_i.reshape(-1) - first
        is_held = (local >= 0) & (local < count)
        slot = jnp.where(is_held, local, count)       # absent: a last group
        group_sizes = jnp.bincount(slot, length=count + 1)[:count].astype(
            jnp.int32)
        spans = span_sizes(group_sizes)
        # The buffer's spare rows: a row tile a group (and T·k's remainder
        # to a whole tile), a group's as far as its span pads.
        spare = buffer_rows(T * K, count) - T * K
        i = jnp.arange(spare)
        pad_slot = jnp.where(i % ROW_TILE < jnp.repeat(
            spans - group_sizes, ROW_TILE, total_repeat_length=spare),
            jnp.minimum(i // ROW_TILE, count), count)
        # Stable, the spare rows first: a span's pad lies before its rows.
        order = jnp.argsort(jnp.concatenate([pad_slot, slot]), stable=True)
        with jax.named_scope("pt.gather"):            # token → expert order
            x = jnp.take(b, jnp.maximum(order - spare, 0) // K,
                         axis=0, mode="clip")         # (rows, H) by expert
    with jax.named_scope("lk.moe_experts"):
        g = grouped_matmul(x, p_experts["gate"]["kernel"], spans)
        u = grouped_matmul(x, p_experts["up"]["kernel"], spans)
        y = grouped_matmul(_ACTIVATIONS[k.expert_activation](g) * u,
                           p_experts["down"]["kernel"], spans)
        # Back to token order and summed over a token's choices: one
        # kernel (ops/expert_combine.py) that fetches from `y`, where the
        # product left it, the rows of the experts a tile's tokens were
        # given and of no others. A choice that is not held has weight 0
        # and points past the last span, at rows the product never wrote:
        # never read.
        back = jnp.argsort(order)[spare:].reshape(T, K)
        w = jnp.where(is_held.reshape(T, K), top_p, 0.0)
        out = combine(y, back, w, slot, group_sizes, b.dtype)
    return out, group_sizes


def identity_part(b, top_p, top_i, k):
    """b · Σ of the gates of a token's choices that are IDENTITY experts
    (ids from `n_routed_experts` on: "zero-compute" experts that return
    the router's input): token-local, no row and no product, so every chip
    of a deployment computes it whole for its own tokens. b (T, H), → (T,
    H) in b's type."""
    w = jnp.sum(jnp.where(top_i >= k.n_routed_experts, top_p, 0.0), axis=-1)
    return (b.astype(jnp.float32) * w[:, None]).astype(b.dtype)


def gated_mlp(x, p):
    return _dense(jax.nn.silu(_dense(x, p["gate"])) * _dense(x, p["up"]),
                  p["down"])


def feed_forward(cfg, p, h, dense: bool):
    """The second half of a pre-norm layer whose parameters `p` hold
    `mlp_norm` and either `mlp` (a dense gated-SiLU MLP: `dense`) or
    `router`, `experts` and `shared`: h + MLP(RMSNorm(h)), or h + the held
    experts' part (`route`, `held_expert_part`) + the shared expert of it.
    → (h, (tokens per held expert, each token's chosen experts (B, L, k)) —
    (None, None) from the dense form)."""
    k = cfg.tokens
    dt, eps = jnp.dtype(cfg.dtype), k.rms_norm_eps
    B, L, _ = h.shape
    if dense:
        with jax.named_scope("lk.dense_mlp"):
            b = rms_norm(h, p["mlp_norm"]["scale"], eps).astype(dt)
            return h + gated_mlp(b, p["mlp"]), (None, None)
    with jax.named_scope("lk.moe_route"):
        b32 = rms_norm(h, p["mlp_norm"]["scale"], eps).reshape(B * L, -1)
        top_p, top_i = route(b32, p["router"], k)
        b = b32.astype(dt)
    routed, counts = held_expert_part(b, top_p, top_i, p["experts"], k)
    with jax.named_scope("lk.moe_shared"):
        shared = gated_mlp(b, p["shared"])
        h = h + (shared + routed).reshape(B, L, -1)
    return h, (counts, top_i.reshape(B, L, -1))


def conv_qkv(p, qkv, tail, heads: int, scale: float):
    """A delta-rule layer's q, k, v projections each through its own taps
    (`p["q_conv"]`, …), SiLU, q's and k's head-wise L2 norm (q's with the
    scan's `scale`) and into the scan in the compute type, the heads side
    by side as the projections left them: a head is a run of lanes to both
    kernels, of any width (ops/short_conv.py packs heads into lane
    blocks). `tail`: the three tails side by side, as the cache keeps them
    (None: the sequence starts here). → ((q, k, v), the new tail)."""
    widths = [x.shape[-1] for x in qkv]
    tails = (None,) * 3 if tail is None else jnp.split(
        tail, (widths[0], widths[0] + widths[1]), axis=-1)
    out = [short_conv(x, p[n + "_conv"]["kernel"], t, heads=h, scale=c)
           for n, x, t, h, c in zip("qkv", qkv, tails, (heads, heads, None),
                                    (scale, 1.0, 1.0))]
    return [y for y, _ in out], jnp.concatenate([t for _, t in out], axis=-1)


# ---------------------------------------------------------------------------
# The trunks' layers. A layer object is built from the ModelConfig and
# gives: `cache_name` (the batch entry `precompute` returns its caches
# under, one entry a layer), `cache_kind(i)` (what layer i's entry is:
# a latent, keys and values, a recurrent state), `param_shapes(w, i)`
# (layer i's parameter tree), `tables(positions)` (static numpy, made once
# a frame) and `__call__(i, p, h, tables, cache)` → (h, this frame's cache
# entry, (tokens per held expert (held,), each token's chosen experts (B,
# L, k)) — (None, None) from a layer without experts) for layer i over one
# frame's tokens h (B, L, hidden), `cache` layer i's entry of the frames
# before it or None; and `key_columns(L)`, the (visited, visible) key
# columns of its windowed layers' attention over a step's L target
# queries, (0, 0) for a trunk without windows. `has_experts`: whether any
# layer of the trunk routes. `publishes`: whether its layers hand state to
# LATER layers of the same pass — such a trunk's `__call__` takes one more
# argument, a dict the frame makes anew for every pass, which a layer
# writes and later layers read; its `cache_kind(i)` is None, and its cache
# entry None, for a layer that keeps nothing of a frame. A trunk may also
# give `derive(i, p)`: arrays made of layer i's parameters `p` alone, as a
# tree that is merged over `p` before `__call__` sees it — made once a
# sampling call (`TokenDenoiser.precompute`), not once a step.
# ---------------------------------------------------------------------------
class Mistral4Layer:
    """Mistral-Small-4's layer (latent attention, a shared expert)."""

    cache_name = "latent_cache"
    has_experts, publishes = True, False

    def __init__(self, config: ModelConfig):
        self.config = config

    def cache_kind(self, i):
        return "latent"

    def param_shapes(self, w, i=0):
        """Every layer's tree is the same."""
        k = self.config.tokens
        H, NH = k.hidden_size, k.num_attention_heads
        return {
            "attn_norm": {"scale": w(H)},
            "q_a": {"kernel": w(H, k.q_lora_rank)},
            "q_norm": {"scale": w(k.q_lora_rank)},
            "q_b": {"kernel": w(k.q_lora_rank, NH * k.qk_head_dim)},
            "kv_a": {"kernel": w(H, k.kv_lora_rank + k.qk_rope_head_dim)},
            "kv_norm": {"scale": w(k.kv_lora_rank)},
            "kv_b": {"kernel": w(k.kv_lora_rank,
                                 NH * (k.qk_nope_head_dim + k.v_head_dim))},
            "o": {"kernel": w(NH * k.v_head_dim, H)},
            "mlp_norm": {"scale": w(H)},
            "router": {"kernel": w(H, k.n_routed_experts)},
            "shared": _mlp_shapes(
                w, H, k.moe_intermediate_size * k.n_shared_experts),
            "experts": _mlp_shapes(w, H, k.moe_intermediate_size,
                                   k.held_experts[1]),
        }

    def tables(self, positions):
        return rope_tables(positions, self.config.tokens)

    def derive(self, i, p):
        """The kernels that write q, the keys and the values where the
        attention kernel reads them."""
        return attention_kernels(self.config.tokens, p, "q_b", rotary=True)

    def __call__(self, i, p, h, tables, cache):
        """`cache` is the (c_kv, k_rope) of the frames before this one."""
        del i  # every layer is the same
        cfg, k = self.config, self.config.tokens
        dt = jnp.dtype(cfg.dtype)
        eps = k.rms_norm_eps
        cos, sin, qscale = tables
        with jax.named_scope("lk.mla_proj"):
            a = rms_norm(h, p["attn_norm"]["scale"], eps).astype(dt)
            q = low_rank_queries(cfg, p, a, cos, sin)
            if np.any(qscale != 1.0):
                q = jax.tree.map(
                    lambda x: x * jnp.asarray(qscale, dt)[None, :, None], q)
        h, own = latent_attention(cfg, p, h, a, q, cache, softmax_scale(k),
                                  rope=(cos, sin))
        h, routed = feed_forward(cfg, p, h, dense=False)
        return h, own, routed

    def key_columns(self, L: int):
        """No layer of this trunk has a window."""
        return 0, 0


def plain_rope_tables(positions, dim: int, theta: float):
    """cos, sin (L, dim/2) float32: θ^(−2i/dim), no scaling."""
    freq = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.asarray(positions, np.float64)[:, None] * freq[None]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


class SmallThinkerLayer:
    """SmallThinker's layer (grouped-query heads, a window or none and
    rotary or none per layer, the router ahead of attention, ReGLU
    experts)."""

    cache_name = "kv_cache"
    has_experts, publishes = True, False

    def __init__(self, config: ModelConfig):
        self.config = config

    def cache_kind(self, i):
        return "keys_values"

    def param_shapes(self, w, i=0):
        """Every layer's tree is the same."""
        k = self.config.tokens
        H, D = k.hidden_size, k.head_dim
        return {
            "attn_norm": {"scale": w(H)},
            "router": {"kernel": w(H, k.n_routed_experts)},
            "q": {"kernel": w(H, k.num_attention_heads * D)},
            "k": {"kernel": w(H, k.num_key_value_heads * D)},
            "v": {"kernel": w(H, k.num_key_value_heads * D)},
            "o": {"kernel": w(k.num_attention_heads * D, H)},
            "mlp_norm": {"scale": w(H)},
            "experts": _mlp_shapes(w, H, k.moe_ffn_hidden_size,
                                   k.held_experts[1]),
        }

    def tables(self, positions):
        k = self.config.tokens
        return plain_rope_tables(positions, k.head_dim, k.rope_theta)

    def window(self, i):
        """Layer i's window, or None where it sees every key."""
        k = self.config.tokens
        return k.sliding_window_size if k.sliding_window_layout[i] else None

    def __call__(self, i, p, h, tables, cache):
        """`cache` is the (keys, values) (B, L', kv heads, head_dim) of
        the frames before this one, keys rotated where this layer
        rotates."""
        cfg, k = self.config, self.config.tokens
        dt = jnp.dtype(cfg.dtype)
        eps = k.rms_norm_eps
        B, L, _ = h.shape
        NH, NKV, D = k.num_attention_heads, k.num_key_value_heads, k.head_dim
        window = self.window(i)
        with jax.named_scope("lk.gqa_proj"):
            a32 = rms_norm(h, p["attn_norm"]["scale"], eps)
        with jax.named_scope("lk.moe_route"):
            # The router reads the attention's input: the experts of a
            # token are known before its attention runs.
            top_p, top_i = route(a32.reshape(B * L, -1), p["router"], k)
        with jax.named_scope("lk.gqa_proj"):
            a = a32.astype(dt)
            q = _dense(a, p["q"]).reshape(B, L, NH, D)
            keys = _dense(a, p["k"]).reshape(B, L, NKV, D)
            values = _dense(a, p["v"]).reshape(B, L, NKV, D)
            if k.rope_layout[i]:
                q = apply_rope(q, *tables, False)
                keys = apply_rope(keys, *tables, False)
            own = (keys, values)
            if cache is not None:
                keys = jnp.concatenate([cache[0].astype(dt), keys], axis=1)
                values = jnp.concatenate([cache[1].astype(dt), values],
                                         axis=1)
        binds = window_binds(L, window, keys.shape[1] - L)
        with jax.named_scope("lk.attn_window" if binds else "lk.attn_full"):
            o = _attention(q, keys, values, D ** -0.5,
                           resolve_flash(cfg.use_flash_attention), window)
        with jax.named_scope("lk.gqa_proj"):
            h = h + _dense(o.reshape(B, L, NH * D), p["o"])
        with jax.named_scope("lk.moe_route"):
            b = rms_norm(h, p["mlp_norm"]["scale"], eps).reshape(
                B * L, -1).astype(dt)
        routed, counts = held_expert_part(b, top_p, top_i, p["experts"], k)
        with jax.named_scope("lk.moe_experts"):
            h = h + routed.reshape(B, L, -1)
        return h, own, (counts, top_i.reshape(B, L, -1))

    def key_columns(self, L: int):
        """(visited, visible) key columns of one head's L target queries
        against [cache ; own], summed over the layers whose window binds
        there: what the banded kernel walks over what the band lets
        through (ops/flash_attention.band_key_columns)."""
        k = self.config.tokens
        per_layer = [band_key_columns(L, 2 * L, self.window(i), L)
                     for i in range(k.num_hidden_layers)
                     if window_binds(L, self.window(i), L)]
        return tuple(sum(c) for c in zip(*per_layer)) if per_layer else (0, 0)


class KimiLinearLayer:
    """Kimi-Linear's layers: by index KDA (a gated delta rule behind a
    short convolution; its cache entry the state after the frame's last
    token and the convolution's tail) or latent attention without a
    positional term (its cache entry the latent and the shared key part);
    by index a dense MLP or a sigmoid-routed expert layer with one shared
    expert."""

    cache_name = "layer_cache"
    has_experts, publishes = True, False

    def __init__(self, config: ModelConfig):
        self.config = config

    def cache_kind(self, i):
        return "latent" if self.config.tokens.is_full_attention(i) \
            else "recurrent_state"

    def param_shapes(self, w, i):
        k = self.config.tokens
        H = k.hidden_size
        if k.is_full_attention(i):
            NH = k.num_attention_heads
            mix = {"mla": {
                "q": {"kernel": w(H, NH * k.qk_head_dim)},
                "kv_a": {"kernel": w(H, k.kv_lora_rank
                                     + k.qk_rope_head_dim)},
                "kv_norm": {"scale": w(k.kv_lora_rank)},
                "kv_b": {"kernel": w(k.kv_lora_rank, NH * (
                    k.qk_nope_head_dim + k.v_head_dim))},
                "o": {"kernel": w(NH * k.v_head_dim, H)}}}
        else:
            lin = k.linear_attn_config
            NH, D, K = lin.num_heads, lin.head_dim, \
                lin.short_conv_kernel_size
            mix = {"kda": {
                **{n: {"kernel": w(H, NH * D)} for n in ("q", "k", "v")},
                **{n + "_conv": {"kernel": w(K, NH * D)}
                   for n in ("q", "k", "v")},
                # the decay's and the output gate's low-rank pairs (rank =
                # the head dimension), down then up
                "f_a": {"kernel": w(H, D)}, "f_b": {"kernel": w(D, NH * D)},
                "A_log": w(NH), "dt_bias": w(NH * D),
                "beta": {"kernel": w(H, NH)},
                "g_a": {"kernel": w(H, D)}, "g_b": {"kernel": w(D, NH * D)},
                "o_norm": {"scale": w(D)},
                "o": {"kernel": w(NH * D, H)}}}
        if k.is_dense(i):
            ffn = {"mlp": _mlp_shapes(w, H, k.intermediate_size)}
        else:
            ffn = {"router": {"kernel": w(H, k.num_experts),
                              "bias": w(k.num_experts)},
                   "shared": _mlp_shapes(
                       w, H, k.moe_intermediate_size * k.num_shared_experts),
                   "experts": _mlp_shapes(w, H, k.moe_intermediate_size,
                                          k.held_experts[1])}
        return {"attn_norm": {"scale": w(H)}, **mix,
                "mlp_norm": {"scale": w(H)}, **ffn}

    def tables(self, positions):
        """No layer of this trunk has a positional term."""
        return None

    def _kda(self, layer, h, cache):
        """h + KDA(RMSNorm(h)) over one frame's tokens h (B, L, hidden),
        from `cache` = (the state, the convolution's tail) of the frames
        before (None: the sequence starts here). → (h, this frame's
        (state, tail))."""
        k, p = self.config.tokens, layer["kda"]
        lin = k.linear_attn_config
        NH, D = lin.num_heads, lin.head_dim
        state, tail = (None, None) if cache is None else cache
        f32 = jnp.float32
        with jax.named_scope("lk.kda_proj"):
            a = rms_norm(h, layer["attn_norm"]["scale"],
                         k.rms_norm_eps).astype(jnp.dtype(self.config.dtype))
            qkv = [_dense(a, p[n]) for n in "qkv"]
            # per head AND per channel, float32 from the projection on
            g = -jnp.repeat(jnp.exp(p["A_log"].astype(f32)), D) \
                * jax.nn.softplus(
                    _dense(_dense(a, p["f_a"]), p["f_b"]).astype(f32)
                    + p["dt_bias"].astype(f32))
            beta = jax.nn.sigmoid(_dense(a, p["beta"]).astype(f32))
            # before its logistic, which `gated_head_norm` takes in VMEM
            gate = _dense(_dense(a, p["g_a"]), p["g_b"])
        with jax.named_scope("lk.kda_conv"):
            (q, keys, v), tail = conv_qkv(p, qkv, tail, NH, D ** -0.5)
        with jax.named_scope("lk.kda_core"):
            o, state = kda_chunked(q, keys, v, g, beta, state)
        with jax.named_scope("lk.kda_proj"):
            h = h + _dense(gated_head_norm(
                o, gate, p["o_norm"]["scale"], heads=NH, eps=k.rms_norm_eps,
                activation="sigmoid"), p["o"])
        return h, (state, tail)

    def derive(self, i, p):
        """A latent layer's kernels for the keys and the values, and at
        its cell's widths for the queries' two operands."""
        k = self.config.tokens
        if not k.is_full_attention(i):
            return {}
        return {"mla": attention_kernels(k, p["mla"], "q", rotary=False)}

    def _mla(self, layer, h, cache):
        """h + latent attention, without a positional term and with
        full-rank queries, of RMSNorm(h) over one frame's tokens; `cache` =
        (c_kv, the shared key part) of the frames before."""
        cfg, k, p = self.config, self.config.tokens, layer["mla"]
        with jax.named_scope("lk.mla_proj"):
            a = rms_norm(h, layer["attn_norm"]["scale"],
                         k.rms_norm_eps).astype(jnp.dtype(cfg.dtype))
            q = (_dense(a, p["q_nope"]), _dense(a, p["q_rope"])) \
                if shares_key_part(k) else _dense(a, p["q"])
        return latent_attention(cfg, p, h, a, q, cache, k.qk_head_dim ** -0.5)

    def __call__(self, i, p, h, tables, cache):
        del tables
        k = self.config.tokens
        mix = self._mla if k.is_full_attention(i) else self._kda
        h, own = mix(p, h, cache)
        h, routed = feed_forward(self.config, p, h, k.is_dense(i))
        return h, own, routed

    def key_columns(self, L: int):
        """No layer of this trunk has a window."""
        return 0, 0


def tail_key_columns(layer, L: int):
    """`key_columns` of a trunk whose window layers keep their window's
    TAIL of a frame (`layer.window(i)`, the config's `sliding_window`):
    (visited, visible) key columns of L target queries against [tail ;
    own], summed over the layers whose window binds there
    (ops/flash_attention.band_key_columns)."""
    k = layer.config.tokens
    tail = min(L, k.sliding_window - 1)
    per_layer = [band_key_columns(L, tail + L, layer.window(i), tail)
                 for i in range(k.num_hidden_layers)
                 if window_binds(L, layer.window(i), tail)]
    return tuple(sum(c) for c in zip(*per_layer)) if per_layer else (0, 0)


class Phi4FlashLayer:
    """Phi-4-mini-flash-reasoning's layers (SambaY), five kinds by index
    (`config.Phi4FlashTrunkConfig.layer_kind`), a dense gated-SiLU MLP in
    each, LayerNorm before both halves:

      - "mamba": Mamba-1 — in-projection to (u, z), a causal depthwise
        convolution of 4 taps with a bias and SiLU, (δ, B, C) from u′, Δ =
        softplus(W_dt δ + b), the selective scan (ops/ssm.py) from the
        cached state, m ⊙ SiLU(z), out-projection. Its cache entry is the
        state after the frame's last token (float32, (B, N, channels)) and
        the convolution's tail. The LAST Mamba layer publishes its scan
        output m (with the D term, before the gate).
      - "attn_window" / "attn_full": differential attention — adjacent
        query heads pair, adjacent key heads pair, a pair's two value heads
        side by side are ONE value of twice the width; two softmax maps
        over it, subtracted with a learned λ, a pair-wise RMSNorm, × (1 −
        λ⁰). No positional term. A window layer's cache entry is the
        window's TAIL alone: the last `sliding_window` − 1 rows of the
        frame's keys and values (no later query sees an earlier row); the
        full layer's is the frame's keys and values whole, and it
        publishes them over [cache ; own].
      - "gmu": y = W_out(SiLU(W_in x) ⊙ m), m the published scan output,
        token for token. No cache entry.
      - "attn_cross": queries only, against the published keys and
        values, the same differential form. No cache entry."""

    cache_name = "layer_cache"
    has_experts, publishes = False, True
    CACHE_KINDS = {"mamba": "recurrent_state", "attn_window": "window_tail",
                   "attn_full": "keys_values"}

    def __init__(self, config: ModelConfig):
        self.config = config

    def cache_kind(self, i):
        """None for a layer that keeps nothing of a frame."""
        return self.CACHE_KINDS.get(self.config.tokens.layer_kind(i))

    def param_shapes(self, w, i):
        k = self.config.tokens
        H, D = k.hidden_size, k.head_dim
        NH, NKV = k.num_attention_heads, k.num_key_value_heads
        C, N, R = k.mamba_d_inner, k.mamba_d_state, k.mamba_dt_rank
        kind = k.layer_kind(i)

        def norm(width):
            return {"scale": w(width), "bias": w(width)}

        def diff(qkv_width):
            return {"qkv": {"kernel": w(H, qkv_width), "bias": w(qkv_width)},
                    **{"lambda_" + n: w(D) for n in ("q1", "k1", "q2", "k2")},
                    "sub_norm": {"scale": w(2 * D)},
                    "o": {"kernel": w(NH * D, H), "bias": w(H)}}

        if kind == "mamba":
            mix = {"mamba": {
                "in": {"kernel": w(H, 2 * C)},
                "conv": {"kernel": w(k.mamba_d_conv, C), "bias": w(C)},
                "x": {"kernel": w(C, R + 2 * N)},
                "dt": {"kernel": w(R, C), "bias": w(C)},
                "A_log": w(C, N), "D": w(C),
                "out": {"kernel": w(C, H)}}}
        elif kind == "gmu":
            mix = {"gmu": {"in": {"kernel": w(H, C)},
                           "out": {"kernel": w(C, H)}}}
        elif kind == "attn_cross":      # queries only
            mix = {"attn": diff(NH * D)}
        else:
            mix = {"attn": diff((NH + 2 * NKV) * D)}
        return {"norm": norm(H), **mix, "mlp_norm": norm(H),
                "mlp": _mlp_shapes(w, H, k.intermediate_size)}

    def tables(self, positions):
        """No layer of this trunk has a positional term."""
        return None

    def window(self, i):
        """Layer i's window, or None where it sees every key."""
        k = self.config.tokens
        return k.sliding_window if k.layer_kind(i) == "attn_window" else None

    def _mamba(self, i, layer, h, cache, published):
        """h + Mamba(LN(h)) over one frame's tokens, from `cache` = (the
        state, the convolution's tail) of the frames before (None: the
        sequence starts here). → (h, this frame's (state, tail))."""
        k, p = self.config.tokens, layer["mamba"]
        dt = jnp.dtype(self.config.dtype)
        N, R = k.mamba_d_state, k.mamba_dt_rank
        state, tail = (None, None) if cache is None else cache
        f32 = jnp.float32
        with jax.named_scope("lk.ssm_proj"):
            a = layer_norm(h, layer["norm"], k.layer_norm_eps).astype(dt)
            u, z = jnp.split(_dense(a, p["in"]), 2, axis=-1)
        with jax.named_scope("lk.ssm_conv"):
            x, tail = short_conv(u, p["conv"]["kernel"], tail,
                                 p["conv"]["bias"])
        with jax.named_scope("lk.ssm_proj"):
            dbc = _dense(x, p["x"])
            with jax.named_scope("pt.matmul"):   # the step, float32 out
                step = jnp.dot(dbc[..., :R], p["dt"]["kernel"].astype(dt),
                               preferred_element_type=f32)
            step = jax.nn.softplus(step + p["dt"]["bias"].astype(f32))
            A = -jnp.exp(p["A_log"].astype(f32))
        with jax.named_scope("lk.ssm_core"):
            m, state = selective_scan(x, step, A, dbc[..., R:R + N],
                                      dbc[..., R + N:], p["D"], state)
        if k.layer_kind(i + k.mb_per_layer) != "mamba":   # the last one
            published["m"] = m
        with jax.named_scope("lk.ssm_proj"):
            h = h + _dense((m * jax.nn.silu(z.astype(f32))).astype(dt),
                           p["out"])
        return h, (state, tail)

    def _attn(self, i, layer, h, cache, published):
        """h + differential attention of LN(h) over one frame's tokens.
        A window or full layer projects q, k, v and reads [`cache` ; own];
        a cross layer projects q alone and reads the published keys and
        values. → (h, this frame's cache entry or None)."""
        cfg, k, p = self.config, self.config.tokens, layer["attn"]
        dt, f32 = jnp.dtype(cfg.dtype), jnp.float32
        B, L, _ = h.shape
        NH, NKV, D = k.num_attention_heads, k.num_key_value_heads, k.head_dim
        kind, window = k.layer_kind(i), self.window(i)
        own = None
        with jax.named_scope("lk.gqa_proj"):
            a = layer_norm(h, layer["norm"], k.layer_norm_eps).astype(dt)
            qkv = _dense(a, p["qkv"]) + p["qkv"]["bias"].astype(dt)
            q = qkv[..., :NH * D]
            if kind == "attn_cross":
                keys, values = published["kv"]
            else:
                keys = qkv[..., NH * D:(NH + NKV) * D]
                values = qkv[..., (NH + NKV) * D:]
                own = (keys, values)
                if window is not None and L >= window:
                    # no query of a later frame sees an earlier row
                    own = (keys[:, L - window + 1:],
                           values[:, L - window + 1:])
                if cache is not None:
                    keys = jnp.concatenate([cache[0].astype(dt), keys],
                                           axis=1)
                    values = jnp.concatenate([cache[1].astype(dt), values],
                                             axis=1)
                if kind == "attn_full":
                    published["kv"] = (keys, values)
            Lk = keys.shape[1]
            # Adjacent heads pair: a pair's queries and keys are maps 1
            # and 2, its two value heads side by side one value. A pair
            # stays whole, 2·D lanes: map s reads the pair's queries with
            # the other map's lanes zeroed against the pair's keys as
            # they lie — the other map's products are exact zeros —, so
            # no map is sliced out of a pair and padded back to whole
            # lanes for the kernel.
            lane_map = np.tile(np.arange(2 * D) // D, NH // 2)
            maps = [(q * jnp.asarray(lane_map == s, dt)).reshape(
                B, L, NH // 2, 2 * D) for s in (0, 1)]
            kp = keys.reshape(B, Lk, NKV // 2, 2 * D)
            vp = values.reshape(B, Lk, NKV // 2, 2 * D)
            lam0 = k.lambda_init(i)
            lam = jnp.exp(jnp.sum(p["lambda_q1"].astype(f32)
                                  * p["lambda_k1"].astype(f32))) \
                - jnp.exp(jnp.sum(p["lambda_q2"].astype(f32)
                                  * p["lambda_k2"].astype(f32))) + lam0
        binds = window_binds(L, window, Lk - L)
        with jax.named_scope("lk.attn_cross" if kind == "attn_cross" else
                             "lk.attn_window" if binds else "lk.attn_full"):
            flash = resolve_flash(cfg.use_flash_attention)
            o1, o2 = (_attention(m, kp, vp, D ** -0.5, flash, window)
                      for m in maps)
        with jax.named_scope("lk.gqa_proj"):
            # A¹V − λA²V and its pair-wise RMSNorm on the heads side by
            # side, as the kernel wrote them: on (B, L, pairs, 2·D) XLA
            # re-lays both float32 arrays for the norm (PERF.md §6, PR 41).
            d = o1.reshape(B, L, NH * D).astype(f32) \
                - lam * o2.reshape(B, L, NH * D).astype(f32)
            o = rms_norm_lane_groups(d, p["sub_norm"]["scale"], 2 * D,
                                     k.layer_norm_eps) * (1.0 - lam0)
            h = h + _dense(o.astype(dt), p["o"]) + p["o"]["bias"].astype(dt)
        return h, own

    def _gmu(self, i, layer, h, cache, published):
        """h + W_out(SiLU(W_in LN(h)) ⊙ m), m the published scan output."""
        k, p = self.config.tokens, layer["gmu"]
        dt = jnp.dtype(self.config.dtype)
        with jax.named_scope("lk.gmu"):
            a = layer_norm(h, layer["norm"], k.layer_norm_eps).astype(dt)
            g = jax.nn.silu(_dense(a, p["in"]).astype(jnp.float32))
            h = h + _dense((g * published["m"]).astype(dt), p["out"])
        return h, None

    def __call__(self, i, p, h, tables, cache, published):
        del tables
        k = self.config.tokens
        mix = {"mamba": self._mamba, "gmu": self._gmu}.get(
            k.layer_kind(i), self._attn)
        h, own = mix(i, p, h, cache, published)
        with jax.named_scope("lk.dense_mlp"):
            b = layer_norm(h, p["mlp_norm"], k.layer_norm_eps).astype(
                jnp.dtype(self.config.dtype))
            h = h + gated_mlp(b, p["mlp"])
        return h, own, (None, None)

    def key_columns(self, L: int):
        """(visited, visible) key columns of one map's L target queries
        against [the window's tail ; own], summed over the window layers
        whose window binds there."""
        return tail_key_columns(self, L)


class OlmoHybridLayer:
    """Olmo-Hybrid's layers: by index (`layer_types`) Gated DeltaNet (a
    gated delta rule with one decay a head behind a short convolution; its
    cache entry the state after the frame's last token and the
    convolution's tail) or full attention under a QK norm without a
    positional term (its cache entry the frame's keys and values); a dense
    MLP in each. No sublayer normalises its input: each normalises its
    OUTPUT, inside the residual."""

    cache_name = "layer_cache"
    has_experts, publishes = False, False

    def __init__(self, config: ModelConfig):
        self.config = config

    def cache_kind(self, i):
        return "keys_values" if self.config.tokens.is_full_attention(i) \
            else "recurrent_state"

    def param_shapes(self, w, i):
        k = self.config.tokens
        H = k.hidden_size
        if k.is_full_attention(i):
            D = k.head_dim
            NQ, NKV = k.num_attention_heads * D, k.num_key_value_heads * D
            mix = {"attn": {
                "q": {"kernel": w(H, NQ)}, "k": {"kernel": w(H, NKV)},
                "v": {"kernel": w(H, NKV)},
                "q_norm": {"scale": w(NQ)}, "k_norm": {"scale": w(NKV)},
                "o": {"kernel": w(NQ, H)}}}
        else:
            NH, K = k.linear_num_value_heads, k.linear_conv_kernel_dim
            wk, wv = NH * k.linear_key_head_dim, NH * k.linear_value_head_dim
            mix = {"gdn": {
                **{n: {"kernel": w(H, d)}
                   for n, d in (("q", wk), ("k", wk), ("v", wv))},
                **{n + "_conv": {"kernel": w(K, d)}
                   for n, d in (("q", wk), ("k", wk), ("v", wv))},
                # the decay's and the write strength's projections, a
                # number a head each; the output gate's, a value's width
                "a": {"kernel": w(H, NH)}, "A_log": w(NH), "dt_bias": w(NH),
                "b": {"kernel": w(H, NH)},
                "g": {"kernel": w(H, wv)},
                "o_norm": {"scale": w(k.linear_value_head_dim)},
                "o": {"kernel": w(wv, H)}}}
        return {**mix, "mix_norm": {"scale": w(H)},
                "mlp": _mlp_shapes(w, H, k.intermediate_size),
                "mlp_norm": {"scale": w(H)}}

    def tables(self, positions):
        """No layer of this trunk has a positional term."""
        return None

    def _gdn(self, layer, h, cache):
        """h + RMSNorm(GatedDeltaNet(h)) over one frame's tokens h (B, L,
        hidden), from `cache` = (the state, the convolution's tail) of the
        frames before (None: the sequence starts here). → (h, this frame's
        (state, tail))."""
        k, p = self.config.tokens, layer["gdn"]
        NH, dk = k.linear_num_value_heads, k.linear_key_head_dim
        state, tail = (None, None) if cache is None else cache
        dt, f32 = jnp.dtype(self.config.dtype), jnp.float32
        with jax.named_scope("lk.gdn_proj"):
            a = h.astype(dt)          # the sublayer reads h as it is
            qkv = [_dense(a, p[n]) for n in "qkv"]
            # one number a head, float32 from the projection on
            g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
                _dense(a, p["a"]).astype(f32) + p["dt_bias"].astype(f32))
            beta = jax.nn.sigmoid(_dense(a, p["b"]).astype(f32))
            if k.linear_allow_neg_eigval:
                beta = 2.0 * beta
            # before its SiLU, which `gated_head_norm` takes in VMEM
            gate = _dense(a, p["g"])
        with jax.named_scope("lk.gdn_conv"):
            # heads of 96 and 192 lanes: the kernel packs them
            (q, keys, v), tail = conv_qkv(p, qkv, tail, NH, dk ** -0.5)
        with jax.named_scope("lk.gdn_core"):
            o, state = gated_delta_chunked(q, keys, v, g, beta, state)
        with jax.named_scope("lk.gdn_proj"):
            mixed = _dense(gated_head_norm(
                o, gate, p["o_norm"]["scale"], heads=NH, eps=k.rms_norm_eps,
                activation="silu"), p["o"])
            h = h + rms_norm(mixed, layer["mix_norm"]["scale"],
                             k.rms_norm_eps).astype(dt)
        return h, (state, tail)

    def _attn(self, layer, h, cache):
        """h + RMSNorm(attention(h)) over one frame's tokens; `cache` =
        the (keys, values) (B, L', kv heads, head_dim) of the frames
        before, keys normalised."""
        cfg, k, p = self.config, self.config.tokens, layer["attn"]
        dt, eps = jnp.dtype(cfg.dtype), k.rms_norm_eps
        B, L, _ = h.shape
        NH, NKV, D = k.num_attention_heads, k.num_key_value_heads, k.head_dim
        with jax.named_scope("lk.gqa_proj"):
            a = h.astype(dt)
            # normalised over the whole projection, then split into heads
            q = rms_norm(_dense(a, p["q"]), p["q_norm"]["scale"],
                         eps).astype(dt).reshape(B, L, NH, D)
            keys = rms_norm(_dense(a, p["k"]), p["k_norm"]["scale"],
                            eps).astype(dt).reshape(B, L, NKV, D)
            values = _dense(a, p["v"]).reshape(B, L, NKV, D)
            own = (keys, values)
            if cache is not None:
                keys = jnp.concatenate([cache[0].astype(dt), keys], axis=1)
                values = jnp.concatenate([cache[1].astype(dt), values],
                                         axis=1)
        with jax.named_scope("lk.attn_full"):
            o = _attention(q, keys, values, D ** -0.5,
                           resolve_flash(cfg.use_flash_attention))
        with jax.named_scope("lk.gqa_proj"):
            mixed = _dense(o.reshape(B, L, NH * D), p["o"])
            h = h + rms_norm(mixed, layer["mix_norm"]["scale"],
                             eps).astype(dt)
        return h, own

    def __call__(self, i, p, h, tables, cache):
        del tables
        k = self.config.tokens
        mix = self._attn if k.is_full_attention(i) else self._gdn
        h, own = mix(p, h, cache)
        with jax.named_scope("lk.dense_mlp"):
            h = h + rms_norm(gated_mlp(h, p["mlp"]), p["mlp_norm"]["scale"],
                             k.rms_norm_eps).astype(h.dtype)
        return h, own, (None, None)

    def key_columns(self, L: int):
        """No layer of this trunk has a window."""
        return 0, 0


class LongcatFlashLayer:
    """LongCat-Flash's shortcut-connected double layer: latent attention
    (low-rank queries and a key/value latent, both scaled after their
    norms, one shared rotary key head), a dense MLP, latent attention, a
    dense MLP — each with its own norm and weights — and ONE expert branch
    across them: it reads the first attention's normalised output b (the
    first MLP's input) and its result m joins the residual after the
    second MLP,

        h₁ = h + MLA₀(N(h));  b = N(h₁);  m = MoE(b);  h₂ = h₁ + MLP₀(b)
        h₃ = h₂ + MLA₁(N(h₂));  h_out = h₃ + MLP₁(N(h₃)) + m.

    MoE(b): softmax scores over `n_routed_experts + zero_expert_num`
    outputs, the choice on score + bias, the gate 6 × the score; a choice
    under `n_routed_experts` is a gated-SiLU expert (the held ones computed
    here, `held_expert_part`), one past it an identity that returns b
    (`identity_part`, whole). Both sublayers and the branch are ONE
    `__call__`: `num_layers` keeps the source's meaning, layer i's cache
    entry is its two latents ((c_kv, k_rope), (c_kv, k_rope)), and m lives
    inside the call — nothing for the frame to carry between layers."""

    cache_name = "layer_cache"
    has_experts, publishes = True, False

    def __init__(self, config: ModelConfig):
        self.config = config

    def cache_kind(self, i):
        return "latent"

    def param_shapes(self, w, i=0):
        """Every layer's tree is the same."""
        k = self.config.tokens
        H, NH = k.hidden_size, k.num_attention_heads

        def mla():
            return {
                "norm": {"scale": w(H)},
                "q_a": {"kernel": w(H, k.q_lora_rank)},
                "q_norm": {"scale": w(k.q_lora_rank)},
                "q_b": {"kernel": w(k.q_lora_rank, NH * k.qk_head_dim)},
                "kv_a": {"kernel": w(H, k.kv_lora_rank
                                     + k.qk_rope_head_dim)},
                "kv_norm": {"scale": w(k.kv_lora_rank)},
                "kv_b": {"kernel": w(k.kv_lora_rank, NH * (
                    k.qk_nope_head_dim + k.v_head_dim))},
                "o": {"kernel": w(NH * k.v_head_dim, H)}}

        return {"mla_0": mla(), "mla_1": mla(),
                "mlp_norm_0": {"scale": w(H)}, "mlp_norm_1": {"scale": w(H)},
                "mlp_0": _mlp_shapes(w, H, k.ffn_hidden_size),
                "mlp_1": _mlp_shapes(w, H, k.ffn_hidden_size),
                "router": {"kernel": w(H, k.router_width),
                           "bias": w(k.router_width)},
                "experts": _mlp_shapes(w, H, k.expert_ffn_hidden_size,
                                       k.held_experts[1])}

    def tables(self, positions):
        k = self.config.tokens
        return plain_rope_tables(positions, k.qk_rope_head_dim, k.rope_theta)

    def derive(self, i, p):
        """Both attentions' kernels that write q, the keys and the values
        where the attention kernel reads them."""
        return {n: attention_kernels(self.config.tokens, p[n], "q_b",
                                     rotary=True)
                for n in ("mla_0", "mla_1")}

    def _mla(self, p, h, tables, cache):
        """h + latent attention of RMSNorm(h) over one frame's tokens, both
        latents scaled AFTER their norms; `cache` = this attention's (c_kv
        after its scale, rotated shared key) of the frames before. → (h,
        this frame's)."""
        cfg, k = self.config, self.config.tokens
        H = k.hidden_size
        q_scale = (H / k.q_lora_rank) ** 0.5 if k.mla_scale_q_lora else 1.0
        kv_scale = (H / k.kv_lora_rank) ** 0.5 if k.mla_scale_kv_lora \
            else 1.0
        with jax.named_scope("lk.mla_proj"):
            a = rms_norm(h, p["norm"]["scale"],
                         k.rms_norm_eps).astype(jnp.dtype(cfg.dtype))
            q = low_rank_queries(cfg, p, a, *tables, scale=q_scale)
        return latent_attention(cfg, p, h, a, q, cache,
                                k.qk_head_dim ** -0.5, rope=tables,
                                kv_scale=kv_scale)

    def __call__(self, i, p, h, tables, cache):
        """`cache` is layer i's pair of latents of the frames before."""
        del i  # every layer is the same
        k = self.config.tokens
        dt, eps = jnp.dtype(self.config.dtype), k.rms_norm_eps
        B, L, _ = h.shape
        cache_0, cache_1 = (None, None) if cache is None else cache
        h, own_0 = self._mla(p["mla_0"], h, tables, cache_0)
        with jax.named_scope("lk.moe_route"):
            b32 = rms_norm(h, p["mlp_norm_0"]["scale"], eps).reshape(
                B * L, -1)
            top_p, top_i = route(b32, p["router"], k)
            b = b32.astype(dt)
        routed, counts = held_expert_part(b, top_p, top_i, p["experts"], k)
        with jax.named_scope("lk.moe_zero"):
            m = (routed + identity_part(b, top_p, top_i, k)).reshape(
                B, L, -1)
        with jax.named_scope("lk.dense_mlp"):
            h = h + gated_mlp(b, p["mlp_0"]).reshape(B, L, -1)
        h, own_1 = self._mla(p["mla_1"], h, tables, cache_1)
        with jax.named_scope("lk.dense_mlp"):
            b_1 = rms_norm(h, p["mlp_norm_1"]["scale"], eps).astype(dt)
            h = h + gated_mlp(b_1, p["mlp_1"]) + m     # the branch joins
        return h, (own_0, own_1), (counts, top_i.reshape(B, L, -1))

    def key_columns(self, L: int):
        """No layer of this trunk has a window."""
        return 0, 0


def partial_rope_tables(positions, rope, head_dim: int):
    """cos, sin (L, dim/2) float32 of one of `LagunaRopeParameters`' laws
    over the `partial_rotary_factor` · head_dim lanes it rotates: plain
    θ^(−2i/dim), or yarn's blended frequencies with cos and sin times
    `attention_factor`."""
    dim = int(head_dim * rope.partial_rotary_factor)
    if rope.rope_type != "yarn":
        return plain_rope_tables(positions, dim, rope.rope_theta)
    ang = np.asarray(positions, np.float64)[:, None] \
        * yarn_inv_freq(rope, dim)[None]
    factor = float(rope.attention_factor)
    return ((np.cos(ang) * factor).astype(np.float32),
            (np.sin(ang) * factor).astype(np.float32))


def apply_rope_prefix(x, cos, sin):
    """`apply_rope` (halves) on the first 2 · cos.shape[-1] lanes of every
    head of x (B, L, heads, D); the lanes past them pass as they are."""
    dim = 2 * cos.shape[-1]
    if dim == x.shape[-1]:
        return apply_rope(x, cos, sin, False)
    return jnp.concatenate([apply_rope(x[..., :dim], cos, sin, False),
                            x[..., dim:]], axis=-1)


class LagunaLayer:
    """Laguna's layers: grouped-query attention with a query-head count, a
    rotary law and a window or none BY INDEX (`num_attention_heads_per_layer`,
    `layer_types`) on one set of key/value heads, a sigmoid gate a head on
    the attention's output, and by index (`mlp_layer_types`) a dense MLP or
    softmax-routed experts beside one shared expert. A full layer's cache
    entry is the frame's rotated keys and its values; a window layer's is
    their last `sliding_window` − 1 rows (no later query sees an earlier
    one)."""

    cache_name = "layer_cache"
    has_experts, publishes = True, False

    def __init__(self, config: ModelConfig):
        self.config = config

    def cache_kind(self, i):
        return "window_tail" if self.config.tokens.is_window(i) \
            else "keys_values"

    def param_shapes(self, w, i):
        k = self.config.tokens
        H, D = k.hidden_size, k.head_dim
        N, NKV = k.num_attention_heads_per_layer[i], k.num_key_value_heads
        if k.is_dense(i):
            ffn = {"mlp": _mlp_shapes(w, H, k.intermediate_size)}
        else:
            ffn = {"router": {"kernel": w(H, k.num_experts)},
                   "shared": _mlp_shapes(
                       w, H, k.shared_expert_intermediate_size),
                   "experts": _mlp_shapes(w, H, k.moe_intermediate_size,
                                          k.held_experts[1])}
        return {"attn_norm": {"scale": w(H)},
                "q": {"kernel": w(H, N * D)},
                "k": {"kernel": w(H, NKV * D)},
                "v": {"kernel": w(H, NKV * D)},
                "head_gate": {"kernel": w(H, N)},
                "o": {"kernel": w(N * D, H)},
                "mlp_norm": {"scale": w(H)}, **ffn}

    def tables(self, positions):
        """{layer kind: (cos, sin)}: the pair of laws, a layer takes its
        kind's."""
        k = self.config.tokens
        return {kind: partial_rope_tables(
            positions, getattr(k.rope_parameters, kind), k.head_dim)
            for kind in ("full_attention", "sliding_attention")}

    def window(self, i):
        """Layer i's window, or None where it sees every key."""
        k = self.config.tokens
        return k.sliding_window if k.is_window(i) else None

    def __call__(self, i, p, h, tables, cache):
        """`cache` is the (keys, values) (B, L', kv heads, head_dim) of the
        frames before this one — under a window their tail —, keys rotated
        by this layer's law."""
        cfg, k = self.config, self.config.tokens
        dt, f32 = jnp.dtype(cfg.dtype), jnp.float32
        eps = k.rms_norm_eps
        B, L, _ = h.shape
        N, NKV, D = k.num_attention_heads_per_layer[i], \
            k.num_key_value_heads, k.head_dim
        window = self.window(i)
        rope = tables[k.layer_types[i]]
        with jax.named_scope("lk.gqa_proj"):
            a = rms_norm(h, p["attn_norm"]["scale"], eps).astype(dt)
            q = apply_rope_prefix(
                _dense(a, p["q"]).reshape(B, L, N, D), *rope)
            keys = apply_rope_prefix(
                _dense(a, p["k"]).reshape(B, L, NKV, D), *rope)
            values = _dense(a, p["v"]).reshape(B, L, NKV, D)
            gate = _dense(a, p["head_gate"])         # before its sigmoid
            own = (keys, values)
            if window is not None and L >= window:
                # no query of a later frame sees an earlier row
                own = (keys[:, L - window + 1:], values[:, L - window + 1:])
            if cache is not None:
                keys = jnp.concatenate([cache[0].astype(dt), keys], axis=1)
                values = jnp.concatenate([cache[1].astype(dt), values],
                                         axis=1)
        binds = window_binds(L, window, keys.shape[1] - L)
        with jax.named_scope("lk.attn_window" if binds else "lk.attn_full"):
            o = _attention(q, keys, values, D ** -0.5,
                           resolve_flash(cfg.use_flash_attention), window)
        with jax.named_scope("lk.attn_gate"):
            o = (o.astype(f32)
                 * jax.nn.sigmoid(gate.astype(f32))[..., None]).astype(dt)
        with jax.named_scope("lk.gqa_proj"):
            h = h + _dense(o.reshape(B, L, N * D), p["o"])
        h, routed = feed_forward(cfg, p, h, k.is_dense(i))
        return h, own, routed

    def key_columns(self, L: int):
        """(visited, visible) key columns of one head's L target queries
        against [the window's tail ; own], summed over the window layers
        whose window binds there."""
        return tail_key_columns(self, L)


TRUNK_LAYERS = {TokenTrunkConfig: Mistral4Layer,
                SmallThinkerTrunkConfig: SmallThinkerLayer,
                KimiLinearTrunkConfig: KimiLinearLayer,
                Phi4FlashTrunkConfig: Phi4FlashLayer,
                OlmoHybridTrunkConfig: OlmoHybridLayer,
                LongcatFlashTrunkConfig: LongcatFlashLayer,
                LagunaTrunkConfig: LagunaLayer}


def laid_over(p: dict, d: dict) -> dict:
    """The tree `p` with the tree `d` laid over it, dict by dict."""
    return {**p, **{n: laid_over(p[n], v) if isinstance(p.get(n), dict)
                    else v for n, v in d.items()}}


def trunk_layer(cfg: ModelConfig):
    """The layer object of `cfg.tokens`' trunk."""
    return TRUNK_LAYERS[type(cfg.tokens)](cfg)


class TokenDenoiser:
    """The denoiser contract (models/__init__.py) for `family: tokens`."""

    family = "tokens"

    def __init__(self, config: ModelConfig, mesh=None):
        if config.tokens is None:
            raise ValueError("TokenDenoiser needs config.tokens (the trunk)")
        if mesh is not None and math.prod(dict(mesh.shape).values()) > 1:
            raise NotImplementedError(
                "the token denoiser runs on one chip: the all-to-all that "
                "exchanges tokens between the chips of an expert-parallel "
                "layer is not in parallel/ yet")
        self.config = config
        self.mesh = mesh
        self.layer = trunk_layer(config)

    # -- parameters --------------------------------------------------------
    def init(self, rngs, batch=None, *, cond_mask=None, train=False):
        """{"params": tree}. `batch` is not read (the tree does not depend
        on the image size); it is there for flax's calling convention."""
        key = rngs["params"] if isinstance(rngs, dict) else rngs
        leaves, treedef = jax.tree_util.tree_flatten_with_path(
            param_shapes(self.config))
        out = [_init_leaf(jax.random.fold_in(key, i),
                          tuple(str(getattr(q, "key", q)) for q in path), s)
               for i, (path, s) in enumerate(leaves)]
        return {"params": jax.tree_util.tree_unflatten(treedef, out)}

    # -- pieces --------------------------------------------------------------
    def _patches(self, img):
        """(B, H, W, C) → (B, L, p·p·C), patches in raster order."""
        p = self.config.tokens.patch_size
        B, H, W, C = img.shape
        x = img.reshape(B, H // p, p, W // p, p, C).transpose(0, 1, 3, 2, 4, 5)
        return x.reshape(B, (H // p) * (W // p), p * p * C)

    def _unpatch(self, tok, side_h, side_w):
        p = self.config.tokens.patch_size
        B = tok.shape[0]
        x = tok.reshape(B, side_h // p, side_w // p, p, p, -1)
        return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, side_h, side_w, -1)

    def _logsnr_emb(self, params, logsnr):
        """(B,) → (B, hidden): the X-UNet's logsnr embedding (clip ±20,
        squash to (0, 1), DDPM sinusoid, Dense → swish → Dense). The
        sinusoid is taken in float32: 1000·u needs more than bfloat16's 8
        bits near 1000."""
        dt = jnp.dtype(self.config.dtype)
        with jax.named_scope("lk.emb"):
            lam = jnp.clip(logsnr.astype(jnp.float32), -20.0, 20.0)
            u = 2.0 * jnp.arctan(jnp.exp(-lam / 2.0)) / np.pi
            e = posenc_ddpm(u, emb_ch=self.config.tokens.hidden_size,
                            max_time=1.0).astype(dt)
            p = params["emb"]
            e = _dense(e, p["dense_0"]) + p["dense_0"]["bias"].astype(dt)
            return _dense(jax.nn.swish(e), p["dense_1"]) \
                + p["dense_1"]["bias"].astype(dt)

    def _frame_tokens(self, params, img, R, t, K, logsnr, cond_mask):
        """One frame's tokens (B, L, hidden) in the compute type."""
        dt = jnp.dtype(self.config.dtype)
        H, W = img.shape[1:3]
        with jax.named_scope("lk.pose"):
            pos, dirs = camera_rays(R.astype(jnp.float32),
                                    t.astype(jnp.float32),
                                    K.astype(jnp.float32), resolution=(H, W))
            rays = jnp.concatenate([posenc_nerf(pos, 0, 15),
                                    posenc_nerf(dirs, 0, 8)], axis=-1)
            rays = self._patches(rays).astype(dt)
        with jax.named_scope("lk.patch"):
            tok = _dense(self._patches(img).astype(dt), params["patch_in"])
            ray_tok = _dense(rays, params["ray_in"])
            if cond_mask is not None:
                # Classifier-free guidance: the ray term drops per row, as
                # the X-UNet's pose embedding does.
                ray_tok = ray_tok * cond_mask.astype(dt)[:, None, None]
            tok = tok + ray_tok
        emb = self._logsnr_emb(params, logsnr)
        with jax.named_scope("lk.emb"):
            return tok + emb[:, None, :]

    def _derived(self, params):
        """{layer label: what the trunk `derive`s from that layer's
        parameters}, a tree to lay over `params`; {} for a trunk that
        derives nothing."""
        derive = getattr(self.layer, "derive", None)
        if derive is None:
            return {}
        return {layer_label(i): derive(i, params[layer_label(i)])
                for i in range(self.config.tokens.num_hidden_layers)}

    def _frame(self, params, tok, frame_index, caches, layers=None):
        """One frame's tokens through the first `layers` layers (all where
        None), `params` with `_derived`'s tree laid over them. → (h,
        per-layer cache entries of this frame — None for a layer that
        keeps nothing or did not run —, (tokens per held expert
        of each layer that has experts (expert layers, held), None for a
        trunk without experts; those layers' chosen experts, a tuple of
        (B, L, k)))."""
        L = tok.shape[1]
        N = self.config.tokens.num_hidden_layers
        tables = self.layer.tables(np.arange(L) + frame_index * L)
        h, owns, counts, choices = tok, [], [], []
        # What a layer publishes for later layers of THIS pass, beside h.
        published = ({},) if self.layer.publishes else ()
        for i in range(N if layers is None else layers):
            label = layer_label(i)
            with jax.named_scope(f"og.{label}"):
                h, own, (c, chosen) = self.layer(
                    i, params[label], h, tables,
                    None if caches is None else caches[i], *published)
            owns.append(own)
            if c is not None:      # a layer with experts
                counts.append(c)
                choices.append(chosen)
        owns += [None] * (N - len(owns))
        return h, tuple(owns), (jnp.stack(counts) if counts else None,
                                tuple(choices))

    def _cond_frame(self, params, cond, cond_mask):
        """The conditioning frame through the trunk: its per-layer cache.
        `params` with `_derived`'s tree laid over them."""
        x, R1, t1 = cond["x"], cond["R1"], cond["t1"]
        if x.ndim == 5:       # (B, 1, H, W, 3): one conditioning frame
            x, R1, t1 = x[:, 0], R1[:, 0], t1[:, 0]
        B = x.shape[0]
        with jax.named_scope("og.prelude"):
            tok = self._frame_tokens(
                params, x, R1, t1, cond["K"],
                jnp.full((B,), LOGSNR_CLEAN, jnp.float32), cond_mask)
        # Nothing past the last layer that keeps a cache entry is ever read
        # of this frame: the pass stops there.
        last = max(i for i in range(self.config.tokens.num_hidden_layers)
                   if self.layer.cache_kind(i) is not None)
        _, cache, routed = self._frame(params, tok, 0, None, last + 1)
        return cache, routed

    # -- the contract --------------------------------------------------------
    def precompute(self, params, cond: dict):
        """What does not change over a call, for `_raw_eps`'s doubled
        guidance layout (rows [conditional…, unconditional…]): the
        conditioning frame's per-layer cache (each layer's own: a latent,
        keys and values or their window's tail, or a recurrent state with
        its convolution's tail; None for a layer that keeps nothing), as
        one batch entry; and, where the trunk `derive`s arrays from its
        parameters, those under `derived`."""
        B = cond["x"].shape[0]
        doubled = jax.tree.map(lambda a: jnp.concatenate([a, a], axis=0),
                               dict(cond))
        mask = jnp.concatenate([jnp.ones((B,)), jnp.zeros((B,))])
        with jax.named_scope("precompute"):
            derived = self._derived(params)
            cache, _ = self._cond_frame(laid_over(params, derived), doubled,
                                        mask)
        return {self.layer.cache_name: cache,
                **({"derived": derived} if derived else {})}

    def _forward(self, params, batch, cond_mask):
        derived = batch.get("derived")
        params = laid_over(params, self._derived(params) if derived is None
                           else derived)
        cache = batch.get(self.layer.cache_name)
        if cache is None:
            cache, _ = self._cond_frame(params, batch, cond_mask)
        z = batch["z"]
        with jax.named_scope("og.prelude"):
            tok = self._frame_tokens(params, z, batch["R2"], batch["t2"],
                                     batch["K"], batch["logsnr"], cond_mask)
        h, _, routed = self._frame(params, tok, 1, cache)
        with jax.named_scope("og.final"):
            with jax.named_scope("lk.patch"):
                k = self.config.tokens
                hn = rms_norm(h, params["final_norm"]["scale"],
                              k.rms_norm_eps).astype(h.dtype)
                with jax.named_scope("pt.matmul"):
                    out = jnp.dot(hn, params["out"]["kernel"].astype(h.dtype),
                                  preferred_element_type=jnp.float32)
                eps = self._unpatch(out, z.shape[1], z.shape[2])
        return eps, routed

    def apply(self, variables, batch, *, cond_mask=None, train=False,
              **unsupported):
        """ε̂ (B, H, W, 3) float32 of the target frame. With the cache
        entry `precompute` returns in `batch`, only the target's tokens
        run; without it the conditioning frame runs first."""
        if unsupported:
            raise NotImplementedError(
                "the token denoiser has no "
                + ", ".join(sorted(unsupported)) + " path (pipeline stages "
                "and op slices are the X-UNet's)")
        return self._forward(variables["params"], batch, cond_mask)[0]

    def _needs_experts(self, who: str):
        if not self.layer.has_experts:
            raise NotImplementedError(
                f"{who}: {type(self.config.tokens).__name__} is a trunk "
                "without expert layers; it routes nothing")

    def routing_counts(self, params, batch, cond_mask=None):
        """Tokens each held expert is given, per layer that has experts, in
        the pass that `apply` makes over the target's tokens: (expert
        layers, held) int32. Every assignment to a held expert is in it —
        nothing is dropped."""
        self._needs_experts("routing_counts")
        return self._forward(params, batch, cond_mask)[1][0]

    def routing_choices(self, params, batch, cond_mask=None):
        """The experts each token of [conditioning frame ; target frame]
        is sent to, per layer that has experts, in the passes `apply`
        makes: (expert layers, B, 2L, k) int32, held or not."""
        self._needs_experts("routing_choices")
        derived = self._derived(params)
        cache, (_, cond) = self._cond_frame(laid_over(params, derived),
                                            batch, cond_mask)
        own = self._forward(params, dict(batch, derived=derived, **{
            self.layer.cache_name: cache}), cond_mask)[1][1]
        return jnp.stack([jnp.concatenate(pair, axis=1)
                          for pair in zip(cond, own)])

    def window_key_columns(self, side: int):
        """(visited, visible) key columns a head of one target row's
        queries walks and sees in a step at `side` px, summed over the
        layers whose window binds; (0, 0) for a trunk without windows."""
        return self.layer.key_columns(
            (side // self.config.tokens.patch_size) ** 2)

    def cond_cache_bytes(self, side: int) -> dict:
        """Bytes ONE row of the doubled batch keeps of the conditioning
        frame at `side` px, summed over the layers, by kind of cache entry
        (`cache_kind`): what `precompute` returns, from shapes."""
        cfg = self.config

        def one_row(params):
            f32 = jnp.float32
            cond = {"x": jnp.zeros((1, side, side, 3), f32),
                    "R1": jnp.zeros((1, 3, 3), f32),
                    "t1": jnp.zeros((1, 3), f32),
                    "K": jnp.zeros((1, 3, 3), f32)}
            return self._cond_frame(
                laid_over(params, self._derived(params)), cond,
                jnp.ones((1,)))[0]

        out = {}
        for i, entry in enumerate(jax.eval_shape(one_row,
                                                 param_shapes(cfg))):
            kind = self.layer.cache_kind(i)
            if kind is None:       # a layer that keeps nothing
                continue
            out[kind] = out.get(kind, 0) + sum(
                a.size * a.dtype.itemsize for a in jax.tree.leaves(entry))
        return out

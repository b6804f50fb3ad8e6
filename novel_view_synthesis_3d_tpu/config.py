"""Single config tree for the whole framework.

The reference has no config system at all: hyperparameters are dataclass
defaults (`/root/reference/model/xunet.py:207-215`), Trainer keyword defaults
(`/root/reference/train.py:82-88`), or module constants
(`/root/reference/sampling.py:55,66,134`), and two key model attributes
(`ch_mult`, `attn_resolutions`) are frozen class attributes that cannot be
overridden without editing the source. Here every knob from SURVEY.md §2.2/§5.6
is a real, serializable field, with the BASELINE.json config ladder as presets.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Any, Optional, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class RopeParameters:
    """`rope_parameters` of a `mistral4` config.json (yarn), key for key."""

    beta_fast: float = 32
    beta_slow: float = 1
    factor: float = 128
    llama_4_scaling_beta: float = 0.1
    mscale: float = 1
    mscale_all_dim: float = 1
    original_max_position_embeddings: int = 8192
    rope_theta: float = 10000
    rope_type: str = "yarn"


@dataclasses.dataclass(frozen=True)
class TokenTrunkConfig:
    """The token denoiser's trunk (models/token_denoiser.py): a decoder
    layer with latent attention and sparse experts, under the key names of
    the `config.json` it is read from. The defaults are
    Mistral-Small-4-119B-2603's published values; a preset sets the depth
    and the experts this chip holds."""

    hidden_size: int = 4096
    num_hidden_layers: int = 36
    num_attention_heads: int = 32
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # The router's width: it always scores all of these and takes
    # `num_experts_per_tok` of them, whichever experts live here.
    n_routed_experts: int = 128
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    moe_intermediate_size: int = 2048
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    rope_interleave: bool = True
    rope_parameters: RopeParameters = dataclasses.field(
        default_factory=RopeParameters)
    # (first, count): the routed experts this chip holds of each layer —
    # its share of an expert-parallel deployment. The layer computes the
    # part of the result these give and nothing of the others'.
    held_experts: Tuple[int, int] = (0, 128)
    # The patch adapter (this repo's): patch × patch pixels make a token.
    patch_size: int = 4

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    # What the expert layer every trunk shares (models/token_denoiser.py:
    # `route`, `held_expert_part`) reads besides the fields above.
    expert_activation = "silu"
    router_activation = "softmax"


_WINDOW_PERIOD = (0, 1, 1, 1)


@dataclasses.dataclass(frozen=True)
class SmallThinkerTrunkConfig:
    """The token denoiser's second trunk: SmallThinker-21BA3B-Instruct's
    decoder layer under the key names of its `config.json` — grouped-query
    attention, per layer rotary or no positional term (`rope_layout`) and
    a one-sided window or none (`sliding_window_layout`), a router that
    reads the attention's input, ReGLU experts, no shared expert. The
    defaults are the published values; a preset sets the depth."""

    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 16384
    # Per layer, 1 = rotary on q and k / a window of sliding_window_size;
    # 0 = no positional term at all / every key of the sequence.
    rope_layout: Tuple[int, ...] = _WINDOW_PERIOD * 13
    sliding_window_layout: Tuple[int, ...] = _WINDOW_PERIOD * 13
    sliding_window_size: int = 4096
    rope_theta: float = 1500000
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    # As TokenTrunkConfig's: the (first, count) experts this chip holds,
    # and the patch adapter.
    held_experts: Tuple[int, int] = (0, 64)
    patch_size: int = 4

    # The expert layer's names for the same things (`route`,
    # `held_expert_part` are one function each for every trunk).
    expert_activation = "relu"
    router_activation = "softmax"
    routed_scaling_factor = 1.0

    @property
    def n_routed_experts(self) -> int:
        return self.moe_num_primary_experts

    @property
    def num_experts_per_tok(self) -> int:
        return self.moe_num_active_primary_experts


@dataclasses.dataclass(frozen=True)
class KimiLinearAttnConfig:
    """`linear_attn_config` of a `kimi_linear` config.json, key for key:
    which layers (1-BASED) are KDA and which full attention, the KDA
    heads and their size, the short convolution's taps."""

    full_attn_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15,
                                   17, 18, 19, 21, 22, 23, 25, 26)
    head_dim: int = 128
    num_heads: int = 32
    short_conv_kernel_size: int = 4


@dataclasses.dataclass(frozen=True)
class KimiLinearTrunkConfig:
    """The token denoiser's third trunk: Kimi-Linear-48B-A3B-Instruct's
    decoder stack under the key names of its `config.json` — layers of two
    kinds by index (`linear_attn_config`): KDA, a gated delta rule with a
    per-channel decay behind a short causal convolution, whose cache is a
    recurrent state; and latent attention with NO positional term
    (`mla_use_nope`, no low-rank query path: `q_lora_rank` is null in the
    source and has no key here), whose cache is the latent. The first
    `first_k_dense_replace` layers carry a dense gated-SiLU MLP, the others
    a sigmoid router over `num_experts` with a per-expert correction bias
    in the choice, top-k renormalised and scaled, and one shared expert.
    The defaults are the published values (the source's `head_dim` 72 is
    read by neither kind of layer and has no key here); a preset sets the
    depth and the experts this chip holds."""

    hidden_size: int = 2304
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64     # the shared key head; never rotated
    v_head_dim: int = 128
    mla_use_nope: bool = True
    linear_attn_config: KimiLinearAttnConfig = dataclasses.field(
        default_factory=KimiLinearAttnConfig)
    first_k_dense_replace: int = 1
    intermediate_size: int = 9216
    hidden_act: str = "silu"
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    moe_intermediate_size: int = 1024
    moe_router_activation_func: str = "sigmoid"
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    # One group of which one is taken: the grouped top-k is the identity.
    num_expert_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-5
    # As TokenTrunkConfig's: the (first, count) experts this chip holds,
    # and the patch adapter.
    held_experts: Tuple[int, int] = (0, 256)
    patch_size: int = 4

    # The expert layer's names for the same things.
    @property
    def expert_activation(self) -> str:
        return self.hidden_act

    @property
    def router_activation(self) -> str:
        return self.moe_router_activation_func

    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def num_experts_per_tok(self) -> int:
        return self.num_experts_per_token

    @property
    def norm_topk_prob(self) -> bool:
        return self.moe_renormalize

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def is_full_attention(self, i: int) -> bool:
        """Layer i (0-based; the source counts from 1) is latent
        attention; the others are KDA."""
        return i + 1 in self.linear_attn_config.full_attn_layers

    def is_dense(self, i: int) -> bool:
        """Layer i carries the dense MLP, not experts."""
        return i < self.first_k_dense_replace


@dataclasses.dataclass(frozen=True)
class Phi4FlashTrunkConfig:
    """The token denoiser's fourth trunk: Phi-4-mini-flash-reasoning's whole
    decoder stack (SambaY) under the key names of its `phi4flash`
    `config.json` — layers of FIVE kinds by index (`layer_kind`, the
    source's own rule in `num_hidden_layers` and `mb_per_layer`): Mamba-1
    selective-scan layers whose cache is a recurrent state; differential
    attention (adjacent head pairs, two softmax maps subtracted) under a
    one-sided `sliding_window`; ONE layer of differential attention over
    everything, the only layer whose keys and values are kept; and, past
    it, gated memory units that read the LAST Mamba layer's scan output and
    differential cross-attention that projects queries only and reads that
    one layer's keys and values. LayerNorm (weight and bias), a dense
    gated-SiLU MLP in every layer, no expert layer at all. The defaults
    are the published values; the Mamba sizes are Mamba-1's defaults,
    which the source's config class carries and `config.json` does not
    repeat."""

    hidden_size: int = 2560
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    intermediate_size: int = 10240
    hidden_act: str = "silu"
    mlp_bias: bool = False
    layer_norm_eps: float = 1e-5
    # Every `mb_per_layer`-th layer (from 0) is a Mamba layer in the first
    # half and a gated memory unit past layer N/2 + 1.
    mb_per_layer: int = 2
    sliding_window: int = 512
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    # The patch adapter (this repo's), as every trunk's.
    patch_size: int = 4

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def mamba_dt_rank(self) -> int:
        """Mamba-1's "auto": ⌈hidden / 16⌉."""
        return -(-self.hidden_size // 16)

    @property
    def rms_norm_eps(self) -> float:
        """The frame's own last RMSNorm takes the trunk's eps."""
        return self.layer_norm_eps

    def layer_kind(self, i: int) -> str:
        """Layer i's (0-based) kind, as the source writes it. With N
        layers: a layer is a Mamba SLOT where i is a multiple of
        `mb_per_layer`; up to N/2 + 1 (the self-decoder) a slot is "mamba"
        and the others differential attention, "attn_window" except layer
        N/2 + 1, "attn_full"; from N/2 + 2 on (the cross-decoder) a slot
        is "gmu" and the others "attn_cross"."""
        half = self.num_hidden_layers // 2
        slot = self.mb_per_layer > 0 and i % self.mb_per_layer == 0
        if i >= half + 2:
            return "gmu" if slot else "attn_cross"
        if slot:
            return "mamba"
        return "attn_full" if i == half + 1 else "attn_window"

    def lambda_init(self, i: int) -> float:
        """λ⁰ of layer i's differential attention: 0.8 − 0.6·e^(−0.3 i)."""
        return 0.8 - 0.6 * math.exp(-0.3 * i)


_HYBRID_PERIOD = ("linear_attention",) * 3 + ("full_attention",)


@dataclasses.dataclass(frozen=True)
class OlmoHybridTrunkConfig:
    """The token denoiser's fifth trunk: Olmo-Hybrid-7B's decoder stack
    under the key names of its `olmo_hybrid` `config.json` — layers of two
    kinds by index (`layer_types`, as published: three "linear_attention"
    to one "full_attention"): Gated DeltaNet, a gated delta rule with ONE
    decay a head behind a short causal convolution, keys narrower than
    values (`linear_key_head_dim` on `linear_value_head_dim`), a write
    strength β up to 2 (`linear_allow_neg_eigval`), whose cache is a
    recurrent state; and full attention with as many key/value heads as
    query heads, q and k RMS-normalised over the whole projection before
    the head split, and no positional term (`rope_theta` is null in the
    source and has no key here), whose cache is keys and values. Every
    sublayer's OUTPUT is normalised inside the residual, h + Norm(f(h)); a
    dense gated-SiLU MLP in every layer; no bias, no expert layer. The
    defaults are the published values; a preset sets the depth, of which
    the first `num_hidden_layers` entries of `layer_types` are run."""

    hidden_size: int = 3840
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    intermediate_size: int = 11008
    hidden_act: str = "silu"
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    layer_types: Tuple[str, ...] = _HYBRID_PERIOD * 8
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    # The patch adapter (this repo's), as every trunk's.
    patch_size: int = 4

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def is_full_attention(self, i: int) -> bool:
        """Layer i (0-based) is full attention; the others are Gated
        DeltaNet."""
        return self.layer_types[i] == "full_attention"


@dataclasses.dataclass(frozen=True)
class LongcatFlashTrunkConfig:
    """The token denoiser's sixth trunk: LongCat-Flash's decoder stack
    (LongCat-Flash-Omni's language model) under the key names of its
    `config.json` — the shortcut-connected DOUBLE layer: `num_layers`
    counts layers of two sublayers each, a latent attention (low-rank
    queries, a compressed key/value latent, one shared rotary key head;
    both latents SCALED after their norms, `mla_scale_q_lora` /
    `mla_scale_kv_lora`: × (hidden_size / rank)^½) and a dense gated-SiLU
    MLP of `ffn_hidden_size`, twice; the expert branch reads the FIRST
    attention's normalised output and joins the residual after the SECOND
    MLP. Its router has `n_routed_experts + zero_expert_num` outputs: the
    last `zero_expert_num` ids are identities ("zero-compute" experts) that
    return the router's input. Softmax scores over all outputs, the choice
    on score + a per-output correction bias, the gate the score alone ×
    `routed_scaling_factor`, not renormalised. A layer's cache of a frame
    is its TWO latents. The defaults are the published values; a preset
    sets the depth and the experts this chip holds."""

    hidden_size: int = 6144
    num_layers: int = 28           # double layers, as published
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    attention_method: str = "MLA"
    attention_bias: bool = False
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    # The REAL experts; the router is `zero_expert_num` outputs wider.
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rope_theta: float = 10000000
    rms_norm_eps: float = 1e-5
    # As TokenTrunkConfig's: the (first, count) REAL experts this chip
    # holds, and the patch adapter.
    held_experts: Tuple[int, int] = (0, 512)
    patch_size: int = 4

    # The frame's and the expert layer's names for the same things.
    expert_activation = "silu"
    router_activation = "softmax"
    norm_topk_prob = False
    rope_interleave = True

    @property
    def num_hidden_layers(self) -> int:
        return self.num_layers

    @property
    def num_experts_per_tok(self) -> int:
        return self.moe_topk

    @property
    def router_width(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


_LAGUNA_PERIOD = ("full_attention",) + ("sliding_attention",) * 3


@dataclasses.dataclass(frozen=True)
class LagunaRope:
    """One entry of a `laguna` config.json's `rope_parameters`: the rotary
    law of ONE kind of layer. `rope_type` "default" is plain θ^(−2i/d);
    "yarn" reads the four keys after it and scales cos and sin by
    `attention_factor`. The first `partial_rotary_factor` of a head's
    lanes rotate, the rest pass."""

    rope_type: str = "default"
    rope_theta: float = 10000
    partial_rotary_factor: float = 1
    factor: float = 1
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32
    beta_slow: float = 1
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class LagunaRopeParameters:
    """`rope_parameters` of a `laguna` config.json: keyed BY LAYER KIND."""

    full_attention: LagunaRope = LagunaRope(
        rope_type="yarn", rope_theta=500000, partial_rotary_factor=0.5,
        factor=128, attention_factor=1.4852030263919618)
    sliding_attention: LagunaRope = LagunaRope()


@dataclasses.dataclass(frozen=True)
class LagunaTrunkConfig:
    """The token denoiser's seventh trunk: Laguna-S-2.1's decoder stack
    under the key names of its `laguna` `config.json` — grouped-query
    attention whose QUERY-HEAD COUNT depends on the layer
    (`num_attention_heads_per_layer`: 48 in a "full_attention" layer, 72 in
    a "sliding_attention" layer, `layer_types`) on the same
    `num_key_value_heads` keys and values; a rotary law a layer kind
    (`rope_parameters`: yarn on half of a head's lanes in full layers,
    plain on all of them under the one-sided `sliding_window`); a sigmoid
    gate a head on the attention's output (`gating` "per-head"); by
    `mlp_layer_types` a dense gated-SiLU MLP (the leading layer) or a
    softmax router over `num_experts`, top-k renormalised and scaled,
    beside one shared expert. The three per-layer tuples keep their
    published 48 entries; the program reads the first `num_hidden_layers`.
    The defaults are the published values (`num_attention_heads` 48 is the
    full layers' count and is read by no layer); a preset sets the depth
    and the experts this chip holds."""

    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = (0,)
    gating: str = "per-head"
    sliding_window: int = 512
    rope_parameters: LagunaRopeParameters = LagunaRopeParameters()
    layer_types: Tuple[str, ...] = _LAGUNA_PERIOD * 12
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 47
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 72, 72, 72) * 12
    moe_apply_router_weight_on_input: bool = False
    moe_routed_scaling_factor: float = 2.5
    moe_router_logit_softcapping: float = 0
    # As TokenTrunkConfig's: the (first, count) experts this chip holds,
    # and the patch adapter.
    held_experts: Tuple[int, int] = (0, 256)
    patch_size: int = 4

    # The expert layer's names for the same things. The source names no
    # score function: its keys are the Qwen2-MoE family's, whose router is
    # a float32 softmax (the configuration file's `assumed`).
    expert_activation = "silu"
    router_activation = "softmax"

    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def routed_scaling_factor(self) -> float:
        return self.moe_routed_scaling_factor

    def is_window(self, i: int) -> bool:
        """Layer i (0-based) attends under `sliding_window`."""
        return self.layer_types[i] == "sliding_attention"

    def is_dense(self, i: int) -> bool:
        """Layer i carries the dense MLP, not experts."""
        return self.mlp_layer_types[i] == "dense"


# The trunks `ModelConfig.tokens` may hold; a serialized config says
# which by its keys (they share only sizes every trunk has).
TOKEN_TRUNKS = (TokenTrunkConfig, SmallThinkerTrunkConfig,
                KimiLinearTrunkConfig, Phi4FlashTrunkConfig,
                OlmoHybridTrunkConfig, LongcatFlashTrunkConfig,
                LagunaTrunkConfig)


def _trunk_of_keys(keys) -> type:
    for tp in TOKEN_TRUNKS:
        if set(keys) <= {f.name for f in dataclasses.fields(tp)}:
            return tp
    raise KeyError(f"model.tokens: no trunk has the keys {sorted(keys)}")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """X-UNet hyperparameters (reference: model/xunet.py:205-215)."""

    ch: int = 32
    ch_mult: Tuple[int, ...] = (1, 2)
    emb_ch: int = 32
    num_res_blocks: int = 2
    # DDPM-style superset filter: attention runs at every UNet level whose
    # resolution is in this set; entries with no matching level are inert
    # by design (one list serves all depths/image sizes). validate()
    # rejects lists where NO level matches, and entries that could never
    # match at any depth (not power-of-two-related to the sidelength).
    attn_resolutions: Tuple[int, ...] = (8, 16, 32)
    attn_heads: int = 4
    dropout: float = 0.1
    use_pos_emb: bool = False
    use_ref_pose_emb: bool = False
    # Number of conditioning frames (k in 3DiM). The reference hardcodes 1
    # (frame axis F = k+1 = 2 throughout model/xunet.py); here it is a field.
    num_cond_frames: int = 1
    # --- behavior-vs-bug compat flags (SURVEY.md §7 ledger) ---
    # Reference GroupNorm shares statistics across both frames
    # (model/xunet.py:46-52); per-frame stats are what the architecture
    # intends. Default True = per-frame; False reproduces reference behavior.
    groupnorm_per_frame: bool = True
    # Reference attention has no output projection (commented out at
    # model/xunet.py:126). Default False matches the reference.
    attn_out_proj: bool = False
    # --- TPU knobs ---
    dtype: str = "float32"  # compute dtype: "float32" | "bfloat16"
    param_dtype: str = "float32"
    # Rematerialization of UNet blocks: False/'none' = off; True/'full' =
    # jax.checkpoint each block (min memory, max recompute); 'dots' = save
    # conv/matmul outputs, recompute elementwise chains
    # (checkpoint_policies.dots_saveable) — cuts HBM traffic without
    # re-running convs, often the right setting for bandwidth-bound configs.
    remat: Any = False
    # Fused Pallas attention kernel (ops/flash_attention.py) instead of the
    # XLA dot_product_attention path. "auto" (default) enables it on TPU
    # backends only and keeps the XLA path elsewhere; True forces the
    # kernel (interpret mode off-TPU, slow but exact); False forces the
    # XLA path. Measured +26-35% train step on v5e at tiny64 in ROUND 2,
    # BEFORE the r3 backward-path split (_PALLAS_BWD_MIN_HEAD_DIM) — the
    # r4 bench matrix re-validates with tiny64/base128 flash-off A/Bs
    # (results/tpu_r04/).
    use_flash_attention: Any = "auto"
    # Sequence parallelism: shard the H·W token axis of every attention over
    # the mesh 'seq' axis and run ring attention (parallel/ring_attention.py,
    # ppermute over ICI). Requires mesh.seq > 1 and token counts divisible
    # by it; a no-op when the mesh has seq=1.
    sequence_parallel: bool = False
    # Scene-category conditioning (ROADMAP item 5): > 0 adds a ZERO-INIT
    # category embedding table (num_classes, emb_ch) inside
    # ConditioningProcessor_0, looked up by the batch's int32 `category`
    # ids and added to the logsnr embedding BEHIND the CFG cond-drop mask
    # (so classifier-free guidance and distillation drop it together with
    # the pose conditioning). Zero-init makes enabling it a numeric no-op
    # at init, and lets checkpoints taken at num_classes=0 load into a
    # num_classes>0 model via the versioned param-tree splice
    # (train/ladder.restore_with_growth). 0 = off (no table, param tree
    # unchanged).
    num_classes: int = 0
    # Which denoiser `models.build_denoiser` builds: "xunet" (everything
    # above) or "tokens" (models/token_denoiser.py: patch tokens through
    # the trunk that `tokens` describes; of the fields above it reads
    # dtype, param_dtype, num_cond_frames and use_flash_attention).
    family: str = "xunet"
    tokens: Any = None   # one of TOKEN_TRUNKS

    @property
    def num_frames(self) -> int:
        return self.num_cond_frames + 1


@dataclasses.dataclass(frozen=True)
class DiffusionConfig:
    """Diffusion process (reference: sampling.py:16-53,73-76, T=1000 cosine)."""

    timesteps: int = 1000
    # 'cosine' (the reference's only schedule), 'linear' (Ho et al. 2020
    # 1e-4→0.02 ladder, endpoints scaled by 1000/T), or 'shifted_cosine'
    # (Hoogeboom et al. 2023 "simple diffusion": cosine logsnr shifted by
    # `logsnr_shift` — at resolution S set it to 2·log(64/S), e.g. −2.77 at
    # 256px, so high-res training sees as much signal destruction as 64px).
    # Non-cosine schedules condition the model on the exact per-timestep
    # log(ᾱ/(1−ᾱ)).
    schedule: str = "cosine"
    logsnr_shift: float = 0.0  # shifted_cosine only
    cosine_s: float = 0.008
    logsnr_min: float = -20.0
    logsnr_max: float = 20.0
    # What the network predicts / is trained against: 'eps' (the reference's
    # noise prediction), 'x0' (clean image), or 'v' (√ᾱε − √(1−ᾱ)x₀,
    # Salimans & Ho 2022). Train step and samplers both honor this.
    objective: str = "eps"
    # Sampling
    sample_timesteps: int = 1000  # respaced steps for the ancestral sampler
    guidance_weight: float = 3.0  # CFG w (reference sampling.py:134)
    # CFG rescale φ (Lin et al. 2023, arXiv 2305.08891 §3.4): after guidance,
    # rescale x̂₀ so its per-sample std matches the conditional prediction's,
    # then blend x̂₀ ← φ·rescaled + (1−φ)·guided. 0 = off (reference
    # behavior); ~0.7 counters the over-saturation large w causes.
    cfg_rescale: float = 0.0
    clip_denoised: bool = True
    # 'ddpm' = ancestral (the reference's sampler); 'ddim' = Song et al.
    # 2021 non-Markovian update — deterministic at ddim_eta=0, ancestral-like
    # at ddim_eta=1; pairs well with aggressive respacing (sample_timesteps).
    # 'dpm++' = DPM-Solver++(2M) (Lu et al. 2022) — deterministic
    # second-order multistep solver; comparable quality at ~8× fewer steps
    # (sample_timesteps 25–50 instead of 256+).
    sampler: str = "ddpm"
    ddim_eta: float = 0.0
    # Fused Pallas denoise-step kernel (ops/fused_step.py): everything
    # after the UNet forward — CFG combine, x̂₀ reconstruction + clip,
    # the ddpm/ddim update, the noise add — runs as ONE kernel call per
    # step instead of ~a dozen elementwise HLOs, consuming the per-row
    # (B, K) schedule-coefficient matrix as device arguments. Honored by
    # the serving samplers (sample/ddpm.make_request_sampler and
    # make_ring_step_fn — both serve.scheduler values share it). "auto"
    # enables it on TPU backends only; True forces it (interpret mode
    # off-TPU: exact, slow — the tier-1 parity path); False keeps the
    # unfused chain. dpm++ 2M cannot fuse (multistep history): True
    # errors, 'auto' falls back to the unfused scan ('request'
    # scheduler) / the first-order fallback fuses fine ('step').
    fused_step: Any = False
    # Stochastic multi-view conditioning for trajectory serving
    # (3DiM §3.2; docs/DESIGN.md "Trajectory serving & stochastic
    # conditioning"). True (default): each denoise step of a trajectory
    # row draws its conditioning view UNIFORMLY from the row's frame
    # bank with the slot's PRNG carry — the paper's protocol, what makes
    # a k=1 model render consistent orbits. False: condition every step
    # on the MOST RECENT bank frame (deterministic; an ablation/debug
    # mode, not the paper protocol). Changes the compiled step program
    # body, so it rides the stepper program-cache key; the bank gather
    # happens BEFORE the UNet forward either way, so diffusion.fused_step
    # kernels (ops/fused_step.py) fuse unchanged.
    stochastic_cond: Any = True


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """SRN-format dataset options (reference: dataset/data_loader.py:116-140)."""

    root_dir: str = "cars_train_val"
    img_sidelength: int = 64
    max_num_instances: int = -1
    max_observations_per_instance: int = 50
    specific_observation_idcs: Optional[Tuple[int, ...]] = None
    samples_per_instance: int = 1
    # Record backend: 'files' = walk the SRN per-scene PNG/pose tree (the
    # reference layout); 'packed' = read the sharded record format
    # (data/records.py — root_dir is then a `nvs3d pack` output dir with
    # index.json). Packed reads are per-host at shard granularity, served
    # through the compute-overlapped PipelinedLoader (decode worker pool
    # sized by num_workers, depth by prefetch), and produce bit-identical
    # training batches to 'files' for the same (seed, epoch, index). The
    # `loader` knob below only applies to 'files'.
    backend: str = "files"
    # Pipeline loader for backend='files': 'native' = C++ threaded loader
    # (native/libnvs3d_io.so, falls back to grain if the library can't
    # build), 'grain' = Grain worker processes, 'python' = in-process
    # iterator.
    loader: str = "native"
    num_workers: int = 8
    prefetch: int = 4
    shuffle_seed: int = 0
    # Data fault tolerance: a record whose image/pose fails to load is
    # QUARANTINED (skipped for the rest of the run, reported on stderr) and
    # a substitute record is drawn, up to this many consecutive redraws
    # before the batch is declared unbuildable. Uniform across the python,
    # Grain, and native backends. 0 = faults are fatal (old behavior).
    max_record_retries: int = 3
    # Corpus mixer (data/corpus.py; ROADMAP item 5): '' = off (root_dir is
    # the single corpus, exactly the pre-mixer behavior). Otherwise a
    # comma-separated list of `name:weight:path` entries, e.g.
    # "cars:3:/data/cars_packed,chairs:1:/data/chairs_packed" — N named
    # packed corpora sampled per batch-slot with probability weight/Σ,
    # drawn from the SAME single sequential rng as the plain packed
    # loader (a one-corpus mix is bit-identical to backend='packed'
    # today). Requires backend='packed'; every corpus must be a `nvs3d
    # pack` output dir. Batches gain int32 `corpus_id` (loss attribution)
    # and `category` (scene-category conditioning when model.num_classes
    # > 0) fields.
    mix: str = ""


@dataclasses.dataclass(frozen=True)
class WatchdogConfig:
    """Hang/stall watchdog (utils/watchdog.py; docs/DESIGN.md "Stall
    recovery"). Budgets are wall-clock seconds a single armed phase may
    run before the watchdog declares a stall, dumps a diagnosis bundle
    (all-thread stacks, heartbeat ages, device memory if reachable), logs
    a `stall` row in events.csv, and escalates. Compile budgets are
    separate from steady-state step budgets: the first dispatch of a jitted
    program legitimately takes minutes, while a steady-state step that
    takes 10 minutes is a wedged backend. Defaults are generous on purpose
    — the watchdog exists to catch hour-scale silent hangs, not to police
    slow steps."""

    enabled: bool = True
    # Monitor thread poll interval. Stall detection latency is one
    # interval past the budget; the thread is asleep otherwise.
    check_interval_s: float = 2.0
    # Per-phase budgets (seconds). A phase is armed while the trainer is
    # inside it; 0 disables that phase's deadline.
    data_fetch_s: float = 600.0
    step_s: float = 600.0
    compile_s: float = 3600.0  # first dispatch of each jitted program
    checkpoint_save_s: float = 900.0
    eval_s: float = 1800.0
    # Hard-exit grace: if an armed phase is STILL stuck this many seconds
    # AFTER its budget expired (the main thread never came back to observe
    # the soft stall flag — a true wedge, e.g. uninterruptible IO),
    # the monitor thread dumps a final diagnosis and os._exit()s with
    # EXIT_STALL so a supervisor can restart the host. 0 = disabled.
    hard_exit_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class NumericsConfig:
    """In-jit per-layer-group numerics observatory (obs/numerics.py;
    docs/DESIGN.md "Training numerics & compile observatory").

    The train step ALWAYS emits per-group grad norm, param norm,
    update/param RMS ratio, grad max-abs, and non-finite leaf counts as
    READ-ONLY (G,)-shaped reductions grouped by the pipeline op list
    (models/xunet.pipeline_op_specs); `enabled` gates only the HOST-side
    consumer (numerics.jsonl rows, `nvs3d_grad_norm{group=...}` gauges,
    the EWMA spike detector's `numerics_spike` events). That split is
    the contract: flipping `enabled` is bitwise identical with zero
    recompiles by construction — one step program either way, with
    host-side decimation per `every`."""

    # Host-side publication switch. The device-side reductions are a
    # fixed part of the step program (see the module docstring).
    enabled: bool = False
    # Host-side decimation: device_get + publish the per-group stats every
    # N steps. The device-side reductions run every step either way (same
    # XLA program regardless); this only bounds host traffic.
    every: int = 1
    # EWMA spike detector: flag a group whose grad norm sits more than
    # this many EWMA standard deviations above its running mean.
    spike_z: float = 6.0
    # Decay of the per-group EWMA mean/variance the z-score is computed
    # against (0.9 ≈ a ~10-sample window).
    ewma_decay: float = 0.9


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training loop options (reference: train.py:82-126)."""

    batch_size: int = 2  # GLOBAL batch (sharded over the data axis)
    lr: float = 1e-4
    num_steps: int = 100_000
    save_every: int = 1000
    log_every: int = 50
    sample_every: int = 0  # 0 = never dump eval samples during training
    # Every N steps, sample the held batch's target poses and log PSNR/SSIM
    # vs ground truth to results_folder/eval.csv (0 = off). Cheap in-loop
    # quality signal; full held-out evaluation stays in the `eval` CLI.
    eval_every: int = 0
    eval_sample_steps: int = 64  # respaced steps for the in-loop eval
    # Held-out SRN tree for the in-loop probe: when set, the eval.csv curve
    # scores these views (true validation); when empty, the probe scores a
    # fixed batch of TRAINING views (reconstruction-progress signal only).
    eval_folder: str = ""
    seed: int = 0
    # Per-sample probability of dropping pose conditioning for CFG
    # (reference: train.py:64 uses 0.1, but bakes the mask at trace time).
    cond_drop_prob: float = 0.1
    # 'mse' (per-element mean squared error, the sane default) or 'frobenius'
    # (reference train.py:67: L2 norm of the whole flattened residual).
    loss: str = "mse"
    # Optimizer: 'adam' (reference train.py:46) or 'adafactor' (factored
    # second moments + no first moment — optimizer state drops from 2x
    # param bytes to ~sqrt-sized row/col stats; the fallback that gives
    # paper256 real HBM margin on a 16G chip, see train/state.make_optimizer)
    optimizer: str = "adam"
    grad_clip: float = 0.0  # 0 = off
    # Adam first-moment (m) storage dtype. 'bfloat16' halves m's HBM
    # footprint (0.5× param bytes saved) with negligible quality impact —
    # m is a fast EMA (β₁=0.9) whose per-step relative increments are well
    # above bf16 resolution. The second moment v stays f32 (its increments
    # are squared-gradient-scale and underflow bf16), and so does the
    # sampling EMA (decay 0.9999 increments sit below bf16 ulp — a bf16
    # EMA would freeze). Default f32 = exact reference-equivalent Adam.
    adam_mu_dtype: str = "float32"
    warmup_steps: int = 0
    # LR decay after warmup: 'constant' (reference behavior, train.py:46)
    # or 'cosine' (decay to lr_final_fraction·lr over num_steps).
    lr_schedule: str = "constant"
    lr_final_fraction: float = 0.1
    # Per-timestep loss weighting: 'none' (uniform — the reference and DDPM
    # default) or 'min_snr' (min-SNR-γ, Hang et al. 2023: clamp the
    # effective SNR-dependent weight at γ so easy low-noise timesteps stop
    # dominating training). Requires loss='mse' (the frobenius compat loss
    # is a whole-batch norm with no per-sample decomposition).
    loss_weighting: str = "none"
    min_snr_gamma: float = 5.0
    # Micro-batching inside the jitted step (lax.scan over batch slices,
    # gradients averaged) — trains configs whose full-batch activations
    # exceed HBM (paper256 ladder) without changing the effective batch.
    # This is an UPPER BOUND: the step uses the largest divisor of the
    # per-data-shard batch ≤ this value (train/step.effective_accum_steps),
    # so a single-chip tuning stays valid on any mesh. 1 = off.
    grad_accum_steps: int = 1
    # Fused multi-step dispatch: lax.scan over K staged batches in ONE XLA
    # program. Each scanned step is the full train step (fresh data, fresh
    # fold_in(rng, step) keys, optimizer update) — semantics identical to K
    # single dispatches; what changes is K-1 fewer host dispatch round
    # trips, which dominate wall clock for small models. Cadences
    # (log/save/eval/sample_every, num_steps,
    # profile window) must be multiples of K — validate() enforces.
    steps_per_dispatch: int = 1
    # ZeRO/FSDP: shard params + optimizer state over the mesh 'data' axis
    # (parallel/mesh.fsdp_spec). The reference replicates everything per
    # device (train.py:46).
    fsdp: bool = False
    # Weight-update sharding ('replicated' or 'zero'). 'zero' keeps params
    # REPLICATED for fwd/bwd (unlike fsdp, no per-layer all-gathers in the
    # forward) but shards the Adam moments + EMA over the mesh 'data' axis
    # (parallel/zero.py): gradients reduce-scatter into 1/N shards, the
    # update runs on each replica's shard, and fresh params all-gather out —
    # "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
    # Training" (Xu et al. 2020). opt_state+EMA device bytes drop to
    # ~1/data_shards while the step stays numerically identical to
    # 'replicated'. Requires optimizer='adam' (adafactor's factored second
    # moments don't survive the flatten/pad shard layout) and fsdp=False
    # (fsdp already shards the whole state its own way).
    update_sharding: str = "replicated"
    # Tensor parallelism: shard attention heads + conv/dense output channels
    # over the mesh 'model' axis (parallel/mesh.tp_spec). No-op unless
    # mesh.model > 1. The reference has no TP (SURVEY.md §2.3).
    tp: bool = False
    ema_decay: float = 0.0  # 0 = off; 3DiM paper uses EMA for sampling
    # Host-side EMA: keep the EMA buffer in host RAM instead of HBM
    # (frees 4 bytes/param on-chip — 2.6G for the 708M-param paper256
    # model, the margin between fitting a 16G chip and OOM). The Trainer
    # pulls params every ema_host_every steps and folds them in with the
    # decay^k correction (ema ← d^k·ema + (1−d^k)·params — the standard
    # sparse-EMA update; exact for k=1). Checkpointed with the state.
    ema_host: bool = False
    ema_host_every: int = 25
    # Dtype for the in-loop probe's pinned param copy (sample/eval probes).
    # '' = keep the param/EMA dtype (f32 — exact). 'bfloat16' halves the
    # probe pin: at paper256 scale the f32 probe copy is ~2.6G on a chip
    # already at ~15.3G of 15.75G (record deleted in PR 21) —
    # the probe would OOM mid-training. The probe is a trend signal
    # (eval.csv curve), and the paper256 model computes in bf16 anyway, so
    # bf16 probe weights cost ~nothing in signal. The probe copy is
    # explicitly freed after each probe either way.
    probe_dtype: str = ""
    results_folder: str = "./results"
    checkpoint_dir: str = "./checkpoints"
    resume: bool = True  # auto-resume from latest checkpoint (ref: absent)
    # --- observability (SURVEY.md §5.1-5.2: the reference has none) ---
    # jax.profiler trace window: [profile_from, profile_from+profile_steps).
    # Traces land in <results_folder>/profile; 0 steps disables.
    profile_from: int = 10
    profile_steps: int = 0
    # Debug mode: jax_debug_nans (NaN source localization in jitted code).
    debug_nans: bool = False
    # Checkpoint + clean exit on SIGTERM (TPU preemption); with resume=True
    # the rescheduled run continues from the last step.
    handle_preemption: bool = True
    # --- fault tolerance: the guard → rollback → fallback ladder ---
    # (docs/DESIGN.md "Fault tolerance"; SURVEY.md §5.3-§5.4 — the
    # reference dies on the first NaN and bricks on a torn checkpoint.)
    # Step anomaly guard (train/guard.py): skip the optimizer/EMA update on
    # steps with non-finite loss or grad norm. On by default: for clean
    # runs the guarded step is numerically identical to the unguarded one.
    anomaly_guard: bool = True
    # > 0: additionally flag steps whose loss exceeds factor × a running
    # EMA of accepted losses (e.g. 10.0). Off by default — unlike the
    # non-finite check it can fire on legitimate loss spikes.
    loss_spike_factor: float = 0.0
    # Consecutive anomalous steps before the Trainer rolls back to the last
    # good checkpoint (with a reseeded RNG so the replayed window draws
    # different noise/timesteps).
    max_anomaly_strikes: int = 3
    # Rollback budget: after this many rollbacks the run aborts loudly
    # instead of thrashing between a poisoned basin and the checkpoint.
    max_rollbacks: int = 2
    # Remat override for the TRAINING build of the model: '' (default) =
    # inherit model.remat; otherwise one of model.remat's values
    # (False/'none', True/'full', 'dots') applied to the XUNet blocks
    # for the train step only. Lets one config train with
    # rematerialization (activation memory bound) while sampling/serving
    # build the same checkpoint-compatible model without it (forward-only
    # paths gain nothing from remat) — the remat/donation tuning knob of
    # ROADMAP item 5.
    remat: Any = ""
    # --- hang/stall robustness (docs/DESIGN.md "Stall recovery") ---
    # Heartbeat watchdog over the run's phases (utils/watchdog.py).
    watchdog: WatchdogConfig = dataclasses.field(
        default_factory=WatchdogConfig)
    # Per-layer-group numerics observatory (obs/numerics.py): read-only,
    # bitwise-neutral, zero-recompile stats over the train step.
    numerics: NumericsConfig = dataclasses.field(
        default_factory=NumericsConfig)
    # `nvs3d train --supervise` restart budget: the supervisor restarts a
    # crashed or watchdog-stalled child (resuming via the checkpoint
    # integrity walk-back) at most this many times, with exponential
    # backoff, then gives up loudly.
    max_restarts: int = 3
    # Resolution ladder (train/ladder.py; ROADMAP item 5): '' = off (one
    # flat run at data.img_sidelength for num_steps). Otherwise a
    # comma-separated `res:steps` schedule, e.g. "64:20000,128:10000" —
    # progressive training that runs each rung at its resolution for its
    # step count against ONE checkpoint_dir (the fully-convolutional
    # XUNet keeps an identical param tree at every resolution, PROVIDED
    # model.attn_resolutions selects the same UNet levels at every rung
    # — validate() enforces this). Rung
    # boundaries are canonical checkpoint boundaries (forced save), rung
    # selection on resume derives from the restored step alone, and
    # mid-rung resume is bit-identical to an uninterrupted rung. The
    # promotion gate probes at EVERY rung resolution
    # (registry/gate.run_gate_matrix). Overrides train.num_steps with the
    # schedule's cumulative total.
    ladder: str = ""


@dataclasses.dataclass(frozen=True)
class BrownoutConfig:
    """Serving brownout ladder (docs/DESIGN.md "Serving survivability").

    Two pressure signals — queue depth (queued, undispatched requests)
    and step debt (denoise steps still owed to the ring + queue) — drive
    a three-level ladder evaluated at admission time:

      level 0 (serving)  admit normally;
      level 1 (degraded) admit, but cap trajectory requests' bank window
                         at `k_cap` and their frame count at
                         `max_frames_cap` (cheaper orbits, full refusal
                         not yet needed);
      level 2 (shedding) reject with a structured retryable reason
                         (`Rejected.retryable=True`, `retry_after_s`)
                         BEFORE the hard queue-full backstop.

    A threshold of 0 disables that signal/level; all four at 0 (the
    default) disables the ladder entirely. Transitions are logged
    (events.csv `brownout` rows) and exported as the
    `nvs3d_brownout_level` gauge."""

    # Level-1 (degrade) thresholds: queued requests / owed denoise steps.
    queue_soft: int = 0
    debt_soft: int = 0
    # Level-2 (shed) thresholds. Must be >= the soft ones when both set.
    queue_hard: int = 0
    debt_hard: int = 0
    # Degraded-admission caps for trajectory requests (0 = leave as
    # requested). Applied at admission, so an in-flight orbit never
    # changes shape mid-ring.
    k_cap: int = 0
    max_frames_cap: int = 0
    # Hint returned with level-2 rejects: how long the client should
    # back off before retrying.
    retry_after_s: float = 0.25


@dataclasses.dataclass(frozen=True)
class SLOConfig:
    """Serving SLOs (obs/slo.py; docs/DESIGN.md "Request tracing, SLOs
    & flight recorder"): declarative per-step-class latency objectives
    scored live against every completed request, with multi-window
    burn-rate breach detection (`nvs3d_slo_*` gauges, `slo_breach`
    events)."""

    # Per-step-class latency budgets: "<steps>:<latency_ms>,..." e.g.
    # "4:500,64:2000" — a 4-step request owes a response in 500 ms.
    # Requests are scored against the smallest class covering their
    # step count. "" (default) disables the engine entirely.
    targets: str = ""
    # Availability objective per class: the fraction of requests that
    # must meet their latency budget (and succeed at all).
    objective: float = 0.99
    # Multi-window burn-rate alerting: a breach needs BOTH the fast
    # window burning above fast_burn (paging-fast, noisy alone) AND the
    # slow window above slow_burn (sustained, slow alone). The default
    # thresholds are the standard 14x/2x pairing for a 99% objective.
    fast_window_s: float = 60.0
    slow_window_s: float = 600.0
    fast_burn: float = 14.0
    slow_burn: float = 2.0


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Sampling-service front-end (sample/service.py; `nvs3d serve`).

    The service coalesces concurrent requests into padded batches at
    power-of-two bucket sizes and dispatches each bucket through an LRU
    cache of compiled sampler programs — warm traffic never recompiles
    (docs/DESIGN.md "Serving")."""

    # Scheduler: 'step' (default) = persistent stepper with STEP-LEVEL
    # continuous batching — one compiled denoise-step program per bucket
    # shape runs over a ring of active request slots; new arrivals join
    # the ring between steps and finished rows exit immediately, so a
    # 4-step distilled request never waits behind a 256-step one, and
    # requests with different step counts / guidance weights share one
    # program (t and w are device arguments, not compile-time constants).
    # 'request' = the PR 3 whole-request dispatcher (one lax.scan per
    # coalesced group), kept as the serve_bench baseline and for exact
    # dpm++ 2M serving — the stepper serves dpm++ with the first-order
    # (history-free) update, same rule as the stochastic sampler.
    scheduler: str = "step"
    # Largest coalesced batch (top of the power-of-two bucket ladder).
    max_batch: int = 8
    # Bounded request queue: a submit past this depth is REJECTED with a
    # reason (events.csv `reject` row) instead of growing latency unboundedly.
    queue_depth: int = 64
    # How long the batcher holds the oldest queued request open for
    # co-riders before dispatching a partial bucket. 0 = dispatch
    # immediately (no coalescing beyond what is already queued).
    flush_timeout_ms: float = 10.0
    # Default per-request queue-wait deadline; a request still undispatched
    # past it is rejected (deadline_exceeded). 0 = no deadline. Requests
    # can override per call.
    default_deadline_ms: float = 0.0
    # LRU capacity of the sampler-program cache, in (bucket, sampler
    # config) entries. Each entry holds a compiled XLA program.
    program_cache_entries: int = 8
    # Respaced reverse-process steps for served requests; 0 = use
    # diffusion.sample_timesteps.
    sample_steps: int = 0
    # Serving precision (sample/precision.py): what the service/watcher
    # put ON DEVICE at weight-stage time. 'float32' = weights as
    # published (exact, the default); 'bfloat16' = every float leaf cast
    # to bf16 (half the HBM residency/transfer, flax promotes on-chip);
    # 'int8' = per-channel symmetric weight-only int8 for conv/dense
    # kernels with f32 scales, bf16 elsewhere — the sampler program
    # dequantizes in-jit so weights REST quantized. The program-cache
    # key folds precision in, and the registry gate probes candidates AT
    # this precision so quantization loss counts against
    # registry.gate_margin_db. int8 requires registry staging: the
    # quantized deployment must serve gate-probed registry versions
    # (`nvs3d serve --registry`), never raw checkpoints.
    precision: str = "float32"
    # Trajectory serving (docs/DESIGN.md "Trajectory serving & stochastic
    # conditioning"): per-ring-slot FRAME BANK capacity — the device-
    # resident (k_max, H, W, C) buffer of clean frames each trajectory
    # request conditions on (a random bank view per denoise step, the
    # 3DiM stochastic-conditioning protocol, drawn in-jit from the
    # slot's PRNG carry). 0 (default) disables trajectory serving
    # entirely: the stepper runs the exact pre-bank program, so
    # single-shot serving is bit-identical to a build without this
    # feature (zero-cost when unused). > 0 requires scheduler='step'
    # (the whole-request dispatcher has no ring for frames to re-enter).
    # k_max is part of the stepper program SHAPE, so one service serves
    # one k_max — mixed single-shot + trajectory traffic still compiles
    # one program per bucket (per-request banks smaller than k_max ride
    # the same arrays with a lower effective window).
    k_max: int = 0
    # Upper bound on poses per TrajectoryRequest (backpressure for
    # orbit-sized requests: a 10k-frame request is a typo, not a load).
    max_frames: int = 64
    # Where the service writes its events.csv (rejections, deadline
    # expiries) — same schema as the trainer's.
    results_folder: str = "./serve"
    # --- survivability (docs/DESIGN.md "Serving survivability") ---
    # Graceful drain: on SIGTERM/SIGINT (`nvs3d serve`) or
    # SamplingService.drain(), new admissions are rejected with a
    # structured retryable reason while queued + in-ring work finishes;
    # past this budget the leftovers fail retryably and the worker stops.
    drain_timeout_s: float = 30.0
    # Worker supervisor (the serving analogue of `nvs3d train
    # --supervise`): a died worker thread is restarted with exponential
    # backoff at most this many times per service lifetime; undispatched
    # requests stay queued across the restart, in-flight ring rows fail
    # retryably. 0 disables restarts (a worker death stops the service).
    max_worker_restarts: int = 3
    # First-restart backoff; doubles per consecutive restart (capped at
    # 30 s). Small default: serving restarts race an SLO, not a
    # checkpoint restore.
    worker_backoff_s: float = 0.05
    # In-ring anomaly quarantine: consecutive non-finite steps (the
    # per-row device-side finite mask) a slot survives before it is
    # evicted and its ticket failed with SampleAnomaly. NaN never heals
    # under further denoising, so 1 (evict on first strike) is right for
    # production; > 1 exists for drills/diagnosis.
    anomaly_strikes: int = 1
    # stop()'s worker-join budget: past it the service writes a
    # stall-style all-thread-stacks diagnosis and raises instead of
    # silently leaking a wedged thread (PR 2 watchdog convention).
    stop_timeout_s: float = 10.0
    # Conditioning cache (docs/DESIGN.md "Conditioning cache & fused
    # serving attention"): compute XUNet's conditioning branch — the
    # per-level pose/FiLM embeddings and the cond-frame stem features,
    # which never change within a request — ONCE at admission (once per
    # frame-bank encode for trajectories) instead of inside every
    # denoise step. The activations live device-resident on the ring
    # slot alongside z/keys/banks and enter the step program as device
    # arguments, so program identity stays bucket/shape-only; the CFG
    # uncond (cond_mask=0) half is cached globally per (H, W) — it is
    # pose-independent — so guidance pairs share one encode, and a hot
    # swap invalidates it (in-flight slots die with the drain, pinned
    # to their start version). False (default) keeps the in-jit encode;
    # True requires scheduler='step'. Cached and uncached programs are
    # bit-identical single-key (tests/test_cond_cache.py).
    cond_cache: bool = False
    # Minimum wall-clock per ring dispatch, milliseconds (0 = off). After
    # the device work of a dispatch completes, the worker sleeps out the
    # residual — a PACING floor, not a slowdown of the device program.
    # Two uses: (a) rate-limiting a replica that shares a host with
    # latency-sensitive neighbors; (b) fleet drills on few-core CI hosts,
    # where N CPU replicas otherwise contend for the same core and a
    # router scaling lane measures scheduler noise instead of dispatch
    # overlap — the sleep releases the GIL/core, emulating N device-bound
    # replicas honestly (tools/serve_bench.py --fleet records the floor
    # it ran with in the artifact).
    step_floor_ms: float = 0.0
    # Brownout degradation ladder (off by default).
    brownout: BrownoutConfig = dataclasses.field(
        default_factory=BrownoutConfig)
    # Per-step-class latency SLOs + burn-rate alerting (off by default).
    slo: SLOConfig = dataclasses.field(default_factory=SLOConfig)


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """Fleet router (serve/router.py; `nvs3d route`; docs/DESIGN.md
    "Fleet serving").

    A thin front-end that spreads traffic over N SamplingService
    replicas: least-step-debt dispatch fed by each replica's /healthz
    gauges, session affinity for trajectory orbits, transparent failover
    on death/drain/retryable rejection, and registry-channel rolling
    deploys gated on SLO burn + swap-breaker state."""

    # Health-poll period for the background poller (seconds). Between
    # polls the router tracks its own outstanding-steps delta per
    # replica, so dispatch pressure is poll-fresh + local-accurate.
    health_poll_s: float = 0.5
    # A polled snapshot older than this is STALE: the replica is treated
    # as unknown-health (dispatchable only if nothing fresh is) rather
    # than trusted at its last-known debt.
    health_ttl_s: float = 5.0
    # Failover budget PER REQUEST: how many times a request may be
    # re-routed (replica died, drained, or shed retryably) before the
    # router gives up and surfaces the structured error to the caller.
    # Distinct from sample/client.submit_with_retry's retries: that loop
    # re-asks the SAME endpoint later; this budget moves the request
    # ACROSS replicas now.
    retry_budget: int = 3
    # When EVERY eligible replica sheds (fleet-wide brownout) the router
    # does NOT burn the retry budget spinning across replicas — it
    # raises FleetSaturated (retryable, carrying the fleet's max
    # retry_after_s) after this many full-fleet sweeps.
    saturation_sweeps: int = 1
    # Session-affinity table capacity (orbit sessions pinned to the
    # replica holding their frame bank); oldest entries evict first.
    affinity_entries: int = 1024
    # --- rolling deploy (serve/deploy.py; `nvs3d route deploy`) ---
    # Per-replica router-level drain budget: out-of-rotation wait for
    # step_debt+queue_depth to hit zero before the channel poke.
    deploy_drain_timeout_s: float = 30.0
    # Post-swap probation: the canary serves back in rotation this long
    # while the gate watches its SLO fast-burn and swap breaker.
    deploy_probation_s: float = 2.0
    # Gate threshold: probation fails when the replica's fast-window SLO
    # burn rate reaches this (default = the fast-window page threshold,
    # SLOConfig.fast_burn).
    deploy_burn_max: float = 14.0
    # Budget for a poked replica to report the target model_version
    # before the deploy declares the swap failed and rolls back.
    deploy_swap_timeout_s: float = 30.0
    # --- self-healing fleet (docs/DESIGN.md "Fleet survivability") ---
    # Virtual nodes per replica on the consistent-hash affinity ring.
    # More vnodes = smoother key spread; the ring is rebuilt only when
    # the replica SET changes, so this is a startup cost.
    affinity_vnodes: int = 64
    # Hedged dispatch for stateless singles: if the first replica has
    # not answered after this long, a second copy goes to the next
    # replica on the ring; first response wins, the loser is abandoned
    # (recorded as a `router_hedge` span). 0 disables hedging.
    # Trajectories never hedge — their frame bank is single-homed.
    hedge_delay_s: float = 0.0
    # Per-hop timeout budget (seconds): one replica attempt may consume
    # at most this much of the request's total timeout before the
    # router abandons the hop and fails over — a wedged replica can
    # never eat the whole client deadline. 0 = no per-hop bound (the
    # request timeout is the only clock).
    hop_timeout_s: float = 0.0
    # Gray-failure demotion: a replica whose polled latency_p99_s is
    # >= this factor x the fleet's BEST fresh p99 is demoted — it only
    # receives dispatches when no un-demoted replica is eligible.
    # 0 disables (PR 16 behavior).
    demote_p99_factor: float = 0.0
    # Router journal (serve/journal.py): a full outstanding-steps
    # snapshot row is appended every N hop records so replay cost stays
    # bounded. The journal itself is enabled by passing journal= to
    # FleetRouter (or `journal` in the router_main spec).
    journal_snapshot_every: int = 32
    # --- fleet supervisor (serve/fleet_supervisor.py) ---
    # Restart budget PER SLOT; exhaustion marks the slot failed loudly
    # (replica_giveup event) instead of flapping forever.
    supervisor_max_restarts: int = 3
    # Exponential restart backoff base / cap (PR 2 discipline:
    # min(cap, backoff_s * 2**(restarts-1))).
    supervisor_backoff_s: float = 1.0
    supervisor_backoff_cap_s: float = 60.0
    # A replica whose ready-file heartbeat is older than this is WEDGED
    # (the process is alive but its event loop stopped beating).
    supervisor_heartbeat_max_age_s: float = 15.0
    # Consecutive /healthz failures before a live process is declared
    # wedged (transient poll misses must not trigger a restart).
    supervisor_health_fails: int = 3
    # Supervisor monitor-loop period (seconds).
    supervisor_poll_s: float = 1.0
    # Budget for a restarted replica to write its ready file AND answer
    # /healthz with the expected version before the resurrection is
    # declared failed (burning one restart from the budget).
    supervisor_ready_timeout_s: float = 300.0


@dataclasses.dataclass(frozen=True)
class DistillConfig:
    """Progressive distillation (train/distill.py; `nvs3d distill`).

    Salimans & Ho 2022 (arXiv 2202.00512): each round trains a student —
    initialized from the teacher — to match TWO teacher DDIM steps with
    ONE of its own, halving the sampling-step count per round
    (start_steps → start_steps/2 → … → target_steps). The registry is
    the teacher/student store: the teacher is read from a channel, each
    student generation is published as a version, and promotion runs the
    existing fixed-seed PSNR gate (registry/gate.py)."""

    # Step count of the first teacher (respaced from diffusion.timesteps).
    start_steps: int = 256
    # Stop once the student reaches this many sampling steps. Must divide
    # start_steps by a power of two (one halving per round).
    target_steps: int = 4
    # Optimizer updates per halving round.
    steps_per_round: int = 200
    # Distillation batch size (host-assembled; single-device).
    batch_size: int = 8
    lr: float = 1e-4
    # Truncated-SNR loss-weight cap: weight = clip(SNR, 1, snr_clip) on
    # the x₀-space distillation loss (the paper's max(SNR, 1), bounded so
    # near-clean timesteps cannot dominate a round).
    snr_clip: float = 5.0
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class RegistryConfig:
    """Model lifecycle registry (novel_view_synthesis_3d_tpu/registry/;
    docs/DESIGN.md "Model lifecycle").

    A content-hashed, versioned store of publishable model snapshots with
    channel pointers (`latest` = newest published, `stable` = quality-
    gated): the trainer PUBLISHES to `latest` every `publish_every` steps,
    `nvs3d registry promote` runs the PSNR gate and advances `stable`, and
    a serving process subscribed to a channel HOT-RELOADS the new params
    under live traffic (sample/service.py swap path)."""

    # Registry root directory (one dir per version under <dir>/versions).
    dir: str = "./registry"
    # Trainer hook cadence: every N steps the EMA snapshot (params when
    # EMA is off) is published to the `latest` channel without blocking
    # the step loop. 0 = trainer never publishes.
    publish_every: int = 0
    # Publish the EMA tree when the run trains one (it is what you sample
    # with); False forces raw params.
    publish_ema: bool = True
    # Channel a serving process subscribes to (`nvs3d serve --registry`);
    # production serves `stable`, canaries can ride `latest`.
    channel: str = "stable"
    # Reload-watcher poll period (seconds) for the serving subscription.
    poll_s: float = 2.0
    # Quality gate: a candidate may regress the fixed-seed PSNR probe vs
    # the incumbent by at most this many dB before promotion is refused
    # (gate_fail event + non-zero exit; the stable pointer never moves).
    gate_margin_db: float = 0.5
    # Respaced reverse-process steps for the gate's PSNR probe (small on
    # purpose: the gate is a regression tripwire, not a benchmark).
    gate_sample_steps: int = 8
    # Probe batch rows scored by the gate.
    gate_batch: int = 4
    # Multi-view consistency gate (eval/metrics.multi_view_consistency):
    # when > 0, `nvs3d registry promote` and the distill auto-promote
    # ALSO probe adjacent-frame PSNR over a fixed autoregressive orbit
    # of this many frames (stochastic conditioning, fixed seed), and a
    # candidate regressing that metric beyond gate_margin_db is refused
    # — distilled/quantized models are gated on TRAJECTORY quality, not
    # just single-frame PSNR. 0 (default) = single-frame gate only.
    # Needs >= 2 frames for an adjacent pair.
    gate_trajectory_frames: int = 0
    # Fixed probe seed: candidate and incumbent see identical noise.
    gate_seed: int = 0
    # `registry gc` retention: keep the newest K versions (channel-pinned
    # versions are always kept).
    keep: int = 5


@dataclasses.dataclass(frozen=True)
class ObsProfileConfig:
    """Continuous profiling windows (obs/profiler.py; docs/DESIGN.md
    "Performance observatory"): periodically re-arm a bounded
    jax.profiler window, attribute the captured device time to the
    shared op-group vocabulary, and land profile_window rows +
    nvs3d_group_device_time_seconds gauges. Host-side only — bitwise
    outputs and compile identity are unchanged; window-armed steps are
    excluded from the step-rate gauges. On by default: the defaults
    amortize to well under the 1% overhead contract (one ~2-step window
    per 500 steps), and tiny test runs never reach the first cadence."""

    enabled: bool = True
    # Training cadence: arm a window every N steps (window covers
    # [N, N + window_steps) etc.). 0 disables the training profiler.
    every_steps: int = 500
    # Steps per window. Short on purpose: a window prices ~window/every
    # in excluded step-rate samples plus the host-side parse.
    window_steps: int = 2
    # Serving cadence, counted in dispatches (SamplingService.dispatches
    # spans ring steps and batched dispatches). 0 disables in serving.
    serve_every_dispatches: int = 2000
    serve_window_dispatches: int = 2


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Unified telemetry layer (novel_view_synthesis_3d_tpu/obs/;
    docs/DESIGN.md "Observability"): span tracing with Perfetto export,
    the metrics registry + sinks, and utilization gauges. Everything here
    is host-side — no jitted code changes, zero new recompiles."""

    # Master switch. False: NullTracer, no JSONL, no device polling, no
    # endpoint — the legacy metrics.csv/events.csv still write (they are
    # the run's primary record, not optional telemetry).
    enabled: bool = True
    # Span tracing: collect trainer/serving phase spans and export
    # <results_folder>/trace.json (Chrome-trace JSON, Perfetto-loadable)
    # at the end of the run.
    trace: bool = True
    # Bounded span buffer: a million-step run keeps the most recent spans
    # and counts the rest as dropped instead of growing host memory.
    trace_max_events: int = 200_000
    # Prometheus text-exposition endpoint (/metrics + /healthz, stdlib
    # http.server). 0 (default) = no socket is ever opened; set a port to
    # serve from `nvs3d train` and `nvs3d serve`.
    metrics_port: int = 0
    # Bind address for the endpoint. 127.0.0.1 by default — an
    # unauthenticated scrape target must not face the network; scrape
    # remotely over an SSH port forward (docs/TPU_VM_SETUP.md).
    metrics_host: str = "127.0.0.1"
    # telemetry.jsonl sink: machine-readable span/gauge/event stream in
    # the results folder (tools/summarize_bench.py reads it).
    jsonl: bool = True
    # Size cap on telemetry.jsonl: past this many MB the file rotates
    # aside to telemetry.jsonl.old (one generation kept, the events.csv
    # stale-schema convention) so a multi-day serve run cannot fill the
    # disk. 0 = unbounded.
    telemetry_max_mb: float = 256.0
    # Device-memory poll period (seconds) for the bytes-in-use/peak/limit
    # gauges; 0 disables the monitor thread.
    device_poll_s: float = 10.0
    # On-demand jax.profiler window over the step range [a, b): XProf
    # captures line up with span timestamps. (0, 0) = off. Complements
    # train.profile_from/profile_steps (kept for back-compat).
    xprof_steps: Tuple[int, int] = (0, 0)
    # One-time jit(...).lower().cost_analysis() FLOPs estimate of the
    # train step, feeding the MFU / imgs-per-sec gauges and the mfu
    # column in metrics.csv. Costs one extra trace (no XLA compile) at
    # startup.
    cost_analysis: bool = True
    # Continuous per-op-group profiling windows (obs/profiler.py).
    profile: ObsProfileConfig = dataclasses.field(
        default_factory=ObsProfileConfig)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device mesh for distributed execution (replaces reference pmap, §2.3).

    Axes: 'data' = DP (batch sharding, psum over ICI emitted by XLA),
    'model' = reserved for TP, 'seq' = ring-attention sequence parallelism.
    """

    data: int = -1  # -1 = all remaining devices
    model: int = 1
    seq: int = 1
    # Pipeline parallelism: partition the XUNet's block sequence into this
    # many stages placed along the 'model' axis (parallel/pipeline.py).
    # stages>1 runs the train.grad_accum_steps microbatches through a
    # GPipe-style fill/drain schedule with ppermute stage handoff, so the
    # model's activations (and its stage params inside the step) scale past
    # one chip. Requires mesh.model == stages and is mutually exclusive
    # with tensor parallelism / sequence parallelism / fsdp.
    stages: int = 1


@dataclasses.dataclass(frozen=True)
class Config:
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    diffusion: DiffusionConfig = dataclasses.field(default_factory=DiffusionConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)
    obs: ObsConfig = dataclasses.field(default_factory=ObsConfig)
    registry: RegistryConfig = dataclasses.field(
        default_factory=RegistryConfig)
    distill: DistillConfig = dataclasses.field(
        default_factory=DistillConfig)
    router: RouterConfig = dataclasses.field(
        default_factory=RouterConfig)

    # ------------------------------------------------------------------
    # Validation
    # ------------------------------------------------------------------
    def validate(self) -> "Config":
        """Cross-field sanity checks with actionable messages.

        Catches the mistakes that otherwise surface as opaque errors deep
        inside flax/XLA (e.g. GroupNorm's 32-group divisibility failing as
        a reshape error three modules down). Returns self so call sites can
        chain. Enum-valued fields (loss, objective, sampler, remat, …) are
        checked at their point of use, where the full context lives.
        """
        m, d, t = self.model, self.data, self.train
        errors = []
        if m.family == "tokens":
            errors.extend(_token_family_errors(m, d))
        elif m.family != "xunet":
            errors.append(f"model.family={m.family!r}: 'xunet' or 'tokens'")
        else:
            if m.tokens is not None:
                errors.append(
                    "model.tokens is set but model.family is 'xunet'")
            errors.extend(_xunet_family_errors(m, d))
        if not 0.0 <= m.dropout < 1.0:
            errors.append(f"model.dropout={m.dropout} outside [0, 1)")
        if m.num_cond_frames < 1:
            errors.append("model.num_cond_frames must be >= 1")
        if self.diffusion.timesteps < 1:
            errors.append("diffusion.timesteps must be >= 1")
        if not 1 <= self.diffusion.sample_timesteps <= self.diffusion.timesteps:
            errors.append(
                f"diffusion.sample_timesteps="
                f"{self.diffusion.sample_timesteps} must be in "
                f"[1, diffusion.timesteps={self.diffusion.timesteps}]")
        if t.eval_every > 0 and not (
                1 <= t.eval_sample_steps <= self.diffusion.timesteps):
            # Only enforced when the probe is on: eval_sample_steps is inert
            # otherwise, and a direct eval_step() call still gets a clear
            # error from sampling_schedule/respace.
            errors.append(
                f"train.eval_sample_steps={t.eval_sample_steps} must be in "
                f"[1, diffusion.timesteps={self.diffusion.timesteps}] when "
                "train.eval_every is set")
        if t.batch_size < 1:
            errors.append("train.batch_size must be >= 1")
        if self.data.samples_per_instance < 1:
            errors.append(
                f"data.samples_per_instance={self.data.samples_per_instance}"
                " must be >= 1")
        elif t.batch_size % self.data.samples_per_instance != 0:
            # Each index draw contributes samples_per_instance consecutive
            # batch slots (reference data_loader.py:183-195 semantics).
            errors.append(
                f"train.batch_size={t.batch_size} must be a multiple of "
                f"data.samples_per_instance="
                f"{self.data.samples_per_instance}")
        spd = t.steps_per_dispatch
        if spd < 1:
            errors.append(
                f"train.steps_per_dispatch={spd} must be >= 1")
        elif spd > 1:
            if t.num_steps % spd:
                errors.append(
                    f"train.num_steps={t.num_steps} must be a multiple of "
                    f"train.steps_per_dispatch={spd} (the loop advances "
                    "K steps per dispatch)")
            for nm in ("log_every", "save_every", "eval_every",
                       "sample_every"):
                v = getattr(t, nm)
                if v and v % spd:
                    errors.append(
                        f"train.{nm}={v} must be a multiple of "
                        f"train.steps_per_dispatch={spd} — the trainer only "
                        "observes step counts at dispatch boundaries, so a "
                        "misaligned cadence would silently never fire")
            if t.profile_steps and (t.profile_from % spd
                                    or t.profile_steps % spd):
                errors.append(
                    f"train.profile_from={t.profile_from}/profile_steps="
                    f"{t.profile_steps} must be multiples of "
                    f"train.steps_per_dispatch={spd}")
        if t.optimizer not in ("adam", "adafactor"):
            errors.append(
                f"train.optimizer={t.optimizer!r} must be 'adam' "
                "(reference, train.py:46) or 'adafactor' (memory-lean: "
                "factored second moments, no first moment)")
        if t.grad_accum_steps > 1 and t.loss == "frobenius":
            # Lifted out of train/step.make_train_step: the whole-tensor L2
            # norm is not decomposable across micro-batches (mean of micro
            # norms != full-batch norm), so accumulation would silently
            # change the reference-parity objective. Failing here costs
            # nothing; failing at step-build time costs the compile.
            errors.append(
                f"train.grad_accum_steps={t.grad_accum_steps} > 1 requires "
                "train.loss='mse' — the 'frobenius' whole-tensor norm has "
                "no per-micro-batch decomposition")
        if t.update_sharding not in ("replicated", "zero"):
            errors.append(
                f"train.update_sharding={t.update_sharding!r} must be "
                "'replicated' or 'zero' (ZeRO-style sharded Adam+EMA "
                "update, parallel/zero.py)")
        elif t.update_sharding == "zero":
            if t.optimizer != "adam":
                errors.append(
                    "train.update_sharding='zero' requires "
                    f"train.optimizer='adam' (got {t.optimizer!r}) — the "
                    "sharded update flattens optimizer moments per leaf, "
                    "which breaks adafactor's factored row/col stats")
            if t.fsdp:
                errors.append(
                    "train.update_sharding='zero' conflicts with "
                    "train.fsdp=True: fsdp already shards params + "
                    "optimizer state over 'data'; pick one")
        if t.adam_mu_dtype not in ("float32", "bfloat16"):
            errors.append(
                f"train.adam_mu_dtype={t.adam_mu_dtype!r} must be "
                "'float32' or 'bfloat16'")
        if t.probe_dtype not in ("", "float32", "bfloat16"):
            errors.append(
                f"train.probe_dtype={t.probe_dtype!r} must be '' (param "
                "dtype), 'float32', or 'bfloat16'")
        if t.remat not in ("", False, True, "none", "full", "dots"):
            errors.append(
                f"train.remat={t.remat!r} must be '' (inherit "
                "model.remat), False/'none', True/'full', or 'dots' — it "
                "overrides the checkpoint policy over XUNet blocks for "
                "the training build only")
        if t.ema_host and t.ema_decay <= 0:
            errors.append(
                "train.ema_host=True is inert without train.ema_decay > 0")
        if t.ema_host_every < 1:
            errors.append(
                f"train.ema_host_every={t.ema_host_every} must be >= 1")
        if not 0.0 <= t.cond_drop_prob <= 1.0:
            errors.append(
                f"train.cond_drop_prob={t.cond_drop_prob} outside [0, 1]")
        if t.loss_spike_factor != 0 and t.loss_spike_factor <= 1.0:
            errors.append(
                f"train.loss_spike_factor={t.loss_spike_factor} must be 0 "
                "(off) or > 1 — a factor <= 1 would flag ordinary steps "
                "whose loss sits at or above its own running mean")
        if t.max_anomaly_strikes < 1:
            errors.append(
                f"train.max_anomaly_strikes={t.max_anomaly_strikes} must "
                "be >= 1")
        if t.max_rollbacks < 0:
            errors.append(
                f"train.max_rollbacks={t.max_rollbacks} must be >= 0")
        if d.max_record_retries < 0:
            errors.append(
                f"data.max_record_retries={d.max_record_retries} must be "
                ">= 0")
        if d.backend not in ("files", "packed"):
            errors.append(
                f"data.backend={d.backend!r} must be 'files' (SRN "
                "PNG/pose tree) or 'packed' (sharded records from "
                "`nvs3d pack`; data.root_dir is the packed corpus dir)")
        if d.backend == "packed" and d.prefetch < 1:
            errors.append(
                f"data.prefetch={d.prefetch} must be >= 1 with "
                "data.backend='packed' (it sizes the pipelined loader's "
                "decode-ahead depth)")
        if d.mix:
            # Mirrors the train.adam_mu_dtype style: structural checks
            # with the semantics in the message — a malformed mix spec
            # must fail at startup, never as a mid-run KeyError.
            if d.backend != "packed":
                errors.append(
                    f"data.mix requires data.backend='packed' (got "
                    f"{d.backend!r}) — the mixer samples across `nvs3d "
                    "pack` corpora, the files backend has no corpus "
                    "identity")
            seen_names = set()
            for entry in d.mix.split(","):
                parts = entry.strip().split(":", 2)
                if len(parts) != 3 or not all(p.strip() for p in parts):
                    errors.append(
                        f"data.mix entry {entry.strip()!r} must be "
                        "'name:weight:path' (e.g. "
                        "'cars:3:/data/cars_packed')")
                    continue
                name, weight, _path = (p.strip() for p in parts)
                if name in seen_names:
                    errors.append(
                        f"data.mix names corpus {name!r} twice — names "
                        "key the per-corpus metrics and must be unique")
                seen_names.add(name)
                try:
                    w = float(weight)
                except ValueError:
                    w = -1.0
                if w <= 0:
                    errors.append(
                        f"data.mix corpus {name!r} has weight "
                        f"{weight!r} — must be a number > 0 (weights "
                        "are relative sampling odds, normalized over "
                        "the mix)")
        if m.num_classes < 0:
            errors.append(
                f"model.num_classes={m.num_classes} must be >= 0 (0 = no "
                "category conditioning, > 0 sizes the zero-init category "
                "embedding table)")
        if t.ladder:
            # Same loud-at-startup contract as data.mix above.
            rungs = []
            for entry in t.ladder.split(","):
                parts = entry.strip().split(":")
                if len(parts) != 2:
                    errors.append(
                        f"train.ladder entry {entry.strip()!r} must be "
                        "'resolution:steps' (e.g. '64:20000,128:10000')")
                    continue
                try:
                    res, steps = int(parts[0]), int(parts[1])
                except ValueError:
                    errors.append(
                        f"train.ladder entry {entry.strip()!r} must be "
                        "two integers 'resolution:steps'")
                    continue
                if res < 8 or res & (res - 1) != 0:
                    errors.append(
                        f"train.ladder resolution {res} must be a power "
                        "of two >= 8 (the UNet downsample chain halves "
                        "H/W per level)")
                if steps < 1:
                    errors.append(
                        f"train.ladder rung {entry.strip()!r} must train "
                        "for >= 1 step")
                rungs.append(res)
            if rungs != sorted(rungs):
                errors.append(
                    f"train.ladder={t.ladder!r} resolutions must be "
                    "non-decreasing — the ladder is progressive "
                    "low-to-high (64 before 128)")
            # The rung param trees must be STRUCTURALLY identical (one
            # checkpoint spans the ladder). Conv/norm shapes are
            # resolution-free, but model.attn_resolutions is keyed on
            # absolute feature-map resolution — if it selects different
            # UNet LEVELS at different rung resolutions, the trees
            # diverge (AttnBlock params appear under different blocks).
            patterns = {
                res: tuple(
                    lvl for lvl in range(len(m.ch_mult))
                    if (res >> lvl) in m.attn_resolutions)
                for res in sorted(set(rungs))}
            if len(set(patterns.values())) > 1:
                errors.append(
                    f"train.ladder={t.ladder!r} places attention at "
                    "different UNet levels per rung "
                    f"({ {r: list(p) for r, p in patterns.items()} }): "
                    "model.attn_resolutions is keyed on absolute "
                    "feature-map resolution, so the rung param trees "
                    "would be structurally incompatible — choose "
                    "attn_resolutions that select the SAME levels at "
                    "every rung resolution (e.g. [] to disable "
                    "attention for the ladder run)")
        if t.max_restarts < 0:
            errors.append(
                f"train.max_restarts={t.max_restarts} must be >= 0")
        nc = t.numerics
        if nc.every < 1:
            errors.append(
                f"train.numerics.every={nc.every} must be >= 1 (host-side "
                "decimation period for the per-group stats)")
        if nc.spike_z <= 0:
            errors.append(
                f"train.numerics.spike_z={nc.spike_z} must be > 0 (EWMA "
                "z-score threshold for numerics_spike events)")
        if not 0.0 < nc.ewma_decay < 1.0:
            errors.append(
                f"train.numerics.ewma_decay={nc.ewma_decay} must be in "
                "(0, 1)")
        wd = t.watchdog
        if wd.check_interval_s <= 0:
            errors.append(
                f"train.watchdog.check_interval_s={wd.check_interval_s} "
                "must be > 0")
        for nm in ("data_fetch_s", "step_s", "compile_s",
                   "checkpoint_save_s", "eval_s", "hard_exit_s"):
            if getattr(wd, nm) < 0:
                errors.append(
                    f"train.watchdog.{nm}={getattr(wd, nm)} must be >= 0 "
                    "(0 disables that deadline)")
        sv = self.serve
        if sv.scheduler not in ("step", "request"):
            errors.append(
                f"serve.scheduler={sv.scheduler!r} must be 'step' "
                "(step-level continuous batching) or 'request' (whole-"
                "request dispatch)")
        if sv.max_batch < 1 or (sv.max_batch & (sv.max_batch - 1)) != 0:
            errors.append(
                f"serve.max_batch={sv.max_batch} must be a power of two "
                "(the micro-batcher's bucket ladder is 1, 2, 4, …)")
        if sv.queue_depth < 1:
            errors.append(f"serve.queue_depth={sv.queue_depth} must be >= 1")
        if sv.flush_timeout_ms < 0:
            errors.append(
                f"serve.flush_timeout_ms={sv.flush_timeout_ms} must be >= 0")
        if sv.default_deadline_ms < 0:
            errors.append(f"serve.default_deadline_ms="
                          f"{sv.default_deadline_ms} must be >= 0")
        if sv.program_cache_entries < 1:
            errors.append(
                f"serve.program_cache_entries={sv.program_cache_entries} "
                "must be >= 1")
        if sv.sample_steps < 0 or sv.sample_steps > self.diffusion.timesteps:
            errors.append(
                f"serve.sample_steps={sv.sample_steps} must be in "
                f"[0, diffusion.timesteps={self.diffusion.timesteps}] "
                "(0 = diffusion.sample_timesteps)")
        if sv.precision not in ("float32", "bfloat16", "int8"):
            # Mirrors the train.adam_mu_dtype style: enum membership with
            # the semantics in the message (CLI overrides arrive as raw
            # strings — a typo must fail loudly, not serve f32 silently).
            errors.append(
                f"serve.precision={sv.precision!r} must be 'float32' "
                "(weights as published), 'bfloat16' (cast at stage "
                "time), or 'int8' (per-channel symmetric weight-only "
                "quantization, f32 scales, bf16 elsewhere)")
        elif sv.precision == "int8" and not self.registry.dir:
            # int8-requires-registry-staging: a quantized deployment must
            # serve gate-probed registry versions (the gate probes AT the
            # serving precision), never raw checkpoints with no
            # quality-gate lineage. `nvs3d serve` enforces the --registry
            # flag itself; this catches configs that disarm the registry
            # entirely.
            errors.append(
                "serve.precision='int8' requires registry staging "
                "(registry.dir must be set): quantized serving only "
                "deploys versions whose PSNR gate probed them at int8 "
                "(registry/gate.py), so quantization loss counts "
                "against registry.gate_margin_db")
        if sv.k_max < 0:
            errors.append(
                f"serve.k_max={sv.k_max} must be >= 0 (0 disables "
                "trajectory serving; > 0 sizes each ring slot's device-"
                "resident frame bank)")
        elif sv.k_max > 0 and sv.scheduler != "step":
            errors.append(
                f"serve.k_max={sv.k_max} requires serve.scheduler='step' "
                "— trajectory frames re-enter the stepper RING between "
                "denoise steps; the whole-request dispatcher has no ring "
                "for them to re-enter (set serve.scheduler='step' or "
                "serve.k_max=0)")
        if sv.cond_cache not in (True, False):
            errors.append(
                f"serve.cond_cache={sv.cond_cache!r} must be True or "
                "False (the admission-time conditioning cache is host "
                "orchestration, not a backend kernel — there is no "
                "'auto' tier)")
        elif sv.cond_cache and sv.scheduler != "step":
            errors.append(
                "serve.cond_cache=True requires serve.scheduler='step' "
                "— cached cond activations live on stepper ring slots; "
                "the whole-request dispatcher has no slot to pin them "
                "to (set serve.scheduler='step' or cond_cache=False)")
        if sv.max_frames < 1:
            errors.append(
                f"serve.max_frames={sv.max_frames} must be >= 1 (it "
                "bounds the poses per trajectory request)")
        if sv.drain_timeout_s < 0:
            errors.append(
                f"serve.drain_timeout_s={sv.drain_timeout_s} must be "
                ">= 0 (the in-flight budget of a graceful drain)")
        if sv.max_worker_restarts < 0:
            errors.append(
                f"serve.max_worker_restarts={sv.max_worker_restarts} "
                "must be >= 0 (0 disables supervised worker restarts)")
        if sv.worker_backoff_s < 0:
            errors.append(
                f"serve.worker_backoff_s={sv.worker_backoff_s} must be "
                ">= 0")
        if sv.anomaly_strikes < 1:
            errors.append(
                f"serve.anomaly_strikes={sv.anomaly_strikes} must be "
                ">= 1 (strikes before a non-finite ring row is evicted)")
        if sv.stop_timeout_s <= 0:
            errors.append(
                f"serve.stop_timeout_s={sv.stop_timeout_s} must be > 0 "
                "(stop()'s worker-join budget before the stall "
                "diagnosis)")
        bo = sv.brownout
        for fname in ("queue_soft", "queue_hard", "debt_soft",
                      "debt_hard", "k_cap", "max_frames_cap"):
            if getattr(bo, fname) < 0:
                errors.append(
                    f"serve.brownout.{fname}={getattr(bo, fname)} must "
                    "be >= 0 (0 disables that signal)")
        if (bo.queue_soft > 0 and bo.queue_hard > 0
                and bo.queue_hard < bo.queue_soft):
            errors.append(
                f"serve.brownout.queue_hard={bo.queue_hard} must be >= "
                f"queue_soft={bo.queue_soft} (shed only past degrade)")
        if (bo.debt_soft > 0 and bo.debt_hard > 0
                and bo.debt_hard < bo.debt_soft):
            errors.append(
                f"serve.brownout.debt_hard={bo.debt_hard} must be >= "
                f"debt_soft={bo.debt_soft} (shed only past degrade)")
        if bo.retry_after_s < 0:
            errors.append(
                f"serve.brownout.retry_after_s={bo.retry_after_s} must "
                "be >= 0")
        if bo.k_cap > 0 and sv.k_max > 0 and bo.k_cap > sv.k_max:
            errors.append(
                f"serve.brownout.k_cap={bo.k_cap} must be <= "
                f"serve.k_max={sv.k_max} (a degraded admission cannot "
                "widen the bank window)")
        slo = sv.slo
        try:
            from novel_view_synthesis_3d_tpu.obs.slo import parse_targets

            targets = parse_targets(slo.targets)
        except ValueError as e:
            targets = {}
            errors.append(str(e))
        if targets and any(v <= 0 for v in targets.values()):
            errors.append(
                f"serve.slo.targets={slo.targets!r}: latency budgets "
                "must be > 0 ms")
        if not (0.0 < slo.objective < 1.0):
            errors.append(
                f"serve.slo.objective={slo.objective} must be in (0, 1)")
        if slo.fast_window_s <= 0 or slo.slow_window_s < slo.fast_window_s:
            errors.append(
                f"serve.slo windows ({slo.fast_window_s}, "
                f"{slo.slow_window_s}) must satisfy 0 < fast <= slow")
        if slo.fast_burn <= 0 or slo.slow_burn <= 0:
            errors.append(
                f"serve.slo burn thresholds ({slo.fast_burn}, "
                f"{slo.slow_burn}) must be > 0")
        if sv.step_floor_ms < 0:
            errors.append(
                f"serve.step_floor_ms={sv.step_floor_ms} must be >= 0 "
                "(0 disables dispatch pacing)")
        rt = self.router
        for fname in ("health_poll_s", "health_ttl_s",
                      "deploy_drain_timeout_s", "deploy_probation_s",
                      "deploy_burn_max", "deploy_swap_timeout_s"):
            if getattr(rt, fname) <= 0:
                errors.append(
                    f"router.{fname}={getattr(rt, fname)} must be > 0")
        if rt.health_ttl_s < rt.health_poll_s:
            errors.append(
                f"router.health_ttl_s={rt.health_ttl_s} must be >= "
                f"router.health_poll_s={rt.health_poll_s} (a snapshot "
                "must outlive at least one poll period)")
        if rt.retry_budget < 0:
            errors.append(
                f"router.retry_budget={rt.retry_budget} must be >= 0 "
                "(0 = no failover, surface the first error)")
        if rt.saturation_sweeps < 1:
            errors.append(
                f"router.saturation_sweeps={rt.saturation_sweeps} must "
                "be >= 1 (full-fleet shed sweeps before FleetSaturated)")
        if rt.affinity_entries < 1:
            errors.append(
                f"router.affinity_entries={rt.affinity_entries} must be "
                ">= 1 (orbit sessions need at least one pin slot)")
        if self.obs.telemetry_max_mb < 0:
            errors.append(
                f"obs.telemetry_max_mb={self.obs.telemetry_max_mb} must "
                "be >= 0 (0 = unbounded)")
        sc = self.diffusion.stochastic_cond
        if sc not in (True, False):
            errors.append(
                f"diffusion.stochastic_cond={sc!r} must be True (draw a "
                "random frame-bank view per denoise step — the 3DiM "
                "protocol) or False (condition on the most recent bank "
                "frame; deterministic ablation mode)")
        fs = self.diffusion.fused_step
        if fs not in (True, False, "auto"):
            errors.append(
                f"diffusion.fused_step={fs!r} must be True, False, or "
                "'auto' (the fused Pallas denoise-step kernel, "
                "ops/fused_step.py; 'auto' = TPU backends only)")
        elif fs is True and self.diffusion.sampler == "dpm++":
            errors.append(
                "diffusion.fused_step=True requires sampler 'ddpm' or "
                "'ddim' — dpm++ 2M carries x̂₀ history across steps and "
                "cannot run as one fused step (use 'auto' to fuse where "
                "possible; the step scheduler's first-order dpm++ "
                "fallback still fuses)")
        rg = self.registry
        if rg.publish_every < 0:
            errors.append(
                f"registry.publish_every={rg.publish_every} must be >= 0 "
                "(0 = the trainer never publishes)")
        if rg.publish_every > 0 and not rg.dir:
            errors.append(
                "registry.publish_every is set but registry.dir is empty — "
                "there is nowhere to publish to")
        if not rg.channel or "/" in rg.channel or os.sep in rg.channel:
            errors.append(
                f"registry.channel={rg.channel!r} must be a non-empty name "
                "with no path separators (it becomes a pointer file under "
                "<registry.dir>/channels/)")
        if rg.poll_s <= 0:
            errors.append(
                f"registry.poll_s={rg.poll_s} must be > 0 (the serving "
                "reload watcher polls the subscribed channel)")
        if rg.gate_margin_db < 0:
            errors.append(
                f"registry.gate_margin_db={rg.gate_margin_db} must be >= 0")
        if rg.gate_sample_steps < 1:
            errors.append(
                f"registry.gate_sample_steps={rg.gate_sample_steps} must "
                "be >= 1")
        elif (rg.publish_every > 0
                and rg.gate_sample_steps > self.diffusion.timesteps):
            # Only enforced when the registry lane is armed: the default
            # gate ladder must not invalidate tiny-timesteps configs that
            # never touch the registry (sampling_schedule still errors
            # clearly if a CLI promote exceeds the ladder).
            errors.append(
                f"registry.gate_sample_steps={rg.gate_sample_steps} must "
                f"be <= diffusion.timesteps={self.diffusion.timesteps} "
                "when registry.publish_every is set")
        if rg.gate_batch < 1:
            errors.append(
                f"registry.gate_batch={rg.gate_batch} must be >= 1")
        if rg.gate_trajectory_frames < 0 or rg.gate_trajectory_frames == 1:
            errors.append(
                f"registry.gate_trajectory_frames="
                f"{rg.gate_trajectory_frames} must be 0 (single-frame "
                "gate only) or >= 2 (adjacent-frame consistency needs at "
                "least one frame pair)")
        if rg.keep < 1:
            errors.append(
                f"registry.keep={rg.keep} must be >= 1 (gc must retain at "
                "least the newest version)")
        dl = self.distill
        if dl.target_steps < 1:
            errors.append(
                f"distill.target_steps={dl.target_steps} must be >= 1")
        elif dl.start_steps < dl.target_steps:
            errors.append(
                f"distill.start_steps={dl.start_steps} must be >= "
                f"distill.target_steps={dl.target_steps}")
        else:
            ratio, rem = divmod(dl.start_steps, dl.target_steps)
            if rem or (ratio & (ratio - 1)) != 0:
                errors.append(
                    f"distill.start_steps={dl.start_steps} must be "
                    f"target_steps × a power of two (each round halves "
                    f"the step count; got target_steps={dl.target_steps})")
        # start_steps <= diffusion.timesteps is enforced at the point of
        # use (train/distill.run_distill): the default ladder must not
        # invalidate tiny-timesteps test configs that never distill.
        if dl.steps_per_round < 1:
            errors.append(
                f"distill.steps_per_round={dl.steps_per_round} must be "
                ">= 1")
        if dl.batch_size < 1:
            errors.append(f"distill.batch_size={dl.batch_size} must be >= 1")
        if dl.lr <= 0:
            errors.append(f"distill.lr={dl.lr} must be > 0")
        if dl.snr_clip < 1.0:
            errors.append(
                f"distill.snr_clip={dl.snr_clip} must be >= 1 (the "
                "truncated-SNR weight is clip(SNR, 1, snr_clip))")
        ob = self.obs
        if not 0 <= ob.metrics_port <= 65535:
            errors.append(
                f"obs.metrics_port={ob.metrics_port} must be in [0, 65535] "
                "(0 = endpoint off)")
        if ob.trace_max_events < 1:
            errors.append(
                f"obs.trace_max_events={ob.trace_max_events} must be >= 1")
        if ob.device_poll_s < 0:
            errors.append(
                f"obs.device_poll_s={ob.device_poll_s} must be >= 0 "
                "(0 disables the device-memory monitor)")
        xp = tuple(ob.xprof_steps)
        if len(xp) != 2 or any(int(v) < 0 for v in xp) or (
                xp != (0, 0) and xp[1] <= xp[0]):
            errors.append(
                f"obs.xprof_steps={ob.xprof_steps} must be (start, end) "
                "with 0 <= start < end, or (0, 0) for off")
        pf = ob.profile
        for fname in ("every_steps", "window_steps",
                      "serve_every_dispatches", "serve_window_dispatches"):
            if getattr(pf, fname) < 0:
                errors.append(
                    f"obs.profile.{fname}={getattr(pf, fname)} must be "
                    ">= 0 (0 disables)")
        if (pf.every_steps > 0 and pf.window_steps > 0
                and pf.window_steps >= pf.every_steps):
            errors.append(
                f"obs.profile.window_steps={pf.window_steps} must be < "
                f"every_steps={pf.every_steps} (a window must close "
                "before the next cadence)")
        if (pf.serve_every_dispatches > 0
                and pf.serve_window_dispatches > 0
                and pf.serve_window_dispatches >= pf.serve_every_dispatches):
            errors.append(
                f"obs.profile.serve_window_dispatches="
                f"{pf.serve_window_dispatches} must be < "
                f"serve_every_dispatches={pf.serve_every_dispatches}")
        for axis in ("model", "seq", "stages"):
            if getattr(self.mesh, axis) < 1:
                errors.append(f"mesh.{axis} must be >= 1")
        if self.mesh.data == 0 or self.mesh.data < -1:
            errors.append("mesh.data must be -1 (all remaining) or >= 1")
        if self.mesh.stages > 1:
            # Pipeline stages ride the 'model' axis (parallel/pipeline.py):
            # one stage per model-shard, so the axis size must match, and
            # the other uses of that axis (TP) — or of shard_map-managed
            # model partitioning (sequence parallel, fsdp) — can't coexist
            # with the stage placement.
            if self.mesh.model != self.mesh.stages:
                errors.append(
                    f"mesh.stages={self.mesh.stages} requires mesh.model="
                    f"{self.mesh.stages} (stages are placed one per "
                    f"'model' shard; got mesh.model={self.mesh.model})")
            if t.tp:
                errors.append(
                    "mesh.stages > 1 conflicts with train.tp=True — both "
                    "claim the 'model' axis")
            if t.fsdp:
                errors.append(
                    "mesh.stages > 1 conflicts with train.fsdp=True — the "
                    "pipelined step passes stage-sliced params through "
                    "shard_map and cannot compose with data-axis param "
                    "sharding (use train.update_sharding='zero' for the "
                    "optimizer-state memory win instead)")
            if m.sequence_parallel:
                errors.append(
                    "mesh.stages > 1 conflicts with "
                    "model.sequence_parallel=True — ring attention's "
                    "shard_map cannot nest inside the pipeline stage "
                    "shard_map")
            if self.mesh.seq != 1:
                errors.append(
                    f"mesh.stages={self.mesh.stages} requires mesh.seq=1 "
                    f"(got {self.mesh.seq})")
        if errors:
            raise ValueError("invalid config:\n  - " + "\n  - ".join(errors))
        return self

    # ------------------------------------------------------------------
    # Serialization + overrides
    # ------------------------------------------------------------------
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), indent=2, **kw)

    @classmethod
    def from_dict(cls, d: dict) -> "Config":
        def build(tp, sub):
            fields = {f.name: f for f in dataclasses.fields(tp)}
            kwargs = {}
            for k, v in sub.items():
                if k not in fields:
                    raise KeyError(f"unknown config field {tp.__name__}.{k}")
                ftype = fields[k].type
                if isinstance(ftype, str):  # from __future__ annotations
                    ftype = globals().get(ftype, ftype)
                if tp is ModelConfig and k == "tokens" and isinstance(
                        v, dict):
                    ftype = _trunk_of_keys(v)
                if dataclasses.is_dataclass(ftype) and isinstance(v, dict):
                    # Nested sub-config (e.g. TrainConfig.watchdog): rebuild
                    # the dataclass so dotted overrides round-trip through
                    # to_dict() without degrading the field to a plain dict.
                    v = build(ftype, v)
                elif isinstance(v, list):
                    v = tuple(v)
                kwargs[k] = v
            return tp(**kwargs)

        return cls(
            model=build(ModelConfig, d.get("model", {})),
            diffusion=build(DiffusionConfig, d.get("diffusion", {})),
            data=build(DataConfig, d.get("data", {})),
            train=build(TrainConfig, d.get("train", {})),
            mesh=build(MeshConfig, d.get("mesh", {})),
            serve=build(ServeConfig, d.get("serve", {})),
            obs=build(ObsConfig, d.get("obs", {})),
            registry=build(RegistryConfig, d.get("registry", {})),
            distill=build(DistillConfig, d.get("distill", {})),
        )

    @classmethod
    def from_json(cls, s: str) -> "Config":
        return cls.from_dict(json.loads(s))

    def override(self, **dotted: Any) -> "Config":
        """Override with dotted keys: cfg.override(**{'model.ch': 64})."""
        d = self.to_dict()
        for key, val in dotted.items():
            parts = key.split(".")
            node = d
            for p in parts[:-1]:
                node = node[p]
            if parts[-1] not in node:
                raise KeyError(f"unknown config field {key}")
            node[parts[-1]] = val
        return Config.from_dict(d)

    def apply_cli(self, argv: Sequence[str]) -> "Config":
        """Apply 'model.ch=64'-style CLI overrides.

        Values parse as JSON, plus the Python spellings True/False/None —
        otherwise `model.use_flash_attention=False` would silently arrive
        as the string 'False' (truthy!) and either crash later or flip the
        wrong way.
        """
        py_literals = {"True": True, "False": False, "None": None}
        overrides = {}
        for arg in argv:
            if "=" not in arg:
                raise ValueError(f"override must look like key=value: {arg!r}")
            k, v = arg.split("=", 1)
            if v in py_literals:
                overrides[k] = py_literals[v]
            else:
                try:
                    overrides[k] = json.loads(v)
                except json.JSONDecodeError:
                    overrides[k] = v  # bare string
        return self.override(**overrides)


def _xunet_family_errors(m: ModelConfig, d: DataConfig) -> list:
    """The X-UNet's shape checks (GroupNorm groups, heads, attention
    levels, downsampling)."""
    errors = []
    if m.ch <= 0 or not m.ch_mult:
        errors.append("model.ch must be positive and model.ch_mult "
                      "non-empty")
    for level, mult in enumerate(m.ch_mult):
        c = m.ch * mult
        if c % 32 != 0:
            errors.append(
                f"model.ch×mult = {c} is not divisible by 32 "
                "(GroupNorm runs with 32 groups at every level)")
        # Heads only matter at levels where attention actually runs.
        if (d.img_sidelength // (2 ** level) in m.attn_resolutions
                and c % m.attn_heads != 0):
            errors.append(
                f"model.ch×mult = {c} (level {level}, attention "
                f"resolution {d.img_sidelength // (2 ** level)}) is "
                f"not divisible by attn_heads={m.attn_heads}")
    # Cross-frame attention is the ONLY path from the conditioning
    # image to the target frame (convs are per-frame). A non-empty
    # attn_resolutions that matches NO UNet level silently trains an
    # unconditional pose-memorizer: seen-pose metrics look great,
    # held-out eval sits at the mean-image floor (r2/r3 quality-run
    # postmortem — the r2 tool used size//4 on a 2-level UNet).
    level_res = {d.img_sidelength // (2 ** lv)
                 for lv in range(len(m.ch_mult))}
    stray = set(m.attn_resolutions) - level_res
    if m.attn_resolutions and stray == set(m.attn_resolutions):
        errors.append(
            f"model.attn_resolutions={tuple(m.attn_resolutions)} "
            f"matches NO UNet level (levels run at "
            f"{tuple(sorted(level_res, reverse=True))} for "
            f"data.img_sidelength={d.img_sidelength}, "
            f"{len(m.ch_mult)} levels): cross-frame attention would "
            "never fire and the conditioning image could not influence "
            "the generated view. Pick resolutions from the level set, "
            "or set attn_resolutions=() explicitly for an attention-free "
            "model")
    elif stray:
        # Partial match: attention fires somewhere, but stray entries
        # are silently inert (advisor r3 — a sub-lethal recurrence of
        # the r2/r3 postmortem class). Entries related to the
        # sidelength by a power of two are a deliberate DDPM-style
        # superset list (the presets keep one attn list across depths
        # and image sizes; e.g. 8 on a 3-level 64px UNet) — allowed.
        # Anything else (e.g. 5 at sidelength 16) can never name a
        # UNet level at any depth or power-of-two rescale of this
        # config: error.
        def _pow2_related(e: int) -> bool:
            if e <= 0:
                return False
            a, b = max(e, d.img_sidelength), min(e, d.img_sidelength)
            q, r = divmod(a, b)
            return r == 0 and (q & (q - 1)) == 0
        bogus = {e for e in stray if not _pow2_related(e)}
        if bogus:
            errors.append(
                f"model.attn_resolutions entries "
                f"{tuple(sorted(bogus))} match no UNet level and never "
                f"could (level resolutions are "
                f"data.img_sidelength={d.img_sidelength} divided by "
                "powers of 2): each would be silently inert. Remove "
                "them or pick resolutions from the level set")
    down = 2 ** (len(m.ch_mult) - 1)
    if d.img_sidelength % down != 0:
        errors.append(
            f"data.img_sidelength={d.img_sidelength} is not divisible "
            f"by 2^{len(m.ch_mult) - 1} (the UNet downsamples "
            f"{len(m.ch_mult) - 1} times)")
    return errors


def _phi4flash_errors(k: Phi4FlashTrunkConfig) -> list:
    """What `layer_kind`'s rule and the paired heads need."""
    errors = []
    N, mb = k.num_hidden_layers, k.mb_per_layer
    if N < 4 or N % 2 or mb < 2 or (N // 2) % mb:
        errors.append(
            f"model.tokens.num_hidden_layers={N}, mb_per_layer={mb}: layer "
            "N/2 must be a Mamba layer (the one whose scan output the "
            "gated memory units read) and layer N/2 + 1 attention (the one "
            "whose keys and values are kept)")
    if k.num_attention_heads % 2 or k.num_key_value_heads % 2 \
            or (k.num_attention_heads // 2) % (k.num_key_value_heads // 2):
        errors.append(
            "model.tokens: differential attention pairs adjacent heads: "
            "num_attention_heads and num_key_value_heads must be even and "
            "the query pairs a multiple of the key pairs")
    if k.hidden_size % k.num_attention_heads:
        errors.append("model.tokens.hidden_size is not a multiple of "
                      "num_attention_heads")
    if k.hidden_act != "silu" or k.mlp_bias:
        errors.append("model.tokens.hidden_act other than 'silu' and "
                      "mlp_bias=True are not carried")
    return errors


def _olmo_hybrid_errors(k: OlmoHybridTrunkConfig) -> list:
    """What the two kinds of layer need of the sizes."""
    errors = []
    kinds = set(k.layer_types[:k.num_hidden_layers])
    if len(k.layer_types) < k.num_hidden_layers \
            or not kinds <= {"linear_attention", "full_attention"}:
        errors.append(
            f"model.tokens.layer_types: {k.num_hidden_layers} layers need "
            "an entry each, 'linear_attention' or 'full_attention'")
    if k.hidden_size % k.num_attention_heads:
        errors.append("model.tokens.hidden_size is not a multiple of "
                      "num_attention_heads")
    if k.num_attention_heads % k.num_key_value_heads:
        errors.append(
            f"model.tokens.num_attention_heads={k.num_attention_heads} is "
            f"not a multiple of num_key_value_heads={k.num_key_value_heads}")
    if k.linear_num_key_heads != k.linear_num_value_heads:
        errors.append("model.tokens.linear_num_key_heads other than "
                      "linear_num_value_heads (grouped delta-rule heads) is "
                      "not carried")
    if k.hidden_act != "silu" or k.attention_bias:
        errors.append("model.tokens.hidden_act other than 'silu' and "
                      "attention_bias=True are not carried")
    return errors


def _longcat_flash_errors(k: LongcatFlashTrunkConfig) -> list:
    """What the double layer and its wider router need of the settings."""
    errors = []
    if k.qk_rope_head_dim % 2:
        errors.append("model.tokens.qk_rope_head_dim must be even (rotary "
                      "pairs)")
    if k.attention_method != "MLA" or k.attention_bias:
        errors.append("model.tokens.attention_method other than 'MLA' and "
                      "attention_bias=True are not carried")
    if k.zero_expert_num < 0 or k.zero_expert_type != "identity":
        errors.append("model.tokens.zero_expert_type other than 'identity' "
                      "(and a negative zero_expert_num) is not carried")
    if not 1 <= k.moe_topk <= k.router_width:
        errors.append("model.tokens.moe_topk must be in [1, "
                      "n_routed_experts + zero_expert_num]")
    return errors


def _laguna_errors(k: LagunaTrunkConfig) -> list:
    """What layers that differ by index in THREE tuples, and two rotary
    laws, need of the settings."""
    errors = []
    N = k.num_hidden_layers
    tuples = {n: getattr(k, n) for n in (
        "layer_types", "num_attention_heads_per_layer", "mlp_layer_types")}
    if len({len(t) for t in tuples.values()}) != 1 \
            or len(k.layer_types) < N:
        return [f"model.tokens: {', '.join(tuples)} must have one length, "
                f"an entry a layer for {N} layers (got "
                f"{[len(t) for t in tuples.values()]})"]
    if not set(k.layer_types[:N]) <= {"full_attention", "sliding_attention"}:
        errors.append("model.tokens.layer_types: 'full_attention' or "
                      "'sliding_attention'")
    elif k.sliding_window < 1 and "sliding_attention" in k.layer_types[:N]:
        errors.append("model.tokens.layer_types names sliding_attention "
                      f"layers under sliding_window={k.sliding_window}")
    dense = tuple(i for i in range(N) if k.mlp_layer_types[i] == "dense")
    if not set(k.mlp_layer_types[:N]) <= {"dense", "sparse"} \
            or dense != tuple(i for i in k.mlp_only_layers if i < N) \
            or k.decoder_sparse_step != 1:
        errors.append(
            "model.tokens.mlp_layer_types ('dense' or 'sparse') must name "
            "as dense exactly mlp_only_layers, at decoder_sparse_step=1")
    bad = [n for n in k.num_attention_heads_per_layer[:N]
           if n < 1 or n % k.num_key_value_heads]
    if bad:
        errors.append(
            f"model.tokens.num_attention_heads_per_layer: {sorted(set(bad))}"
            f" is not a multiple of num_key_value_heads="
            f"{k.num_key_value_heads}")
    for kind in ("full_attention", "sliding_attention"):
        rope = getattr(k.rope_parameters, kind)
        dim = k.head_dim * rope.partial_rotary_factor
        if dim != int(dim) or int(dim) % 2 or not 0 < dim <= k.head_dim:
            errors.append(
                f"model.tokens.rope_parameters.{kind}: head_dim × "
                f"partial_rotary_factor = {dim:g} must be an even number "
                "of lanes of a head (rotary pairs)")
        if rope.rope_type not in ("default", "yarn"):
            errors.append(f"model.tokens.rope_parameters.{kind}.rope_type="
                          f"{rope.rope_type!r}: 'default' or 'yarn'")
    if k.attention_bias or k.gating != "per-head" \
            or k.moe_apply_router_weight_on_input \
            or k.moe_router_logit_softcapping:
        errors.append(
            "model.tokens: attention_bias=True, gating other than "
            "'per-head', moe_apply_router_weight_on_input=True and a "
            "router logit soft cap are not carried")
    return errors


def _token_family_errors(m: ModelConfig, d: DataConfig) -> list:
    """What a `family: tokens` model needs of its settings."""
    k = m.tokens
    if k is None:
        return ["model.family='tokens' needs model.tokens (the trunk)"]
    errors = []
    if isinstance(k, Phi4FlashTrunkConfig):     # the trunks without experts
        errors += _phi4flash_errors(k)
    elif isinstance(k, OlmoHybridTrunkConfig):
        errors += _olmo_hybrid_errors(k)
    else:
        first, count = k.held_experts
        if not (0 <= first and count >= 1
                and first + count <= k.n_routed_experts):
            errors.append(
                f"model.tokens.held_experts={tuple(k.held_experts)} is not "
                f"a (first, count) range inside the router's "
                f"{k.n_routed_experts} experts")
        if isinstance(k, LongcatFlashTrunkConfig):
            errors += _longcat_flash_errors(k)
        elif not 1 <= k.num_experts_per_tok <= k.n_routed_experts:
            errors.append("model.tokens.num_experts_per_tok must be in "
                          "[1, n_routed_experts]")
        if isinstance(k, LagunaTrunkConfig):
            errors += _laguna_errors(k)
    if d.img_sidelength % k.patch_size:
        errors.append(
            f"data.img_sidelength={d.img_sidelength} is not a multiple of "
            f"model.tokens.patch_size={k.patch_size}")
    if isinstance(k, SmallThinkerTrunkConfig):
        if k.head_dim % 2:
            errors.append("model.tokens.head_dim must be even (rotary "
                          "pairs)")
        if k.num_attention_heads % k.num_key_value_heads:
            errors.append(
                f"model.tokens.num_attention_heads={k.num_attention_heads} "
                f"is not a multiple of num_key_value_heads="
                f"{k.num_key_value_heads}")
        for name in ("rope_layout", "sliding_window_layout"):
            if len(getattr(k, name)) < k.num_hidden_layers:
                errors.append(
                    f"model.tokens.{name} has {len(getattr(k, name))} "
                    f"entries for {k.num_hidden_layers} layers")
    elif isinstance(k, KimiLinearTrunkConfig):
        lin = k.linear_attn_config
        layers = set(range(1, k.num_hidden_layers + 1))
        kda, full = set(lin.kda_layers) & layers, \
            set(lin.full_attn_layers) & layers
        if kda & full or kda | full != layers:
            errors.append(
                "model.tokens.linear_attn_config: kda_layers and "
                f"full_attn_layers do not divide layers 1-"
                f"{k.num_hidden_layers} between them")
        if (k.num_expert_group, k.topk_group) != (1, 1):
            errors.append("model.tokens.num_expert_group and topk_group "
                          "other than 1 (a grouped top-k) are not carried")
        if k.moe_router_activation_func not in ("sigmoid", "softmax"):
            errors.append("model.tokens.moe_router_activation_func must be "
                          "'sigmoid' or 'softmax'")
        if not k.mla_use_nope:
            errors.append("model.tokens.mla_use_nope=False (rotary in this "
                          "trunk's latent attention) is not carried")
    elif isinstance(k, TokenTrunkConfig) and k.qk_rope_head_dim % 2:
        errors.append("model.tokens.qk_rope_head_dim must be even (rotary "
                      "pairs)")
    if m.num_cond_frames != 1:
        errors.append("model.family='tokens' carries one conditioning "
                      "frame (model.num_cond_frames=1)")
    return errors


# ----------------------------------------------------------------------
# Config ladder presets (BASELINE.json "configs")
# ----------------------------------------------------------------------
PRESET_NAMES = ("reference", "tiny64", "base128", "paper256", "pod64",
                "ms4_denoiser128", "st21_denoiser256", "kl48_denoiser256",
                "p4f_denoiser256", "oh7_denoiser256", "lcf_denoiser256",
                "lgs_denoiser256")


def get_preset(name: str) -> Config:
    """Presets for the BASELINE.json config ladder.

    - 'reference': exact reference defaults incl. its behavior quirks
      (shared-frame GroupNorm stats, Frobenius loss) for parity checks.
    - 'tiny64':   XUnet-tiny 64px (single-host smoke; ref defaults, sane flags)
    - 'base128':  XUnet-base 128px, ch=128, ch_mult=(1,2,2,4)
    - 'paper256': 3DiM paper config 256px, ch=256, ch_mult=(1,2,2,4,4)
    """
    if name == "reference":
        return Config(
            # Pin the XLA attention path too: this preset exists for parity
            # checks against the reference, and the fused kernel matches it
            # only approximately on TPU.
            model=ModelConfig(groupnorm_per_frame=False,
                              use_flash_attention=False),
            train=TrainConfig(loss="frobenius"),
        )
    if name == "tiny64":
        return Config()
    if name == "base128":
        return Config(
            model=ModelConfig(ch=128, ch_mult=(1, 2, 2, 4), emb_ch=512,
                              dtype="bfloat16"),
            data=DataConfig(img_sidelength=128),
            train=TrainConfig(batch_size=8, ema_decay=0.9999),
            diffusion=DiffusionConfig(sample_timesteps=256),
        )
    if name == "paper256":
        return Config(
            model=ModelConfig(ch=256, ch_mult=(1, 2, 2, 4, 4), emb_ch=1024,
                              num_res_blocks=3, dtype="bfloat16", remat=True),
            data=DataConfig(img_sidelength=256),
            # Measured on v5e (record deleted in PR 21): the
            # 708M-param state is params f32 2.64G + Adam nu f32 2.64G +
            # mu bf16 1.32G, and a DEVICE EMA copy (f32 2.64G) pushed total
            # usage to 17.94G of 15.75G — OOM. ema_host moves that copy to
            # host RAM (bf16 EMA would be wrong: decay 0.9999 updates round
            # to nothing in 8 mantissa bits). grad_accum: the batch-8 256px
            # step wants ~32G of activations; micro-batches of 1 with
            # remat fit. On an N-chip mesh the effective accumulation
            # shrinks automatically (per-chip memory scales as 1/N).
            train=TrainConfig(batch_size=8, ema_decay=0.9999,
                              ema_host=True,
                              grad_accum_steps=8,
                              # 0.5x param bytes of HBM back on the 16G
                              # chip; see TrainConfig.adam_mu_dtype.
                              adam_mu_dtype="bfloat16",
                              # In-loop probes pin the EMA copy on-chip;
                              # f32 would be 2.6G the margin doesn't have
                              # (see TrainConfig.probe_dtype).
                              probe_dtype="bfloat16"),
            diffusion=DiffusionConfig(sample_timesteps=256),
        )
    if name == "pod64":
        # BASELINE ladder step 5: v5e-64 pod-scale DP pretrain of the
        # paper256 model (derived from that preset so the model can't
        # drift). 'data=-1' absorbs all chips of the slice; each of the
        # pod's hosts feeds its local shard (Grain/native loader per
        # process); FSDP shards params+Adam state so the 256-ch UNet leaves
        # HBM room for batch; run with NVS3D_MULTIHOST=1 (parallel/dist.py).
        return get_preset("paper256").override(**{
            "data.num_workers": 16,
            "data.prefetch": 8,
            "train.batch_size": 256,
            "train.fsdp": True,
            # Per-chip batch is already small on 64 chips (256/64 = 4) and
            # FSDP frees the param/optimizer HBM — no micro-batching needed.
            "train.grad_accum_steps": 1,
            # FSDP shards the EMA copy too (2.64G/64 per chip) — keep it
            # on device; the host-EMA path would all-gather params on every
            # update across the pod instead.
            "train.ema_host": False,
        })
    if name == "ms4_denoiser128":
        # A token denoiser whose trunk is Mistral-Small-4-119B-2603's
        # decoder layer at its published widths (TokenTrunkConfig's
        # defaults), cut to chip 0's share of a deployment in which four
        # chips divide each layer by expert parallelism: 6 of 36 layers,
        # experts 0-31 of 128 held (the router keeps 128 and top-4).
        # bfloat16 parameters: 1.72 GB a layer, 10.3 GB in all.
        return Config(
            model=ModelConfig(
                family="tokens", dtype="bfloat16", param_dtype="bfloat16",
                dropout=0.0,
                tokens=TokenTrunkConfig(num_hidden_layers=6,
                                        held_experts=(0, 32))),
            data=DataConfig(img_sidelength=128),
            diffusion=DiffusionConfig(sample_timesteps=256),
        )
    if name == "st21_denoiser256":
        # A token denoiser whose trunk is SmallThinker-21BA3B-Instruct's
        # decoder layer at its published widths (SmallThinkerTrunkConfig's
        # defaults), cut to one pipeline stage that holds every expert of
        # its layers: layers 0-11 of 52, three periods of [full attention
        # without a positional term, window 4096 with rotary x 3]. 256 px,
        # 4096 tokens a frame: the size at which the published window
        # binds. bfloat16 parameters: 0.80 GB a layer, 9.6 GB in all.
        return Config(
            model=ModelConfig(
                family="tokens", dtype="bfloat16", param_dtype="bfloat16",
                dropout=0.0,
                tokens=SmallThinkerTrunkConfig(num_hidden_layers=12)),
            data=DataConfig(img_sidelength=256),
            diffusion=DiffusionConfig(sample_timesteps=256),
        )
    if name == "kl48_denoiser256":
        # A token denoiser whose trunk is Kimi-Linear-48B-A3B-Instruct's
        # decoder stack at its published widths (KimiLinearTrunkConfig's
        # defaults), cut to chip 0's share of a deployment in which two
        # chips divide each layer by expert parallelism: layers 1-5 of 27
        # (the leading dense layer once, then a whole period of the four
        # expert layers that follow: KDA, KDA, latent attention, KDA),
        # experts 0-127 of 256 held (the router keeps 256 and top-8).
        # bfloat16 parameters: 3.92 B = 7.84 GB. 256 px, 4096 tokens a
        # frame: 64 chunks of 64 a head in every KDA layer's scan.
        return Config(
            model=ModelConfig(
                family="tokens", dtype="bfloat16", param_dtype="bfloat16",
                dropout=0.0,
                tokens=KimiLinearTrunkConfig(num_hidden_layers=5,
                                             held_experts=(0, 128))),
            data=DataConfig(img_sidelength=256),
            diffusion=DiffusionConfig(sample_timesteps=256),
        )
    if name == "p4f_denoiser256":
        # A token denoiser whose trunk is Phi-4-mini-flash-reasoning's
        # WHOLE decoder stack at its published widths and depth
        # (Phi4FlashTrunkConfig's defaults): 9 Mamba layers, 8 of
        # differential attention under the 512 window, 1 over everything,
        # then 7 gated memory units and 7 cross layers that read layer
        # 16's scan output and layer 17's keys and values; a dense MLP in
        # each. bfloat16 parameters: 3.34 B = 6.68 GB, one chip holds all
        # 32 layers. 256 px, 4096 tokens a frame: the window is an eighth
        # of a frame.
        return Config(
            model=ModelConfig(
                family="tokens", dtype="bfloat16", param_dtype="bfloat16",
                dropout=0.0, tokens=Phi4FlashTrunkConfig()),
            data=DataConfig(img_sidelength=256),
            diffusion=DiffusionConfig(sample_timesteps=256),
        )
    if name == "oh7_denoiser256":
        # A token denoiser whose trunk is Olmo-Hybrid-7B's decoder stack at
        # its published widths (OlmoHybridTrunkConfig's defaults), cut to
        # stage 0 of a two-stage pipeline, one chip a stage and no layer
        # shared between chips: layers 0-15 of 32, four whole periods of
        # [Gated DeltaNet x 3, full attention], a dense MLP in each.
        # bfloat16 parameters: 3.33 B = 6.66 GB. 256 px, 4096 tokens a
        # frame: 64 chunks of 64 a head in every delta-rule layer's scan.
        return Config(
            model=ModelConfig(
                family="tokens", dtype="bfloat16", param_dtype="bfloat16",
                dropout=0.0,
                tokens=OlmoHybridTrunkConfig(num_hidden_layers=16)),
            data=DataConfig(img_sidelength=256),
            diffusion=DiffusionConfig(sample_timesteps=256),
        )
    if name == "lcf_denoiser256":
        # A token denoiser whose trunk is LongCat-Flash-Omni's decoder
        # stack at its published widths (LongcatFlashTrunkConfig's
        # defaults), cut to chip 0 of stage 0 of a deployment in which 32
        # chips share each layer by expert parallelism (16 of 512 real
        # experts a chip; attention and the dense MLPs replicated) and
        # seven pipeline stages hold 4 double layers each: layers 0-3 of
        # 28, experts 0-15 held (the router keeps its 768 outputs, of
        # which 256 are identities, and top-12). bfloat16 parameters:
        # 1.243 B a layer, 4.97 B = 9.94 GB. 256 px, 4096 tokens a frame:
        # 8192 keys a target query in each of a layer's two attentions.
        return Config(
            model=ModelConfig(
                family="tokens", dtype="bfloat16", param_dtype="bfloat16",
                dropout=0.0,
                tokens=LongcatFlashTrunkConfig(num_layers=4,
                                               held_experts=(0, 16))),
            data=DataConfig(img_sidelength=256),
            diffusion=DiffusionConfig(sample_timesteps=256),
        )
    if name == "lgs_denoiser256":
        # A token denoiser whose trunk is Laguna-S-2.1's decoder stack at
        # its published widths (LagunaTrunkConfig's defaults), cut to chip
        # 0 of stage 0 of a deployment in which 2 chips share each layer
        # by expert parallelism (128 of 256 routed experts a chip;
        # attention, the dense layer and the shared expert replicated):
        # layers 0-4 of 48 — the leading dense layer under 48-head full
        # attention, then one whole period of [72-head window 512 x 3,
        # 48-head full] with experts 0-127 held (the router keeps its 256
        # outputs and top-10). bfloat16 parameters: 5.29 B = 10.58 GB.
        # 256 px, 4096 tokens a frame: the window is an eighth of a frame.
        return Config(
            model=ModelConfig(
                family="tokens", dtype="bfloat16", param_dtype="bfloat16",
                dropout=0.0,
                tokens=LagunaTrunkConfig(num_hidden_layers=5,
                                         held_experts=(0, 128))),
            data=DataConfig(img_sidelength=256),
            diffusion=DiffusionConfig(sample_timesteps=256),
        )
    raise KeyError(f"unknown preset {name!r}")

"""The scope vocabulary of tracing and staging, for every denoiser family.

Two levels of stamp inside the `og.<label>` around each op group of a
model's op loop. The program stamps `jax.named_scope("lk.<kind>")` where
the work happens; `layer_of` reads a scope path back into (block, kind).
Inside a kind it stamps `pt.<part>`, one of four (LAYER_PARTS): `kernel`
around a `pl.pallas_call` and nothing else; `layout` around what a
kernel's wrapper does to feed it and to hand its result back (transposes,
reshapes, pads, slices); `gather` around a row gather between token order
and expert order; `matmul` around a dense product. `layer_part_of` reads
a path into (block, kind or kind.part). What a kind holds outside every
part is its remainder (norms, rotary, activations, sorts, casts): kind −
Σ parts, no fifth name. A new kernel's wrapper stamps `pt.kernel` and
`pt.layout` itself, in `ops/`, not at its callers, so every caller's
capture splits alike. ONE kernel is not `kernel`: `moe_combine`
(ops/expert_combine.py) IS the row gather from expert order back to token
order, so its wrapper stamps `pt.gather` around its `pl.pallas_call` —
`moe_experts.gather` goes on reading the combine and `moe_experts.kernel`
the three grouped products, whatever implements either.

One vocabulary serves both families, so one reader a level
(benchmarks/layer_metrics/layer_ms_per_call.py, part_ms_per_call.py)
serves every cell; `models/xunet.py` re-exports these names.
"""

from __future__ import annotations

import re

# The kinds each family stamps. `emb`, `pose` and `update` are shared: the
# logsnr MLP, the rays and their encoding, the sampler's update.
XUNET_LAYER_KINDS = ("conv", "gn", "attn", "emb", "pose", "update")
TOKEN_LAYER_KINDS = ("mla_proj", "mla_core", "moe_route", "moe_experts",
                     "moe_shared", "patch", "emb", "pose", "update")
# The token family's second trunk (grouped-query attention, a window on
# some layers, no shared expert): its attention is stamped by what the
# layer's mask is, so a windowed layer's time is told from a full one's.
GQA_TOKEN_LAYER_KINDS = ("gqa_proj", "attn_window", "attn_full",
                         "moe_route", "moe_experts", "patch", "emb", "pose",
                         "update")
# The token family's third trunk (Kimi-Linear's stack): KDA layers stamp
# their projections (with the gates, `o` and, in front of it, the scan's
# output under its head-wise norm and gate: the kernel `head_norm_fwd`),
# the short convolution (with its SiLU and the L2 norms: the kernel
# `short_conv_fwd`, a call a projection) and the chunked scan apart;
# its latent-attention and expert layers stamp as the first trunk's, its
# leading dense layer's MLP as `dense_mlp`.
KDA_TOKEN_LAYER_KINDS = ("kda_proj", "kda_conv", "kda_core", "mla_proj",
                         "mla_core", "moe_route", "moe_experts",
                         "moe_shared", "dense_mlp", "patch", "emb", "pose",
                         "update")
# The token family's fourth trunk (Phi-4-mini-flash's stack, SambaY): a
# Mamba layer stamps its products (in, x, dt, out, with the gate) as
# `ssm_proj`, the short convolution with its SiLU as `ssm_conv` and the
# selective scan as `ssm_core`; differential attention stamps its
# projections, λ and the pair-wise norm as `gqa_proj` and its two maps by
# what the layer reads — `attn_window`, `attn_full`, or `attn_cross` where
# the keys and values are another layer's —; a gated memory unit is `gmu`;
# every layer's MLP `dense_mlp`. No expert kind.
SSM_TOKEN_LAYER_KINDS = ("ssm_proj", "ssm_conv", "ssm_core", "gqa_proj",
                         "attn_window", "attn_full", "attn_cross", "gmu",
                         "dense_mlp", "patch", "emb", "pose", "update")
# The token family's fifth trunk (Olmo-Hybrid's stack): a Gated DeltaNet
# layer stamps its projections (with the decay, β, the gate, the scan's
# output under its head-wise norm and gate — the kernel `head_norm_fwd` —,
# `o` and the norm of the sublayer's output) as `gdn_proj`, the short
# convolution (the kernel `short_conv_fwd`, a call a projection) as
# `gdn_conv` and the chunked scalar-decay scan as `gdn_core`; a full layer
# its projections, the QK norm and its output's norm as `gqa_proj` and its
# attention as `attn_full`; every layer's MLP, with its output's norm, as
# `dense_mlp`. No expert kind.
GDN_TOKEN_LAYER_KINDS = ("gdn_proj", "gdn_conv", "gdn_core", "gqa_proj",
                         "attn_full", "dense_mlp", "patch", "emb", "pose",
                         "update")
# The token family's sixth trunk (LongCat-Flash's shortcut-connected double
# layer): each of a layer's two latent attentions stamps as the first
# trunk's (`mla_proj`, `mla_core`), each of its two dense MLPs (the second
# with the add that joins the expert branch) as `dense_mlp`, the router and
# the sort as `moe_route`, the held experts' products and the combine as
# `moe_experts`, and what the chosen IDENTITY experts give — the router's
# input times the sum of their gates, token-local, with its sum onto the
# held experts' part — as `moe_zero`. No shared expert.
SCMOE_TOKEN_LAYER_KINDS = ("mla_proj", "mla_core", "dense_mlp", "moe_route",
                           "moe_experts", "moe_zero", "patch", "emb", "pose",
                           "update")
# The token family's seventh trunk (Laguna's stack): grouped-query attention
# stamps as the second trunk's — the norm, the q, k, v, gate and o products
# and both rotary laws as `gqa_proj`, the kernel by what the layer's mask
# is, `attn_window` or `attn_full`, whatever its head count — and the
# sigmoid gate a head on the attention's output, with its product and the
# cast, as `attn_gate`: the one elementwise pass between the kernel and
# W_o, so a trace says what it costs. The leading layer's MLP is
# `dense_mlp`, the expert layers stamp as the first trunk's.
HEADMIX_TOKEN_LAYER_KINDS = ("gqa_proj", "attn_window", "attn_full",
                             "attn_gate", "dense_mlp", "moe_route",
                             "moe_experts", "moe_shared", "patch", "emb",
                             "pose", "update")
# Every kind a `jax.named_scope("lk.<kind>")` may stamp. The stamps sit
# where the work happens (models/layers.py, models/xunet.py,
# models/token_denoiser.py, sample/ddpm.py); these tuples and layer_of are
# the only other place a kind is spelled.
LAYER_KINDS = tuple(dict.fromkeys(
    XUNET_LAYER_KINDS + TOKEN_LAYER_KINDS + GQA_TOKEN_LAYER_KINDS
    + KDA_TOKEN_LAYER_KINDS + SSM_TOKEN_LAYER_KINDS
    + GDN_TOKEN_LAYER_KINDS + SCMOE_TOKEN_LAYER_KINDS
    + HEADMIX_TOKEN_LAYER_KINDS))
# Every part a `jax.named_scope("pt.<part>")` may stamp inside a kind
# (ops/flash_attention.py, ops/grouped_matmul.py, ops/kda.py, ops/gdn.py,
# ops/ssm.py, ops/short_conv.py, ops/head_norm.py, ops/expert_combine.py,
# models/token_denoiser.py); this
# tuple and layer_part_of are the only other place a part is spelled.
LAYER_PARTS = ("kernel", "layout", "gather", "matmul")


def layer_of(path: str):
    """(block, kind) of a scope path — an HLO `op_name`, which a profiler
    capture carries as the `tf_op` of a device event's metadata.

    `block` is the `og.<label>` of op_groups ('' outside the model's op
    loop). `kind` is the innermost `lk.<kind>` stamp (LAYER_KINDS), with
    two exceptions: a stamp from outside a block does not reach into it
    (the sampler's `update` encloses the model call, whose unstamped
    instructions are the model's `other`, not the sampler's), and `pose`
    anywhere on the path wins, because the pose path is one thing to a
    reader whichever convolutions and norms it is made of. A path inside
    a program scope with no kind is `other`; a path with no program
    scope at all (the compiler's own instructions, an RNG helper called
    outside every stamp) is `unattributed`. Transform wrappers
    (`transpose(jvp(XUNet))/og.final/...`) are split like slashes; of the
    `;`-joined paths of instructions XLA merged, the first holds.
    """
    segs = [s for s in re.split(r"[/()]", path.split(";", 1)[0]) if s]
    blocks = [i for i, s in enumerate(segs) if s.startswith("og.")]
    block = segs[blocks[-1]][3:] if blocks else ""
    kinds = [(i, s[3:]) for i, s in enumerate(segs)
             if s.startswith("lk.") and s[3:] in LAYER_KINDS]
    if any(k == "pose" for _, k in kinds):
        return block, "pose"
    if blocks:
        kinds = [(i, k) for i, k in kinds if i > blocks[-1]]
    if kinds:
        return block, kinds[-1][1]
    return block, "other" if blocks else "unattributed"


def layer_part_of(path: str):
    """(block, "<kind>" or "<kind>.<part>") of a scope path: `layer_of`'s
    block and kind, then the innermost `pt.<part>` (LAYER_PARTS) that
    lies INSIDE the winning `lk.<kind>` segment — a part stamped before
    its kind, or under a kind further out that an inner kind or an `og.`
    block took the instruction from, is not this kind's. `pose`, `other`
    and `unattributed` take no part. Summing the keys of one kind over
    its parts gives `layer_of`'s kind."""
    block, kind = layer_of(path)
    if kind in ("pose", "other", "unattributed"):
        return block, kind
    segs = [s for s in re.split(r"[/()]", path.split(";", 1)[0]) if s]
    at = len(segs) - 1 - segs[::-1].index("lk." + kind)
    parts = [s[3:] for s in segs[at + 1:]
             if s.startswith("pt.") and s[3:] in LAYER_PARTS]
    return block, f"{kind}.{parts[-1]}" if parts else kind

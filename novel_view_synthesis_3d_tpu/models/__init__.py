"""The denoisers, and the one place a model is built.

**The denoiser contract.** Whatever `build_denoiser` returns has

  - `config` (the ModelConfig) and `mesh`;
  - `init(rngs, batch, cond_mask=, train=)` → `{"params": tree}` and
    `apply({"params": tree}, batch, cond_mask=, train=)` → ε̂ (B, H, W, 3)
    of the target frame, flax's calling convention;
  - `precompute(params, cond)` → a dict of batch entries that do not
    change over a sampling call, laid out for the samplers' doubled
    guidance batch (rows [conditional…, unconditional…]); `apply` takes
    them in `batch`. What is in it is the model's own affair
    (sample/ddpm.make_sampler hands it through and names no model);
  - a scope vocabulary: `lk.<kind>` stamps from models/vocab.LAYER_KINDS
    (with `pt.<part>` of LAYER_PARTS inside a kind) and `og.<label>`
    blocks named by `op_groups(config)` of the family's module.

`model.family` selects: "xunet" (models/xunet.XUNet, the default) or
"tokens" (models/token_denoiser.TokenDenoiser). The token family has seven
trunks behind that one class — `model.tokens` is one of
config.TOKEN_TRUNKS and names the layers: Mistral-Small-4's (latent
attention, a shared expert; its cache entry a latent), SmallThinker's
(grouped-query heads, a window and rotary per layer, the router ahead of
attention; its cache entry keys and values), Kimi-Linear's stack, whose
layers differ BY INDEX (KDA, a gated delta rule, or latent attention
without a positional term; a dense MLP or sigmoid-routed experts),
Olmo-Hybrid's stack (Gated DeltaNet — a delta rule with one decay a head,
keys narrower than values — or full attention under a QK norm, by index;
every sublayer's OUTPUT normalised inside the residual; no expert layer),
LongCat-Flash's shortcut-connected double layer (two latent attentions and
two dense MLPs a layer, one expert branch across them over a router whose
last outputs are identity experts; its cache entry TWO latents), Laguna's
stack (grouped-query heads whose COUNT depends on the layer, 48 full and
72 under a window on the same 8 keys and values, a rotary law a layer
kind, a sigmoid gate a head on the attention's output; a window layer's
cache entry the window's tail), or
Phi-4-mini-flash's whole stack (Mamba selective-scan layers, differential
attention under a window and full, then gated memory units and cross
layers that READ what two earlier layers publish in the same pass — one
scan output, one key/value cache; no expert layer): what `precompute`
returns holds one cache entry a layer, each of its layer's own kind — a
recurrent state with its convolution's tail beside a latent, a window's
tail beside one layer's keys and values — and None for a layer that keeps
nothing of a frame; the once-a-call pass stops at the last layer that
keeps one. Entry points that carry only the X-UNet say so through
`require_family`.
"""

from novel_view_synthesis_3d_tpu.models.rays import camera_rays  # noqa: F401
from novel_view_synthesis_3d_tpu.models.xunet import (  # noqa: F401
    ConditioningProcessor,
    XUNet,
)


def build_denoiser(model_config, mesh=None):
    """The denoiser `model_config.family` names."""
    if model_config.family == "xunet":
        return XUNet(model_config, mesh=mesh)
    if model_config.family == "tokens":
        from novel_view_synthesis_3d_tpu.models.token_denoiser import (
            TokenDenoiser)

        return TokenDenoiser(model_config, mesh=mesh)
    raise ValueError(f"model.family={model_config.family!r}: 'xunet' or "
                     "'tokens'")


def require_family(model_config, family: str, who: str, missing: str):
    """Refuse, at construction, a family that `who` does not carry yet:
    one sentence naming the missing piece, and no fallback."""
    if model_config.family != family:
        raise NotImplementedError(
            f"{who} does not carry model.family={model_config.family!r} "
            f"yet: {missing}")


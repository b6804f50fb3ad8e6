"""`make_sampler` of the configurations a change did not mean to touch
lowers to the text it lowered to before.

The offline sampler is one XLA program a call; a PR that adds a second
trunk, a kernel form or a seam must leave the other configurations'
programs alone, and "the lowered text is the parent's" is the proof that
costs no chip time. The digests below are of the text lowered on the CPU
at toy sizes (kernels through the Pallas interpreter, as tier-1 runs
them) and, where the TPU compiler can describe a v5e here, of the text
lowered for it with the kernels compiled (a Mosaic body enters as the
digest of its module printed without locations: the serialized form
carries the checkout's path and line numbers). A digest changes only with
what the program computes — scopes and names are not part of the text.

When a PR does mean to change one of these programs, it replaces the
digest and says so in CHANGES.md; `python tests/test_sampler_lowering.py`
prints the current ones.
"""

import base64
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from novel_view_synthesis_3d_tpu.config import get_preset  # noqa: E402
from novel_view_synthesis_3d_tpu.diffusion.schedules import (  # noqa: E402
    sampling_schedule)
from novel_view_synthesis_3d_tpu.models import build_denoiser  # noqa: E402
from novel_view_synthesis_3d_tpu.ops import _pallas  # noqa: E402
from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler  # noqa: E402

TOY = {
    "paper256": {
        "model.ch": 32, "model.ch_mult": [1, 2], "model.emb_ch": 32,
        "model.num_res_blocks": 1, "model.attn_resolutions": [8],
        "data.img_sidelength": 16, "model.use_flash_attention": True},
    "ms4_denoiser128": {
        "model.tokens.hidden_size": 64, "model.tokens.num_hidden_layers": 2,
        "model.tokens.num_attention_heads": 4,
        "model.tokens.q_lora_rank": 32, "model.tokens.kv_lora_rank": 16,
        "model.tokens.qk_nope_head_dim": 8,
        "model.tokens.qk_rope_head_dim": 8, "model.tokens.v_head_dim": 16,
        "model.tokens.n_routed_experts": 16,
        "model.tokens.num_experts_per_tok": 4,
        "model.tokens.moe_intermediate_size": 32,
        "model.tokens.held_experts": [0, 4], "data.img_sidelength": 16,
        "model.use_flash_attention": True},
    "p4f_denoiser256": {
        "model.tokens.hidden_size": 64, "model.tokens.num_hidden_layers": 8,
        "model.tokens.num_attention_heads": 4,
        "model.tokens.num_key_value_heads": 2,
        "model.tokens.intermediate_size": 96,
        "model.tokens.sliding_window": 6, "model.tokens.mamba_d_state": 8,
        "data.img_sidelength": 16, "model.use_flash_attention": True},
    # KDA heads of 128 lanes, as its cell's: the width at which its two
    # kernels take a head as a whole lane block; and since PR 45 its latent
    # heads at the cell's 128 + 64 on 128 (the preset's), the widths at
    # which the attention kernel takes the shared key part as an operand
    "kl48_denoiser256": {
        "model.tokens.hidden_size": 64, "model.tokens.num_hidden_layers": 4,
        "model.tokens.num_attention_heads": 2,
        "model.tokens.kv_lora_rank": 16,
        "model.tokens.linear_attn_config.num_heads": 2,
        "model.tokens.linear_attn_config.head_dim": 128,
        "model.tokens.intermediate_size": 96, "model.tokens.num_experts": 8,
        "model.tokens.num_experts_per_token": 4,
        "model.tokens.moe_intermediate_size": 32,
        "model.tokens.held_experts": [0, 8], "data.img_sidelength": 16,
        "model.use_flash_attention": True},
    # delta-rule heads of 32 lanes on 64: four key heads fill a lane block
    "oh7_denoiser256": {
        "model.tokens.hidden_size": 64, "model.tokens.num_hidden_layers": 4,
        "model.tokens.num_attention_heads": 4,
        "model.tokens.num_key_value_heads": 4,
        "model.tokens.intermediate_size": 96,
        "model.tokens.linear_num_key_heads": 4,
        "model.tokens.linear_num_value_heads": 4,
        "model.tokens.linear_key_head_dim": 32,
        "model.tokens.linear_value_head_dim": 64,
        "data.img_sidelength": 16, "model.use_flash_attention": True},
    # grouped heads under a window that binds (16 tokens a frame), its
    # rehearsal's sizes: `flash_fwd`'s banded form, one call a query block
    "st21_denoiser256": {
        "model.tokens.hidden_size": 64, "model.tokens.num_hidden_layers": 4,
        "model.tokens.num_attention_heads": 4,
        "model.tokens.num_key_value_heads": 2, "model.tokens.head_dim": 16,
        "model.tokens.sliding_window_size": 16,
        "model.tokens.moe_num_primary_experts": 8,
        "model.tokens.moe_num_active_primary_experts": 3,
        "model.tokens.moe_ffn_hidden_size": 32,
        "model.tokens.held_experts": [0, 8], "data.img_sidelength": 16,
        "model.use_flash_attention": True},
    # heads of 128 + 64 on 128, as its cell's latent attention
    "lcf_denoiser256": {
        "model.tokens.hidden_size": 64, "model.tokens.num_layers": 2,
        "model.tokens.num_attention_heads": 2,
        "model.tokens.q_lora_rank": 32, "model.tokens.kv_lora_rank": 16,
        "model.tokens.ffn_hidden_size": 96,
        "model.tokens.expert_ffn_hidden_size": 32,
        "model.tokens.n_routed_experts": 16,
        "model.tokens.zero_expert_num": 8, "model.tokens.moe_topk": 4,
        "model.tokens.held_experts": [0, 4], "data.img_sidelength": 16,
        "model.use_flash_attention": True},
    # 4 and 6 query heads on 2 key/value heads, a window of 8 on 16 tokens
    "lgs_denoiser256": {
        "model.tokens.hidden_size": 64, "model.tokens.num_hidden_layers": 5,
        "model.tokens.num_key_value_heads": 2, "model.tokens.head_dim": 16,
        "model.tokens.num_attention_heads_per_layer": [4, 6, 6, 6] * 12,
        "model.tokens.sliding_window": 8,
        "model.tokens.intermediate_size": 96,
        "model.tokens.num_experts": 16,
        "model.tokens.num_experts_per_tok": 4,
        "model.tokens.moe_intermediate_size": 32,
        "model.tokens.shared_expert_intermediate_size": 32,
        "model.tokens.held_experts": [0, 8], "data.img_sidelength": 16,
        "model.use_flash_attention": True},
}
# (preset, "cpu" | "v5e") → sha256 of the lowered text, from the tree of
# the PR that last meant to change it.
DIGESTS = {
    # PR 41's own tree (six of its ten stand): `flash_fwd` reads q, K and V
    # token-major, a head a block of lanes, and every sampler has it (the
    # X-UNet's `AttnLayer` too); `ms4` and `kl48` make their latent
    # layers' keys and values each by a product of its own and `ms4`
    # rotates q where its product writes it, by kernels derived once a
    # call, `p4f` hands a pair's two maps to the kernel without slicing
    # the pair and norms A¹V − λA²V where the kernel wrote it
    # (CHANGES.md, PR 41).
    ("paper256", "cpu"):
        "8cd2128e75b5b22a83aab8ad4cfd3167e9cfdd8ce111b123c2c84fe84031c0ef",
    ("paper256", "v5e"):
        "e4f5062f6e47b970b0b079c0b8b1e6fa815fb60c3c780ebc5f68914bc9567a95",
    ("ms4_denoiser128", "cpu"):
        "cdc63de9af67baa26e39ac8cb6734d5757b555f6e91b1ff5ca14ec01128b3f08",
    ("ms4_denoiser128", "v5e"):
        "30f7d595b672193d7c91ab3813229d8acf223326e6b5a4a2864ad62977ada6dc",
    ("p4f_denoiser256", "cpu"):
        "05f8f63c7fa473cb754df607ae529763c219dde26b375809c49a704be6f2f054",
    ("p4f_denoiser256", "v5e"):
        "8d3815a1662ead5a40d76159f15dcc8e09008623a3a645cd0baedb5df00a31eb",
    # PR 46's tree, these two: `gdn_fwd` multiplies q and k as the bfloat16
    # they arrive in and S and U in three parts, two chunks side by side
    # wherever an operand is (C, C), T's merges on the rows they change,
    # the heads past the edge not walked;
    # until then they were PR 42's (both delta-rule layers hand their
    # scan's o and the gate's projection to `head_norm_fwd`,
    # ops/head_norm.py), as `kl48`'s scan still is. The other twelve are
    # the parent's (CHANGES.md, PR 46).
    ("oh7_denoiser256", "cpu"):
        "4bb1c0b6a74ddc4e79b8f4e0be563228d07365bf92feec93933edf7bdeb055a6",
    ("oh7_denoiser256", "v5e"):
        "99aae2d5daaa435d323e6d30be69c63f1a74afd3bae51b44716e0a643fec28ca",
    # PR 44's tree, pinned by PR 45 (whose tree lowers the same text: the
    # banded form takes no second operand)
    ("st21_denoiser256", "cpu"):
        "8760b1e5edfb6f8975bc788e6f6d28dc38ecf25c2718547b4b08016899201c28",
    ("st21_denoiser256", "v5e"):
        "569eeeb217b5fc1eac55de5a3746e8472b52ccb57b781832e66fd990f29ef8d9",
    # PR 45's tree, these four: latent attention at heads of 128 + 64
    # hands `flash_fwd` the nope lanes and the shared rotary part as two
    # operands (no pad 192 → 256, no identity block under the keys'
    # kernel, the pair-swapped product on the rotary columns alone);
    # `kl48`'s toy moved to those widths with it — at the 16 + 8 it had,
    # its text was still PR 42's. The ten above are the parent's
    # (CHANGES.md, PR 45).
    ("kl48_denoiser256", "cpu"):
        "9a7ef4a4488467e978719760f3467c70d3984e3c0d00f9b4d6c5cbbf1f7f1daf",
    ("kl48_denoiser256", "v5e"):
        "b2344b38923a9116bb7ffdf0ffa0407b17ab6705b56006b58b8948dc6e135478",
    ("lcf_denoiser256", "cpu"):
        "bd7ff69c8c69e00a33700e588bfd09f436a4f777630984e4a262ef8d0ba34f15",
    ("lcf_denoiser256", "v5e"):
        "08af418bf9ade7147e962d60aeea843c45b7ed1a3ca3baf4247468c58aa53af9",
    # PR 47's tree: the seventh trunk, pinned as it landed (the sixteen
    # above are the parent's: `LagunaLayer` is its own class and the shared
    # functions it calls were not touched)
    ("lgs_denoiser256", "cpu"):
        "f71e7d6b713bc12775b6504672bcee4a8e1ef00ec5b512f47aadd3918a1e4b42",
    ("lgs_denoiser256", "v5e"):
        "c23db595955e06a26a5a08f8c7560b07dfd3978ea6145cd6307eb1e8406200c3",
}


def lowered_text(preset, sharding=None):
    """The text of `make_sampler(trajectory_every=1)` at the toy size: 2
    views a call, 4 respaced ddpm steps, guidance 3."""
    cfg = get_preset(preset).override(**dict(
        TOY[preset], **{"diffusion.sample_timesteps": 4,
                        "diffusion.guidance_weight": 3.0})).validate()
    model = build_denoiser(cfg.model)
    side, B = cfg.data.img_sidelength, 2

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    cond = {"x": spec(B, side, side, 3), "R1": spec(B, 3, 3),
            "t1": spec(B, 3), "R2": spec(B, 3, 3), "t2": spec(B, 3),
            "K": spec(B, 3, 3)}

    def init():
        batch = {k: jnp.zeros(v.shape) for k, v in cond.items()}
        batch.update(z=jnp.zeros((B, side, side, 3)),
                     logsnr=jnp.zeros((B,)))
        return model.init({"params": jax.random.PRNGKey(0),
                           "dropout": jax.random.PRNGKey(1)}, batch,
                          cond_mask=jnp.ones((B,)), train=False)["params"]

    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(init))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding)
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, 4),
                           cfg.diffusion, trajectory_every=1)
    return jax.jit(sampler).lower(params, key, cond).as_text()


def _body_without_locations(match):
    """A compiled kernel's `backend_config` with its serialized body (MLIR
    bytecode, which carries the source's path and line numbers) replaced
    by the digest of the same module printed without locations."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    config = json.loads(re.sub(
        r"\\([0-9A-Fa-f]{2})", lambda m: chr(int(m.group(1), 16)),
        match.group(1)))
    call = config.get("custom_call_config", {})
    if "body" in call:
        with jax_mlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            asm = ir.Module.parse(base64.b64decode(call["body"])) \
                .operation.get_asm(enable_debug_info=False)
        call["body"] = hashlib.sha256(asm.encode()).hexdigest()
    return "backend_config = " + json.dumps(config, sort_keys=True)


def digest(text):
    text = re.sub(r'backend_config = "([^"]*)"', _body_without_locations,
                  text)
    return hashlib.sha256(text.encode()).hexdigest()


def v5e_sharding():
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("preset", sorted(TOY))
def test_sampler_lowers_to_the_pinned_text_on_the_cpu(preset):
    assert digest(lowered_text(preset)) == DIGESTS[preset, "cpu"]


@pytest.mark.parametrize("preset", sorted(TOY))
def test_sampler_lowers_to_the_pinned_text_for_a_described_v5e(
        preset, monkeypatch):
    try:
        sharding = v5e_sharding()
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    text = lowered_text(preset, sharding)
    assert "tpu_custom_call" in text
    assert digest(text) == DIGESTS[preset, "v5e"]


if __name__ == "__main__":
    jax.config.update("jax_platforms", "cpu")
    for preset in sorted(TOY):
        print(preset, "cpu", digest(lowered_text(preset)))
    sharding = v5e_sharding()
    _pallas.use_interpret = lambda: False
    for preset in sorted(TOY):
        text = lowered_text(preset, sharding)
        print(preset, "v5e", digest(text), len(text),
              text.count("tpu_custom_call"))

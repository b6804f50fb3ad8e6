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
    # kernels take a head as a whole lane block
    "kl48_denoiser256": {
        "model.tokens.hidden_size": 64, "model.tokens.num_hidden_layers": 4,
        "model.tokens.num_attention_heads": 4,
        "model.tokens.kv_lora_rank": 16, "model.tokens.qk_nope_head_dim": 16,
        "model.tokens.qk_rope_head_dim": 8, "model.tokens.v_head_dim": 16,
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
}
# (preset, "cpu" | "v5e") → sha256 of the lowered text, from the parent
# of the PR that last meant to change it (CHANGES.md, PR 30) — `paper256`'s
# two from PR 31's own tree, which meant to change that program (the
# X-UNet carries (B·F, H, W, C)) and no other, and `ms4_denoiser128`'s two
# from PR 37's, which meant to change that one (the expert layer's combine
# is the kernel `moe_combine`) and left `paper256`'s as they were.
DIGESTS = {
    ("paper256", "cpu"):
        "39347a7dc4a454945a858ac36c13fad5a51d48cd03e335f9c293d145eaad0b23",
    ("ms4_denoiser128", "cpu"):
        "e1116f1eeaaa10248ac67a2016934a786d7d303045df7dc20c87fd35a04b8d4e",
    ("paper256", "v5e"):
        "63517c08226f48f0a0478fde26dc9d9b22e2bfe776035c1783a9bf535b087e71",
    ("ms4_denoiser128", "v5e"):
        "3c9874938e8fdba4f6ff895d76c5b6423762b73f3a7467c81779b0344a4d8243",
    # PR 39's own tree: the fourth trunk's sampler with its Mamba layers'
    # short convolution as the kernel `short_conv_fwd` (PR 38's two
    # replaced, CHANGES.md; the four above untouched).
    ("p4f_denoiser256", "cpu"):
        "a1e966152de9a583a2316ffd726ee694d86b35d55bbf5d28deaa071320e49ca9",
    ("p4f_denoiser256", "v5e"):
        "d493bcf03616a4886b0dd0b99ba0b3e7b1749c7b12b6a33b9a2f21d5741f55c2",
    # PR 40's PARENT (PR 39's tree), pinned by PR 40, which moved what
    # `kda_fwd` and `gdn_fwd` share into one module and gave `short_conv`
    # its head groups: at heads that are whole lane blocks the third
    # trunk's sampler lowers to the parent's text, on both.
    ("kl48_denoiser256", "cpu"):
        "80ad7fb1518157a8ef8804111937ba4400e59ec82e7671671f0ccf2a7124ad64",
    ("kl48_denoiser256", "v5e"):
        "c133b30ad66c75314497d2f55a5061cbdbe1d982aed6cd97caf288be12482ef4",
    # PR 40's own tree: the fifth trunk's sampler as that PR made it (the
    # eight above are the parent's).
    ("oh7_denoiser256", "cpu"):
        "328545c2ea4d64c3277074b67ff93e974658a36db19eaec829fc5641f71a2cf1",
    ("oh7_denoiser256", "v5e"):
        "3fef6256c52f8b55141f5909b832719b2369bee294a889763adf03cf01393508",
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

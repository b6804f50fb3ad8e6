"""The denoiser contract and its one factory (models/__init__.py): the
X-UNet through `build_denoiser` and the samplers' `precompute` seam is the
program it was before them, and every entry point that does not carry the
token family refuses it by name instead of falling back."""

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.config import (
    Config, DiffusionConfig, ModelConfig, get_preset)
from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
from novel_view_synthesis_3d_tpu.diffusion.schedules import sampling_schedule
from novel_view_synthesis_3d_tpu.models import (
    build_denoiser, require_family, token_denoiser)
from novel_view_synthesis_3d_tpu.models.xunet import (
    XUNet, op_groups, precompute_guidance_pose_embs)
from novel_view_synthesis_3d_tpu.sample import ddpm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# paper256's shape at toy sizes: five levels' worth of structure in three,
# bfloat16 compute, float32 parameters, the guidance pair's 1 × 1 extent.
TOY = ModelConfig(ch=32, ch_mult=(1, 2, 2), emb_ch=32, num_res_blocks=1,
                  attn_resolutions=(8,), dtype="bfloat16", dropout=0.0,
                  use_flash_attention=False)
DIFF = DiffusionConfig(timesteps=8, sample_timesteps=4)
TINY_TOKENS = {
    "model.tokens.hidden_size": 32, "model.tokens.num_hidden_layers": 1,
    "model.tokens.num_attention_heads": 2, "model.tokens.q_lora_rank": 16,
    "model.tokens.kv_lora_rank": 8, "model.tokens.qk_nope_head_dim": 8,
    "model.tokens.qk_rope_head_dim": 8, "model.tokens.v_head_dim": 8,
    "model.tokens.n_routed_experts": 4,
    "model.tokens.num_experts_per_tok": 2,
    "model.tokens.moe_intermediate_size": 16,
    "model.tokens.held_experts": [0, 2], "data.img_sidelength": 16,
    "model.dtype": "float32", "model.param_dtype": "float32",
    "diffusion.timesteps": 8, "diffusion.sample_timesteps": 2,
}


# The second token trunk (SmallThinker's layer) at toy sizes: a full and
# a window layer, the window as long as a frame.
TINY_GQA_TOKENS = {
    "model.tokens.hidden_size": 32, "model.tokens.num_hidden_layers": 2,
    "model.tokens.num_attention_heads": 4,
    "model.tokens.num_key_value_heads": 2, "model.tokens.head_dim": 8,
    "model.tokens.sliding_window_size": 16,
    "model.tokens.moe_num_primary_experts": 4,
    "model.tokens.moe_num_active_primary_experts": 2,
    "model.tokens.moe_ffn_hidden_size": 16,
    "model.tokens.held_experts": [0, 4], "data.img_sidelength": 16,
    "model.dtype": "float32", "model.param_dtype": "float32",
    "diffusion.timesteps": 8, "diffusion.sample_timesteps": 2,
}
# The third token trunk (Kimi-Linear's stack) at toy sizes: KDA + dense,
# KDA + experts, KDA + experts, latent attention + experts.
TINY_KDA_TOKENS = {
    "model.tokens.hidden_size": 32, "model.tokens.num_hidden_layers": 4,
    "model.tokens.num_attention_heads": 2, "model.tokens.kv_lora_rank": 8,
    "model.tokens.qk_nope_head_dim": 8, "model.tokens.qk_rope_head_dim": 4,
    "model.tokens.v_head_dim": 8,
    "model.tokens.linear_attn_config.num_heads": 2,
    "model.tokens.linear_attn_config.head_dim": 8,
    "model.tokens.intermediate_size": 48, "model.tokens.num_experts": 4,
    "model.tokens.num_experts_per_token": 2,
    "model.tokens.moe_intermediate_size": 16,
    "model.tokens.held_experts": [0, 2], "data.img_sidelength": 16,
    "model.dtype": "float32", "model.param_dtype": "float32",
    "diffusion.timesteps": 8, "diffusion.sample_timesteps": 2,
}
# The fourth token trunk (Phi-4-mini-flash's stack) at toy sizes: Mamba,
# window, Mamba, window, Mamba, full, a gated memory unit, a cross layer.
TINY_SSM_TOKENS = {
    "model.tokens.hidden_size": 32, "model.tokens.num_hidden_layers": 8,
    "model.tokens.num_attention_heads": 4,
    "model.tokens.num_key_value_heads": 2,
    "model.tokens.intermediate_size": 48, "model.tokens.sliding_window": 6,
    "model.tokens.mamba_d_state": 4, "data.img_sidelength": 16,
    "model.dtype": "float32", "model.param_dtype": "float32",
    "diffusion.timesteps": 8, "diffusion.sample_timesteps": 2,
}
# The fifth token trunk (Olmo-Hybrid's stack) at toy sizes: Gated DeltaNet
# x 3 (keys of 8 on values of 16), full attention under the QK norm.
TINY_GDN_TOKENS = {
    "model.tokens.hidden_size": 32, "model.tokens.num_hidden_layers": 4,
    "model.tokens.num_attention_heads": 4,
    "model.tokens.num_key_value_heads": 4,
    "model.tokens.intermediate_size": 48,
    "model.tokens.linear_num_key_heads": 2,
    "model.tokens.linear_num_value_heads": 2,
    "model.tokens.linear_key_head_dim": 8,
    "model.tokens.linear_value_head_dim": 16, "data.img_sidelength": 16,
    "model.dtype": "float32", "model.param_dtype": "float32",
    "diffusion.timesteps": 8, "diffusion.sample_timesteps": 2,
}
# The sixth token trunk (LongCat-Flash's double layer) at toy sizes: two
# latent attentions and two dense MLPs a layer, a router of 8 real + 4
# identity outputs, experts 0-3 held.
TINY_SCMOE_TOKENS = {
    "model.tokens.hidden_size": 32, "model.tokens.num_layers": 2,
    "model.tokens.num_attention_heads": 2, "model.tokens.q_lora_rank": 16,
    "model.tokens.kv_lora_rank": 8, "model.tokens.qk_nope_head_dim": 8,
    "model.tokens.qk_rope_head_dim": 4, "model.tokens.v_head_dim": 8,
    "model.tokens.ffn_hidden_size": 48,
    "model.tokens.expert_ffn_hidden_size": 16,
    "model.tokens.n_routed_experts": 8, "model.tokens.zero_expert_num": 4,
    "model.tokens.moe_topk": 3, "model.tokens.held_experts": [0, 4],
    "data.img_sidelength": 16,
    "model.dtype": "float32", "model.param_dtype": "float32",
    "diffusion.timesteps": 8, "diffusion.sample_timesteps": 2,
}
# The seventh token trunk (Laguna's stack) at toy sizes: 2 and 4 query
# heads by layer on 2 key/value heads, a window of 8 on 16 tokens, a
# leading dense layer, 8 experts top-2 of which 4 are held.
TINY_HEADMIX_TOKENS = {
    "model.tokens.hidden_size": 32, "model.tokens.num_hidden_layers": 3,
    "model.tokens.num_key_value_heads": 2, "model.tokens.head_dim": 8,
    "model.tokens.num_attention_heads_per_layer": [2, 4, 4, 4] * 12,
    "model.tokens.sliding_window": 8, "model.tokens.intermediate_size": 48,
    "model.tokens.num_experts": 8, "model.tokens.num_experts_per_tok": 2,
    "model.tokens.moe_intermediate_size": 16,
    "model.tokens.shared_expert_intermediate_size": 16,
    "model.tokens.held_experts": [0, 4], "data.img_sidelength": 16,
    "model.dtype": "float32", "model.param_dtype": "float32",
    "diffusion.timesteps": 8, "diffusion.sample_timesteps": 2,
}
TINY_BY_PRESET = {"ms4_denoiser128": TINY_TOKENS,
                  "st21_denoiser256": TINY_GQA_TOKENS,
                  "kl48_denoiser256": TINY_KDA_TOKENS,
                  "p4f_denoiser256": TINY_SSM_TOKENS,
                  "oh7_denoiser256": TINY_GDN_TOKENS,
                  "lcf_denoiser256": TINY_SCMOE_TOKENS,
                  "lgs_denoiser256": TINY_HEADMIX_TOKENS}


def token_cfg(**over) -> Config:
    return get_preset("ms4_denoiser128").override(
        **dict(TINY_TOKENS, **over)).validate()


def toy_inputs():
    b = make_example_batch(batch_size=2, sidelength=32, seed=0)
    mb = {"x": b["x"], "z": b["target"], "logsnr": jnp.zeros((2,)),
          "R1": b["R1"], "t1": b["t1"], "R2": b["R2"], "t2": b["t2"],
          "K": b["K"]}
    cond = {k: v for k, v in mb.items() if k not in ("z", "logsnr")}
    return mb, cond


def parents_make_sampler(model, schedule, config, trajectory_every):
    """`make_sampler` as the commit before the seam wrote it: the X-UNet's
    pose embeddings hoisted by name and handed to `_cfg_eps` as such."""
    w = config.guidance_weight
    update, init_aux = ddpm._make_update(schedule, config)
    T = schedule.num_timesteps

    def body(cond, params, pose_embs, carry, t):
        z, key, aux = carry
        key, k_step = jax.random.split(key)
        batch = dict(cond, z=z,
                     logsnr=jnp.full((z.shape[0],), schedule.logsnr(t)))
        outs = ddpm._cfg_eps(model, params, batch, w,
                             {"pose_embs": pose_embs})
        z, aux = update(z, t, outs, k_step, aux)
        return (z, key, aux), None

    @jax.jit
    @jax.named_scope("lk.update")
    def sample(params, key, cond):
        z_shape = cond["x"].shape[:1] + cond["x"].shape[-3:]
        key, k_init = jax.random.split(key)
        z0 = jax.random.normal(k_init, z_shape)
        ts = jnp.arange(T - 1, -1, -1)
        pose_embs = precompute_guidance_pose_embs(model, params, cond)
        step = partial(body, cond, params, pose_embs)
        carry0 = (z0, key, init_aux(z0))
        if not trajectory_every:
            (z, _, _), _ = jax.lax.scan(step, carry0, ts)
            return z

        def outer(carry, ts_chunk):
            carry, _ = jax.lax.scan(step, carry, ts_chunk)
            return carry, carry[0]

        chunks = ts.reshape(T // trajectory_every, trajectory_every)
        carry, traj = jax.lax.scan(outer, carry0, chunks)
        return carry[0], traj

    return sample


def test_build_denoiser_gives_the_xunet_and_its_tree():
    model = build_denoiser(TOY)
    assert isinstance(model, XUNet) and model.family == "xunet"
    assert model == XUNet(TOY)
    mb, _ = toy_inputs()

    def shapes(m):
        return jax.eval_shape(lambda: m.init(
            {"params": jax.random.PRNGKey(0)}, mb, cond_mask=jnp.ones((2,)),
            train=False))["params"]

    assert jax.tree.map(lambda a: (a.shape, a.dtype), shapes(model)) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), shapes(XUNet(TOY)))
    assert len(op_groups(TOY)) > 5


@pytest.mark.parametrize("trajectory_every", [0, 1])
def test_the_seam_leaves_the_xunets_sampler_as_it_was(trajectory_every):
    """The program `make_sampler` lowers for an X-UNet is, text for text,
    the one the commit before the seam lowered (whose own lowering of these
    sizes had the same text when the seam was written: PERF.md, PR 26)."""
    model = build_denoiser(TOY)
    mb, cond = toy_inputs()
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0)}, mb, cond_mask=jnp.ones((2,)),
        train=False))["params"]
    schedule = sampling_schedule(DIFF, 4)
    args = (params, jax.ShapeDtypeStruct((2,), jnp.uint32),
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in cond.items()})
    now = ddpm.make_sampler(model, schedule, DIFF,
                            trajectory_every=trajectory_every)
    then = parents_make_sampler(model, schedule, DIFF, trajectory_every)
    assert now.lower(*args).as_text() == then.lower(*args).as_text()


def test_xunet_precompute_is_the_doubled_pose_embeddings():
    model = build_denoiser(TOY)
    mb, cond = toy_inputs()
    params = model.init({"params": jax.random.PRNGKey(0)}, mb,
                        cond_mask=jnp.ones((2,)), train=False)["params"]
    pre = model.precompute(params, cond)
    assert set(pre) == {"pose_embs"}
    want = precompute_guidance_pose_embs(model, params, cond)
    for (c, u), (wc, wu) in zip(pre["pose_embs"], want):
        np.testing.assert_array_equal(np.asarray(c), np.asarray(wc))
        np.testing.assert_array_equal(np.asarray(u), np.asarray(wu))
        assert u.shape[2:4] == (1, 1)
    # the full-extent fallback, where the configuration forbids the pair
    import dataclasses
    flagged = build_denoiser(dataclasses.replace(TOY, use_pos_emb=True))
    p2 = flagged.init({"params": jax.random.PRNGKey(0)}, mb,
                      cond_mask=jnp.ones((2,)), train=False)["params"]
    level0 = flagged.precompute(p2, cond)["pose_embs"][0]
    assert not isinstance(level0, tuple) and level0.shape[0] == 4


def test_build_denoiser_gives_the_token_denoiser():
    from novel_view_synthesis_3d_tpu.models.token_denoiser import (
        TokenDenoiser)

    cfg = token_cfg()
    model = build_denoiser(cfg.model)
    assert isinstance(model, TokenDenoiser) and model.family == "tokens"
    labels = [label for label, _ in token_denoiser.op_groups(cfg.model)]
    assert labels == ["prelude", "layer_0", "final"]
    with pytest.raises(ValueError, match="family"):
        build_denoiser(ModelConfig(family="resnet"))


def _tokens():
    cfg = token_cfg()
    return cfg, build_denoiser(cfg.model)


def _refuse_trainer():
    from novel_view_synthesis_3d_tpu.train.trainer import Trainer

    Trainer(config=token_cfg(), use_grain=False)


def _refuse_service():
    from novel_view_synthesis_3d_tpu.sample.service import SamplingService

    cfg, model = _tokens()
    SamplingService(model, {}, cfg.diffusion, cfg.serve, start=False)


def _refuse_gate(which):
    from novel_view_synthesis_3d_tpu.registry import gate

    cfg, model = _tokens()
    extra = {"frames": 2} if which == "make_trajectory_probe" else {}
    getattr(gate, which)(model, cfg.diffusion, {}, sample_steps=2, **extra)


REFUSALS = {
    "trainer": (_refuse_trainer, "balance loss"),
    "request_sampler": (lambda: ddpm.make_request_sampler(
        _tokens()[1], sampling_schedule(DIFF, 2), DIFF), "precompute seam"),
    "slot_step": (lambda: ddpm.make_ring_step_fn(_tokens()[1], DIFF, k_max=0),
                  "latent cache per ring slot"),
    "bank_step": (lambda: ddpm.make_ring_step_fn(_tokens()[1], DIFF, k_max=2),
                  "frame of the bank"),
    "stochastic": (lambda: ddpm.make_stochastic_sampler(
        _tokens()[1], sampling_schedule(DIFF, 2), DIFF, 2), "pool view"),
    "cond_encode": (lambda: ddpm.make_cond_encode_fn(_tokens()[1]),
                    "latent cache"),
    "service": (_refuse_service, "precompute seam"),
    "gate_psnr": (lambda: _refuse_gate("make_psnr_probe"), "staging"),
    "gate_trajectory": (lambda: _refuse_gate("make_trajectory_probe"),
                        "staging"),
}


@pytest.mark.parametrize("name", sorted(REFUSALS))
def test_entry_points_without_the_token_family_refuse_it(name):
    call, missing = REFUSALS[name]
    with pytest.raises(NotImplementedError) as err:
        call()
    text = str(err.value)
    assert "model.family='tokens'" in text and missing in text, text


def test_require_family_passes_the_family_it_carries():
    require_family(TOY, "xunet", "a test", "nothing")
    with pytest.raises(NotImplementedError, match="a test does not carry"):
        require_family(token_cfg().model, "xunet", "a test", "x")


def test_token_denoiser_refuses_what_is_the_xunets():
    cfg, model = _tokens()
    with pytest.raises(NotImplementedError, match="ops"):
        model.apply({"params": {}}, {}, cond_mask=None, train=False,
                    ops=(0, 1))


@pytest.mark.parametrize("preset", sorted(TINY_BY_PRESET))
def test_cli_sample_runs_the_preset_through_the_factory(tmp_path, capsys,
                                                        preset):
    """`nvs3d sample --preset <a token preset>` at tiny overrides: the
    factory builds the token denoiser on that preset's trunk, a checkpoint
    of its tree restores, and `make_sampler` writes views."""
    from novel_view_synthesis_3d_tpu.cli import main
    from novel_view_synthesis_3d_tpu.data.synthetic import (
        write_synthetic_srn)
    from novel_view_synthesis_3d_tpu.train.checkpoint import (
        CheckpointManager)
    from novel_view_synthesis_3d_tpu.train.state import create_train_state
    from novel_view_synthesis_3d_tpu.train.trainer import (
        _sample_model_batch)

    root = str(tmp_path / "srn")
    write_synthetic_srn(root, num_instances=1, views_per_instance=3,
                        image_size=16)
    over = dict(TINY_BY_PRESET[preset], **{
        "train.checkpoint_dir": str(tmp_path / "ckpt"),
        "train.results_folder": str(tmp_path / "results")})
    cfg = get_preset(preset).override(**over).validate()
    model = build_denoiser(cfg.model)
    b = make_example_batch(batch_size=1, sidelength=16, seed=0)
    state = create_train_state(cfg.train, model, _sample_model_batch(
        {k: np.asarray(b[k]) for k in ("x", "target", "R1", "t1", "R2",
                                       "t2", "K")}))
    # the output adapter is zero at init: give ε̂ something to say
    state = state.replace(params=dict(state.params, out={
        "kernel": jnp.full_like(state.params["out"]["kernel"], 0.02)}))
    state = state.replace(step=jnp.asarray(3, jnp.int32))
    ckpt = CheckpointManager(cfg.train.checkpoint_dir)
    ckpt.save(3, state, force=True)
    ckpt.close()
    out = str(tmp_path / "views")
    args = [f"{k}={v if not isinstance(v, list) else str(v).replace(' ', '')}"
            for k, v in over.items()]
    assert main(["sample", root, "--preset", preset, "--out", out,
                 "--num-views", "2", "--sample-steps", "2"] + args) == 0
    assert "restored checkpoint at step 3" in capsys.readouterr().out
    assert os.path.exists(os.path.join(out, "view_000.png"))
    assert os.path.exists(os.path.join(out, "view_001.png"))

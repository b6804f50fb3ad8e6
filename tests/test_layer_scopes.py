"""The layer names the program stamps: `og.<block>` / `lk.<kind>` scopes
with `pt.<part>` inside a kind, what `models/xunet.layer_of` and
`layer_part_of` make of a scope path, the benchmark's readers of the
parts, the names of the Pallas kernels' instructions aside
(tests/test_tpu_compile.py), and what importing the program must not load.

The stamps are HLO metadata: that they change no number is what the
sampler and train-step goldens (tests/test_sampler.py,
tests/test_trajectory.py, tests/test_train_step.py) show by passing
untouched.
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.config import (
    DiffusionConfig, ModelConfig, get_preset)
from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
from novel_view_synthesis_3d_tpu.diffusion.schedules import sampling_schedule
from novel_view_synthesis_3d_tpu.models import build_denoiser
from novel_view_synthesis_3d_tpu.models.xunet import (
    LAYER_PARTS, XUNET_LAYER_KINDS as LAYER_KINDS, XUNet, layer_of,
    layer_part_of, op_groups)
from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
LOOP = "jit(sample)/lk.update/while/body/closed_call"
MODEL = LOOP + "/XUNet"

# Hand-written paths in the forms a compiled program carries them.
PATHS = {
    # innermost kind wins
    "conv_in_resnet": (
        MODEL + "/og.XUNetBlock_3/XUNetBlock_3/ResnetBlock_0/"
        "FrameConv_0/lk.conv/Conv_0/conv_general_dilated",
        ("XUNetBlock_3", "conv")),
    "gn_inside_resnet_is_gn": (
        MODEL + "/og.XUNetBlock_3/XUNetBlock_3/ResnetBlock_0/"
        "GroupNorm_0/lk.gn/GroupNorm_0/reduce_sum",
        ("XUNetBlock_3", "gn")),
    "gn_inside_attention_is_gn": (
        MODEL + "/og.middle/XUNetBlock_9/AttnBlock_0/GroupNorm_0/"
        "lk.gn/GroupNorm_0/rsqrt",
        ("middle", "gn")),
    "attention_core": (
        MODEL + "/og.XUNetBlock_2/XUNetBlock_2/AttnBlock_1/lk.attn/"
        "AttnLayer_0/flash_fwd/pallas_call",
        ("XUNetBlock_2", "attn")),
    "film_inside_resnet_is_emb": (
        MODEL + "/og.ResnetBlock_1/ResnetBlock_1/FiLM_0/lk.emb/"
        "Dense_0/dot_general",
        ("ResnetBlock_1", "emb")),
    "resnet_residual_is_conv": (
        MODEL + "/og.ResnetBlock_1/ResnetBlock_1/lk.conv/mul",
        ("ResnetBlock_1", "conv")),
    "level_embedding_sum": (
        MODEL + "/og.XUNetBlock_0/lk.emb/add", ("XUNetBlock_0", "emb")),
    # the sampler's stamp does not reach into a block
    "unstamped_in_block_is_other": (
        MODEL + "/og.XUNetBlock_7/concatenate", ("XUNetBlock_7", "other")),
    "prelude_stack_is_other": (
        MODEL + "/og.prelude/concatenate", ("prelude", "other")),
    # the sampler's own work
    "sampler_noise_draw": (
        LOOP + "/jit(_normal)/jit(_normal_real)/erf_inv", ("", "update")),
    "sampler_guidance_combine": (LOOP + "/sub", ("", "update")),
    "sampler_loop_itself": ("jit(sample)/lk.update/while", ("", "update")),
    # pose wins over everything, in the sampler and in training
    "pose_conv_in_sampler": (
        "jit(sample)/lk.update/ConditioningProcessor/lk.pose/"
        "FrameConv_2/lk.conv/Conv_0/conv_general_dilated", ("", "pose")),
    "pose_doubling_in_sampler": (
        "jit(sample)/lk.update/concatenate", ("", "update")),
    # nested stamps of one kind, and of two: the innermost holds
    "nested_same_kind": (
        MODEL + "/og.final/lk.conv/FrameConv_1/lk.conv/Conv_0/add",
        ("final", "conv")),
    "nested_two_kinds": (
        MODEL + "/og.middle/XUNetBlock_9/AttnBlock_0/lk.attn/lk.gn/mul",
        ("middle", "gn")),
    "pose_in_training_prelude": (
        "jit(train_step)/jvp(XUNet)/og.prelude/ConditioningProcessor_0/"
        "lk.pose/FrameConv_0/lk.conv/Conv_0/conv_general_dilated",
        ("prelude", "pose")),
    "logsnr_mlp_in_prelude": (
        "jit(train_step)/jvp(XUNet)/og.prelude/ConditioningProcessor_0/"
        "lk.emb/Dense_1/dot_general", ("prelude", "emb")),
    # transform wrappers are split like slashes
    "backward_pass": (
        "jit(train_step)/transpose(jvp(XUNet))/og.final/GroupNorm_0/lk.gn/"
        "GroupNorm_0/mul", ("final", "gn")),
    "wrapped_block": (
        "jit(f)/transpose(jvp(XUNet/og.final/FrameConv_1/lk.conv))/Conv_0/"
        "conv_general_dilated", ("final", "conv")),
    "remat_block": (
        "jit(train_step)/jvp(XUNet)/og.XUNetBlock_0/checkpoint/"
        "XUNetBlock_0/ResnetBlock_0/lk.conv/add", ("XUNetBlock_0", "conv")),
    # XLA joins the paths of merged instructions with ';': the first holds
    "merged_instructions": (
        MODEL + "/og.final/GroupNorm_0/lk.gn/mul;" + LOOP + "/add",
        ("final", "gn")),
    # no program scope at all
    "compiler_helper": ("reduce_sum", ("", "unattributed")),
    "parameter": ("params['FrameConv_0']['Conv_0']['kernel']",
                  ("", "unattributed")),
    "foreign_program": ("jit(_threefry_fold_in)/threefry2x32",
                        ("", "unattributed")),
    "empty": ("", ("", "unattributed")),
    # a stamp outside the vocabulary is no kind
    "unknown_stamp_in_block": (MODEL + "/og.final/lk.bogus/add",
                               ("final", "other")),
    "unknown_stamp_alone": ("jit(f)/lk.bogus/add", ("", "unattributed")),
}


@pytest.mark.parametrize("case", sorted(PATHS))
def test_layer_of_rules(case):
    path, want = PATHS[case]
    assert layer_of(path) == want


TOKENS = LOOP + "/while/body/closed_call"   # the token family's step
# Paths with parts, hand-written: (block, kind or kind.part).
PART_PATHS = {
    "kernel_call": (
        TOKENS + "/og.layer_1/lk.mla_core/pt.kernel/flash_fwd/pallas_call",
        ("layer_1", "mla_core.kernel")),
    "kernel_call_of_a_jitted_wrapper": (
        TOKENS + "/og.layer_2/lk.moe_experts/jit(_gmm)/pt.kernel/gmm/"
        "pallas_call", ("layer_2", "moe_experts.kernel")),
    "wrapper_layout": (
        TOKENS + "/og.layer_1/lk.attn_window/pt.layout/transpose",
        ("layer_1", "attn_window.layout")),
    # the short convolution in front of a scan: its call, what its wrapper
    # does around it, and what the layer does between the calls
    "conv_kernel_call": (
        TOKENS + "/og.layer_0/lk.kda_conv/jit(_conv_call)/pt.kernel/"
        "short_conv_fwd/pallas_call", ("layer_0", "kda_conv.kernel")),
    # o's gated head-wise norm behind a delta rule: its call is the
    # projections' kind's `kernel` part
    "head_norm_kernel_call": (
        TOKENS + "/og.layer_0/lk.kda_proj/jit(_norm_call)/pt.kernel/"
        "head_norm_fwd/pallas_call", ("layer_0", "kda_proj.kernel")),
    "conv_wrapper_layout": (
        TOKENS + "/og.layer_0/lk.kda_conv/pt.layout/pad",
        ("layer_0", "kda_conv.layout")),
    "conv_remainder": (
        TOKENS + "/og.layer_0/lk.kda_conv/concatenate",
        ("layer_0", "kda_conv")),
    "dispatch_gather": (
        TOKENS + "/og.layer_0/lk.moe_route/pt.gather/gather",
        ("layer_0", "moe_route.gather")),
    "dense_product": (
        "jit(sample)/lk.update/precompute/og.layer_3/lk.kda_proj/pt.matmul/"
        "dot_general", ("layer_3", "kda_proj.matmul")),
    "kernel_in_the_x_unet": (
        MODEL + "/og.XUNetBlock_2/XUNetBlock_2/AttnBlock_1/lk.attn/"
        "AttnLayer_0/pt.kernel/flash_fwd/pallas_call",
        ("XUNetBlock_2", "attn.kernel")),
    "remainder_of_a_kind": (
        TOKENS + "/og.layer_1/lk.mla_proj/rsqrt", ("layer_1", "mla_proj")),
    # the innermost part holds
    "innermost_part_wins": (
        TOKENS + "/og.layer_1/lk.mla_core/pt.layout/pt.kernel/flash_fwd",
        ("layer_1", "mla_core.kernel")),
    # the one kernel that is not `kernel`: the combine is the row gather
    "the_combine_kernel_is_its_kinds_gather": (
        TOKENS + "/og.layer_1/lk.moe_experts/pt.gather/moe_combine",
        ("layer_1", "moe_experts.gather")),
    "the_combine_kernels_interpreted_body": (
        TOKENS + "/og.layer_1/lk.moe_experts/pt.gather/moe_combine/while/"
        "body/cond/branch_1_fun/mul", ("layer_1", "moe_experts.gather")),
    # a part outside its kind is ignored
    "part_before_its_kind": (
        TOKENS + "/og.layer_1/pt.matmul/lk.mla_proj/add",
        ("layer_1", "mla_proj")),
    "part_of_an_outer_kind_the_inner_kind_took": (
        TOKENS + "/og.layer_0/lk.moe_route/pt.gather/lk.moe_experts/mul",
        ("layer_0", "moe_experts")),
    "part_with_no_kind_in_a_block": (
        TOKENS + "/og.final/pt.matmul/dot_general", ("final", "other")),
    "part_with_no_scope_of_the_program": (
        "jit(f)/pt.matmul/dot_general", ("", "unattributed")),
    "reduction_body_of_a_part": (
        "pt.layout/reduce_sum", ("", "unattributed")),
    # `pose` wins and takes no part
    "pose_takes_no_part": (
        "jit(sample)/lk.update/precompute/og.prelude/lk.pose/lk.patch/"
        "pt.matmul/dot_general", ("prelude", "pose")),
    # a part from outside an `og.` block does not reach in
    "outer_part_does_not_reach_into_a_block": (
        "jit(sample)/lk.update/pt.layout/while/body/og.layer_0/"
        "lk.mla_proj/mul", ("layer_0", "mla_proj")),
    "outer_part_and_an_unstamped_instruction": (
        "jit(sample)/lk.update/pt.layout/while/body/og.layer_0/add",
        ("layer_0", "other")),
    "part_of_the_samplers_own_kind": (
        "jit(sample)/lk.update/pt.layout/transpose", ("", "update.layout")),
    # a part outside the vocabulary is no part
    "unknown_part": (
        TOKENS + "/og.layer_1/lk.mla_proj/pt.bogus/add",
        ("layer_1", "mla_proj")),
    # of `;`-joined paths the first holds
    "merged_instructions": (
        TOKENS + "/og.layer_1/lk.mla_proj/pt.matmul/dot_general;"
        + TOKENS + "/og.layer_1/lk.mla_proj/add",
        ("layer_1", "mla_proj.matmul")),
}


@pytest.mark.parametrize("case", sorted(PART_PATHS))
def test_layer_part_of_rules(case):
    path, want = PART_PATHS[case]
    assert layer_part_of(path) == want
    # summing a kind's keys over its parts gives layer_of's kind
    block, key = want
    assert layer_of(path) == (block, key.split(".")[0])


@pytest.mark.parametrize("case", sorted(PATHS))
def test_layer_part_of_is_layer_of_where_no_part_is_stamped(case):
    path, want = PATHS[case]
    assert layer_part_of(path) == want


def _tiny_sampler():
    # With the attention kernel (through the Pallas interpreter here), as
    # on the chip: its wrapper's `pt.` stamps are part of what is tested.
    cfg = ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                      attn_resolutions=(8,), attn_heads=2, dropout=0.0,
                      use_flash_attention=True)
    model = XUNet(cfg)
    raw = make_example_batch(batch_size=2, sidelength=16)
    cond = {k: jnp.asarray(raw[k]) for k in ("x", "R1", "t1", "R2", "t2",
                                             "K")}
    batch = dict(cond, z=jnp.asarray(raw["target"]),
                 logsnr=jnp.zeros((2,)))
    params = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, batch,
                           cond_mask=jnp.ones((2,)), train=False))["params"]
    dcfg = DiffusionConfig(timesteps=16, sample_timesteps=4,
                           guidance_weight=3.0)
    sampler = make_sampler(model, sampling_schedule(dcfg), dcfg,
                           trajectory_every=1)
    return cfg, sampler, params, cond


@pytest.fixture(scope="module")
def compiled_sampler():
    """The compiled trajectory sampler's text (the benchmark cell's program
    at rehearsal size). The persistent cache's key leaves metadata out,
    so it would hand back an executable compiled under an older
    stamping: for this compile the metadata is part of the key."""
    cfg, sampler, params, cond = _tiny_sampler()
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        text = sampler.lower(
            params, jax.ShapeDtypeStruct((2,), jnp.uint32),
            cond).compile().as_text()
    finally:
        jax.config.update(flag, before)
    return cfg, text


@pytest.fixture(scope="module")
def sampler_paths(compiled_sampler):
    """Every `op_name` of the compiled trajectory sampler."""
    cfg, text = compiled_sampler
    paths = sorted(set(re.findall(r'op_name="([^"]*)"', text)))
    assert len(paths) > 200
    return cfg, paths


def test_compiled_sampler_model_paths_all_have_a_block(sampler_paths):
    cfg, paths = sampler_paths
    labels = {label for label, _ in op_groups(cfg)}
    model = [p for p in paths if "XUNet/" in p.split(";")[0]]
    assert len(model) > 100
    seen = set()
    for p in model:
        block, kind = layer_of(p)
        assert kind != "unattributed", p
        assert block in labels, p
        seen.add(block)
    assert seen == labels  # every pipeline op left instructions behind


def test_compiled_sampler_kinds_are_the_vocabulary(sampler_paths):
    _, paths = sampler_paths
    stamps = {s for p in paths for s in re.split(r"[/();]", p)
              if s.startswith("lk.")}
    assert stamps == {"lk." + k for k in LAYER_KINDS}
    kinds = {layer_of(p)[1] for p in paths}
    assert set(LAYER_KINDS) <= kinds <= set(LAYER_KINDS) | {
        "other", "unattributed"}


def test_compiled_sampler_outside_the_model_is_update_or_pose(
        sampler_paths):
    _, paths = sampler_paths
    outside = [p for p in paths if p.startswith("jit(sample)/")
               and "XUNet/" not in p.split(";")[0]]
    assert len(outside) > 50
    for p in outside:
        assert layer_of(p)[1] in ("update", "pose"), p
    assert any("ConditioningProcessor" in p and layer_of(p)[1] == "pose"
               for p in outside)


def test_compiled_sampler_other_is_small(sampler_paths):
    """`other` is what the model's op loop does between modules (frame
    stacking, the skip concatenation, a cast): a handful of paths."""
    _, paths = sampler_paths
    other = [p for p in paths if layer_of(p)[1] == "other"]
    assert 0 < len(other) <= 0.02 * len(paths), other


def test_each_module_call_is_stamped_once(sampler_paths):
    """A module stamps its own kind once: no path carries the same stamp
    twice, and at most three (`lk.update` around the call, `lk.pose`
    around the pose path, one leaf's kind)."""
    _, paths = sampler_paths
    for p in paths:
        for part in p.split(";"):
            stamps = [s for s in re.split(r"[/()]", part)
                      if s.startswith("lk.")]
            assert len(stamps) == len(set(stamps)) <= 3, part
            assert len(stamps) <= 2 or "lk.pose" in stamps, part


# The compiled toy sampler of every denoiser the benchmark has a cell of:
# the X-UNet above, and the token family's seven trunks at the sizes their
# cells rehearse at (benchmarks/traffic/<traffic>.json, `rehearse`).
TRUNKS = {"ms4_denoiser128": "sample_scan_tokens",
          "st21_denoiser256": "sample_scan_swa",
          "kl48_denoiser256": "sample_scan_kda",
          "p4f_denoiser256": "sample_scan_ssm",
          "oh7_denoiser256": "sample_scan_gdn",
          "lcf_denoiser256": "sample_scan_scmoe",
          "lgs_denoiser256": "sample_scan_headmix"}
KERNELS = ("flash_fwd", "gmm", "kda_fwd", "ssm_fwd", "short_conv_fwd",
           "gdn_fwd", "head_norm_fwd")
# The parts each compiled sampler must show (it may show more: the
# wrappers' own `layout` under `moe_experts` and `kda_core`). A `layout`
# of `flash_fwd` is listed where the toy size leaves something under the
# stamp — a token axis padded to its block, the windowed calls' slabs
# joined —: since PR 41 the wrapper transposes nothing, and the fourth
# trunk's cross layers, whose operands need no pad, show none.
PARTS_SEEN = {
    "x_unet": {"attn.kernel", "attn.layout"},
    "ms4_denoiser128": {
        "mla_core.kernel", "mla_core.layout", "mla_proj.matmul",
        "moe_route.matmul", "moe_route.gather", "moe_experts.kernel",
        "moe_experts.gather", "moe_shared.matmul", "patch.matmul",
        "emb.matmul"},
    "st21_denoiser256": {
        "attn_window.kernel", "attn_window.layout", "attn_full.kernel",
        "attn_full.layout", "gqa_proj.matmul", "moe_route.matmul",
        "moe_route.gather", "moe_experts.kernel", "moe_experts.gather",
        "patch.matmul", "emb.matmul"},
    "kl48_denoiser256": {
        "kda_core.kernel", "kda_conv.kernel", "kda_conv.layout",
        "kda_proj.matmul", "kda_proj.kernel", "mla_core.kernel",
        "mla_core.layout", "mla_proj.matmul", "dense_mlp.matmul",
        "moe_route.matmul", "moe_route.gather", "moe_experts.kernel",
        "moe_experts.gather", "moe_shared.matmul", "patch.matmul",
        "emb.matmul"},
    "p4f_denoiser256": {
        "ssm_core.kernel", "ssm_core.layout", "ssm_conv.kernel",
        "ssm_conv.layout", "ssm_proj.matmul",
        "attn_window.kernel", "attn_window.layout", "attn_full.kernel",
        "attn_full.layout", "attn_cross.kernel",
        "gqa_proj.matmul", "gmu.matmul", "dense_mlp.matmul",
        "patch.matmul", "emb.matmul"},
    "oh7_denoiser256": {
        "gdn_core.kernel", "gdn_core.layout", "gdn_conv.kernel",
        "gdn_conv.layout",
        "gdn_proj.matmul", "gdn_proj.kernel", "attn_full.kernel",
        "attn_full.layout",
        "gqa_proj.matmul", "dense_mlp.matmul", "patch.matmul",
        "emb.matmul"},
    "lcf_denoiser256": {
        "mla_core.kernel", "mla_core.layout", "mla_proj.matmul",
        "dense_mlp.matmul", "moe_route.matmul", "moe_route.gather",
        "moe_experts.kernel", "moe_experts.gather", "patch.matmul",
        "emb.matmul"},
    "lgs_denoiser256": {
        "attn_window.kernel", "attn_window.layout", "attn_full.kernel",
        "attn_full.layout", "gqa_proj.matmul", "dense_mlp.matmul",
        "moe_route.matmul", "moe_route.gather", "moe_experts.kernel",
        "moe_experts.gather", "moe_shared.matmul", "patch.matmul",
        "emb.matmul"},
}
# What the X-UNet's op loop does between modules stays `other` (the frame
# stacking, the skip concatenation, a cast: `paper256.sample_scan` reads
# 0.89 ms a call there); every instruction of a token trunk's block has a
# kind.
OTHER_ALLOWED = {"x_unet": re.compile(
    r"/og\.\w+/(\w+/)*(concatenate|convert_element_type|reshape|squeeze|"
    r"broadcast_in_dim|slice|dynamic_slice|transpose)$")}


def _token_sampler_text(preset):
    with open(os.path.join(BENCH, "traffic", TRUNKS[preset] + ".json")) as fh:
        over = json.load(fh)["rehearse"]["overrides"]
    cfg = get_preset(preset).override(**dict(over, **{
        "diffusion.sample_timesteps": 4, "diffusion.guidance_weight": 3.0,
        "model.use_flash_attention": True})).validate()
    model = build_denoiser(cfg.model)
    side, B = cfg.data.img_sidelength, 2

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    cond = {"x": spec(B, side, side, 3), "R1": spec(B, 3, 3),
            "t1": spec(B, 3), "R2": spec(B, 3, 3), "t2": spec(B, 3),
            "K": spec(B, 3, 3)}
    params = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}))["params"]
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, 4),
                           cfg.diffusion, trajectory_every=1)
    # As `compiled_sampler`: a cached executable carries the scopes of
    # whatever compiled first.
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return jax.jit(sampler).lower(
            params, jax.ShapeDtypeStruct((2,), jnp.uint32),
            cond).compile().as_text()
    finally:
        jax.config.update(flag, before)


@pytest.fixture(scope="module", params=["x_unet"] + sorted(TRUNKS))
def stamped_paths(request, compiled_sampler):
    """(which sampler, every scope path of its compiled text that starts
    in the program): of `;`-joined paths each on its own. A reduction's
    body carries a path relative to its caller (`pt.layout/reduce_sum`)
    and is no instruction of its own."""
    text = compiled_sampler[1] if request.param == "x_unet" \
        else _token_sampler_text(request.param)
    paths = sorted({part for p in re.findall(r'op_name="([^"]*)"', text)
                    for part in p.split(";") if part.startswith("jit(")})
    assert len(paths) > 200
    return request.param, paths


def test_every_part_sits_under_a_kind_and_none_is_doubled(stamped_paths):
    which, paths = stamped_paths
    seen = set()
    for p in paths:
        segs = [s for s in re.split(r"[/()]", p) if s]
        parts = [i for i, s in enumerate(segs) if s.startswith("pt.")]
        if not parts:
            continue
        names = [segs[i] for i in parts]
        assert set(names) <= {"pt." + n for n in LAYER_PARTS}, p
        assert len(names) == len(set(names)) == 1, p
        kinds = [i for i, s in enumerate(segs) if s.startswith("lk.")]
        blocks = [i for i, s in enumerate(segs) if s.startswith("og.")]
        assert kinds and blocks, p
        assert blocks[-1] < kinds[-1] < parts[0], p
        seen.add(layer_part_of(p)[1])
    assert PARTS_SEEN[which] <= {k for k in seen if "." in k}


def test_each_kernel_call_is_stamped_kernel_and_nothing_else_is(
        stamped_paths):
    """A kernel's instructions (one custom call on the chip; the
    interpreter's loop here) lie under `pt.kernel/<the kernel's name>`,
    and `pt.kernel` holds nothing but them."""
    which, paths = stamped_paths
    calls = [p for p in paths if re.search(
        r"/(%s)(/|$)" % "|".join(KERNELS), p)]
    assert calls
    for p in calls:
        assert re.search(r"/pt\.kernel/(%s)(/|$)" % "|".join(KERNELS), p), p
        assert layer_part_of(p)[1].endswith(".kernel"), p
    assert {p for p in paths if "/pt.kernel" in p} == set(calls)


def test_the_combine_kernel_is_stamped_gather(stamped_paths):
    """The one kernel that is not `kernel` (models/vocab.py): the expert
    layer's combine, `moe_combine`, is the row gather from expert order to
    token order, so its call lies under `lk.moe_experts/pt.gather` — the
    part that read XLA's gathers goes on reading the combine — and the
    X-UNet and the trunks without expert layers have none."""
    which, paths = stamped_paths
    calls = [p for p in paths if re.search(r"/moe_combine(/|$)", p)]
    assert bool(calls) == (which not in (
        "x_unet", "p4f_denoiser256", "oh7_denoiser256"))
    for p in calls:
        assert re.search(r"/lk\.moe_experts/(jit\(_combine\)/)?pt\.gather/"
                         r"moe_combine(/|$)", p), p
        assert layer_part_of(p)[1] == "moe_experts.gather", p
    under = {p for p in paths if "/lk.moe_experts/" in p and "/pt.gather" in p}
    assert under == set(calls)


def test_summing_parts_gives_the_kinds(stamped_paths):
    _, paths = stamped_paths
    for p in paths:
        block, key = layer_part_of(p)
        kind, _, part = key.partition(".")
        assert (block, kind) == layer_of(p), p
        assert part in ("",) + LAYER_PARTS, p


def test_no_instruction_of_a_block_is_other_but_the_named(stamped_paths):
    which, paths = stamped_paths
    other = [p for p in paths if layer_of(p)[1] == "other"]
    allowed = OTHER_ALLOWED.get(which)
    assert [p for p in other if not (allowed and allowed.search(p))] == []
    assert bool(other) == (which in OTHER_ALLOWED)


# The benchmark's readers of the parts (benchmarks/layer_metrics/).
SCOPED_CAPTURE = os.path.join(BENCH, "tests", "fixtures",
                              "chip_trace_scoped.xplane.pb")


def _fixture_parts(path):
    """A vocabulary function over the recorded capture's scopes (a matmul
    under `og.block_a/lk.conv`, a norm's four fusions under
    `og.block_b/lk.gn`, a named kernel under `og.block_b/lk.attn`): the
    kernel's call is its kind's `kernel`, the product its kind's `matmul`,
    and of the norm's fusions the division stands in for a `layout`."""
    block, kind = layer_of(path)
    if kind == "attn" and path.endswith("scoped_fixture_scale/pallas_call"):
        return block, "attn.kernel"
    if kind == "conv" and path.endswith("dot_general"):
        return block, "conv.matmul"
    if kind == "gn" and path.endswith("/div"):
        return block, "gn.layout"
    return block, kind


@pytest.fixture()
def part_reader(tmp_path, monkeypatch):
    import harness
    import scope_reduce
    import stamped_time
    import novel_view_synthesis_3d_tpu.models.xunet as xunet

    monkeypatch.setattr(stamped_time, "_capture",
                        lambda: (str(tmp_path), SCOPED_CAPTURE))
    monkeypatch.setattr(xunet, "layer_part_of", _fixture_parts)
    scope_reduce.reduce.cache_clear()
    mod = harness.load_module(os.path.join(
        BENCH, "layer_metrics", "part_ms_per_call.py"), "part_ms_per_call")
    kinds = scope_reduce.reduce(SCOPED_CAPTURE, layer_of)
    return mod, kinds, {"busy_s": kinds["total_s"]}, tmp_path


@pytest.mark.parametrize("variant,kind,share", [
    ("attn.kernel", "attn", "all"), ("conv.matmul", "conv", "all"),
    ("gn.layout", "gn", "some"), ("attn.layout", "attn", "none")])
def test_part_reader_gives_ms_per_call_under_a_part(part_reader, variant,
                                                    kind, share):
    mod, kinds, trace, out_dir = part_reader
    got = mod.compute([], trace, {"variant": variant})
    of_kind = 1e3 * kinds["by_kind_s"][kind] / kinds["module_runs"]
    if share == "none":     # no such stamp in the capture
        assert got == 0.0
    elif share == "all":    # the kind is that one instruction
        assert got == pytest.approx(of_kind)
    else:                   # a part, and a remainder beside it
        assert 0.0 < got < of_kind
    kept = json.load(open(out_dir / "parts.json"))
    assert kept["module_runs"] == 3
    assert sum(kept["ms_per_call"].values()) == pytest.approx(
        1e3 * trace["busy_s"] / 3, rel=1e-3)
    for k in ("attn", "conv", "gn"):    # Σ parts = layer_of's kinds
        assert sum(v for key, v in kept["ms_per_call"].items()
                   if key.split(".")[0] == k) == pytest.approx(
            1e3 * kinds["by_kind_s"][k] / 3)


@pytest.mark.parametrize("missing", [
    "no_trace", "no_capture", "no_layer_part_of"])
def test_part_reader_reads_nothing_where_something_is_missing(
        part_reader, monkeypatch, missing):
    mod, _, trace, _ = part_reader
    if missing == "no_trace":           # --trace 0
        trace = None
    elif missing == "no_capture":
        import stamped_time
        monkeypatch.setattr(stamped_time, "_capture", lambda: None)
    else:                               # the parent commit's program
        import novel_view_synthesis_3d_tpu.models.xunet as xunet
        monkeypatch.delattr(xunet, "layer_part_of")
    assert mod.compute([], trace, {"variant": "attn.kernel"}) is None


@pytest.mark.parametrize("variant,busy,match", [
    ("attn.kernal", 1.0, "no part 'attn.kernal'"),
    ("atn.kernel", 1.0, "no part 'atn.kernel'"),
    ("attn.kernel", 2.0, "not this run's capture")])
def test_part_reader_refuses_a_misspelt_part_and_a_foreign_capture(
        part_reader, variant, busy, match):
    mod, _, trace, _ = part_reader
    with pytest.raises(ValueError, match=match):
        mod.compute([], {"busy_s": busy * trace["busy_s"]},
                    {"variant": variant})


@pytest.mark.parametrize("counters,want", [
    # one layer: a full tile, a lone row, an absent expert, 2.34 tiles
    ({"routing_counts": [[128, 1, 0, 300]]}, (128 + 128 + 0 + 384) / 429),
    # two layers add up before the division
    ({"routing_counts": [[128, 128], [64, 64]]}, (256 + 256) / 384),
    # every expert at whole tiles: no pad row
    ({"routing_counts": [[256, 128, 384]]}, 1.0),
    # counted over 4 rows where a step of the timed program has 2 x 1
    ({"routing_counts": [[256, 2, 0, 600]], "counted_rows": 4, "views": 1},
     (128 + 128 + 0 + 384) / 429),
    ({"routing_counts": [[0, 0]]}, None),
    ({}, None)])
def test_moe_rows_visited_over_held_on_hand_made_counts(counters, want):
    import harness

    got = harness.layer_reader("moe_rows_visited_over_held", BENCH)(
        [], None, counters)
    assert got == (want if want is None else pytest.approx(want))


_SIZES = {"side": 16, "patch_size": 4, "num_experts_per_tok": 4}


@pytest.mark.parametrize("counters,want", [
    # one layer, one pass of 2 x 1 rows of 16 tokens: 32 held of 128 choices
    ({"routing_counts": [[20, 12, 0, 0]], "counted_rows": 2, "views": 1,
      "sizes": _SIZES}, 32 / 128),
    # two layers add up before the division
    ({"routing_counts": [[32, 32, 32, 32], [0, 0, 0, 64]],
      "counted_rows": 2, "views": 1, "sizes": _SIZES}, (128 + 64) / 256),
    # counted over 4 rows where a step of the timed program has 2 x 1
    ({"routing_counts": [[40, 24, 0, 0]], "counted_rows": 4, "views": 1,
      "sizes": _SIZES}, 32 / 128),
    # every choice held
    ({"routing_counts": [[64, 64, 0, 128]], "counted_rows": 4, "views": 2,
      "sizes": _SIZES}, 1.0),
    ({"routing_counts": [[20, 12]], "sizes": _SIZES}, None),
    ({"routing_counts": [[20, 12]], "counted_rows": 2, "views": 1}, None),
    ({}, None)])
def test_moe_combine_fetched_over_choices_on_hand_made_counts(counters,
                                                              want):
    import harness

    got = harness.layer_reader("moe_combine_fetched_over_choices", BENCH)(
        [], None, counters)
    assert got == (want if want is None else pytest.approx(want))


def test_loose_by_category_splits_what_no_kind_reaches():
    """benchmarks/tools/loose_by_category.py: the whole of a capture's
    `other` and `unattributed` by `hlo_category`, adding up to the kinds'
    own reading."""
    import harness
    import scope_reduce

    tool = harness.load_module(os.path.join(
        BENCH, "tools", "loose_by_category.py"), "loose_by_category")
    got = tool.split(SCOPED_CAPTURE, layer_of)
    kinds = scope_reduce.reduce(SCOPED_CAPTURE, layer_of)
    assert set(got) == {"unattributed"}     # every block's work has a kind
    cats = got["unattributed"]
    assert list(cats)[0] == "convolution fusion"   # heaviest first
    assert cats["convolution fusion"]["instructions"][0][:2] == [
        "convolution_tanh_fusion", 1]
    assert cats["copy-done"]["instructions"][0][:2] == ["copy-done", 2]
    assert {"while", "copy-start"} <= set(cats)
    assert sum(c["ms_per_call"] for c in cats.values()) == pytest.approx(
        1e3 * kinds["by_kind_s"]["unattributed"] / kinds["module_runs"])


def test_film_projects_the_unconditional_rows_once_a_frame(
        compiled_sampler):
    """In the compiled sampler the rows entering FiLM's matmuls are, per
    site, the conditional half's B·F·H·W pixels plus the unconditional
    half's B·F frames — not 2·B·F·H·W. (XLA may share one `swish(level
    embedding)` between the sites of a level; each site keeps its own
    matmuls, whose result's leading dimensions are the rows.)"""
    cfg, text = compiled_sampler
    B, F, side = 2, 2, 16
    rows = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                     r"(?:dot|convolution)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if not (m and name and name.group(1).endswith(
                "FiLM_0/lk.emb/Dense_0/dot_general")):
            continue
        block, kind = layer_of(name.group(1))
        assert kind == "emb"
        dims = [int(d) for d in m.group(1).split(",")]
        rows.setdefault(block, []).append(int(np.prod(dims[:-1])))
    assert set(rows) == {label for label, _ in op_groups(cfg)} - {
        "prelude", "final"}
    per_level = [side // 2 ** lvl for lvl in range(len(cfg.ch_mult))]
    for block, parts in rows.items():
        assert min(parts) == B * F, (block, parts)
        assert sum(parts) in {B * F * p * p + B * F for p in per_level}, (
            block, parts)


def test_film_collapse_is_said_once_with_its_counts(capfd):
    """Tracing the sampler says once, on stderr, how many FiLM sites
    project how many rows per pixel and how many per frame."""
    from novel_view_synthesis_3d_tpu.utils.profiling import reset_log_once

    cfg, sampler, params, cond = _tiny_sampler()
    reset_log_once()
    capfd.readouterr()
    for _ in range(2):
        sampler.lower(params, jax.ShapeDtypeStruct((2,), jnp.uint32), cond)
    said = [line for line in capfd.readouterr().err.splitlines()
            if "FiLM sites" in line]
    # 9 ResnetBlocks; B·F·H·W = 2·2·16² at level 0 (4 sites) and 2·2·8²
    # at level 1 (5 sites); B·F = 4 at each.
    sites, per_pixel, per_frame = 9, 4 * 1024 + 5 * 256, 9 * 4
    assert len(said) == 1, said
    assert (f"{sites} FiLM sites project {per_pixel} rows per pixel and "
            f"{per_frame} rows per frame") in said[0]


_NO_PROTOBUF = """
import sys
sys.path[:0] = [{root!r}, {root!r} + "/benchmarks"]
{imports}
bad = sorted(m for m in sys.modules
             if m.split(".")[0] in ("tensorflow", "xprof", "tensorboard",
                                    "tensorboard_plugin_profile", "tsl")
             or m == "google.protobuf" or m.startswith("google.protobuf."))
assert not bad, bad
print("clean")
"""


@pytest.mark.parametrize("what,imports", [
    ("the_program", "import novel_view_synthesis_3d_tpu\n"
                    "import novel_view_synthesis_3d_tpu.models.xunet\n"
                    "import novel_view_synthesis_3d_tpu.sample.ddpm"),
    ("the_benchmark_untraced",
     "import run, harness, trace_reduce\n"
     "harness.load_cell('paper256.sample_scan')"),
    ("the_benchmark_traced_readers",
     "import harness, scope_reduce\n"
     "harness.layer_reader('layer_ms_per_call.gn')"),
])
def test_no_protobuf_module_is_imported(what, imports):
    """`setup_s` is an end-to-end metric: neither the program nor the
    benchmark's path may pay TensorFlow's or a protobuf runtime's import."""
    out = subprocess.run(
        [sys.executable, "-c",
         _NO_PROTOBUF.format(root=ROOT, imports=imports)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("clean")

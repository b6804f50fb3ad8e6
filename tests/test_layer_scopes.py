"""The layer names the program stamps: `og.<block>` / `lk.<kind>` scopes
and what `models/xunet.layer_of` makes of a scope path, the names of the
Pallas kernels' instructions aside (tests/test_tpu_compile.py), and what
importing the program must not load.

The stamps are HLO metadata: that they change no number is what the
sampler and train-step goldens (tests/test_sampler.py,
tests/test_trajectory.py, tests/test_train_step.py) show by passing
untouched.
"""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.config import DiffusionConfig, ModelConfig
from novel_view_synthesis_3d_tpu.data.synthetic import make_example_batch
from novel_view_synthesis_3d_tpu.diffusion.schedules import sampling_schedule
from novel_view_synthesis_3d_tpu.models.xunet import (
    XUNET_LAYER_KINDS as LAYER_KINDS, XUNet, layer_of, op_groups)
from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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


def _tiny_sampler():
    cfg = ModelConfig(ch=32, ch_mult=(1, 2), emb_ch=32, num_res_blocks=1,
                      attn_resolutions=(8,), attn_heads=2, dropout=0.0)
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

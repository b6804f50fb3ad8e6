"""X-UNet: pose-conditional two(+)-frame diffusion UNet (3DiM).

Clean-room TPU-first reimplementation of the architecture at
/root/reference/model/xunet.py:142-280, generalized so that:

  - every hyperparameter is a real config field (the reference freezes
    `ch_mult`/`attn_resolutions` as class attributes — SURVEY.md §2.2 quirk);
  - the frame axis F = num_cond_frames + 1 is free (reference hardcodes 2);
    conditioning frames come first, the noised target frame is LAST, and the
    model returns the target frame's noise prediction (for F=2 this matches
    the reference's `[:, 1]` selection at xunet.py:280);
  - camera rays come from models/rays.py (pure jnp) instead of visu3d;
  - compute dtype / remat are configurable for TPU memory/throughput.

Batch contract (canonical keys, reference train.py:23-34):
  x      (B, H, W, 3) or (B, Fc, H, W, 3)   clean conditioning view(s), [-1,1]
  z      (B, H, W, 3)                        noised target view
  logsnr (B,)
  R1, t1 (B, 3, 3) / (B, 3) or (B, Fc, ...)  cond camera cam→world pose(s)
  R2, t2 (B, 3, 3) / (B, 3)                  target camera pose
  K      (B, 3, 3)                           shared pinhole intrinsics

Inside, between the stem and the selection of the target frame, the
activation is (B·F, H, W, C) — rows batch-major, a sample's F frames
adjacent — and so is everything added to it (each level's pose embedding,
the logsnr embedding repeated per frame): models/layers.py says why. What
crosses the boundary keeps its (B, F, …) shape — `x`, `cond_feats`,
`pose_embs` (precompute_pose_embs, precompute_guidance_pose_embs) — and is
flattened once where it enters (`_rows`).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from novel_view_synthesis_3d_tpu.config import ModelConfig
from novel_view_synthesis_3d_tpu.models.layers import (
    FrameConv,
    GroupNorm,
    ResnetBlock,
    XUNetBlock,
    nonlinearity,
)
from novel_view_synthesis_3d_tpu.models.rays import camera_rays
# The scope vocabulary lives in models/vocab.py for every family; these
# names stay importable here (benchmarks/layer_metrics reads them here).
from novel_view_synthesis_3d_tpu.models.vocab import (  # noqa: F401
    LAYER_KINDS,
    LAYER_PARTS,
    XUNET_LAYER_KINDS,
    layer_of,
    layer_part_of,
)
from novel_view_synthesis_3d_tpu.ops.flash_attention import resolve_flash
from novel_view_synthesis_3d_tpu.ops.posenc import posenc_ddpm, posenc_nerf
from novel_view_synthesis_3d_tpu.utils.profiling import log_once


def _as_frames(arr: jnp.ndarray, frame_rank: int) -> jnp.ndarray:
    """Insert a singleton frame axis after batch if not already present."""
    if arr.ndim == frame_rank:
        return arr[:, None]
    return arr


def _rows(arr: jnp.ndarray) -> jnp.ndarray:
    """(B, F, …) → (B·F, …): the form the network carries."""
    return arr.reshape((-1,) + arr.shape[2:])


def _frames(arr: jnp.ndarray, F: int) -> jnp.ndarray:
    """(B·F, …) → (B, F, …): the form that crosses the model's boundary."""
    return arr.reshape((-1, F) + arr.shape[1:])


def _num_frames(batch: dict) -> int:
    """F = conditioning frames + the target, from the batch's shapes."""
    if "cond_feats" in batch:
        return batch["cond_feats"].shape[1] + 1
    x = batch["x"]
    return (1 if x.ndim == 4 else x.shape[1]) + 1


def _named_remat(policy=None):
    """nn.remat(XUNetBlock) renamed back to 'XUNetBlock'.

    Flax derives parameter paths from the class name, and the lifted
    transform returns a class called 'CheckpointXUNetBlock' — which would
    silently fork the param tree ('CheckpointXUNetBlock_0' vs
    'XUNetBlock_0') and make checkpoints non-portable between remat
    settings (train at 256px with remat, sample without). Renaming the
    wrapped class keeps one layout for every mode. (A checkpoint written by
    a pre-rename build with remat on can be migrated by renaming its
    'CheckpointXUNetBlock_N' keys to 'XUNetBlock_N'.)
    """
    cls = nn.remat(XUNetBlock, policy=policy)
    cls.__name__ = "XUNetBlock"
    cls.__qualname__ = "XUNetBlock"
    return cls


def _remat_block(remat):
    """Resolve config.model.remat → the (possibly rematerialized) block class.

    False = no remat. True / 'full' = recompute everything in the backward
    pass (smallest memory, most recompute FLOPs). 'dots' = save matmul/conv
    outputs, recompute only the elementwise chains between them
    (jax.checkpoint_policies.dots_saveable) — the bandwidth-flops middle
    ground for an HBM-bound model: GroupNorm/swish/FiLM intermediates are
    never written to HBM, while no conv runs twice.
    """
    if remat in (False, "none"):
        return XUNetBlock
    if remat in (True, "full"):
        return _named_remat()
    if remat == "dots":
        return _named_remat(jax.checkpoint_policies.dots_saveable)
    raise ValueError(
        f"model.remat must be False, True, 'none', 'full', or 'dots'; "
        f"got {remat!r}")




class ConditioningProcessor(nn.Module):
    """logsnr + camera-pose conditioning → per-level FiLM embeddings.

    Reference: model/xunet.py:142-203. Produces `logsnr_emb` (B, emb_ch) and
    one (B·F, H/2ˡ, W/2ˡ, emb_ch) pose embedding per UNet resolution level
    (precomputed ones arrive as (B, F, …), a pair's parts each, and leave
    in the same rows-flattened form).
    """

    emb_ch: int
    num_resolutions: int
    use_pos_emb: bool = False
    use_ref_pose_emb: bool = False
    # Scene-category conditioning (model.num_classes): > 0 adds a
    # ZERO-INIT (num_classes, emb_ch) embedding table looked up by the
    # batch's int32 `category` ids and added into logsnr_emb, behind the
    # same CFG cond-drop mask as the pose embedding. Zero init makes the
    # table a numeric no-op at creation, which is what lets checkpoints
    # trained at num_classes=0 load into a num_classes>0 model by
    # splicing the fresh zero table (train/ladder.py).
    num_classes: int = 0
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, batch: dict, cond_mask: jnp.ndarray):
        z = batch["z"]
        B, H, W, _ = z.shape
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)

        with jax.named_scope("lk.emb"):
            # --- logsnr embedding (reference xunet.py:152-157) ---
            # clip ±20, squash to (0,1) via 2·atan(e^{−λ/2})/π, DDPM
            # sinusoid (max_time=1 ⇒ internal ×1000), then Dense →
            # Dense∘swish.
            logsnr = jnp.clip(batch["logsnr"], -20.0, 20.0)
            logsnr = 2.0 * jnp.arctan(jnp.exp(-logsnr / 2.0)) / np.pi
            logsnr_emb = posenc_ddpm(logsnr, emb_ch=self.emb_ch,
                                     max_time=1.0, dtype=self.dtype)
            logsnr_emb = nn.Dense(self.emb_ch, **kw)(logsnr_emb)
            logsnr_emb = nn.Dense(self.emb_ch, **kw)(
                nonlinearity(logsnr_emb))

        # --- scene-category embedding (data/corpus.py mixed batches) ---
        # Rides the logsnr channel so it reaches every FiLM site without
        # touching the pose-embedding shapes, and sits BEFORE the
        # precomputed-pose early return so the serving/sampling fast
        # paths stay category-aware. A batch without a `category` field
        # conditions on nothing (zero vector) — old single-corpus batches
        # are numerically unchanged even with the table present.
        if self.num_classes > 0:
            table = self.param("category_emb", nn.initializers.zeros,
                               (self.num_classes, self.emb_ch),
                               self.param_dtype)
            if "category" in batch:
                cat_emb = jnp.take(table.astype(self.dtype),
                                   batch["category"], axis=0)
                if cond_mask is not None:
                    # CFG cond-drop: the category drops with the pose
                    # conditioning (one mask, one uncond branch) so
                    # guidance and distillation survive unchanged.
                    assert cond_mask.shape == (B,), cond_mask.shape
                    cat_emb = jnp.where(cond_mask[:, None], cat_emb,
                                        jnp.zeros_like(cat_emb))
                logsnr_emb = logsnr_emb + cat_emb

        # Precomputed pose path (sampling): the pose embeddings depend only
        # on the cameras, not on (z_t, logsnr) — a sampler can compute them
        # ONCE and hoist them out of its reverse-process scan instead of
        # re-running rays→posenc→convs every denoising step. The caller
        # must have applied the CFG cond_mask at precompute time (the mask
        # zeroes the pose embedding, xunet.py:174-179 in the reference).
        # init() never takes this path, so the param tree is unchanged.
        if "pose_embs" in batch:
            return logsnr_emb, jax.tree.map(_rows, list(batch["pose_embs"]))

        with jax.named_scope("lk.pose"):
            # --- pose embeddings (reference xunet.py:158-173) ---
            # Stack cond + target cameras on the frame axis, generate world
            # rays, NeRF-posenc origins (deg 15 → 93) and directions
            # (deg 8 → 51), concat → (B, F, H, W, 144).
            R1 = _as_frames(batch["R1"], 3)   # (B, Fc, 3, 3)
            t1 = _as_frames(batch["t1"], 2)   # (B, Fc, 3)
            R = jnp.concatenate([R1, batch["R2"][:, None]], axis=1)
            t = jnp.concatenate([t1, batch["t2"][:, None]], axis=1)
            F = R.shape[1]
            K = jnp.broadcast_to(batch["K"][:, None], (B, F, 3, 3))
            pos, dirs = camera_rays(R, t, K, resolution=(H, W))
            pose_emb = jnp.concatenate(
                [
                    posenc_nerf(pos, min_deg=0, max_deg=15),
                    posenc_nerf(dirs, min_deg=0, max_deg=8),
                ],
                axis=-1,
            ).astype(self.dtype)
            D = pose_emb.shape[-1]

            # Classifier-free guidance: zero the whole pose embedding per
            # sample where cond_mask == 0 (reference xunet.py:174-179).
            assert cond_mask.shape == (B,), cond_mask.shape
            mask = cond_mask[:, None, None, None, None]
            pose_emb = jnp.where(mask, pose_emb, jnp.zeros_like(pose_emb))

            if self.use_pos_emb:
                pos_emb = self.param(
                    "pos_emb",
                    nn.initializers.normal(stddev=1.0 / np.sqrt(D)),
                    (H, W, D), self.param_dtype)
                pose_emb += pos_emb[None, None].astype(self.dtype)

            if self.use_ref_pose_emb:
                # Binary frame-identity embedding: 'first' on frame 0,
                # 'other' on the rest (reference xunet.py:186-194,
                # generalized to F frames).
                init = nn.initializers.normal(stddev=1.0 / np.sqrt(D))
                first = self.param("ref_pose_emb_first", init, (D,),
                                   self.param_dtype)
                other = self.param("ref_pose_emb_other", init, (D,),
                                   self.param_dtype)
                frame_emb = jnp.stack([first] + [other] * (F - 1), axis=0)
                pose_emb += frame_emb[None, :, None, None, :].astype(
                    self.dtype)

            # Per-resolution strided downsampling of the full-res embedding
            # (reference xunet.py:197-202): one conv per level, stride 2ˡ.
            pose_emb = _rows(pose_emb)
            pose_embs = []
            for i_level in range(self.num_resolutions):
                pose_embs.append(FrameConv(
                    self.emb_ch, kernel=3, stride=2 ** i_level,
                    **kw)(pose_emb))
        return logsnr_emb, pose_embs


def precompute_pose_embs(model: "XUNet", params, cond: dict,
                         cond_mask: jnp.ndarray):
    """Per-level pose embeddings for a fixed conditioning layout.

    They are loop-invariant across diffusion steps (cameras don't change
    while denoising), so samplers compute them once here and pass them via
    `batch["pose_embs"]` instead of re-running rays → NeRF posenc →
    per-level downsampling convs inside every scan step. `cond_mask` is
    baked in (CFG zeroing happens at this stage). `cond` needs x/R1/t1/
    R2/t2/K; z/logsnr are synthesized for shape purposes only.

    Every level comes back at full extent, (B, F, H/2ˡ, W/2ˡ, emb), masked
    rows too; a guidance pair's unconditional rows at 1 × 1 extent are
    precompute_guidance_pose_embs's to give.
    """
    cfg = model.config
    x = cond["x"]
    spatial = x.shape[-3:-1]
    B = x.shape[0]
    proc = ConditioningProcessor(
        emb_ch=cfg.emb_ch,
        num_resolutions=len(cfg.ch_mult),
        use_pos_emb=cfg.use_pos_emb,
        use_ref_pose_emb=cfg.use_ref_pose_emb,
        num_classes=cfg.num_classes,
        dtype=jnp.dtype(cfg.dtype),
        param_dtype=jnp.dtype(cfg.param_dtype),
    )
    batch = dict(cond,
                 z=jnp.zeros((B,) + spatial + (x.shape[-1],), x.dtype),
                 logsnr=jnp.zeros((B,)))
    _, pose_embs = proc.apply({"params": params["ConditioningProcessor_0"]},
                              batch, cond_mask)
    F = _num_frames(cond)
    return tuple(_frames(p, F) for p in pose_embs)


def precompute_guidance_pose_embs(model: "XUNet", params, cond: dict):
    """Per-level pose embeddings for a guidance pair, rows [cond…, uncond…],
    with the unconditional rows at 1 × 1 extent — or None where the
    configuration does not admit that.

    The mask zeroes an unconditional row's whole ray encoding, and a
    zero-padded convolution of zeros is its bias at every pixel, borders
    included. With `use_pos_emb` and `use_ref_pose_emb` both off such a
    row's embedding is therefore ONE vector per frame, repeated over
    H × W, and each level comes back as the pair

      (conditional (B, F, H/2ˡ, W/2ˡ, emb), unconditional (B, F, 1, 1, emb))

    where a 1 × 1 extent says "this value at every pixel of the frame".
    The model carries it as such through `level_emb` and FiLM's Dense,
    which projects those rows once a frame instead of once a pixel, and
    broadcasts only where the modulation is applied: the same numbers,
    computed once. The unconditional part is the processor run on masked
    rows of the smallest image every level still has a pixel of.

    With either flag on, an unconditional row is not constant over the
    frame (a learned (H, W, D) table; a non-zero constant that zero
    padding changes at the borders): None, and the caller keeps
    `precompute_pose_embs` of its doubled layout. The choice is a static
    property of the configuration and shows in the shapes.
    """
    cfg = model.config
    blocked = [f for f in ("use_pos_emb", "use_ref_pose_emb")
               if getattr(cfg, f)]
    if blocked:
        log_once(
            ("film_collapse_forbidden",) + tuple(blocked),
            "note: guidance pair keeps full-extent pose embeddings: with "
            f"{' and '.join(blocked)} on, an unconditional row's embedding "
            "is not constant over the frame — every FiLM site projects "
            "both halves per pixel")
        return None
    x = cond["x"]
    B = x.shape[0]
    pose_c = precompute_pose_embs(model, params, cond, jnp.ones((B,)))
    side = 2 ** (len(cfg.ch_mult) - 1)
    small = dict(cond, x=jnp.zeros(
        x.shape[:-3] + (side, side, x.shape[-1]), x.dtype))
    pose_u = precompute_pose_embs(model, params, small, jnp.zeros((B,)))
    return tuple((c, u[:, :, :1, :1]) for c, u in zip(pose_c, pose_u))


def precompute_cond_feats(model: "XUNet", params, cond: dict) -> jnp.ndarray:
    """Stem features of the conditioning frame(s), (B, Fc, H, W, ch).

    The stem FrameConv convolves each frame independently, so the cond
    frames' features never change while the target frame denoises — the
    serving cond cache (sample/service.py) computes them once here and
    passes them via `batch["cond_feats"]`, leaving only the noised
    target frame's conv inside the step program. Unlike the pose
    embeddings these are NOT CFG-masked (the reference feeds the clean
    cond image to both guidance halves — only the pose embedding is
    zeroed), so one tensor serves both halves of a guidance pair.
    """
    cfg = model.config
    x = cond["x"]
    if x.ndim == 4:  # (B,H,W,3) → (B,1,H,W,3)
        x = x[:, None]
    conv = FrameConv(cfg.ch, dtype=jnp.dtype(cfg.dtype),
                     param_dtype=jnp.dtype(cfg.param_dtype))
    return _frames(conv.apply({"params": params["FrameConv_0"]},
                              _rows(x.astype(jnp.dtype(cfg.dtype)))),
                   x.shape[1])


def pipeline_op_specs(cfg: ModelConfig):
    """Static, ordered op list for the XUNet — the pipeline partition unit.

    Each entry is (kind, info) where `kind` selects a body in
    XUNet.__call__ and `info` carries the static metadata INCLUDING the
    explicit flax module name. Names replicate the per-class auto-counter
    flax would have assigned in the monolithic call order, so:
      - the param tree is IDENTICAL to pre-refactor checkpoints, and
      - a stage-sliced execution (ops=(a, b)) creates modules under the
        SAME paths as the full run — which also makes flax's per-path
        dropout-rng folding identical under pipeline execution.
    `param_names` lists the top-level param-tree keys the op owns, so the
    pipeline planner can slice per-stage param subtrees exactly.
    """
    counters: dict = {}

    def nm(cls: str) -> str:
        i = counters.get(cls, 0)
        counters[cls] = i + 1
        return f"{cls}_{i}"

    num_resolutions = len(cfg.ch_mult)
    specs = []
    cond, stem = nm("ConditioningProcessor"), nm("FrameConv")
    specs.append(("prelude", dict(cond=cond, stem=stem,
                                  param_names=(cond, stem))))
    for i_level in range(num_resolutions):
        for _ in range(cfg.num_res_blocks):
            name = nm("XUNetBlock")
            specs.append(("down_block", dict(
                level=i_level, features=cfg.ch * cfg.ch_mult[i_level],
                name=name, param_names=(name,))))
        if i_level != num_resolutions - 1:
            name = nm("ResnetBlock")
            specs.append(("down_trans", dict(level=i_level, name=name,
                                             param_names=(name,))))
    name = nm("XUNetBlock")
    specs.append(("middle", dict(features=cfg.ch * cfg.ch_mult[-1],
                                 name=name, param_names=(name,))))
    for i_level in reversed(range(num_resolutions)):
        for _ in range(cfg.num_res_blocks + 1):
            name = nm("XUNetBlock")
            specs.append(("up_block", dict(
                level=i_level, features=cfg.ch * cfg.ch_mult[i_level],
                name=name, param_names=(name,))))
        if i_level != 0:
            name = nm("ResnetBlock")
            specs.append(("up_trans", dict(level=i_level, name=name,
                                           param_names=(name,))))
    gn, out = nm("GroupNorm"), nm("FrameConv")
    specs.append(("final", dict(gn=gn, out=out, param_names=(gn, out))))
    return specs


def op_groups(cfg: ModelConfig):
    """Ordered (label, param_names) layer groups for the numerics
    observatory (obs/numerics.py) — one group per pipeline op.

    Labels are the op's explicit flax module name (stable across builds
    by construction of pipeline_op_specs), except the multi-module
    prelude/final ops which keep their kind as the label. Together the
    groups partition the top-level param-tree keys exactly.
    """
    groups = []
    for kind, info in pipeline_op_specs(cfg):
        label = kind if kind in ("prelude", "final") else info["name"]
        groups.append((label, tuple(info["param_names"])))
    return groups


class XUNet(nn.Module):
    """The X-UNet (reference model/xunet.py:205-280), config-driven.

    `mesh` is the device mesh the model's programs are partitioned
    over: the Pallas attention kernels then run per 'data' shard (GSPMD
    cannot partition them — ops/_pallas.over_data_axis), and with
    config.sequence_parallel attention is the exact ring over the 'seq'
    axis (parallel/ring_attention.py). None = one device.

    The body is an ordered list of ops (pipeline_op_specs): the default
    call runs all of them — numerically and param-tree identical to the
    monolithic forward — while `ops=(a, b)` runs the half-open slice
    [a, b) for pipeline-stage execution (parallel/pipeline.py): a slice
    starting at 0 consumes `batch`/`cond_mask` and later slices consume
    `carry` (the (h, skip-stack, logsnr_emb, pose_embs) state, `h`, the
    skips and each pose embedding as (B·F, H/2ˡ, W/2ˡ, ·)); a slice
    ending before the last op returns the carry instead of the output.
    `batch` is still required for ops>0 slices — only its SHAPES are used
    (e.g. the output-channel count), never its values.
    """

    config: ModelConfig = ModelConfig()
    mesh: object = None
    family = "xunet"

    @nn.nowrap
    def precompute(self, params, cond: dict) -> dict:
        """The denoiser contract's once-a-call part (models/__init__.py):
        pose embeddings for the samplers' doubled guidance layout —
        conditional half with the mask on, unconditional half with the
        pose embedding zeroed, exactly what the in-loop mask produced.
        Cameras are fixed for a whole reverse process, so the rays →
        posenc → per-level convs run once here instead of every step.

        Wherever the configuration admits it the unconditional half comes
        at 1 × 1 extent — one vector per frame, which is all the mask
        leaves of it: each level is then a (cond, uncond) pair, and the
        model projects the unconditional rows once a frame at each FiLM
        site (precompute_guidance_pose_embs, which also says when it does
        not hold; each level is then one array over the doubled rows)."""
        pairs = precompute_guidance_pose_embs(self, params, cond)
        if pairs is None:
            B = cond["x"].shape[0]
            doubled = jax.tree.map(
                lambda a: jnp.concatenate([a, a], axis=0), cond)
            mask = jnp.concatenate([jnp.ones((B,)), jnp.zeros((B,))])
            pairs = precompute_pose_embs(self, params, doubled, mask)
        return {"pose_embs": pairs}

    @nn.compact
    def __call__(self, batch: dict, *, cond_mask: jnp.ndarray = None,
                 train: bool, ops=None, carry=None) -> jnp.ndarray:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        param_dtype = jnp.dtype(cfg.param_dtype)
        kw = dict(dtype=dtype, param_dtype=param_dtype)
        F = _num_frames(batch)
        blk_kw = dict(per_frame_gn=cfg.groupnorm_per_frame, frames=F, **kw)
        num_resolutions = len(cfg.ch_mult)
        C = batch["z"].shape[-1]

        # `train` is threaded as a module attribute (static by construction)
        # so the blocks can be remat'd without static-argnum plumbing.
        Block = _remat_block(cfg.remat)

        def block(features, use_attn, h, emb, train, name):
            return Block(
                features=features,
                use_attn=use_attn,
                attn_heads=cfg.attn_heads,
                attn_out_proj=cfg.attn_out_proj,
                attn_use_flash=resolve_flash(cfg.use_flash_attention),
                attn_mesh=self.mesh,
                attn_ring=(cfg.sequence_parallel
                           and self.mesh is not None),
                dropout=cfg.dropout,
                train=train,
                name=name,
                **blk_kw,
            )(h, emb)

        def run_op(kind, info, state):
            if kind == "prelude":
                logsnr_emb, pose_embs = ConditioningProcessor(
                    emb_ch=cfg.emb_ch,
                    num_resolutions=num_resolutions,
                    use_pos_emb=cfg.use_pos_emb,
                    use_ref_pose_emb=cfg.use_ref_pose_emb,
                    num_classes=cfg.num_classes,
                    name=info["cond"],
                    **kw,
                )(batch, cond_mask)
                # Frame stacking: cond frames first, noised target LAST.
                if "cond_feats" in batch:
                    # Conditioning cache (sample/service.py): the stem
                    # conv runs per frame, so the cond frames' features
                    # are loop-invariant across denoise steps — the
                    # caller computed them once (precompute_cond_feats)
                    # and only the noised target frame is convolved
                    # here. Bitwise identical to the joint conv below
                    # (per-frame batch rows are independent).
                    # init() never takes this path: param tree unchanged.
                    hz = FrameConv(cfg.ch, name=info["stem"], **kw)(
                        batch["z"].astype(dtype))
                    h = _rows(jnp.concatenate(
                        [batch["cond_feats"].astype(hz.dtype), hz[:, None]],
                        axis=1))
                else:
                    x = batch["x"]
                    if x.ndim == 4:  # (B,H,W,3) → (B,1,H,W,3)
                        x = x[:, None]
                    h = jnp.concatenate([x, batch["z"][:, None]],
                                        axis=1).astype(dtype)
                    h = FrameConv(cfg.ch, name=info["stem"], **kw)(_rows(h))
                return (h, (h,), logsnr_emb, tuple(pose_embs))

            h, hs, logsnr_emb, pose_embs = state

            def level_emb(i_level):
                # (B·F, 1, 1, emb) + (B·F, H/2ˡ, W/2ˡ, emb) broadcast add,
                # a sample's logsnr embedding on each of its F rows.
                # A level given as a pair (precompute_guidance_pose_embs:
                # leading rows at full extent, the rest at 1 × 1) stays a
                # pair, each part with its own rows of logsnr_emb.
                pose = pose_embs[i_level]
                with jax.named_scope("lk.emb"):
                    lemb = jnp.repeat(logsnr_emb, F, axis=0)[:, None, None, :]
                    if not isinstance(pose, tuple):
                        return lemb + pose
                    full, per_frame = pose
                    n = full.shape[0]
                    assert n + per_frame.shape[0] == lemb.shape[0], (
                        full.shape, per_frame.shape, lemb.shape)
                    film_rows.append((int(np.prod(full.shape[:-1])),
                                      int(np.prod(per_frame.shape[:-1]))))
                    return lemb[:n] + full, lemb[n:] + per_frame

            if kind == "down_block":
                use_attn = h.shape[2] in cfg.attn_resolutions
                h = block(info["features"], use_attn, h,
                          level_emb(info["level"]), train, info["name"])
                return (h, hs + (h,), logsnr_emb, pose_embs)
            if kind == "down_trans":
                # Strided transition conditioned with the NEXT level's pose
                # embedding (reference xunet.py:243-246).
                h = ResnetBlock(dropout=cfg.dropout, resample="down",
                                name=info["name"], **blk_kw)(
                    h, level_emb(info["level"] + 1), train=train)
                return (h, hs + (h,), logsnr_emb, pose_embs)
            if kind == "middle":
                # Bottleneck features = ch·ch_mult[-1], ref xunet.py:248-255.
                use_attn = h.shape[2] in cfg.attn_resolutions
                h = block(info["features"], use_attn, h,
                          level_emb(num_resolutions - 1), train,
                          info["name"])
                return (h, hs, logsnr_emb, pose_embs)
            if kind == "up_block":
                # Skip-concat then block (num_res_blocks+1 per level).
                use_attn = hs[-1].shape[2] in cfg.attn_resolutions
                h = jnp.concatenate([h, hs[-1]], axis=-1)
                h = block(info["features"], use_attn, h,
                          level_emb(info["level"]), train, info["name"])
                return (h, hs[:-1], logsnr_emb, pose_embs)
            if kind == "up_trans":
                # Upsample transition conditioned with the FINER level's
                # pose embedding (reference xunet.py:269-271).
                h = ResnetBlock(dropout=cfg.dropout, resample="up",
                                name=info["name"], **blk_kw)(
                    h, level_emb(info["level"] - 1), train=train)
                return (h, hs, logsnr_emb, pose_embs)
            assert kind == "final", kind
            assert not hs
            h = GroupNorm(per_frame=cfg.groupnorm_per_frame, act="swish",
                          frames=F, dtype=dtype, name=info["gn"])(h)
            # Zero-init output conv in float32 for stable noise predictions.
            out = FrameConv(C, zero_init=True, dtype=jnp.float32,
                            param_dtype=param_dtype, name=info["out"])(
                h.astype(jnp.float32))
            return _frames(out, F)[:, -1]

        specs = pipeline_op_specs(cfg)
        a, b = (0, len(specs)) if ops is None else ops
        state = carry
        film_rows = []  # (rows at full extent, rows at 1 × 1) per FiLM site
        for kind, info in specs[a:b]:
            # og.<label> named scope: stamps each op's HLO with its
            # op-group label (the op_groups vocabulary) so profiler
            # traces attribute device time per group (obs/profiler.py).
            # Metadata only — no effect on the computation, the param
            # tree, or flax's module naming/rng folding.
            label = kind if kind in ("prelude", "final") else info["name"]
            with jax.named_scope(f"og.{label}"):
                state = run_op(kind, info, state)
        if film_rows:
            per_pixel, per_frame = map(sum, zip(*film_rows))
            log_once(
                ("film_collapse", len(film_rows), per_pixel, per_frame),
                f"note: guidance pair with the unconditional half's pose "
                f"embedding at 1 × 1 extent: {len(film_rows)} FiLM sites "
                f"project {per_pixel} rows per pixel and {per_frame} rows "
                f"per frame (at full extent: {2 * per_pixel} per pixel)")
        return state

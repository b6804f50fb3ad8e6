"""Novel-view evaluation: sample views for held-out pairs, score PSNR/SSIM.

The reference has no evaluation path at all (its sampling.py displays images
in a blocking cv2 window, sampling.py:153-154, and computes nothing). This is
the quality-measurement loop the 3DiM paper's SRN-cars protocol implies:
condition on one view of an instance, synthesize other (ground-truth-posed)
views, and score the synthesis against the held-out real images.

Two protocols:
- ``single`` — every target view is sampled in one reverse process
  conditioned on the same fixed view (fast; one batched sampler call).
- ``autoregressive`` — the 3DiM paper protocol: targets are generated in
  sequence with stochastic conditioning over the growing pool of available
  views (sample/ddpm.py:autoregressive_generate), so later views are
  conditioned on earlier generated ones.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from novel_view_synthesis_3d_tpu.config import Config
from novel_view_synthesis_3d_tpu.data.srn import SRNDataset
from novel_view_synthesis_3d_tpu.diffusion.schedules import sampling_schedule
from novel_view_synthesis_3d_tpu.eval.metrics import fid, psnr, ssim
from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib
from novel_view_synthesis_3d_tpu.sample.ddpm import (
    autoregressive_generate,
    make_sampler,
    make_stochastic_sampler,
)


@dataclasses.dataclass
class EvalResult:
    psnr: float
    ssim: float
    num_views: int
    per_view_psnr: np.ndarray
    per_view_ssim: np.ndarray
    fid: Optional[float] = None
    # Honest labeling: the default Fréchet metric uses the deterministic
    # random-conv feature extractor (eval/metrics.py), which is valid for
    # relative comparisons but NOT comparable to published Inception-FID —
    # so it is reported as "fid_random". A caller who supplies a pretrained
    # feature_fn gets the plain "fid" key.
    fid_label: str = "fid_random"
    protocol: str = "single"
    # Relative output delta when the conditioning image is swapped across
    # instances (see cond_sensitivity). 0.0 means the model IGNORES its
    # conditioning image — the r2/r3 failure class (inert cross-frame
    # attention trains an unconditional pose-memorizer whose seen-pose
    # PSNR looks healthy). None when too few distinct instances to swap.
    cond_sens: Optional[float] = None

    def to_dict(self) -> dict:
        d = {
            "psnr": self.psnr,
            "ssim": self.ssim,
            "num_views": self.num_views,
            "protocol": self.protocol,
        }
        if self.fid is not None:
            d[self.fid_label] = self.fid
        if self.cond_sens is not None:
            d["cond_sens"] = self.cond_sens
        return d


def make_cond_sensitivity_fn(model, logsnr: float = 0.0):
    """Jitted conditioning-sensitivity probe: swap the cond image, measure
    the output delta.

    Returns fn(params, key, batch) -> scalar, where batch holds x/R1/t1/
    R2/t2/K/target (B ≥ 2, distinct conditioning images). The target is
    noised to the given logsnr (α = σ(logsnr); default 0.0 = mid-noise,
    α = ½), the denoiser is applied twice — once with the true
    conditioning images, once with them rolled by one along the batch
    axis (poses NOT rolled: only the image path is probed) — and the
    scalar is mean|Δout| / mean|out|.

    Cross-frame attention is the ONLY path from the conditioning image to
    the target-frame output (convs are per-frame, models/layers.py), so an
    inert-attention config — the r2/r3 postmortem class
    (record deleted in PR 21) — yields EXACTLY 0.0 here while its seen-pose
    PSNR curve still looks healthy. A healthy conditioned model yields
    O(0.1–1). One forward pair per call: cheap enough for the in-loop
    probe at every eval point.
    """

    @jax.jit
    def fn(params, key, batch):
        target = batch["target"]
        B = target.shape[0]
        alpha = jax.nn.sigmoid(jnp.asarray(logsnr, jnp.float32))
        noise = jax.random.normal(key, target.shape)
        z = jnp.sqrt(alpha) * target + jnp.sqrt(1.0 - alpha) * noise
        mb = {k: batch[k] for k in ("x", "R1", "t1", "R2", "t2", "K")}
        mb["z"] = z
        mb["logsnr"] = jnp.full((B,), logsnr, jnp.float32)
        mask = jnp.ones((B,))
        out = model.apply({"params": params}, mb, cond_mask=mask,
                          train=False)
        swapped = dict(mb, x=jnp.roll(mb["x"], 1, axis=0))
        out_swap = model.apply({"params": params}, swapped, cond_mask=mask,
                               train=False)
        # (delta, scale) rather than the ratio: the ratio's degenerate
        # cases (vacuous swap, all-zero output) need host-side None
        # semantics — see cond_sensitivity.
        return (jnp.mean(jnp.abs(out - out_swap)),
                jnp.mean(jnp.abs(out)))

    return fn


# Below this output scale the ratio is meaningless, not alarming: a model
# whose output is ~identically zero (fresh zero-init head, collapsed run)
# would otherwise score delta/scale = 0/ε = 0.0 — the exact value documented
# as the inert-attention alarm.
_COND_SENS_MIN_SCALE = 1e-6


def cond_sensitivity(model, params, batch: dict, *, key,
                     logsnr: float = 0.0, fn=None) -> Optional[float]:
    """One-shot conditioning-sensitivity probe (see make_cond_sensitivity_fn).

    Returns None when the probe cannot distinguish pathology from
    degeneracy — fewer than 2 samples, all conditioning images identical
    (rolled == original ⇒ delta is 0 by construction), or an output that is
    itself ~0 (fresh zero-init head / collapsed run).

    `fn`: a cached make_cond_sensitivity_fn(model, logsnr) result; pass it
    from a loop (e.g. the trainer's in-loop probe) to avoid re-jitting —
    `model`/`logsnr` are ignored when given.
    """
    x = np.asarray(batch["x"])
    if x.shape[0] < 2 or not np.any(x != np.roll(x, 1, axis=0)):
        return None
    if fn is None:
        fn = make_cond_sensitivity_fn(model, logsnr)
    delta, scale = (float(v) for v in fn(params, key, batch))
    if scale < _COND_SENS_MIN_SCALE:
        return None
    return delta / scale


def evaluate_dataset(
    config: Config,
    model,
    params,
    dataset: SRNDataset,
    *,
    key: jax.Array,
    num_instances: Optional[int] = None,
    views_per_instance: int = 1,
    cond_view: int = 0,
    sample_steps: Optional[int] = None,
    batch_size: int = 8,
    compute_fid: bool = False,
    fid_feature_fn=None,
    protocol: str = "single",
    mesh=None,
    dump_comparisons: Optional[str] = None,
    max_comparisons: int = 8,
) -> EvalResult:
    """Sample novel views for held-out (cond, target) pairs and score them.

    For each of the first `num_instances` instances: condition on
    k = config.model.num_cond_frames CONSECUTIVE views starting at
    `cond_view` (k=1 is the reference's single-view protocol), synthesize
    `views_per_instance` of the remaining views at their ground-truth
    poses, and score PSNR/SSIM against the real images. The k cond views
    are excluded from the target pool, so an instance with V views yields
    at most V−k targets. Under protocol="autoregressive" all k views seed
    the stochastic-conditioning pool.

    `protocol`: "single" scores every target independently conditioned on
    the fixed view; "autoregressive" runs the 3DiM stochastic-conditioning
    protocol, where each generated view joins the conditioning pool for the
    next (`batch_size` then counts instances per sampler call).

    `fid_feature_fn`: optional pretrained (B,H,W,C)→(B,D) embedder; when
    given, the Fréchet metric is reported as "fid". Default None uses the
    deterministic random-conv features and reports "fid_random" (not
    comparable to published Inception-FID numbers).

    `mesh`: a jax Mesh — the conditioning batch is sharded over its 'data'
    axis and params replicated, so the reverse process runs data-parallel
    across chips (batch_size must be a multiple of the data-axis size).
    None = default-device sampling.

    `dump_comparisons`: optional PNG path — writes a
    [conditioning | ground truth | synthesis] row per scored pair (first
    `max_comparisons` pairs), the human-legible form of the PSNR table.
    """
    if protocol not in ("single", "autoregressive"):
        raise ValueError(f"unknown eval protocol {protocol!r}")
    dcfg = config.diffusion
    schedule = sampling_schedule(dcfg, sample_steps)
    if protocol == "autoregressive" and jax.process_count() > 1:
        # Every process would duplicate the full eval and race on any
        # output file (the batched pool/target inputs here are host-local).
        raise ValueError(
            "evaluate_dataset(protocol='autoregressive') is "
            "single-process only; on a pod, run eval on one host")
    if mesh is not None:
        if jax.process_count() > 1:
            # Every process assembles the FULL batch here; the multi-process
            # branch of shard_batch would treat it as a per-host shard and
            # P-plicate the work, and the sharded psnr/ssim outputs would
            # span non-addressable devices at device_get.
            raise ValueError(
                "evaluate_dataset(mesh=...) is single-process only; on a "
                "pod, run eval on one host (or mesh=None)")
        shards = mesh_lib.num_data_shards(mesh)
        if batch_size % shards != 0:
            raise ValueError(
                f"eval batch_size {batch_size} not divisible by the mesh "
                f"data axis ({shards})")
        params = mesh_lib.replicate(mesh, params)

    n_inst = (dataset.num_instances if num_instances is None
              else min(num_instances, dataset.num_instances))

    # Assemble (cond views, target views) per instance host-side. A k>1
    # model (model.num_cond_frames) is conditioned on k CONSECUTIVE views
    # starting at cond_view — the 3DiM multi-view conditioning the model
    # was trained with; k=1 keeps the reference's single-view protocol
    # (and the frame-axis-free record layout).
    k = config.model.num_cond_frames
    instances = []  # (x, R1, t1, K, [(target_img, target_pose)])
    for i in range(n_inst):
        inst = dataset.instances[i]
        cond_idx = [(cond_view + j) % len(inst) for j in range(k)]
        views = [inst.view(v) for v in cond_idx]
        if k == 1:
            x, pose1 = views[0]
            R1, t1 = pose1[:3, :3], pose1[:3, 3]
        else:
            x = np.stack([v[0] for v in views])
            R1 = np.stack([v[1][:3, :3] for v in views])
            t1 = np.stack([v[1][:3, 3] for v in views])
        others = [v for v in range(len(inst)) if v not in cond_idx]
        targets = [inst.view(v) for v in others[:views_per_instance]]
        if targets:
            instances.append((x, R1, t1, inst.K, targets))
    truths = [t for (_, _, _, _, targets) in instances for (t, _) in targets]
    if not truths:
        raise ValueError("no evaluation pairs (need ≥2 views per instance)")
    if compute_fid and len(truths) < 2:
        raise ValueError(
            "FID needs ≥2 evaluation pairs for a covariance estimate; "
            "raise num_instances/views_per_instance or drop compute_fid")

    # Standing conditioning-sensitivity probe (one forward pair over one
    # (cond, target) pair per instance — needs ≥2 distinct instances to
    # swap across). Runs before sampling so a cond_sens == 0.0 failure is
    # visible even if the (much slower) sampling loop is interrupted.
    sens = None
    if len(instances) >= 2:
        # Cap the probe batch: one pair per instance but no more than the
        # sampler's batch_size — a full-split eval (hundreds of instances)
        # must not stack them all into one jitted forward.
        probe = instances[:max(2, min(len(instances), batch_size))]
        sens_batch = jax.tree.map(jnp.asarray, {
            "x": np.stack([c[0] for c in probe]),
            "R1": np.stack([c[1] for c in probe]),
            "t1": np.stack([c[2] for c in probe]),
            "R2": np.stack([c[4][0][1][:3, :3] for c in probe]),
            "t2": np.stack([c[4][0][1][:3, 3] for c in probe]),
            "K": np.stack([c[3] for c in probe]),
            "target": np.stack([c[4][0][0] for c in probe]),
        })
        key, k_sens = jax.random.split(key)
        sens = cond_sensitivity(model, params, sens_batch, key=k_sens)

    all_psnr, all_ssim, all_imgs = [], [], []
    comparisons = []  # (cond, truth, pred) rows for dump_comparisons

    def add_comparison(cond_img, truth_img, pred_img):
        if dump_comparisons and len(comparisons) < max_comparisons:
            cond_img = np.asarray(cond_img)
            if cond_img.ndim == 4:  # k>1: show the first conditioning view
                cond_img = cond_img[0]
            comparisons.append((cond_img, np.asarray(truth_img),
                                np.asarray(pred_img)))

    def score(imgs, truth):
        all_psnr.append(np.asarray(jax.device_get(
            psnr(imgs, jnp.asarray(truth)))))
        all_ssim.append(np.asarray(jax.device_get(
            ssim(imgs, jnp.asarray(truth)))))
        if compute_fid:
            all_imgs.append(np.asarray(jax.device_get(imgs)))

    if protocol == "autoregressive":
        # 3DiM protocol: per instance, generate the target views in sequence
        # with stochastic conditioning over the pool of available views.
        # Batch instances together (autoregressive_generate is batched over
        # its leading axis); the pool length must match within a call, so a
        # short-tailed instance set falls back to the min target count. The
        # stochastic sampler is built ONCE and the tail chunk padded to
        # batch_size, so one compiled program serves every chunk.
        n_targets = min(len(t) for (_, _, _, _, t) in instances)
        if n_targets < views_per_instance:
            print(f"note: autoregressive eval truncated to {n_targets} "
                  f"target views per instance (requested "
                  f"{views_per_instance}; shortest instance bounds all)")
            truths = [t for (_, _, _, _, targets) in instances
                      for (t, _) in targets[:n_targets]]
        # A k>1 model's k conditioning views all seed the stochastic pool
        # (autoregressive_generate accepts (B, P0, …) pools natively);
        # k=1 keeps the paper's pool-of-one protocol.
        ar_sampler = make_stochastic_sampler(model, schedule, dcfg,
                                             max_pool=n_targets + k)
        for start in range(0, len(instances), batch_size):
            chunk = instances[start:start + batch_size]
            n = len(chunk)
            chunk = chunk + [chunk[-1]] * (batch_size - n)
            first_view = {
                "x": jnp.asarray(np.stack([c[0] for c in chunk])),
                "R1": jnp.asarray(np.stack([c[1] for c in chunk])),
                "t1": jnp.asarray(np.stack([c[2] for c in chunk])),
                "K": jnp.asarray(np.stack([c[3] for c in chunk])),
            }
            target_poses = {
                "R2": jnp.asarray(np.stack(
                    [[p[:3, :3] for (_, p) in c[4][:n_targets]]
                     for c in chunk])),
                "t2": jnp.asarray(np.stack(
                    [[p[:3, 3] for (_, p) in c[4][:n_targets]]
                     for c in chunk])),
            }
            if mesh is not None:
                # Shard the instance batch over the mesh 'data' axis; the
                # growing view pool inside autoregressive_generate inherits
                # the sharding from these inputs, so every reverse process
                # runs data-parallel across chips.
                first_view = mesh_lib.shard_batch(mesh, first_view)
                target_poses = mesh_lib.shard_batch(mesh, target_poses)
            truth = np.stack([[t for (t, _) in c[4][:n_targets]]
                              for c in chunk[:n]])  # (n, N, H, W, 3)
            key, k_s = jax.random.split(key)
            imgs = autoregressive_generate(
                model, schedule, dcfg, params, k_s, first_view, target_poses,
                max_pool=n_targets + k, sampler=ar_sampler)
            if dump_comparisons and len(comparisons) < max_comparisons:
                per_inst = np.asarray(jax.device_get(imgs[:n]))
                for j in range(n):
                    for ti in range(n_targets):
                        add_comparison(chunk[j][0], truth[j][ti],
                                       per_inst[j][ti])
            imgs = imgs[:n].reshape((-1,) + imgs.shape[2:])
            score(imgs, truth.reshape((-1,) + truth.shape[2:]))
    else:
        # Flatten to (cond, target) pairs; batch through the sampler (pad
        # the tail so one compilation serves all).
        sampler = make_sampler(model, schedule, dcfg)
        conds = []
        for (x, R1, t1, K, targets) in instances:
            for (_, pose2) in targets:
                conds.append({
                    "x": x, "R1": R1, "t1": t1,
                    "R2": pose2[:3, :3], "t2": pose2[:3, 3], "K": K,
                })
        for start in range(0, len(conds), batch_size):
            chunk = conds[start:start + batch_size]
            truth = np.stack(truths[start:start + batch_size])
            n = len(chunk)
            pad = batch_size - n
            stacked = {k: np.stack([c[k] for c in chunk] +
                                   [chunk[-1][k]] * pad)
                       for k in chunk[0]}
            key, k_s = jax.random.split(key)
            device_batch = jax.tree.map(jnp.asarray, stacked)
            if mesh is not None:
                device_batch = mesh_lib.shard_batch(mesh, device_batch)
            imgs = sampler(params, k_s, device_batch)
            imgs = imgs[:n]
            if dump_comparisons and len(comparisons) < max_comparisons:
                preds = np.asarray(jax.device_get(imgs))
                for j in range(n):
                    add_comparison(chunk[j]["x"], truth[j], preds[j])
            score(imgs, truth)

    if dump_comparisons and comparisons:
        from novel_view_synthesis_3d_tpu.utils.images import save_image_grid

        rows = np.stack([im for trip in comparisons for im in trip])
        save_image_grid(rows, dump_comparisons, cols=3)

    per_psnr = np.concatenate(all_psnr)
    per_ssim = np.concatenate(all_ssim)
    fid_value = None
    if compute_fid:
        fid_value = fid(np.stack(truths), np.concatenate(all_imgs),
                        feature_fn=fid_feature_fn)
    return EvalResult(
        psnr=float(per_psnr.mean()),
        ssim=float(per_ssim.mean()),
        num_views=len(per_psnr),
        per_view_psnr=per_psnr,
        per_view_ssim=per_ssim,
        fid=fid_value,
        fid_label="fid" if fid_feature_fn is not None else "fid_random",
        protocol=protocol,
        cond_sens=sens,
    )

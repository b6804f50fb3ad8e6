"""The jitted data-parallel train step.

TPU-native redesign of the reference hot path (train.py:49-76 + the CPU-side
noising at data_loader.py:92-110):

  - forward noising (t, ε, z_t, logsnr) happens ON DEVICE inside the jit —
    the data pipeline ships clean image pairs only. This both removes the
    reference's float64 `z` / list-typed collate bug (SURVEY.md §3.4) and
    keeps host→device traffic to 2 images per sample;
  - fresh per-step PRNG keys via fold_in(state.rng, state.step) — dropout,
    CFG mask, t and ε all differ every step (reference baked them at trace
    time, SURVEY.md §3.1);
  - batch arrives SHARDED over the mesh 'data' axis; the mean loss makes XLA
    emit the gradient all-reduce over ICI (the psum the reference never had);
  - state is donated (in-place buffer reuse in HBM).

Batch contract (clean, from data/pipeline.py):
  x (B,[Fc],H,W,3) cond view(s) · target (B,H,W,3) clean target view ·
  R1,t1 cond pose(s) · R2,t2 target pose · K intrinsics.
"""

from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import optax

from novel_view_synthesis_3d_tpu.config import Config
from novel_view_synthesis_3d_tpu.diffusion.schedules import DiffusionSchedule
from novel_view_synthesis_3d_tpu.parallel import mesh as mesh_lib
from novel_view_synthesis_3d_tpu.parallel import zero as zero_lib
from novel_view_synthesis_3d_tpu.parallel.pipeline import MODEL_KEYS
from novel_view_synthesis_3d_tpu.train import guard as guard_lib
from novel_view_synthesis_3d_tpu.train.state import TrainState, make_optimizer
from novel_view_synthesis_3d_tpu.utils import faultinject


def effective_accum_steps(batch_size: int, data_shards: int,
                          requested: int) -> int:
    """Largest usable accumulation ≤ `requested` for this batch and mesh.

    Accumulation only helps while each micro-batch can stay sharded over
    the 'data' axis (micro % data_shards == 0) — otherwise GSPMD replicates
    the batch inside the scan and memory goes UP. Per-chip memory already
    scales as 1/data_shards, so the accumulation a config requests for one
    chip is naturally satisfied by the sharding on many. Hence: the largest
    divisor of the per-shard batch that is ≤ `requested`.
    """
    if batch_size % max(1, data_shards) != 0:
        raise ValueError(
            f"global batch {batch_size} not divisible by data-axis size "
            f"{data_shards}")
    per_shard = batch_size // max(1, data_shards)
    requested = max(1, requested)
    for accum in range(min(requested, per_shard), 0, -1):
        if per_shard % accum == 0:
            return accum
    return 1


def compute_loss(eps_pred: jnp.ndarray, noise: jnp.ndarray, kind: str,
                 weight: jnp.ndarray | None = None) -> jnp.ndarray:
    if kind == "mse":
        if weight is None:
            return jnp.mean(jnp.square(eps_pred - noise))
        # Per-sample MSE over pixel dims, then weighted batch mean.
        per_sample = jnp.mean(
            jnp.square(eps_pred - noise).reshape(eps_pred.shape[0], -1),
            axis=-1)
        return jnp.mean(weight * per_sample)
    if kind == "frobenius":
        if weight is not None:
            raise ValueError("loss weighting requires kind='mse' — the "
                             "whole-tensor norm has no per-sample terms")
        # Reference parity (train.py:67): L2 norm of the whole flattened
        # residual tensor (jnp.mean over a scalar is the identity).
        return jnp.linalg.norm((eps_pred - noise).reshape(-1))
    raise ValueError(f"unknown loss {kind!r}")


def min_snr_weight(snr: jnp.ndarray, gamma: float,
                   objective: str) -> jnp.ndarray:
    """Min-SNR-γ per-sample loss weight (Hang et al. 2023, arXiv 2303.09556).

    The paper weights the x₀-space loss by min(SNR, γ); expressed in each
    prediction space that becomes min(SNR,γ)/SNR for ε-prediction and
    min(SNR,γ)/(SNR+1) for v-prediction.
    """
    clipped = jnp.minimum(snr, gamma)
    if objective == "eps":
        return clipped / snr
    if objective == "x0":
        return clipped
    if objective == "v":
        return clipped / (snr + 1.0)
    raise ValueError(f"unknown objective {objective!r}")


def make_train_step(config: Config, model, schedule: DiffusionSchedule,
                    mesh, state_sharding=None
                    ) -> Callable[[TrainState, dict], Tuple[TrainState, dict]]:
    """Build the jitted train step bound to a mesh.

    Returns step(state, batch) -> (state, metrics); `batch` must already be
    device-put with `parallel.mesh.shard_batch`. `state_sharding` (default
    fully replicated) carries the FSDP layout when train.fsdp is on: with
    params/opt-state sharded over 'data', XLA emits the all-gather before
    use and reduce-scatters the gradient — ZeRO-3 from annotations alone.
    """
    tcfg = config.train
    objective = config.diffusion.objective
    if objective not in ("eps", "x0", "v"):
        raise ValueError(f"unknown objective {objective!r}")
    data_shards = mesh_lib.num_data_shards(mesh)
    accum = effective_accum_steps(tcfg.batch_size, data_shards,
                                  tcfg.grad_accum_steps)
    # (grad_accum_steps > 1 with loss='frobenius' is rejected by
    # Config.validate() at startup — the whole-tensor norm has no
    # per-micro-batch decomposition.)
    if tcfg.loss_weighting not in ("none", "min_snr"):
        raise ValueError(
            f"unknown loss_weighting {tcfg.loss_weighting!r}")
    if tcfg.loss_weighting != "none" and tcfg.loss != "mse":
        raise ValueError("loss_weighting requires loss='mse'")
    # Composable update sharding (train.update_sharding): 'zero' runs the
    # Adam+EMA update on 1/data_shards shards (parallel/zero.py). Its inner
    # chain swaps the global-norm clip for identity (a shard-local norm
    # would be wrong); the clip then runs here on the FULL gradient before
    # the sharded region — same math, same order as the replicated chain.
    zero = tcfg.update_sharding == "zero"
    stages = config.mesh.stages
    tx, lr_schedule = make_optimizer(tcfg, return_schedule=True,
                                     shard_local=zero)
    full_clip = (optax.clip_by_global_norm(tcfg.grad_clip)
                 if zero and tcfg.grad_clip > 0 else None)
    # Corpus mixer (data/corpus.py): per-corpus loss attribution. The mix
    # spec fixes the number of corpora at TRACE time (static C), so the
    # segment_sum below compiles to a fixed-shape (C,) reduction — no
    # dynamic shapes, no recompiles as corpus proportions drift per batch.
    if config.data.mix:
        from novel_view_synthesis_3d_tpu.data.corpus import parse_mix_spec
        corpus_count = len(parse_mix_spec(config.data.mix))
    else:
        corpus_count = 0
    if corpus_count and tcfg.loss != "mse":
        raise ValueError(
            "data.mix per-corpus loss attribution requires train.loss="
            "'mse' — the whole-tensor frobenius norm has no per-sample "
            "terms to attribute to a corpus")
    if stages > 1 and (corpus_count or config.model.num_classes > 0):
        raise ValueError(
            "data.mix / model.num_classes are not supported with "
            "mesh.stages > 1 — the pipeline-staged step streams only "
            "MODEL_KEYS through its stage shard_map; run the corpus "
            "mixer on the sequential (stages=1) step")
    if stages > 1:
        from novel_view_synthesis_3d_tpu.parallel import (
            pipeline as pipeline_lib)
    # Fault injection (utils/faultinject.py): read at TRACE time — a clean
    # build compiles no injection ops at all.
    fi_nan_steps = faultinject.nan_loss_steps()
    fi_nan_group = faultinject.nan_grad_group()
    # Numerics observatory (obs/numerics.py): per-layer-group read-only
    # reductions grouped by the pipeline op list, UNCONDITIONALLY traced
    # into the step (see finish_step). train.numerics.enabled only gates
    # the host-side consumer, which is what makes flipping it bitwise
    # identical with zero recompiles: earlier Python-gated variants
    # changed XLA's fusion around the optimizer update (~1-ulp param
    # drift on CPU even behind an optimization_barrier).
    from novel_view_synthesis_3d_tpu.models.xunet import op_groups
    from novel_view_synthesis_3d_tpu.obs import numerics as numerics_lib
    layer_groups = op_groups(config.model)

    def derive_fields(batch, k_t, k_noise, k_mask, B, rows):
        """Diffusion training fields for `rows` of a B-row batch.

        Randoms (t, noise, cond_mask) are drawn FULL-batch from the given
        keys and then sliced to `rows` — so the per-row values are the
        same no matter which shard computes them, which is what lets the
        pipeline path rerun this inside its shard_map (parallel/pipeline.py
        explains why it must). `rows=None` keeps the whole batch.
        """
        target = batch["target"]
        t = jax.random.randint(k_t, (B,), 0, schedule.num_timesteps)
        noise = jax.random.normal(
            k_noise, (B,) + target.shape[1:], dtype=target.dtype)
        cond_mask = (
            jax.random.uniform(k_mask, (B,)) >= tcfg.cond_drop_prob
        ).astype(jnp.float32)
        if rows is not None:
            n = target.shape[0]
            t = jax.lax.dynamic_slice_in_dim(t, rows, n)
            noise = jax.lax.dynamic_slice_in_dim(noise, rows, n)
            cond_mask = jax.lax.dynamic_slice_in_dim(cond_mask, rows, n)
        z = schedule.q_sample(target, t, noise)
        logsnr = schedule.logsnr(t)

        model_batch = {
            "x": batch["x"],
            "z": z,
            "logsnr": logsnr,
            "R1": batch["R1"],
            "t1": batch["t1"],
            "R2": batch["R2"],
            "t2": batch["t2"],
            "K": batch["K"],
        }

        # Regression target per diffusion.objective: ε (reference behavior),
        # clean x₀, or v = √ᾱε − √(1−ᾱ)x₀ (Salimans & Ho 2022).
        if objective == "eps":
            regression_target = noise
        elif objective == "x0":
            regression_target = target
        else:  # 'v'
            regression_target = schedule.v_from_eps_x0(t, noise, target)

        full = dict(model_batch, cond_mask=cond_mask,
                    regression_target=regression_target)
        if tcfg.loss_weighting == "min_snr":
            acp = jnp.take(schedule.alphas_cumprod, t, axis=0)
            snr = acp / (1.0 - acp)
            full["loss_weight"] = min_snr_weight(
                snr, tcfg.min_snr_gamma, objective)
        # Mixed-corpus batches (data/corpus.py): category feeds the
        # conditioning table (only when the model grew one), corpus_id
        # feeds loss attribution (never the model).
        if config.model.num_classes > 0 and "category" in batch:
            full["category"] = batch["category"]
        if corpus_count and "corpus_id" in batch:
            full["corpus_id"] = batch["corpus_id"]
        return full

    def train_step(state: TrainState, batch: dict) -> Tuple[TrainState, dict]:
        step_rng = jax.random.fold_in(state.rng, state.step)
        k_t, k_noise, k_mask, k_dropout = jax.random.split(step_rng, 4)

        target = batch["target"]
        B = target.shape[0]

        if stages > 1:
            # Pipeline-staged forward/backward (parallel/pipeline.py):
            # same per-row t/noise/cond_mask and dropout keys as the
            # accumulation path below, but the micro-batches stream
            # through S model stages in a GPipe fill/drain schedule
            # instead of a sequential scan — equivalent loss/grads up to
            # f32 reduction order (tests/test_pipeline.py). The field
            # derivation reruns inside the shard_map, per data shard;
            # see parallel/pipeline.py for why it cannot stay out here.
            def derive_local(local_batch, rng, data_index):
                k_t_, k_noise_, k_mask_, k_drop_ = jax.random.split(rng, 4)
                rows = data_index * local_batch["target"].shape[0]
                full = derive_fields(local_batch, k_t_, k_noise_, k_mask_,
                                     B, rows)
                micro = jax.tree.map(
                    lambda a: a.reshape((accum, a.shape[0] // accum)
                                        + a.shape[1:]), full)
                return micro, jax.random.split(k_drop_, accum)

            def micro_loss_of(pred, mb):
                return compute_loss(pred, mb["regression_target"],
                                    tcfg.loss, weight=mb.get("loss_weight"))

            loss, grads = pipeline_lib.value_and_grad_pipelined(
                model, mesh, stages, state.params, batch, step_rng,
                accum, derive_local, micro_loss_of)
            return finish_step(state, loss, grads)

        full = derive_fields(batch, k_t, k_noise, k_mask, B, None)

        def model_keys_of(mb):
            # corpus_id/regression_target/... never reach the model;
            # category does, iff the batch carries it (the model grew a
            # conditioning table — derive_fields gates on num_classes).
            return (MODEL_KEYS + ("category",) if "category" in mb
                    else MODEL_KEYS)

        def micro_loss(params, mb):
            pred = model.apply(
                {"params": params},
                {k: mb[k] for k in model_keys_of(mb)},
                cond_mask=mb["cond_mask"], train=True,
                rngs={"dropout": mb["dropout_key"]})
            return compute_loss(pred, mb["regression_target"], tcfg.loss,
                                weight=mb.get("loss_weight"))

        def micro_loss_attributed(params, mb):
            """micro_loss + per-corpus (loss_sum, count) aux — the same
            per-sample terms the scalar mean reduces, bucketed by
            corpus_id with a static-C segment_sum."""
            pred = model.apply(
                {"params": params},
                {k: mb[k] for k in model_keys_of(mb)},
                cond_mask=mb["cond_mask"], train=True,
                rngs={"dropout": mb["dropout_key"]})
            per_sample = jnp.mean(
                jnp.square(pred - mb["regression_target"]).reshape(
                    pred.shape[0], -1), axis=-1)
            w = mb.get("loss_weight")
            if w is not None:
                per_sample = w * per_sample
            sums = jax.ops.segment_sum(
                per_sample, mb["corpus_id"], num_segments=corpus_count)
            counts = jax.ops.segment_sum(
                jnp.ones_like(per_sample), mb["corpus_id"],
                num_segments=corpus_count)
            return jnp.mean(per_sample), (sums, counts)

        attributed = corpus_count > 0 and "corpus_id" in full
        corpus_aux = None
        if accum == 1:
            if attributed:
                (loss, corpus_aux), grads = jax.value_and_grad(
                    micro_loss_attributed, has_aux=True)(
                        state.params, dict(full, dropout_key=k_dropout))
            else:
                loss, grads = jax.value_and_grad(micro_loss)(
                    state.params, dict(full, dropout_key=k_dropout))
        else:
            # lax.scan over micro-batches: activations live one slice at a
            # time; gradients accumulate in a params-shaped f32 tree. Equal
            # slice sizes make mean-of-means == full-batch mean.
            micro = jax.tree.map(
                lambda a: a.reshape((accum, a.shape[0] // accum)
                                    + a.shape[1:]), full)
            micro["dropout_key"] = jax.random.split(k_dropout, accum)

            if attributed:
                def body(carry, mb):
                    loss_sum, grad_sum, (s_sum, c_sum) = carry
                    (l, (s, c)), g = jax.value_and_grad(
                        micro_loss_attributed, has_aux=True)(
                            state.params, mb)
                    return (loss_sum + l,
                            jax.tree.map(
                                lambda a, x: a + x.astype(jnp.float32),
                                grad_sum, g),
                            (s_sum + s, c_sum + c)), None
            else:
                def body(carry, mb):
                    loss_sum, grad_sum, aux = carry
                    l, g = jax.value_and_grad(micro_loss)(state.params, mb)
                    return (loss_sum + l,
                            jax.tree.map(
                                lambda s, x: s + x.astype(jnp.float32),
                                grad_sum, g),
                            aux), None

            # Accumulate in f32 regardless of param_dtype — bf16 sums would
            # swallow small per-micro-batch contributions — then cast back.
            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            zero_aux = (jnp.zeros((corpus_count,), jnp.float32),
                        jnp.zeros((corpus_count,), jnp.float32))
            (loss, grads, corpus_aux), _ = jax.lax.scan(
                body, (0.0, zero_grads, zero_aux), micro)
            if not attributed:
                corpus_aux = None
            loss = loss / accum
            grads = jax.tree.map(
                lambda g, p: (g / accum).astype(p.dtype),
                grads, state.params)
        return finish_step(state, loss, grads, corpus_aux)

    def finish_step(state: TrainState, loss, grads, corpus_aux=None):
        """Everything after the forward/backward: fault injection, clip,
        (possibly ZeRO-sharded) update, anomaly guard, metrics. Shared by
        the sequential and pipeline-staged paths."""
        if fi_nan_steps:
            # Injected fault: poison loss AND gradients at the armed steps,
            # exactly what a numerically-blown forward/backward produces.
            # NVS3D_FI_NAN_GRAD_GROUP narrows the grad poisoning to one
            # layer group — the NaN-provenance drill.
            bad_step = jnp.isin(state.step,
                                jnp.asarray(fi_nan_steps, jnp.int32))
            loss = jnp.where(bad_step, jnp.float32(jnp.nan), loss)
            if fi_nan_group:
                poison_keys = {name for label, names in layer_groups
                               if label == fi_nan_group for name in names}
                if not poison_keys:
                    raise ValueError(
                        f"NVS3D_FI_NAN_GRAD_GROUP={fi_nan_group!r} matches "
                        "no layer group; labels: "
                        f"{[label for label, _ in layer_groups]}")

                def poison(path, g):
                    top = getattr(path[0], "key", None)
                    if top in poison_keys:
                        return jnp.where(bad_step,
                                         jnp.asarray(jnp.nan, g.dtype), g)
                    return g

                grads = jax.tree_util.tree_map_with_path(poison, grads)
            else:
                grads = jax.tree.map(
                    lambda g: jnp.where(bad_step,
                                        jnp.asarray(jnp.nan, g.dtype),
                                        g), grads)

        grad_norm = optax.global_norm(grads)

        def apply_update(_):
            if zero:
                # ZeRO path: clip on the full gradient (exactly what the
                # replicated chain's first link does), then the sharded
                # Adam+EMA update — state.opt_state/ema_params are in the
                # packed (N, c) layout (parallel/zero.py).
                g = grads
                if full_clip is not None:
                    g, _ = full_clip.update(g, full_clip.init(None))
                return zero_lib.sharded_update(
                    mesh, tx, g, state.params, state.opt_state,
                    state.ema_params, tcfg.ema_decay)
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
            ema_params = state.ema_params
            if ema_params is not None:
                d = tcfg.ema_decay
                ema_params = jax.tree.map(
                    lambda e, p: e * d + p.astype(e.dtype) * (1.0 - d),
                    ema_params, params)
            return params, opt_state, ema_params

        new_guard = None
        if state.guard is not None:
            # Anomaly guard (train/guard.py): an anomalous step keeps
            # params/opt-state/EMA bit-identical (lax.cond skips the whole
            # update) and advances only the strike counters; step still
            # increments so the fold_in-derived keys move on.
            anomalous = guard_lib.detect_anomaly(
                loss, grad_norm, state.guard, tcfg.loss_spike_factor)
            params, opt_state, ema_params = jax.lax.cond(
                anomalous,
                lambda _: (state.params, state.opt_state, state.ema_params),
                apply_update, None)
            new_guard = guard_lib.update_guard(state.guard, loss, anomalous)
        else:
            params, opt_state, ema_params = apply_update(None)

        new_state = TrainState(
            step=state.step + 1,
            params=params,
            opt_state=opt_state,
            rng=state.rng,
            ema_params=ema_params,
            guard=new_guard,
        )
        lr = lr_schedule(state.step) if callable(lr_schedule) else lr_schedule
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "lr": jnp.asarray(lr, jnp.float32),
        }
        if new_guard is not None:
            metrics["anomalies"] = new_guard.anomalies.astype(jnp.float32)
            metrics["strikes"] = new_guard.strikes.astype(jnp.float32)
        if corpus_aux is not None:
            # (C,) per-corpus loss sums and sample counts; the trainer's
            # host side divides at log time (mean of sums / mean of counts
            # across a fused window reduces to the same ratio).
            metrics["corpus_loss_sum"] = corpus_aux[0]
            metrics["corpus_count"] = corpus_aux[1]
        # Per-layer-group numerics (obs/numerics.py): read-only reductions
        # over pre-update params, the gradient, and the post-update params
        # (guard-skipped steps read update_ratio 0). ALWAYS part of the
        # program — train.numerics.enabled gates only the host-side
        # consumer (NumericsMonitor), so flipping it is bitwise identical
        # and recompile-free by construction: there is exactly one step
        # program either way. The (G,) outputs cost two elementwise passes
        # over params+grads, noise next to the fwd/bwd and Adam's own
        # tree passes.
        metrics["numerics"] = numerics_lib.group_stats(
            numerics_lib.group_assignment(
                layer_groups, list(state.params.keys())),
            len(layer_groups),
            grads=grads, params=state.params, new_params=params)
        return new_state, metrics

    repl = mesh_lib.replicated(mesh)
    if state_sharding is None:
        state_sharding = repl
    if tcfg.steps_per_dispatch <= 1:
        return jax.jit(
            train_step,
            donate_argnums=(0,),
            in_shardings=(state_sharding, mesh_lib.batch_sharding(mesh)),
            out_shardings=(state_sharding, repl),
        )

    # Fused multi-step dispatch (train.steps_per_dispatch = K > 1): scan
    # the SAME step body over a (K, B, ...) stack of fresh batches — one
    # XLA program per K steps. Semantics are identical to K single
    # dispatches (state.step advances inside the scan, so fold_in-derived
    # noise/dropout/CFG keys match the sequential run exactly); what
    # disappears is K-1 host dispatch round trips, the dominant cost for
    # small models. loss/grad_norm come back as
    # the window mean (per-step values inside the window are unobservable
    # to the logger anyway); lr is the LAST step's value — a schedule
    # position, where a window mean would misreport the logged step.
    def multi_step(state: TrainState, batches: dict):
        state, ms = jax.lax.scan(train_step, state, batches)
        out = jax.tree.map(lambda a: jnp.mean(a, axis=0), ms)
        out["lr"] = ms["lr"][-1]
        # Guard counters are cumulative/positional, not window averages:
        # the logger (and the rollback check) want the value AFTER the
        # window's last step.
        for k in ("anomalies", "strikes"):
            if k in ms:
                out[k] = ms[k][-1]
        # Numerics stats are positional like lr (last step's values),
        # EXCEPT nonfinite which takes the window max — an anomaly inside
        # a fused window must keep its provenance observable.
        if "numerics" in ms:
            out["numerics"] = {
                k: (jnp.max(v, axis=0) if k == "nonfinite" else v[-1])
                for k, v in ms["numerics"].items()}
        return state, out

    return jax.jit(
        multi_step,
        donate_argnums=(0,),
        in_shardings=(state_sharding, mesh_lib.stacked_batch_sharding(mesh)),
        out_shardings=(state_sharding, repl),
    )

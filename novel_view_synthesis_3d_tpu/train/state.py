"""Train state + optimizer factory.

Replaces the reference's per-device `flax.training.TrainState` under pmap
(train.py:36-47). One logical state, replicated over the mesh by sharding
annotations; `step` and the base PRNG key live IN the state so per-step keys
are derived on device (`fold_in`) — the reference instead baked a fixed
dropout key and a host-numpy CFG mask into the trace (train.py:64-66).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax

from novel_view_synthesis_3d_tpu.config import TrainConfig
from novel_view_synthesis_3d_tpu.train.guard import (
    GuardState,
    init_guard_state,
)


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray  # () int32
    params: Any
    opt_state: Any
    rng: jax.Array  # base key; per-step keys are fold_in(rng, step)
    ema_params: Optional[Any] = None
    # Anomaly-guard bookkeeping (train/guard.py). Lives in the state so it
    # (a) threads through the steps_per_dispatch fused scan as part of the
    # carry and (b) survives checkpoint/restore. None when
    # train.anomaly_guard is off.
    guard: Optional[GuardState] = None


def make_lr_schedule(cfg: TrainConfig):
    """LR schedule per config — probeable directly (scalar or step→lr)."""
    if cfg.lr_schedule == "constant":
        return (optax.linear_schedule(0.0, cfg.lr, cfg.warmup_steps)
                if cfg.warmup_steps > 0 else cfg.lr)
    if cfg.lr_schedule == "cosine":
        if cfg.warmup_steps >= cfg.num_steps:
            raise ValueError(
                f"lr_schedule='cosine' needs num_steps ({cfg.num_steps}) > "
                f"warmup_steps ({cfg.warmup_steps})")
        if cfg.warmup_steps > 0:
            return optax.warmup_cosine_decay_schedule(
                init_value=0.0, peak_value=cfg.lr,
                warmup_steps=cfg.warmup_steps,
                decay_steps=cfg.num_steps,
                end_value=cfg.lr * cfg.lr_final_fraction)
        return optax.cosine_decay_schedule(
            init_value=cfg.lr, decay_steps=max(1, cfg.num_steps),
            alpha=cfg.lr_final_fraction)
    raise ValueError(f"unknown lr_schedule {cfg.lr_schedule!r}")


def make_optimizer(cfg: TrainConfig, return_schedule: bool = False,
                   shard_local: bool = False):
    """Optimizer chain per config; with return_schedule=True also returns
    the EXACT lr schedule handed to optax, so callers logging lr can never
    drift from what the optimizer applies.

    'adam' is the reference optimizer (train.py:46, optax.adam(1e-4));
    'adafactor' is the memory-lean alternative for HBM-bound single-chip
    configs: factored second moments + no first moment cut optimizer state
    from 2x param bytes (Adam f32 mu+nu; 5.3G for the 708M-param paper256
    model) to ~sqrt-sized row/col stats, the difference between paper256
    fitting a 16G v5e with margin and scraping the ceiling.

    `shard_local=True` (the ZeRO update path, parallel/zero.py) builds the
    chain that runs INSIDE shard_map on each replica's 1/N shard: the
    global-norm clip is replaced by optax.identity() — a shard-local norm
    would be wrong, so the caller clips the full gradient before entering
    the sharded region. identity's state is EmptyState(), exactly like
    clip_by_global_norm's, so the opt_state TREEDEF is identical across
    both variants and checkpoints move freely between update_sharding
    settings.
    """
    schedule = make_lr_schedule(cfg)
    parts = []
    if cfg.grad_clip > 0:
        parts.append(optax.identity() if shard_local
                     else optax.clip_by_global_norm(cfg.grad_clip))
    if cfg.optimizer == "adam":
        parts.append(optax.adam(
            schedule, mu_dtype=jnp.dtype(cfg.adam_mu_dtype)))
    elif cfg.optimizer == "adafactor":
        # min_dim_size_to_factor=128: small tensors (biases, norm scales)
        # keep an unfactored (exact) second moment — factoring them saves
        # nothing and costs accuracy. multiply_by_parameter_scale=False +
        # momentum=None keeps the update closest to Adam's geometry so lr
        # presets transfer; momentum would reintroduce the 1x-param-bytes
        # buffer this optimizer exists to avoid.
        parts.append(optax.adafactor(
            schedule, min_dim_size_to_factor=128,
            multiply_by_parameter_scale=False, momentum=None))
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    tx = optax.chain(*parts)
    return (tx, schedule) if return_schedule else tx


def create_train_state(cfg: TrainConfig, model, sample_batch: dict,
                       seed: Optional[int] = None,
                       on_cpu: Optional[bool] = None) -> TrainState:
    """Initialize params ONCE (same everywhere — the reference initialized
    each device differently, train.py:122-123) and build the state.

    `on_cpu` (default: automatically True off the CPU backend) runs the init
    forward on the host (the accelerator run's platform list must include
    'cpu'): the state is then built in host memory and reaches the
    accelerator only through the caller's sharded device_put, and the
    threefry PRNG makes the resulting params bitwise identical on every
    backend. The init pass swaps in a dense-attention model (Pallas kernels
    can't lower on CPU, a shard_map over the accelerator mesh can't run
    there) — neither feature has parameters, so the tree is unchanged.
    """
    seed = cfg.seed if seed is None else seed
    root = jax.random.PRNGKey(seed)
    k_params, k_dropout, k_train = jax.random.split(root, 3)
    if on_cpu is None:
        on_cpu = jax.default_backend() != "cpu"

    # Params are batch-size independent: init on the smallest batch slice
    # so the traced init forward costs ~1/B of the real step (at paper256
    # scale the full batch-8 256px forward takes tens of minutes on the
    # host). A sequence-parallel model initializing on its real mesh needs
    # the batch divisible by the 'data' axis, so keep that many rows.
    min_b = 1
    model_mesh = getattr(model, "mesh", None)
    if not on_cpu and model_mesh is not None:
        min_b = dict(model_mesh.shape).get("data", 1)
    full_b = sample_batch["z"].shape[0]
    min_b = min(min_b, full_b)
    sample_batch = jax.tree.map(lambda a: a[:min_b], sample_batch)
    B = min_b

    init_model = model
    if on_cpu and hasattr(model, "config"):
        import dataclasses

        init_model = type(model)(dataclasses.replace(
            model.config, use_flash_attention=False,
            sequence_parallel=False))

    def run_init():
        # jit makes the init forward an XLA program instead of thousands of
        # eager dispatches — the dominant cost of large-model host init.
        @jax.jit
        def _init(k_p, k_d, batch):
            return init_model.init(
                {"params": k_p, "dropout": k_d}, batch,
                cond_mask=jnp.ones((B,)), train=True)

        return _init(k_params, k_dropout, sample_batch)

    tx = make_optimizer(cfg)

    def build_state():
        params = run_init()["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            # Optimizer + EMA state are ~3x param bytes — they must follow
            # the same host-side path as params or they'd materialize on
            # accelerator device 0 before any sharded device_put.
            opt_state=tx.init(params),
            rng=k_train,
            # Distinct buffers from params: the donated train step must not
            # see the same buffer twice (f(donate(a), donate(a)) invalid).
            # With ema_host the EMA buffer lives in host RAM instead
            # (Trainer._host_ema) — no device copy at all.
            ema_params=(jax.tree.map(jnp.copy, params)
                        if cfg.ema_decay > 0 and not cfg.ema_host else None),
            guard=init_guard_state() if cfg.anomaly_guard else None,
        )

    if on_cpu:
        with jax.default_device(jax.devices("cpu")[0]):
            return build_state()
    return build_state()


# ---------------------------------------------------------------------------
# ZeRO (train.update_sharding='zero') state layout
# ---------------------------------------------------------------------------
# Between steps the TrainState carries opt_state/ema_params in the packed
# (N, c) row-sharded layout of parallel/zero.py; params stay replicated.
# Checkpoints and the registry/probe always see the canonical UNPACKED
# layout — pack/unpack live here so every boundary (trainer init, save,
# restore, publish) converts the same way.

def _zero_plans(cfg: TrainConfig, params: Any, has_ema: bool, n: int):
    from novel_view_synthesis_3d_tpu.parallel import zero as zero_lib

    tx = make_optimizer(cfg, shard_local=True)
    return zero_lib.state_plans(tx, params, has_ema, n)


def pack_train_state(cfg: TrainConfig, mesh, state: TrainState):
    """Canonical state → (packed state, matching per-leaf sharding tree).

    The sharding tree mirrors the PACKED state leaf-for-leaf (packed
    opt/EMA rows over 'data', everything else replicated) so it can feed
    both jax.device_put and the train step's in/out_shardings."""
    import jax.sharding as js

    from novel_view_synthesis_3d_tpu.parallel import zero as zero_lib

    n = mesh.shape["data"]
    plans = _zero_plans(cfg, state.params, state.ema_params is not None, n)
    packed = state.replace(
        opt_state=zero_lib.pack(state.opt_state, plans["opt_state"]),
        ema_params=(zero_lib.pack(state.ema_params, plans["ema_params"])
                    if state.ema_params is not None else None))
    repl = js.NamedSharding(mesh, js.PartitionSpec())
    shardings = packed.replace(
        step=repl,
        params=jax.tree.map(lambda _: repl, state.params),
        opt_state=zero_lib.packed_shardings(mesh, plans["opt_state"]),
        rng=repl,
        ema_params=(zero_lib.packed_shardings(mesh, plans["ema_params"])
                    if state.ema_params is not None else None),
        guard=(jax.tree.map(lambda _: repl, state.guard)
               if state.guard is not None else None))
    return packed, shardings


def unpack_train_state(cfg: TrainConfig, mesh, packed: TrainState
                       ) -> TrainState:
    """Packed state → canonical layout (leaf shapes re-derived from the
    params avals; works on device or host-numpy leaves alike)."""
    from novel_view_synthesis_3d_tpu.parallel import zero as zero_lib

    n = mesh.shape["data"]
    plans = _zero_plans(cfg, packed.params, packed.ema_params is not None, n)
    return packed.replace(
        opt_state=zero_lib.unpack(packed.opt_state, plans["opt_state"]),
        ema_params=(zero_lib.unpack(packed.ema_params, plans["ema_params"])
                    if packed.ema_params is not None else None))


def unpack_ema(cfg: TrainConfig, mesh, params: Any, ema_packed: Any):
    """Gather a ZeRO-packed EMA tree back to canonical leaves.

    The registry publisher and the sampling probes call this ONCE per
    publish/probe — the shard gather stays off the train-step hot loop.
    Works on device or host-numpy leaves alike (parallel/zero.py unpack
    is pure reshape/slice)."""
    from novel_view_synthesis_3d_tpu.parallel import zero as zero_lib

    n = mesh.shape["data"]
    plans = _zero_plans(cfg, params, True, n)
    return zero_lib.unpack(ema_packed, plans["ema_params"])

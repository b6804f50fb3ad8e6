"""Multi-host bring-up (SURVEY.md §2.3: the reference is single-host only —
`jax.device_count()` over local GPUs, no process coordination).

On TPU pods each host runs the same program; `jax.distributed.initialize`
wires the processes together (DCN for control, ICI for collectives). On
single-host (or under tests) this is a no-op.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

import jax

# Structured exit code for "the platform that was asked for did not
# answer", shared by every entry point that needs the device (cli
# train/sample/serve/eval/distill, bench). Distinct from
# utils/watchdog.EXIT_STALL (74): absent-at-startup and stalled-mid-run
# are different diagnoses.
EXIT_BACKEND_UNREACHABLE = 3


def answered_platform() -> str:
    """Bring the backend up IN THIS PROCESS (a chip belongs to one
    process at a time, so no child may probe it first) and return the
    platform that answered — which must be the one that was asked for.

    Asked for means the first entry of `jax_platforms` (the
    JAX_PLATFORMS variable). With nothing asked for JAX picks the best
    platform it can initialise and falls back to the CPU on its own;
    that fallback is refused here: a CPU run says JAX_PLATFORMS=cpu."""
    asked = (jax.config.jax_platforms or "").split(",")[0].strip()
    platform = jax.devices()[0].platform
    backend = jax.default_backend()
    if backend != platform:
        raise RuntimeError(
            f"default backend {backend!r} holds {platform!r} devices")
    if asked and asked != platform:
        raise RuntimeError(
            f"platform {asked!r} was asked for and {platform!r} answered")
    if not asked and platform == "cpu":
        raise RuntimeError(
            "no accelerator answered and JAX fell back to the CPU by "
            "itself; a CPU run asks for it with JAX_PLATFORMS=cpu")
    return platform


def require_platform() -> str:
    """`answered_platform()` for entry points: SystemExit(3) with a
    one-line reason on stderr when the device is not there."""
    try:
        return answered_platform()
    except RuntimeError as e:
        reason = str(e).strip().splitlines()[0]
        print(f"error: {reason}", file=sys.stderr)
        raise SystemExit(EXIT_BACKEND_UNREACHABLE) from e


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Initialize multi-process JAX if we're in a multi-host environment.

    On Cloud TPU VMs `jax.distributed.initialize()` auto-discovers the pod
    topology from the metadata server; explicit args cover other clusters.
    Safe to call unconditionally: single-process environments skip init.

    NOTE: must not touch the XLA backend before deciding — jax.distributed
    rejects initialization after any backend query (jax.devices,
    jax.process_count, any computation), so the already-initialized check
    uses jax.distributed.is_initialized(), not jax.process_count().
    """
    if jax.distributed.is_initialized():
        return
    explicit = coordinator_address is not None
    # Opt-in env gate (NVS3D_MULTIHOST=1) rather than sniffing TPU_* vars:
    # single-host TPU containers may set TPU_WORKER_HOSTNAMES themselves.
    auto_tpu = os.environ.get("NVS3D_MULTIHOST") == "1"
    if explicit or auto_tpu:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )


def process_shard(n: int) -> tuple[int, int]:
    """(shard_index, shard_count) for per-host data sharding of n records."""
    del n
    return jax.process_index(), jax.process_count()


def local_batch_size(global_batch_size: int) -> int:
    count = jax.process_count()
    if global_batch_size % count != 0:
        raise ValueError(
            f"global batch {global_batch_size} not divisible by "
            f"{count} processes")
    return global_batch_size // count

"""Shared Pallas-kernel plumbing for ops/.

Every kernel in this package carries the same off-TPU contract: the
IDENTICAL kernel code path runs through the Pallas interpreter on CPU
(so tier-1 exercises the real kernel, not a shadow implementation). The
two kernels that still have an XLA form beside them
(ops/flash_attention.py, ops/fused_step.py) resolve their 'auto' | bool
config value here, and the slab-sized one bounds its VMEM residency and
falls back to XLA above it.
"""

from __future__ import annotations

import math

import jax
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from novel_view_synthesis_3d_tpu.parallel.dist import answered_platform
from novel_view_synthesis_3d_tpu.parallel.mesh import DATA_AXIS

VMEM = pltpu.VMEM

# Per-program budget for a kernel's resident input slab. A slab kernel
# holds its in/out blocks double-buffered plus f32 working copies.
# Strict `<` in fits_vmem so power-of-two slab sizes (every UNet level
# is one) can't sit on a zero-headroom boundary.
SLAB_LIMIT_BYTES = 3 * 1024 * 1024


def use_interpret() -> bool:
    """True off-TPU: run the kernel through the Pallas interpreter.

    This is how tier-1 (JAX_PLATFORMS=cpu) executes the exact same
    kernel code path the TPU compiles — correctness is proven on the
    bits that ship, not on an XLA stand-in. On platform 'tpu' the
    kernels are compiled: the platform is the one that answered AND was
    asked for (parallel/dist.answered_platform raises otherwise), never
    a guess from the backend's name."""
    return answered_platform() != "tpu"


def over_data_axis(fn, mesh):
    """`fn` as a per-shard call over `mesh`'s 'data' axis.

    GSPMD cannot partition a compiled Pallas kernel ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map") — on a multi-chip mesh the program does not lower. Every
    kernel here treats the rows of its leading (batch) dimension
    independently, and the batch is what the mesh shards over 'data', so
    the call runs inside a shard_map over that axis; the other axes see
    replicated operands. `fn` takes and returns arrays whose leading
    dimension is the batch. No-op without a mesh, on a one-shard data
    axis, and inside a shard_map that already holds the axis (the
    pipeline-staged step, parallel/pipeline.py)."""
    if (mesh is None or mesh.shape[DATA_AXIS] == 1
            or DATA_AXIS in jax.sharding.get_abstract_mesh().manual_axes):
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(DATA_AXIS),
                         out_specs=P(DATA_AXIS), check_vma=False)


def resolve_flag(flag, field: str) -> bool:
    """Resolve an 'auto' | bool kernel-enable config value.

    'auto' → the Pallas kernel on TPU backends (where it is compiled
    and fast), the XLA path elsewhere (interpreted Pallas on CPU is
    correct but slow). Booleans pass through; anything else is an
    error — CLI overrides arrive as raw strings, and silently coercing
    a typo like 'False' to truthy would force interpret-mode Pallas on
    CPU. `field` names the config knob in the error message."""
    if flag == "auto":
        return not use_interpret()
    if isinstance(flag, bool):
        return flag
    raise ValueError(
        f"{field} must be True, False, or 'auto'; got {flag!r}")


def fits_vmem(nbytes: int, limit: int = SLAB_LIMIT_BYTES) -> bool:
    """True if a per-program input slab of `nbytes` fits the budget."""
    return nbytes < limit


def head_group(head: int) -> int:
    """Lanes of the fewest whole heads of `head` lanes that fill whole
    128-lane blocks: a head of 128 or 256 by itself, two of 192 or four of
    96 are 384, two of 64 are 128."""
    return head * (128 // math.gcd(head, 128))


def lanes_a_step(D: int, group: int, most: int) -> int:
    """Lanes a grid step takes of a width D walked in whole groups: the
    largest multiple of `group` up to `most` that divides D; where none
    does, the largest there is, and the last step's block hangs over the
    array's edge (its lanes past the edge are read as they come and never
    written: whole heads)."""
    steps = range(group, max(min(most, -(-D // group) * group), group) + 1,
                  group)
    return max([n for n in steps if D % n == 0] or steps)

"""A grouped matrix product: row block g of `lhs` times `rhs[g]`.

    out[start_g : start_g + group_sizes[g]] = lhs[same rows] @ rhs[g]

with start_g the running sum of `group_sizes`. The expert layer of
models/token_denoiser.py sorts its token→expert assignments by expert and
multiplies all held experts' rows in one such product. `lhs` has a static
row count that covers the worst case (every assignment lands here); the
rows past the last group belong to no expert, the product does no work for
them and what it leaves there is unspecified — the caller masks them.

This is the Pallas grouped-matmul kernel that ships with JAX
(`jax.experimental.pallas.ops.tpu.megablox`): its grid runs over the row
tiles that groups actually cover (a dynamic bound), so the tail costs
nothing. Off the TPU the same kernel runs through the Pallas interpreter
(ops/_pallas.py's contract). A chip run chose it over `jax.lax.ragged_dot`
(PERF.md, PR 26), which is not merged.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from novel_view_synthesis_3d_tpu.ops import _pallas

# (rows, contraction, columns) tile of the kernel, from the same chip run.
TILING = (256, 2048, 1024)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray) -> jnp.ndarray:
    """lhs (M, K) · rhs (G, K, N) by `group_sizes` (G,) int32 → (M, N) in
    lhs's dtype, accumulated in float32."""
    m, k = lhs.shape
    n = rhs.shape[-1]
    tiling = (min(TILING[0], m), min(TILING[1], k), min(TILING[2], n))
    # The kernel takes whole row tiles: rows added here lie past the last
    # group (none at the sizes the chip runs, 32768 rows).
    pad = -m % tiling[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    return gmm(lhs, rhs.astype(lhs.dtype), group_sizes, lhs.dtype, tiling,
               interpret=_pallas.use_interpret())[:m]

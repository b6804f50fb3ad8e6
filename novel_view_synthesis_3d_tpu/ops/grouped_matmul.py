"""A grouped matrix product over tile-aligned spans: the rows of span g of
`lhs` times `rhs[g]`.

    out[end_g - span_g : end_g] = lhs[same rows] @ rhs[g]

with end_g the running sum of the spans. **The contract**: every span is a
whole number of `ROW_TILE`-row tiles (`span_sizes` rounds a group's row
count up), so no row tile belongs to two groups and each is multiplied
once, by one expert's weights. A group's live rows lie at its span's END,
the pad rows before them at its head; pad rows are multiplied like any
other and nobody reads what comes of them. `lhs` has a static row count
that covers the worst case (`buffer_rows`: every assignment lands here,
each group with a tile's remainder); the rows past the last span belong to
no group, the product does no work for them and what it leaves there is
unspecified — the caller masks them. `rows_visited` is the account of it:
the rows a product multiplies for given group sizes; live rows ÷ that is
the tile fill.

The kernel. The grid runs over (column blocks, the row tiles the spans
cover — a dynamic bound, so the tail costs nothing). A row tile and its
output tile go through Pallas's own pipeline. The weights stay in HBM and
are fetched by hand, a whole (K, tn) block of one expert at a time into
one of two VMEM slots: the block stays there for all of that expert's
consecutive row tiles — each weight is read once per product — and the
next expert's block is fetched while they are multiplied (the pipeline's
own lookahead is one row tile, however many the expert has). `tn` is the
widest column block whose two slots fit the budget; at the chip's sizes
that is all of N, so `lhs` is read once too. Off the TPU the same kernel
runs through the Pallas interpreter (ops/_pallas.py's contract).

The wrapper stamps `pt.kernel` around the `gmm` call and nothing else,
`pt.layout` around what feeds it (the weights' cast, the row tiles'
place among the groups) — models/vocab.py, LAYER_PARTS; metadata only.

Chip runs (PERF.md, PR 27; one product of the token cell, ms) chose it:
JAX's `megablox.gmm` on unaligned groups 1.83 as shipped before, 1.52–1.54
at its best tiling, 1.59 on aligned spans (it exposes no VMEM limit, so
no block wider than 512 columns of a whole K); this grid with the weights
in the pipeline 1.37; by hand 1.26; a third and fourth slot, 256-row tiles
or another visiting order gave nothing — a 128-row tile keeps the MXU at
71 % of its peak. None of the others is merged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from novel_view_synthesis_3d_tpu.ops import _pallas

# Rows of a tile: what a span is a multiple of, and what a nearly empty
# group costs.
ROW_TILE = 128
# One VMEM slot of weights, (K, tn) of one expert; there are two.
WEIGHT_SLOT_BYTES = 16 * 1024 * 1024
# Beside the slots: the pipeline's row and output tiles, twice each, and
# the float32 product of one tile.
VMEM_LIMIT_BYTES = 2 * WEIGHT_SLOT_BYTES + 24 * 1024 * 1024


def span_sizes(group_sizes):
    """Each group's row count rounded up to whole row tiles."""
    return -(-group_sizes // ROW_TILE) * ROW_TILE


def rows_visited(group_sizes) -> int:
    """Rows a product multiplies for these group sizes: Σ ⌈size ÷ tile⌉ ·
    tile. Live rows ÷ this is the tile fill."""
    return int(span_sizes(group_sizes).sum())


def buffer_rows(assignments: int, groups: int) -> int:
    """The static row count that holds `assignments` rows in `groups`
    aligned spans however they fall: Σ ⌈s_g ÷ tile⌉ ≤ ⌈Σ s_g ÷ tile⌉ +
    groups."""
    return (-(-assignments // ROW_TILE) + groups) * ROW_TILE


def _column_block(k: int, n: int, itemsize: int) -> int:
    """All of N if a (K, N) block fits a slot, else the widest multiple of
    128 columns that divides N and fits."""
    if k * n * itemsize <= WEIGHT_SLOT_BYTES:
        return n
    fits = [tn for tn in range(128, n, 128)
            if n % tn == 0 and k * tn * itemsize <= WEIGHT_SLOT_BYTES]
    if not fits:
        raise ValueError(f"no column block of a ({k}, {n}) weight fits "
                         f"{WEIGHT_SLOT_BYTES} bytes of VMEM")
    return fits[-1]


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray) -> jnp.ndarray:
    """lhs (M, K) · rhs (G, K, N) by `group_sizes` (G,) int32, each a
    multiple of ROW_TILE as M is → (M, N) in lhs's dtype, accumulated in
    float32."""
    return _gmm(lhs, rhs, group_sizes, interpret=_pallas.use_interpret())


# Jitted: the kernel is traced and lowered once a shape, not once a layer
# and product (36 times in the token cell's sampler, seconds of a start).
@functools.partial(jax.jit, static_argnames="interpret")
def _gmm(lhs, rhs, group_sizes, *, interpret: bool):
    m, k = lhs.shape
    n = rhs.shape[-1]
    if m % ROW_TILE:
        raise ValueError(f"{m} rows are not whole {ROW_TILE}-row tiles")
    tm, tn = ROW_TILE, _column_block(k, n, lhs.dtype.itemsize)
    tiles_n = n // tn

    with jax.named_scope("pt.layout"):
        rhs = rhs.astype(lhs.dtype)
        # Weight blocks run in the order (column block, non-empty group).
        # Per row tile, its group's place among the non-empty ones; and
        # those groups in order.
        ends = jnp.cumsum(group_sizes) // tm
        held = group_sizes > 0
        tile = jnp.arange(m // tm, dtype=jnp.int32)
        place = jnp.sum(held & (ends <= tile[:, None]),
                        axis=1).astype(jnp.int32)
        in_order = jnp.argsort(~held, stable=True).astype(jnp.int32)
        n_held = jnp.sum(held).astype(jnp.int32)[None]
        row_tiles = ends[-1]   # a dynamic bound: the tail costs nothing

    def kernel(place, in_order, n_held, lhs_ref, rhs_hbm, out_ref, slots, sem):
        j, i = pl.program_id(0), pl.program_id(1)
        groups = n_held[0]
        block = j * groups + place[i]
        slot = block % 2

        def fetch(b, s):
            return pltpu.make_async_copy(
                rhs_hbm.at[in_order[b % groups], :,
                           pl.ds((b // groups) * tn, tn)],
                slots.at[s], sem.at[s])

        @pl.when((i == 0) | (place[i] != place[jnp.maximum(i - 1, 0)]))
        def _a_groups_first_tile():
            @pl.when(block == 0)
            def _():
                fetch(block, slot).start()
            fetch(block, slot).wait()

            # The other slot's block has run all its tiles.
            @pl.when(block + 1 < groups * tiles_n)
            def _():
                fetch(block + 1, 1 - slot).start()

        out_ref[...] = jnp.dot(
            lhs_ref[...], slots[slot],
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    with jax.named_scope("pt.kernel"):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(tiles_n, row_tiles),
                in_specs=[pl.BlockSpec((tm, k), lambda j, i, *_: (i, 0)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((tm, tn), lambda j, i, *_: (i, j)),
                scratch_shapes=[pltpu.VMEM((2, k, tn), lhs.dtype),
                                pltpu.SemaphoreType.DMA((2,))]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            name="gmm", interpret=interpret,
        )(place, in_order, n_held, lhs, rhs)

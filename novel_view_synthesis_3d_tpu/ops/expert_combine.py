"""The expert layer's combine: back from expert order to token order, a
token's held choices summed.

    out[t] = Σ_c where(w[t, c] > 0, float32(y[back[t, c]]) · w[t, c], 0)

in the choice order c = 0 … K−1, in float32, one cast at the end — the
arithmetic of one XLA gather a choice with its mask and weighted sum, which
this replaces, operation for operation. `y` is the down product in expert
order (ops/grouped_matmul.py's buffer: an expert's live rows at the end of
its tile-aligned span), `back[t, c]` the row of token t's choice c, `w` its
gate, 0 where the choice is not held here. A choice with `w ≤ 0` is never
read: its row may lie past the last span, where nothing was written.

The kernel, ONE `pl.pallas_call` over tiles of `tt` tokens. `y` stays in
HBM. The chip's compiler takes no slice of an HBM array thinner than its
tiling — `CHUNK` rows — so what a DMA moves is an aligned chunk of CHUNK
rows (36–64 KB at the cells' widths, one contiguous piece). What makes
that cheap is the layout's own order: the sort that made it is stable, so
the rows a tile's tokens were given by ONE expert are consecutive in `y`.
A tile therefore needs one row range an expert, `ranges` finds them from
the counts, and the kernel fetches the chunks that cover them — the live
rows of the experts this tile's tokens chose and nothing of the others',
of the spans' pad rows or of the rows past the last span — into one of two
VMEM slots, the next tile's while this tile is summed. A DMA semaphore is
waited on once a chunk started, the count handed in with the ranges.

In VMEM a token's rows are picked out of the slot one at a time (a 16-bit
row is half of a 32-bit sublane row: the slot is read as uint32 and the
half shifted into a float32's high bits, which IS the cast), multiplied by
the gate and added, held choices only, in choice order; the tile's
float32 sums are cast and written once.

`tt` is the largest tile whose slot — sized for the worst case, every
expert's range a chunk's remainder at both ends — fits SLOT_BYTES. Off the
TPU the same kernel runs through the Pallas interpreter (ops/_pallas.py's
contract). The wrapper stamps `pt.gather` around the call: the kernel IS
the row gather between expert order and token order (models/vocab.py), and
the ranges' arithmetic before it is its remainder.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from novel_view_synthesis_3d_tpu.ops import _pallas
from novel_view_synthesis_3d_tpu.ops.grouped_matmul import span_sizes

# Rows of the thinnest slice of an HBM array a DMA may move: the tiling's
# (8, 128) for 32-bit rows and (8, 128)(2, 1) for 16-bit ones.
CHUNK = 8
# One VMEM slot of fetched chunks; there are two.
SLOT_BYTES = 20 * 1024 * 1024
# Beside the slots: the tile's float32 sums and the pipeline's two output
# tiles.
VMEM_LIMIT_BYTES = 2 * SLOT_BYTES + 24 * 1024 * 1024
# The tiles tried, widest first; an output tile is whole 16-row sublane
# tiles of a 16-bit type.
TOKEN_TILES = (1024, 512, 256, 128, 64, 32, 16)


def rows_fetched(group_sizes) -> int:
    """Rows of the down product the combine reads for these counts: the
    held assignments, Σ size. (The DMAs that bring them move whole chunks
    of CHUNK rows: `chunks_max` bounds them, a capture times them.)"""
    return int(np.asarray(group_sizes).sum())


def chunks_max(tt: int, choices: int, groups: int) -> int:
    """The most chunks a tile of `tt` tokens can need: a range of n ≥ 1
    rows touches at most (n + 14) // 8 chunks of 8, and the ranges hold
    tt · choices rows among at most `groups` experts."""
    rows = tt * choices
    return (rows + (2 * CHUNK - 2) * min(groups, rows)) // CHUNK


def token_tile(tokens: int, choices: int, groups: int, width: int,
               itemsize: int) -> int:
    """The widest tile of TOKEN_TILES whose slot fits SLOT_BYTES, no wider
    than the tokens there are (rounded up to whole sublane tiles)."""
    fits = [tt for tt in TOKEN_TILES
            if chunks_max(tt, choices, groups) * CHUNK * width * itemsize
            <= SLOT_BYTES]
    if not fits:
        raise ValueError(f"no token tile of {choices} choices of width "
                         f"{width} fits {SLOT_BYTES} bytes of VMEM")
    return min(fits[0], -(-tokens // 16) * 16)


def ranges(slot, group_sizes):
    """Per (tile, expert): the first row of the aligned chunks that cover
    the rows the tile's tokens were given by that expert, how many chunks,
    and — per assignment (tiles, assignments a tile) — where its expert's
    first chunk lands in the tile's VMEM slot minus where it lies in `y` (a
    row's place in the slot is its row + this). `slot` (tiles,
    assignments a tile) int32 is each assignment's held expert, `groups`
    for none; rows follow grouped_matmul's layout: an expert's live rows
    at the end of its span of whole row tiles, in assignment order."""
    groups = group_sizes.shape[0]
    hit = slot[:, :, None] == jnp.arange(groups, dtype=jnp.int32)
    count = jnp.sum(hit, axis=1, dtype=jnp.int32)          # (tiles, groups)
    first = (jnp.cumsum(span_sizes(group_sizes)) - group_sizes
             + jnp.cumsum(count, axis=0) - count)
    start = first // CHUNK * CHUNK
    chunks = jnp.where(count > 0, -(-(first + count) // CHUNK)
                       - first // CHUNK, 0)
    shift = (jnp.cumsum(chunks, axis=1) - chunks) * CHUNK - start
    # A row's shift is its expert's; one sum a tile, no gather of scalars.
    shift = jnp.sum(jnp.where(hit, shift[:, None, :], 0), axis=-1)
    return start, chunks, shift


def combine(y: jnp.ndarray, back: jnp.ndarray, w: jnp.ndarray,
            slot: jnp.ndarray, group_sizes: jnp.ndarray,
            dtype) -> jnp.ndarray:
    """y (rows, H) in expert order, back (T, K) int32, w (T, K) float32,
    slot (T·K,) int32 each assignment's held expert (`groups` for none),
    group_sizes (groups,) int32 the experts' true counts → (T, H) in
    `dtype`."""
    return _combine(y, back, w, slot, group_sizes, dtype=jnp.dtype(dtype),
                    interpret=_pallas.use_interpret())


# Jitted: the kernel is traced and lowered once a shape, not once a layer.
@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _combine(y, back, w, slot, group_sizes, *, dtype, interpret: bool):
    if y.dtype not in (jnp.bfloat16, jnp.float32):
        y = y.astype(jnp.float32)       # exact, and what the sum casts to
    packed = y.dtype.itemsize == 2      # two rows a 32-bit sublane row
    T, K = back.shape
    H = y.shape[-1]
    groups = group_sizes.shape[0]
    tt = token_tile(T, K, groups, H, y.dtype.itemsize)
    tiles = -(-T // tt)
    slot_rows = chunks_max(tt, K, groups) * CHUNK

    pad = tiles * tt - T

    def by_tile(a, fill):
        """(T·K values) → (tiles, tt · K), the last tile filled up."""
        return jnp.pad(a.reshape(T, K), ((0, pad), (0, 0)),
                       constant_values=fill).reshape(tiles, tt * K)

    start, chunks, shift = ranges(by_tile(slot, groups), group_sizes)
    # Each held assignment's row in its tile's slot; −1: not fetched.
    place = jnp.where(by_tile(w > 0, False), by_tile(back, 0) + shift, -1)
    gate = by_tile(w, 0.0)
    started = jnp.sum(chunks, axis=1, dtype=jnp.int32)

    def kernel(start, chunks, started, place, gate, y_hbm, out_ref, slots,
               sums, sem):
        i = pl.program_id(0)

        def chunk_copy(row, at, s):
            return pltpu.make_async_copy(
                y_hbm.at[pl.ds(pl.multiple_of(row, CHUNK), CHUNK), :],
                slots.at[s, pl.ds(pl.multiple_of(at, CHUNK), CHUNK), :],
                sem.at[s])

        def fetch(tile, s, wanted):
            """Start tile `tile`'s chunks into slot s — none where `wanted`
            is false: the loop's bound says so, no branch around loops."""
            def an_experts_range(e, at):
                n = chunks[tile * groups + e]
                row = start[tile * groups + e]

                def a_chunk(j, _):
                    chunk_copy(row + j * CHUNK, at + j * CHUNK, s).start()
                    return 0

                jax.lax.fori_loop(0, n, a_chunk, 0)
                return at + n * CHUNK

            jax.lax.fori_loop(0, groups * wanted.astype(jnp.int32),
                              an_experts_range, 0)

        fetch(0, 0, i == 0)
        # The other slot's tile has been summed.
        fetch(jnp.minimum(i + 1, tiles - 1), (i + 1) % 2, i + 1 < tiles)

        s = i % 2

        def a_wait(j, _):
            chunk_copy(0, 0, s).wait()
            return 0

        jax.lax.fori_loop(0, started[i], a_wait, 0)

        rows = slots.bitcast(jnp.uint32) if packed else slots

        def row_of(at):
            # Never below the slot: the chip issues this load ahead of the
            # branch it stands under, and a row of −1 is out of VMEM's
            # range (the device halts on it).
            at = jnp.maximum(at, 0)
            if not packed:
                return rows[s, pl.ds(at, 1), :]
            both = rows[s, pl.ds(at >> 1, 1), :]
            up = ((1 - (at & 1)) * 16).astype(jnp.uint32)
            return pltpu.bitcast((both << up) & jnp.uint32(0xFFFF0000),
                                 jnp.float32)

        def a_token(t, _):
            # Read ahead of the branches: the scalar loads do not wait on
            # one another.
            ats = [place[0, t * K + c] for c in range(K)]
            gs = [gate[0, t * K + c] for c in range(K)]
            total = jnp.zeros((1, H), jnp.float32)
            for at, g in zip(ats, gs):
                # where(w > 0, y · w, 0) as a branch, so the product is
                # rounded before the sum wherever this runs (one fused
                # loop would contract them on the CPU). The skipped case
                # FIRST: on the chip the same two branches the other way
                # round read 1.15 / 3.14 / 3.79 ms a pass for 0.91 / 2.84 /
                # 3.38 at ms4's / st21's / kl48's shapes (PERF.md, PR 37).
                total = total + jax.lax.cond(
                    at < 0, lambda: jnp.zeros((1, H), jnp.float32),
                    lambda at=at, g=g: row_of(at) * g)
            sums[pl.ds(t, 1), :] = total
            return 0

        jax.lax.fori_loop(0, tt, a_token, 0)
        out_ref[...] = sums[...].astype(out_ref.dtype)

    with jax.named_scope("pt.gather"):
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((tiles * tt, H), dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(tiles,),
                in_specs=[
                    pl.BlockSpec((None, 1, tt * K), lambda i, *_: (i, 0, 0),
                                 memory_space=pltpu.SMEM),
                    pl.BlockSpec((None, 1, tt * K), lambda i, *_: (i, 0, 0),
                                 memory_space=pltpu.SMEM),
                    pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((tt, H), lambda i, *_: (i, 0)),
                scratch_shapes=[pltpu.VMEM((2, slot_rows, H), y.dtype),
                                pltpu.VMEM((tt, H), jnp.float32),
                                pltpu.SemaphoreType.DMA((2,))]),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            name="moe_combine", interpret=interpret,
        )(start.reshape(-1), chunks.reshape(-1), started,
          place[:, None], gate[:, None], y)
    return out[:T] if pad else out

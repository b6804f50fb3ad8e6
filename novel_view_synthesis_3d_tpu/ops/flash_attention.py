"""Fused multi-head attention as a Pallas TPU kernel.

The reference computes attention with `flax.nn.dot_product_attention`
(/root/reference/model/xunet.py:101), which materializes the (L, L) score
matrix in HBM between ops. This kernel keeps the whole
score→softmax→weighted-sum chain in VMEM. The operands stay where the
layer has them, token-major with the heads side by side: q (B, Lq, H·D),
k and v (B, Lk, Hkv·D), a head a block of lanes, and the output is
written the same way, (B, Lq, H·Dv), which is what the caller's `o`
projection reads. The grid is (batch, query head, query block): a grid
step takes q's block (1, block_q, D) at (b, i, h) against that head's
keys and values, whole in VMEM, at (b, 0, h // (H // Hkv)) — an index
that does not change over a head's query blocks, nor over the query heads
of a group, so the pipeline fetches them once a key/value head: 2 × 512
KB at the token trunk's 2048 keys of 128 bfloat16, in tiles of 16 tokens
by 128 lanes, 4 KB each, H·4 KB apart (on v5e the kernel reads such
blocks as fast as contiguous ones: 2.179 ms against 2.180 at 8 × 32 heads
of 1024 × 2048; PERF.md §6, PR 41) — and the step WALKS them in key blocks
with a running row max, row sum and float32 accumulator (online softmax).
The score tile is then (block_q, block_k) whatever the key length, so the
query block can grow until it fills a grid step (a quarter of the grid
steps, and a score tile that stays a quarter of the size: on v5e that is
the gain, 2.0 → 1.5 ms at the trunk's shapes, 91 % of what the two
products take at the MXU's peak). The walk is unrolled at trace time —
static slices, and block j+1's q·kᵀ does not wait for block j's softmax;
as a `fori_loop` it measured slower than no blocking at all.
`forward_blocks` chooses both blocks from the operands' shapes alone.
Where one key block covers the padded key axis — every X-UNet preset:
attention runs at {8,16,32}² ⇒ L ≤ 1024 tokens — the walk has one
iteration, no running statistic is rescaled, and the body is the plain
max → exp → sum → p·v over a (block_q, Lk) tile. The token trunk
(models/token_denoiser.py) attends one frame's 1024 queries to 2048 keys
and takes the blocked form (PERF.md §6, PR 29, has the table that set the
blocks).

Two things the second token trunk (SmallThinker's layer) brought, both
forward only. **Grouped key/value heads**: k and v may have fewer heads
than q; query head h reads the lanes of key/value head h // (H // Hkv) —
the index map above —, so K and V are neither repeated in HBM nor fetched
more than once a group (the heads of a group are consecutive grid rows,
and K's and V's block index does not change over them). **A one-sided
window with an offset**: a query at position p sees key j iff j > p −
window, the queries being the last Lq positions of the key axis by
default. Which key blocks a query block visits is then static ONCE THE
QUERY BLOCK IS: the wrapper makes one kernel call a query block (four at
the trunk's 4096 queries), each with its own unrolled walk — blocks no
row sees left out at trace time, blocks on the band's edge masked, the
others bare — over every head's block of that index, each call writing
its (B, block_q, H·Dv) slab, and joins the slabs along the token axis.
(One call for all query blocks, its skip a `pl.when` on the block's index
and its running statistics in VMEM scratch, read 27 ms where the
unwindowed walk over MORE keys reads 10.6: PERF.md §6, PR 30.) At the
trunk's 8192 keys a head's K and V are 2 × 2 MB, whole in VMEM and
double-buffered: the call asks for a 64 MiB scoped limit where the
default 16 would not hold them.

Two things the third token trunk (Kimi-Linear's latent attention) brought,
forward only too. **Values of another width than the keys**: K and V have
a BlockSpec each and the output takes V's width; each is padded to its OWN
whole lanes (a 24-wide key beside a 16-wide value at the toy sizes; where
the two widths are equal the program is the one it was). **A key part all
heads share, as an operand of its own** (PR 45): that trunk's queries and
keys are 128 lanes up-projected from the latent beside a 64-wide part that
is ONE vector a token for every head (LongCat-Flash's too, rotated). As one
192-wide head it was padded 192 → 256 in HBM, q and the keys, before every
call — a fifth of the time under the attention's stamp at 64 heads
(PERF.md §6, PR 45) — and the shared part was copied under every head.
With `shared=(qs, ks)` a score is the sum of two
products on whole lane blocks, q_h·k_hᵀ + qs_h·ksᵀ: q, k, v and o a head a
lane block as ever; ks (B, Lk, 64) laid twice side by side, (B, Lk, 128),
at block index (b, 0, 0) — fetched once a batch row, not once a head; qs
(B, Lq, H·64) fetched as the 128-lane block of the head PAIR, the other
head's half zeroed by one select a grid step. Every contraction is 128
deep and aligned; the MXU makes the two passes a key block it made on
256-lane heads (a 64-deep contraction takes a whole pass), so the kernel's
own time is what it was (on v5e 17.82 ms against 17.62 on pre-padded
256-lane heads, 2 × 4096 queries on 8192 keys of 64 heads) and the pad's
is gone (24.04 with it). `shared_part_fits` says for which widths; a
caller without the operand traces to the program it had.

Layout notes (pallas_guide.md "Tiling Constraints"):
  - the wrapper transposes nothing. (B, L, H, D) → (B, L, H·D) is a
    reshape of the trailing axes, and the output's way back the same;
    on the chip it costs nothing WHERE THE CALLER LAST WROTE ITS OPERAND
    AS (B, L, H·D) — a projection's product, an elementwise pass over it:
    the two reshapes cancel. A caller that works on the 4-D form (slices
    a head apart, concatenates along it, rotates pairs of its lanes) has
    its array in tiles of (H, D), or token-minor, and XLA re-lays it
    once for the kernel; the layers of models/token_denoiser.py that
    matter write their operands in the 3-D form for that reason.
  - lanes (a head's width) are padded to a multiple of 128 per head in
    place, (B, L, H, D) → (B, L, H, Dp) → (B, L, H·Dp), and the token axes
    to their blocks: one pad an operand that needs one (64-wide maps →
    128, the X-UNet's 16- and 64-wide heads; under the interpreter off the
    chip no lane is padded), none where none does (the latent trunks'
    128 + 64 go in as two operands, unpadded). Padding is masked inside
    the kernel with a
    statically-known length, and sliced off afterwards.
  - matmuls request `preferred_element_type=float32` so the MXU accumulates
    in f32 even for bf16 inputs; softmax runs in f32.

The backward pass is a custom VJP using the standard flash-attention
residuals (out, logsumexp): probabilities are recomputed from q·k and lse —
no (L, L) tensor is saved between forward and backward. Only the VJP's
forward writes lse; the primal call (a sampler takes no gradient) leaves
that output out, a kernel's output being nothing XLA can eliminate. For
head_dim ≥ _PALLAS_BWD_MIN_HEAD_DIM the backward runs as two blocked Pallas
kernels (_dq_kernel over query blocks, _dkv_kernel over kv blocks — scores
never leave VMEM); below that, lane padding (D → 128) wastes more MXU than
VMEM residency saves, and an XLA einsum backward (_flash_bwd_xla) is used
instead (measured on v5e at D=16: ~20% faster train step).

The forward's wrapper stamps what it does for every caller
(models/vocab.py, LAYER_PARTS): `pt.kernel` around each `flash_fwd` call
and nothing else, `pt.layout` around the pads and slices that feed it
and hand its result back, the windowed form's concatenation of its
calls' outputs among them (where an operand needs no pad nothing is left
under the stamp, and the part reads 0). Metadata only.

Falls back to interpreter mode off-TPU so the same code path is unit-tested
on the CPU mesh (tests/test_flash_attention.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from novel_view_synthesis_3d_tpu.ops import _pallas

_NEG_INF = -1e30


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# The forward's blocks (`forward_blocks`): past _SINGLE_BLOCK_KEYS padded
# keys a grid step walks the key axis _BLOCK_K at a time and takes up to
# _BLOCKED_Q query rows; up to it, one block is the key axis and the query
# block is the _BLOCK_Q it always was (the backward kernels' too).
_BLOCK_Q = 256
_SINGLE_BLOCK_KEYS = 1024
_BLOCK_K = 512
_BLOCKED_Q = 1024


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def forward_blocks(Lq: int, Lk: int, D: int, itemsize: int,
                   block_q: int = _BLOCKED_Q) -> tuple[int, int, int]:
    """(block_q, block_k, key_blocks) of the forward kernel for q (·, Lq, D)
    against k/v (·, Lk, D) of `itemsize` bytes an element: the whole of the
    choice, a function of shapes (the kernel's wrapper calls it).

    `key_blocks` > 1 is the blocked form (a running max and sum over
    `block_k` keys at a time); 1 is the plain body over the padded key
    axis. `block_q` caps the query block (rounded up to the 16 sublanes
    that cover the f32 and bf16 tile minima)."""
    # Neither moves the choice at a shape a caller has: the table that set
    # the blocks read D 128 and 256 in bfloat16 (PERF.md §6, PR 29).
    del D, itemsize
    lk = _round_up(Lk, 128)
    if lk <= _SINGLE_BLOCK_KEYS:
        bq, bk = _BLOCK_Q, lk
    else:
        bq, bk = _BLOCKED_Q, _BLOCK_K
    bq = min(bq, _round_up(block_q, 16), max(16, _round_up(Lq, 16)))
    return bq, bk, _round_up(lk, bk) // bk


def _band_blocks(Lk_pad: int, block_k: int, band, rows: int) -> list:
    """The key blocks a query block of `rows` rows visits under `band` =
    (window, position of its first row), last block first: [(lo, masked)],
    `masked` where some row of it does not see the whole block. A block no
    row sees is not listed."""
    window, first = band
    return [(lo, lo <= first + rows - 1 - window)
            for lo in range(Lk_pad - block_k, -1, -block_k)
            if lo + block_k - 1 > first - window]


def _attn_kernel(q_ref, k_ref, v_ref, *refs, scale: float, kv_len: int,
                 block_k: int, band=None, shared_width: int = 0):
    """One query block vs. one (batch, head)'s keys and values, all in VMEM,
    the key axis walked `block_k` at a time (unrolled: static slices).

    q_ref (1, Bq, D) · k_ref/v_ref (1, Lk_pad, D) · then o_ref (1, Bq, D) ·
    and `lse_out`, empty or one more output ref (1, Bq, 128) — lse
    broadcast across the lane dim to satisfy the TPU (sublane, lane) tiling
    constraint on output blocks. `kv_len` is the true (unpadded) kv length
    — static; the block that holds the boundary masks its padded columns
    (padding never fills a whole block, so no block's max is the mask's).

    `shared_width` > 0: two more inputs stand before o_ref, and a score is
    the sum of two products, q·kᵀ + qs·ksᵀ. qs_ref (1, Bq, 128) is the lane
    block that holds this head's `shared_width` lanes beside its
    neighbours' (head h's at lane (h mod 128/width)·width; the others are
    zeroed here, one select a grid step), ks_ref (1, Lk_pad, 128) the ONE
    key part all heads share, laid 128/width times side by side so that
    whichever lanes are this head's meet it: a contraction 128 deep on
    whole lane blocks, half of it zeros at a width of 64 — the MXU pass a
    64-deep one would take.

    `band` = (window, position of this block's first query row), static: a
    query at p sees key j iff j > p − window. The walk is then
    `_band_blocks`' — from the last key block down, so that a row has met
    keys it sees before any block that is masked for it; blocks no row
    sees are left out at trace time, blocks on the band's edge are masked,
    the others run bare."""
    q = q_ref[0]
    if shared_width:
        qs_ref, ks_ref, o_ref, *lse_out = refs
        lane = jax.lax.broadcasted_iota(jnp.int32, qs_ref.shape[1:], 1)
        mine = jax.lax.rem(pl.program_id(1), 128 // shared_width)
        qs = jnp.where(jax.lax.div(lane, shared_width) == mine, qs_ref[0],
                       jnp.zeros((), qs_ref.dtype))
    else:
        o_ref, *lse_out = refs
    m = l = acc = None
    if band is None:
        blocks = [(lo, False) for lo in range(0, k_ref.shape[1], block_k)]
    else:
        blocks = _band_blocks(k_ref.shape[1], block_k, band, q.shape[0])
    for lo, edge in blocks:
        s = jax.lax.dot_general(
            q, k_ref[0, lo:lo + block_k, :],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if shared_width:
            s = s + jax.lax.dot_general(
                qs, ks_ref[0, lo:lo + block_k, :],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        s = s * scale
        if kv_len < lo + block_k:  # mask padded kv columns (static)
            col = lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col < kv_len, s, _NEG_INF)
        if edge:                   # and the keys behind a row's window
            col = lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            row = band[1] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(col > row - band[0], s, _NEG_INF)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = m_blk if m is None else jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new)
        l_blk = jnp.sum(p, axis=-1, keepdims=True)
        o_blk = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, lo:lo + block_k, :],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if m is None:
            l, acc = l_blk, o_blk
        else:
            alpha = jnp.exp(m - m_new)
            l, acc = alpha * l + l_blk, alpha * acc + o_blk
        m = m_new
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    for lse_ref in lse_out:
        lse = m + jnp.log(l)  # (Bq, 1)
        lse_ref[0] = jnp.broadcast_to(lse, (lse.shape[0], lse_ref.shape[-1]))


def band_key_columns(Lq: int, Lk: int, window: int, q_offset: int,
                     block_q: int = _BLOCKED_Q) -> tuple[int, int]:
    """(key columns the banded walk visits, key columns the band lets
    through), each summed over one head's Lq query rows: from shapes
    alone, `_band_blocks` with `forward_blocks`' blocks. Their ratio is 1
    where every masked block is skipped and nothing visited is masked."""
    bq, bk, _ = forward_blocks(Lq, Lk, 0, 0, block_q)
    lk = _round_up(_round_up(Lk, 128), bk)
    visited = sum(
        len(_band_blocks(lk, bk, (window, q_offset + q0), bq)) * bk
        * min(bq, Lq - q0) for q0 in range(0, Lq, bq))
    visible = sum(Lk - max(q_offset + r - window + 1, 0) for r in range(Lq))
    return visited, visible


# Past this many bytes of one head's keys and values, fetched whole and
# double-buffered, the forward asks for more than the 16 MiB of scoped
# VMEM a kernel has by default (v5e has 128 MiB a core).
_KV_DEFAULT_VMEM_BYTES = 4 * 1024 * 1024
_LONG_KV_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _flash_fwd_padded(q, k, v, *, heads: tuple[int, int], scale: float,
                      kv_len: int, block_q: int, block_k: int,
                      with_lse: bool, interpret: bool, band=None,
                      shared=None):
    """q (B, Lq_pad, H·Dp) · k (B, Lk_pad, Hkv·Dp) · v (B, Lk_pad, Hkv·Dvp),
    `heads` = (H, Hkv), a head a block of lanes → (out (B, Lq_pad, H·Dvp),
    lse (B·H, Lq_pad) or None); the values' width may differ from the
    keys'. The grid is (batch, query head, query block), the query blocks
    innermost: K's and V's block index (b, 0, h // (H // Hkv)) does not
    change over a group's query heads and query blocks, so a key/value
    head is fetched once a group. `band` = (window, q_offset): one kernel
    call a query block then, each with its own static walk
    (`_attn_kernel`) and every head's block i in its grid, their
    (B, block_q, H·Dvp) slabs joined along the token axis. `shared` = (qs
    (B, Lq_pad, H·w), ks (B, Lk_pad, 128)), without a band only: the
    second product of a score (`_attn_kernel`) — qs's lane block of the
    128/w heads that h is one of, and ks at (b, 0, 0), an index that never
    changes over a row's heads and query blocks: fetched once a row."""
    B, Lq, Lk = q.shape[0], q.shape[1], k.shape[1]
    H, Hkv = heads
    group, D, Dv = H // Hkv, q.shape[2] // H, v.shape[2] // Hkv
    mem = {} if interpret else {"memory_space": _pallas.VMEM}
    extra = {}
    operands, shared_specs, shared_width = (q, k, v), [], 0
    if shared is not None:
        operands += shared
        shared_width = shared[0].shape[2] // H
        per = 128 // shared_width
        shared_specs = [
            pl.BlockSpec((1, block_q, 128),
                         lambda b, h, i: (b, i, jax.lax.div(h, per)), **mem),
            pl.BlockSpec((1, Lk, 128), lambda b, h, i: (b, 0, 0), **mem)]
    kv_lanes = D + Dv + (128 if shared_width else 0)
    if 2 * Lk * kv_lanes * k.dtype.itemsize > _KV_DEFAULT_VMEM_BYTES:
        extra["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_LONG_KV_VMEM_LIMIT_BYTES)
    def its_group(b, h, *_):  # both grids: (b, h) and (b, h, i)
        # lax.div, not `//`: floor division's sign handling is a dozen
        # scalar ops an index map, and seconds of lowering a sampler
        return b, 0, h if group == 1 else jax.lax.div(h, group)

    k_spec = pl.BlockSpec((1, Lk, D), its_group, **mem)
    v_spec = pl.BlockSpec((1, Lk, Dv), its_group, **mem)
    if band is not None:
        window, q_offset = band
        outs = []
        for i in range(Lq // block_q):
            with jax.named_scope("pt.kernel"):
                outs.append(pl.pallas_call(
                    functools.partial(_attn_kernel, scale=scale,
                                      kv_len=kv_len, block_k=block_k,
                                      band=(window, q_offset + i * block_q)),
                    grid=(B, H),
                    in_specs=[pl.BlockSpec((1, block_q, D),
                                           lambda b, h, i=i: (b, i, h),
                                           **mem),
                              k_spec, v_spec],
                    out_specs=pl.BlockSpec((1, block_q, Dv),
                                           lambda b, h: (b, 0, h), **mem),
                    out_shape=jax.ShapeDtypeStruct((B, block_q, H * Dv),
                                                   q.dtype),
                    name="flash_fwd", interpret=interpret, **extra,
                )(q, k, v))
        with jax.named_scope("pt.layout"):
            return jnp.concatenate(outs, axis=1), None
    kernel = functools.partial(_attn_kernel, scale=scale, kv_len=kv_len,
                               block_k=block_k, shared_width=shared_width)
    out_specs = [pl.BlockSpec((1, block_q, Dv), lambda b, h, i: (b, i, h),
                              **mem)]
    out_shape = [jax.ShapeDtypeStruct((B, Lq, H * Dv), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec(
            (1, block_q, 128), lambda b, h, i: (b * H + h, i, 0), **mem))
        out_shape.append(jax.ShapeDtypeStruct((B * H, Lq, 128), jnp.float32))
    with jax.named_scope("pt.kernel"):
        out, *lse = pl.pallas_call(
            kernel,
            grid=(B, H, Lq // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, h, i: (b, i, h),
                             **mem),
                k_spec, v_spec, *shared_specs,
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            name="flash_fwd",
            interpret=interpret,
            **extra,
        )(*operands)
    with jax.named_scope("pt.layout"):
        return out, (lse[0][:, :, 0] if with_lse else None)


def _use_interpret() -> bool:
    return _pallas.use_interpret()


def resolve_flash(flag) -> bool:
    """Resolve a use_flash_attention config value ('auto' | bool);
    see ops/_pallas.resolve_flag for the shared semantics."""
    return _pallas.resolve_flag(flag, "use_flash_attention")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_attention(q, k, v, scale: float, block_q: int):
    out, _ = _flash_fwd_core(q, k, v, scale, block_q, with_lse=False)
    return out


def _heads_side_by_side(x, block: int, lanes: int):
    """x (B, L, H, D) → (B, L', H·D'): the token axis padded to a multiple
    of `block` and every head, in place, to a multiple of `lanes` — one
    pad, none where neither is short —, then the heads side by side."""
    x = jnp.pad(x, ((0, 0), (0, -x.shape[1] % block), (0, 0),
                    (0, -x.shape[3] % lanes)))
    return x.reshape(*x.shape[:2], -1)


def shared_part_fits(heads: int, width: int, shared: int) -> bool:
    """Whether heads whose queries and keys are `width` lanes of their own
    beside `shared` lanes of ONE key part all heads share want the
    two-operand form (`flash_attention`'s `shared`): the own part whole
    lane blocks, the sum not — the per-head pad the one-operand form would
    write —, and the shared lanes of a whole number of heads a lane
    block."""
    return (width % 128 == 0 and 0 < shared < 128 and 128 % shared == 0
            and heads % (128 // shared) == 0)


def _flash_fwd_core(q, k, v, scale: float, block_q: int, *,
                    with_lse: bool, window=None, shared=None):
    """(B, L, H, D) inputs → padded kernel call → unpadded (out (B, Lq, H,
    Dv), lse (B, H, Lq)); lse is None unless asked for. The kernel reads
    q, k and v where they lie: the heads side by side, (B, L, H·D), a
    free reshape of what the caller holds, and writes the output the same
    way — nothing is transposed. k and v may have fewer heads than q
    (grouped-query attention): K and V are neither repeated in HBM nor
    fetched more than once a group. `window` is (size, q_offset) or None.
    v's last axis may be narrower or wider than q's and k's (latent
    attention with a key part no value has). A width that is no whole
    number of 128-lane blocks is padded per head in place, each operand
    to its own, and the token axes to their blocks: one pad an operand
    that needs one, none where none does. `shared` = (qs (B, Lq, H, w), ks
    (B, Lk, w)) where `shared_part_fits`: no lane of them is padded — qs's
    heads side by side as they are, ks laid 128/w times side by side
    (B, Lk, 128), one small array a call."""
    B, Lq, H, D = q.shape
    Lk, Hkv, Dv = k.shape[1], k.shape[2], v.shape[3]
    interpret = _use_interpret()
    bq, bk, _ = forward_blocks(Lq, Lk, D, q.dtype.itemsize, block_q)
    lanes = 1 if interpret else 128  # lane alignment for the MXU
    with jax.named_scope("pt.layout"):
        qt = _heads_side_by_side(q, bq, lanes)
        kt = _heads_side_by_side(k, bk, lanes)
        vt = _heads_side_by_side(v, bk, lanes)
        if shared is not None:
            qs, ks = shared
            shared = (_heads_side_by_side(qs, bq, 1),
                      jnp.tile(_pad_to(ks, 1, bk), 128 // ks.shape[-1]))
    out, lse = _flash_fwd_padded(
        qt, kt, vt, heads=(H, Hkv), scale=scale, kv_len=Lk, block_q=bq,
        block_k=bk, with_lse=with_lse, interpret=interpret, band=window,
        shared=shared)
    with jax.named_scope("pt.layout"):
        out = out.reshape(B, out.shape[1], H, -1)[:, :Lq, :, :Dv]
        if with_lse:
            lse = lse[:, :Lq].reshape(B, H, Lq)
    return out, lse


def _flash_vjp_fwd(q, k, v, scale: float, block_q: int):
    out, lse = _flash_fwd_core(q, k, v, scale, block_q, with_lse=True)
    return out, (q, k, v, out, lse)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dq_ref, *,
               scale: float, kv_len: int):
    """dq for one query block: recompute p from lse, ds = p·(dp−δ)·scale,
    dq = ds·K. q/do (1,Bq,D) · k/v (1,Lk,D) · lse/dlt (1,Bq,128)."""
    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if kv_len < k.shape[0]:
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < kv_len, s, _NEG_INF)
    p = jnp.exp(s - lse_ref[0][:, :1])                       # (Bq, Lk)
    dp = jax.lax.dot_general(
        do_ref[0], v_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - dlt_ref[0][:, :1]) * scale
    dq_ref[0] = jax.lax.dot_general(
        ds.astype(k.dtype), k, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, dk_ref,
                dv_ref, *, scale: float):
    """dk/dv for one kv block against the full query sequence.
    k/v (1,Bk,D) · q/do (1,Lq,D) · lse/dlt (1,Lq,128). Padded q rows carry
    lse=+inf ⇒ p=0 ⇒ they contribute nothing."""
    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (Lq, Bk)
    p = jnp.exp(s - lse_ref[0][:, :1])
    do = do_ref[0]
    dv_ref[0] = jax.lax.dot_general(
        p.astype(do.dtype), do, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(
        do, v_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (Lq, Bk)
    ds = p * (dp - dlt_ref[0][:, :1]) * scale
    dk_ref[0] = jax.lax.dot_general(
        ds.astype(q.dtype), q, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _flash_bwd_pallas(q, k, v, out, lse, g, scale: float,
                      block_q: int):
    """Blocked Pallas backward: one pass for dq (grid over q blocks), one
    for dk/dv (grid over kv blocks); no (Lq, Lk) tensor ever leaves VMEM."""
    block_q = min(block_q, _BLOCK_Q)
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    interpret = _use_interpret()
    g32 = g.astype(jnp.float32)
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1)  # (B, Lq, H)

    def to_nld(x, L):
        return x.transpose(0, 2, 1, 3).reshape(B * H, L, x.shape[-1])

    qt, kt, vt = to_nld(q, Lq), to_nld(k, Lk), to_nld(v, Lk)
    dot = to_nld(g, Lq)
    lse_n = lse.reshape(B * H, Lq)
    dlt_n = delta.transpose(0, 2, 1).reshape(B * H, Lq)

    block_q = ((block_q + 15) // 16) * 16
    bq = min(block_q, max(16, ((Lq + 15) // 16) * 16))
    bk = min(block_q, max(16, ((Lk + 15) // 16) * 16))
    qt = _pad_to(qt, 1, bq)
    dot = _pad_to(dot, 1, bq)
    # kv must pad to a common multiple of the block size AND the 128-lane
    # tile so the (Lk_p // bk) grid covers every row exactly — padding to
    # max(bk, 128) alone leaves a partial trailing block unwritten when bk
    # doesn't divide 128.
    kv_mult = bk * 128 // math.gcd(bk, 128)
    kt = _pad_to(kt, 1, kv_mult)
    vt = _pad_to(vt, 1, kv_mult)
    # Padded q rows: lse=+inf makes their probabilities exactly 0.
    Lq_p, Lk_p = qt.shape[1], kt.shape[1]
    lse_p = jnp.pad(lse_n, ((0, 0), (0, Lq_p - Lq)),
                    constant_values=jnp.inf)
    dlt_p = jnp.pad(dlt_n, ((0, 0), (0, Lq_p - Lq)))
    # Lane-broadcast lse/delta to (N, L, 128) to satisfy output/input tiling.
    lse_b = jnp.broadcast_to(lse_p[..., None], lse_p.shape + (128,))
    dlt_b = jnp.broadcast_to(dlt_p[..., None], dlt_p.shape + (128,))
    if not interpret:
        qt = _pad_to(qt, 2, 128)
        kt = _pad_to(kt, 2, 128)
        vt = _pad_to(vt, 2, 128)
        dot = _pad_to(dot, 2, 128)
    N, _, Dp = qt.shape
    mem = {} if interpret else {"memory_space": _pallas.VMEM}

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, kv_len=Lk),
        grid=(N, Lq_p // bq),
        in_specs=[
            pl.BlockSpec((1, bq, Dp), lambda n, i: (n, i, 0), **mem),
            pl.BlockSpec((1, Lk_p, Dp), lambda n, i: (n, 0, 0), **mem),
            pl.BlockSpec((1, Lk_p, Dp), lambda n, i: (n, 0, 0), **mem),
            pl.BlockSpec((1, bq, Dp), lambda n, i: (n, i, 0), **mem),
            pl.BlockSpec((1, bq, 128), lambda n, i: (n, i, 0), **mem),
            pl.BlockSpec((1, bq, 128), lambda n, i: (n, i, 0), **mem),
        ],
        out_specs=pl.BlockSpec((1, bq, Dp), lambda n, i: (n, i, 0), **mem),
        out_shape=jax.ShapeDtypeStruct((N, Lq_p, Dp), q.dtype),
        name="flash_dq",
        interpret=interpret,
    )(qt, kt, vt, dot, lse_b, dlt_b)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale),
        grid=(N, Lk_p // bk),
        in_specs=[
            pl.BlockSpec((1, Lq_p, Dp), lambda n, j: (n, 0, 0), **mem),
            pl.BlockSpec((1, bk, Dp), lambda n, j: (n, j, 0), **mem),
            pl.BlockSpec((1, bk, Dp), lambda n, j: (n, j, 0), **mem),
            pl.BlockSpec((1, Lq_p, Dp), lambda n, j: (n, 0, 0), **mem),
            pl.BlockSpec((1, Lq_p, 128), lambda n, j: (n, 0, 0), **mem),
            pl.BlockSpec((1, Lq_p, 128), lambda n, j: (n, 0, 0), **mem),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, Dp), lambda n, j: (n, j, 0), **mem),
            pl.BlockSpec((1, bk, Dp), lambda n, j: (n, j, 0), **mem),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, Lk_p, Dp), k.dtype),
            jax.ShapeDtypeStruct((N, Lk_p, Dp), v.dtype),
        ],
        name="flash_dkv",
        interpret=interpret,
    )(qt, kt, vt, dot, lse_b, dlt_b)

    def from_nld(x, L):
        return x[:, :L, :D].reshape(B, H, L, D).transpose(0, 2, 1, 3)

    return from_nld(dq, Lq), from_nld(dk, Lk), from_nld(dv, Lk)


def _flash_bwd_xla(q, k, v, out, lse, g, scale: float):
    """Einsum backward with p recomputed from lse. Materializes (Lq, Lk) in
    HBM, but for small head_dim XLA's unpadded contractions beat the Pallas
    kernels' 128-lane padding (measured on v5e at D=16: ~20% faster step)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    p = jnp.exp(s - lse[..., None])                      # (B,H,Lq,Lk)
    g32 = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, g32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", g32, v.astype(jnp.float32))
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1)  # (B,Lq,H)
    ds = p * (dp - delta.transpose(0, 2, 1)[..., None]) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32))
    return dq, dk, dv


# Below this head_dim the Pallas backward's lane padding (D → 128) wastes
# more MXU than the fused VMEM residency saves.
_PALLAS_BWD_MIN_HEAD_DIM = 64


def _flash_vjp_bwd(scale: float, block_q: int, res, g):
    q, k, v, out, lse = res
    if q.shape[-1] >= _PALLAS_BWD_MIN_HEAD_DIM or _use_interpret():
        dq, dk, dv = _flash_bwd_pallas(q, k, v, out, lse, g, scale, block_q)
    else:
        dq, dk, dv = _flash_bwd_xla(q, k, v, out, lse, g, scale)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention_forward_only(q, k, v, scale: float, block_q: int,
                                  window, shared):
    return _flash_fwd_core(q, k, v, scale, block_q, with_lse=False,
                           window=window, shared=shared)[0]


def _forward_only_fwd(q, k, v, scale, block_q, window, shared):
    return _flash_attention_forward_only(q, k, v, scale, block_q,
                                         window, shared), None


def _forward_only_bwd(scale, block_q, window, res, g):
    raise NotImplementedError(
        "flash_attention has no backward for grouped key/value heads, a "
        "window, values of another width than the keys or a shared key "
        "part yet: the dq and dk/dv kernels take one key/value head a "
        "query head, one width, one product a score and no band")


_flash_attention_forward_only.defvjp(_forward_only_fwd, _forward_only_bwd)


def window_binds(Lq: int, window: Optional[int], q_offset: int) -> bool:
    """Whether any of Lq queries, row i at position q_offset + i, has a
    key j ≥ 0 outside its band j > position − window."""
    return window is not None and q_offset + Lq - 1 - window >= 0


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    scale: Optional[float] = None,
                    block_q: int = _BLOCKED_Q,
                    window: Optional[int] = None,
                    q_offset: Optional[int] = None,
                    shared=None) -> jnp.ndarray:
    """Fused softmax(q·kᵀ/√D)·v. q (B, Lq, H, D), k (B, Lk, Hkv, D), v (B,
    Lk, Hkv, Dv) with H a multiple of Hkv: query head h reads key/value
    head h // (H // Hkv); → (B, Lq, H, Dv). Dv ≠ D is forward only.

    `shared` = (qs (B, Lq, H, w), ks (B, Lk, w)): every head's queries
    have w more lanes, which meet ONE key part that all heads share — head
    h's score is q_h·k_hᵀ + qs_h·ksᵀ, the attention of [q ‖ qs] over
    [k ‖ ks under every head] without either array being written
    (`shared_part_fits` says for which widths; the default scale is then
    (D + w)^−½). Forward only, and without a window.

    Drop-in for `flax.linen.dot_product_attention` (same layout/scaling)
    where Hkv = H and there is no window. `block_q` is an upper bound on
    the query (and the backward's kv) block; under it the shapes choose
    (`forward_blocks`).

    `window`: key j is at position j, query row i at `q_offset` + i
    (default Lk − Lq: the queries are the sequence's last), and a query at
    p sees key j iff j > p − window. The band is one-sided — keys past
    the query are the caller's to leave out or in; every query must see
    at least one key. Key blocks wholly outside a query block's band are
    not visited. Forward only, as are grouped heads.
    """
    B, Lq, H, D = q.shape
    Lk, Hkv = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError(f"{H} query heads do not divide into {Hkv} "
                         "key/value heads")
    q_offset = Lk - Lq if q_offset is None else int(q_offset)
    w = 0 if shared is None else shared[1].shape[-1]
    scale = float((D + w) ** -0.5) if scale is None else float(scale)
    if shared is not None:
        if window is not None or not shared_part_fits(H, D, w):
            raise ValueError(
                f"no shared key part of {w} lanes beside {H} heads of {D}"
                + (" under a window" if window is not None else ""))
        return _flash_attention_forward_only(q, k, v, scale, int(block_q),
                                             None, shared)
    if not window_binds(Lq, window, q_offset):
        if H == Hkv and v.shape[-1] == D:
            return _flash_attention(q, k, v, scale, int(block_q))
        window = None
    else:
        window = (int(window), q_offset)
    return _flash_attention_forward_only(q, k, v, scale, int(block_q),
                                         window, None)

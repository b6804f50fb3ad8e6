"""The short causal depthwise convolution in front of a recurrent layer's
scan (KDA's q, k, v; Mamba's u), with everything that sits between the
projection and the scan: the taps, a bias where there is one, SiLU, the
head-wise L2 norm and a scale where they are asked for, and the cast back
to the compute type.

    a_t = Σ_j w_j ⊙ x_{t−(K−1)+j} (+ b)      the last tap on the token itself
    y_t = SiLU(a_t)
    y_t ← scale · y_t / sqrt(Σ_head y_t² + eps)   per head of D/heads lanes,
                                                  where `heads` is given

**One Pallas kernel, `short_conv_fwd`; what the grid walks and what stays
in VMEM.** The grid is (row, block of lanes, run of RUN_ROWS tokens), the
runs last and in order. A step's x and y are (RUN_ROWS, lanes) blocks of
the model's own (B, L, D) arrays in the compute type — nothing is re-laid,
no float32 array of the sequence's size is written. The run's rows are
widened ONCE into a float32 scratch of RUN_ROWS + 8 rows whose first eight
hold the rows before the run's first: `tail` at run 0 (the rows before the
sequence's first, zeros where it starts here), the run's own last eight
after it — the convolution's carried state is K − 1 rows. The step is
walked in tiles of TILE_ROWS rows (a loop: traced and compiled once) by a
GROUP of lanes, so that a tile's taps, SiLU, square, lane reduction, `rsqrt`
and cast stay in registers. A group is a head where a head is whole
128-lane blocks (KDA's 128), else the fewest whole heads that fill whole
lane blocks — four heads of 96 lanes or two of 192 are 384: packing heads
into lane blocks is the kernel's business, and every load, roll and store
stays lane-aligned; a head's Σ y² is then the reduction of its own lanes
with the group's other heads masked. Where no multiple of the group
divides the width (30 heads of 96: 2880 = 7.5 × 384) the last grid step's
block hangs over the array's edge: what it reads past the edge belongs to
no head that exists and is never written. A tile is loaded with the eight rows before it; a
tap is that block rolled down the sublanes by its distance (`pltpu.roll`:
float32 rows shift by one, a packed bfloat16 tile does not), the first
eight rows dropped — on the chip faster than loading the scratch at a
sublane offset a tap (PERF.md §6, PR 39).
Everything is float32 from the widening to the one cast; a head's Σ y² is a
lane reduction in float32.

The new tail — the last K − 1 rows of [tail ; x] — is three rows and XLA's.
A length that is not whole runs pays a pad and a slice. `short_conv` stamps
`pt.kernel` around the `short_conv_fwd` call and nothing else, `pt.layout`
around what feeds it and hands its result back (the tail's eight float32
rows, the pad and its slice, the new tail) — models/vocab.py, LAYER_PARTS;
metadata only. Off the TPU the same kernel runs through the Pallas
interpreter (ops/_pallas.py's contract), at any width and in the same
groups; compiled, the width is whole heads, or whole 128-lane blocks where
no norm is asked for.

Forward only: a gradient through `short_conv` raises by name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from novel_view_synthesis_3d_tpu.ops import _pallas

RUN_ROWS = 512    # tokens a grid step
RUN_LANES = 1024  # lanes a grid step, where the width divides
TILE_ROWS = 64    # rows of a head's lanes taken through the arithmetic at once
CARRY = 8         # float32 rows kept of the run before: one sublane tile
_INTERPRET_ROWS = 16


def _conv_kernel(*refs, taps: int, rows: int, tile: int, head: int,
                 group: int, norm: bool, has_bias: bool, eps: float,
                 scale: float):
    """One (row, lane block, run of `rows` tokens). Blocks: x, y (1, rows,
    W) — the block's lanes of the model's own (B, L, D) arrays —, the taps
    (K, W) and the bias (1, W) float32, `tail` (1, CARRY, W) float32 with
    the K − 1 rows before the sequence's first LAST; `ext_ref` (CARRY +
    rows, W) float32 is [the rows before the run ; the run], its first
    CARRY rows resident while the grid walks a (row, lane block)'s runs."""
    x_ref, w_ref = refs[:2]
    b_ref = refs[2] if has_bias else None
    tail_ref, o_ref, ext_ref = refs[-3:]
    f32 = jnp.float32
    W = x_ref.shape[2]

    @pl.when(pl.program_id(2) == 0)
    def _enter():
        ext_ref[0:CARRY, :] = tail_ref[0]

    ext_ref[CARRY:CARRY + rows, :] = x_ref[0].astype(f32)

    def through(i, carry):
        """Tile i of the run's rows, a head's lanes at a time."""
        r0 = pl.multiple_of(i * tile, tile)
        for c0 in range(0, W, group):
            lanes = slice(c0, min(c0 + group, W))
            w = w_ref[:, lanes]
            # The tile's rows behind the CARRY before them: a tap is the
            # block rolled down by its distance, those first rows dropped.
            block = ext_ref[pl.ds(r0, CARRY + tile), lanes]
            a = None
            for j in range(taps):
                back = taps - 1 - j
                term = (pltpu.roll(block, back, 0) if back else block)[
                    CARRY:] * w[j:j + 1]
                a = term if a is None else a + term
            if has_bias:
                a = a + b_ref[:, lanes]
            y = a * jax.nn.sigmoid(a)
            if norm and head == group:
                y = y * (jax.lax.rsqrt(
                    jnp.sum(y * y, axis=1, keepdims=True) + eps) * scale)
            elif norm:
                # Several heads share the group's lane blocks: a head's
                # Σ y² is the reduction of its own lanes, the others
                # masked, laid back over them.
                lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
                sq, ss = y * y, jnp.zeros_like(y)
                for h0 in range(0, y.shape[1], head):
                    own = (lane >= h0) & (lane < h0 + head)
                    ss = jnp.where(own, jnp.sum(
                        jnp.where(own, sq, 0.0), axis=1, keepdims=True), ss)
                y = y * (jax.lax.rsqrt(ss + eps) * scale)
            elif scale != 1.0:
                y = y * scale
            o_ref[0, pl.ds(r0, tile), lanes] = y.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // tile, through, 0)
    ext_ref[0:CARRY, :] = ext_ref[rows:rows + CARRY, :]


def _blocks(L: int, D: int, heads,
            interpret: bool) -> tuple[int, int, int, int, int]:
    """(rows a run, rows a tile, lanes a grid step, lanes a head, lanes a
    GROUP: what the kernel takes through the arithmetic at once — a head
    that is whole 128-lane blocks, else the fewest whole heads that fill
    whole lane blocks: four heads of 96 or two of 192 are 384 lanes) from
    the shapes alone."""
    head = D // heads if heads else 128
    if heads and D % heads:
        raise ValueError(f"short_conv: {heads} heads do not divide D={D}")
    group = _pallas.head_group(head)
    if interpret:   # any width, whole; short runs, so the carry is walked
        rows = min(_INTERPRET_ROWS, -(-L // 8) * 8)
        return rows, rows, D, head if heads else D, group if heads else D
    if not heads and D % 128:
        raise ValueError(
            f"short_conv_fwd on the chip takes whole 128-lane blocks of "
            f"(B, L, D) where no head is given; got D={D}")
    rows = min(RUN_ROWS, -(-L // TILE_ROWS) * TILE_ROWS)
    return (rows, TILE_ROWS, _pallas.lanes_a_step(D, group, RUN_LANES), head,
            group)


@functools.partial(jax.jit, static_argnames=("heads", "scale", "eps",
                                             "interpret"))
def _conv_call(x, w, bias, tail, *, heads, scale: float, eps: float,
               interpret: bool):
    B, L, D = x.shape
    K = w.shape[0]
    rows, tile, lanes, head, group = _blocks(L, D, heads, interpret)
    pad = (-L) % rows
    if pad:   # rows after the last: their y is sliced away
        with jax.named_scope("pt.layout"):
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    tokens = pl.BlockSpec((1, rows, lanes), lambda b, c, r: (b, r, c))

    def channels(n):
        return pl.BlockSpec((n, lanes), lambda b, c, r: (0, c))

    operands = [x, w] + ([] if bias is None else [bias]) + [tail]
    with jax.named_scope("pt.kernel"):
        y = pl.pallas_call(
            functools.partial(_conv_kernel, taps=K, rows=rows, tile=tile,
                              head=head, group=group,
                              norm=heads is not None,
                              has_bias=bias is not None, eps=eps,
                              scale=scale),
            out_shape=jax.ShapeDtypeStruct((B, L + pad, D), x.dtype),
            grid=(B, -(-D // lanes), (L + pad) // rows),
            in_specs=[tokens, channels(K)]
            + ([] if bias is None else [channels(1)])
            + [pl.BlockSpec((1, CARRY, lanes), lambda b, c, r: (b, 0, c))],
            out_specs=tokens,
            scratch_shapes=[_pallas.VMEM((CARRY + rows, lanes),
                                         jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name="short_conv_fwd", interpret=interpret,
        )(*operands)
    if pad:
        with jax.named_scope("pt.layout"):
            y = y[:, :L]
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _conv(x, w, bias, tail, heads, scale, eps):
    return _conv_call(x, w, bias, tail, heads=heads, scale=scale, eps=eps,
                      interpret=_pallas.use_interpret())


def _conv_fwd(x, w, bias, tail, heads, scale, eps):
    return _conv(x, w, bias, tail, heads, scale, eps), None


def _conv_bwd(heads, scale, eps, res, ct):
    raise NotImplementedError(
        "short_conv has no backward yet: the convolution's VJP (the taps "
        "reversed, through SiLU and the head norm) is not written")


_conv.defvjp(_conv_fwd, _conv_bwd)


def short_conv(x, w, tail=None, bias=None, *, heads=None, scale=1.0,
               eps=1e-6):
    """SiLU of the causal depthwise convolution over the sequence, a_t =
    Σ_j w_j ⊙ x_{t−(K−1)+j} (+ `bias` (D,) where one is given: Mamba's;
    KDA's has none), then — where `heads` is given — each of `heads` equal
    blocks of the last axis divided by sqrt(Σ y² + eps), and `scale`. x
    (B, L, D), w (K, D), `tail` (B, K−1, D) the rows before x's first
    (zeros where None: the sequence starts here). Float32 from x's
    widening to the one cast. → (y (B, L, D) in x's type, the last K−1
    rows of [tail ; x], which a continuation takes as its `tail`)."""
    B, L, D = x.shape
    K = w.shape[0]
    if not 2 <= K <= CARRY + 1:
        raise ValueError(f"short_conv carries K − 1 rows in one tile of "
                         f"{CARRY}; got K={K}")
    f32 = jnp.float32
    with jax.named_scope("pt.layout"):
        if tail is None:
            tail = jnp.zeros((B, K - 1, D), x.dtype)
        tail = tail.astype(x.dtype)
        carried = jnp.pad(tail.astype(f32),
                          ((0, 0), (CARRY - (K - 1), 0), (0, 0)))
        new_tail = jnp.concatenate([tail, x], axis=1)[:, L:]
    y = _conv(x, w.astype(f32), None if bias is None
              else bias.astype(f32)[None], carried,
              None if heads is None else int(heads), float(scale),
              float(eps))
    return y, new_tail

"""What a delta-rule layer does to its scan's output before `o`: the
head-wise RMS norm of o under the output gate, with the gate's activation
and the cast back to the compute type.

    y = o / sqrt(mean_head(o²) + eps) · scale · act(gate)     per head of
                                                              D/heads lanes

`scale` (d,) is the one weight the heads share; `act` is the logistic
(Kimi-Linear's KDA) or SiLU (Gated DeltaNet), exact; `gate` comes as its
projection left it, BEFORE the activation.

**One Pallas kernel, `head_norm_fwd`; what the grid walks and what stays in
VMEM.** The grid is (row, run of tokens, block of lanes), every step on its
own. A step's o, gate and y are (rows, lanes) blocks of the model's own
(B, L, H·d) arrays — o in float32 as the scan's kernel wrote it, gate and y
in the compute type —: nothing is reshaped, padded or re-laid in HBM, and no
float32 array of the sequence's size is written. A step is walked in tiles
of TILE_ROWS rows (a loop: traced and compiled once) by a GROUP of lanes:
the fewest whole heads that fill whole 128-lane blocks (a head of 128 or
256 lanes by itself; two of 192 or four of 96 are 384; two of 64 are 128),
so every load and store is lane-aligned. A head's Σ o² is gathered a lane
block at a time: a block that lies inside one head is reduced whole (the
XLU's lane reduction), one that two heads share once a head with the
other's lanes masked; 1 / sqrt(Σ / d + eps) is laid back over the head's
lanes the same way. Then ((o · that) · scale) · act(gate), float32
throughout in the plain form's order — only the order of a head's sum of
squares differs from it —, and ONE cast. Where no multiple of the group
divides the width (30 heads of 96, five of 192) the last step's block
hangs over the array's edge, and where the tokens are not whole runs the
last run's does: what is read there belongs to no head and no token that
exists, enters no sum of one that does, and is never written.

`gated_head_norm` stamps `pt.kernel` around the `head_norm_fwd` call and
nothing else (models/vocab.py, LAYER_PARTS; metadata only); the scale laid
out a step's lanes wide (a few KB) is its caller's remainder. Off the TPU
the same kernel runs through the Pallas interpreter (ops/_pallas.py's
contract), in the same blocks.

Forward only: a gradient through `gated_head_norm` raises by name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from novel_view_synthesis_3d_tpu.ops import _pallas

RUN_LANES = 2048      # lanes a grid step, where the width divides
RUN_ELEMENTS = 2**19  # rows × lanes a grid step: o's block is 2 MiB
TILE_ROWS = 32        # rows of a group's lanes taken through the arithmetic
ACTIVATIONS = ("sigmoid", "silu")


def _inverse_rms(blocks, head: int, eps: float):
    """1 / sqrt(mean of squares + eps) of each head of a group, laid over
    the group's lanes: `blocks` are the group's 128-lane blocks (T, 128)
    float32 in order, heads of `head` lanes side by side across them. →
    one (T, 128) or (T, 1) factor a block."""
    f32 = jnp.float32
    lane = jax.lax.broadcasted_iota(jnp.int32, blocks[0].shape, 1)
    sums = {}
    shared = []   # per block: [(head, its first lane in the block, its end)]
    for b, x in enumerate(blocks):
        sq = x * x
        shared.append([(h, max(h * head - b * 128, 0),
                        min((h + 1) * head - b * 128, 128))
                       for h in range(b * 128 // head,
                                      ((b + 1) * 128 - 1) // head + 1)])
        for h, lo, hi in shared[-1]:
            own = sq if (lo, hi) == (0, 128) else jnp.where(
                (lane >= lo) & (lane < hi), sq, 0.0)
            part = jnp.sum(own, axis=1, keepdims=True)
            sums[h] = part if h not in sums else sums[h] + part
    inv = {h: jax.lax.rsqrt(s / f32(head) + eps) for h, s in sums.items()}
    laid = []
    for here in shared:
        r = inv[here[0][0]]
        for h, lo, _ in here[1:]:
            r = jnp.where(lane >= lo, inv[h], r)
        laid.append(r)
    return laid


def _norm_kernel(o_ref, g_ref, w_ref, y_ref, *, tile: int, head: int,
                 group: int, eps: float, activation: str):
    """One (row, run of tokens, block of lanes). Blocks: o (1, rows, W)
    float32, the gate's projection and y (1, rows, W) in the compute type —
    the block's lanes of the model's own (B, L, H·d) arrays —, the scale
    (1, W) float32, a head's weights repeated."""
    f32 = jnp.float32
    rows, W = o_ref.shape[1:]

    def through(i, carry):
        """Tile i of the run's rows, a group's lanes at a time."""
        at = pl.ds(pl.multiple_of(i * tile, tile), tile)
        for c0 in range(0, W, group):
            lanes = [slice(c, c + 128) for c in range(c0, c0 + group, 128)]
            xs = [o_ref[0, at, c] for c in lanes]
            for c, x, inv in zip(lanes, xs, _inverse_rms(xs, head, eps)):
                g = g_ref[0, at, c].astype(f32)
                act = jax.nn.sigmoid(g)
                if activation == "silu":
                    act = g * act
                y_ref[0, at, c] = (x * inv * w_ref[:, c] * act).astype(
                    y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // tile, through, 0)


def _blocks(L: int, D: int, heads: int) -> tuple[int, int, int, int]:
    """(rows a grid step, lanes a grid step, lanes a head, lanes a group)
    from the shapes alone."""
    if D % heads:
        raise ValueError(f"gated_head_norm: {heads} heads do not divide "
                         f"D={D}")
    head = D // heads
    group = _pallas.head_group(head)
    lanes = _pallas.lanes_a_step(D, group, RUN_LANES)
    rows = max(RUN_ELEMENTS // lanes // TILE_ROWS, 1) * TILE_ROWS
    return min(rows, -(-L // TILE_ROWS) * TILE_ROWS), lanes, head, group


@functools.partial(jax.jit, static_argnames=("heads", "eps", "activation",
                                             "interpret"))
def _norm_call(o, gate, scale, *, heads: int, eps: float, activation: str,
               interpret: bool):
    B, L, D = o.shape
    rows, lanes, head, group = _blocks(L, D, heads)
    tokens = pl.BlockSpec((1, rows, lanes), lambda b, r, c: (b, r, c))
    weights = jnp.tile(scale.astype(jnp.float32), lanes // head)[None]
    with jax.named_scope("pt.kernel"):
        return pl.pallas_call(
            functools.partial(_norm_kernel, tile=TILE_ROWS, head=head,
                              group=group, eps=eps, activation=activation),
            out_shape=jax.ShapeDtypeStruct((B, L, D), gate.dtype),
            grid=(B, -(-L // rows), -(-D // lanes)),
            in_specs=[tokens, tokens,
                      pl.BlockSpec((1, lanes), lambda b, r, c: (0, 0))],
            out_specs=tokens,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            name="head_norm_fwd", interpret=interpret,
        )(o, gate, weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm(o, gate, scale, heads, eps, activation):
    return _norm_call(o, gate, scale, heads=heads, eps=eps,
                      activation=activation,
                      interpret=_pallas.use_interpret())


def _norm_fwd(o, gate, scale, heads, eps, activation):
    return _norm(o, gate, scale, heads, eps, activation), None


def _norm_bwd(heads, eps, activation, res, ct):
    raise NotImplementedError(
        "gated_head_norm has no backward yet: the VJP of the head-wise RMS "
        "norm under its gate is not written")


_norm.defvjp(_norm_fwd, _norm_bwd)


def gated_head_norm(o, gate, scale, *, heads: int, eps: float,
                    activation: str):
    """RMSNorm of o over each of `heads` equal blocks of the last axis,
    times `scale` (D/heads,) and `activation` ("sigmoid" | "silu") of
    `gate`: o (B, L, D) float32 as the scan left it, `gate` (B, L, D) the
    gate's projection BEFORE its activation. Float32 from the gate's
    widening to the one cast. → (B, L, D) in gate's type."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"gated_head_norm: activation={activation!r} is "
                         f"none of {ACTIVATIONS}")
    if o.shape != gate.shape or scale.shape != (o.shape[-1] // heads,):
        raise ValueError(
            f"gated_head_norm: o {o.shape}, gate {gate.shape} and scale "
            f"{scale.shape} are not (B, L, D) twice and (D / {heads},)")
    return _norm(o.astype(jnp.float32), gate, scale, int(heads), float(eps),
                 activation)

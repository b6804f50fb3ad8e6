"""Mamba-1's selective scan: a diagonal recurrence with an input-dependent
step, walked in chunks from an initial state.

The recurrence, a row at a time (C channels, N states a channel; u_t, Δ_t ∈
R^C, B_t, C_t ∈ R^N, A ∈ R^(C × N) negative, D ∈ R^C, the state s ∈ R^(C ×
N) in float32):

    s_t = exp(Δ_t ⊙ A) ⊙ s_{t−1} + (Δ_t ⊙ u_t) B_tᵀ     Δ_t, u_t over the N
    m_t = s_t C_t + D ⊙ u_t                             states, B_t over C

The decay is per channel AND per state and changes with the token, so the
scan does not reduce to matrix products (ops/kda.py's chunked form needs a
decay shared across the value axis); an `associative_scan` over the
sequence writes (L, C, N) float32 several times — 1.34 GB a row at L 4096,
C 5120, N 16. It is 2N exponentials-and-multiplies a channel a token on
the VPU and EUP, and nothing else.

**One Pallas kernel, `ssm_fwd`; what the grid walks and what stays in
VMEM.** The grid is (row, block of `CHANNELS` channels, chunk of `CHUNK`
tokens), the chunks last and in order. Resident while the grid walks a
(row, channel block)'s chunks: that block's state, (N, channels) float32
— kept TRANSPOSED, the N states on sublanes and a channel a lane, so that
Δ_t and u_t, rows of the model's own (B, L, C) arrays, broadcast over
sublanes as they lie, and the sum over states is a sublane reduction. It
is loaded from `s0` at the first chunk and written to `s_L` at the last,
never rounded. A chunk's u (compute type), Δ (float32) and m (float32) are
(CHUNK, channels) blocks of the (B, L, C) arrays, nothing re-laid; B and C
come TRANSPOSED, (B, N, L), a chunk's block (N, CHUNK), so that token t's
B_t is a column — one lane, broadcast over the channels' lanes — (the two
transposes are 0.5 MB a row-layer, stamped `pt.layout`). Inside a chunk
the tokens are walked one at a time, unrolled at trace time (static
slices): exp(Δ_t ⊙ A) and Δ_t u_t B_tᵀ do not wait for the state, only
the multiply-add does. A ragged length is padded with Δ = 0, u = 0 rows,
which the state passes unchanged.

**The decay never leaves the exponent's safe side**: Δ ≥ 0 (a softplus)
and A < 0, so Δ_t ⊙ A ≤ 0 and exp(·) ∈ (0, 1]; an underflow to 0 is the
value. Everything is float32 (`ssm_state_precision`): u is widened in
VMEM as it arrives.

The operations and bytes counted for its roofline share
(benchmarks/flops_tokens_ssm.py) are of the recurrence above, whatever
implements it. Off the TPU the same kernel runs through the Pallas
interpreter (ops/_pallas.py's contract), at any width; compiled, the
channels must be whole 128-lane blocks.

`selective_scan` stamps `pt.kernel` around the `ssm_fwd` call and nothing
else, `pt.layout` around what feeds it and hands its result back (the
transposes of B, C and A, the casts, the pad to whole chunks and its
slice) — models/vocab.py, LAYER_PARTS; metadata only.

Forward only: a gradient through `selective_scan` raises by name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from novel_view_synthesis_3d_tpu.ops import _pallas

CHUNK = 128      # tokens a grid step: a lane block of Bᵀ and Cᵀ
CHANNELS = 512   # channels a grid step: the state block is (N, 512) float32
_INTERPRET_CHUNK = 16


def _ssm_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s0_ref, m_ref,
                sl_ref, st_ref, *, chunk: int):
    """One (row, channel block, chunk). Blocks: u, Δ, m (1, chunk, Cb) —
    the block's lanes of the model's own (B, L, C) arrays —, Aᵀ (N, Cb), D
    (1, Cb), Bᵀ, Cᵀ (1, N, chunk), s0 / s_L (1, N, Cb); `st_ref` (N, Cb)
    float32 is the state, resident while the grid walks a (row, channel
    block)'s chunks."""
    f32 = jnp.float32
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _enter():
        st_ref[...] = s0_ref[0]

    A, D = a_ref[...], d_ref[...]
    bt, ct = b_ref[0], c_ref[0]                      # (N, chunk)
    s = st_ref[...]
    for t in range(chunk):
        dt = dt_ref[0, t:t + 1, :]                   # (1, Cb)
        x = u_ref[0, t:t + 1, :].astype(f32)
        s = jnp.exp(dt * A) * s + (dt * x) * bt[:, t:t + 1]
        m_ref[0, t:t + 1, :] = jnp.sum(s * ct[:, t:t + 1], axis=0,
                                       keepdims=True) + D * x
    st_ref[...] = s

    @pl.when(step == pl.num_programs(2) - 1)
    def _leave():
        sl_ref[0] = s


def _blocks(L: int, C: int, interpret: bool) -> tuple[int, int]:
    """(tokens a chunk, channels a block) from the shapes alone."""
    if interpret:  # any width; short chunks (the walk is unrolled)
        return min(_INTERPRET_CHUNK, -(-L // 8) * 8), C
    if C % 128:
        raise ValueError(
            f"ssm_fwd on the chip takes channels that are whole 128-lane "
            f"blocks of (B, L, C); got C={C}")
    return CHUNK, max(c for c in range(128, CHANNELS + 1, 128) if C % c == 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_call(u, dt, At, Bt, Ct, D, s0, *, interpret: bool):
    B, L, C = u.shape
    N = At.shape[0]
    chunk, cb = _blocks(L, C, interpret)
    pad = (-L) % chunk
    if pad:   # Δ = 0, u = 0 rows: the state passes them unchanged
        with jax.named_scope("pt.layout"):
            u, dt = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (u, dt))
            Bt, Ct = (jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
                      for x in (Bt, Ct))
    tokens = pl.BlockSpec((1, chunk, cb), lambda b, c, t: (b, t, c))
    columns = pl.BlockSpec((1, N, chunk), lambda b, c, t: (b, 0, t))
    state = pl.BlockSpec((1, N, cb), lambda b, c, t: (b, 0, c))
    with jax.named_scope("pt.kernel"):
        m, sL = pl.pallas_call(
            functools.partial(_ssm_kernel, chunk=chunk),
            out_shape=(jax.ShapeDtypeStruct((B, L + pad, C), jnp.float32),
                       jax.ShapeDtypeStruct((B, N, C), jnp.float32)),
            grid=(B, C // cb, (L + pad) // chunk),
            in_specs=[tokens, tokens,
                      pl.BlockSpec((N, cb), lambda b, c, t: (0, c)),
                      columns, columns,
                      pl.BlockSpec((1, cb), lambda b, c, t: (0, c)),
                      state],
            out_specs=(tokens, state),
            scratch_shapes=[_pallas.VMEM((N, cb), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name="ssm_fwd", interpret=interpret,
        )(u, dt, At, Bt, Ct, D, s0)
    with jax.named_scope("pt.layout"):
        return m[:, :L], sL


@jax.custom_vjp
def _ssm(u, dt, At, Bt, Ct, D, s0):
    return _ssm_call(u, dt, At, Bt, Ct, D, s0,
                     interpret=_pallas.use_interpret())


def _ssm_fwd(*args):
    return _ssm(*args), None


def _ssm_bwd(res, ct):
    raise NotImplementedError(
        "selective_scan has no backward yet: the scan's VJP (the reverse "
        "walk over the tokens with ds carried) is not written")


_ssm.defvjp(_ssm_fwd, _ssm_bwd)


def selective_scan(u, dt, A, B, C, D, s0=None):
    """Mamba-1's selective scan over a sequence. u (B, L, C) the
    convolved, activated input; Δ `dt` (B, L, C) ≥ 0 (after its softplus);
    A (C, N) negative; B, C (B, L, N); D (C,); `s0` (B, N, C) the state
    the sequence is entered with (zeros where None) — kept TRANSPOSED, a
    channel a lane. → (m (B, L, C) float32 with the D term, the state
    after the last token (B, N, C) float32). L need not be a multiple of
    CHUNK."""
    f32 = jnp.float32
    rows, _, width = u.shape
    with jax.named_scope("pt.layout"):
        if s0 is None:
            s0 = jnp.zeros((rows, A.shape[1], width), f32)
        At = A.astype(f32).T
        Bt, Ct = (jnp.swapaxes(x.astype(f32), 1, 2) for x in (B, C))
        dt, D, s0 = dt.astype(f32), D.astype(f32)[None], s0.astype(f32)
    return _ssm(u, dt, At, Bt, Ct, D, s0)

"""Kimi Delta Attention's sequence operator: the gated delta rule with a
per-channel decay, computed in chunks from an initial state. (The short
convolution in front of it, with its SiLU and the head-wise L2 norms, is
ops/short_conv.py's kernel, which hands q, k, v over as this one takes
them.)

The recurrence, a head at a time (q, k of width d_k, v of width d_v, a
state S (d_k, d_v) in float32, g_t ≤ 0 the log-decay PER CHANNEL of d_k,
β_t a scalar):

    S′  = Diag(exp g_t)·S_{t−1}         every channel forgets at its rate
    u_t = β_t·(v_t − S′ᵀ k_t)           what the state does not yet say of
    S_t = S′ + k_t u_tᵀ                 k_t is written, β_t of it
    o_t = S_tᵀ q_t

A token at a time that is L dependent rank-1 updates. `kda_chunked` walks
the sequence C = 64 tokens at a time instead. With G_r = Σ_{s≤r} g_s inside
a chunk entered at S_0:

    A   = Diag(β)·strict_tril[ Σ_c k_r[c]·k_i[c]·exp(G_r[c] − G_i[c]) ]
    T   = (I + A)⁻¹                      unit lower triangular, (C, C)
    U   = T·Diag(β)·(V − (K ⊙ exp G)·S_0)
    O   = (Q ⊙ exp G)·S_0 + tril[ Σ_c q_r[c]·k_i[c]·exp(G_r[c] − G_i[c]) ]·U
    S_C = Diag(exp G_C)·S_0 + (K ⊙ exp(G_C − G))ᵀ·U

**One Pallas kernel, `kda_fwd`; what the grid walks and what stays in
VMEM.** The grid is (row, pair of heads, run of 4 chunks), the runs last
and in order. A head is a 128-lane block of the model's own (B, L, H·d)
arrays — q, k, v in the compute type, g in float32, o in float32 — so
nothing is re-laid for the kernel; β (B, L, H) comes whole and a head's
column is picked out of it. Resident while the grid walks a (row, heads)'s
runs: the heads' states, (d_v, d_k) float32 each (kept TRANSPOSED: a
channel's decay is then a lane's factor), loaded from `S0` at the first
run and written to `S_L` at the last; they are never rounded. Inside a
run everything lives in VMEM and registers: G, the decayed operands, the
two (C, C) matrices, T. What no state enters is made for the run at once,
two chunks side by side wherever a product's operand is (C, C) — their
matrices lie block-diagonally in one (128, 128), so the product fills an
MXU tile with both —: A's sub-blocks and T's of 16 rows by substitution
on the VPU, the sub-blocks against earlier ones and T's merges on the MXU.
Then the chunks in sequence, four products each: [K̄ ; Q̄]·S, U = T·(β ⊙
(V − K̄·S)), O = Q̄·S + A_qk·U, S ← Diag(e^{G_C})·S + K̂ᵀ·U. The two heads
of a grid step share no number; side by side, one's products run while
the other's wait for their operands.

**The decay never leaves the exponent's safe side.** exp(G_r − G_i) with
i ≤ r is at most 1, but factored as (k_r·exp G_r)·(k_i·exp(−G_i)) the
second factor overflows float32 wherever a channel decays fast (−G passes
88 within a chunk as soon as g < −1.4 a token; the source's A reaches 16).
So a chunk is cut into sub-blocks of 16 rows. A sub-block against an
EARLIER one is a product of two factors that are both at most 1, taken
against the later block's first row r₀: (k_r·exp(G_r − G_{r₀})) ·
(k_i·exp(G_{r₀} − G_i)); a sub-block against itself is summed channel by
channel from the differences themselves, masked before the exponential.
exp(G), exp(G_C − G) and exp(G_C) have non-positive exponents as they are.
An underflow to 0 is the value. T is built by substitution — rows inside a
sub-block, then block halves (T₂₁ = −T₂₂·A₂₁·T₁₁) — and not as the series
Σ(−A)ⁿ, whose terms grow combinatorially for near-parallel keys (a mostly
white frame's are) before they cancel.

**Every product is the configuration's float32**
(`kda_state_precision`: the state, the decays, β and the whole scan):
float32 operands at `Precision.HIGHEST` — six MXU passes of bfloat16
parts — into a float32 accumulator, the inverse and every exponential in
float32; q, k, v are widened in VMEM as they arrive. No product takes
fewer passes: Mosaic refuses `Precision.HIGH`, and a single pass of
bfloat16 operands is another result (at the cell's shape o moves by 3e-3
of its largest value and the state by 8e-3, where the six passes stay
within 2e-6 of the same scan in XLA's float32). The kernel is bound by those
passes on 64- and 128-row operands (PERF.md §6, PR 35, has the chip's
split); the operations and bytes counted for its roofline share
(benchmarks/flops_tokens_kda.py) are of the chunked form above in ONE
pass over triangles, whatever implements it. Off the TPU the same kernel
runs through the Pallas interpreter (ops/_pallas.py's contract), at any
head width; compiled, THIS kernel's head must be whole 128-lane blocks
(the convolution in front of it no longer asks that: ops/short_conv.py
packs narrower heads into lane blocks, and ops/gdn.py's kernel slices
heads of 96 and 192 lanes out of them). What the two delta-rule kernels
share —
the float32 product (and its form at fewer passes for operands that arrive
in bfloat16, which only the scalar decay's kernel has), a block's placement
among zeros, the upper levels of the triangular inverse — is
ops/_delta_rule.py's.

`kda_chunked` and `_kda_call` stamp `pt.kernel` around the `kda_fwd` call
and nothing else, `pt.layout` around what feeds it and hands its result
back (the casts of g, β and the state, the pad to whole runs and its slice)
— models/vocab.py, LAYER_PARTS; metadata only.

Forward only: a gradient through `kda_chunked` raises by name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from novel_view_synthesis_3d_tpu.ops import _pallas
from novel_view_synthesis_3d_tpu.ops._delta_rule import (
    merge_blocks, mm as _mm, placed as _placed)

CHUNK = 64      # tokens a chunk: one step of the scan
SUB_BLOCK = 16  # rows a sub-block: the decay's reference row moves this often
RUN_CHUNKS = 4  # chunks a grid step
RUN_HEADS = 2   # heads a grid step, where H divides


def _kda_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, sl_ref,
                st_ref, *, chunk: int, chunks: int, wide: int, heads: int):
    """One (row, `heads` heads, run of `chunks` chunks). Blocks: q, k, g
    (1, R, heads·d_k), v, o (1, R, heads·d_v) — the heads' lanes of the
    model's own (B, L, H·d) arrays, R = chunks·chunk rows —, β (1, R, H)
    every head's, S0 / S_L (1, heads, d_k, d_v); `st_ref` (heads, d_v, d_k)
    float32 is the state, TRANSPOSED (a channel's decay is then a lane's
    factor), resident while the grid walks a (row, heads)'s runs."""
    f32 = jnp.float32
    C, s = chunk, SUB_BLOCK
    # Chunks are taken `wide` at a time where no state is involved: their
    # (C, C) matrices lie block-diagonally in one (P, P), P = wide·C ≤ 128
    # lanes, so a product on them fills an MXU tile with two chunks' work.
    R, P = chunks * C, wide * C
    nb = heads * R // s
    run = pl.program_id(2)

    @pl.when(run == 0)
    def _enter():
        for i in range(heads):
            st_ref[i] = s0_ref[0, i].T

    # The heads one under the other: head i is rows i·R, … of everything
    # below, a sequence of its own. They share no number; side by side,
    # one's products run while another's wait for their operands.
    def stacked(ref):
        x = ref[0].astype(f32)
        d = x.shape[1] // heads
        return jnp.concatenate([x[:, i * d:(i + 1) * d]
                                for i in range(heads)], axis=0)

    q, k, g, v = (stacked(ref) for ref in (q_ref, k_ref, g_ref, v_ref))
    dk = q.shape[1]
    every = beta_ref[0]
    head_of = jax.lax.broadcasted_iota(jnp.int32, every.shape, 1) \
        - pl.program_id(1) * heads
    beta = jnp.concatenate(
        [jnp.sum(jnp.where(head_of == i, every, 0.0), axis=1, keepdims=True)
         for i in range(heads)], axis=0)

    rows = jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (P, P), 1)
    same_chunk = rows // C == cols // C
    rows, cols = rows % C, cols % C
    # G: the running sum of g inside each chunk.
    tril = (same_chunk & (rows >= cols)).astype(f32)
    G = jnp.concatenate([_mm(tril, g[at:at + P])
                         for at in range(0, heads * R, P)], axis=0)

    def sub(x):
        return x.reshape(nb, s, x.shape[-1])

    q3, k3, G3, b3 = sub(q), sub(k), sub(G), sub(beta)
    # A sub-block against itself, row r at a time and channel by channel
    # from the differences (a row's products come out as a COLUMN over the
    # sub-block's earlier rows: what the substitution scales T's rows by),
    # and row r of the sub-block's inverse in the same step. The inverses
    # and the query-key sub-blocks (these transposed) are written where
    # they lie in (P, P): the sub-block at rows 16·i has lanes 16·i.
    shape = (nb, s, P)
    row_of = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2) \
        - jax.lax.broadcasted_iota(jnp.int32, shape, 0) % (P // s) * s
    T = jnp.zeros(shape, f32)
    qk_t = jnp.zeros(shape, f32)
    for r in range(s):
        decay = jnp.exp(jnp.minimum(G3[:, r:r + 1] - G3, 0.0))
        kd = k3 * decay
        col_k = jnp.sum(kd * k3[:, r:r + 1], axis=2, keepdims=True)
        col_q = jnp.sum(kd * q3[:, r:r + 1], axis=2, keepdims=True)
        here = lane == r
        qk_t = jnp.where(here & (row_of <= r), col_q, qk_t)
        above = jnp.sum(jnp.where(row_of < r, col_k, 0.0) * T, axis=1,
                        keepdims=True)
        new = jnp.where(here[:, :1], 1.0, 0.0) - b3[:, r:r + 1] * above
        T = jnp.where(row_of == r, new, T)
    T, qk_t = T.reshape(heads * R, P), qk_t.reshape(heads * R, P)

    # Against earlier sub-blocks of its chunk: both factors at most 1,
    # taken against the later sub-block's first row.
    down = jnp.exp(G3 - G3[:, :1]).reshape(heads * R, dk)
    qd, kdn = q * down, k * down

    def group(at):
        """T and the query-key matrix of the `wide` chunks at rows `at`, …"""
        a_qk, a_kk = [], []
        for c0 in range(0, P, C):
            kc, Gc = k[at + c0:at + c0 + C], G[at + c0:at + c0 + C]
            a_qk.append(jnp.zeros((s, P), f32))
            a_kk.append(jnp.zeros((s, P), f32))
            for n in range(s, C, s):
                up = _placed(kc[:n] * jnp.exp(Gc[n:n + 1] - Gc[:n]), c0, P)
                block = slice(at + c0 + n, at + c0 + n + s)
                both = _mm(jnp.concatenate([qd[block], kdn[block]], axis=0),
                           up, ((1,), (1,)))
                a_qk.append(both[:s])
                a_kk.append(both[s:])
        M = beta[at:at + P] * jnp.concatenate(a_kk, axis=0)
        # The chunks' T from their sub-blocks' (ops/_delta_rule.py).
        Tg = merge_blocks(T[at:at + P], M, s, C, rows, cols, same_chunk)
        return Tg, jnp.concatenate(a_qk, axis=0) + qk_t[at:at + P].T

    def step(i, at, c0, Tg, a_qk):
        """Head i's chunk at rows at + c0, …, from its state and on it."""
        here = slice(at + c0, at + c0 + C)
        kc, Gc = k[here], G[here]
        S = st_ref[i]                                         # (d_v, d_k)
        into = jnp.exp(Gc)
        kqs = _mm(jnp.concatenate([kc * into, q[here] * into], axis=0), S,
                  ((1,), (1,)))                               # (2C, d_v)
        # the group's other chunks' columns of T and A_qk are zeros
        U = _mm(Tg[c0:c0 + C],
                _placed(beta[here] * (v[here] - kqs[:C]), c0, P))
        end = Gc[C - 1:]
        st_ref[i] = jnp.exp(end) * S + _mm(U, kc * jnp.exp(end - Gc),
                                           ((0,), (0,)))
        return kqs[C:] + _mm(a_qk[c0:c0 + C], _placed(U, c0, P))

    o = [[] for _ in range(heads)]
    for at in range(0, R, P):
        made = [group(i * R + at) for i in range(heads)]
        for c0 in range(0, P, C):
            for i in range(heads):
                o[i].append(step(i, i * R + at, c0, *made[i]))
    o_ref[0] = jnp.concatenate([jnp.concatenate(x, axis=0) for x in o],
                               axis=1)

    @pl.when(run == pl.num_programs(2) - 1)
    def _leave():
        for i in range(heads):
            sl_ref[0, i] = st_ref[i].T


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _kda_call(q, k, v, g, beta, S0, *, chunk: int, interpret: bool):
    B, L, H = beta.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    if not interpret and (dk % 128 or dv % 128):
        raise ValueError(
            f"kda_fwd on the chip takes heads that are whole 128-lane "
            f"blocks of (B, L, H·d); got d_k={dk}, d_v={dv}")
    heads = max(n for n in range(1, RUN_HEADS + 1) if H % n == 0)
    # a run is whole groups of `wide` chunks, a group at most 128 rows
    chunks = min(RUN_CHUNKS, -(-L // chunk))
    wide = min(chunks, max(1, 128 // chunk))
    chunks = -(-chunks // wide) * wide
    R = chunks * chunk
    pad = (-L) % R
    if pad:   # k = v = β = g = 0 rows: the state passes them unchanged
        with jax.named_scope("pt.layout"):
            q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                                for x in (q, k, v, g, beta))

    def lanes(d):
        return pl.BlockSpec((1, R, heads * d), lambda b, h, c: (b, c, h))

    state = pl.BlockSpec((1, heads, dk, dv), lambda b, h, c: (b, h, 0, 0))
    with jax.named_scope("pt.kernel"):
        o, S = pl.pallas_call(
            functools.partial(_kda_kernel, chunk=chunk, chunks=chunks,
                              wide=wide, heads=heads),
            out_shape=(jax.ShapeDtypeStruct((B, L + pad, H * dv),
                                            jnp.float32),
                       jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32)),
            grid=(B, H // heads, (L + pad) // R),
            in_specs=[lanes(dk), lanes(dk), lanes(dv), lanes(dk),
                      pl.BlockSpec((1, R, H), lambda b, h, c: (b, c, 0)),
                      state],
            out_specs=(lanes(dv), state),
            scratch_shapes=[_pallas.VMEM((heads, dv, dk), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name="kda_fwd", interpret=interpret,
        )(q, k, v, g, beta, S0)
    with jax.named_scope("pt.layout"):
        return o[:, :L], S


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _kda(q, k, v, g, beta, S0, chunk):
    return _kda_call(q, k, v, g, beta, S0, chunk=chunk,
                     interpret=_pallas.use_interpret())


def _kda_fwd(q, k, v, g, beta, S0, chunk):
    return _kda(q, k, v, g, beta, S0, chunk), None


def _kda_bwd(chunk, res, ct):
    raise NotImplementedError(
        "kda_chunked has no backward yet: the chunked scan's VJP (the "
        "reverse walk over the chunks with dS carried) is not written")


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_chunked(q, k, v, g, beta, S0=None, *, chunk: int = CHUNK):
    """The gated delta rule with a per-channel decay over a sequence, in
    chunks. Heads lie side by side in the last axis, as the projections
    leave them: q, k (B, L, H·d_k) — the caller's to normalise and scale —,
    v (B, L, H·d_v), g (B, L, H·d_k) float32 log-decays ≤ 0, β (B, L, H)
    in [0, 1], `S0` (B, H, d_k, d_v) the state the sequence is entered
    with (zeros where None). → (o (B, L, H·d_v) float32, the state after
    the last token (B, H, d_k, d_v) float32). L need not be a multiple of
    `chunk` (itself one of SUB_BLOCK)."""
    if chunk % SUB_BLOCK:
        raise ValueError(f"chunk={chunk} is not a multiple of {SUB_BLOCK}")
    B, _, H = beta.shape
    with jax.named_scope("pt.layout"):
        if S0 is None:
            S0 = jnp.zeros((B, H, q.shape[-1] // H, v.shape[-1] // H),
                           jnp.float32)
        g, beta, S0 = (x.astype(jnp.float32) for x in (g, beta, S0))
    return _kda(q, k, v, g, beta, S0, int(chunk))

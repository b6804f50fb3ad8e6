"""Gated DeltaNet's sequence operator: the gated delta rule with ONE decay a
head, keys narrower than values and a write strength β up to 2, computed
in chunks from an initial state. (The short convolution in front of it,
with its SiLU and the head-wise L2 norms, is ops/short_conv.py's kernel,
which hands q, k, v over as this one takes them.)

The recurrence, a head at a time (q, k of width d_k, v of width d_v ≠ d_k,
a state S (d_k, d_v) in float32, g_t ≤ 0 the head's log-decay — a SCALAR a
head-token —, β_t ∈ [0, 2]):

    S′  = exp(g_t)·S_{t−1}              the whole state forgets at one rate
    u_t = β_t·(v_t − S′ᵀ k_t)           what the state does not yet say of
    S_t = S′ + k_t u_tᵀ                 k_t is written, β_t of it
    o_t = S_tᵀ q_t

so S_t = (I − β_t k_t k_tᵀ)·e^{g_t}·S_{t−1} + β_t k_t v_tᵀ, and with β > 1
(the source's `linear_allow_neg_eigval`) the transition has an eigenvalue
in (−1, 0): a key's old value is not only erased but written with the
other sign. `gated_delta_chunked` walks the sequence C = 64 tokens at a
time. With γ_r = Σ_{s≤r} g_s inside a chunk entered at S_0 and D[r, i] =
exp(γ_r − γ_i) for i ≤ r, 0 above the diagonal — the decay is a scalar, so
it factors OUT of the products: one (C, d_k)·(d_k, C) product and a (C, C)
mask whose exponent is never positive, no sub-blocks for the exponent's
sake (ops/kda.py's per-channel decay needs them) —:

    A   = Diag(β)·strict_tril[(K Kᵀ) ⊙ D]
    T   = (I + A)⁻¹                      unit lower triangular, (C, C)
    U   = T·Diag(β)·(V − e^γ ⊙ K·S_0)   = U⁰ − W·S_0
    O   = e^γ ⊙ Q·S_0 + tril[(Q Kᵀ) ⊙ D]·U
    S_C = e^{γ_C}·S_0 + (e^{γ_C − γ} ⊙ K)ᵀ·U

**One Pallas kernel, `gdn_fwd`; what the grid walks and what stays in
VMEM.** The grid is (row, group of heads, run of 4 chunks), the runs last
and in order. A head is a slice of lanes of the model's own (B, L, H·d)
arrays — q, k, v in the compute type, o in float32 — AT ANY OFFSET: the
source's heads are 96 and 192 lanes, no whole 128-lane blocks, so a grid
step takes the fewest heads whose keys and values both fill whole lane
blocks (four: 384 lanes of q and k, 768 of v and o) and slices a head out
of them in VMEM; nothing is padded or re-laid in HBM for the kernel. 30
heads are 7.5 such groups: the last step's blocks hang over the arrays'
edge, and the heads that do not exist are neither read nor walked (the
kernel holds the walk of a whole group and, for the last one, of the
heads that exist). γ, the running sum of g inside
a chunk, is made outside (a (B, L, H) float32 array, 1 MB at the cell's
size) and comes whole with β; a head's column is picked out of them.
Resident while the grid walks a (row, group)'s runs: the heads' states,
(d_k, d_v) float32 each, loaded from `S0` at the first run and written to
`S_L` at the last; they are never rounded. Inside a run everything lives in
VMEM and registers. What no state enters is made first for every head of
the step, TWO chunks side by side wherever an operand is (C, C) — their
matrices lie block-diagonally in one (128, 128), so a product on them
fills an MXU tile with both, as in ops/kda.py —: [Q ; K]·Kᵀ in one product,
the mask D from γ's column against its transpose, A and Aᵀ; then T — rows
inside a sub-block of 16 by substitution on the VPU for all the step's
sub-blocks at once, a pair's eight sub-blocks side by side in a vreg's
lanes (row r of the inverse is e_r − Σ_{j<r} A[r, j]·row j, A's row a
column of Aᵀ), block halves on the MXU (ops/_delta_rule.py says why
substitution and not the series) — the merges are this kernel's dearest
products (PERF.md §6, PR 46), so a level multiplies only the rows it
changes, the later half of each doubled block (`merge_rows`). Then the chunks in sequence, four
products each: [K ; Q]·S, U = T·(β ⊙ (V − e^γ ⊙ K·S)), O = e^γ ⊙ Q·S +
A_qk·U, S ← e^{γ_C}·S + Kᵀ·(e^{γ_C − γ} ⊙ U). exp(γ), exp(γ_C − γ),
exp(γ_C) and D have non-positive exponents as they are; an underflow to 0
is the value, and nothing is ever divided by a decay.

**Every product is the configuration's float32**
(`gdn_state_precision`: the state, the decays, β, T, U, A_qk and every
accumulator), at the MXU passes its operands' TYPES leave to do
(`_delta_rule.mm_parts`, decided when the kernel is traced). q and k
arrive in bfloat16 from the convolution (the cell's compute type) and are
never widened for a product: a float32 that holds a bfloat16 has a middle
and a low part of zeros, and five of `Precision.HIGHEST`'s six passes over
[Q ; K]·Kᵀ multiplied them — bfloat16 · bfloat16 into the float32
accumulator is the same number in ONE pass. The decay is a scalar a
head-token, so it never rides on them: it scales the ROWS of a product's
result ([K ; Q]·S) or of its float32 operand (U), and K and Q stay exact
in one part against a float32 S or U cut into the three bfloat16 parts
that hold its 24 bits — THREE passes, issued as one product with the
parts stacked along the contraction (288 and 192 deep: the MXU sums them,
and the state's fills two tiles where three products 64 deep fill three),
and every term of the float32 product where six-pass `HIGHEST` drops the
three smallest. The products of two float32 operands — T's merges, T·(…),
A_qk·U — keep their six passes. Float32 q, k, v (the CPU tests, another
compute type) run the same algebra at six passes throughout. The
operations and bytes counted for its roofline share
(benchmarks/flops_tokens_gdn.py) are of the chunked form above in ONE pass
over triangles, whatever implements it. Off the TPU the same kernel runs
through the Pallas interpreter (ops/_pallas.py's contract), at any head
widths.

`gated_delta_chunked` and `_gdn_call` stamp `pt.kernel` around the
`gdn_fwd` call and nothing else, `pt.layout` around what feeds it and hands
its result back (the casts, γ's running sum, the pad to whole runs and its
slice) — models/vocab.py, LAYER_PARTS; metadata only.

Forward only: a gradient through `gated_delta_chunked` raises by name.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from novel_view_synthesis_3d_tpu.ops import _pallas
from novel_view_synthesis_3d_tpu.ops._delta_rule import (
    merge_rows, mm, mm_parts)

CHUNK = 64      # tokens a chunk: one step of the scan
SUB_BLOCK = 16  # rows of the inverse made by substitution before halves merge
RUN_CHUNKS = 4  # chunks a grid step


def _gdn_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, s0_ref, o_ref,
                sl_ref, st_ref, *, chunk: int, chunks: int, wide: int,
                heads: int, total_heads: int):
    """One (row, group of `heads` heads, run of `chunks` chunks). Blocks:
    q, k (1, R, heads·d_k), v, o (1, R, heads·d_v) — the group's lanes of
    the model's own (B, L, H·d) arrays, R = chunks·chunk rows —; γ and β
    (1, R, H), every head's, a head's column picked out of them; S0 / S_L
    (1, heads, d_k, d_v); `st_ref` (heads, d_k, d_v) float32 is the state,
    resident while the grid walks a (row, group)'s runs. A head is a slice
    of lanes at any offset (96 and 192 in the source: no whole lane
    blocks); the last group may hang over the array's edge, and the heads
    that do not exist are neither read nor walked."""
    f32 = jnp.float32
    C, s = chunk, min(SUB_BLOCK, chunk)
    # Chunks are taken `wide` at a time where no state is involved: their
    # (C, C) matrices lie block-diagonally in one (P, P), P = wide·C ≤ 128
    # lanes, so a product on them fills an MXU tile with two chunks' work.
    P = wide * C
    run, group = pl.program_id(2), pl.program_id(1)
    dk = q_ref.shape[2] // heads
    dv = v_ref.shape[2] // heads

    @pl.when(run == 0)
    def _enter():
        st_ref[...] = s0_ref[0]

    def walk(live: int):
        """The run for the group's first `live` heads."""
        every_g, every_b = gam_ref[0], beta_ref[0]                 # (R, H)
        head_of = jax.lax.broadcasted_iota(jnp.int32, every_g.shape, 1) \
            - group * heads
        rows = jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (P, P), 1)
        same_chunk = rows // C == cols // C
        same_block = rows // s == cols // s
        rows, cols = rows % C, cols % C

        # What no state enters, for every (head, `wide` chunks) of the
        # step: the two (P, P) matrices, and A TRANSPOSED — A's row r over
        # a sub-block's earlier rows is then a column, which is what the
        # substitution scales T's rows by.
        made = []
        for i in range(live):
            q, k, v = (ref[0, :, i * d:(i + 1) * d]
                       for ref, d in ((q_ref, dk), (k_ref, dk), (v_ref, dv)))
            col_g, col_b = (jnp.sum(jnp.where(head_of == i, x, 0.0), axis=1,
                                    keepdims=True)
                            for x in (every_g, every_b))           # (R, 1)
            for at in range(0, chunks * C, P):
                here = slice(at, at + P)
                both = mm_parts(jnp.concatenate([q[here], k[here]], axis=0),
                                k[here], ((1,), (1,)))             # (2P, P)
                down = jnp.broadcast_to(col_g[here], (P, P))
                diff = down - down.T                     # γ_r − γ_i at [r, i]
                a_qk = both[:P] * jnp.exp(
                    jnp.where(same_chunk & (rows >= cols), diff, -jnp.inf))
                M = col_b[here] * both[P:] * jnp.exp(
                    jnp.where(same_chunk & (rows > cols), diff, -jnp.inf))
                made.append((i, q[here], k[here], v[here], col_g[here],
                             col_b[here], a_qk, M))

        # Rows inside a sub-block of `s`, every sub-block of the step at
        # once and a (P, P)'s sub-blocks SIDE BY SIDE in lanes — (s, P),
        # lanes s·b, … sub-block b's (s, s): a vreg is full of entries the
        # substitution moves, where T's own rows are zeros outside their
        # sub-block. Row r of a sub-block's inverse is e_r − Σ_{j<r}
        # A[r, j]·row j; A[r, j] is lane s·b + r of Aᵀ's row j, spread over
        # the sub-block's lanes by rolls (which no T enters: they are off
        # the chain).
        def side_by_side(x):
            """(P, P) → its diagonal sub-blocks, (s, P)."""
            x = jnp.where(same_block, x, 0.0)
            return sum(x[b:b + s] for b in range(0, P, s))

        AT = jnp.stack([side_by_side(x[-1].T) for x in made])
        shape = AT.shape                                  # (·, s, P)
        row_of = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2) % s
        T = jnp.zeros(shape, f32)
        for r in range(s):
            here = lane == r
            col = jnp.where(here, AT, 0.0)
            if r:
                col = pltpu.roll(col, P - r, 2)
            reach = 1
            while reach < s:
                col = col + pltpu.roll(col, reach, 2)
                reach *= 2
            above = jnp.sum(jnp.where(row_of < r, col, 0.0) * T, axis=1,
                            keepdims=True)
            T = jnp.where(row_of == r, jnp.where(here, 1.0, 0.0) - above, T)

        # Then the chunks in sequence, four products each on the state.
        o = [[] for _ in range(live)]
        for n, (i, q, k, v, gam, beta, a_qk, M) in enumerate(made):
            # back where the sub-blocks lie in (P, P), then the chunks' T
            # from their sub-blocks' (ops/_delta_rule.py)
            Tg = merge_rows(
                jnp.where(same_block,
                          jnp.concatenate([T[n]] * (P // s), axis=0), 0.0),
                M, s, C, rows, cols, same_chunk)
            for c0 in range(0, P, C):
                here = slice(c0, c0 + C)
                o[i].append(step(i, Tg[here, here], a_qk[here, here],
                                 *(x[here] for x in (q, k, v, gam, beta))))
        o_ref[0, :, :live * dv] = jnp.concatenate(
            [jnp.concatenate(x, axis=0) for x in o], axis=1)

    def step(i, Tc, a_qk, q, k, v, gam, beta):
        """Head i's chunk from its state and on it; `Tc`, `a_qk` (C, C):
        the chunk's own block of its group's T and A_qk."""
        S = st_ref[i]                                         # (d_k, d_v)
        into = jnp.exp(gam)
        kqs = jnp.concatenate([into, into], axis=0) * mm_parts(
            jnp.concatenate([k, q], axis=0), S)               # (2C, d_v)
        U = mm(Tc, beta * (v.astype(f32) - kqs[:C]))
        end = gam[C - 1:]
        st_ref[i] = jnp.exp(end) * S + mm_parts(
            k, jnp.exp(end - gam) * U, ((0,), (0,)))
        return kqs[C:] + mm(a_qk, U)

    # the last group's heads past the array's edge do not exist
    rest = total_heads % heads
    if rest:
        last = pl.num_programs(1) - 1
        pl.when(group < last)(lambda: walk(heads))
        pl.when(group == last)(lambda: walk(rest))
    else:
        walk(heads)

    @pl.when(run == pl.num_programs(2) - 1)
    def _leave():
        sl_ref[0] = st_ref[...]


def _heads_a_step(H: int, dk: int, dv: int, interpret: bool) -> int:
    """The fewest heads whose keys AND values fill whole 128-lane blocks
    (four of 96 on 192), or all of them where there are fewer; a step's
    heads are unrolled, so at most eight."""
    n = min(H, math.lcm(128 // math.gcd(dk, 128), 128 // math.gcd(dv, 128)))
    if interpret:       # any widths: the interpreter takes any block
        return min(n, 8)
    if n > 8:
        raise ValueError(
            f"gdn_fwd on the chip takes heads of which a few fill whole "
            f"128-lane blocks of (B, L, H·d); got d_k={dk}, d_v={dv}")
    return n


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _gdn_call(q, k, v, g, beta, S0, *, chunk: int, interpret: bool):
    B, L, H = beta.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    heads = _heads_a_step(H, dk, dv, interpret)
    # a run is whole groups of `wide` chunks, a group at most 128 rows
    chunks = min(RUN_CHUNKS, -(-L // chunk))
    wide = min(chunks, max(1, 128 // chunk))
    chunks = -(-chunks // wide) * wide
    R = chunks * chunk
    pad = (-L) % R
    with jax.named_scope("pt.layout"):
        if pad:   # k = v = β = g = 0 rows: the state passes them unchanged
            q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                                for x in (q, k, v, g, beta))
        # γ: the running sum of g inside each chunk
        gam = jnp.cumsum(g.reshape(B, -1, chunk, H), axis=2).reshape(g.shape)

    def lanes(d):
        return pl.BlockSpec((1, R, heads * d), lambda b, h, c: (b, c, h))

    columns = pl.BlockSpec((1, R, H), lambda b, h, c: (b, c, 0))
    state = pl.BlockSpec((1, heads, dk, dv), lambda b, h, c: (b, h, 0, 0))
    with jax.named_scope("pt.kernel"):
        o, S = pl.pallas_call(
            functools.partial(_gdn_kernel, chunk=chunk, chunks=chunks,
                              wide=wide, heads=heads, total_heads=H),
            out_shape=(jax.ShapeDtypeStruct((B, L + pad, H * dv),
                                            jnp.float32),
                       jax.ShapeDtypeStruct((B, H, dk, dv), jnp.float32)),
            grid=(B, -(-H // heads), (L + pad) // R),
            in_specs=[lanes(dk), lanes(dk), lanes(dv), columns, columns,
                      state],
            out_specs=(lanes(dv), state),
            scratch_shapes=[_pallas.VMEM((heads, dk, dv), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name="gdn_fwd", interpret=interpret,
        )(q, k, v, gam, beta, S0)
    with jax.named_scope("pt.layout"):
        return o[:, :L], S


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _gdn(q, k, v, g, beta, S0, chunk):
    return _gdn_call(q, k, v, g, beta, S0, chunk=chunk,
                     interpret=_pallas.use_interpret())


def _gdn_fwd(q, k, v, g, beta, S0, chunk):
    return _gdn(q, k, v, g, beta, S0, chunk), None


def _gdn_bwd(chunk, res, ct):
    raise NotImplementedError(
        "gated_delta_chunked has no backward yet: the chunked scan's VJP "
        "(the reverse walk over the chunks with dS carried) is not written")


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


def gated_delta_chunked(q, k, v, g, beta, S0=None, *, chunk: int = CHUNK):
    """The gated delta rule with one decay a head over a sequence, in
    chunks. Heads lie side by side in the last axis, as the projections
    leave them: q, k (B, L, H·d_k) — the caller's to normalise and scale —,
    v (B, L, H·d_v), g (B, L, H) float32 log-decays ≤ 0, β (B, L, H) in
    [0, 2], `S0` (B, H, d_k, d_v) the state the sequence is entered with
    (zeros where None). → (o (B, L, H·d_v) float32, the state after the
    last token (B, H, d_k, d_v) float32). L need not be a multiple of
    `chunk` (a power of two)."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk={chunk} is not a power of two")
    B, _, H = beta.shape
    with jax.named_scope("pt.layout"):
        if S0 is None:
            S0 = jnp.zeros((B, H, q.shape[-1] // H, v.shape[-1] // H),
                           jnp.float32)
        g, beta, S0 = (x.astype(jnp.float32) for x in (g, beta, S0))
    return _gdn(q, k, v, g, beta, S0, int(chunk))

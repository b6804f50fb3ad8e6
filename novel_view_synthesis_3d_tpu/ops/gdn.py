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
edge, and what they read of heads that do not exist is set to zero and
never written. γ, the running sum of g inside a chunk, is made outside (a
(B, L, H) float32 array, 1 MB at the cell's size) and comes whole with β;
a head's column is picked out of them. Resident while the grid walks a
(row, group)'s runs: the heads' states, (d_k, d_v) float32 each, loaded
from `S0` at the first run and written to `S_L` at the last; they are
never rounded. Inside a run everything lives in VMEM and registers. What
no state enters is made first for every (head, chunk) of the step: [Q ;
K]·Kᵀ in one product, the mask D from γ's column against its transpose, A
and Aᵀ; then T — rows inside a sub-block of 16 by substitution on the VPU
for all the step's sub-blocks at once (row r of the inverse is e_r −
Σ_{j<r} A[r, j]·row j, A's row a column of Aᵀ), block halves on the MXU
(ops/_delta_rule.py says why substitution and not the series). Then the
chunks in sequence, four products each: [K̄ ; Q̄]·S, U = T·(β ⊙ (V − K̄·S)),
O = Q̄·S + A_qk·U, S ← e^{γ_C}·S + K̂ᵀ·U. exp(γ), exp(γ_C − γ), exp(γ_C)
and D have non-positive exponents as they are; an underflow to 0 is the
value, and nothing is ever divided by a decay.

**Every product is the configuration's float32**
(`gdn_state_precision`: the state, the decays, β and the whole scan):
float32 operands at `Precision.HIGHEST` — six MXU passes of bfloat16
parts, which are the kernel's time as they are `kda_fwd`'s — into a
float32 accumulator; q, k, v are widened in VMEM as they arrive. The
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
from novel_view_synthesis_3d_tpu.ops._delta_rule import merge_blocks, mm

CHUNK = 64      # tokens a chunk: one step of the scan
SUB_BLOCK = 16  # rows of the inverse made by substitution before halves merge
RUN_CHUNKS = 4  # chunks a grid step


def _gdn_kernel(q_ref, k_ref, v_ref, gam_ref, beta_ref, s0_ref, o_ref,
                sl_ref, st_ref, *, chunk: int, chunks: int, heads: int,
                total_heads: int):
    """One (row, group of `heads` heads, run of `chunks` chunks). Blocks:
    q, k (1, R, heads·d_k), v, o (1, R, heads·d_v) — the group's lanes of
    the model's own (B, L, H·d) arrays, R = chunks·chunk rows —; γ and β
    (1, R, H), every head's, a head's column picked out of them; S0 / S_L
    (1, heads, d_k, d_v); `st_ref` (heads, d_k, d_v) float32 is the state,
    resident while the grid walks a (row, group)'s runs. A head is a slice of lanes at any
    offset (96 and 192 in the source: no whole lane blocks); the last
    group may hang over the array's edge, and what it reads of heads that
    do not exist is set to zero."""
    f32 = jnp.float32
    C, s = chunk, min(SUB_BLOCK, chunk)
    run = pl.program_id(2)
    first = pl.program_id(1) * heads
    dk = q_ref.shape[2] // heads
    dv = v_ref.shape[2] // heads

    @pl.when(run == 0)
    def _enter():
        st_ref[...] = s0_ref[0]

    every_g, every_b = gam_ref[0], beta_ref[0]                     # (R, H)
    head_of = jax.lax.broadcasted_iota(jnp.int32, every_g.shape, 1)
    at = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    to = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)

    # What no state enters, for every (head, chunk) of the step: the two
    # (C, C) matrices, and A TRANSPOSED — A's row r over a sub-block's
    # earlier rows is then a column, which is what the substitution scales
    # T's rows by.
    made = []
    for i in range(heads):
        live = first + i < total_heads

        def lanes(ref, d):
            return jnp.where(live, ref[0, :, i * d:(i + 1) * d].astype(f32),
                             0.0)

        q, k, v = lanes(q_ref, dk), lanes(k_ref, dk), lanes(v_ref, dv)
        col_g, col_b = (jnp.sum(jnp.where(head_of == first + i, x, 0.0),
                                axis=1, keepdims=True)
                        for x in (every_g, every_b))               # (R, 1)
        for c0 in range(0, chunks * C, C):
            here = slice(c0, c0 + C)
            both = mm(jnp.concatenate([q[here], k[here]], axis=0), k[here],
                      ((1,), (1,)))                                # (2C, C)
            down = jnp.broadcast_to(col_g[here], (C, C))
            diff = down - down.T                         # γ_r − γ_i at [r, i]
            a_qk = both[:C] * jnp.exp(jnp.where(at >= to, diff, -jnp.inf))
            M = col_b[here] * both[C:] * jnp.exp(
                jnp.where(at > to, diff, -jnp.inf))
            made.append((i, q[here], k[here], v[here], col_g[here],
                         col_b[here], a_qk, M, M.T))

    # Rows inside a sub-block of `s`, every sub-block of the step at once:
    # row r of a sub-block's inverse is e_r − Σ_{j<r} A[r, j]·row j.
    nb = len(made) * C // s
    MT3 = jnp.concatenate([x[-1] for x in made], axis=0).reshape(nb, s, C)
    shape = (nb, s, C)
    row_of = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2) \
        - jax.lax.broadcasted_iota(jnp.int32, shape, 0) % (C // s) * s
    T = jnp.zeros(shape, f32)
    for r in range(s):
        here = lane == r
        col = jnp.sum(jnp.where(here, MT3, 0.0), axis=2, keepdims=True)
        above = jnp.sum(jnp.where(row_of < r, col, 0.0) * T, axis=1,
                        keepdims=True)
        T = jnp.where(row_of == r, jnp.where(here, 1.0, 0.0) - above, T)
    T = T.reshape(len(made) * C, C)

    # Then the chunks in sequence, four products each on the state.
    o = [[] for _ in range(heads)]
    for n, (i, q, k, v, gam, beta, a_qk, M, _) in enumerate(made):
        Tc = merge_blocks(T[n * C:(n + 1) * C], M, s, C, at, to)
        S = st_ref[i]                                         # (d_k, d_v)
        into = jnp.exp(gam)
        kqs = mm(jnp.concatenate([k * into, q * into], axis=0), S)
        U = mm(Tc, beta * (v - kqs[:C]))
        end = gam[C - 1:]
        st_ref[i] = jnp.exp(end) * S + mm(k * jnp.exp(end - gam), U,
                                          ((0,), (0,)))
        o[i].append(kqs[C:] + mm(a_qk, U))
    o_ref[0] = jnp.concatenate([jnp.concatenate(x, axis=0) for x in o],
                               axis=1)

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
    chunks = min(RUN_CHUNKS, -(-L // chunk))
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
                              heads=heads, total_heads=H),
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

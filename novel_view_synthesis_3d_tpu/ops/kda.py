"""Kimi Delta Attention's two sequence operators: the causal depthwise
short convolution with a carried tail, and the gated delta rule with a
per-channel decay, computed in chunks from an initial state.

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
    U   = T·Diag(β)·V − T·Diag(β)·(K ⊙ exp G)·S_0        = U⁰ − W·S_0
    O   = (Q ⊙ exp G)·S_0 + tril[ Σ_c q_r[c]·k_i[c]·exp(G_r[c] − G_i[c]) ]·U
    S_C = Diag(exp G_C)·S_0 + (K ⊙ exp(G_C − G))ᵀ·U

**What is made for all chunks at once, and what is walked.** A, T, U⁰, W,
the query-key matrix and the decayed operands depend on no state: they are
batched products over every (row, head, chunk). What remains in sequence
is a `lax.scan` over the chunks whose body is three products against the
(d_k, d_v) state — U⁰ − W·S, Q̄·S + A_qk·U, K̂ᵀ·U — batched over the heads:
64 dependent steps a row at 4096 tokens, where the recurrence has 4096.
Resident across the scan: a row's state (H, d_k, d_v) float32. The rows of
the batch go one after another (`lax.map`): a row's float32 working set is
a dozen arrays of the size of q.

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

Everything here is float32 with the MXU's full-precision passes: the
state is the layer's memory over thousands of tokens and is not rounded
between chunks. **XLA, not a Pallas kernel, for now** (PERF.md §6, PR 34,
has the chip's reading of `lk.kda_core`); the operations and bytes counted
for its roofline share (benchmarks/flops_tokens_kda.py) are of the chunked
form above, whatever implements it.

Forward only: a gradient through `kda_chunked` raises by name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 64      # tokens a chunk: one step of the scan
SUB_BLOCK = 16  # rows a sub-block: the decay's reference row moves this often
_HIGHEST = jax.lax.Precision.HIGHEST


def short_conv(x, w, tail=None):
    """Causal depthwise convolution over the sequence: y_t = Σ_j w_j ⊙
    x_{t−(K−1)+j}, the last tap on the token itself, no bias. x (B, L, D),
    w (K, D), `tail` (B, K−1, D) the rows before x's first (zeros where
    None: the sequence starts here). → (y (B, L, D) float32, the last K−1
    rows of [tail ; x], which a continuation takes as its `tail`)."""
    K = w.shape[0]
    B, L, D = x.shape
    if tail is None:
        tail = jnp.zeros((B, K - 1, D), x.dtype)
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w32 = w.astype(jnp.float32)
    y = sum(ext[:, j:j + L].astype(jnp.float32) * w32[j] for j in range(K))
    return y, ext[:, L:]


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


def _unit_lower_inverse(M):
    """(I + A)⁻¹ for M = I + A (..., n, n), A strictly lower triangular:
    by rows up to SUB_BLOCK, by halves above it."""
    n = M.shape[-1]
    if n <= SUB_BLOCK:
        eye = jnp.broadcast_to(jnp.eye(n, dtype=M.dtype), M.shape)
        rows = [eye[..., 0, :]]
        for r in range(1, n):
            above = jnp.stack(rows, axis=-2)                  # (..., r, n)
            rows.append(eye[..., r, :] - jnp.sum(
                M[..., r, :r, None] * above, axis=-2))
        return jnp.stack(rows, axis=-2)
    h = n // 2
    halves = _unit_lower_inverse(jnp.stack(
        [M[..., :h, :h], M[..., h:, h:]], axis=-3))
    t11, t22 = halves[..., 0, :, :], halves[..., 1, :, :]
    t21 = -_mm(_mm(t22, M[..., h:, :h]), t11)
    top = jnp.concatenate([t11, jnp.zeros_like(t21)], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([t21, t22], axis=-1)],
                           axis=-2)


def _decayed_products(q, k, G):
    """The two (C, C) matrices of a chunk, before any mask's β: rows r,
    columns i ≤ r of Σ_c x_r[c]·k_i[c]·exp(G_r[c] − G_i[c]) for x = q
    (diagonal included) and x = k (strictly lower). q, k, G (..., C, d)."""
    C, d = k.shape[-2:]
    n = C // SUB_BLOCK
    lead = k.shape[:-2]

    def sub(x):
        return x.reshape(lead + (n, SUB_BLOCK, d))

    qs, ks, Gs = sub(q), sub(k), sub(G)
    # A sub-block against itself: from the differences, channel by channel.
    both = jnp.concatenate([qs, ks], axis=-2)               # (.., n, 2s, d)
    Gb = jnp.concatenate([Gs, Gs], axis=-2)
    r = np.arange(SUB_BLOCK)
    seen = np.concatenate([r[:, None] >= r[None], r[:, None] > r[None]])
    diff = Gb[..., :, None, :] - Gs[..., None, :, :]         # (.., 2s, s, d)
    decay = jnp.exp(jnp.where(seen[..., None], diff, -jnp.inf))
    own = jnp.sum(both[..., :, None, :] * ks[..., None, :, :] * decay,
                  axis=-1)                                   # (.., n, 2s, s)
    # Against earlier sub-blocks: both factors at most 1, taken against
    # this sub-block's first row.
    down = both * jnp.exp(Gb - Gs[..., :1, :])
    rows_q, rows_k = [], []
    for i in range(n):
        parts = []
        if i:
            before = i * SUB_BLOCK
            up = k[..., :before, :] * jnp.exp(
                Gs[..., i, :1, :] - G[..., :before, :])
            parts.append(_mm(down[..., i, :, :],
                             jnp.swapaxes(up, -1, -2)))     # (.., 2s, before)
        parts.append(own[..., i, :, :])
        rest = C - (i + 1) * SUB_BLOCK
        if rest:
            parts.append(jnp.zeros(lead + (2 * SUB_BLOCK, rest), k.dtype))
        row = jnp.concatenate(parts, axis=-1)                # (.., 2s, C)
        rows_q.append(row[..., :SUB_BLOCK, :])
        rows_k.append(row[..., SUB_BLOCK:, :])
    return jnp.concatenate(rows_q, axis=-2), jnp.concatenate(rows_k, axis=-2)


def _kda_row(q, k, v, g, beta, S0, chunk):
    """One row of the batch: q, k, g (L, H, d_k), v (L, H, d_v), β (L, H),
    S0 (H, d_k, d_v) → (o (L, H, d_v), the last state). Everything is
    laid out (chunks, H, chunk, ·) from the start: what is made for all
    chunks at once is then already in the order the scan walks."""
    L, H, _ = q.shape
    dv = v.shape[-1]
    pad = (-L) % chunk
    NC = (L + pad) // chunk

    def blocks(x):
        """(L, H, ·) → (chunks, H, chunk, ·) float32; the padding's rows
        have k = v = β = g = 0: the state passes them unchanged."""
        x = jnp.pad(x.astype(jnp.float32),
                    ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        x = x.reshape((NC, chunk) + x.shape[1:])
        return jnp.moveaxis(x, 1, 2)

    q, k, v, g, beta = (blocks(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=2)
    a_qk, a_kk = _decayed_products(q, k, G)
    T = _unit_lower_inverse(
        jnp.eye(chunk, dtype=jnp.float32) + beta[..., None] * a_kk)
    Tb = T * beta[..., None, :]                              # T·Diag(β)
    G_end = G[..., -1:, :]
    xs = (_mm(Tb, v),                                        # U⁰
          _mm(Tb, k * jnp.exp(G)),                           # W
          q * jnp.exp(G), a_qk,
          k * jnp.exp(G_end - G),                            # K̂
          jnp.exp(G_end[..., 0, :]))                         # (NC, H, dk)

    def step(S, x):
        u0, w, q_in, a_qk, k_out, keep = x
        u = u0 - _mm(w, S)
        o = _mm(q_in, S) + _mm(a_qk, u)
        S = keep[..., None] * S + _mm(jnp.swapaxes(k_out, -1, -2), u)
        return S, o

    S_end, o = jax.lax.scan(step, S0.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 1, 2).reshape(NC * chunk, H, dv)[:L], S_end


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _kda(q, k, v, g, beta, S0, chunk):
    """A row of the batch at a time: a row's float32 working set (a dozen
    arrays of the size of q) is then a row's, not the batch's — 1.1 GB in
    place of 4.5 at 4 rows × 4096 tokens × 32 heads of 128."""
    return jax.lax.map(lambda x: _kda_row(*x, chunk),
                       (q, k, v, g, beta, S0))


def _kda_fwd(q, k, v, g, beta, S0, chunk):
    return _kda(q, k, v, g, beta, S0, chunk), None


def _kda_bwd(chunk, res, ct):
    raise NotImplementedError(
        "kda_chunked has no backward yet: the chunked scan's VJP (the "
        "reverse walk over the chunks with dS carried) is not written")


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_chunked(q, k, v, g, beta, S0=None, *, chunk: int = CHUNK):
    """The gated delta rule with a per-channel decay over a sequence, in
    chunks. q, k (B, L, H, d_k) — the caller's to normalise and scale —,
    v (B, L, H, d_v), g (B, L, H, d_k) log-decays ≤ 0, β (B, L, H) in
    [0, 1], `S0` (B, H, d_k, d_v) the state the sequence is entered with
    (zeros where None). → (o (B, L, H, d_v) float32, the state after the
    last token (B, H, d_k, d_v) float32). L need not be a multiple of
    `chunk` (itself one of SUB_BLOCK)."""
    if chunk % SUB_BLOCK:
        raise ValueError(f"chunk={chunk} is not a multiple of {SUB_BLOCK}")
    B, _, H, dk = q.shape
    if S0 is None:
        S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    return _kda(q, k, v, g, beta, S0, int(chunk))

"""What the two chunked delta-rule kernels share (ops/kda.py, `kda_fwd`: a
decay per channel; ops/gdn.py, `gdn_fwd`: a decay per head): the float32
product — `mm`, six MXU passes of two float32 operands, and `mm_parts`,
the same float32 at the passes the operands' types leave to do: one where
both arrive in bfloat16, three where one does (`gdn_fwd`'s products on q
and k, which its scalar decay never touches; `kda_fwd`'s decayed operands
are genuine float32 and take `mm`) —, the placement of a block among
zeros, and the upper levels of the triangular inverse.

**The inverse is built by substitution**, never as the series Σ(−A)ⁿ,
whose terms grow combinatorially for near-parallel keys (a mostly white
frame's are) before they cancel. Rows inside a sub-block of 16 are each
kernel's own (KDA sums a sub-block's entries channel by channel with the
decay inside; the scalar-decay form reads them off one product); from
there up both double the block: with T the inverses of (I + M)'s diagonal
blocks of `size` rows, T₂₁ = −T₂₂·M₂₁·T₁₁ — `merge_blocks` on whole
(P, P) operands, `merge_rows` on the rows that change.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b, contract=((1,), (0,))):
    """A product in the configuration's float32: float32 operands, every
    pass of the MXU (six of bfloat16 parts), a float32 accumulator.
    `contract`: the contracted axis of each operand."""
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


PARTS = 3   # bfloat16 arrays that hold a float32: 3 × 8 bits are its 24


def bfloat16_parts(x):
    """x float32 → `PARTS` bfloat16 arrays whose sum is x to its last bit:
    each the rounding of what the ones before it left, every subtraction
    in float32. By casts, for a Mosaic kernel (which has no
    `reduce_precision` and drops no cast); models/token_denoiser.py's
    `bfloat16_terms` is the same cut for XLA, which may drop one."""
    parts = []
    for _ in range(PARTS):
        part = x.astype(jnp.bfloat16)
        parts.append(part)
        x = x - part.astype(jnp.float32)
    return parts


def mm_parts(a, b, contract=((1,), (0,))):
    """`mm`'s float32 product at the MXU passes the operands' types leave
    to do. An operand that ARRIVES in bfloat16 is exact in one part: its
    float32 form has a middle and a low part of zeros, and a pass on them
    adds nothing. So bfloat16 · bfloat16 is ONE pass into the float32
    accumulator; bfloat16 · float32 is THREE — the float32 operand cut in
    its `bfloat16_parts`, the parts stacked along the contraction against
    the other operand three times over, so that one product a tile deeper
    sums them on the MXU: every term of the float32 product, where `mm`'s
    six passes of two three-part operands drop the three smallest cross
    terms —; float32 · float32 is `mm`. Decided by dtype at trace time."""
    narrow = [x.dtype == jnp.bfloat16 for x in (a, b)]
    if not any(narrow):
        return mm(a, b, contract)
    if not all(narrow):
        a, b = (jnp.concatenate([x] * PARTS if is_narrow
                                else bfloat16_parts(x), axis=axis)
                for x, is_narrow, (axis,) in zip((a, b), narrow, contract))
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def placed(x, at: int, rows: int):
    """x as rows `at`, … of `rows` rows, zeros around it."""
    def zeros(n):
        return [jnp.zeros((n, x.shape[1]), x.dtype)] if n else []

    return jnp.concatenate(
        zeros(at) + [x] + zeros(rows - at - x.shape[0]), axis=0)


def merge_blocks(T, M, size: int, upto: int, rows, cols, same=True):
    """T (P, P), the inverses of (I + M)'s diagonal blocks of `size` rows
    lying block-diagonally → those of its blocks of `upto` rows, a level
    of twice the block size at a time. `rows`, `cols` (P, P): an entry's
    row and column inside its own `upto`-row block; `same`: the entries
    whose row and column lie in the same such block (all of them where P
    = `upto`)."""
    while size < upto:
        pair = same & (rows // size % 2 == 1) \
            & (cols // size == rows // size - 1)
        T = T - mm(T, mm(jnp.where(pair, M, 0.0), T))
        size *= 2
    return T


def merge_rows(T, M, size: int, upto: int, rows, cols, same=True):
    """`merge_blocks` on the rows a level changes alone. Doubling a block
    writes T₂₁ and nothing else — T₁₁, T₂₂ and the zeros above them stand —
    so both of a level's products take only the LATER half of each doubled
    block as their left operand, `size` rows of every 2·`size`: half the
    rows through the MXU for the same T (M₂₁·T₁₁ at those rows, laid back
    among zero rows as the right operand of T₂₂'s)."""
    P = T.shape[0]
    while size < upto:
        later = range(size, P, 2 * size)

        def halves(x):
            return jnp.concatenate([x[at:at + size] for at in later], axis=0)

        pair = same & (rows // size % 2 == 1) \
            & (cols // size == rows // size - 1)
        X = mm(halves(jnp.where(pair, M, 0.0)), T)
        low = halves(T)
        low = low - mm(low, jnp.concatenate(
            [placed(X[n * size:(n + 1) * size], size, 2 * size)
             for n in range(len(later))], axis=0))
        T = jnp.concatenate(
            [x for n, at in enumerate(later)
             for x in (T[at - size:at], low[n * size:(n + 1) * size])],
            axis=0)
        size *= 2
    return T

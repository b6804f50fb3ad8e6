"""What the two chunked delta-rule kernels share (ops/kda.py, `kda_fwd`: a
decay per channel; ops/gdn.py, `gdn_fwd`: a decay per head): the float32
product, the placement of a block among zeros, and the upper levels of the
triangular inverse.

**The inverse is built by substitution**, never as the series Σ(−A)ⁿ,
whose terms grow combinatorially for near-parallel keys (a mostly white
frame's are) before they cancel. Rows inside a sub-block of 16 are each
kernel's own (KDA sums a sub-block's entries channel by channel with the
decay inside; the scalar-decay form reads them off one product); from
there up both double the block: with T the inverses of (I + M)'s diagonal
blocks of `size` rows, T₂₁ = −T₂₂·M₂₁·T₁₁ — `merge_blocks`.
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

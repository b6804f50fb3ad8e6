"""Seeded weights for the token denoiser on Olmo-Hybrid's stack: what
token_weights.py makes (every leaf random from `--seed`, kernels scaled by
1/sqrt(fan-in), norm scales about 1), with the two leaves of a Gated
DeltaNet layer that set how fast its state forgets drawn as the public
implementation initialises them — token_weights.py would draw `A_log` and
`dt_bias` as 0.1·N(0, 1): A ≈ 1 and softplus ≈ 0.7, a half-life of ONE
token, a state that forgets at once and a cache that holds nothing:

    A_log   = log U(0, 16)                              a head's rate A
    dt_bias = softplus⁻¹(dt), dt = exp U(log lo, log hi)   a head's step

so that a head's log-decay a token is g = −A·softplus(x + dt_bias), x = a·W_a
the data's part: −A·dt·eˣ while dt is small. (A is floored at 16e-3 so
that its logarithm exists.) `dt_range` = (lo, hi) is the configuration's
(`assumed.gdn_dt_range`), the public (1e-3, 1e-1). What came of it is
read off the reference's own g (oh7_ref.gated_delta_net's
`decay_rate_quantiles`, printed by tools/read_limits_tokens_gdn.py).

Only the SHAPES of the tree come from the program, as in token_weights.py;
a top-level group made alone has the same values as in the whole tree.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import token_weights

PUBLIC_DT_RANGE = (1e-3, 1e-1)


def _gdn_leaves(key, gdn, dt_range):
    """The two decay leaves of one delta-rule layer's subtree, drawn anew."""
    lo, hi = (float(x) for x in dt_range)
    k_a, k_dt = jax.random.split(key)
    a_log, b = gdn["A_log"], gdn["dt_bias"]
    u = jax.random.uniform(k_a, a_log.shape, jnp.float32)
    dt = jnp.exp(math.log(lo) + jax.random.uniform(
        k_dt, b.shape, jnp.float32) * math.log(hi / lo))
    return dict(
        gdn, A_log=jnp.log(16.0 * jnp.maximum(u, 1e-3)).astype(a_log.dtype),
        dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(b.dtype))


def make_group(seed: int, shapes, group: str, dt_range=PUBLIC_DT_RANGE):
    """The filled subtree `shapes[group]`, on the default device."""
    tree = token_weights.make_group(seed, shapes, group)
    if "gdn" in tree:
        key = jax.random.fold_in(
            token_weights._group_key(seed, shapes, group), 10 ** 6)
        tree = dict(tree, gdn=_gdn_leaves(key, tree["gdn"], dt_range))
    return tree


def make_weights(seed: int, shapes, groups=None, dt_range=PUBLIC_DT_RANGE):
    """The filled tree (or the named top-level groups of it)."""
    return {g: make_group(seed, shapes, g, dt_range)
            for g in (sorted(shapes) if groups is None else groups)}


def decay_args(config: dict) -> dict:
    """`make_group`'s keyword arguments from a configuration file."""
    return {"dt_range": tuple(config["assumed"].get("gdn_dt_range",
                                                    PUBLIC_DT_RANGE))}

"""Seeded weights for the token denoiser on Phi-4-mini-flash's stack: what
token_weights.py makes (every leaf random from `--seed`, kernels scaled by
1/sqrt(fan-in), norm scales about 1, biases and the λ vectors 0.1·N(0, 1)),
with the four leaves of a Mamba layer that set how fast its state forgets
drawn as the public implementation initialises them — token_weights.py
would draw `A_log` and the step's bias as 0.1·N(0, 1): A ≈ 1 in every
state and softplus ≈ 0.7, a half-life of ONE token, a state that forgets at
once and a cache that holds nothing:

    A_log = log(1, 2, …, N)                  a channel's N rates (S4D-real)
    b_dt  = softplus⁻¹(dt), dt = exp U(log lo, log hi)    a channel's step
    W_dt  = U(±rank^(−1/2))                               the data's part
    D     = 1

so that a (channel, state)'s log-decay a token is −n·softplus(x + b_dt), x
the data's part: −n·dt·eˣ while dt is small. `dt_range` = (lo, hi) is the
configuration's (`assumed.ssm_dt_range`); the public value is (1e-3, 1e-1).
What came of it is read off the reference's own Δ (p4f_ref.mamba's
`decay_rate_quantiles`, printed by tools/read_limits_tokens_ssm.py).

Only the SHAPES of the tree come from the program, as in token_weights.py;
a top-level group made alone has the same values as in the whole tree.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import token_weights

PUBLIC_DT_RANGE = (1e-3, 1e-1)


def _mamba_leaves(key, mamba, dt_range):
    """The four decay leaves of one Mamba layer's subtree, drawn anew."""
    lo, hi = (float(x) for x in dt_range)
    k_w, k_dt = jax.random.split(key)
    a_log, w, b = mamba["A_log"], mamba["dt"]["kernel"], mamba["dt"]["bias"]
    dt = jnp.exp(math.log(lo) + jax.random.uniform(
        k_dt, b.shape, jnp.float32) * math.log(hi / lo))
    return dict(
        mamba,
        A_log=jnp.broadcast_to(jnp.log(jnp.arange(
            1, a_log.shape[1] + 1, dtype=jnp.float32)),
            a_log.shape).astype(a_log.dtype),
        D=jnp.ones_like(mamba["D"]),
        dt={"kernel": (jax.random.uniform(k_w, w.shape, jnp.float32, -1.0,
                                          1.0) / math.sqrt(w.shape[0])
                       ).astype(w.dtype),
            "bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(b.dtype)})


def make_group(seed: int, shapes, group: str, dt_range=PUBLIC_DT_RANGE):
    """The filled subtree `shapes[group]`, on the default device."""
    tree = token_weights.make_group(seed, shapes, group)
    if "mamba" in tree:
        key = jax.random.fold_in(
            token_weights._group_key(seed, shapes, group), 10 ** 6)
        tree = dict(tree, mamba=_mamba_leaves(key, tree["mamba"], dt_range))
    return tree


def make_weights(seed: int, shapes, groups=None, dt_range=PUBLIC_DT_RANGE):
    """The filled tree (or the named top-level groups of it)."""
    return {g: make_group(seed, shapes, g, dt_range)
            for g in (sorted(shapes) if groups is None else groups)}


def decay_args(config: dict) -> dict:
    """`make_group`'s keyword arguments from a configuration file."""
    return {"dt_range": tuple(config["assumed"].get("ssm_dt_range",
                                                    PUBLIC_DT_RANGE))}

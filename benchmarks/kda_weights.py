"""Seeded weights for the token denoiser on Kimi-Linear's stack: what
token_weights.py makes (every leaf random from `--seed`, kernels scaled by
1/sqrt(fan-in), norm scales about 1, the router's columns AND its
correction bias tied in `router_replicas`), with the three leaves of a KDA
layer that set how fast its state forgets drawn as the public
implementation initialises them — token_weights.py would draw `A_log` and
`dt_bias` as 0.1·N(0, 1): A ≈ 1 and softplus ≈ 0.7, a half-life of ONE
token, a state that forgets at once and a cache that holds nothing:

    A_log   = log U(1, 16)                              a head's rate A
    dt_bias = softplus⁻¹(dt), dt = exp U(log lo, log hi)  a channel's step
    W_f↑    = N(0, 1)/sqrt(rank) × `f_up_scale`         the data's part

so that a channel's log-decay a token is g = −A·softplus(x + dt_bias), x
the data's part: −A·dt·eˣ while dt is small. `dt_range` = (lo, hi) and
`f_up_scale` are the configuration's (`assumed.kda_dt_range`,
`assumed.kda_f_up_scale`); the public values are (1e-3, 1e-1) and 1. What
came of them is read off the reference's own g (kl48_ref.layer's
`decay_rate_quantiles`, printed by tools/read_limits_tokens_kda.py).

Only the SHAPES of the tree come from the program, as in token_weights.py;
a top-level group made alone has the same values as in the whole tree.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import token_weights

PUBLIC_DT_RANGE = (1e-3, 1e-1)


def _kda_leaves(key, kda, dt_range, f_up_scale):
    """The three decay leaves of one KDA layer's subtree, drawn anew."""
    lo, hi = (float(x) for x in dt_range)
    k_a, k_dt = jax.random.split(key)
    a_log = kda["A_log"]
    u = jax.random.uniform(k_a, a_log.shape, jnp.float32)
    dt = jnp.exp(math.log(lo) + jax.random.uniform(
        k_dt, kda["dt_bias"].shape, jnp.float32) * math.log(hi / lo))
    f_b = kda["f_b"]["kernel"]
    return dict(
        kda, A_log=jnp.log(1.0 + 15.0 * u).astype(a_log.dtype),
        dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(
            kda["dt_bias"].dtype),
        f_b={"kernel": (f_b.astype(jnp.float32) * float(f_up_scale)).astype(
            f_b.dtype)})


def make_group(seed: int, shapes, group: str, router_replicas: int = 1,
               dt_range=PUBLIC_DT_RANGE, f_up_scale: float = 1.0):
    """The filled subtree `shapes[group]`, on the default device."""
    tree = token_weights.make_group(seed, shapes, group, router_replicas)
    if "kda" in tree:
        key = jax.random.fold_in(
            token_weights._group_key(seed, shapes, group), 10 ** 6)
        tree = dict(tree, kda=_kda_leaves(key, tree["kda"], dt_range,
                                          f_up_scale))
    return tree


def make_weights(seed: int, shapes, groups=None, router_replicas: int = 1,
                 dt_range=PUBLIC_DT_RANGE, f_up_scale: float = 1.0):
    """The filled tree (or the named top-level groups of it)."""
    return {g: make_group(seed, shapes, g, router_replicas, dt_range,
                          f_up_scale)
            for g in (sorted(shapes) if groups is None else groups)}


def decay_args(config: dict) -> dict:
    """`make_group`'s keyword arguments from a configuration file."""
    a = config["assumed"]
    return {"router_replicas": int(a.get("router_replicas", 1)),
            "dt_range": tuple(a.get("kda_dt_range", PUBLIC_DT_RANGE)),
            "f_up_scale": float(a.get("kda_f_up_scale", 1.0))}


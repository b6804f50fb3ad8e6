"""Seeded weights for the token denoiser on LongCat-Flash's stack: what
token_weights.py makes (every leaf random from `--seed`, kernels scaled by
1/sqrt(fan-in), norm scales about 1), with ONE leaf a layer drawn
otherwise — the router's correction bias.

token_weights.py draws a bias as 0.1·N(0, 1), which is kl48's law for its
router's bias: there the scores are sigmoids of order 1/2 and the bias
moves a choice where two scores are within a tenth or so. This router's
scores are a softmax over `router_width` = 768 outputs, of order 1/768: a
bias of 0.1·N(0, 1) beside them IS the choice — every token would take the
twelve outputs of the largest bias and the scores would decide nothing. So
the same law is kept in units of the uniform score:

    e_score_correction_bias = 0.1·N(0, 1) / router_width

(the configuration's `assumed.router_bias_law` says it too): a tenth of a
uniform score, enough to move the 12th and 13th of a token's ranking past
each other where they are close and not to rank the outputs by itself. The source's bias is what its load-balancing controller left,
on the scores' scale too; config.json is silent on it.

Only the SHAPES of the tree come from the program, as in token_weights.py;
a top-level group made alone has the same values as in the whole tree.
"""

from __future__ import annotations

import jax.numpy as jnp

import token_weights

def make_group(seed: int, shapes, group: str, router_replicas: int = 1):
    """The filled subtree `shapes[group]`, on the default device: the
    router's bias token_weights.py's 0.1·N(0, 1) over the router's width."""
    tree = token_weights.make_group(seed, shapes, group, router_replicas)
    if "bias" in tree.get("router", {}):
        bias = tree["router"]["bias"]
        tree = dict(tree, router=dict(tree["router"], bias=(
            bias.astype(jnp.float32) / bias.shape[-1]).astype(bias.dtype)))
    return tree


def make_weights(seed: int, shapes, groups=None, router_replicas: int = 1):
    """The filled tree (or the named top-level groups of it)."""
    return {g: make_group(seed, shapes, g, router_replicas)
            for g in (sorted(shapes) if groups is None else groups)}


def router_args(config: dict) -> dict:
    """`make_group`'s keyword arguments from a configuration file."""
    return {"router_replicas": int(
        config["assumed"].get("router_replicas", 1))}

"""Seeded weights for the token denoiser, made on the device a leaf at a
time (benchmarks/weights.py fills a whole tree in one jit through float32
and takes a 3-D `kernel` for a per-head dense; an expert stack is (held,
in, out) and one stacked leaf is 1 GB in float32, so this family brings its
own builder. The rules are the same: every leaf random from `--seed`,
kernels scaled by 1/sqrt(fan-in) so activations keep unit scale, norm
scales about 1, biases small).

**The router's columns.** With 128 independent random columns a router
over correlated tokens concentrates: the tokens of a frame share a large
common component (the logsnr embedding, attention over a mostly white
conditioning frame), so a few experts take most assignments, and whether
they are among the 32 held here is the seed's luck — the held share of a
pass swings between 0.15 and 0.39 (CPU, full widths) and the step time
with it: 2.2–2.5 % spread over twelve seeds on the chip (PERF.md, PR 26),
where a trained router is balanced over the chips of its layer. So with
`router_replicas=r` the kernel is E / r seeded prototype columns, each at
r experts (e, e + E/r, …): with r = top-k = the chips that share a layer,
a token's top-k are the r replicas of its best prototype, one on each
chip, and this chip is given exactly one assignment a token whatever the
seed; how they spread over its experts stays the seed's. The configuration
file states it under `assumed`; r = 1 is independent columns (the tests).

Only the SHAPES of the tree come from the program (`jax.eval_shape` of its
init); no value does. A top-level group (`layer_3`, `patch_in`, ...) can
be made alone — `make_group` — and gets the same values as in the whole
tree: that is how the reference, which holds one layer at a time on the
chip, is handed the program's weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from weights import seed_key


@functools.lru_cache(maxsize=None)
def _filler(name: str, shape, dtype, replicas: int = 1):
    def fill(key):
        drawn = shape[:-1] + (shape[-1] // replicas,)
        n = jnp.tile(jax.random.normal(key, drawn, jnp.float32),
                     (1,) * (len(shape) - 1) + (replicas,))
        if name == "kernel":
            # dense (in, out) or an expert stack (held, in, out)
            v = n / math.sqrt(shape[-2])
        elif name == "scale":
            v = 1.0 + 0.1 * n
        else:
            v = 0.1 * n
        return v.astype(dtype)

    return jax.jit(fill)


def _group_key(seed: int, shapes, group: str):
    return jax.random.fold_in(seed_key(seed), sorted(shapes).index(group))


def make_group(seed: int, shapes, group: str, router_replicas: int = 1):
    """The filled subtree `shapes[group]`, on the default device."""
    key = _group_key(seed, shapes, group)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes[group])
    out = []
    for i, (path, s) in enumerate(leaves):
        names = [str(getattr(p, "key", p)) for p in path]
        r = router_replicas if "router" in names else 1
        if s.shape[-1] % r:
            raise ValueError(f"{s.shape[-1]} router columns do not divide "
                             f"into {r} replicas")
        out.append(_filler(names[-1], tuple(s.shape),
                           jnp.dtype(s.dtype).name, r)(
            jax.random.fold_in(key, i)))
    return jax.tree_util.tree_unflatten(treedef, out)


def make_weights(seed: int, shapes, groups=None, router_replicas: int = 1):
    """The filled tree (or the named top-level groups of it)."""
    return {g: make_group(seed, shapes, g, router_replicas)
            for g in (sorted(shapes) if groups is None else groups)}

"""Seeded weights, made by the benchmark on the device in one jitted call.

The program initialises its output convolutions to zero, so at its own
initial weights ε̂ is 0 for every input and no arithmetic can be told from
any other. The benchmark therefore makes the weights itself, for the
program and for the reference alike, from `--seed`: every leaf random,
kernels scaled by 1/sqrt(fan-in) so activations keep unit scale through
the residual stack, GroupNorm scales about 1, biases small. Only the
SHAPES of the tree come from the program (`jax.eval_shape` of its init);
no value does.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (the driver's seeds
    pass 2**31; both words are folded in)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def program_seed(seed: int) -> int:
    """The same seed folded into the range every int32 seed field of the
    program accepts."""
    return int(seed) % (2 ** 31 - 1)


def _leaf(key, name: str, shape, dtype):
    n = jax.random.normal(key, shape, jnp.float32)
    if name == "kernel":
        # conv (kh, kw, cin, cout), dense (cin, cout), per-head dense
        # (cin, heads, head_dim): fan-in is everything but the outputs.
        out = shape[-1] if len(shape) != 3 else shape[-1] * shape[-2]
        v = n / math.sqrt(math.prod(shape) / out)
    elif name == "scale":
        v = 1.0 + 0.1 * n
    else:
        v = 0.1 * n
    return v.astype(dtype)


def build_fn(shapes):
    """key → the filled tree, for use inside a jit. `shapes`: a pytree of
    ShapeDtypeStruct with flax leaf names (kernel, bias, scale)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            name = str(getattr(path[-1], "key", path[-1]))
            out.append(_leaf(jax.random.fold_in(key, i), name, s.shape,
                             s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def make_weights(seed: int, shapes):
    """The filled tree on the default device, in one jitted call."""
    return jax.jit(build_fn(shapes))(seed_key(seed))

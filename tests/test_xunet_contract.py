"""The X-UNet's parameter tree and outputs are those of the tree the pins
were written from (the parent of PR 31, which turned the activation the
network carries from (B, F, H, W, C) into (B·F, H, W, C)).

Checkpoints and benchmarks/weights.py address leaves by path, so a change
of the network's inner form must leave the tree alone, path for path,
shape for shape, dtype for dtype; and what crosses `XUNet.apply`'s
boundary — plain rows, a guidance pair with precomputed pose embeddings,
the cond cache's `cond_feats` — must give the numbers it gave. Toy sizes of
`paper256` and `base128`, with per-frame GroupNorm statistics and with the
reference's statistics over a sample's frames.

`PYTHONPATH=<a tree> python tests/test_xunet_contract.py --write` pins
that tree's listing and outputs under tests/golden/ (the batch contract is
all it uses, so it runs on the tree before the change as on the one after).
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.append(ROOT)  # behind PYTHONPATH: --write may name another tree

from novel_view_synthesis_3d_tpu.config import get_preset  # noqa: E402
from novel_view_synthesis_3d_tpu.models.xunet import (  # noqa: E402
    XUNet,
    precompute_cond_feats,
)

GOLDEN = os.path.join(ROOT, "tests", "golden")
TREES = os.path.join(GOLDEN, "xunet_param_trees.json")
OUTPUTS = os.path.join(GOLDEN, "xunet_outputs.npz")
# Two levels and one block a level; three levels and the preset's two.
TOY = {"paper256": {"model.ch": 32, "model.ch_mult": [1, 2],
                    "model.emb_ch": 32, "model.num_res_blocks": 1,
                    "model.attn_resolutions": [8], "model.remat": False},
       "base128": {"model.ch": 32, "model.ch_mult": [1, 1, 2],
                   "model.emb_ch": 48, "model.attn_resolutions": [8, 4]}}
SIDE, B = 16, 2
MODELS = [(preset, per_frame) for preset in ("paper256", "base128")
          for per_frame in (True, False)]
INPUTS = ("plain", "pair", "cond_feats")


def tag(preset, per_frame):
    return f"{preset}-{'per_frame' if per_frame else 'shared'}"


def build(preset, per_frame):
    cfg = get_preset(preset).override(**dict(
        TOY[preset], **{"model.groupnorm_per_frame": per_frame,
                        "data.img_sidelength": SIDE})).validate()
    return XUNet(cfg.model)


def make_batch():
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    eye = jnp.broadcast_to(jnp.eye(3), (B, 3, 3))
    K = jnp.array([[SIDE / 2.0, 0, SIDE / 2.0], [0, SIDE / 2.0, SIDE / 2.0],
                   [0, 0, 1.0]])
    return {"x": jax.random.uniform(ks[0], (B, SIDE, SIDE, 3), minval=-1,
                                    maxval=1),
            "z": jax.random.normal(ks[1], (B, SIDE, SIDE, 3)),
            "logsnr": jax.random.uniform(ks[2], (B,), minval=-6, maxval=6),
            "R1": eye, "t1": jax.random.normal(ks[3], (B, 3)),
            "R2": eye, "t2": jax.random.normal(ks[4], (B, 3)),
            "K": jnp.broadcast_to(K, (B, 3, 3))}


def make_params(model, batch):
    """Seeded weights with no zero leaf (the output head is zero at init,
    and so is every block's second convolution)."""
    params = model.init({"params": jax.random.PRNGKey(0)}, batch,
                        cond_mask=jnp.ones((B,)), train=False)["params"]
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(11), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def listing(params):
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            [list(leaf.shape), str(leaf.dtype)]
            for path, leaf in jax.tree_util.tree_leaves_with_path(params)}


def output(model, params, batch, inputs):
    """`XUNet.apply` on the rows a caller of that kind hands it."""
    cond = {k: batch[k] for k in ("x", "R1", "t1", "R2", "t2", "K")}
    if inputs == "plain":
        mask = jnp.array([1.0, 0.0])
    else:
        # A guidance pair as the samplers lay it out: rows [cond…,
        # uncond…], the conditioning computed once outside the step.
        batch = jax.tree.map(lambda a: jnp.concatenate([a, a]), batch)
        mask = jnp.concatenate([jnp.ones((B,)), jnp.zeros((B,))])
        batch.update(model.precompute(params, cond))
        assert isinstance(batch["pose_embs"][0], tuple)  # a pair a level
        if inputs == "cond_feats":
            feats = precompute_cond_feats(model, params, cond)
            batch["cond_feats"] = jnp.concatenate([feats, feats])
    return np.asarray(model.apply({"params": params}, batch, cond_mask=mask,
                                  train=False), np.float32)


@functools.lru_cache(maxsize=None)
def setup(preset, per_frame):
    """(model, batch, params): built once for a model's four cases."""
    model, batch = build(preset, per_frame), make_batch()
    return model, batch, make_params(model, batch)


CASES = ([("tree", *m) for m in MODELS]
         + [(inputs, *m) for m in MODELS for inputs in INPUTS])


@pytest.mark.parametrize(
    "what,preset,per_frame", CASES,
    ids=[f"{what}-{tag(p, f)}" for what, p, f in CASES])
def test_xunet_is_the_pinned_one(what, preset, per_frame):
    model, batch, params = setup(preset, per_frame)
    if what == "tree":
        with open(TREES) as fh:
            pinned = json.load(fh)[tag(preset, per_frame)]
        assert listing(params) == pinned
        return
    got = output(model, params, batch, what)
    want = np.load(OUTPUTS)[f"{what}-{tag(preset, per_frame)}"]
    assert got.shape == want.shape and np.isfinite(got).all()
    assert float(np.abs(want).mean()) > 1e-3  # a pin of zeros pins nothing
    # On the machine that wrote the pins the rank-4 network returns them
    # to the bit (`apply` runs op by op here). Both toys compute in
    # bfloat16, where another CPU's convolution may round a sum the other
    # way: held to a few bfloat16 ulps of the output's scale, which a row
    # paired with the wrong frame or normalised with the wrong rows
    # misses by the scale itself.
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=0, atol=0.03 * scale)
    assert float(np.sqrt(np.mean((got - want) ** 2))) <= 0.006 * scale


if __name__ == "__main__":
    assert sys.argv[1:] == ["--write"], __doc__
    jax.config.update("jax_platforms", "cpu")
    trees, outs = {}, {}
    for preset, per_frame in MODELS:
        model, batch, params = setup(preset, per_frame)
        trees[tag(preset, per_frame)] = listing(params)
        for inputs in INPUTS:
            outs[f"{inputs}-{tag(preset, per_frame)}"] = output(
                model, params, batch, inputs)
    with open(TREES, "w") as fh:
        json.dump(trees, fh, indent=0, sort_keys=True)
    np.savez_compressed(OUTPUTS, **outs)
    print("pinned", len(trees), "trees and", len(outs), "outputs from",
          os.path.dirname(os.path.dirname(sys.modules[
              XUNet.__module__].__file__)))

"""X-UNet building blocks (clean-room Flax, TPU-first layout).

Capability-matches the blocks at /root/reference/model/xunet.py:46-140 with
two deliberate layout changes for TPU:

  1. All spatial convolutions operate on (B·F, H, W, C) via 2-D `nn.Conv`
     instead of the reference's 3-D `Conv(kernel=(1,3,3))` over (B,F,H,W,C).
     The math is identical (the frame-axis kernel is 1), but 2-D NHWC convs
     hit XLA:TPU's well-tuned conv→MXU path and avoid degenerate-dim layouts.
  2. GroupNorm defaults to **per-frame** statistics (one set per row of
     B·F). The reference shares statistics across frames (xunet.py:46-52
     applies flax GroupNorm over the full (B,2,H,W,C) view — SURVEY.md §2.2
     quirk); set `per_frame=False` for bit-faithful reference behavior.

Shape contract: every module here takes and returns the activation as
**(B·F, H, W, C)** — rows batch-major, a sample's F frames adjacent — with
the frame count F a static integer beside it (`frames=`), free (the
reference hardcodes 2). No reshape splits or merges the row axis between
two convolutions: XLA:TPU lays a convolution's operand out with the rows
in the sublanes and the channels in the lanes (`{3,0,2,1:T(8,128)}` at 8
rows), a (B, F, …) array cannot carry that tiling, and every such reshape
was a physical copy to row-major with the norm, FiLM, swish and residual
passes run apart from the convolutions (PERF.md §6, PR 31). (B, F) exists
in two places only: inside AttnBlock, whose tokens are (B, F, H·W, C) —
it runs at the small levels —, and inside GroupNorm(per_frame=False),
whose statistics span a sample's frames.

Each leaf (FrameConv, GroupNorm, FiLM, an AttnBlock's attention) runs
inside one `jax.named_scope("lk.<kind>")`, and so do a block's few own
ops: the layer kind its instructions are booked under when device time is
read back from a profiler capture (models/xunet.layer_of holds the
vocabulary and the precedence). Metadata only, like `og.<label>`. The
attention kernel's `pt.kernel` / `pt.layout` parts inside `lk.attn` are
stamped by its wrapper (ops/flash_attention.py), not here.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from novel_view_synthesis_3d_tpu.ops._pallas import over_data_axis
from novel_view_synthesis_3d_tpu.ops.resample import (
    avgpool_downsample,
    nearest_neighbor_upsample,
)

nonlinearity = nn.swish

INV_SQRT2 = float(1.0 / np.sqrt(2.0))

# What FiLM reads: one array over all rows, or a (full extent, 1 × 1) pair.
Emb = Union[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]


def out_init_scale():
    """Zero-init for output convs (reference model/xunet.py:11-12)."""
    return nn.initializers.variance_scaling(0.0, "fan_in", "truncated_normal")


class FrameConv(nn.Module):
    """k×k spatial conv applied independently to every row of (B·F, H, W,
    C_in): a frame is a batch row of the 2-D convolution."""

    features: int
    kernel: int = 3
    stride: int = 1
    zero_init: bool = False
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h: jnp.ndarray) -> jnp.ndarray:
        with jax.named_scope("lk.conv"):
            return nn.Conv(
                self.features,
                kernel_size=(self.kernel, self.kernel),
                strides=(self.stride, self.stride),
                kernel_init=(out_init_scale() if self.zero_init
                             else nn.linear.default_kernel_init),
                dtype=self.dtype,
                param_dtype=self.param_dtype,
            )(h)


class GroupNorm(nn.Module):
    """32-group GroupNorm over (B·F, H, W, C), then `act` ('swish' or
    none) in the module's dtype.

    Per-frame statistics are per row. `per_frame=False` (statistics over a
    sample's `frames` rows jointly) views the rows as (B, F, H, W, C) for
    the norm alone, and needs `frames`.

    The norm has no pass of its own on the chip: between two convolutions
    of (B·F, H, W, C) XLA:TPU fuses the statistics into the convolution
    before and the apply into the one after (PERF.md §6, PR 31;
    tests/test_tpu_compile.py pins it a block shape).
    """

    per_frame: bool = True
    act: Optional[str] = None
    frames: Optional[int] = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h: jnp.ndarray) -> jnp.ndarray:
        with jax.named_scope("lk.gn"):
            N, H, W, C = h.shape
            norm = nn.GroupNorm(num_groups=32, dtype=self.dtype)
            if self.per_frame:
                y = norm(h)
            else:
                # Reference-compat: statistics reduce over (F, H, W) jointly.
                F = self.frames
                assert F and N % F == 0, (N, F)
                y = norm(h.reshape(N // F, F, H, W, C)).reshape(h.shape)
            return nonlinearity(y) if self.act == "swish" else y


class FiLM(nn.Module):
    """Feature-wise linear modulation (reference model/xunet.py:54-61).

    `emb` is one array over all of `h`'s rows, or a pair (leading rows at
    `h`'s spatial extent, the remaining rows at 1 × 1): what
    models/xunet.precompute_guidance_pose_embs gives for a guidance pair
    whose unconditional rows' embedding is one vector per frame. The one
    Dense then projects each part at its own extent — a 1 × 1 part once a
    frame, not once a pixel — and the two projections are summed with
    zero rows standing in for each other's: an exact sum, an array over
    all rows to everything after it, and the 1 × 1 part's broadcast left
    to whichever fusion consumes it."""

    features: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h: jnp.ndarray, emb: Emb) -> jnp.ndarray:
        with jax.named_scope("lk.emb"):
            dense = nn.Dense(2 * self.features, dtype=self.dtype,
                             param_dtype=self.param_dtype)
            if isinstance(emb, tuple):
                full, per_frame = (dense(nonlinearity(e)) for e in emb)
                n, m = full.shape[0], per_frame.shape[0]

                def rows(x, before, after):
                    return jax.lax.pad(
                        x, jnp.zeros((), x.dtype),
                        [(before, after, 0)] + [(0, 0, 0)] * (x.ndim - 1))

                # Padded before the split and summed after it: in that
                # order XLA:TPU fuses pad, split and sum into the
                # modulation's one pass over `h`. Summed before the
                # split, it writes the sum out at 2C channels for every
                # row first; split before the pad, the split becomes a
                # copy of its own (PERF.md §6, PR 25).
                scale, shift = (
                    f + rows(p, n, 0) for f, p in
                    zip(jnp.split(rows(full, 0, m), 2, axis=-1),
                        jnp.split(per_frame, 2, axis=-1)))
            else:
                scale, shift = jnp.split(dense(nonlinearity(emb)), 2,
                                         axis=-1)
            return h * (1.0 + scale) + shift


class ResnetBlock(nn.Module):
    """BigGAN-style residual block with optional 2× up/down resampling.

    Reference: model/xunet.py:63-92 — GN→swish→(resample)→conv→GN→FiLM→swish→
    dropout→zero-init conv, Dense skip projection on channel change, output
    scaled by 1/√2.
    """

    features: Optional[int] = None
    dropout: float = 0.0
    resample: Optional[str] = None
    per_frame_gn: bool = True
    frames: Optional[int] = None  # read by GroupNorm(per_frame=False) alone
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    # The convolutions, the norms and the FiLM stamp their own kind; the
    # block's own few ops (resampling, dropout, skip projection, residual
    # sum) are booked as `conv`, the activation after the FiLM as `gn`.
    @nn.compact
    def __call__(self, h_in: jnp.ndarray, emb: Emb, *,
                 train: bool) -> jnp.ndarray:
        C = h_in.shape[-1]
        features = C if self.features is None else self.features
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        gn_kw = dict(per_frame=self.per_frame_gn, frames=self.frames,
                     dtype=self.dtype)

        h = GroupNorm(act="swish", **gn_kw)(h_in)
        if self.resample is not None:
            updown = {
                "up": nearest_neighbor_upsample,
                "down": avgpool_downsample,
            }[self.resample]
            with jax.named_scope("lk.conv"):
                h = updown(h)
                h_in = updown(h_in)
        h = FrameConv(features, **kw)(h)
        h = FiLM(features=features, **kw)(GroupNorm(**gn_kw)(h), emb)
        with jax.named_scope("lk.gn"):
            h = nonlinearity(h)
        with jax.named_scope("lk.conv"):
            h = nn.Dropout(rate=self.dropout)(h, deterministic=not train)
        h = FrameConv(features, zero_init=True, **kw)(h)
        with jax.named_scope("lk.conv"):
            if C != features:
                h_in = nn.Dense(features, **kw)(h_in)
            return (h + h_in) * INV_SQRT2


class AttnLayer(nn.Module):
    """Multi-head dot-product attention over token sequences.

    Reference: model/xunet.py:94-103. The reference's output projection is
    commented out (xunet.py:126); `out_proj=True` enables a zero-init
    projection for configs that want it.
    """

    attn_heads: int = 4
    out_proj: bool = False
    use_flash: bool = False
    # jax Mesh the program is partitioned over: the Pallas kernels run
    # per 'data' shard (ops/_pallas.over_data_axis); with `ring`, exact
    # ring attention over its 'seq' axis instead.
    mesh: Optional[object] = None
    ring: bool = False
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, *, q: jnp.ndarray, kv: jnp.ndarray) -> jnp.ndarray:
        C = q.shape[-1]
        head_dim = C // self.attn_heads
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        qh = nn.DenseGeneral((self.attn_heads, head_dim), **kw)(q)
        kh = nn.DenseGeneral((self.attn_heads, head_dim), **kw)(kv)
        vh = nn.DenseGeneral((self.attn_heads, head_dim), **kw)(kv)
        if self.ring:
            # Sequence-parallel exact attention: tokens sharded over 'seq',
            # batch riding the 'data' axis, k/v blocks rotating via ppermute.
            from novel_view_synthesis_3d_tpu.parallel.mesh import DATA_AXIS
            from novel_view_synthesis_3d_tpu.parallel.ring_attention import (
                ring_self_attention)
            out = ring_self_attention(qh, kh, vh, self.mesh,
                                      batch_axis=DATA_AXIS)
        elif self.use_flash:
            from novel_view_synthesis_3d_tpu.ops.flash_attention import (
                flash_attention)
            out = over_data_axis(flash_attention, self.mesh)(qh, kh, vh)
        else:
            out = nn.dot_product_attention(qh, kh, vh)  # (B, L, heads, hd)
        if self.out_proj:
            return nn.DenseGeneral(C, axis=(-2, -1), kernel_init=out_init_scale(),
                                   **kw)(out)
        return out.reshape(out.shape[:-2] + (C,))


class AttnBlock(nn.Module):
    """Self- or cross-frame attention over flattened H·W token sequences.

    Reference: model/xunet.py:105-127. A single shared AttnLayer serves all
    frames (shared q/k/v weights). 'self': each frame attends to itself —
    batched over B·F in one call. 'cross': frame i attends to the
    concatenation of all *other* frames' pre-update tokens (for F=2 this is
    exactly the reference's frame0↔frame1 exchange). Residual scaled 1/√2.

    Takes and returns (B·F, H, W, C) like its neighbours; cross attention
    alone tells a sample's `frames` apart, as (B, F, H·W, C) tokens formed
    and dissolved in here.
    """

    attn_type: str
    frames: int
    attn_heads: int = 4
    out_proj: bool = False
    use_flash: bool = False
    mesh: Optional[object] = None
    ring: bool = False
    per_frame_gn: bool = True
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h_in: jnp.ndarray) -> jnp.ndarray:
        N, H, W, C = h_in.shape
        F = self.frames
        h = GroupNorm(per_frame=self.per_frame_gn, frames=F,
                      dtype=self.dtype)(h_in)
        # Everything after the norm is `attn`: the one stamp covers the
        # AttnLayer (this block is its only caller) and the block's own
        # token shuffling and residual.
        with jax.named_scope("lk.attn"):
            tokens = h.reshape(N, H * W, C)
            layer = AttnLayer(attn_heads=self.attn_heads,
                              out_proj=self.out_proj,
                              use_flash=self.use_flash, mesh=self.mesh,
                              ring=self.ring,
                              dtype=self.dtype, param_dtype=self.param_dtype)
            if self.attn_type == "self":
                out = layer(q=tokens, kv=tokens)
            elif self.attn_type == "cross":
                if F < 2:
                    raise ValueError("cross-frame attention needs F >= 2")
                tokens = tokens.reshape(N // F, F, H * W, C)
                outs = []
                for i in range(F):
                    others = [tokens[:, j] for j in range(F) if j != i]
                    kv = jnp.concatenate(others, axis=1)  # (B, (F-1)·HW, C)
                    outs.append(layer(q=tokens[:, i], kv=kv))
                out = jnp.stack(outs, axis=1)
            else:
                raise NotImplementedError(self.attn_type)
            return (out.reshape(h_in.shape) + h_in) * INV_SQRT2


class XUNetBlock(nn.Module):
    """ResnetBlock + optional (self-attn, cross-attn) pair.

    Reference: model/xunet.py:129-140.
    """

    features: int
    frames: int
    use_attn: bool = False
    attn_heads: int = 4
    attn_out_proj: bool = False
    attn_use_flash: bool = False
    attn_mesh: Optional[object] = None
    attn_ring: bool = False
    dropout: float = 0.0
    train: bool = False  # attribute (not call arg) so nn.remat needs no statics
    per_frame_gn: bool = True
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, emb: Emb) -> jnp.ndarray:
        kw = dict(per_frame_gn=self.per_frame_gn, frames=self.frames,
                  dtype=self.dtype, param_dtype=self.param_dtype)
        attn_kw = dict(attn_heads=self.attn_heads, out_proj=self.attn_out_proj,
                       use_flash=self.attn_use_flash, mesh=self.attn_mesh,
                       ring=self.attn_ring, **kw)
        h = ResnetBlock(features=self.features, dropout=self.dropout,
                        **kw)(x, emb, train=self.train)
        if self.use_attn:
            h = AttnBlock(attn_type="self", **attn_kw)(h)
            if self.frames >= 2:
                h = AttnBlock(attn_type="cross", **attn_kw)(h)
        return h

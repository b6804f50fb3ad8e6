"""Spatial up/down-sampling for feature maps (…, H, W, C): any leading axes,
(B·F,) as the X-UNet carries them or (B, F).

Behavior-matches /root/reference/model/xunet.py:14-21: 2× nearest-neighbor
upsampling via broadcast (no gather — XLA lowers this to a cheap reshape
pattern on TPU) and 2×2 average-pool downsampling.
"""

from __future__ import annotations

import jax.numpy as jnp


def nearest_neighbor_upsample(h: jnp.ndarray, k: int = 2) -> jnp.ndarray:
    """(…, H, W, C) → (…, kH, kW, C) by nearest neighbor."""
    *lead, H, W, C = h.shape
    h = h.reshape(*lead, H, 1, W, 1, C)
    h = jnp.broadcast_to(h, (*lead, H, k, W, k, C))
    return h.reshape(*lead, H * k, W * k, C)


def avgpool_downsample(h: jnp.ndarray, k: int = 2) -> jnp.ndarray:
    """(…, H, W, C) → (…, H/k, W/k, C) by k×k mean pooling.

    Implemented as a reshape + mean (not a conv): maps to a pure VPU
    reduction on TPU with no MXU round-trip.
    """
    *lead, H, W, C = h.shape
    h = h.reshape(*lead, H // k, k, W // k, k, C)
    return h.mean(axis=(-4, -2))

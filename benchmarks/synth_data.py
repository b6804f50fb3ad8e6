"""Inputs made from the seed: conditioning views with valid cameras for the
samplers. (Cameras and images follow the program's data/synthetic.py, by
copy, so that the yardstick does not move when that file is edited.)"""

from __future__ import annotations

import numpy as np


def look_at_pose(cam_pos, target=None):
    """cam→world 4×4, camera +z looking from cam_pos toward target."""
    target = np.zeros(3) if target is None else target
    fwd = target - cam_pos
    fwd = fwd / np.linalg.norm(fwd)
    right = np.cross(fwd, np.array([0.0, 0.0, 1.0]))
    if np.linalg.norm(right) < 1e-6:
        right = np.array([1.0, 0.0, 0.0])
    right = right / np.linalg.norm(right)
    pose = np.eye(4, dtype=np.float32)
    pose[:3, 0], pose[:3, 1] = right, np.cross(fwd, right)
    pose[:3, 2], pose[:3, 3] = fwd, cam_pos
    return pose


def _orbit_cam(az, el, dist=2.5):
    return np.array([dist * np.cos(az) * np.cos(el),
                     dist * np.sin(az) * np.cos(el), dist * np.sin(el)])


def render_view(base_color, azimuth, elevation, size):
    """A pose-dependent image: a coloured blob over a textured ground, so
    that rows of a batch all differ. uint8 HWC."""
    img = np.full((size, size, 3), 255, dtype=np.uint8)
    cx = int((np.cos(azimuth) * 0.3 + 0.5) * size)
    cy = int((np.sin(azimuth) * 0.3 + 0.5) * size)
    r = max(2, int(size * (0.15 + 0.05 * np.sin(elevation))))
    yy, xx = np.mgrid[0:size, 0:size]
    img[(xx - cx) ** 2 + (yy - cy) ** 2 <= r * r] = (
        base_color * 255).astype(np.uint8)
    strip = np.linspace(0, 1, size)[:, None] * base_color[None]
    img[: size // 8] = (strip * 255).astype(np.uint8)[None]
    return img


def cond_views(n, size, seed):
    """n conditioning views as float32 arrays with a leading axis of n:
    x in [-1, 1], cam→world R1/t1 (source) and R2/t2 (target), K."""
    rng = np.random.default_rng(seed)
    f = size * 1.2
    K = np.array([[f, 0, size / 2], [0, f, size / 2], [0, 0, 1]], np.float32)
    out = {k: [] for k in ("x", "R1", "t1", "R2", "t2", "K")}
    for _ in range(n):
        az, el = rng.uniform(0, 2 * np.pi), rng.uniform(0.2, 0.5)
        src = look_at_pose(_orbit_cam(az, el))
        tgt = look_at_pose(_orbit_cam(az + rng.uniform(0.3, 2.0),
                                      rng.uniform(0.2, 0.5)))
        img = render_view(rng.uniform(0.2, 1.0, size=3), az, el, size)
        out["x"].append(img.astype(np.float32) / 127.5 - 1.0)
        out["R1"].append(src[:3, :3]); out["t1"].append(src[:3, 3])
        out["R2"].append(tgt[:3, :3]); out["t2"].append(tgt[:3, 3])
        out["K"].append(K)
    return {k: np.stack(v).astype(np.float32) for k, v in out.items()}

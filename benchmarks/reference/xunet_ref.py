"""Plain reference of the X-UNet (3DiM, arXiv 2210.04628) and of the
diffusion arithmetic around it: the forward pass, the guided ε̂ of a
denoising step, and the cosine schedule's tables with DDPM respacing.

Straightforward jax.numpy in float32 with matmul precision "highest", no
kernels, no cache, no batching tricks. It imports nothing of the program
and takes nothing the program made: weights come from
`benchmarks/weights.py` (the benchmark's own, from the seed), inputs from
the traffic. Parameter NAMES follow the program's flax tree because the
same seeded tree is handed to both sides.

`prec` selects the arithmetic of every matmul/conv/attention product:
  "f32"  float32 inputs, precision highest (the reference proper)
  "bf16" inputs rounded to bfloat16, float32 accumulation
  "fp8"  inputs rounded to float8_e4m3fn (scaled per tensor to its
         range), float32 accumulation
  "fp8_act"  the same, and every layer's output rounded to fp8 as well:
         fp8 as the compute type throughout, the way the program's
         bfloat16 is
The lower ones are the controls: the reference put in the program's
place in the nearest precision below the one the configuration states
(bf16 → fp8). Everything else stays float32 in every mode.

Departures from the paper, all the program's own and stated in its
models/: GroupNorm statistics per frame, no attention output
projection, 2-D convs per frame.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
_JITS = {}  # jitted closures, kept across calls so each compiles once


def _sizes_key(m):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


_Q = {"f32": None, "bf16": (jnp.bfloat16, None),
      "fp8": (jnp.float8_e4m3fn, 448.0),
      "fp8_act": (jnp.float8_e4m3fn, 448.0)}


def _q(x, prec):
    """Round x to the control's input type (identity for the reference).
    fp8 is scaled per tensor to the type's range, as an fp8 matmul path
    would."""
    if _Q[prec] is None:
        return x
    dtype, top = _Q[prec]
    s = 1.0 if top is None else jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _qa(x, prec):
    """Round a layer's OUTPUT too, where the control's compute type is the
    lower precision throughout (`*_act`), as the program's bf16 is."""
    return _q(x, prec) if prec.endswith("_act") else x


def swish(x):
    return x * jax.nn.sigmoid(x)


def dense(p, x, prec):
    """x (..., Cin) @ kernel (Cin, *out) + bias."""
    k = p["kernel"]
    out_shape = k.shape[1:]
    y = jnp.matmul(_q(x, prec), _q(k.reshape(k.shape[0], -1), prec),
                   precision=HI)
    return _qa(y.reshape(x.shape[:-1] + out_shape) + p["bias"], prec)


def conv(p, x, prec, stride=1):
    """Per-frame 2-D SAME conv on (B, F, H, W, C)."""
    p = p["Conv_0"]
    B, F = x.shape[:2]
    y = jax.lax.conv_general_dilated(
        _q(x.reshape((B * F,) + x.shape[2:]), prec), _q(p["kernel"], prec),
        (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)
    y = _qa(y + p["bias"], prec)
    return y.reshape((B, F) + y.shape[1:])


def group_norm(p, x, groups=32, eps=1e-6):
    """GroupNorm with statistics per frame over (H, W, C/groups)."""
    p = p["GroupNorm_0"]
    B, F, H, W, C = x.shape
    g = x.reshape(B, F, H * W, groups, C // groups)
    mean = g.mean(axis=(2, 4), keepdims=True)
    var = jnp.square(g - mean).mean(axis=(2, 4), keepdims=True)
    y = ((g - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    return y * p["scale"] + p["bias"]


def resnet_block(p, h_in, emb, prec, resample=None):
    C = h_in.shape[-1]
    h = swish(group_norm(p["GroupNorm_0"], h_in))
    if resample == "down":
        def pool(a):
            B, F, H, W, c = a.shape
            return a.reshape(B, F, H // 2, 2, W // 2, 2, c).mean(axis=(3, 5))
        h, h_in = pool(h), pool(h_in)
    elif resample == "up":
        def up(a):
            return jnp.repeat(jnp.repeat(a, 2, axis=2), 2, axis=3)
        h, h_in = up(h), up(h_in)
    h = conv(p["FrameConv_0"], h, prec)
    features = h.shape[-1]
    film = dense(p["FiLM_0"]["Dense_0"], swish(emb), prec)
    scale, shift = jnp.split(film, 2, axis=-1)
    h = _qa(swish(group_norm(p["GroupNorm_1"], h) * (1.0 + scale) + shift),
            prec)
    h = conv(p["FrameConv_1"], h, prec)
    if C != features:
        h_in = dense(p["Dense_0"], h_in, prec)
    return _qa((h + h_in) / math.sqrt(2.0), prec)


def attention(p, q_tok, kv_tok, prec):
    """Multi-head attention, q (B, Lq, C), kv (B, Lk, C); no output
    projection (the program's default)."""
    q = dense(p["DenseGeneral_0"], q_tok, prec)   # (B, Lq, heads, hd)
    k = dense(p["DenseGeneral_1"], kv_tok, prec)
    v = dense(p["DenseGeneral_2"], kv_tok, prec)
    hd = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", _q(q / math.sqrt(hd), prec),
                   _q(k, prec), precision=HI)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", _q(w, prec), _q(v, prec),
                     precision=HI)
    return out.reshape(out.shape[:2] + (-1,))


def attn_block(p, h_in, kind, prec):
    B, F, H, W, C = h_in.shape
    tok = group_norm(p["GroupNorm_0"], h_in).reshape(B, F, H * W, C)
    layer = p["AttnLayer_0"]
    if kind == "self":
        flat = tok.reshape(B * F, H * W, C)
        out = attention(layer, flat, flat, prec).reshape(B, F, H * W, C)
    else:
        outs = []
        for i in range(F):
            others = jnp.concatenate(
                [tok[:, j] for j in range(F) if j != i], axis=1)
            outs.append(attention(layer, tok[:, i], others, prec))
        out = jnp.stack(outs, axis=1)
    return _qa((out.reshape(h_in.shape) + h_in) / math.sqrt(2.0), prec)


def xunet_block(p, h, emb, use_attn, prec):
    h = resnet_block(p["ResnetBlock_0"], h, emb, prec)
    if use_attn:
        h = attn_block(p["AttnBlock_0"], h, "self", prec)
        h = attn_block(p["AttnBlock_1"], h, "cross", prec)
    return h


def posenc_nerf(x, max_deg):
    scales = jnp.asarray([2.0 ** i for i in range(max_deg)], x.dtype)
    xb = (x[..., None, :] * scales[:, None]).reshape(x.shape[:-1] + (-1,))
    emb = jnp.sin(jnp.concatenate([xb, xb + np.pi / 2.0], axis=-1))
    return jnp.concatenate([x, emb], axis=-1)


def camera_rays(R, t, K, H, W):
    """World-space ray origins and unit directions through pixel centres."""
    v, u = jnp.meshgrid(jnp.arange(H, dtype=jnp.float32) + 0.5,
                        jnp.arange(W, dtype=jnp.float32) + 0.5,
                        indexing="ij")
    fx, fy = K[..., 0, 0, None, None], K[..., 1, 1, None, None]
    cx, cy = K[..., 0, 2, None, None], K[..., 1, 2, None, None]
    x, y = (u - cx) / fx, (v - cy) / fy
    d = jnp.stack([x, y, jnp.ones_like(x)], axis=-1)
    d = jnp.einsum("...ij,...hwj->...hwi", R, d, precision=HI)
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    return jnp.broadcast_to(t[..., None, None, :], d.shape), d


def conditioning(p, m, batch, cond_mask, prec):
    """logsnr embedding (B, emb) and one pose embedding per level."""
    emb_ch, levels = m["emb_ch"], len(m["ch_mult"])
    B, H, W, _ = batch["z"].shape
    lam = jnp.clip(batch["logsnr"], -20.0, 20.0)
    lam = 2.0 * jnp.arctan(jnp.exp(-lam / 2.0)) / np.pi
    half = emb_ch // 2
    freqs = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                    * -(math.log(10000.0) / (half - 1)))
    ang = (lam * 1000.0)[:, None] * freqs[None]
    e = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    e = dense(p["Dense_0"], e, prec)
    logsnr_emb = dense(p["Dense_1"], swish(e), prec)

    R = jnp.stack([batch["R1"], batch["R2"]], axis=1)
    t = jnp.stack([batch["t1"], batch["t2"]], axis=1)
    K = jnp.broadcast_to(batch["K"][:, None], (B, 2, 3, 3))
    pos, dirs = camera_rays(R, t, K, H, W)
    pose = jnp.concatenate([posenc_nerf(pos, 15), posenc_nerf(dirs, 8)],
                           axis=-1)
    pose = pose * cond_mask[:, None, None, None, None]
    pose_embs = [conv(p[f"FrameConv_{i}"], pose, prec, stride=2 ** i)
                 for i in range(levels)]
    return logsnr_emb, pose_embs


def forward(params, m, batch, cond_mask, prec="f32"):
    """ε̂ for the target frame. `m` is the model's sizes (ch, ch_mult,
    emb_ch, num_res_blocks, attn_resolutions); batch holds x, z (B,H,W,3),
    logsnr (B,), R1, t1, R2, t2, K; cond_mask (B,) is 1 where the pose
    conditioning is kept (0 = the unconditional half of guidance)."""
    levels, nrb = len(m["ch_mult"]), m["num_res_blocks"]
    attn_res = set(m["attn_resolutions"])
    logsnr_emb, pose_embs = conditioning(
        params["ConditioningProcessor_0"], m, batch, cond_mask, prec)

    def emb(level):
        return logsnr_emb[:, None, None, None, :] + pose_embs[level]

    h = jnp.stack([batch["x"], batch["z"]], axis=1)
    h = conv(params["FrameConv_0"], h, prec)
    hs = [h]
    nb = nr = 0
    for lvl in range(levels):
        for _ in range(nrb):
            h = xunet_block(params[f"XUNetBlock_{nb}"], h, emb(lvl),
                            h.shape[3] in attn_res, prec)
            nb += 1
            hs.append(h)
        if lvl != levels - 1:
            h = resnet_block(params[f"ResnetBlock_{nr}"], h, emb(lvl + 1),
                             prec, resample="down")
            nr += 1
            hs.append(h)
    h = xunet_block(params[f"XUNetBlock_{nb}"], h, emb(levels - 1),
                    h.shape[3] in attn_res, prec)
    nb += 1
    for lvl in reversed(range(levels)):
        for _ in range(nrb + 1):
            skip = hs.pop()
            h = xunet_block(params[f"XUNetBlock_{nb}"],
                            jnp.concatenate([h, skip], axis=-1), emb(lvl),
                            skip.shape[3] in attn_res, prec)
            nb += 1
        if lvl != 0:
            h = resnet_block(params[f"ResnetBlock_{nr}"], h, emb(lvl - 1),
                             prec, resample="up")
            nr += 1
    h = swish(group_norm(params["GroupNorm_0"], h))
    # The output head is float32 in the program too: inputs only.
    return conv(params["FrameConv_1"], h, prec.replace("_act", ""))[:, -1]


# ---------------------------------------------------------------------------
# diffusion arithmetic (cosine schedule, DDPM respacing, guidance)
# ---------------------------------------------------------------------------
def cosine_tables(T, steps=None, s=0.008):
    """float64 tables of the cosine schedule, respaced to `steps` evenly
    spaced timesteps when given. Returns a dict of float32 arrays plus
    `t_orig`, the original timestep of each kept index."""
    x = np.linspace(0, T, T + 1, dtype=np.float64)
    acp = np.cos((x / T + s) / (1 + s) * np.pi * 0.5) ** 2
    acp = acp / acp[0]
    betas = np.clip(1.0 - acp[1:] / acp[:-1], 0.0, 0.9999)
    use = np.arange(T)
    if steps is not None and steps != T:
        full = np.cumprod(1.0 - betas)
        use = np.unique(np.linspace(0, T - 1, steps).round().astype(np.int64))
        kept = full[use]
        betas = 1.0 - kept / np.append(1.0, kept[:-1])
    alphas = 1.0 - betas
    acp = np.cumprod(alphas)
    prev = np.append(1.0, acp[:-1])
    var = betas * (1.0 - prev) / (1.0 - acp)
    clipped = np.append(var[1], var[1:]) if len(var) > 1 else np.maximum(
        var, 1e-20)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    return {
        "t_orig": use, "T": T,
        "sqrt_acp": f32(np.sqrt(acp)), "sqrt_1macp": f32(np.sqrt(1.0 - acp)),
        "sqrt_recip": f32(np.sqrt(1.0 / acp)),
        "sqrt_recipm1": f32(np.sqrt(1.0 / acp - 1.0)),
        "c1": f32(betas * np.sqrt(prev) / (1.0 - acp)),
        "c2": f32((1.0 - prev) * np.sqrt(alphas) / (1.0 - acp)),
        "log_var": f32(np.log(clipped)),
    }


def logsnr_cosine(t_orig, T, lo=-20.0, hi=20.0):
    u = jnp.asarray(t_orig, jnp.float32) / float(T)
    b = jnp.arctan(jnp.exp(-0.5 * hi))
    a = jnp.arctan(jnp.exp(-0.5 * lo)) - b
    return -2.0 * jnp.log(jnp.tan(a * u + b))


def guided_eps_fn(m, w, prec="f32"):
    """(params, batch, cond_mask) → the guided ε̂ of ONE view, (H, W, 3): the
    batch holds the view twice, cond_mask is (1, 0), and
    ε̂ = (1 + w)·ε̂(conditional) − w·ε̂(unconditional). Jitted once per
    (sizes, w, precision)."""
    def guided(params, batch, cond_mask):
        eps = forward(params, m, batch, cond_mask, prec)
        return (1.0 + w) * eps[0] - w * eps[1]

    return _JITS.setdefault(("guided", _sizes_key(m), prec, float(w)),
                            jax.jit(guided))

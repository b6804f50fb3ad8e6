"""Plain reference of the token denoiser (models/token_denoiser.py): patch
tokens of both frames through Mistral-Small-4-119B-2603's decoder layer
(`mistral4` config.json: latent attention, a router over all experts with
top-k renormalised, gated-SiLU experts, one shared expert), ε̂ of the
target frame out.

Straightforward jax.numpy in float32 with matmul precision "highest". BOTH
frames go through ONE full forward with the frame mask (a token sees its
own frame and the frames before it): no cache, no once-a-call pass, no
sorting and no grouped product — the expert layer is a loop over the held
experts with a dense mask. It imports nothing of the program and takes
nothing the program made: weights come from the benchmark's own seeded
builder (token_weights.py), inputs from the traffic. Parameter NAMES follow
the program's tree because the same seeded tree is handed to both sides.
The reference is given the same held experts as the program: what the
absent experts would have added is left out on both sides.

`m` (sizes): hidden_size, num_hidden_layers, num_attention_heads,
q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
n_routed_experts, num_experts_per_tok, norm_topk_prob,
routed_scaling_factor, rms_norm_eps, rope_interleave, rope_parameters
(dict), held_experts [first, count], patch_size.

`prec` selects the arithmetic of every matmul and attention product, as in
xunet_ref.py: "f32" (the reference proper), "bf16", "fp8" (inputs rounded
to float8_e4m3fn, scaled per tensor), "fp8_act" (and every layer's output
as well). The lower ones are the controls. Norms, softmax, the router's
logits and rotary tables stay float32 in every mode (the configuration
states them so).

`attention` selects the algebraic form of latent attention: "up_projected"
(keys and values of every head made from the latent, the published
equations) or "absorbed" (the key up-projection folded into the query, the
value up-projection applied after the softmax) — equal in exact
arithmetic; the program ships one and a test holds it to the other.

The trunk can be run whole (`forward`) or piecewise (`embed`, `layer`,
`head`), which is how the chip fits it: one float32 layer is 3.4 GB.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LOGSNR_CLEAN = 20.0
_JITS = {}

_Q = {"f32": None, "bf16": (jnp.bfloat16, None),
      "fp8": (jnp.float8_e4m3fn, 448.0),
      "fp8_act": (jnp.float8_e4m3fn, 448.0)}


def _q(x, prec):
    """Round x to the control's input type (identity for the reference);
    fp8 is scaled per tensor to the type's range."""
    if _Q[prec] is None:
        return x
    dtype, top = _Q[prec]
    s = 1.0 if top is None else jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _qa(x, prec):
    return _q(x, prec) if prec == "fp8_act" else x


def mm(x, w, prec):
    return _qa(jnp.matmul(_q(x.astype(jnp.float32), prec),
                          _q(w.astype(jnp.float32), prec), precision=HI),
               prec)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def silu(x):
    return x * jax.nn.sigmoid(x)


# -- rotary embedding ----------------------------------------------------------
def yarn_inv_freq(rope, dim):
    """(dim/2,) float64 frequencies: per-dimension blend of θ^(−2i/dim)
    and the same ÷ factor by the linear ramp between the two correction
    dimensions (β_fast and β_slow rotations inside the original length)."""
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])
    i = np.arange(dim // 2, dtype=np.float64)
    freq = base ** (-2.0 * i / dim)

    def corr(rot):
        return dim * math.log(orig / (rot * 2.0 * math.pi)) / (
            2.0 * math.log(base))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freq * (1.0 - ramp) + (freq / factor) * ramp


def rope_rotate(x, positions, m):
    """x (..., L, dim) or (..., L, heads, dim) rotated at `positions` (L,)."""
    dim = x.shape[-1]
    ang = np.asarray(positions, np.float64)[:, None] * yarn_inv_freq(
        m["rope_parameters"], dim)[None]
    cos, sin = (jnp.asarray(f(ang), jnp.float32) for f in (np.cos, np.sin))
    if x.ndim == 4:
        cos, sin = cos[:, None], sin[:, None]
    if m["rope_interleave"]:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def query_position_scale(positions, m):
    rope = m["rope_parameters"]
    return 1.0 + float(rope["llama_4_scaling_beta"]) * np.log1p(np.floor(
        np.asarray(positions, np.float64)
        / float(rope["original_max_position_embeddings"])))


def softmax_scale(m):
    rope = m["rope_parameters"]
    ms = 0.1 * float(rope["mscale_all_dim"]) * math.log(
        float(rope["factor"])) + 1.0 if float(rope["factor"]) > 1 else 1.0
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 * ms * ms


# -- the adapters (this repo's) --------------------------------------------------
def posenc_nerf(x, max_deg):
    scales = jnp.asarray([2.0 ** i for i in range(max_deg)], x.dtype)
    xb = jnp.reshape(x[..., None, :] * scales[:, None], x.shape[:-1] + (-1,))
    emb = jnp.sin(jnp.concatenate([xb, xb + np.pi / 2.0], axis=-1))
    return jnp.concatenate([x, emb], axis=-1)


def camera_rays(R, t, K, H, W):
    v, u = jnp.meshgrid(jnp.arange(H, dtype=jnp.float32) + 0.5,
                        jnp.arange(W, dtype=jnp.float32) + 0.5,
                        indexing="ij")
    fx, fy = K[..., 0, 0][..., None, None], K[..., 1, 1][..., None, None]
    cx, cy = K[..., 0, 2][..., None, None], K[..., 1, 2][..., None, None]
    x, y = (u - cx) / fx, (v - cy) / fy
    d_cam = jnp.stack([x, y, jnp.ones_like(x)], axis=-1)
    d = jnp.einsum("...ij,...hwj->...hwi", R, d_cam, precision=HI)
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    return jnp.broadcast_to(t[..., None, None, :], d.shape), d


def patches(img, p):
    B, H, W, C = img.shape
    x = img.reshape(B, H // p, p, W // p, p, C).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, (H // p) * (W // p), p * p * C)


def unpatch(tok, H, W, p):
    B = tok.shape[0]
    x = tok.reshape(B, H // p, W // p, p, p, -1)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, H, W, -1)


def logsnr_emb(p, m, logsnr, prec):
    lam = jnp.clip(logsnr.astype(jnp.float32), -20.0, 20.0)
    u = 2.0 * jnp.arctan(jnp.exp(-lam / 2.0)) / np.pi
    half = m["hidden_size"] // 2
    freq = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                   * -(np.log(10000.0) / (half - 1)))
    ang = (u * 1000.0)[:, None] * freq[None]
    e = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    e = mm(e, p["dense_0"]["kernel"], prec) \
        + p["dense_0"]["bias"].astype(jnp.float32)
    return mm(silu(e), p["dense_1"]["kernel"], prec) \
        + p["dense_1"]["bias"].astype(jnp.float32)


def frame_tokens(params, m, img, R, t, K, logsnr, cond_mask, prec):
    H, W = img.shape[1:3]
    pos, dirs = camera_rays(R, t, K, H, W)
    rays = jnp.concatenate([posenc_nerf(pos, 15), posenc_nerf(dirs, 8)],
                           axis=-1)
    p = m["patch_size"]
    tok = mm(patches(img.astype(jnp.float32), p),
             params["patch_in"]["kernel"], prec)
    ray = mm(patches(rays, p), params["ray_in"]["kernel"], prec)
    tok = tok + ray * cond_mask.astype(jnp.float32)[:, None, None]
    return tok + logsnr_emb(params["emb"], m, logsnr, prec)[:, None, :]


def embed(params, m, batch, cond_mask, prec="f32"):
    """→ h (B, 2L, hidden): [conditioning frame's tokens, target's]."""
    x = batch["x"]
    B = x.shape[0]
    clean = jnp.full((B,), LOGSNR_CLEAN, jnp.float32)
    tc = frame_tokens(params, m, x, batch["R1"], batch["t1"], batch["K"],
                      clean, cond_mask, prec)
    tz = frame_tokens(params, m, batch["z"], batch["R2"], batch["t2"],
                      batch["K"], batch["logsnr"], cond_mask, prec)
    return jnp.concatenate([tc, tz], axis=1)


# -- the layer -------------------------------------------------------------------
def attention(p, m, a, prec, form):
    """Latent attention over the whole sequence a (B, S, hidden), S = 2L,
    with the frame mask. → (B, S, heads·v)."""
    B, S, _ = a.shape
    NH, dn, dr, dv, C = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"],
                         m["kv_lora_rank"])
    eps = m["rms_norm_eps"]
    pos = np.arange(S)
    frame = pos // (S // 2)
    allowed = jnp.asarray(frame[:, None] >= frame[None, :])  # (q, k)
    c_q = rms_norm(mm(a, p["q_a"]["kernel"], prec), p["q_norm"]["scale"],
                   eps)
    q = mm(c_q, p["q_b"]["kernel"], prec).reshape(B, S, NH, dn + dr)
    q_nope, q_rope = q[..., :dn], rope_rotate(q[..., dn:], pos, m)
    qs = jnp.asarray(query_position_scale(pos, m), jnp.float32)
    q_nope, q_rope = (t * qs[None, :, None, None] for t in (q_nope, q_rope))
    kv_a = mm(a, p["kv_a"]["kernel"], prec)
    c_kv = rms_norm(kv_a[..., :C], p["kv_norm"]["scale"], eps)
    k_rope = rope_rotate(kv_a[..., C:], pos, m)               # one head
    w_kvb = p["kv_b"]["kernel"].astype(jnp.float32).reshape(C, NH, dn + dv)
    scale = softmax_scale(m)

    def one_row(args):
        q_nope, q_rope, c_kv, k_rope = args
        if form == "up_projected":
            kv = mm(c_kv, w_kvb.reshape(C, -1), prec).reshape(S, NH, dn + dv)
            s = jnp.einsum("qnd,knd->nqk", _q(q_nope, prec),
                           _q(kv[..., :dn], prec), precision=HI)
            v = kv[..., dn:]
        else:
            # absorbed: q_nope·W_uk into the latent's space, values after
            q_abs = jnp.einsum("qnd,cnd->qnc", _q(q_nope, prec),
                               _q(w_kvb[..., :dn], prec), precision=HI)
            s = jnp.einsum("qnc,kc->nqk", _q(q_abs, prec), _q(c_kv, prec),
                           precision=HI)
        s = s + jnp.einsum("qnd,kd->nqk", _q(q_rope, prec),
                           _q(k_rope, prec), precision=HI)
        s = jnp.where(allowed[None], s * scale, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        if form == "up_projected":
            o = jnp.einsum("nqk,knd->qnd", _q(w, prec), _q(v, prec),
                           precision=HI)
        else:
            o_lat = jnp.einsum("nqk,kc->qnc", _q(w, prec), _q(c_kv, prec),
                               precision=HI)
            o = jnp.einsum("qnc,cnd->qnd", _q(o_lat, prec),
                           _q(w_kvb[..., dn:], prec), precision=HI)
        return _qa(o.reshape(S, NH * dv), prec)

    return jax.lax.map(one_row, (q_nope, q_rope, c_kv, k_rope))


def router(p, m, b):
    """(top-k probabilities (T, k), ids (T, k), margin (T,)): softmax over
    all experts in float32, top-k, renormalised; `margin` is
    ln p_(k) − ln p_(k+1), how far the last chosen expert leads the first
    one left out."""
    logits = jnp.matmul(b, p["kernel"].astype(jnp.float32), precision=HI)
    probs = jax.nn.softmax(logits, axis=-1)
    k = m["num_experts_per_tok"]
    top_p, top_i = jax.lax.top_k(probs, k + 1)
    margin = jnp.log(top_p[:, k - 1]) - jnp.log(top_p[:, k])
    top_p, top_i = top_p[:, :k], top_i[:, :k]
    if m["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p * float(m["routed_scaling_factor"]), top_i, margin


def gated_mlp(x, gate, up, down, prec):
    return mm(silu(mm(x, gate, prec)) * mm(x, up, prec), down, prec)


def experts_part(p, m, b, top_p, top_i, prec, held=None):
    """Σ_{e ∈ top-k(token) ∩ held} p_e·expert_e(b): a loop over the held
    experts, each applied to every token under a dense mask. → (part,
    tokens per held expert)."""
    first, count = m["held_experts"] if held is None else held
    off = first - m["held_experts"][0]   # into the stack that is held here

    def body(j, carry):
        acc, counts = carry
        hit = top_i == first + j                             # (T, k)
        w = jnp.sum(jnp.where(hit, top_p, 0.0), axis=-1)
        y = gated_mlp(b, p["gate"]["kernel"][off + j],
                      p["up"]["kernel"][off + j],
                      p["down"]["kernel"][off + j], prec)
        return (acc + w[:, None] * y,
                counts.at[j].set(jnp.sum(hit).astype(jnp.int32)))

    return jax.lax.fori_loop(
        0, count, body, (jnp.zeros_like(b), jnp.zeros((count,), jnp.int32)))


def layer(p, m, h, prec="f32", attention_form="up_projected", held=None,
          parts=False):
    """One decoder layer over h (B, S, hidden). → (h, aux) with aux =
    {"margin" (B, S), "counts" (count,) tokens per held expert, "held_hits"
    (B, S) a token's assignments to held experts}; with `parts`, aux also holds the
    layer's three additions apart ("attn", "shared", "routed") and "b", the
    normalised tokens the router and the experts are given."""
    eps = m["rms_norm_eps"]
    B, S, H = h.shape
    a = rms_norm(h, p["attn_norm"]["scale"], eps)
    attn = mm(attention(p, m, a, prec, attention_form), p["o"]["kernel"],
              prec)
    h = h + attn
    b = rms_norm(h, p["mlp_norm"]["scale"], eps).reshape(B * S, H)
    top_p, top_i, margin = router(p["router"], m, b)
    routed, counts = experts_part(p["experts"], m, b, top_p, top_i, prec,
                                  held)
    shared = gated_mlp(b, p["shared"]["gate"]["kernel"],
                       p["shared"]["up"]["kernel"],
                       p["shared"]["down"]["kernel"], prec)
    first, count = m["held_experts"] if held is None else held
    hits = jnp.sum((top_i >= first) & (top_i < first + count), axis=-1)
    aux = {"margin": margin.reshape(B, S), "counts": counts,
           "held_hits": hits.reshape(B, S)}
    if parts:
        aux.update(attn=attn, shared=shared.reshape(B, S, H),
                   routed=routed.reshape(B, S, H), b=b.reshape(B, S, H))
    return h + (shared + routed).reshape(B, S, H), aux


def head(params, m, h, side, prec="f32"):
    """Last norm and the output adapter on the target's tokens → ε̂
    (B, side, side, 3)."""
    L = h.shape[1] // 2
    hn = rms_norm(h[:, L:], params["final_norm"]["scale"], m["rms_norm_eps"])
    return unpatch(mm(hn, params["out"]["kernel"], prec), side, side,
                   m["patch_size"])


def forward(params, m, batch, cond_mask, prec="f32",
            attention_form="up_projected", aux=False):
    """ε̂ (B, H, W, 3) of the whole model; with `aux` also the per-layer
    aux dicts."""
    h = embed(params, m, batch, cond_mask, prec)
    auxes = []
    for i in range(m["num_hidden_layers"]):
        h, a = layer(params[f"layer_{i}"], m, h, prec, attention_form)
        auxes.append(a)
    eps = head(params, m, h, batch["z"].shape[1], prec)
    return (eps, auxes) if aux else eps


def _key(m):
    return tuple(sorted((k, repr(v)) for k, v in m.items()))


def jitted(name, m, *static):
    """embed/layer/head/forward jitted once per (sizes, static args)."""
    fn = {"embed": embed, "layer": layer, "head": head,
          "forward": forward}[name]
    return _JITS.setdefault(
        (name, _key(m)) + static,
        jax.jit(lambda *a: fn(a[0], m, *a[1:], *static)))


def guided_eps_fn(m, w, prec="f32", attention_form="up_projected"):
    """(params, batch, cond_mask) → the guided ε̂ of ONE view, (H, W, 3): the
    batch holds the view twice, cond_mask is (1, 0), and
    ε̂ = (1 + w)·ε̂(conditional) − w·ε̂(unconditional)."""
    def guided(params, batch, cond_mask):
        eps = forward(params, m, batch, cond_mask, prec, attention_form)
        return (1.0 + w) * eps[0] - w * eps[1]

    return _JITS.setdefault(("guided", _key(m), prec, attention_form,
                             float(w)), jax.jit(guided))

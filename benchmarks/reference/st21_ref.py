"""Plain reference of the token denoiser on SmallThinker-21BA3B-Instruct's
decoder layer (models/token_denoiser.py, `SmallThinkerLayer`), ε̂ of the
target frame out.

The layer, as the source's config.json and model card give it, for the
tokens x (S, hidden) of a sequence at positions p = 0 … S−1:

    a      = RMSNorm(x)
    logits = a·W_r                         the router, BEFORE attention
    q, k, v = a·W_q, a·W_k, a·W_v          28 query heads, 4 key/value
                                           heads of 128; no biases
    rope_layout[l] = 1: q, k rotated, θ = rope_theta, halves (i, i + 64),
                        no scaling; 0: no positional term at all
    scores = q·kᵀ·128^(−1/2); query head h reads key/value head h // 7
    x′     = x + heads·W_o;   b = RMSNorm(x′)
    chosen = top-k of logits;  gates = softmax over the chosen logits
    y      = Σ_e gate_e · W_down,e( relu(W_gate,e·b) ⊙ (W_up,e·b) )
    x_next = x′ + y                        no shared expert, no dense layer

**Departures from the source, each the denoiser's and said in the
configuration file too.** (1) The mask: a token at p sees a key at p′ iff
frame(p′) ≤ frame(p) — this repo's frame rule in place of the language
model's p′ ≤ p — and, where sliding_window_layout[l] = 1, p − p′ <
sliding_window_size, the source's one-sided window as published. It is
written below as one dense (S, S) predicate. (2) The adapters around the
trunk (patches, rays, the logsnr embedding, the output Dense) are this
repo's, the same as the other token configuration's. (3) The router reads
the NORMALISED attention input a (`assumed.router_input`): the source says
"router placed before attention" and not on which side of the norm.

Straightforward jax.numpy in float32 with matmul precision "highest". BOTH
frames go through ONE full forward: no cache, no once-a-call pass, no
kernels, no sorting and no grouped product — the expert layer is a loop
over the held experts with a dense mask, attention a loop over rows and
heads (one head's (S, S) scores at a time: 268 MB at S = 8192, so that a
layer fits the chip beside nothing else). It imports nothing of the
program; weights come from the benchmark's own seeded builder
(token_weights.py); parameter NAMES follow the program's tree because the
same seeded tree is handed to both sides.

`m` (sizes, the source's key names): hidden_size, num_hidden_layers,
num_attention_heads, num_key_value_heads, head_dim, rope_layout,
sliding_window_layout, sliding_window_size, rope_theta,
moe_num_primary_experts, moe_num_active_primary_experts,
moe_ffn_hidden_size, norm_topk_prob, rms_norm_eps, held_experts [first,
count], patch_size.

`prec` selects the arithmetic of every matmul and attention product, as in
ms4_ref.py: "f32" (the reference proper), "bf16", "fp8" (inputs rounded
to float8_e4m3fn, scaled per tensor), "fp8_act". The lower ones are the
controls. Norms, softmax, the router's logits and rotary tables stay
float32 in every mode.

**A near tie in the router.** With 64 independent columns and top-6 the
sixth and seventh logits of a token lie within 0.01 of each other in about
one layer-row in ten, and which of the two bfloat16 picks says nothing of
its arithmetic. `layer(..., choice=, margin=)` therefore takes the
PROGRAM's chosen experts: where the reference's own margin ln p₆ − ln p₇
is under `margin`, and every expert the program chose is, by the
reference's own logits, within `margin` of the reference's sixth, the
reference computes the token with the program's set (gates from its own
logits). A program's choice outside that is not adopted and the token is
reported (`excluded`); a token at a clear margin always keeps the
reference's own choice.
"""

from __future__ import annotations

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


def rope_rotate(x, positions, theta):
    """x (S, heads, dim) rotated at `positions` (S,): pairs (i, i + dim/2),
    frequencies θ^(−2i/dim), no scaling."""
    dim = x.shape[-1]
    freq = float(theta) ** (-2.0 * np.arange(dim // 2, dtype=np.float64)
                            / dim)
    ang = np.asarray(positions, np.float64)[:, None] * freq[None]
    cos, sin = (jnp.asarray(f(ang), jnp.float32)[:, None]
                for f in (np.cos, np.sin))
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# -- the adapters (this repo's, as ms4_ref.py's) -------------------------------
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
def visible(m, i, S):
    """The dense (S, S) predicate [query p, key p′] of layer i over a
    sequence of two frames: frame(p′) ≤ frame(p), and in a window layer
    p − p′ < sliding_window_size."""
    pos = np.arange(S)
    frame = pos // (S // 2)
    seen = frame[:, None] >= frame[None, :]
    if m["sliding_window_layout"][i]:
        seen &= pos[:, None] - pos[None, :] < m["sliding_window_size"]
    return seen


def attention(p, m, i, a, prec):
    """Grouped-query attention of layer i over the whole sequence a (B, S,
    hidden) under `visible`. → (B, S, heads·head_dim)."""
    B, S, _ = a.shape
    NH, NKV, D = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    q = mm(a, p["q"]["kernel"], prec).reshape(B, S, NH, D)
    k = mm(a, p["k"]["kernel"], prec).reshape(B, S, NKV, D)
    v = mm(a, p["v"]["kernel"], prec).reshape(B, S, NKV, D)
    seen = jnp.asarray(visible(m, i, S))
    scale = D ** -0.5
    pos = np.arange(S)

    def one_row(args):
        q, k, v = args
        if m["rope_layout"][i]:
            q = rope_rotate(q, pos, m["rope_theta"])
            k = rope_rotate(k, pos, m["rope_theta"])
        k = jnp.repeat(k, NH // NKV, axis=1)      # head h reads h // group
        v = jnp.repeat(v, NH // NKV, axis=1)

        def one_head(hqkv):
            qh, kh, vh = hqkv                                  # (S, D) each
            s = jnp.matmul(_q(qh, prec), _q(kh, prec).T, precision=HI)
            w = jax.nn.softmax(jnp.where(seen, s * scale, -jnp.inf), axis=-1)
            return jnp.matmul(_q(w, prec), _q(vh, prec), precision=HI)

        o = jax.lax.map(one_head, tuple(
            t.transpose(1, 0, 2) for t in (q, k, v)))          # (NH, S, D)
        return _qa(o.transpose(1, 0, 2).reshape(S, NH * D), prec)

    return jax.lax.map(one_row, (q, k, v))


def router(p, m, a, choice=None, margin=0.0):
    """(gates (T, k), ids (T, k), margin (T,), adopted (T,), excluded (T,))
    of the normalised attention input a (T, hidden): the top-k logits,
    softmax over the chosen; `margin` out is ln p_(k) − ln p_(k+1) of the
    reference's own ranking. With `choice` (T, k), the program's chosen
    experts, a token whose own margin is under `margin` takes them if all
    lie within `margin` of its k-th (`adopted`), and is `excluded` if not
    (the module's head)."""
    logits = jnp.matmul(a, p["kernel"].astype(jnp.float32), precision=HI)
    k = m["moe_num_active_primary_experts"]
    top_l, top_i = jax.lax.top_k(logits, k + 1)
    gap = top_l[:, k - 1] - top_l[:, k]          # = ln p_(k) − ln p_(k+1)
    top_l, top_i = top_l[:, :k], top_i[:, :k]
    T = a.shape[0]
    adopted = excluded = jnp.zeros((T,), bool)
    if choice is not None:
        theirs = jnp.take_along_axis(logits, choice, axis=1)
        near = gap < margin
        within = jnp.min(theirs, axis=1) >= top_l[:, k - 1] - margin
        adopted, excluded = near & within, near & ~within
        top_i = jnp.where(adopted[:, None], choice, top_i)
        top_l = jnp.where(adopted[:, None], theirs, top_l)
    # softmax over the chosen = softmax over all, renormalised
    # (moe_primary_router_apply_softmax, norm_topk_prob)
    gates = jax.nn.softmax(top_l, axis=-1)
    return gates, top_i, gap, adopted, excluded


def experts_part(p, m, b, gates, top_i, prec, held=None):
    """Σ_{e ∈ chosen(token) ∩ held} gate_e·expert_e(b), expert_e(b) =
    W_down( relu(W_gate·b) ⊙ W_up·b ): a loop over the held experts, each
    applied to every token under a dense mask. → (part, tokens per held
    expert)."""
    first, count = m["held_experts"] if held is None else held
    off = first - m["held_experts"][0]   # into the stack that is held here

    def body(j, carry):
        acc, counts = carry
        hit = top_i == first + j                             # (T, k)
        w = jnp.sum(jnp.where(hit, gates, 0.0), axis=-1)
        g = mm(b, p["gate"]["kernel"][off + j], prec)
        u = mm(b, p["up"]["kernel"][off + j], prec)
        y = mm(jnp.maximum(g, 0.0) * u, p["down"]["kernel"][off + j], prec)
        return (acc + w[:, None] * y,
                counts.at[j].set(jnp.sum(hit).astype(jnp.int32)))

    return jax.lax.fori_loop(
        0, count, body, (jnp.zeros_like(b), jnp.zeros((count,), jnp.int32)))


def layer(p, m, h, i, prec="f32", held=None, parts=False, choice=None,
          margin=0.0):
    """Decoder layer i over h (B, S, hidden). → (h, aux) with aux =
    {"margin", "adopted", "excluded" (B, S), "counts" (count,) tokens per
    held expert}; with `parts`, aux also holds the layer's two additions
    apart ("attn", "routed"), "a" and "b" (the normalised tokens the router
    and the experts are given) and "gates", "chosen" (B, S, k). `choice`
    (B, S, k) and `margin` as `router` takes them."""
    eps = m["rms_norm_eps"]
    B, S, H = h.shape
    a = rms_norm(h, p["attn_norm"]["scale"], eps)
    gates, top_i, gap, adopted, excluded = router(
        p["router"], m, a.reshape(B * S, H),
        None if choice is None else choice.reshape(B * S, -1), margin)
    attn = mm(attention(p, m, i, a, prec), p["o"]["kernel"], prec)
    h = h + attn
    b = rms_norm(h, p["mlp_norm"]["scale"], eps).reshape(B * S, H)
    routed, counts = experts_part(p["experts"], m, b, gates, top_i, prec,
                                  held)
    aux = {"margin": gap.reshape(B, S), "counts": counts,
           "adopted": adopted.reshape(B, S),
           "excluded": excluded.reshape(B, S)}
    if parts:
        aux.update(attn=attn, routed=routed.reshape(B, S, H), a=a,
                   b=b.reshape(B, S, H), gates=gates.reshape(B, S, -1),
                   chosen=top_i.reshape(B, S, -1))
    return h + routed.reshape(B, S, H), aux


def head(params, m, h, side, prec="f32"):
    """Last norm and the output adapter on the target's tokens → ε̂
    (B, side, side, 3)."""
    L = h.shape[1] // 2
    hn = rms_norm(h[:, L:], params["final_norm"]["scale"], m["rms_norm_eps"])
    return unpatch(mm(hn, params["out"]["kernel"], prec), side, side,
                   m["patch_size"])


def forward(params, m, batch, cond_mask, prec="f32", aux=False):
    """ε̂ (B, H, W, 3) of the whole model; with `aux` also the per-layer
    aux dicts."""
    h = embed(params, m, batch, cond_mask, prec)
    auxes = []
    for i in range(m["num_hidden_layers"]):
        h, a = layer(params[f"layer_{i}"], m, h, i, prec)
        auxes.append(a)
    eps = head(params, m, h, batch["z"].shape[1], prec)
    return (eps, auxes) if aux else eps


def _key(m):
    return tuple(sorted((k, repr(v)) for k, v in m.items()))


def jitted(name, m, *static):
    """embed/head/forward jitted once per (sizes, static args)."""
    fn = {"embed": embed, "head": head, "forward": forward}[name]
    return _JITS.setdefault(
        (name, _key(m)) + static,
        jax.jit(lambda *a: fn(a[0], m, *a[1:], *static)))


def jitted_layer(m, i, prec="f32", parts=False, margin=0.0):
    """(p, h[, choice]) → `layer`'s (h, aux) for layer i, jitted once per
    (sizes, layer kind, static args): the layers of one kind — same
    rotary, same window — share a program."""
    kind = (m["rope_layout"][i], m["sliding_window_layout"][i])
    i = list(zip(m["rope_layout"], m["sliding_window_layout"])).index(kind)

    def run(p, h, choice=None):
        return layer(p, m, h, i, prec, None, parts, choice, margin)

    return _JITS.setdefault(("layer", _key(m), kind, prec, parts, margin),
                            jax.jit(run))

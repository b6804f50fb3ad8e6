"""Plain reference of the token denoiser on Laguna-S-2.1's decoder stack
(models/token_denoiser.py, `LagunaLayer`), ε̂ of the target frame out.

The layer, as the source's config.json gives it (i = layer index; RMSNorm ε
10⁻⁶; no bias anywhere: `attention_bias` false and the config names no
other), for the tokens h (S, hidden) of a sequence at positions p = 0 … S−1:

    a   = RMSNorm(h; w_attn)               N_i = num_attention_heads_per_layer[i]
    q   = a·Wq_i → (S, N_i, 128);   k, v = a·Wk, a·Wv → (S, 8, 128)
    full_attention   : lanes 0..63 of q and k rotated (partial_rotary_factor
                       0.5), yarn frequencies over dim 64 (θ 5e5, factor
                       128, original 8192, β_fast 32, β_slow 1), cos and sin
                       × attention_factor; lanes 64..127 pass unrotated
    sliding_attention: all 128 lanes rotated, θ 1e4, no scaling
    o_n = softmax(q_n·k_{n // (N_i/8)}ᵀ / √128 over the visible keys)·v_{n // (N_i/8)}
    g   = sigmoid(a·Wg_i) → (S, N_i);   o_n ← g_n·o_n       ("gating": "per-head")
    h   ← h + concat_n(o_n)·Wo_i
    b   = RMSNorm(h; w_mlp)
    mlp_layer_types[i] "dense":  h ← h + Wd·(silu(Wg·b) ⊙ Wu·b), width 12288
    "sparse": s = softmax_float32(b·Wr) over 256 (no soft cap); top-10 of s;
              p = s_top / Σ s_top × 2.5; gates on the OUTPUT
              h ← h + Σ_{e held} p_e·E_e(b) + S(b)    E_e, S silu-gated, width 1024

**Departures from the source, each the denoiser's and said in the
configuration file too.** (1) The mask: a token at p sees a key at p′ iff
frame(p′) ≤ frame(p) — this repo's frame rule in place of the language
model's p′ ≤ p — and, in a sliding layer, p − p′ < sliding_window, the
source's one-sided window as published. It is written below as one dense
(S, S) predicate (`visible`). (2) The adapters around the trunk (patches,
rays, the logsnr embedding, the output Dense) are this repo's, the same as
the other token configurations'. (3) Of each expert layer only
`held_experts` are computed — this chip's share; the router keeps all its
outputs and its top-10, and the absent experts add nothing. (4) What
config.json is silent on is the configuration file's `assumed`: softmax
scores with no correction bias, no gate on the shared expert, no QK norm,
the head gate a sigmoid of a linear map of the layer's NORMALISED input a,
rotary pairs (i, i + d/2) inside the rotated lanes, the router on b.

Straightforward jax.numpy in float32 with matmul precision "highest". BOTH
frames go through ONE full forward: no cache, no once-a-call pass, no
kernels, no sorting and no grouped product — the expert layer is a loop
over the held experts with a dense mask, attention a loop over rows and
heads (one head's (S, S) scores at a time: 268 MB at S = 8192), the dense
MLP a row at a time. It imports nothing of the program; weights come from
the benchmark's own seeded builder (token_weights.py); parameter NAMES
follow the program's tree because the same seeded tree is handed to both
sides.

`m` (sizes, the source's key names): hidden_size, intermediate_size,
num_hidden_layers, num_key_value_heads, head_dim, rms_norm_eps, num_experts,
num_experts_per_tok, moe_intermediate_size, norm_topk_prob,
sliding_window, rope_parameters {full_attention, sliding_attention},
layer_types, mlp_layer_types, num_attention_heads_per_layer,
moe_routed_scaling_factor, held_experts [first, count], patch_size.

`prec` selects the arithmetic of every matmul and attention product, as in
ms4_ref.py: "f32" (the reference proper), "bf16", "fp8" (inputs rounded
to float8_e4m3fn, scaled per tensor), "fp8_act". The lower ones are the
controls. Norms, softmax, the router's logits, the gate's sigmoid and the
rotary tables stay float32 in every mode.

`control` plants one fault of THIS mechanism in the reference (CONTROLS),
which then stands in the program's place: "no_head_gate" (o goes to W_o
ungated), "swapped_rope" (full layers take the sliding law and sliding
layers the full one), "no_routed_scale" (the × 2.5 left out),
"full_visibility" (a sliding layer sees what a full layer sees).

**A near tie in the router.** As st21_ref.py: `layer(..., choice=,
margin=)` takes the PROGRAM's chosen experts where the reference's own
margin ln p₁₀ − ln p₁₁ is under `margin` and every expert the program chose
lies, by the reference's own logits, within `margin` of the reference's
tenth; a choice outside that is not adopted and the token is reported
(`excluded`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LOGSNR_CLEAN = 20.0
_JITS = {}
CONTROLS = ("no_head_gate", "swapped_rope", "no_routed_scale",
            "full_visibility")

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
def is_window(m, i):
    return m["layer_types"][i] == "sliding_attention"


def is_dense(m, i):
    return m["mlp_layer_types"][i] == "dense"


def rotary_frequencies(law, dim):
    """(dim/2,) float64 frequencies of one of `rope_parameters`' laws over
    `dim` rotated lanes: θ^(−2j/dim); under yarn, that where a pair turns
    more than β_fast times inside the original context, the same ÷ factor
    where it turns less than β_slow times, a linear ramp between."""
    theta = float(law["rope_theta"])
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if law["rope_type"] != "yarn":
        return freq
    orig = float(law["original_max_position_embeddings"])

    def turns_at(rotations):   # the pair index that turns so often
        return dim * np.log(orig / (rotations * 2 * np.pi)) / (
            2 * np.log(theta))

    low = max(np.floor(turns_at(law["beta_fast"])), 0)
    high = min(np.ceil(turns_at(law["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return freq * (1 - ramp) + freq / float(law["factor"]) * ramp


def rope_rotate(x, positions, law):
    """x (S, heads, D) at `positions` (S,): the first partial_rotary_factor
    · D lanes of every head rotated, pairs (j, j + half) inside them, cos
    and sin × the law's attention_factor (1 where it names none); the other
    lanes pass."""
    dim = int(x.shape[-1] * law["partial_rotary_factor"])
    ang = np.asarray(positions, np.float64)[:, None] \
        * rotary_frequencies(law, dim)[None]
    factor = float(law.get("attention_factor", 1.0))
    cos, sin = (jnp.asarray(f(ang) * factor, jnp.float32)[:, None]
                for f in (np.cos, np.sin))
    a, b, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def visible(m, i, S, control=None):
    """The dense (S, S) predicate [query p, key p′] of layer i over a
    sequence of two frames: frame(p′) ≤ frame(p), and in a sliding layer
    p − p′ < sliding_window (one-sided, as the source)."""
    pos = np.arange(S)
    frame = pos // (S // 2)
    seen = frame[:, None] >= frame[None, :]
    if is_window(m, i) and control != "full_visibility":
        seen &= pos[:, None] - pos[None, :] < m["sliding_window"]
    return seen


def attention(p, m, i, a, prec, control=None):
    """Gated grouped-query attention of layer i over the whole sequence a
    (B, S, hidden) under `visible`. → (B, S, N_i·head_dim), gated."""
    B, S, _ = a.shape
    NH, NKV, D = (m["num_attention_heads_per_layer"][i],
                  m["num_key_value_heads"], m["head_dim"])
    q = mm(a, p["q"]["kernel"], prec).reshape(B, S, NH, D)
    k = mm(a, p["k"]["kernel"], prec).reshape(B, S, NKV, D)
    v = mm(a, p["v"]["kernel"], prec).reshape(B, S, NKV, D)
    # the gate a head, from the layer's normalised input
    g = jax.nn.sigmoid(mm(a, p["head_gate"]["kernel"], prec))   # (B, S, NH)
    seen = jnp.asarray(visible(m, i, S, control))
    kinds = ("sliding_attention", "full_attention")
    kind = m["layer_types"][i]
    if control == "swapped_rope":
        kind = kinds[1 - kinds.index(kind)]
    law = m["rope_parameters"][kind]
    scale = D ** -0.5
    pos = np.arange(S)

    def one_row(args):
        q, k, v = args
        q, k = rope_rotate(q, pos, law), rope_rotate(k, pos, law)
        k = jnp.repeat(k, NH // NKV, axis=1)      # head n reads n // group
        v = jnp.repeat(v, NH // NKV, axis=1)

        def one_head(hqkv):
            qh, kh, vh = hqkv                                  # (S, D) each
            s = jnp.matmul(_q(qh, prec), _q(kh, prec).T, precision=HI)
            w = jax.nn.softmax(jnp.where(seen, s * scale, -jnp.inf), axis=-1)
            return jnp.matmul(_q(w, prec), _q(vh, prec), precision=HI)

        o = jax.lax.map(one_head, tuple(
            t.transpose(1, 0, 2) for t in (q, k, v)))          # (NH, S, D)
        return o.transpose(1, 0, 2)                            # (S, NH, D)

    o = jax.lax.map(one_row, (q, k, v))
    if control != "no_head_gate":
        o = o * g[..., None]
    return _qa(o.reshape(B, S, NH * D), prec)


def gated_mlp(p, x, prec):
    return mm(silu(mm(x, p["gate"]["kernel"], prec))
              * mm(x, p["up"]["kernel"], prec), p["down"]["kernel"], prec)


def router(p, m, b, choice=None, margin=0.0, control=None):
    """(gates (T, k), ids (T, k), margin (T,), adopted (T,), excluded (T,))
    of the normalised tokens b (T, hidden): float32 softmax scores over
    all outputs, the top-k chosen, gates the chosen scores over their sum
    (norm_topk_prob) × moe_routed_scaling_factor; `margin` out is ln p_(k)
    − ln p_(k+1) of the reference's own ranking. With `choice` (T, k), the
    program's chosen experts, a token whose own margin is under `margin`
    takes them if all lie within `margin` of its k-th (`adopted`), and is
    `excluded` if not (the module's head)."""
    logits = jnp.matmul(b, p["kernel"].astype(jnp.float32), precision=HI)
    k = m["num_experts_per_tok"]
    top_l, top_i = jax.lax.top_k(logits, k + 1)
    gap = top_l[:, k - 1] - top_l[:, k]          # = ln p_(k) − ln p_(k+1)
    kth, top_i = top_l[:, k - 1], top_i[:, :k]
    T = b.shape[0]
    adopted = excluded = jnp.zeros((T,), bool)
    if choice is not None:
        theirs = jnp.take_along_axis(logits, choice, axis=1)
        near = gap < margin
        within = jnp.min(theirs, axis=1) >= kth - margin
        adopted, excluded = near & within, near & ~within
        top_i = jnp.where(adopted[:, None], choice, top_i)
    gates = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), top_i,
                                axis=1)
    if m["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    if control != "no_routed_scale":
        gates = gates * float(m["moe_routed_scaling_factor"])
    return gates, top_i, gap, adopted, excluded


def experts_part(p, m, b, gates, top_i, prec, held=None):
    """Σ_{e ∈ chosen(token) ∩ held} gate_e·expert_e(b), expert_e(b) =
    W_down( SiLU(W_gate·b) ⊙ W_up·b ): a loop over the held experts, each
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
        y = mm(silu(g) * u, p["down"]["kernel"][off + j], prec)
        return (acc + w[:, None] * y,
                counts.at[j].set(jnp.sum(hit).astype(jnp.int32)))

    return jax.lax.fori_loop(
        0, count, body, (jnp.zeros_like(b), jnp.zeros((count,), jnp.int32)))


def layer(p, m, h, i, prec="f32", held=None, parts=False, choice=None,
          margin=0.0, control=None):
    """Decoder layer i over h (B, S, hidden). → (h, aux). The dense layer's
    aux is empty (with `parts`: "attn", the attention's addition); an
    expert layer's is {"margin", "adopted", "excluded" (B, S), "counts"
    (count,) tokens per held expert}, with `parts` also "attn", "routed"
    (the held experts' part alone), "shared", "b" (the normalised tokens
    the router and the experts are given) and "gates", "chosen" (B, S, k).
    `choice` (B, S, k) and `margin` as `router` takes them; `held` another
    (first, count) share of the stack `p["experts"]` holds; `control` one
    of CONTROLS (the module's head)."""
    assert control is None or control in CONTROLS, control
    eps = m["rms_norm_eps"]
    B, S, H = h.shape
    a = rms_norm(h, p["attn_norm"]["scale"], eps)
    attn = mm(attention(p, m, i, a, prec, control), p["o"]["kernel"], prec)
    h = h + attn
    b = rms_norm(h, p["mlp_norm"]["scale"], eps)
    if is_dense(m, i):
        return h + jax.lax.map(lambda x: gated_mlp(p["mlp"], x, prec), b), \
            ({"attn": attn} if parts else {})
    b = b.reshape(B * S, H)
    gates, top_i, gap, adopted, excluded = router(
        p["router"], m, b,
        None if choice is None else choice.reshape(B * S, -1), margin,
        control)
    routed, counts = experts_part(p["experts"], m, b, gates, top_i, prec,
                                  held)
    shared = gated_mlp(p["shared"], b, prec)
    aux = {"margin": gap.reshape(B, S), "counts": counts,
           "adopted": adopted.reshape(B, S),
           "excluded": excluded.reshape(B, S)}
    if parts:
        aux.update(attn=attn, routed=routed.reshape(B, S, H),
                   shared=shared.reshape(B, S, H), b=b.reshape(B, S, H),
                   gates=gates.reshape(B, S, -1),
                   chosen=top_i.reshape(B, S, -1))
    return h + (routed + shared).reshape(B, S, H), aux


def head(params, m, h, side, prec="f32"):
    """Last norm and the output adapter on the target's tokens → ε̂
    (B, side, side, 3)."""
    L = h.shape[1] // 2
    hn = rms_norm(h[:, L:], params["final_norm"]["scale"], m["rms_norm_eps"])
    return unpatch(mm(hn, params["out"]["kernel"], prec), side, side,
                   m["patch_size"])


def forward(params, m, batch, cond_mask, prec="f32", control=None):
    """ε̂ (B, H, W, 3) of the whole model."""
    h = embed(params, m, batch, cond_mask, prec)
    for i in range(m["num_hidden_layers"]):
        h, _ = layer(params[f"layer_{i}"], m, h, i, prec, control=control)
    return head(params, m, h, batch["z"].shape[1], prec)


def _key(m):
    return tuple(sorted((k, repr(v)) for k, v in m.items()))


def jitted(name, m, *static):
    """embed/head/forward jitted once per (sizes, static args)."""
    fn = {"embed": embed, "head": head, "forward": forward}[name]
    return _JITS.setdefault(
        (name, _key(m)) + static,
        jax.jit(lambda *a: fn(a[0], m, *a[1:], *static)))


def jitted_layer(m, i, prec="f32", parts=False, margin=0.0, control=None):
    """(p, h[, choice]) → `layer`'s (h, aux) for layer i, jitted once per
    (sizes, layer kind, static args): the layers of one kind — the same
    head count, rotary law and mask, the same feed-forward — share a
    program."""
    kinds = [(m["layer_types"][j], m["num_attention_heads_per_layer"][j],
              m["mlp_layer_types"][j])
             for j in range(m["num_hidden_layers"])]
    kind = kinds[i]
    i = kinds.index(kind)

    def run(p, h, choice=None):
        return layer(p, m, h, i, prec, None, parts, choice, margin, control)

    return _JITS.setdefault(
        ("layer", _key(m), kind, prec, parts, margin, control), jax.jit(run))

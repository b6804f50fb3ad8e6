"""Plain reference of the token denoiser on LongCat-Flash-Omni's decoder
stack (models/token_denoiser.py, `LongcatFlashLayer`), ε̂ of the target
frame out.

**The layer** (LongCat-Flash's shortcut-connected double layer; config.json
keys `num_layers` 28 — a layer is the whole of what follows —,
`hidden_size` 6144, `ffn_hidden_size` 12288, `expert_ffn_hidden_size` 2048,
`n_routed_experts` 512, `zero_expert_num` 256, `zero_expert_type`
"identity", `moe_topk` 12, `routed_scaling_factor` 6). h in → h out, N_·
an RMSNorm (eps 1e-5) with its own weight, MLA₀ / MLA₁ and MLP₀ / MLP₁ with
their own weights:

    h₁ = h + MLA₀(N_in0(h));   b = N_post0(h₁);   m = MoE(b)
    h₂ = h₁ + MLP₀(b)
    h₃ = h₂ + MLA₁(N_in1(h₂));  h_out = h₃ + MLP₁(N_post1(h₃)) + m

so the expert branch leaves after the FIRST attention and joins after the
SECOND MLP, one attention and two MLPs later.

  MLA(a), 64 heads (`q_lora_rank` 1536, `kv_lora_rank` 512,
  `qk_nope_head_dim` 128, `qk_rope_head_dim` 64, `v_head_dim` 128,
  `mla_scale_q_lora`, `mla_scale_kv_lora`, `rope_theta` 1e7):
    c_q = N(a·W_qa)·(6144/1536)^½;  q = c_q·W_qb → 64 × [128 | 64], the
    last 64 of a head rotated (θ 1e7, no scaling, pairs (2i, 2i+1));
    [c | k_r] = a·W_kva;  c_kv = N(c)·(6144/512)^½;  k_r rotated and NOT
    scaled, shared by all heads;  [k_n | v] = c_kv·W_kvb, 128 | 128 a head;
    softmax(q·[k_n | k_r]ᵀ / √192)·v;  W_o: 64 × 128 → hidden. No bias.
  MLP(x) = W_down(SiLU(W_gate x) ⊙ W_up x), width 12288, no bias.
  MoE(b), float32 up to the gates:
    s = softmax(b·W_r) over all 512 + 256 = 768 outputs;
    chosen = top-12 of s + `e_score_correction_bias`;
    g_e = 6·s_e of the chosen, NOT renormalised;
    m = Σ_{chosen e < 512} g_e·E_e(b) + b·Σ_{chosen e ≥ 512} g_e,
    E_e a gated-SiLU MLP of width 2048. An identity expert returns the
    NORMALISED tokens b (the router's input), not h.

**Departures from the source, each the denoiser's and said in the
configuration file too.** (1) The mask: a token at p sees a key at p′ iff
frame(p′) ≤ frame(p) — this repo's frame rule in place of the language
model's p′ ≤ p — written below as one dense (S, S) predicate; the rotary
position is the token's index in [conditioning frame, target frame]. (2)
The adapters around the trunk (patches, rays, the logsnr embedding, the
output Dense) are this repo's, the same as the other token configurations'.
(3) Of the 512 real experts only `held_experts` are computed — this chip's
share of a 32-chip expert-parallel layer; the router keeps its 768 outputs
and top-12, and what the absent experts would add is left out. The
identity part is token-local: every chip of the deployment computes it for
its own tokens, this chip is home to all of them, so it is computed whole,
once. (4) What config.json is silent on is the configuration file's
`assumed`: where the latent scales enter (on the normalised latents; the
public implementation multiplies q after W_qb, the same function of a
linear map without a bias), the rotary pairing, no renormalisation of the
gates, the router in float32, no biases.

Straightforward jax.numpy in float32 with matmul precision "highest". BOTH
frames go through ONE full forward: no cache, no once-a-call pass, no
kernels, no sorting, no grouped product and no batching of choices — the
expert layer is a loop over the held experts, each applied to every token
under a dense mask of who chose it, attention a loop over rows and heads
(one head's (S, S) scores at a time). So the program's prefill into two
latents a layer, then decode from them, is held to one pass. It imports
nothing of the program; weights come from the benchmark's own seeded
builder (scmoe_weights.py); parameter NAMES follow the program's tree
because the same seeded tree is handed to both sides.

`m` (sizes, the source's key names): hidden_size, num_layers,
num_attention_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
qk_rope_head_dim, v_head_dim, mla_scale_q_lora, mla_scale_kv_lora,
ffn_hidden_size, expert_ffn_hidden_size, n_routed_experts (the REAL
experts), zero_expert_num, moe_topk, routed_scaling_factor, rope_theta,
rms_norm_eps, held_experts [first, count], patch_size.

`prec` selects the arithmetic of every matmul and attention product, as in
ms4_ref.py: "f32" (the reference proper), "bf16", "fp8" (inputs rounded
to float8_e4m3fn, scaled per tensor), "fp8_act". The lower ones are the
controls. Norms, softmax, the router's scores and the rotary tables stay
float32 in every mode.

`control` plants one of three faults of THIS mechanism, for the controls
the limits are read against (never the reference proper): "no_identity"
(the identity experts' part left out of m), "early_join" (m added at h₂,
one sublayer early, and not at h_out), "no_latent_scale" (both latents
left at their norms' output).

**A near tie in the router.** As kl48_ref.py: `layer(..., choice=,
margin=)` takes the PROGRAM's chosen outputs where the reference's own
margin — the 12th less the 13th of score + bias — is under `margin` and
every output the program chose lies, by the reference's own numbers,
within `margin` of the reference's 12th; a choice outside that is not
adopted and the token is reported (`excluded`).
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


# -- the adapters (this repo's, as ms4_ref.py's) ------------------------------
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


# -- the layers ---------------------------------------------------------------
CONTROLS = ("no_identity", "early_join", "no_latent_scale")


def rope_rotate(x, positions, theta, heads=False):
    """x (..., S, d) — with `heads` (..., S, heads, d) — with the pairs
    (2i, 2i+1) of its last axis turned by positions·θ^(−2i/d)."""
    d = x.shape[-1]
    freq = float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.asarray(positions, np.float64)[:, None] * freq[None]
    cos, sin = (jnp.asarray(f(ang), jnp.float32) for f in (np.cos, np.sin))
    if heads:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def visible(S):
    """The dense (S, S) predicate [query p, key p′] over a sequence of two
    frames: frame(p′) ≤ frame(p)."""
    frame = np.arange(S) // (S // 2)
    return frame[:, None] >= frame[None, :]


def mla(p, m, a, prec, scaled=True):
    """Latent attention over the whole sequence a (B, S, hidden),
    normalised, under `visible`; a row at a time, a head at a time. → (B,
    S, hidden)."""
    _, S, H = a.shape
    NH, dn, dr, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                      m["qk_rope_head_dim"], m["v_head_dim"])
    rank, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    q_scale = (H / m["q_lora_rank"]) ** 0.5 \
        if scaled and m["mla_scale_q_lora"] else 1.0
    kv_scale = (H / rank) ** 0.5 if scaled and m["mla_scale_kv_lora"] \
        else 1.0
    pos = np.arange(S)
    seen = jnp.asarray(visible(S))
    scale = (dn + dr) ** -0.5

    def one_head(hqkv):
        qh, kh, vh = hqkv
        s = jnp.matmul(_q(qh, prec), _q(kh, prec).T, precision=HI)
        w = jax.nn.softmax(jnp.where(seen, s * scale, -jnp.inf), axis=-1)
        return jnp.matmul(_q(w, prec), _q(vh, prec), precision=HI)

    def one_row(x):                                       # (S, hidden)
        c_q = rms_norm(mm(x, p["q_a"]["kernel"], prec),
                       p["q_norm"]["scale"], eps) * q_scale
        q = mm(c_q, p["q_b"]["kernel"], prec).reshape(S, NH, dn + dr)
        q = jnp.concatenate([q[..., :dn], rope_rotate(
            q[..., dn:], pos, m["rope_theta"], heads=True)], axis=-1)
        kv_a = mm(x, p["kv_a"]["kernel"], prec)
        c_kv = rms_norm(kv_a[..., :rank], p["kv_norm"]["scale"], eps) \
            * kv_scale
        k_r = rope_rotate(kv_a[..., rank:], pos, m["rope_theta"])
        kv = mm(c_kv, p["kv_b"]["kernel"], prec).reshape(S, NH, dn + dv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_r[:, None, :], (S, NH, dr))], axis=-1)
        o = jax.lax.map(one_head, tuple(
            t.transpose(1, 0, 2) for t in (q, k, kv[..., dn:])))
        o = _qa(o.transpose(1, 0, 2).reshape(S, NH * dv), prec)
        return mm(o, p["o"]["kernel"], prec)

    return jax.lax.map(one_row, a)


def gated_mlp(p, x, prec):
    return mm(silu(mm(x, p["gate"]["kernel"], prec))
              * mm(x, p["up"]["kernel"], prec), p["down"]["kernel"], prec)


def router(p, m, b, choice=None, margin=0.0):
    """(gates (T, k), ids (T, k), margin (T,), adopted (T,), excluded (T,))
    of the normalised tokens b (T, hidden): softmax scores over ALL the
    router's outputs (real experts, then identities), the top-k of score +
    bias chosen, gates the chosen scores (no bias, not renormalised) times
    routed_scaling_factor; `margin` out is the k-th less the (k+1)-th of
    score + bias. With `choice` (T, k), the program's chosen outputs, a
    token whose own margin is under `margin` takes them if all lie within
    `margin` of its k-th (`adopted`), and is `excluded` if not (the
    module's head)."""
    scores = jax.nn.softmax(jnp.matmul(
        b, p["kernel"].astype(jnp.float32), precision=HI), axis=-1)
    ranked = scores + p["bias"].astype(jnp.float32)
    k = m["moe_topk"]
    top_r, top_i = jax.lax.top_k(ranked, k + 1)
    gap = top_r[:, k - 1] - top_r[:, k]
    kth, top_i = top_r[:, k - 1], top_i[:, :k]
    T = b.shape[0]
    adopted = excluded = jnp.zeros((T,), bool)
    if choice is not None:
        theirs = jnp.take_along_axis(ranked, choice, axis=1)
        near = gap < margin
        within = jnp.min(theirs, axis=1) >= kth - margin
        adopted, excluded = near & within, near & ~within
        top_i = jnp.where(adopted[:, None], choice, top_i)
    gates = jnp.take_along_axis(scores, top_i, axis=1)
    return gates * float(m["routed_scaling_factor"]), top_i, gap, adopted, \
        excluded


def experts_part(p, m, b, gates, top_i, prec, held=None):
    """Σ_{e ∈ chosen(token) ∩ held} gate_e·expert_e(b), expert_e(b) =
    W_down( SiLU(W_gate·b) ⊙ W_up·b ): a loop over the held experts, each
    applied to every token under a dense mask. A chosen id past the real
    experts (an identity) matches no held expert. → (part, tokens per held
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


def identity_part(m, b, gates, top_i):
    """b·Σ of the gates of a token's chosen identity experts (ids from
    n_routed_experts on)."""
    return b * jnp.sum(jnp.where(top_i >= m["n_routed_experts"], gates, 0.0),
                       axis=-1, keepdims=True)


def layer(p, m, h, prec="f32", held=None, parts=False, choice=None,
          margin=0.0, control=None):
    """One double layer over h (B, S, hidden). → (h, aux); aux is
    {"margin", "adopted", "excluded" (B, S), "counts" (count,) tokens per
    held expert}, with `parts` also "routed", "zero" (the two parts of the
    branch's result m: the held experts' and the identities'), "b" (the
    normalised tokens the router, the experts and
    the first MLP are given) and "gates", "chosen" (B, S, k). `choice` (B,
    S, k) and `margin` as `router` takes them; `held` another (first,
    count) share of the stack `p["experts"]` holds; `control` one of
    CONTROLS (the module's head)."""
    assert control is None or control in CONTROLS, control
    eps = m["rms_norm_eps"]
    B, S, H = h.shape
    scaled = control != "no_latent_scale"

    def mlp(q, x):
        return jax.lax.map(lambda r: gated_mlp(q, r, prec), x)

    h1 = h + mla(p["mla_0"], m, rms_norm(h, p["mla_0"]["norm"]["scale"],
                                         eps), prec, scaled)
    b = rms_norm(h1, p["mlp_norm_0"]["scale"], eps)
    flat = b.reshape(B * S, H)
    gates, top_i, gap, adopted, excluded = router(
        p["router"], m, flat,
        None if choice is None else choice.reshape(B * S, -1), margin)
    routed, counts = experts_part(p["experts"], m, flat, gates, top_i, prec,
                                  held)
    zero = identity_part(m, flat, gates, top_i)
    moe = (routed if control == "no_identity" else routed + zero).reshape(
        B, S, H)
    h2 = h1 + mlp(p["mlp_0"], b)
    if control == "early_join":
        h2 = h2 + moe
    h3 = h2 + mla(p["mla_1"], m, rms_norm(h2, p["mla_1"]["norm"]["scale"],
                                          eps), prec, scaled)
    out = h3 + mlp(p["mlp_1"], rms_norm(h3, p["mlp_norm_1"]["scale"], eps))
    if control != "early_join":
        out = out + moe
    aux = {"margin": gap.reshape(B, S), "counts": counts,
           "adopted": adopted.reshape(B, S),
           "excluded": excluded.reshape(B, S)}
    if parts:
        aux.update(routed=routed.reshape(B, S, H),
                   zero=zero.reshape(B, S, H), b=b,
                   gates=gates.reshape(B, S, -1),
                   chosen=top_i.reshape(B, S, -1))
    return out, aux


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
    for i in range(m["num_layers"]):
        h, _ = layer(params[f"layer_{i}"], m, h, prec, control=control)
    return head(params, m, h, batch["z"].shape[1], prec)


def _key(m):
    return tuple(sorted((k, repr(v)) for k, v in m.items()))


def jitted(name, m, *static):
    """embed/head/forward jitted once per (sizes, static args)."""
    fn = {"embed": embed, "head": head, "forward": forward}[name]
    return _JITS.setdefault(
        (name, _key(m)) + static,
        jax.jit(lambda *a: fn(a[0], m, *a[1:], *static)))


def jitted_layer(m, prec="f32", parts=False, margin=0.0, control=None):
    """(p, h[, choice]) → `layer`'s (h, aux), jitted once per (sizes,
    static args): every layer of this trunk is the same program."""

    def run(p, h, choice=None):
        return layer(p, m, h, prec, None, parts, choice, margin, control)

    return _JITS.setdefault(("layer", _key(m), prec, parts, margin, control),
                            jax.jit(run))

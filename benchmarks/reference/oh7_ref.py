"""Plain reference of the token denoiser on Olmo-Hybrid-7B's decoder stack
(models/token_denoiser.py, `OlmoHybridLayer`), ε̂ of the target frame out.

Every layer, 0-based i: h ← h + RMSNorm(Mix_i(h)), h ← h + RMSNorm(MLP(h))
— NO norm on a sublayer's input, its OUTPUT normalised inside the residual
(weight, eps 1e-6); MLP(x) = W_down(SiLU(W_gate x) ⊙ W_up x), width 11008;
no bias anywhere. `layer_types[i]` says which mixer. For the tokens a (S,
hidden) of a sequence, a = h as it is:

  "linear_attention" — Gated DeltaNet, 30 heads, keys of 96 on values of
  192, 4 taps:
    q̃ = a·W_q, k̃ = a·W_k ∈ R^{30×96}; ṽ = a·W_v ∈ R^{30×192}; each through
    its own causal depthwise convolution (zeros before the sequence's
    first token, no bias), then SiLU;
    q = q̃/sqrt(Σ_head q̃² + 1e-6)·96^(−1/2), k = k̃/sqrt(Σ_head k̃² + 1e-6)
    g_t = −exp(A_log_h)·softplus(a·W_a + dt_bias_h)     a HEAD, float32
    β_t = 2·sigmoid(a·W_b)                              a head, in (0, 2)
    S_t = e^{g_t}·S_{t−1} + β_t k_t (v_t − e^{g_t}·S_{t−1}ᵀ k_t)ᵀ
    o_t = S_tᵀ q_t            TOKEN BY TOKEN, S_0 = 0 ∈ R^{96×192}, float32
    y_t = RMSNorm_192(o_t; weight, eps 1e-6) ⊙ SiLU(a·W_g)      a head
    Mix = y·W_o

  "full_attention" — 30 query heads on 30 key/value heads of 128:
    q = RMSNorm_3840(a·W_q), k = RMSNorm_3840(a·W_k) — over the WHOLE
    projection, before the head split (weight, eps 1e-6) —, v = a·W_v
    Mix = softmax_M(q kᵀ/√128) v · W_o        a head; float32; M the mask;
    NO positional term (`rope_parameters.rope_theta` is null in the source:
    there is no base to rotate by).

**Departures from the source, each the denoiser's and said in the
configuration file too.** (1) The full layers' mask M: a token at p sees a
key at p′ iff frame(p′) ≤ frame(p) — this repo's frame rule in place of
the language model's p′ ≤ p; written below as one dense (S, S) predicate.
The delta-rule layers keep the source's recurrence in sequence order
unchanged: the conditioning frame's tokens come first, so they never
depend on z_t. (2) The adapters around the trunk (patches, rays, the
logsnr embedding, the last RMSNorm and the output Dense) are this repo's,
the same as the other token configurations'. (3) What config.json does not
say is the configuration file's `assumed`: where the norms sit, NoPE, the
L2 norm's eps and scale, the decay's parametrisation, the state's
precision.

Straightforward jax.numpy in float32 with matmul precision "highest". BOTH
frames go through ONE full forward: no cache, no once-a-call pass, no
chunks, no kernels — the delta rule is a `lax.scan` over the 2L tokens,
attention dense, a row and a head at a time (one (S, S) map), the MLP a
row at a time. It imports nothing of the program; weights come from the
benchmark's own seeded builder (gdn_weights.py); parameter NAMES follow
the program's tree because the same seeded tree is handed to both sides.

`m` (sizes, the source's key names): hidden_size, num_hidden_layers,
num_attention_heads, num_key_value_heads, intermediate_size, rms_norm_eps,
layer_types, linear_num_key_heads, linear_num_value_heads,
linear_key_head_dim, linear_value_head_dim, linear_conv_kernel_dim,
linear_allow_neg_eigval, patch_size.

`prec` selects the arithmetic of every matmul and attention product, as in
ms4_ref.py: "f32" (the reference proper), "bf16", "fp8" (inputs rounded to
float8_e4m3fn, scaled per tensor). The lower ones are the controls. Norms,
softmax, g, β and the recurrence itself stay float32 in every mode. Three
more controls are of THIS mechanism (`control`): "zeroed_state" (every
delta-rule layer's state set to zero before the target frame's first
token: a target frame entered without what the conditioning frame wrote),
"beta_unscaled" (β = sigmoid(·), without the factor 2 that
`linear_allow_neg_eigval` gives it) and "no_decay" (g = 0: nothing is ever
forgotten).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LOGSNR_CLEAN = 20.0
_JITS = {}

_Q = {"f32": None, "bf16": (jnp.bfloat16, None),
      "fp8": (jnp.float8_e4m3fn, 448.0)}


def _q(x, prec):
    """Round x to the control's input type (identity for the reference);
    fp8 is scaled per tensor to the type's range."""
    if _Q[prec] is None:
        return x
    dtype, top = _Q[prec]
    s = 1.0 if top is None else jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


def mm(x, w, prec):
    return jnp.matmul(_q(x.astype(jnp.float32), prec),
                      _q(w.astype(jnp.float32), prec), precision=HI)


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
CONTROLS = ("zeroed_state", "beta_unscaled", "no_decay")
L2_EPS = 1e-6


def is_full_attention(m, i):
    return m["layer_types"][i] == "full_attention"


def causal_conv(x, w):
    """x (S, C), w (K, C): y_t = Σ_j w_j ⊙ x_{t−(K−1)+j}, zeros before the
    sequence's first token."""
    K, S = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(xp[j:j + S] * w[j].astype(jnp.float32) for j in range(K))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule token by token over the sequences of a batch.
    q, k (B, S, H, d_k), v (B, S, H, d_v), g, β (B, S, H) → o (B, S, H,
    d_v), every row from a zero state (B, H, d_k, d_v)."""
    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None, None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                               precision=HI))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=HI)

    B, _, H, dk = q.shape
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))[1], 0, 1)


DECAY_QUANTILES = (0.05, 0.25, 0.5, 0.75, 0.95)


def gated_delta_net(p, m, a, prec, control=None, parts=False):
    """Gated DeltaNet over the whole sequence a (B, S, hidden): the
    projections a row at a time, the recurrence over the sequence once,
    the rows side by side. → (Mix (B, S, hidden), aux)."""
    NH, dk, dv = (m["linear_num_value_heads"], m["linear_key_head_dim"],
                  m["linear_value_head_dim"])
    f32 = jnp.float32
    eps = m["rms_norm_eps"]

    def conv(x, name):
        return silu(causal_conv(mm(x, p[name]["kernel"], prec),
                                p[name + "_conv"]["kernel"]))

    def l2(x):
        x = x.reshape(x.shape[0], NH, dk)
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + L2_EPS)

    def into(x):
        g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
            mm(x, p["a"]["kernel"], prec) + p["dt_bias"].astype(f32))
        beta = jax.nn.sigmoid(mm(x, p["b"]["kernel"], prec))
        if m["linear_allow_neg_eigval"] and control != "beta_unscaled":
            beta = 2.0 * beta
        return (l2(conv(x, "q")) * dk ** -0.5, l2(conv(x, "k")),
                conv(x, "v").reshape(-1, NH, dv),
                jnp.zeros_like(g) if control == "no_decay" else g, beta,
                silu(mm(x, p["g"]["kernel"], prec)))

    q, k, v, g, beta, gate = jax.lax.map(into, a)
    if control == "zeroed_state":
        cut = a.shape[1] // 2          # the target frame's first token
        o = jnp.concatenate(
            [delta_rule(*(x[:, :cut] for x in (q, k, v, g, beta))),
             delta_rule(*(x[:, cut:] for x in (q, k, v, g, beta)))], axis=1)
    else:
        o = delta_rule(q, k, v, g, beta)
    aux = {}
    if parts:
        # a head's decay rate a token is −g: ln 2 over it is its half-life
        # in tokens at that token's step
        aux = {"decay_rate_quantiles": jnp.quantile(
            -g.reshape(-1), jnp.asarray(DECAY_QUANTILES))}
    o = rms_norm(o, p["o_norm"]["scale"], eps) * gate.reshape(o.shape)
    return jax.lax.map(lambda x: mm(x.reshape(x.shape[0], NH * dv),
                                    p["o"]["kernel"], prec), o), aux


def visible(S):
    """The dense (S, S) predicate [query p, key p′] over a sequence of two
    frames: frame(p′) ≤ frame(p)."""
    frame = np.arange(S) // (S // 2)
    return frame[:, None] >= frame[None, :]


def attention(p, m, a, prec):
    """Full attention under the QK norm over the whole sequence a (B, S,
    hidden), every key/value head its own query head's group. → (B, S,
    hidden)."""
    B, S, _ = a.shape
    NH, NKV = m["num_attention_heads"], m["num_key_value_heads"]
    D = m["hidden_size"] // NH
    eps = m["rms_norm_eps"]
    seen = jnp.asarray(visible(S))

    def one_row(x):
        q = rms_norm(mm(x, p["q"]["kernel"], prec), p["q_norm"]["scale"],
                     eps).reshape(S, NH, D)
        k = rms_norm(mm(x, p["k"]["kernel"], prec), p["k_norm"]["scale"],
                     eps).reshape(S, NKV, D)
        v = mm(x, p["v"]["kernel"], prec).reshape(S, NKV, D)
        k, v = (jnp.repeat(t, NH // NKV, axis=1) for t in (k, v))

        def one_head(t):
            qh, kh, vh = t
            s = jnp.matmul(_q(qh, prec), _q(kh, prec).T, precision=HI) \
                * D ** -0.5
            w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.matmul(_q(w, prec), _q(vh, prec), precision=HI)

        o = jax.lax.map(one_head, tuple(jnp.moveaxis(t, 1, 0)
                                        for t in (q, k, v)))
        return mm(jnp.moveaxis(o, 0, 1).reshape(S, NH * D),
                  p["o"]["kernel"], prec)

    return jax.lax.map(one_row, a)


def gated_mlp(p, x, prec):
    return mm(silu(mm(x, p["gate"]["kernel"], prec))
              * mm(x, p["up"]["kernel"], prec), p["down"]["kernel"], prec)


def layer(p, m, h, i, prec="f32", parts=False, control=None):
    """Decoder layer i over h (B, S, hidden) → (h, aux). With `parts` a
    delta-rule layer's aux holds "decay_rate_quantiles" (DECAY_QUANTILES
    of −g over tokens and heads)."""
    eps = m["rms_norm_eps"]
    aux = {}
    if is_full_attention(m, i):
        y = attention(p["attn"], m, h, prec)
    else:
        y, aux = gated_delta_net(p["gdn"], m, h, prec, control, parts)
    h = h + rms_norm(y, p["mix_norm"]["scale"], eps)
    y = jax.lax.map(lambda x: gated_mlp(p["mlp"], x, prec), h)
    return h + rms_norm(y, p["mlp_norm"]["scale"], eps), aux


def head(params, m, h, side, prec="f32"):
    """Last norm (the frame's own) and the output adapter on the target's
    tokens → ε̂ (B, side, side, 3)."""
    L = h.shape[1] // 2
    hn = rms_norm(h[:, L:], params["final_norm"]["scale"], m["rms_norm_eps"])
    return unpatch(mm(hn, params["out"]["kernel"], prec), side, side,
                   m["patch_size"])


def forward(params, m, batch, cond_mask, prec="f32", control=None,
            layers=None):
    """ε̂ (B, H, W, 3) of the whole model; with `layers`, the hidden state
    (B, 2L, hidden) after that many layers instead."""
    h = embed(params, m, batch, cond_mask, prec)
    for i in range(m["num_hidden_layers"] if layers is None else layers):
        h, _ = layer(params[f"layer_{i}"], m, h, i, prec, control=control)
    if layers is not None:
        return h
    return head(params, m, h, batch["z"].shape[1], prec)


def _key(m):
    return tuple(sorted((k, repr(v)) for k, v in m.items()))


def jitted(name, m, *static):
    """embed/head jitted once per (sizes, static args)."""
    fn = {"embed": embed, "head": head}[name]
    return _JITS.setdefault(
        (name, _key(m)) + static,
        jax.jit(lambda *a: fn(a[0], m, *a[1:], *static)))


def jitted_layer(m, i, prec="f32", parts=False, control=None):
    """(p, h) → `layer`'s (h, aux) for layer i, jitted once per (sizes,
    kind of layer, static args): the layers of one kind share a program."""
    same = [is_full_attention(m, j)
            for j in range(m["num_hidden_layers"])].index(
                is_full_attention(m, i))
    return _JITS.setdefault(
        ("layer", _key(m), same, prec, parts, control),
        jax.jit(lambda p, h: layer(p, m, h, same, prec, parts, control)))

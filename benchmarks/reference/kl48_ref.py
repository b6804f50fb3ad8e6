"""Plain reference of the token denoiser on Kimi-Linear-48B-A3B-Instruct's
decoder stack (models/token_denoiser.py, `KimiLinearLayer`), ε̂ of the
target frame out.

Pre-norm residual layers, x ← x + Mix_l(RMSNorm(x)), x ← x + FFN_l(RMSNorm
(x)), eps 1e-5, no biases. With the source's 1-based index l: Mix_l is
latent attention where l ∈ linear_attn_config.full_attn_layers, else KDA;
FFN_l is the dense gated-SiLU MLP for l ≤ first_k_dense_replace, else the
expert layer. For the tokens a (S, hidden) of a sequence, normalised:

  KDA (H = 32 heads of d = 128):
    q̃, k̃, ṽ = a·W_q, a·W_k, a·W_v; each through its own causal depthwise
              convolution over the sequence (4 taps, the last on the token
              itself, zeros before the first token), then SiLU
    q_t = L2norm(q′_t)·d^(−1/2),  k_t = L2norm(k′_t),  v_t = v′_t
    g_t = −exp(A_log_h)·softplus((a·W_f↓)·W_f↑ + dt_bias)   per head, channel
    β_t = sigmoid(a·W_β)                                     per head
    S′  = Diag(exp g_t)·S_{t−1};  u_t = β_t(v_t − S′ᵀk_t);  S_t = S′ + k_t u_tᵀ
    o_t = S_tᵀ q_t                      TOKEN BY TOKEN, S_0 = 0, float32
    y_t = [RMSNorm_head(o_t) ⊙ sigmoid((a·W_g↓)·W_g↑)]·W_o

  latent attention (`mla_use_nope`: no rotary anywhere, `q_lora_rank` null):
    q = a·W_q → 32 × (128 + 64);  a·W_kva → c_kv (512, RMSNorm) and a
    64-wide key part shared by all heads, used as it is;  c_kv·W_kvb → per
    head 128 key + 128 value;  key = [128 ‖ the shared 64];  scores scaled
    192^(−1/2);  out W_o: 32 × 128 → hidden

  experts, on b = RMSNorm(x′):
    s = sigmoid(b·W_r) in float32;  chosen = top-8 of s + bias
    (`e_score_correction_bias`; one group of which one is taken);
    gate = s of the chosen (WITHOUT the bias) ÷ their sum × 2.446;
    y = Σ gate_e·W_down,e(SiLU(W_gate,e b) ⊙ W_up,e b) + one shared expert

**Departures from the source, each the denoiser's and said in the
configuration file too.** (1) The latent-attention layers' mask: a token
at p sees a key at p′ iff frame(p′) ≤ frame(p) — this repo's frame rule in
place of the language model's p′ ≤ p — written below as one dense (S, S)
predicate; with no positional term such a layer is order-free inside a
frame. The KDA layers keep the source's recurrence in sequence order
unchanged (a recurrence has no frame rule to swap in): the conditioning
frame's tokens come first, so they never depend on z_t. (2) The adapters
around the trunk (patches, rays, the logsnr embedding, the output Dense)
are this repo's, the same as the other token configurations'. (3) Of each
expert layer only `held_experts` are computed — this chip's share; the
router keeps all its outputs, and the absent experts add nothing. (4)
Sizes the config.json does not give are the configuration file's
`assumed`: the L2 norm's eps 1e-6 and the scale on q, the decay's
parametrisation and its rank-128 pair, the gate's rank 128.

Straightforward jax.numpy in float32 with matmul precision "highest". BOTH
frames go through ONE full forward: no cache, no once-a-call pass, no
chunks, no kernels, no sorting and no grouped product — KDA is a
`lax.scan` over the 2L tokens, the expert layer a loop over the held
experts with a dense mask, attention a loop over rows and heads (one
head's (S, S) scores at a time). It imports nothing of the program;
weights come from the benchmark's own seeded builder (kda_weights.py);
parameter NAMES follow the program's tree because the same seeded tree is
handed to both sides.

`m` (sizes, the source's key names): hidden_size, num_hidden_layers,
num_attention_heads, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
v_head_dim, linear_attn_config {full_attn_layers, kda_layers, head_dim,
num_heads, short_conv_kernel_size}, first_k_dense_replace,
intermediate_size, num_experts, num_experts_per_token, num_shared_experts,
moe_intermediate_size, moe_renormalize, routed_scaling_factor,
rms_norm_eps, held_experts [first, count], patch_size.

`prec` selects the arithmetic of every matmul and attention product, as in
ms4_ref.py: "f32" (the reference proper), "bf16", "fp8" (inputs rounded
to float8_e4m3fn, scaled per tensor), "fp8_act". The lower ones are the
controls. Norms, softmax, the router's scores, the decay and the KDA
recurrence itself stay float32 in every mode.

**A near tie in the router.** As st21_ref.py: `layer(..., choice=,
margin=)` takes the PROGRAM's chosen experts where the reference's own
margin — the 8th less the 9th of score + bias — is under `margin` and
every expert the program chose lies, by the reference's own numbers,
within `margin` of the reference's 8th; a choice outside that is not
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
L2_EPS = 1e-6


def l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def is_full_attention(m, i):
    """Layer i, 0-based; the source's lists count from 1."""
    return i + 1 in m["linear_attn_config"]["full_attn_layers"]


def is_dense(m, i):
    return i < m["first_k_dense_replace"]


def causal_conv(x, w):
    """x (B, S, C), w (K, C): y_t = Σ_j w_j ⊙ x_{t−(K−1)+j}, zeros before
    the sequence's first token."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + S] * w[j].astype(jnp.float32) for j in range(K))


def delta_rule(q, k, v, g, beta, S0=None):
    """The gated delta rule token by token. q, k, v, g (B, S, H, d), β (B,
    S, H) → (o (B, S, H, d), the last state (B, H, d, d))."""
    B, _, H, d = q.shape

    def step(state, x):
        q, k, v, g, b = x
        state = jnp.exp(g)[..., None] * state
        u = b[..., None] * (v - jnp.sum(state * k[..., None], axis=-2))
        state = state + k[..., None] * u[..., None, :]
        return state, jnp.sum(state * q[..., None], axis=-2)

    if S0 is None:
        S0 = jnp.zeros((B, H, d, v.shape[-1]), jnp.float32)
    last, o = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


def log_decay(p, m, a, prec):
    """g (B, S, heads, d) ≤ 0: a channel's log-decay at each token."""
    lin = m["linear_attn_config"]
    x = jax.nn.softplus(mm(mm(a, p["f_a"]["kernel"], prec),
                           p["f_b"]["kernel"], prec)
                        + p["dt_bias"].astype(jnp.float32))
    return -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * x.reshape(
        x.shape[:2] + (lin["num_heads"], lin["head_dim"]))


DECAY_QUANTILES = (0.05, 0.25, 0.5, 0.75, 0.95)


def kda(p, m, a, prec, zero_state_at=None):
    """KDA over the whole sequence a (B, S, hidden), normalised. →
    (B, S, hidden). `zero_state_at` (a control only): the state is set to
    zero before that token — a target frame entered without what the
    conditioning frame wrote."""
    lin = m["linear_attn_config"]
    NH, D = lin["num_heads"], lin["head_dim"]
    B, S, _ = a.shape

    def heads(x):
        return x.reshape(B, S, NH, D)

    q, k, v = (heads(silu(causal_conv(mm(a, p[n]["kernel"], prec),
                                      p[n + "_conv"]["kernel"])))
               for n in ("q", "k", "v"))
    q, k = l2_normalise(q) * D ** -0.5, l2_normalise(k)
    g = log_decay(p, m, a, prec)
    beta = jax.nn.sigmoid(mm(a, p["beta"]["kernel"], prec))
    if zero_state_at is None:
        o, _ = delta_rule(q, k, v, g, beta)
    else:
        z = zero_state_at
        o = jnp.concatenate(
            [delta_rule(*(x[:, :z] for x in (q, k, v, g, beta)))[0],
             delta_rule(*(x[:, z:] for x in (q, k, v, g, beta)))[0]], axis=1)
    gate = jax.nn.sigmoid(mm(mm(a, p["g_a"]["kernel"], prec),
                             p["g_b"]["kernel"], prec))
    o = rms_norm(o, p["o_norm"]["scale"], m["rms_norm_eps"]) * heads(gate)
    return mm(o.reshape(B, S, NH * D), p["o"]["kernel"], prec)


def visible(S):
    """The dense (S, S) predicate [query p, key p′] over a sequence of two
    frames: frame(p′) ≤ frame(p)."""
    frame = np.arange(S) // (S // 2)
    return frame[:, None] >= frame[None, :]


def mla(p, m, a, prec):
    """Latent attention without a positional term over the whole sequence
    a (B, S, hidden) under `visible`. → (B, S, hidden)."""
    B, S, _ = a.shape
    NH, dn, dr, dv = (m["num_attention_heads"], m["qk_nope_head_dim"],
                      m["qk_rope_head_dim"], m["v_head_dim"])
    rank = m["kv_lora_rank"]
    q = mm(a, p["q"]["kernel"], prec).reshape(B, S, NH, dn + dr)
    kv_a = mm(a, p["kv_a"]["kernel"], prec)
    c_kv = rms_norm(kv_a[..., :rank], p["kv_norm"]["scale"],
                    m["rms_norm_eps"])
    k_pe = kv_a[..., rank:]
    kv = mm(c_kv, p["kv_b"]["kernel"], prec).reshape(B, S, NH, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_pe[:, :, None, :], (B, S, NH, dr))], axis=-1)
    v = kv[..., dn:]
    seen = jnp.asarray(visible(S))
    scale = (dn + dr) ** -0.5

    def one_row(args):
        def one_head(hqkv):
            qh, kh, vh = hqkv
            s = jnp.matmul(_q(qh, prec), _q(kh, prec).T, precision=HI)
            w = jax.nn.softmax(jnp.where(seen, s * scale, -jnp.inf), axis=-1)
            return jnp.matmul(_q(w, prec), _q(vh, prec), precision=HI)

        o = jax.lax.map(one_head, tuple(t.transpose(1, 0, 2) for t in args))
        return _qa(o.transpose(1, 0, 2).reshape(S, NH * dv), prec)

    return mm(jax.lax.map(one_row, (q, k, v)), p["o"]["kernel"], prec)


def gated_mlp(p, x, prec):
    return mm(silu(mm(x, p["gate"]["kernel"], prec))
              * mm(x, p["up"]["kernel"], prec), p["down"]["kernel"], prec)


def router(p, m, b, choice=None, margin=0.0):
    """(gates (T, k), ids (T, k), margin (T,), adopted (T,), excluded (T,))
    of the normalised tokens b (T, hidden): sigmoid scores, the top-k of
    score + bias chosen, gates the chosen scores (no bias) over their sum
    times routed_scaling_factor; `margin` out is the k-th less the
    (k+1)-th of score + bias. With `choice` (T, k), the program's chosen
    experts, a token whose own margin is under `margin` takes them if all
    lie within `margin` of its k-th (`adopted`), and is `excluded` if not
    (the module's head)."""
    scores = jax.nn.sigmoid(jnp.matmul(
        b, p["kernel"].astype(jnp.float32), precision=HI))
    ranked = scores + p["bias"].astype(jnp.float32)
    k = m["num_experts_per_token"]
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
    if m["moe_renormalize"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * float(m["routed_scaling_factor"]), top_i, gap, adopted, \
        excluded


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
          margin=0.0, zero_state_at=None):
    """Decoder layer i over h (B, S, hidden). → (h, aux). With `parts` a
    KDA layer's aux holds "decay_rate_quantiles" (DECAY_QUANTILES of −g);
    a dense layer's holds nothing else; an expert layer's is {"margin",
    "adopted", "excluded" (B, S), "counts" (count,) tokens per held
    expert}, with `parts` also "routed" (the held experts' part alone),
    "shared", "b" (the normalised tokens the router and the experts are
    given) and "gates", "chosen" (B, S, k). `choice` (B, S, k) and
    `margin` as `router` takes them; `zero_state_at` as `kda` takes it."""
    eps = m["rms_norm_eps"]
    B, S, H = h.shape
    a = rms_norm(h, p["attn_norm"]["scale"], eps)
    if is_full_attention(m, i):
        h = h + mla(p["mla"], m, a, prec)
    else:
        h = h + kda(p["kda"], m, a, prec, zero_state_at)
    b = rms_norm(h, p["mlp_norm"]["scale"], eps)
    rates = {}
    if parts and not is_full_attention(m, i):
        # −g over every channel of (a sample of) the tokens: ln 2 over a
        # rate is that channel's half-life in tokens at that token's rate
        r = -log_decay(p["kda"], m, a, prec).reshape(-1)
        rates = {"decay_rate_quantiles": jnp.quantile(
            r[::max(1, r.size // 2 ** 22)], jnp.asarray(DECAY_QUANTILES))}
    if is_dense(m, i):
        return h + jax.lax.map(
            lambda x: gated_mlp(p["mlp"], x, prec), b), rates
    b = b.reshape(B * S, H)
    gates, top_i, gap, adopted, excluded = router(
        p["router"], m, b,
        None if choice is None else choice.reshape(B * S, -1), margin)
    routed, counts = experts_part(p["experts"], m, b, gates, top_i, prec,
                                  held)
    shared = gated_mlp(p["shared"], b, prec)
    aux = {"margin": gap.reshape(B, S), "counts": counts,
           "adopted": adopted.reshape(B, S),
           "excluded": excluded.reshape(B, S), **rates}
    if parts:
        aux.update(routed=routed.reshape(B, S, H),
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


def forward(params, m, batch, cond_mask, prec="f32", aux=False,
            zero_state_at=None):
    """ε̂ (B, H, W, 3) of the whole model; with `aux` also the per-layer
    aux dicts (empty for a dense layer)."""
    h = embed(params, m, batch, cond_mask, prec)
    auxes = []
    for i in range(m["num_hidden_layers"]):
        h, a = layer(params[f"layer_{i}"], m, h, i, prec,
                     zero_state_at=zero_state_at)
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


def jitted_layer(m, i, prec="f32", parts=False, margin=0.0,
                 zero_state_at=None):
    """(p, h[, choice]) → `layer`'s (h, aux) for layer i, jitted once per
    (sizes, layer kind, static args): the layers of one kind — the same
    mixer, the same feed-forward — share a program."""
    kind = (is_full_attention(m, i), is_dense(m, i))
    i = [(is_full_attention(m, j), is_dense(m, j))
         for j in range(m["num_hidden_layers"])].index(kind)

    def run(p, h, choice=None):
        return layer(p, m, h, i, prec, None, parts, choice, margin,
                     zero_state_at)

    return _JITS.setdefault(
        ("layer", _key(m), kind, prec, parts, margin, zero_state_at),
        jax.jit(run))

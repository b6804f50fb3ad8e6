"""Plain reference of the token denoiser on Phi-4-mini-flash-reasoning's
decoder stack (SambaY; models/token_denoiser.py, `Phi4FlashLayer`), ε̂ of
the target frame out.

Every layer, 0-based i of N: h ← h + Mix_i(LN(h)), h ← h + MLP(LN(h)); LN
is LayerNorm with weight and bias, eps 1e-5; MLP(x) = W_down(SiLU(W_gate x)
⊙ W_up x), no bias. With `slot` = (i mod mb_per_layer = 0), as the source
writes it: i < N/2 + 2 — Mamba where slot, else differential attention
under the window, over everything at i = N/2 + 1; i ≥ N/2 + 2 — a gated
memory unit where slot, else differential cross-attention. For the tokens
x (S, hidden) of a sequence, normalised:

  Mamba (d_inner C = 2·hidden, d_state N = 16, 4 taps, dt_rank R =
  ⌈hidden/16⌉):
    [u ; z] = x·W_in;  u′_t = SiLU(Σ_j w_j ⊙ u_{t−3+j} + b_c), zeros
    before the sequence's first token
    [δ ; B ; C] = u′_t·W_x;  Δ_t = softplus(δ·W_dt + b_dt);  A = −exp(A_log)
    s_t = exp(Δ_t ⊙ A) ⊙ s_{t−1} + (Δ_t ⊙ u′_t) B_tᵀ        s ∈ R^(C × N)
    m_t = s_t C_t + D ⊙ u′_t                TOKEN BY TOKEN, s_0 = 0, float32
    y_t = (m_t ⊙ SiLU(z_t))·W_out.   Layer N/2 publishes m.

  differential attention (40 query, 20 key and 20 value heads of 64; no
  positional term): [q ; k ; v] = x·W_qkv + b. Adjacent heads pair: pair
  j's maps use q_{2j}, q_{2j+1} against k_{2g}, k_{2g+1}, its value is
  V_g = [v_{2g} ‖ v_{2g+1}] (128 wide), g = j // 2.
    A¹ = softmax_M(q¹k¹ᵀ/8), A² = softmax_M(q²k²ᵀ/8)   float32, M the mask
    o_j = (1 − λ⁰_i)·RMSNorm_128((A¹ − λ_i A²)·V_g; weight, eps 1e-5)
    λ_i = exp(λ_q1·λ_k1) − exp(λ_q2·λ_k2) + λ⁰_i,  λ⁰_i = 0.8 − 0.6·e^(−0.3 i)
    y = [o_0 ‖ … ‖ o_19]·W_o + b_o.   Layer N/2 + 1 publishes k and v.

  cross layer: q = x·W_q + b only; k, v are layer N/2 + 1's; the same form
  with its own λ vectors, sub-layer norm and W_o.
  gated memory unit: y_t = (SiLU(x_t·W_in) ⊙ m_t)·W_out, m layer N/2's.

**Departures from the source, each the denoiser's and said in the
configuration file too.** (1) The attention layers' mask M: a token at p
sees a key at p′ iff frame(p′) ≤ frame(p) — this repo's frame rule in
place of the language model's p′ ≤ p — AND, in a window layer, p′ > p −
sliding_window (one-sided, as the other windowed trunk's); written below
as one dense (S, S) predicate. The Mamba layers keep the source's
recurrence in sequence order unchanged: the conditioning frame's tokens
come first, so they never depend on z_t. (2) The adapters around the trunk
(patches, rays, the logsnr embedding, the last RMSNorm and the output
Dense) are this repo's, the same as the other token configurations'. (3)
Sizes the config.json does not give are the configuration file's
`assumed`: Mamba-1's d_state, d_conv, expand and dt_rank, the biases, the
pairing order, the sub-layer norm's eps.

Straightforward jax.numpy in float32 with matmul precision "highest". BOTH
frames go through ONE full forward: no cache, no once-a-call pass that
stops early, no chunks, no kernels — the scan is a `lax.scan` over the 2L
tokens, the two softmax maps dense, a row and a pair of heads at a time
(one pair's two (S, S) maps), the MLP a row at a time. It imports nothing
of the program; weights come from the benchmark's own seeded builder
(ssm_weights.py); parameter NAMES follow the program's tree because the
same seeded tree is handed to both sides.

`m` (sizes, the source's key names): hidden_size, num_hidden_layers,
num_attention_heads, num_key_value_heads, intermediate_size,
layer_norm_eps, mb_per_layer, sliding_window, mamba_d_state, mamba_d_conv,
mamba_expand, patch_size.

`prec` selects the arithmetic of every matmul and attention product, as in
ms4_ref.py: "f32" (the reference proper), "bf16", "fp8" (inputs rounded to
float8_e4m3fn, scaled per tensor). The lower ones are the controls. Norms, softmax, Δ, λ and the recurrence itself stay float32 in
every mode. Two more controls are of THIS mechanism: `zero_state_at` (every
Mamba layer's state set to zero before that token: a target frame entered
without what the conditioning frame wrote) and `lost_shared_cache` (the
cross layers see layer N/2 + 1's keys and values of their OWN frame alone:
the conditioning frame's shared cache lost).

What earlier layers published travels beside h in `pub` ({"m", "k", "v"}),
and a layer hands back what it publishes itself, so that the benchmark can
run a layer at a time.
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
def layer_kind(m, i):
    """Layer i's kind by the source's rule (the module's head)."""
    half = m["num_hidden_layers"] // 2
    slot = i % m["mb_per_layer"] == 0
    if i >= half + 2:
        return "gmu" if slot else "attn_cross"
    if slot:
        return "mamba"
    return "attn_full" if i == half + 1 else "attn_window"


def lambda_init(i):
    return 0.8 - 0.6 * float(np.exp(-0.3 * i))


def layer_norm(x, p, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def causal_conv(x, w, b):
    """x (S, C), w (K, C), b (C,): y_t = Σ_j w_j ⊙ x_{t−(K−1)+j} + b, zeros
    before the sequence's first token."""
    K, S = w.shape[0], x.shape[0]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(xp[j:j + S] * w[j].astype(jnp.float32) for j in range(K)) \
        + b.astype(jnp.float32)


def recurrence(u, dt, A, B, C, D):
    """The selective scan token by token over the sequences of a batch. u,
    dt (B, S, C), A (C, N), B, C (B, S, N), D (C,) → m (B, S, C), every
    row from a zero state (B, C, N)."""
    def step(s, x):
        u_t, dt_t, b_t, c_t = x
        s = jnp.exp(dt_t[:, :, None] * A) * s \
            + (dt_t * u_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1) + D * u_t

    s0 = jnp.zeros((u.shape[0],) + A.shape, jnp.float32)
    return jnp.moveaxis(jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (u, dt, B, C)))[1], 0, 1)


DECAY_QUANTILES = (0.05, 0.25, 0.5, 0.75, 0.95)


def mamba(p, m, a, prec, zero_state_at=None, parts=False):
    """Mamba over the whole sequence a (B, S, hidden), normalised: the
    projections a row at a time, the recurrence over the sequence once,
    the rows side by side. → (y (B, S, hidden), the scan's output m (B,
    S, C), aux)."""
    N = m["mamba_d_state"]
    R = -(-m["hidden_size"] // 16)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    D = p["D"].astype(jnp.float32)

    def into(x):
        u, z = jnp.split(mm(x, p["in"]["kernel"], prec), 2, axis=-1)
        u = silu(causal_conv(u, p["conv"]["kernel"], p["conv"]["bias"]))
        dbc = mm(u, p["x"]["kernel"], prec)
        dt = jax.nn.softplus(mm(dbc[:, :R], p["dt"]["kernel"], prec)
                             + p["dt"]["bias"].astype(jnp.float32))
        return u, z, dt, dbc[:, R:R + N], dbc[:, R + N:]

    u, z, dt, B, C = jax.lax.map(into, a)
    if zero_state_at is None:
        out = recurrence(u, dt, A, B, C, D)
    else:
        cut = zero_state_at
        out = jnp.concatenate(
            [recurrence(u[:, :cut], dt[:, :cut], A, B[:, :cut], C[:, :cut],
                        D),
             recurrence(u[:, cut:], dt[:, cut:], A, B[:, cut:], C[:, cut:],
                        D)], axis=1)
    aux = {}
    if parts:
        # a (channel, state)'s decay rate a token is Δ_t·|A|: ln 2 over it
        # is its half-life in tokens at that token's step
        rate = (dt[:, ::max(1, dt.shape[1] // 256), :, None]
                * -A).reshape(-1)
        aux = {"decay_rate_quantiles": jnp.quantile(
            rate[::max(1, rate.size // 2 ** 22)],
            jnp.asarray(DECAY_QUANTILES))}
    y = jax.lax.map(lambda x: mm(x[0] * silu(x[1]), p["out"]["kernel"],
                                 prec), (out, z))
    return y, out, aux


def visible(S, window=None, own_frame_only=False):
    """The dense (S, S) predicate [query p, key p′] over a sequence of two
    frames: frame(p′) ≤ frame(p) (`own_frame_only`: =), and under a window
    p′ > p − window."""
    pos = np.arange(S)
    frame = pos // (S // 2)
    seen = frame[:, None] == frame[None, :] if own_frame_only \
        else frame[:, None] >= frame[None, :]
    if window is not None:
        seen = seen & (pos[None, :] > pos[:, None] - window)
    return seen


def diff_attention(p, m, lam0, q, k, v, seen, prec):
    """The differential form over the whole sequence: q (B, S, NH·D), k, v
    (B, S, NKV·D) under the (S, S) predicate `seen`, `lam0` the layer's
    λ⁰. → (B, S, hidden)."""
    B, S, _ = q.shape
    NH, NKV = m["num_attention_heads"], m["num_key_value_heads"]
    D = m["hidden_size"] // NH
    P, G = NH // 2, NKV // 2
    f32 = jnp.float32
    lam = jnp.exp(jnp.sum(p["lambda_q1"].astype(f32)
                          * p["lambda_k1"].astype(f32))) \
        - jnp.exp(jnp.sum(p["lambda_q2"].astype(f32)
                          * p["lambda_k2"].astype(f32))) + lam0
    # pair j reads key/value pair j // (P // G)
    q = q.reshape(B, S, P, 2, D)
    k = jnp.repeat(k.reshape(B, S, G, 2, D), P // G, axis=2)
    v = jnp.repeat(v.reshape(B, S, G, 2 * D), P // G, axis=2)
    seen = jnp.asarray(seen)

    def one_row(args):
        def one_pair(x):
            qp, kp, vp = x                 # (S, 2, D), (S, 2, D), (S, 2D)
            a1, a2 = (jax.nn.softmax(jnp.where(seen, jnp.matmul(
                _q(qp[:, s], prec), _q(kp[:, s], prec).T, precision=HI)
                * D ** -0.5, -jnp.inf), axis=-1) for s in (0, 1))
            return jnp.matmul(_q(a1 - lam * a2, prec), _q(vp, prec),
                              precision=HI)

        o = jax.lax.map(one_pair, tuple(jnp.moveaxis(t, 1, 0) for t in args))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + m["layer_norm_eps"]) \
            * p["sub_norm"]["scale"].astype(f32) * (1.0 - lam0)
        return jnp.moveaxis(o, 0, 1).reshape(S, NH * D)

    return mm(jax.lax.map(one_row, (q, k, v)), p["o"]["kernel"], prec) \
        + p["o"]["bias"].astype(f32)


def attention(p, m, a, i, pub, prec, lost_shared_cache=False, lam0=None):
    """Layer i's differential attention of a (B, S, hidden), normalised. →
    (y, what it publishes): a window or full layer projects q, k, v; the
    full layer publishes its k and v; a cross layer projects q and reads
    them from `pub`. `lam0`
    stands in for λ⁰_i where given (a layer-at-a-time caller hands it to
    one program for all layers of a kind)."""
    lam0 = lambda_init(i) if lam0 is None else lam0
    S = a.shape[1]
    NH, NKV = m["num_attention_heads"], m["num_key_value_heads"]
    D = m["hidden_size"] // NH
    kind = layer_kind(m, i)
    qkv = mm(a, p["qkv"]["kernel"], prec) + p["qkv"]["bias"].astype(
        jnp.float32)
    q = qkv[..., :NH * D]
    new = {}
    if kind == "attn_cross":
        k, v = pub["k"], pub["v"]
        seen = visible(S, own_frame_only=lost_shared_cache)
    else:
        k, v = qkv[..., NH * D:(NH + NKV) * D], qkv[..., (NH + NKV) * D:]
        seen = visible(S, m["sliding_window"] if kind == "attn_window"
                       else None)
        if kind == "attn_full":
            new = {"k": k, "v": v}
    return diff_attention(p, m, lam0, q, k, v, seen, prec), new


def gated_mlp(p, x, prec):
    return mm(silu(mm(x, p["gate"]["kernel"], prec))
              * mm(x, p["up"]["kernel"], prec), p["down"]["kernel"], prec)


def layer(p, m, h, i, pub, prec="f32", parts=False, zero_state_at=None,
          lost_shared_cache=False, lam0=None):
    """Decoder layer i over h (B, S, hidden), with what earlier layers
    published in `pub`. → (h, what THIS layer publishes ({} or {"m"} or
    {"k", "v"}), aux). With `parts` a Mamba layer's aux
    holds "decay_rate_quantiles" (DECAY_QUANTILES of Δ·|A| over tokens,
    channels and states)."""
    eps = m["layer_norm_eps"]
    kind = layer_kind(m, i)
    a = layer_norm(h, p["norm"], eps)
    new, aux = {}, {}
    if kind == "mamba":
        y, out, aux = mamba(p["mamba"], m, a, prec, zero_state_at, parts)
        if layer_kind(m, i + m["mb_per_layer"]) != "mamba":   # the last one
            new = {"m": out}
    elif kind == "gmu":
        g = p["gmu"]
        y = jax.lax.map(lambda x: mm(
            silu(mm(x[0], g["in"]["kernel"], prec)) * x[1],
            g["out"]["kernel"], prec), (a, pub["m"]))
    else:
        y, new = attention(p["attn"], m, a, i, pub, prec, lost_shared_cache,
                           lam0)
    h = h + y
    b = layer_norm(h, p["mlp_norm"], eps)
    return h + jax.lax.map(lambda x: gated_mlp(p["mlp"], x, prec), b), \
        new, aux


def head(params, m, h, side, prec="f32"):
    """Last norm (RMSNorm, the frame's own) and the output adapter on the
    target's tokens → ε̂ (B, side, side, 3)."""
    L = h.shape[1] // 2
    hn = rms_norm(h[:, L:], params["final_norm"]["scale"],
                  m["layer_norm_eps"])
    return unpatch(mm(hn, params["out"]["kernel"], prec), side, side,
                   m["patch_size"])


def forward(params, m, batch, cond_mask, prec="f32", zero_state_at=None,
            lost_shared_cache=False):
    """ε̂ (B, H, W, 3) of the whole model."""
    h, pub = embed(params, m, batch, cond_mask, prec), {}
    for i in range(m["num_hidden_layers"]):
        h, new, _ = layer(params[f"layer_{i}"], m, h, i, pub, prec,
                          zero_state_at=zero_state_at,
                          lost_shared_cache=lost_shared_cache)
        pub = {**pub, **new}
    return head(params, m, h, batch["z"].shape[1], prec)


def _key(m):
    return tuple(sorted((k, repr(v)) for k, v in m.items()))


def jitted(name, m, *static):
    """embed/head jitted once per (sizes, static args)."""
    fn = {"embed": embed, "head": head}[name]
    return _JITS.setdefault(
        (name, _key(m)) + static,
        jax.jit(lambda *a: fn(a[0], m, *a[1:], *static)))


def jitted_layer(m, i, prec="f32", parts=False, zero_state_at=None,
                 lost_shared_cache=False):
    """(p, h, pub) → `layer`'s (h, new, aux) for layer i, jitted once per
    (sizes, layer kind — the Mamba layer that publishes apart —, static
    args): the layers of one kind share a program, λ⁰_i its argument."""
    def kind(j):
        return (layer_kind(m, j), layer_kind(m, j) == "mamba" and
                layer_kind(m, j + m["mb_per_layer"]) != "mamba")

    same = [kind(j) for j in range(m["num_hidden_layers"])].index(kind(i))
    fn = _JITS.setdefault(
        ("layer", _key(m), same, prec, parts, zero_state_at,
         lost_shared_cache),
        jax.jit(lambda p, h, pub, lam0: layer(
            p, m, h, same, pub, prec, parts, zero_state_at,
            lost_shared_cache, lam0)))
    return lambda p, h, pub: fn(p, h, pub, jnp.float32(lambda_init(i)))

"""The gated delta rule with one decay a head (ops/gdn.py,
`gated_delta_chunked`) against the recurrence written out token by token,
in float32 on the CPU — keys narrower than values, β across (0, 2), from a
state that is not zero, over lengths that are not whole chunks, at the
fastest decay the public draw allows, with float32 operands and with the
bfloat16 ones the convolution hands it (which take fewer MXU passes for the
same float32: held to a float64 recurrence, where a part too few shows) —;
the short convolution in front of it at heads that are no whole lane
blocks (96 and 192 lanes: several heads share a group of lane blocks); and
what the delta-rule operators share (ops/_delta_rule.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.ops import _delta_rule, gdn, kda, short_conv
from novel_view_synthesis_3d_tpu.ops.gdn import CHUNK, gated_delta_chunked

TOL = 2e-5


def recurrence(q, k, v, g, beta, S0):
    """S_t = e^{g_t}·S_{t−1} + β_t k_t (v_t − e^{g_t}·S_{t−1}ᵀ k_t)ᵀ, o_t =
    S_tᵀ q_t, a token at a time."""
    B, L, H = beta.shape
    dk, dv = q.shape[-1] // H, v.shape[-1] // H
    q, k = (x.reshape(B, L, H, dk) for x in (q, k))
    v = v.reshape(B, L, H, dv)

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None, None] * S
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", S, k_t, precision="highest"))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision="highest")

    S, o = jax.lax.scan(step, S0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).reshape(B, L, H * dv), S


def operands(B, L, H, dk, dv, seed=0, beta=None, rate=0.1, state=True):
    """Unit keys, queries scaled as the layer scales them, log-decays in
    (−3·rate, 0], β uniform on (0, 2) or the one given."""
    rng = np.random.default_rng(seed)
    q, k = rng.normal(size=(2, B, L, H, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    b = 2 * rng.uniform(size=(B, L, H)) if beta is None \
        else np.full((B, L, H), beta)
    S0 = rng.normal(size=(B, H, dk, dv)) if state \
        else np.zeros((B, H, dk, dv))
    return [jnp.asarray(x, jnp.float32) for x in (
        q.reshape(B, L, -1), k.reshape(B, L, -1),
        rng.normal(size=(B, L, H * dv)),
        -3 * rate * rng.uniform(size=(B, L, H)) ** 2, b, S0)]


def close(got, want):
    for a, b in zip(got, want):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) < TOL * max(scale, 1.0)


def narrowed(args, dtype):
    """q, k, v as the convolution hands them over in that compute type."""
    return [x.astype(dtype) for x in args[:3]] + list(args[3:])


def widened(args):
    return [x.astype(jnp.float32) for x in args]


@pytest.mark.parametrize("B,L,H,dk,dv,chunk,dtype", [
    (2, 100, 3, 8, 16, 16, "float32"),   # values twice the keys, a ragged
                                         # length
    (1, 200, 2, 96, 192, CHUNK, "float32"),  # the source's head, three
                                             # chunks and a bit
    (1, 200, 2, 96, 192, CHUNK, "bfloat16"),
    (2, 40, 2, 8, 4, 8, "float32"),      # keys WIDER than values, a chunk
                                         # under 16
    (1, 64, 1, 16, 16, 64, "float32"),   # one whole chunk
    (1, 70, 6, 96, 192, CHUNK, "float32"),   # four heads a grid step: the
                                             # second step walks two
    (1, 70, 6, 96, 192, CHUNK, "bfloat16"),
    (2, 300, 3, 32, 64, CHUNK, "float32"),   # two runs of four chunks,
                                             # heads 3 a step
], ids=["8on16", "96on192", "96on192_bf16", "8on4", "one_chunk",
        "edge_group", "edge_group_bf16", "two_runs"])
def test_chunked_matches_the_recurrence(B, L, H, dk, dv, chunk, dtype):
    args = narrowed(operands(B, L, H, dk, dv), dtype)
    got = gated_delta_chunked(*args, chunk=chunk)
    assert got[0].shape == (B, L, H * dv) and got[0].dtype == jnp.float32
    assert got[1].shape == (B, H, dk, dv) and got[1].dtype == jnp.float32
    close(got, recurrence(*widened(args)))


def recurrence64(q, k, v, g, beta, S0):
    """`recurrence` in numpy's float64: what float32 means to approach."""
    q, k, v, g, beta, S = (np.asarray(x, np.float64)
                           for x in (q, k, v, g, beta, S0))
    B, L, H = beta.shape
    q, k, v = (x.reshape(B, L, H, -1) for x in (q, k, v))
    o = np.zeros(v.shape)
    for t in range(L):
        S = np.exp(g[:, t])[..., None, None] * S
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bhkv,bhk->bhv", S, k[:, t]))
        S = S + k[:, t][..., :, None] * u[..., None, :]
        o[:, t] = np.einsum("bhkv,bhk->bhv", S, q[:, t])
    return o.reshape(B, L, -1), S


def worst(got, want):
    """The largest error of o and of the state, each as a share of the
    largest value."""
    return max(float(np.max(np.abs(np.asarray(a, np.float64) - b))
                     / np.max(np.abs(b))) for a, b in zip(got, want))


@pytest.fixture
def retraced():
    """`_gdn_call` is jitted: what a test plants in the product is traced
    in only by a fresh trace, and must not stay traced in behind it."""
    yield
    gdn._gdn_call.clear_cache()


def test_bfloat16_operands_keep_every_bit_of_the_float32_scan(
        monkeypatch, retraced):
    """bfloat16 q, k, v take fewer passes (`_delta_rule.mm_parts`), not
    fewer bits: against the float64 recurrence the scan stays within 3e-6
    of the largest value — and the state or U cut to TWO bfloat16 parts
    (16 of a float32's 24 bits), planted here, does not: no later edit
    trades passes for bits unseen."""
    args = narrowed(operands(1, 256, 2, 96, 192, seed=11), "bfloat16")
    want = recurrence64(*widened(args))
    assert worst(gated_delta_chunked(*args), want) < 3e-6
    monkeypatch.setattr(_delta_rule, "PARTS", 2)
    gdn._gdn_call.clear_cache()
    assert worst(gated_delta_chunked(*args), want) > 3e-6


@pytest.mark.parametrize("beta", [0.0, 0.05, 1.0, 1.95, 2.0])
def test_beta_across_zero_to_two(beta):
    """β = 1 erases a key's old value; past 1 it is written with the other
    sign (an eigenvalue of the transition below 0); at 0 nothing is
    written and the state only decays."""
    args = operands(1, 150, 2, 12, 20, seed=3, beta=beta)
    got = gated_delta_chunked(*args, chunk=32)
    close(got, recurrence(*args))
    if beta == 0.0:
        decayed = args[5] * jnp.exp(jnp.sum(args[3], axis=1))[..., None, None]
        np.testing.assert_allclose(got[1], decayed, rtol=1e-5, atol=1e-6)


def test_no_state_is_a_zero_state_and_a_sequence_continues():
    """S0 = None starts from zeros; a sequence cut in two and entered with
    the first half's state is the sequence whole (what `precompute` and a
    step are to each other)."""
    q, k, v, g, b, _ = operands(2, 96, 2, 8, 16, seed=5, state=False)
    whole = gated_delta_chunked(q, k, v, g, b, None, chunk=32)
    close(whole, recurrence(q, k, v, g, b, jnp.zeros((2, 2, 8, 16))))
    cut = 50    # not a chunk's edge
    first = gated_delta_chunked(*(x[:, :cut] for x in (q, k, v, g, b)),
                                chunk=32)
    second = gated_delta_chunked(*(x[:, cut:] for x in (q, k, v, g, b)),
                                 first[1], chunk=32)
    close((jnp.concatenate([first[0], second[0]], axis=1), second[1]), whole)


def test_the_fastest_decay_of_the_public_draw_neither_overflows_nor_nans():
    """A = 16 and a step the data pushed to softplus(·) = 10: g = −160 a
    token, e^γ underflows within a chunk's first tokens. Nothing is ever
    divided by a decay, so an underflow is the value: finite, and the
    recurrence's."""
    q, k, v, _, b, S0 = operands(1, 130, 2, 12, 20, seed=7)
    g = jnp.full((1, 130, 2), -160.0).at[:, ::7].set(-1e-4)
    got = gated_delta_chunked(q, k, v, g, b, S0 * 1e3)
    assert bool(jnp.isfinite(got[0]).all() & jnp.isfinite(got[1]).all())
    close(got, recurrence(q, k, v, g, b, S0 * 1e3))
    slow = gated_delta_chunked(q, k, v, jnp.zeros_like(g), b, S0)
    close(slow, recurrence(q, k, v, jnp.zeros_like(g), b, S0))


def test_compute_type_operands_are_widened_not_rounded_again():
    """q, k, v arrive in bfloat16 from the convolution: the scan takes
    their values as they are, in float32."""
    args = operands(1, 70, 2, 8, 16, seed=9)
    low = [x.astype(jnp.bfloat16) for x in args[:3]]
    got = gated_delta_chunked(*low, *args[3:], chunk=16)
    close(got, recurrence(*(x.astype(jnp.float32) for x in low), *args[3:]))


def test_a_gradient_raises_by_name():
    args = operands(1, 16, 1, 4, 8)
    with pytest.raises(NotImplementedError,
                       match="gated_delta_chunked has no backward"):
        jax.grad(lambda v: gated_delta_chunked(
            args[0], args[1], v, *args[3:], chunk=8)[0].sum())(args[2])
    with pytest.raises(ValueError, match="power of two"):
        gated_delta_chunked(*args, chunk=24)


@pytest.mark.parametrize("C", [8, 16, 64])
def test_near_parallel_keys_under_beta_two(C):
    """Near-parallel keys (a mostly white frame's are) under β = 2: (I + A)
    is far from the identity — its inverse's entries alternate about ±2 —,
    and the substitution, rows then halves, still gives the recurrence's
    result where the series Σ(−A)ⁿ would have to cancel terms of 2ⁿ."""
    rng = np.random.default_rng(C)
    q, _, v, g, _, S0 = operands(1, 2 * C + 5, 2, 16, 24, seed=C)
    k = rng.normal(size=(1, 1, 2, 16)) + 0.05 * rng.normal(
        size=(1, 2 * C + 5, 2, 16))
    k = jnp.asarray(k / np.linalg.norm(k, axis=-1, keepdims=True),
                    jnp.float32).reshape(1, -1, 32)
    beta = jnp.full((1, 2 * C + 5, 2), 2.0)
    got = gated_delta_chunked(q, k, v, g, beta, S0, chunk=C)
    want = recurrence(q, k, v, g, beta, S0)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-4 * max(
            float(jnp.max(jnp.abs(b))), 1.0)


def test_both_delta_rule_operators_share_one_product_and_one_merge():
    assert kda._mm is _delta_rule.mm and kda._placed is _delta_rule.placed
    assert kda.merge_blocks is _delta_rule.merge_blocks
    assert gdn.mm is _delta_rule.mm and gdn.mm_parts is _delta_rule.mm_parts
    assert gdn.merge_rows is _delta_rule.merge_rows
    a = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
    b = jnp.ones((4, 5), jnp.float32)
    np.testing.assert_allclose(_delta_rule.mm(a, b), a @ b)
    np.testing.assert_allclose(_delta_rule.mm(a, b.T, ((1,), (1,))), a @ b)
    np.testing.assert_allclose(_delta_rule.mm(a.T, b, ((0,), (0,))), a @ b)
    x = jnp.ones((2, 3))
    np.testing.assert_array_equal(
        _delta_rule.placed(x, 1, 4), jnp.pad(x, ((1, 1), (0, 0))))


@pytest.mark.parametrize("contract", [((1,), (0,)), ((1,), (1,)),
                                      ((0,), (0,))],
                         ids=["ab", "abT", "aTb"])
@pytest.mark.parametrize("types", ["bfloat16·bfloat16", "bfloat16·float32",
                                   "float32·bfloat16", "float32·float32"])
def test_mm_parts_is_the_float32_product_whatever_the_passes(types, contract):
    """`mm_parts` against numpy's float64 product, at every contraction
    `mm` takes: a bfloat16 operand is one part, the float32 one opposite
    it three — the WHOLE float32 product, to the accumulator's rounding —,
    two float32 operands are `mm`; two bfloat16 operands and a float32
    result are exact but for the sum's order."""
    rng = np.random.default_rng(5)
    (ca,), (cb,) = contract
    a = rng.normal(size=(24, 40) if ca else (40, 24))
    b = rng.normal(size=(40, 16) if not cb else (16, 40))
    a, b = (jnp.asarray(x, jnp.float32).astype(t)
            for x, t in zip((a, b), types.split("·")))
    got = _delta_rule.mm_parts(a, b, contract)
    assert got.dtype == jnp.float32 and got.shape == (24, 16)
    a64, b64 = (np.asarray(x.astype(jnp.float32), np.float64)
                for x in (a, b))
    want = (a64 if ca else a64.T) @ (b64.T if cb else b64)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())
    if types == "float32·float32":
        np.testing.assert_array_equal(got, _delta_rule.mm(a, b, contract))


@pytest.mark.parametrize("P,C,size", [(128, 64, 16), (64, 64, 16),
                                      (32, 8, 4), (128, 64, 32)])
def test_merge_rows_is_merge_blocks_at_half_the_rows(P, C, size):
    """Both merges from the same sub-block inverses, for P // C chunks
    block-diagonally in one (P, P): the same T, which is (I + M)⁻¹ of
    each chunk."""
    rng = np.random.default_rng(P + size)
    rows, cols = np.indices((P, P))
    same = rows // C == cols // C
    M = np.where(same & (rows > cols), rng.normal(size=(P, P)), 0.0)
    T0 = np.zeros((P, P))
    for at in range(0, P, size):
        block = slice(at, at + size)
        T0[block, block] = np.linalg.inv(np.eye(size) + M[block, block])
    args = (jnp.asarray(T0, jnp.float32), jnp.asarray(M, jnp.float32), size,
            C, jnp.asarray(rows % C), jnp.asarray(cols % C),
            jnp.asarray(same))
    got = _delta_rule.merge_rows(*args)
    np.testing.assert_allclose(got, _delta_rule.merge_blocks(*args),
                               rtol=0, atol=1e-5)
    want = np.zeros((P, P))
    for at in range(0, P, C):
        block = slice(at, at + C)
        want[block, block] = np.linalg.inv(np.eye(C) + M[block, block])
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * np.abs(want).max())


def test_bfloat16_parts_hold_every_bit():
    x = jnp.asarray(np.random.default_rng(6).normal(size=(8, 128))
                    * 10.0 ** np.arange(-4, 4)[:, None], jnp.float32)
    parts = _delta_rule.bfloat16_parts(x)
    assert len(parts) == 3 and all(p.dtype == jnp.bfloat16 for p in parts)
    np.testing.assert_array_equal(
        sum(np.asarray(p.astype(jnp.float32), np.float64) for p in parts),
        np.asarray(x, np.float64))


# ---------------------------------------------------------------------------
# The short convolution at heads that are no whole lane blocks
# ---------------------------------------------------------------------------
def plain_conv(x, w, tail, heads, scale, eps=1e-6):
    B, L, D = x.shape
    K = w.shape[0]
    ext = jnp.concatenate([tail, x], axis=1).astype(jnp.float32)
    a = sum(ext[:, j:j + L] * w[j].astype(jnp.float32) for j in range(K))
    y = a * jax.nn.sigmoid(a)
    if heads:
        y = y.reshape(B, L, heads, D // heads)
        y = y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + eps)
        y = y.reshape(B, L, D)
    return y * scale, ext[:, L:]


@pytest.mark.parametrize("D,heads", [
    (384, 4),     # four heads of 96 lanes fill three lane blocks
    (576, 6),     # six of them: a whole group and a half one
    (384, 2),     # two heads of 192 lanes
    (960, 5),     # five of 192: two groups and a half
    (96, 8),      # heads of 12 lanes, eight to a part of one lane block
], ids=["4x96", "6x96", "2x192", "5x192", "8x12"])
def test_short_conv_at_heads_of_no_whole_lane_blocks(D, heads):
    """Through the interpreter, in the groups the chip's kernel takes: a
    head's norm is its own lanes', whatever heads share its lane blocks."""
    rng = np.random.default_rng(D + heads)
    x = jnp.asarray(rng.normal(size=(2, 37, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, D)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(2, 3, D)), jnp.float32)
    scale = (D // heads) ** -0.5
    got, new_tail = short_conv.short_conv(x, w, tail, heads=heads,
                                          scale=scale)
    want, want_tail = plain_conv(x, w, tail, heads, scale)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(new_tail, want_tail)
    # a head's norm: each head of the result has length `scale`
    norms = jnp.linalg.norm(got.reshape(2, 37, heads, -1), axis=-1)
    np.testing.assert_allclose(norms, scale, rtol=1e-3)
    rows, _, lanes, head, group = short_conv._blocks(37, D, heads, True)
    assert (lanes, head) == (D, D // heads) and group % 128 == 0 \
        and group % head == 0


def test_short_conv_blocks_on_the_chip():
    """What the compiled kernel walks, from the shapes alone: the source's
    30 heads of 96 go 768 lanes a grid step (two groups of four heads), the
    fourth step's block hanging over the edge of 2880; its values and the
    other trunks' calls go as they did."""
    assert short_conv._blocks(4096, 2880, 30, False) == (512, 64, 768, 96,
                                                         384)
    # 5760 = 15 × 384: a step's lanes divide the width where they can
    assert short_conv._blocks(4096, 5760, 30, False) == (512, 64, 384, 192,
                                                         384)
    assert short_conv._blocks(4096, 5760, None, False) == (512, 64, 640, 128,
                                                           128)
    # Kimi-Linear's q and k (32 heads of 128), Mamba's u (no heads)
    assert short_conv._blocks(4096, 4096, 32, False) == (512, 64, 1024, 128,
                                                         128)
    assert short_conv._blocks(4096, 5120, None, False) == (512, 64, 1024,
                                                           128, 128)
    assert short_conv._blocks(4000, 4096, 32, False)[0] == 512
    assert short_conv._blocks(100, 128, 4, False) == (128, 64, 128, 32, 128)
    with pytest.raises(ValueError, match="do not divide"):
        short_conv._blocks(64, 100, 3, False)
    with pytest.raises(ValueError, match="whole 128-lane blocks"):
        short_conv._blocks(64, 96, None, False)

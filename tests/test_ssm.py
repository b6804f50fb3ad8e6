"""ops/ssm.py's selective scan (the Pallas kernel `ssm_fwd`, interpreted
here) against the token-by-token recurrence, and ops/short_conv.py's short
convolution (the Pallas kernel `short_conv_fwd`, interpreted here) against
the plain jnp form kept below, at small sizes on the CPU; float32 on both
sides, differing by nothing but the order of a sum."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.ops import _pallas, ssm
from novel_view_synthesis_3d_tpu.ops.short_conv import short_conv


def recurrence(u, dt, A, B, C, D, s0):
    """s_t = exp(Δ_t ⊙ A) ⊙ s_{t−1} + (Δ_t ⊙ u_t) B_tᵀ, m_t = s_t C_t + D ⊙
    u_t, a token at a time; the state (rows, C, N) as the equations have
    it."""
    def step(s, x):
        u_t, dt_t, b_t, c_t = x
        s = jnp.exp(dt_t[:, :, None] * A) * s \
            + (dt_t * u_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1) + D * u_t

    last, m = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (u, dt, B, C)))
    return jnp.moveaxis(m, 0, 1), last


def inputs(L, rate=1.0, rows=2, C=48, N=8, seed=0):
    """Steps Δ with Δ·|A| planted about `rate` a token."""
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    A = -jnp.exp(0.3 * n(C, N))
    dt = rate * jax.nn.softplus(n(rows, L, C) + 1.0)
    return n(rows, L, C), dt, A, n(rows, L, N), n(rows, L, N), n(C), \
        n(rows, C, N)


def close(got, want, tol=1e-5):
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.max(jnp.abs(got - want))) <= tol * float(
        jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("L,rate,with_state", [
    (16, 1.0, False),   # one chunk, from a zero state
    (64, 0.05, True),   # whole chunks, slow decays, entered from a state
    (37, 1.0, True),    # no multiple of the chunk: padded rows pass the state
    (5, 1.0, True),     # shorter than a chunk
], ids=["one-chunk", "whole-chunks-slow", "ragged", "short"])
def test_selective_scan_is_the_token_by_token_recurrence(L, rate,
                                                         with_state):
    u, dt, A, B, C, D, s0 = inputs(L, rate)
    s0 = s0 if with_state else jnp.zeros_like(s0)
    m, last = ssm.selective_scan(u, dt, A, B, C, D,
                                 jnp.swapaxes(s0, 1, 2) if with_state
                                 else None)
    want, want_last = recurrence(u, dt, A, B, C, D, s0)
    close(m, want)
    # the state after the LAST TRUE token, kept transposed: (rows, N, C)
    assert last.shape == (2, 8, 48) and last.dtype == jnp.float32
    close(jnp.swapaxes(last, 1, 2), want_last)


def test_selective_scan_fast_decay_neither_overflows_nor_nans():
    """Δ·|A| = 16 a token: exp(−16) a step, 1e-7 of a state survives one
    token and nothing overflows on the way."""
    u, dt, A, B, C, D, s0 = inputs(40)
    A = jnp.full_like(A, -16.0)
    dt = jnp.ones_like(dt)
    m, last = ssm.selective_scan(u, dt, A, B, C, D, jnp.swapaxes(s0, 1, 2))
    want, want_last = recurrence(u, dt, A, B, C, D, s0)
    close(m, want)
    close(jnp.swapaxes(last, 1, 2), want_last)


def test_selective_scan_frame_by_frame_is_one_pass():
    """A sequence scanned in two halves, the second entered with the
    first's last state: the once-a-call pass and a step."""
    u, dt, A, B, C, D, _ = inputs(48)
    whole, end = ssm.selective_scan(u, dt, A, B, C, D)
    first, mid = ssm.selective_scan(u[:, :21], dt[:, :21], A, B[:, :21],
                                    C[:, :21], D)
    second, last = ssm.selective_scan(u[:, 21:], dt[:, 21:], A, B[:, 21:],
                                      C[:, 21:], D, mid)
    close(jnp.concatenate([first, second], axis=1), whole, 1e-6)
    close(last, end, 1e-6)


def test_selective_scan_takes_bfloat16_as_the_same_values_widened():
    u, dt, A, B, C, D, s0 = inputs(24)
    u16 = u.astype(jnp.bfloat16)
    got = ssm.selective_scan(u16, dt, A, B, C, D)[0]
    close(got, ssm.selective_scan(u16.astype(jnp.float32), dt, A, B, C,
                                  D)[0], 1e-7)


def test_selective_scan_has_no_backward_and_says_so():
    u, dt, A, B, C, D, _ = inputs(8)
    with pytest.raises(NotImplementedError, match="selective_scan has no "
                       "backward"):
        jax.grad(lambda x: ssm.selective_scan(x, dt, A, B, C, D)[0].sum())(u)


def test_selective_scan_on_the_chip_takes_whole_lane_blocks_only(
        monkeypatch):
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    u, dt, A, B, C, D, _ = inputs(8)
    with pytest.raises(ValueError, match="whole 128-lane"):
        ssm.selective_scan(u, dt, A, B, C, D)


# ---------------------------------------------------------------------------
# ops/short_conv.py
# ---------------------------------------------------------------------------
def plain_conv(x, w, tail=None, bias=None, heads=None, scale=1.0, eps=1e-6):
    """The taps over [tail ; x], the bias, SiLU, each of `heads` blocks of
    the last axis over sqrt(Σ y² + eps), the scale: jnp, float32, no cast."""
    f32 = jnp.float32
    K, L = w.shape[0], x.shape[1]
    before = jnp.zeros((x.shape[0], K - 1, x.shape[2]), f32) \
        if tail is None else tail.astype(f32)
    ext = jnp.concatenate([before, x.astype(f32)], axis=1)
    y = sum(ext[:, j:j + L] * w[j].astype(f32) for j in range(K))
    if bias is not None:
        y = y + bias.astype(f32)
    y = jax.nn.silu(y)
    if heads:
        by_head = y.reshape(y.shape[:-1] + (heads, -1))
        y = (by_head * jax.lax.rsqrt(jnp.sum(
            by_head * by_head, axis=-1, keepdims=True) + eps)).reshape(
                y.shape)
    return y * scale, ext[:, L:]


def conv_inputs(L, rows=2, D=24, K=4, dtype=jnp.float32, seed=3):
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.normal(size=shape), dtype)

    return n(rows, L, D), n(K, D), n(rows, K - 1, D), n(D)


@pytest.mark.parametrize("with_bias", [False, True], ids=["kda", "mamba"])
def test_short_conv_with_a_bias_frame_by_frame_is_one_pass(with_bias):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 20, 12)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 12)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(12,)), jnp.float32) if with_bias \
        else None
    whole, tail = short_conv(x, w, None, b)
    np.testing.assert_allclose(whole, plain_conv(x, w, None, b)[0],
                               rtol=1e-6, atol=1e-6)
    first, mid = short_conv(x[:, :9], w, None, b)
    second, last = short_conv(x[:, 9:], w, mid, b)
    np.testing.assert_array_equal(
        jnp.concatenate([first, second], axis=1), whole)
    np.testing.assert_array_equal(last, tail)
    np.testing.assert_array_equal(tail, x[:, -3:])


@pytest.mark.parametrize("with_tail", [False, True], ids=["start", "tail"])
@pytest.mark.parametrize("with_bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("heads,scale", [(None, 1.0), (3, 8 ** -0.5)],
                         ids=["plain", "normed"])
@pytest.mark.parametrize("L", [16, 37, 50, 2],
                         ids=["one-run", "ragged", "runs", "short"])
def test_short_conv_is_the_plain_form(L, heads, scale, with_bias, with_tail):
    """One run; a length that is not whole runs; several runs a row, so
    the carried rows are walked; a frame shorter than K − 1 rows. Float32
    in, so nothing is cast: within 2e-6 of the largest value."""
    x, w, tail, bias = conv_inputs(L)
    tail, bias = tail if with_tail else None, bias if with_bias else None
    y, new_tail = short_conv(x, w, tail, bias, heads=heads, scale=scale)
    want, want_tail = plain_conv(x, w, tail, bias, heads, scale)
    assert y.dtype == x.dtype and y.shape == x.shape
    close(y, want, 2e-6)
    np.testing.assert_array_equal(new_tail, want_tail)


@pytest.mark.parametrize("K", [2, 4, 9])
def test_short_conv_takes_two_to_nine_taps(K):
    x, w, tail, _ = conv_inputs(40, K=K)
    y, new_tail = short_conv(x, w, tail, heads=2)
    want, want_tail = plain_conv(x, w, tail, heads=2)
    close(y, want, 2e-6)
    np.testing.assert_array_equal(new_tail, want_tail)
    with pytest.raises(ValueError, match="K − 1 rows"):
        short_conv(x, jnp.ones((10, 24)), None)


def test_short_conv_in_the_compute_type_is_one_cast_of_the_float32_form():
    x, w, tail, bias = conv_inputs(40, dtype=jnp.bfloat16)
    y, new_tail = short_conv(x, w, tail, bias, heads=3, scale=0.5)
    want, _ = plain_conv(x, w, tail, bias, 3, 0.5)
    assert y.dtype == jnp.bfloat16 and new_tail.dtype == jnp.bfloat16
    # the float32 values agree to rounding, so the casts differ by at most
    # one step of bfloat16 where a value sits at a rounding boundary
    off = jnp.abs(y.astype(jnp.float32) - want)
    assert float(off.max()) <= 2.0 ** -8 * float(jnp.abs(want).max())
    assert float((y == want.astype(jnp.bfloat16)).mean()) > 0.99
    np.testing.assert_array_equal(new_tail, x[:, -3:])


def test_short_conv_has_no_backward_and_says_so():
    x, w, tail, _ = conv_inputs(16)
    with pytest.raises(NotImplementedError, match="short_conv has no "
                       "backward"):
        jax.grad(lambda x: short_conv(x, w, tail, heads=3)[0].sum())(x)


@pytest.mark.parametrize("heads", [None, 3], ids=["plain", "normed"])
def test_short_conv_on_the_chip_takes_whole_lane_blocks_or_whole_heads(
        monkeypatch, heads):
    """Compiled, a width without heads is whole 128-lane blocks; with heads
    (since PR 40) any width that is whole heads — they are packed into
    lane blocks inside the kernel (tests/test_gdn.py has the blocks)."""
    from novel_view_synthesis_3d_tpu.ops.short_conv import _blocks

    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    x, w, tail, _ = conv_inputs(16)
    if heads is None:
        with pytest.raises(ValueError, match="whole 128-lane"):
            short_conv(x, w, tail, heads=heads)
    else:   # three heads of 8 lanes in one group of 128
        assert _blocks(16, 24, heads, False) == (64, 64, 128, 8, 128)
        with pytest.raises(ValueError, match="do not divide"):
            short_conv(x[..., :23], w[:, :23], tail[..., :23], heads=heads)

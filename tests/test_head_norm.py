"""What the delta-rule layers do to their scan's output before `o`
(ops/head_norm.py, `gated_head_norm`: the Pallas kernel `head_norm_fwd`,
interpreted here) against the plain float32 form on the (B, L, H, d) view —
at heads that are a lane block, that share lane blocks and that span two,
head counts that do not fill the last group of heads or the last grid step,
token counts that are no whole tile of rows, both activations and both
compute types."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.models.token_denoiser import rms_norm
from novel_view_synthesis_3d_tpu.ops import _pallas, head_norm
from novel_view_synthesis_3d_tpu.ops.head_norm import gated_head_norm

EPS = 1e-6
ACTIVATIONS = {"sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu}


def plain(o, gate, scale, heads, activation, eps=EPS):
    """The lines the two layers held: the norm on the 4-D view, float32."""
    B, L, D = o.shape
    act = ACTIVATIONS[activation](gate.astype(jnp.float32))
    return (rms_norm(o.reshape(B, L, heads, D // heads), scale, eps)
            * act.reshape(B, L, heads, D // heads)).reshape(B, L, D)


def operands(B, L, heads, d, dtype, seed=0):
    """o over three decades a head (a head's statistic is its own), the
    gate's projection rounded to the compute type as `_dense` leaves it."""
    rng = np.random.default_rng(seed + 1000 * heads + d)
    o = rng.normal(size=(B, L, heads, d)) * 10.0 ** rng.uniform(
        -2, 1, size=(B, L, heads, 1))
    return (jnp.asarray(o.reshape(B, L, heads * d), jnp.float32),
            jnp.asarray(3 * rng.normal(size=(B, L, heads * d)), dtype),
            jnp.asarray(1 + 0.2 * rng.normal(size=d), jnp.float32))


# (tokens, heads, lanes a head): a head a lane block; heads that share lane
# blocks (two of 192 or four of 96 fill three, two of 64 one); a head of two
# blocks; the two sources' counts, which leave a group or a grid step
# unfilled (30 × 192 = 5760 = 3 × 1920, 30 × 96 = 7.5 groups, 5 × 192,
# 3 × 96); tokens that are no whole tile of 32 rows (17, 4000).
SHAPES = [(40, 4, 128), (17, 32, 128), (4000, 2, 128), (40, 2, 192),
          (17, 30, 192), (4000, 5, 192), (40, 4, 96), (17, 3, 96),
          (33, 30, 96), (40, 2, 64), (17, 5, 64), (40, 2, 256),
          (17, 3, 256)]


@pytest.mark.parametrize("activation", sorted(ACTIVATIONS))
@pytest.mark.parametrize("L,heads,d", SHAPES,
                         ids=[f"{L}x{h}x{d}" for L, h, d in SHAPES])
def test_before_the_cast_it_is_the_plain_form(L, heads, d, activation):
    """float32 out: only the order of a head's sum of squares differs."""
    o, gate, scale = operands(2 if L < 100 else 1, L, heads, d, jnp.float32)
    got = gated_head_norm(o, gate, scale, heads=heads, eps=EPS,
                          activation=activation)
    want = plain(o, gate, scale, heads, activation)
    assert got.dtype == jnp.float32 and got.shape == o.shape
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-30)


@pytest.mark.parametrize("activation", sorted(ACTIVATIONS))
@pytest.mark.parametrize("L,heads,d", [(40, 4, 128), (17, 30, 192),
                                       (33, 3, 96), (40, 2, 64)],
                         ids=["40x4x128", "17x30x192", "33x3x96", "40x2x64"])
def test_in_the_compute_type_it_is_one_rounding_of_the_plain_form(
        L, heads, d, activation):
    """bfloat16 gate in, bfloat16 out: the reference's rounding, but where
    the float32 value sits on the boundary between two bfloat16 numbers."""
    o, gate, scale = operands(2, L, heads, d, jnp.bfloat16)
    got = gated_head_norm(o, gate, scale, heads=heads, eps=EPS,
                          activation=activation)
    want = plain(o, gate, scale, heads, activation)
    assert got.dtype == jnp.bfloat16
    rounded = want.astype(jnp.bfloat16)
    differ = np.asarray(got != rounded)
    assert differ.mean() < 0.01
    # where they differ, the float32 value is a tie of the two to within
    # the float32 forms' distance
    middle = (got.astype(jnp.float32) + rounded.astype(jnp.float32)) / 2
    np.testing.assert_allclose(np.asarray(want)[differ],
                               np.asarray(middle)[differ], rtol=4e-6)


def test_a_head_of_zeros_gives_zeros_and_its_neighbours_their_own():
    """eps sits inside the root: a head of zeros is 0 · rsqrt(eps), and a
    head's statistic takes nothing of the head beside it in its lane
    block."""
    o, gate, scale = operands(1, 24, 5, 192, jnp.float32)
    o = o.reshape(1, 24, 5, 192).at[:, :, 1].set(0.0).reshape(1, 24, 960)
    got = gated_head_norm(o, gate, scale, heads=5, eps=EPS,
                          activation="silu").reshape(1, 24, 5, 192)
    assert not np.asarray(got[:, :, 1]).any()
    want = plain(o, gate, scale, 5, "silu").reshape(1, 24, 5, 192)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-30)
    assert np.asarray(got[:, :, 0]).any() and np.asarray(got[:, :, 2]).any()


def test_what_hangs_over_the_edges_is_never_read_into_a_result_or_written():
    """17 tokens in a tile of 32 rows, 3 heads of 96 lanes in a group of
    four: the call is the kernel on (B, L, D) as it lies — no pad before
    it, no slice after it —, and what the last block holds past the edges
    (the interpreter fills it with NaN) reaches no number of the result."""
    o, gate, scale = operands(2, 17, 3, 96, jnp.bfloat16)
    assert head_norm._blocks(17, 288, 3) == (32, 384, 96, 384)
    got = gated_head_norm(o, gate, scale, heads=3, eps=EPS,
                          activation="sigmoid")
    assert got.shape == (2, 17, 288) and bool(jnp.isfinite(
        got.astype(jnp.float32)).all())
    text = str(jax.make_jaxpr(lambda *a: gated_head_norm(
        *a, heads=3, eps=EPS, activation="sigmoid"))(o, gate, scale))
    assert "pallas_call" in text
    for op in (" pad[", " slice[", " dynamic_slice[", " reshape[",
               " transpose["):
        assert op not in text.split("pallas_call")[0], op


def test_blocks_on_the_chip():
    """What the compiled kernel walks, from the shapes alone: (rows a grid
    step, lanes a grid step, lanes a head, lanes a group)."""
    # Kimi-Linear: 32 heads of a lane block each
    assert head_norm._blocks(4096, 4096, 32) == (256, 2048, 128, 128)
    # Gated DeltaNet: two heads of 192 fill three blocks; 5760 = 3 × 1920
    assert head_norm._blocks(4096, 5760, 30) == (256, 1920, 192, 384)
    # 30 heads of 96 are 7.5 groups: the last step's block hangs over
    assert head_norm._blocks(4096, 2880, 30) == (256, 1920, 96, 384)
    assert head_norm._blocks(4000, 4096, 32)[0] == 256
    assert head_norm._blocks(17, 128, 2) == (32, 128, 64, 128)
    assert _pallas.head_group(256) == 256 and _pallas.head_group(12) == 384
    with pytest.raises(ValueError, match="do not divide"):
        head_norm._blocks(64, 100, 3)


def test_it_refuses_what_it_cannot_mean():
    o, gate, scale = operands(1, 8, 2, 64, jnp.float32)
    with pytest.raises(ValueError, match="activation='tanh' is none of"):
        gated_head_norm(o, gate, scale, heads=2, eps=EPS, activation="tanh")
    with pytest.raises(ValueError, match=r"are not \(B, L, D\) twice"):
        gated_head_norm(o, gate[:, :4], scale, heads=2, eps=EPS,
                        activation="silu")
    with pytest.raises(ValueError, match=r"are not \(B, L, D\) twice"):
        gated_head_norm(o, gate, scale[:32], heads=2, eps=EPS,
                        activation="silu")


def test_it_has_no_backward_and_says_so():
    o, gate, scale = operands(1, 8, 2, 64, jnp.float32)
    with pytest.raises(NotImplementedError, match="gated_head_norm has no "
                       "backward"):
        jax.grad(lambda o: gated_head_norm(
            o, gate, scale, heads=2, eps=EPS, activation="silu").sum())(o)

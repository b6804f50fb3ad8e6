"""Every Pallas kernel compiles for the chip, checked without the chip.

The TPU compiler is installed here and compiles for a v5e that is
described, not attached (`jax.experimental.topologies`). Interpret mode —
how the rest of tier-1 runs these kernels — accepts block shapes, shape
casts and VMEM footprints the chip's compiler refuses, so each kernel of
the main path is compiled at the shapes base128 and paper256 produce.
Nothing runs: a pass says the kernel lowers, not that it is right (the
interpret-mode tests and chip_smoke.py's on-chip twins say that).

The test steers `ops/_pallas.use_interpret` itself (code that asks JAX
for its platform still sees the CPU); the program has no option for it.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
# Describing a chip takes libtpu's one-process lockfile although no chip
# is held; another test process doing the same would skip this file.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from novel_view_synthesis_3d_tpu.ops import (
    _pallas,
    expert_combine,
    flash_attention,
    fused_step,
    gdn,
    grouped_matmul,
    head_norm,
    kda,
    short_conv,
    ssm,
)

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def v5e_devices():
    """The four described chips of a v5e:2x2 host; skips where they cannot
    be described. The persistent compile cache is off around these
    compiles: an entry written for a described chip cannot be read back
    without one, and the next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture()
def v5e(v5e_devices):
    return jax.sharding.SingleDeviceSharding(v5e_devices[0])


def _attn(fn, L, hd, grad):
    shape = (2, L, 4, hd)
    if grad:
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(F32))
        return jax.grad(loss, argnums=(0, 1, 2)), [(shape, BF16)] * 3
    return fn, [(shape, BF16)] * 3


def _step(sampler, B, px):
    img = ((B, px, px, 3), F32)
    return (lambda z, ec, eu, nz, coefs, w: fused_step.fused_denoise_step(
        z, ec, eu, nz, coefs, w, sampler=sampler, objective="eps",
        eta=0.5 if sampler == "ddim" else 0.0),
        [img] * 4 + [((B, 11), F32), ((B,), F32)])


def _rect_attn(Lq, Lk, heads, hd):
    """The token trunk's core: one frame's queries against two frames of
    keys, no mask (models/token_denoiser.py)."""
    return (lambda q, k, v: flash_attention.flash_attention(
        q, k, v, scale=0.2),
        [((2, Lq, heads, hd), BF16)] + [((2, Lk, heads, hd), BF16)] * 2)


def _gqa_attn(Lq, Lk, heads, kv_heads, hd, window):
    """The grouped-query trunk's core at the size its cell runs: a frame's
    4096 queries against [cache ; own], 28 query heads on 4 key/value
    heads whose 8192 keys and values sit whole in VMEM (the kernel asks
    for the scoped limit that takes), with the window's banded walk or
    without."""
    return (lambda q, k, v: flash_attention.flash_attention(
        q, k, v, window=window),
        [((1, Lq, heads, hd), BF16)] + [((1, Lk, kv_heads, hd), BF16)] * 2)


def _latent_attn(Lq, Lk, heads, qk, dv):
    """The third token trunk's latent attention at the size its cell runs:
    keys and queries 192 wide (lane-padded to 256) against values of 128,
    a frame's 4096 queries against [cache ; own]."""
    return (lambda q, k, v: flash_attention.flash_attention(
        q, k, v, scale=qk ** -0.5),
        [((1, Lq, heads, qk), BF16), ((1, Lk, heads, qk), BF16),
         ((1, Lk, heads, dv), BF16)])


def _shared_part_attn(rows, Lq, Lk, heads):
    """Latent attention as the third and sixth trunks' cells run it since
    PR 45: heads of 128 lanes of their own beside 64 that all heads share,
    the shared key part ONE (rows, Lk, 64) operand — two products a score,
    nothing padded to 256."""
    return (lambda q, qs, k, ks, v: flash_attention.flash_attention(
        q, k, v, scale=192 ** -0.5, shared=(qs, ks)),
        [((rows, Lq, heads, 128), BF16), ((rows, Lq, heads, 64), BF16),
         ((rows, Lk, heads, 128), BF16), ((rows, Lk, 64), BF16),
         ((rows, Lk, heads, 128), BF16)])


def _grouped(assignments, experts, k, n):
    """The expert layer's grouped product at the published widths: the
    static row count of the worst case (a step's 8192 tokens × top-4, all
    landing here, each of the 32 held experts' spans with a tile's
    remainder), the held experts' stacked weights, and the shipped column
    block — both VMEM slots of a whole expert's (K, N) weights."""
    return (grouped_matmul.grouped_matmul,
            [((grouped_matmul.buffer_rows(assignments, experts), k), BF16),
             ((experts, k, n), BF16), ((experts,), jnp.int32)])


def _combine(tokens, choices, width, experts):
    """The expert layer's combine at a cell's step: the down product's
    static buffer in HBM, a row and a gate a choice, each assignment's
    held expert and the counts the row ranges come from; the token tile
    is the one the shapes choose (its two VMEM slots of chunks, the scoped
    limit the kernel asks for)."""
    return (lambda y, back, w, slot, sizes: expert_combine.combine(
        y, back, w, slot, sizes, BF16),
        [((grouped_matmul.buffer_rows(tokens * choices, experts), width),
          BF16), ((tokens, choices), jnp.int32), ((tokens, choices), F32),
         ((tokens * choices,), jnp.int32), ((experts,), jnp.int32)])


def _kda_scan(rows, L, heads, d):
    """The third token trunk's chunked scan: q, k, v in the compute type
    and g in float32, (B, L, H·d) as the layer's projections leave them,
    β (B, L, H), from a cached state."""
    tok = ((rows, L, heads * d), BF16)
    return (kda.kda_chunked,
            [tok, tok, tok, ((rows, L, heads * d), F32),
             ((rows, L, heads), F32), ((rows, heads, d, d), F32)])


def _gdn_scan(rows, L, heads, dk, dv):
    """The fifth token trunk's chunked scalar-decay scan: q, k, v in the
    compute type, (B, L, H·d) as the layer's projections leave them — keys
    of 96 lanes on values of 192, four heads a grid step sliced out of
    whole lane blocks, the eighth step's blocks over the edge of 30 heads
    —, g and β (B, L, H), from a cached state."""
    return (gdn.gated_delta_chunked,
            [((rows, L, heads * dk), BF16), ((rows, L, heads * dk), BF16),
             ((rows, L, heads * dv), BF16), ((rows, L, heads), F32),
             ((rows, L, heads), F32), ((rows, heads, dk, dv), F32)])


def _diff_attn(Lq, Lk, pairs, kv_pairs, hd, window):
    """One softmax map of the fourth token trunk's differential attention
    at the size its cell runs: a frame's 4096 queries of 20 pairs on 10
    key pairs, keys 64 wide (lane-padded to 128) against a value PAIR of
    128, over [the window's 511-row tail ; own] — no multiple of a key
    block — under the 512 window, or over the 8192-key shared cache."""
    return (lambda q, k, v: flash_attention.flash_attention(
        q, k, v, scale=hd ** -0.5, window=window),
        [((2, Lq, pairs, hd), BF16), ((2, Lk, kv_pairs, hd), BF16),
         ((2, Lk, kv_pairs, 2 * hd), BF16)])


def _ssm_scan(rows, L, channels, states):
    """The fourth token trunk's selective scan: u in the compute type, Δ,
    B, C in float32, (B, L, ·) as the layer's projections leave them, from
    a cached state kept (rows, states, channels)."""
    return (ssm.selective_scan,
            [((rows, L, channels), BF16), ((rows, L, channels), F32),
             ((channels, states), F32), ((rows, L, states), F32),
             ((rows, L, states), F32), ((channels,), F32),
             ((rows, states, channels), F32)])


def _short_conv(rows, L, width, taps, heads=None, bias=False):
    """The short convolution in front of a scan, as its layer calls it: a
    projection a call, (B, L, width) in the compute type with its own taps
    and the K − 1 rows before it, from a cached tail — with `heads`, KDA's
    q (head norm and scale), k (head norm) and v side by side as `kda_fwd`
    takes them; without, Mamba's u with its bias."""
    calls = [(heads, 0.5), (heads, 1.0), (None, 1.0)] if heads \
        else [(None, 1.0)]
    n = len(calls)

    def conv(*args):
        b = args[3 * n] if bias else None
        return [short_conv.short_conv(x, w, t, b, heads=h, scale=c)
                for x, w, t, (h, c) in zip(args[:n], args[n:2 * n],
                                           args[2 * n:3 * n], calls)]

    return (conv,
            [((rows, L, width), BF16)] * n + [((taps, width), BF16)] * n
            + [((rows, taps - 1, width), BF16)] * n
            + ([((width,), BF16)] if bias else []))


def _short_conv_widths(rows, L, taps, calls):
    """The short convolutions of a layer whose projections differ in width:
    `calls` = (width, heads or None, scale) each, a `short_conv_fwd` call a
    projection, from a cached tail."""
    def conv(*args):
        n = len(calls)
        return [short_conv.short_conv(x, w, t, heads=h, scale=c)
                for x, w, t, (_, h, c) in zip(args[:n], args[n:2 * n],
                                              args[2 * n:], calls)]

    return (conv,
            [((rows, L, d), BF16) for d, _, _ in calls]
            + [((taps, d), BF16) for d, _, _ in calls]
            + [((rows, taps - 1, d), BF16) for d, _, _ in calls])


def _head_norm(rows, L, heads, d, activation):
    """What a delta-rule layer does between its scan and `o`: the scan's
    float32 o, the gate's projection in the compute type, the one scale
    the heads share."""
    return (lambda o, gate, scale: head_norm.gated_head_norm(
        o, gate, scale, heads=heads, eps=1e-6, activation=activation),
        [((rows, L, heads * d), F32), ((rows, L, heads * d), BF16),
         ((d,), BF16)])


# base128 attends at 32² tokens / head dim 64 and 16² / 128; paper256 at
# head dim 256, at 32² tokens and (forward, what its cell runs) at 16².
CASES = {
    **{f"flash_{'fwdbwd' if g else 'fwd'}_L{L}_d{hd}":
       _attn(flash_attention.flash_attention, L, hd, g)
       for g in (False, True)
       for L, hd in ((1024, 64), (256, 128), (1024, 256))},
    "flash_fwd_L256_d256": _attn(flash_attention.flash_attention, 256, 256,
                                 False),
    # The token trunk's two shapes: a step's 2048 keys take the forward's
    # blocked form, the once-a-call pass's 1024 the one-block body
    # (test_token_trunk_shapes_compile_in_both_forms).
    "flash_fwd_Lq1024_Lk2048_d128": _rect_attn(1024, 2048, 32, 128),
    "flash_fwd_Lq1024_Lk1024_d128": _rect_attn(1024, 1024, 32, 128),
    "flash_fwd_gqa_Lq4096_Lk8192_d128": _gqa_attn(4096, 8192, 28, 4, 128,
                                                  None),
    "flash_fwd_gqa_window4096_Lq4096_Lk8192_d128": _gqa_attn(
        4096, 8192, 28, 4, 128, 4096),
    "flash_fwd_Lq4096_Lk8192_qk192_v128": _latent_attn(4096, 8192, 32, 192,
                                                        128),
    "flash_fwd_Lq4096_Lk4096_qk192_v128": _latent_attn(4096, 4096, 32, 192,
                                                        128),
    # the third token trunk's experts: 128 held, 16384 tokens x top-8
    "grouped_matmul_up_2304x1024": _grouped(131072, 128, 2304, 1024),
    "grouped_matmul_down_1024x2304": _grouped(131072, 128, 1024, 2304),
    "grouped_matmul_up_4096x2048": _grouped(32768, 32, 4096, 2048),
    # the second token trunk's experts: 64 held, 16384 tokens x top-6
    "grouped_matmul_up_2560x768": _grouped(98304, 64, 2560, 768),
    "grouped_matmul_down_768x2560": _grouped(98304, 64, 768, 2560),
    "grouped_matmul_down_2048x4096": _grouped(32768, 32, 2048, 4096),
    # the combine of the three token cells: (tokens a step, top-k, hidden,
    # experts held)
    "moe_combine_8192x4x4096": _combine(8192, 4, 4096, 32),
    "moe_combine_16384x6x2560": _combine(16384, 6, 2560, 64),
    "moe_combine_16384x8x2304": _combine(16384, 8, 2304, 128),
    # the third token trunk's scan at the size its cell runs, and a ragged
    # length (padded to whole runs of chunks)
    "kda_chunked_4x4096_h32_d128": _kda_scan(4, 4096, 32, 128),
    "kda_chunked_ragged_1x4000_h32_d128": _kda_scan(1, 4000, 32, 128),
    # the fourth token trunk's scan at the size its cell runs (2 rows of
    # 4096 tokens, 5120 channels of 16 states), a ragged length (padded to
    # whole chunks), and one map of its differential attention under the
    # window and on the shared cache
    "ssm_scan_2x4096_c5120_n16": _ssm_scan(2, 4096, 5120, 16),
    "ssm_scan_ragged_1x4000_c5120_n16": _ssm_scan(1, 4000, 5120, 16),
    # the short convolutions in front of both scans at the sizes their
    # cells run — KDA's q, k, v (32 heads of 128 each, q and k normalised
    # by head), Mamba's u with its bias — and a ragged length (padded to
    # whole tiles of rows)
    "kda_short_conv_4x4096x12288_k4": _short_conv(4, 4096, 4096, 4,
                                                  heads=32),
    "kda_short_conv_ragged_1x4000x12288_k4": _short_conv(1, 4000, 4096, 4,
                                                         heads=32),
    "ssm_short_conv_2x4096x5120_k4": _short_conv(2, 4096, 5120, 4,
                                                 bias=True),
    # the fifth token trunk: the short convolutions in front of its delta
    # rule at the size its cell runs — q and k 30 heads of 96 lanes (2880 =
    # 7.5 groups of four heads: the last grid step's block hangs over the
    # edge), v 30 of 192 without a norm — and its full attention, 30 query
    # heads each on its own key/value head
    "gdn_chunked_2x4096_h30_k96_v192": _gdn_scan(2, 4096, 30, 96, 192),
    "gdn_chunked_ragged_1x4000_h30_k96_v192": _gdn_scan(1, 4000, 30, 96,
                                                        192),
    "gdn_short_conv_2x4096x11520_k4": _short_conv_widths(
        2, 4096, 4, [(2880, 30, 96 ** -0.5), (2880, 30, 1.0),
                     (5760, None, 1.0)]),
    "gdn_short_conv_heads192_1x4000x5760_k4": _short_conv_widths(
        1, 4000, 4, [(5760, 30, 1.0)]),
    "flash_fwd_Lq4096_Lk8192_h30_d128": _gqa_attn(4096, 8192, 30, 30, 128,
                                                  None),
    # the gated head-wise norm behind both delta rules at the sizes their
    # cells run — 32 heads of a lane block under the logistic, 30 heads of
    # 192 lanes (two to three lane blocks) under SiLU — and a ragged one:
    # tokens that are no whole run, 30 heads of 96 that fill 7.5 groups
    "head_norm_4x4096x4096_h32": _head_norm(4, 4096, 32, 128, "sigmoid"),
    "head_norm_2x4096x5760_h30": _head_norm(2, 4096, 30, 192, "silu"),
    "head_norm_ragged_1x4000x2880_h30": _head_norm(1, 4000, 30, 96, "silu"),
    # the sixth token trunk: its latent attention at 64 heads (a step's
    # 8192 keys and the once-a-call pass's 4096), its 16 held experts'
    # products over the static buffer of a step's 8192 tokens x top-12,
    # and the combine of twelve choices a token at hidden 6144
    "flash_fwd_Lq4096_Lk8192_h64_qk192_v128": _latent_attn(4096, 8192, 64,
                                                            192, 128),
    "flash_fwd_Lq4096_Lk4096_h64_qk192_v128": _latent_attn(4096, 4096, 64,
                                                            192, 128),
    # both latent cells' attention in the two-operand form they run: a
    # step's 4096 queries a row on 8192 keys, the once-a-call pass's on
    # 4096 (one key block walk, the shared part in VMEM beside K and V)
    "flash_fwd_shared64_2x4096_Lk8192_h64": _shared_part_attn(
        2, 4096, 8192, 64),
    "flash_fwd_shared64_1x4096_Lk4096_h64": _shared_part_attn(
        1, 4096, 4096, 64),
    "flash_fwd_shared64_4x4096_Lk8192_h32": _shared_part_attn(
        4, 4096, 8192, 32),
    "grouped_matmul_up_6144x2048": _grouped(98304, 16, 6144, 2048),
    "grouped_matmul_down_2048x6144": _grouped(98304, 16, 2048, 6144),
    "moe_combine_8192x12x6144": _combine(8192, 12, 6144, 16),
    "flash_fwd_diff_window512_Lq4096_Lk4607_qk64_v128": _diff_attn(
        4096, 4607, 20, 10, 64, 512),
    "flash_fwd_diff_Lq4096_Lk8192_qk64_v128": _diff_attn(
        4096, 8192, 20, 10, 64, None),
    **{f"fused_step_{s}_B{B}_{px}px": _step(s, B, px)
       for s in ("ddpm", "ddim") for B in (1, 2, 16) for px in (128, 256)},
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, v5e, monkeypatch):
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    fn, arg_specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
            for shape, dtype in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{name}: compiled without a Pallas kernel in it")


# The name each `pl.pallas_call` passes: a kernel is then an instruction
# of that name in a compiled program and in a profiler capture, where an
# unnamed one is told from the next only by its number.
KERNEL_NAMES = {
    "flash_fwd": "flash_fwdbwd_L256_d128",
    "flash_dq": "flash_fwdbwd_L256_d128",
    "flash_dkv": "flash_fwdbwd_L256_d128",
    "fused_step": "fused_step_ddpm_B2_128px",
    "gmm": "grouped_matmul_up_4096x2048",
    "kda_fwd": "kda_chunked_ragged_1x4000_h32_d128",
    "gdn_fwd": "gdn_chunked_ragged_1x4000_h30_k96_v192",
    "ssm_fwd": "ssm_scan_ragged_1x4000_c5120_n16",
    "short_conv_fwd": "ssm_short_conv_2x4096x5120_k4",
    "head_norm_fwd": "head_norm_ragged_1x4000x2880_h30",
    "moe_combine": "moe_combine_8192x4x4096",
}


def _lowered(case, sharding):
    fn, arg_specs = CASES[case]
    return jax.jit(fn).lower(*[
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in arg_specs])


@pytest.mark.parametrize("kernel", sorted(KERNEL_NAMES))
def test_kernel_lowers_under_its_name(kernel, v5e, monkeypatch):
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    text = _lowered(KERNEL_NAMES[kernel], v5e).as_text()
    assert f'kernel_name = "{kernel}"' in text


def test_flash_kernels_are_distinct_instructions(v5e, monkeypatch):
    """Forward and both backward kernels of one attention, compiled: three
    custom calls, each named after its kernel (under a transform the name
    is wrapped, `transpose_jvp_flash_dq__`)."""
    import re

    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    text = _lowered("flash_fwdbwd_L256_d128", v5e).compile().as_text()
    calls = re.findall(r"^\s*%(\S+) = .* custom-call\(", text, re.M)
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert sum(kernel in c for c in calls) == 1, (kernel, calls)
    assert len(set(calls)) == len(calls) == 3


def test_held_expert_part_compiles_to_four_kernels_and_no_gather(
        v5e, monkeypatch):
    """The expert layer compiled for the chip: the three grouped products
    and the combine are its four custom calls, and under `lk.moe_experts`
    no XLA `gather` is left (the one under `lk.moe_route` is the dispatch
    into expert order)."""
    import re
    from types import SimpleNamespace

    from novel_view_synthesis_3d_tpu.models import token_denoiser

    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    T, K, H, F, held = 2048, 4, 1024, 512, 8
    k = SimpleNamespace(held_experts=(0, held), expert_activation="silu")

    def spec(*shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    experts = {"gate": {"kernel": spec(held, H, F)},
               "up": {"kernel": spec(held, H, F)},
               "down": {"kernel": spec(held, F, H)}}
    text = jax.jit(lambda b, p, i, w: token_denoiser.held_expert_part(
        b, p, i, w, k)).lower(
        spec(T, H), spec(T, K, dtype=F32), spec(T, K, dtype=jnp.int32),
        experts).compile().as_text()
    calls = re.findall(r"^\s*%(\S+) = .* custom-call\(.*"
                       r'custom_call_target="tpu_custom_call"', text, re.M)
    assert sorted(c.split(".")[0] for c in calls) == [
        "gmm", "gmm", "gmm", "moe_combine"], calls
    gathers = re.findall(r"^.* gather\(.*$", text, re.M)
    assert gathers and not [g for g in gathers if "lk.moe_experts" in g]
    assert all("lk.moe_route/pt.gather" in g for g in gathers
               if "op_name" in g)


def test_token_trunk_shapes_compile_in_both_forms(v5e, monkeypatch):
    """The two `flash_fwd_Lq1024_*` cases are the forward's two forms
    (`forward_blocks`), each compiled above with the blocks it ships with
    and inside the scoped VMEM the kernel asks for (the compiler refuses a
    kernel that needs more); a sampler's call writes no lse, so its custom
    call has the one output — (B, Lq, H·D), the heads side by side as the
    caller's `o` projection reads them."""
    import re

    assert flash_attention.forward_blocks(1024, 2048, 128, 2)[2] > 1
    assert flash_attention.forward_blocks(1024, 1024, 128, 2)[2] == 1
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    for case in ("flash_fwd_Lq1024_Lk2048_d128",
                 "flash_fwd_Lq1024_Lk1024_d128"):
        text = _lowered(case, v5e).compile().as_text()
        (call,) = re.findall(r"^\s*%flash_fwd\S* = (.*) custom-call\(",
                             text, re.M)
        assert call.startswith("bf16[2,1024,4096]"), (case, call)


def test_flash_compiles_under_a_four_chip_data_mesh(v5e_devices,
                                                    monkeypatch):
    """GSPMD refuses to partition a Mosaic kernel, so a batch-sharded
    program with the bare kernel in it does not lower on a multi-chip
    mesh; through `over_data_axis` (how models/layers.AttnLayer calls it
    when the model holds a mesh) it compiles, with no collective added."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from novel_view_synthesis_3d_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    mesh = make_mesh(devices=v5e_devices)
    assert dict(mesh.shape) == {"data": 4, "model": 1, "seq": 1}
    qkv = [jax.ShapeDtypeStruct((8, 1024, 4, 64), BF16,
                                sharding=NamedSharding(mesh, P("data")))] * 3

    def grads_of(attn):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(attn(q, k, v).astype(F32)),
            argnums=(0, 1, 2)))

    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        grads_of(flash_attention.flash_attention).lower(*qkv)
    text = grads_of(_pallas.over_data_axis(
        flash_attention.flash_attention, mesh)).lower(*qkv).compile(
        ).as_text()
    assert text.count("tpu_custom_call") == 3  # fwd, dq, dk/dv
    assert "all-gather(" not in text and "all-reduce(" not in text


_HLO_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}


def _entry_writes(text):
    """(opcode, layer kind, `op_name`, bytes written) of each top-level
    instruction of a compiled program's entry; the kind is the one its
    `op_name` is stamped with (models/xunet.layer_of). What a fusion
    keeps inside is not written."""
    import re

    from novel_view_synthesis_3d_tpu.models.xunet import layer_of

    for line in text[text.index("ENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if not (m and name) or m.group(2) in (
                "parameter", "get-tuple-element", "tuple", "constant",
                "bitcast"):
            continue
        size = sum(
            _HLO_BYTES[dt] * int(np.prod([int(d) for d in dims.split(",")]))
            for dt, dims in re.findall(r"\b(bf16|f32|s32|u32|pred)\[([\d,]+)\]",
                                       m.group(1)))
        yield m.group(2), layer_of(name.group(1))[1], name.group(1), size


def _bytes_written_by_kind(text):
    """Bytes the entry's top-level instructions write, summed by kind."""
    written = {}
    for _, kind, _, size in _entry_writes(text):
        written[kind] = written.get(kind, 0) + size
    return written


def test_film_pair_buys_no_pass_over_h_on_v5e(v5e):
    """A ResnetBlock at paper256's level-0 shape, compiled for the chip
    with a guidance pair's embedding (conditional rows at full extent,
    unconditional rows at 1 × 1) and with the same rows at full extent.
    With the pair, what is written under `lk.emb` is the conditional
    rows' projection, once as the matmul writes it (W in the sublanes)
    and once re-laid to the convolutions' tiling (the rows in the
    sublanes; `copy_add_fusion`, PERF.md §7), and nothing else of its
    size — no (scale, shift) at full extent for every row, no copy, slice
    or concatenate of `h` — and the norms and convolutions write what
    they write at full extent. It
    holds only while XLA:TPU fuses FiLM's pad → split → sum into the
    modulation's one pass over `h` (models/layers.FiLM says which other
    orders cost which passes); the chip would show a loss as `gn` time."""
    from novel_view_synthesis_3d_tpu.models.layers import ResnetBlock

    n, F, side, C, E = 2, 2, 256, 256, 1024

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=v5e)

    block = ResnetBlock(dtype=BF16)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e),
        jax.eval_shape(lambda: block.init(
            jax.random.PRNGKey(0), jnp.zeros((2 * n * F, 8, 8, C), BF16),
            jnp.zeros((2 * n * F, 8, 8, E), BF16), train=False)))
    h = S(2 * n * F, side, side, C)
    written = {
        name: _bytes_written_by_kind(jax.jit(
            lambda p, h, e: block.apply(p, h, e, train=False)
        ).lower(params, h, emb).compile().as_text())
        for name, emb in (("pair", (S(n * F, side, side, E),
                                    S(n * F, 1, 1, E))),
                          ("full", S(2 * n * F, side, side, E)))}
    cond_projection = n * F * side * side * 2 * C * 2  # bf16 (scale, shift)
    assert written["full"]["emb"] >= 4 * cond_projection, written
    assert written["pair"]["emb"] <= 2.02 * cond_projection, written
    for kind in written["full"]:
        if kind != "emb":
            assert written["pair"].get(kind, 0) <= 1.01 * written["full"][
                kind], (kind, written)
    assert set(written["pair"]) <= set(written["full"]), written


# Every distinct ResnetBlock shape of paper256 (1024-wide embedding) and
# base128's at 128 and 64 px (512-wide): the side the block's input has,
# the channels of `h` and of the skip it is concatenated with on the up
# path (0: no concatenation), the block's features, its resampling — and,
# as found on the tree this table was read off (PR 43's parent; PERF.md §7
# row 17), the bytes the block writes and those of them under `lk.gn`, in
# units of the block's output.
RESNET_SHAPES = {
    "paper256": (1024, [
        (256, 256, 0, 256, None, 4.01, 0),
        (256, 256, 0, 256, "down", 23.01, 0),
        (256, 256, 256, 256, None, 5.01, 0),
        (256, 512, 256, 256, None, 5.01, 0),
        (128, 256, 0, 512, None, 4.01, 0),
        (128, 512, 0, 512, None, 4.01, 0),
        (128, 512, 0, 512, "down", 24.01, 16),
        (128, 512, 0, 512, "up", 6.26, 0.25),
        (128, 512, 256, 512, None, 5.01, 0),
        (128, 512, 512, 512, None, 5.01, 0),
        (64, 512, 0, 512, None, 4.01, 0),
        (64, 512, 0, 512, "down", 24.04, 16),
        (64, 512, 0, 512, "up", 6.26, 0.25),
        (64, 512, 512, 512, None, 5.02, 0),
        (64, 1024, 512, 512, None, 5.02, 0),
        (32, 512, 0, 1024, None, 5.03, 0),
        (32, 1024, 0, 1024, None, 4.04, 0),
        (32, 1024, 0, 1024, "down", 24.15, 16),
        (32, 1024, 0, 1024, "up", 6.26, 0.25),
        (32, 1024, 512, 1024, None, 5.05, 0),
        (32, 1024, 1024, 1024, None, 5.06, 0),
        (16, 1024, 0, 1024, None, 4.15, 0),
        (16, 1024, 0, 1024, "up", 6.29, 0.25),
        (16, 1024, 1024, 1024, None, 5.24, 0),
    ]),
    "base128": (512, [
        (128, 128, 0, 128, None, 4.01, 0),
        (128, 128, 0, 128, "down", 24.02, 16),
        (128, 128, 128, 128, None, 5.01, 0),
        (128, 256, 128, 128, None, 4.01, 0),
        (64, 128, 0, 256, None, 4.01, 0),
        (64, 256, 0, 256, None, 4.01, 0),
        (64, 256, 0, 256, "down", 24.04, 16),
        (64, 256, 0, 256, "up", 5.26, 0.25),
        (64, 256, 128, 256, None, 5.02, 0),
        (64, 256, 256, 256, None, 5.02, 0),
    ]),
}
RESNET_CASES = {
    f"{preset}-{px}px-{c_h + c_skip}to{c_out}" + (f"-{resample}"
                                                   if resample else ""):
    (emb_ch, px, c_h, c_skip, c_out, resample, passes, gn_passes)
    for preset, (emb_ch, shapes) in RESNET_SHAPES.items()
    for px, c_h, c_skip, c_out, resample, passes, gn_passes in shapes}


@pytest.mark.parametrize("case", list(RESNET_CASES))
def test_resnet_blocks_keep_the_convolutions_layout_on_v5e(case, v5e):
    """One ResnetBlock between a stem and a head convolution at a shape
    its preset runs (8 rows: 2 views × 2 guidance halves × 2 frames, a
    guidance pair's embedding), compiled for the chip; where the block
    sits on the up path its input IS the channel concatenation with a
    skip.

    The network carries (B·F, H, W, C), so XLA:TPU keeps the
    convolutions' layout (`{3,0,2,1:T(8,128)}`: the rows in the sublanes)
    through a block: no `copy`, `reshape` or `transpose` of `h`'s size
    under any stamp, none for the concatenation. Between two convolutions
    the norm has no pass of its own — nothing under `lk.gn` writes an
    array of `h`'s size: the statistics fuse into the convolution before,
    the apply, the swishes, FiLM's modulation and the residual sum into
    the one after — and a block writes 4 passes of `h`: its two
    convolutions, the conditional rows' FiLM projection and that
    projection's relayout; a fifth, the 1 × 1 skip projection, where the
    channels change (not everywhere: the table has the count a shape was
    found with). That is what the Pallas norm kernels PR 43 deleted were
    for, at every level and not only the two their VMEM guard admitted.
    While `h` was (B, F, H, W, C) it was 6 and 11 passes a block
    (PERF.md §6, PR 31).

    Pinned as found, and open (PERF.md §7 row 17): a `down` block writes
    its normed, activated input and the skip's in float32 before the
    average pool's reduction (16 outputs' worth, then 2 × 2 more for the
    pooled float32 sums), an `up` block writes both nearest-neighbour
    broadcasts and gives the swish in front of them a pass at the input's
    size."""
    import flax.linen as nn

    from novel_view_synthesis_3d_tpu.models.layers import (
        FrameConv, ResnetBlock)

    emb_ch, px, c_h, c_skip, c_out, resample, passes, gn_passes = (
        RESNET_CASES[case])
    rows, cond_rows = 8, 4
    out_px = {"up": 2 * px, "down": px // 2, None: px}[resample]

    class Chain(nn.Module):
        @nn.compact
        def __call__(self, x, emb):
            h = FrameConv(c_h, dtype=BF16)(x)
            if c_skip:
                h = jnp.concatenate(
                    [h, FrameConv(c_skip, dtype=BF16)(x)], axis=-1)
            h = ResnetBlock(features=c_out, resample=resample,
                            dtype=BF16)(h, emb, train=False)
            return FrameConv(3, dtype=BF16)(h)

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, BF16, sharding=v5e)

    chain = Chain()
    toy = 8 * out_px // px
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e),
        jax.eval_shape(lambda: chain.init(
            jax.random.PRNGKey(0), jnp.zeros((rows, 8, 8, c_h), BF16),
            jnp.zeros((rows, toy, toy, emb_ch), BF16))))
    emb = (S(cond_rows, out_px, out_px, emb_ch),
           S(rows - cond_rows, 1, 1, emb_ch))
    text = jax.jit(chain.apply).lower(
        params, S(rows, px, px, c_h), emb).compile().as_text()
    h_bytes = rows * out_px * out_px * c_out * 2
    # an array of `h`'s size: half the smaller of the block's two ends
    sized = min(h_bytes, rows * px * px * (c_h + c_skip) * 2) // 2
    writes = list(_entry_writes(text))
    moved = [(op, kind, name) for op, kind, name, size in writes
             if op in ("copy", "reshape", "transpose") and size >= sized]
    assert not moved, moved
    under_gn = [(op, name, size / h_bytes) for op, kind, name, size in writes
                if kind == "gn" and size >= sized]
    assert sum(s for _, _, s in under_gn) <= gn_passes * 1.01, under_gn
    in_block = sum(size for _, _, name, size in writes
                   if "ResnetBlock_" in name or "concatenate" in name)
    assert in_block <= passes * h_bytes * 1.01, in_block / h_bytes


# The third and fourth token trunks' sequence operators at the shapes
# their cells run, among the kernels' CASES above: the scans (`kda_fwd`,
# `ssm_fwd`), entered from a cached state, and the short convolutions in
# front of them (`short_conv_fwd`). All must FIT beside 7.8 GB of weights.
# Temporaries the compiled program may take. The kernel keeps a chunk's
# working set in VMEM: at whole runs of chunks it needs NO buffer in HBM
# (2 MB: β re-tiled); a ragged length pays the padded copies of its five
# operands and the slice of o, 0.1 GB a row — where the XLA scan took a
# row's float32 working set, 1.1 GB.
TEMP_LIMITS = {
    "kda_chunked_4x4096_h32_d128": 4e6,
    "kda_chunked_ragged_1x4000_h32_d128": 0.12e9,
    # The short convolution keeps a run's float32 rows in VMEM and takes x
    # and y where they lie: its only buffers in HBM are the tails' eight
    # float32 rows a call (1.6 MB for q, k and v) — where the XLA form
    # wrote [tail ; x] with the token axis minor and the float32 result,
    # 2.5 GB —; a ragged length pays the padded x and the slice of y.
    "kda_short_conv_4x4096x12288_k4": 4e6,
    "kda_short_conv_ragged_1x4000x12288_k4": 0.12e9,
    "ssm_short_conv_2x4096x5120_k4": 4e6,
    # The selective scan keeps its state in VMEM and takes u, Δ and m where
    # they lie: at whole chunks its only buffers in HBM are Bᵀ, Cᵀ and Aᵀ
    # (1.4 MB); a ragged length pays the padded copies of u and Δ and the
    # slice of m, 0.21 GB a row — where an `associative_scan` would write
    # (L, 5120, 16) float32, 1.34 GB a row, several times.
    "ssm_scan_2x4096_c5120_n16": 4e6,
    "ssm_scan_ragged_1x4000_c5120_n16": 0.25e9,
    # heads of 96 and 192 lanes are packed into lane blocks INSIDE the
    # kernel: nothing is padded or re-laid in HBM for them (the tails'
    # eight float32 rows again); a ragged length pays the padded x and the
    # slice of y
    # The scalar-decay scan keeps a run's working set in VMEM too: at whole
    # runs its only buffers in HBM are γ's running sum and the casts (a
    # few MB); a ragged length pays the padded operands and the slice of o.
    "gdn_chunked_2x4096_h30_k96_v192": 8e6,
    "gdn_chunked_ragged_1x4000_h30_k96_v192": 0.12e9,
    "gdn_short_conv_2x4096x11520_k4": 4e6,
    "gdn_short_conv_heads192_1x4000x5760_k4": 0.1e9,
    # The gated head norm takes o, the gate and its result where they lie,
    # whole runs or not (an edge block is masked, not padded): its only
    # buffer in HBM is the scale laid a step's lanes wide (8 KB) — no
    # float32 array of the operand's size, where the 4-D form wrote three.
    "head_norm_4x4096x4096_h32": 1e6,
    "head_norm_2x4096x5760_h30": 1e6,
    "head_norm_ragged_1x4000x2880_h30": 1e6,
}


@pytest.mark.parametrize("name", sorted(TEMP_LIMITS))
def test_kda_compiles_and_fits_for_v5e(name, v5e, monkeypatch):
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    fn, arg_specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
            for shape, dtype in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < TEMP_LIMITS[name], mem.temp_size_in_bytes


def _kernel_products(text):
    """The operand types of every `tpu.matmul` in the one Mosaic kernel of
    a lowered program, read off the kernel's own module (the custom call's
    serialized body)."""
    import base64
    import json

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    (config,) = re.findall(r'backend_config = "([^"]*)"', text)
    body = json.loads(re.sub(
        r"\\([0-9A-Fa-f]{2})", lambda m: chr(int(m.group(1), 16)),
        config))["custom_call_config"]["body"]
    with jax_mlir.make_ir_context() as ctx:
        ctx.allow_unregistered_dialects = True
        asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)
    return re.findall(r"tpu\.matmul.* : \(vector<[0-9x]+x(\w+)>, "
                      r"vector<[0-9x]+x(\w+)>, ", asm)


def test_gdn_products_take_the_passes_their_operands_leave(v5e, monkeypatch):
    """`gdn_fwd` at its cell's shape, lowered for the described v5e: which
    products run on bfloat16 operands is decided by dtype when the kernel
    is traced, so its module is where it shows. A step's kernel holds the
    walk of four heads and, for the last group, of the two that exist: 6
    heads × (2 pairs of chunks × [both; two merge levels of 2 products]
    + 4 chunks × [kqs, U, the state's, O's]) = 156 products. With bfloat16
    q, k, v the 12 `both`, the 24 `kqs` and the 24 state products take
    bfloat16 operands (one pass; three stacked along the contraction), the
    48 merge products — each on the 64 rows of 128 its level changes — and
    the 48 on T and A_qk keep two float32 operands (six passes); with
    float32 q, k, v none takes bfloat16."""
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    fn, arg_specs = CASES["gdn_chunked_2x4096_h30_k96_v192"]

    def products(dtype):
        return _kernel_products(jax.jit(fn).lower(*[
            jax.ShapeDtypeStruct(shape, dtype if was == BF16 else was,
                                 sharding=v5e)
            for shape, was in arg_specs]).as_text())

    narrow = products(BF16)
    assert len(narrow) == 156
    assert narrow.count(("bf16", "bf16")) == 60
    assert narrow.count(("f32", "f32")) == 96
    assert products(F32) == [("f32", "f32")] * 156


def test_lcf_sampler_fits_the_described_v5e(v5e, monkeypatch):
    """`make_sampler` of `lcf_denoiser256` at its cell's size (1 view, 8
    steps, guidance 3) compiled for the described chip: the arguments are
    the program's own tree (5.06 B bfloat16 parameters, 10.12 GB), the
    temporaries — the derived kernels of both attentions of every layer,
    the expert buffer of a step's 98 304 choices at hidden 6144 — stay
    under 4 GB, and all of it inside the chip's 16 GB."""
    from novel_view_synthesis_3d_tpu.config import get_preset
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.models import build_denoiser
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler

    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    cfg = get_preset("lcf_denoiser256").override(**{
        "diffusion.sample_timesteps": 8, "diffusion.guidance_weight": 3.0,
        "diffusion.sampler": "ddpm"}).validate()
    model = build_denoiser(cfg.model)
    side = cfg.data.img_sidelength

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, F32, sharding=v5e)

    cond = {"x": spec(1, side, side, 3), "R1": spec(1, 3, 3),
            "t1": spec(1, 3), "R2": spec(1, 3, 3), "t2": spec(1, 3),
            "K": spec(1, 3, 3)}
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e),
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0)})["params"]))
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, 8),
                           cfg.diffusion, trajectory_every=1)
    compiled = jax.jit(sampler).lower(
        params, jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e),
        cond).compile()
    mem = compiled.memory_analysis()
    assert 10.12e9 < mem.argument_size_in_bytes < 10.13e9
    assert mem.temp_size_in_bytes < 4.0e9, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes < 15.0e9
    text = compiled.as_text()
    # eight latent attentions a step and seven in the once-a-call pass,
    # each one kernel; three grouped products and a combine a branch
    for name, least in (("flash_fwd", 2), ("gmm", 3), ("moe_combine", 1)):
        assert len(re.findall(r"^\s*%%%s\S* = " % name, text, re.M)) \
            >= least, name


def test_lgs_sampler_fits_the_described_v5e(v5e, monkeypatch):
    """`make_sampler` of `lgs_denoiser256` at its cell's size (1 view, 16
    steps, guidance 3) compiled for the described chip: the arguments are
    the program's own tree (5.29 B bfloat16 parameters, 10.58 GB), the
    temporaries — the expert buffer of a step's 81 920 choices at hidden
    3072, a window layer's float32 queries of 72 heads — stay under 2 GB,
    and all of it inside the chip's 16 GB; grouped heads of both counts
    and the band compile as `flash_fwd`."""
    from novel_view_synthesis_3d_tpu.config import get_preset
    from novel_view_synthesis_3d_tpu.diffusion.schedules import (
        sampling_schedule)
    from novel_view_synthesis_3d_tpu.models import build_denoiser
    from novel_view_synthesis_3d_tpu.sample.ddpm import make_sampler

    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    cfg = get_preset("lgs_denoiser256").override(**{
        "diffusion.sample_timesteps": 16, "diffusion.guidance_weight": 3.0,
        "diffusion.sampler": "ddpm"}).validate()
    model = build_denoiser(cfg.model)
    side = cfg.data.img_sidelength

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, F32, sharding=v5e)

    cond = {"x": spec(1, side, side, 3), "R1": spec(1, 3, 3),
            "t1": spec(1, 3), "R2": spec(1, 3, 3), "t2": spec(1, 3),
            "K": spec(1, 3, 3)}
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e),
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0)})["params"]))
    sampler = make_sampler(model, sampling_schedule(cfg.diffusion, 16),
                           cfg.diffusion, trajectory_every=1)
    compiled = jax.jit(sampler).lower(
        params, jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e),
        cond).compile()
    mem = compiled.memory_analysis()
    assert 10.58e9 < mem.argument_size_in_bytes < 10.59e9
    assert mem.temp_size_in_bytes < 2.0e9, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes < 15.0e9
    text = compiled.as_text()
    # a step: 2 full layers one kernel each, 3 window layers four (one a
    # query block of 1024); the once-a-call pass the same less the last
    # layer's; three grouped products and a combine an expert layer
    for name, least in (("flash_fwd", 2 + 12 + 1 + 12), ("gmm", 3),
                        ("moe_combine", 1)):
        assert len(re.findall(r"^\s*%%%s\S* = " % name, text, re.M)) \
            >= least, name


_LAYER_TEXTS = {}   # a preset's delta-rule layer is compiled once a run


def _delta_rule_layer(v5e, preset, rows, state, tail):
    """The compiled text of layer 0 of `preset`, a delta-rule layer, over
    `rows` rows of 4096 tokens from a cached (state float32, convolution
    tail bfloat16) of the shapes `state` and `tail` behind the rows."""
    from novel_view_synthesis_3d_tpu.config import get_preset
    from novel_view_synthesis_3d_tpu.models import build_denoiser

    if preset in _LAYER_TEXTS:
        return _LAYER_TEXTS[preset]
    cfg = get_preset(preset)
    model, k = build_denoiser(cfg.model), cfg.model.tokens
    i, L = 0, 4096
    assert not k.is_full_attention(i)

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    params = jax.tree.map(
        lambda a: S(a.shape, a.dtype),
        jax.eval_shape(lambda: model.init(
            {"params": jax.random.PRNGKey(0)}))["params"][f"layer_{i}"])
    text = jax.jit(lambda p, h, c: model.layer(i, p, h, None, c)[:2]).lower(
        params, S((rows, L, k.hidden_size), BF16),
        (S((rows,) + state, F32), S((rows,) + tail, BF16))).compile(
        ).as_text()
    _LAYER_TEXTS[preset] = text
    return text


def _kl48_kda_layer(v5e):
    """(the compiled text of a KDA layer of `kl48_denoiser256` at the cell's
    shape — 4 rows of 4096 tokens from a cached state —, q's bytes, rows,
    width, head width)."""
    from novel_view_synthesis_3d_tpu.config import get_preset

    lin = get_preset("kl48_denoiser256").model.tokens.linear_attn_config
    rows, width = 4, lin.num_heads * lin.head_dim
    text = _delta_rule_layer(
        v5e, "kl48_denoiser256", rows,
        (lin.num_heads, lin.head_dim, lin.head_dim),
        (lin.short_conv_kernel_size - 1, 3 * width))
    return text, rows * 4096 * width * 2, rows, width, lin.head_dim


def _oh7_gdn_layer(v5e):
    """(the compiled text of a Gated DeltaNet layer of `oh7_denoiser256` at
    the cell's shape — 2 rows of 4096 tokens from a cached state —, the
    bytes of the scan's o in float32, heads, lanes a head)."""
    from novel_view_synthesis_3d_tpu.config import get_preset

    k = get_preset("oh7_denoiser256").model.tokens
    rows, NH = 2, k.linear_num_value_heads
    dk, dv = k.linear_key_head_dim, k.linear_value_head_dim
    text = _delta_rule_layer(
        v5e, "oh7_denoiser256", rows, (NH, dk, dv),
        (k.linear_conv_kernel_dim - 1, NH * (2 * dk + dv)))
    return text, rows * 4096 * NH * dv * 4, NH, dv


def test_kl48_layer_hands_the_scan_its_operands_where_they_lie(v5e,
                                                               monkeypatch):
    """A KDA layer of `kl48_denoiser256` at the cell's shape (4 rows of
    4096 tokens from a cached state), compiled for the chip: under
    `lk.kda_core` there is the kernel and nothing else of q's size — no
    `copy`, `transpose` or `reshape` re-lays q, k, v, g or o for it (a head
    is a 128-lane block of the (B, L, H·128) arrays the layer already
    has), and the kernel writes o in float32 and the states."""
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    text, q_bytes, rows, width, head_dim = _kl48_kda_layer(v5e)
    core = [(op, name, size) for op, kind, name, size in _entry_writes(text)
            if kind == "kda_core"]
    assert [op for op, _, size in core if size >= q_bytes // 2] == [
        "custom-call"], core
    assert not [c for c in core if c[0] in ("copy", "transpose", "reshape")
                and c[2] >= q_bytes // 64], core
    (call,) = [c for c in core if c[0] == "custom-call"]
    assert "kda_fwd" in call[1]
    assert call[2] == 2 * q_bytes + rows * width * head_dim * 4


def test_kl48_layer_convolves_the_projections_where_they_lie(v5e,
                                                             monkeypatch):
    """The same layer's `lk.kda_conv`: the only writes of q's size are the
    three `short_conv_fwd` calls', q, k and v in bfloat16 — 3 · q's bytes
    in all —; no `copy`, `transpose` or `reshape` re-lays a projection for
    them (a call reads a (B, L, H·128) projection as the matmul left it),
    and no float32 array of (B, L, width) is written anywhere under the
    stamp: the taps' sum, SiLU and the norms stay in VMEM."""
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    text, q_bytes, _, _, _ = _kl48_kda_layer(v5e)
    conv = [(op, name, size) for op, kind, name, size in _entry_writes(text)
            if kind == "kda_conv"]
    large = [c for c in conv if c[2] >= q_bytes // 2]
    assert [op for op, _, _ in large] == ["custom-call"] * 3, conv
    assert all("short_conv_fwd" in name for _, name, _ in large), large
    assert sum(size for _, _, size in large) == 3 * q_bytes
    assert not [c for c in conv if c[0] in ("copy", "transpose", "reshape")
                and c[2] >= q_bytes // 64], conv
    # nothing else under the stamp is even a sixty-fourth of a float32
    # (B, L, width): the tails' rows are all there is
    assert max(size for op, _, size in conv if op != "custom-call") \
        < 2 * q_bytes // 64, conv


def _between_the_scan_and_o(text, kind, o_bytes, heads, d):
    """What a delta-rule layer compiled for the chip holds between its
    scan's kernel and `o`'s product: ONE `head_norm_fwd` call under the
    layer's `proj` stamp, which writes o's elements once in the compute
    type; no float32 array seen as (…, heads, d) anywhere in the program,
    under a stamp or under none (the 4-D view of the norm was a `copy`, a
    `reshape`, a `broadcast` and a `mul` of o's size each); and under the
    stamp no `reshape`, `broadcast` or `transpose` of even a sixty-fourth
    of o. → the stamp's writes, for what a caller holds them to besides."""
    import re

    writes = [(op, name, size) for op, k, name, size in _entry_writes(text)
              if k == kind]
    calls = [w for w in writes if w[0] == "custom-call"]
    assert [("head_norm_fwd" in name, size) for _, name, size in calls] == [
        (True, o_bytes // 2)], calls
    assert calls[0][1].endswith("pt.kernel/head_norm_fwd/pallas_call")
    assert not re.findall(rf"f32\[[\d,]+,{heads},{d}\]", text)
    moved = [w for w in writes if w[2] >= o_bytes // 64 and w[0] in (
        "reshape", "broadcast", "transpose")]
    assert not moved, moved
    return writes


def test_kl48_layer_norms_the_scans_output_where_it_lies(v5e, monkeypatch):
    """The same layer's way from `kda_fwd` to `o` (ops/head_norm.py): the
    kernel's float32 o (B, L, 32·128) goes to `head_norm_fwd` as it lies
    and the gate's projection in bfloat16 as its product left it — no
    float32 gate, no `copy` at all under the stamp."""
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    text, q_bytes, _, _, head_dim = _kl48_kda_layer(v5e)
    writes = _between_the_scan_and_o(text, "kda_proj", 2 * q_bytes, 32,
                                     head_dim)
    assert not [w for w in writes if w[0] == "copy"
                and w[2] >= q_bytes // 32], writes
    # the one float32 array of o's size under the stamp is the decay's
    # (per head AND per channel: `kda_fwd`'s operand g)
    assert [op for op, _, size in writes if size == 2 * q_bytes] == [
        "fusion"], writes


def test_oh7_layer_norms_the_scans_output_where_it_lies(v5e, monkeypatch):
    """A Gated DeltaNet layer of `oh7_denoiser256` at the cell's shape: from
    `gdn_fwd` to `o` the float32 o (B, L, 30·192) goes to `head_norm_fwd` as
    it lies — two heads to three lane blocks INSIDE the kernel, where the
    4-D view padded a head to 256 lanes — and nothing of o's float32 size
    is written under the stamp (the decay is a number a head here)."""
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    text, o_bytes, heads, d = _oh7_gdn_layer(v5e)
    writes = _between_the_scan_and_o(text, "gdn_proj", o_bytes, heads, d)
    assert max(size for _, _, size in writes) <= o_bytes // 2, writes

"""flops_tokens_gdn.py against a hand count at the cell's shapes."""
import pytest

import flops_tokens_gdn as ft

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
M = {"hidden_size": 3840, "num_hidden_layers": 16, "num_attention_heads": 30,
     "num_key_value_heads": 30, "intermediate_size": 11008,
     "rms_norm_eps": 1e-6, "layer_types": PERIOD * 8,
     "linear_num_key_heads": 30, "linear_num_value_heads": 30,
     "linear_key_head_dim": 96, "linear_value_head_dim": 192,
     "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
     "patch_size": 4, "side": 256}
L = 4096


def test_layers_by_kind():
    assert ft.tokens_per_frame(M) == L and ft.head_dim(M) == 128
    assert ft.layers_of(M, True) == [3, 7, 11, 15]
    assert len(ft.layers_of(M, False)) == 12
    assert ft.cut_short(M, 15, 1) and not ft.cut_short(M, 15, 2)
    assert not ft.cut_short(M, 11, 1)


def test_sizes_by_hand():
    assert ft.gdn_widths(M) == (30, 96, 192, 4)
    # q, k 3840·2880; v, the gate, o 3840·5760; a, b 3840·30: ISSUE 40's
    # 88.75 M less the convolutions' 46 080 and the three small vectors
    assert ft.gdn_proj_params(M) == 2 * 11059200 + 3 * 22118400 + 2 * 115200
    assert ft.gdn_conv_flops(M, L) == 2 * L * 11520 * 4
    assert ft.attn_proj_params(M) == 4 * 3840 * 3840 == 58982400
    assert ft.attn_proj_params(M, cache_only=True) == 2 * 3840 * 3840
    assert ft.dense_mlp_params(M) == 3 * 3840 * 11008 == 126812160


def test_the_scan_is_counted_in_its_chunked_form_in_one_pass():
    """A chunk of 64 tokens of a head: three products with the (96, 192)
    state, two triangles 96 deep, two 192 deep."""
    macs = 3 * 64 * 96 * 192 + 2 * 2048 * 96 + 2 * 2048 * 192
    assert ft.gdn_core_flops(M, 64) == 2 * 30 * macs
    assert ft.gdn_core_flops(M, L) == 64 * ft.gdn_core_flops(M, 64)
    assert ft.gdn_core_flops(M, 65) == 2 * ft.gdn_core_flops(M, 64)
    # 0.053 GFLOP a token over all twelve layers: ISSUE 40's "~0.05"
    assert 12 * ft.gdn_core_flops(M, L) / L == pytest.approx(0.0531e9,
                                                             rel=0.01)
    assert ft.gdn_core_bytes(M, L) == 30 * (
        L * (2 * 288 * 2 + 8) + 2 * 4 * 96 * 192)
    flops, nbytes = ft.gdn_core_call_work(M, 8, 2)
    assert flops == 2 * 12 * 9 * ft.gdn_core_flops(M, L)
    assert nbytes == 2 * 12 * 9 * ft.gdn_core_bytes(M, L)
    # the bytes bound it on a chip of 197 TFLOP/s and 819 GB/s: in one
    # pass the products would take half as long as q, k, v and o take to
    # cross HBM
    assert 0.45 < (flops / 197e12) / (nbytes / 819e9) < 0.55


def test_attention_counts_every_pair_of_thirty_heads():
    assert ft.attn_flops(M, 2) == 2 * 30 * L * 2 * L * 256
    assert ft.attn_flops(M, 1) == ft.attn_flops(M, 2) // 2
    assert ft.attn_bytes(M, 2) == 2 * 128 * (60 * L + 60 * 2 * L)
    flops, nbytes = ft.attn_call_work(M, 8, 2)
    # four layers' eight steps; three layers' once-a-call pass (the last
    # layer's attention of the conditioning frame feeds nothing)
    assert flops == 2 * (4 * 8 * ft.attn_flops(M, 2)
                         + 3 * ft.attn_flops(M, 1))
    assert nbytes == 2 * (4 * 8 * ft.attn_bytes(M, 2)
                          + 3 * ft.attn_bytes(M, 1))


def test_a_token_needs_seven_gigaflops():
    """ISSUE 40's count: ~7.2 GFLOP a target token — 6.66 of dense
    products, 0.50 of attention pairs, ~0.05 of the chunked scan."""
    mixers = sum(ft.mixer_flops(M, i, 2) for i in range(16))
    mlp = 16 * 2 * L * ft.dense_mlp_params(M)
    pairs = 4 * ft.attn_flops(M, 2)
    scan = 12 * ft.gdn_core_flops(M, L)
    conv = 12 * ft.gdn_conv_flops(M, L)
    dense = mixers + mlp - pairs - scan - conv
    assert dense / L == pytest.approx(6.66e9, rel=0.005)
    assert pairs / L == pytest.approx(0.503e9, rel=0.005)
    assert (mixers + mlp) / L == pytest.approx(7.22e9, rel=0.005)
    step = ft.frame_pass_flops(M, 2)
    assert step == pytest.approx(mixers + mlp, rel=0.005)   # + adapters
    # the once-a-call pass: no second frame of keys, and of layer 15 the
    # key and value projections alone
    once = ft.frame_pass_flops(M, 1)
    assert once < step - 4 * ft.attn_flops(M, 1) - 2 * L * (
        ft.dense_mlp_params(M) + 2 * 3840 * 3840) + 1e9
    assert once > 0.85 * step
    per = ft.per_view_step(M, 8)
    assert per == pytest.approx(2 * step + 2 * once / 8, rel=0.001)
    assert per == pytest.approx(66e12, rel=0.03)    # 59 of steps, 7 of once

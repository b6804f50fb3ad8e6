"""flops_tokens_ssm.py against a hand count at the cell's shapes."""
import pytest

import flops_tokens_ssm as ft

M = {"hidden_size": 2560, "num_hidden_layers": 32, "num_attention_heads": 40,
     "num_key_value_heads": 20, "intermediate_size": 10240,
     "layer_norm_eps": 1e-5, "mb_per_layer": 2, "sliding_window": 512,
     "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
     "patch_size": 4, "side": 256}
L = 4096


def test_layers_by_kind():
    assert ft.tokens_per_frame(M) == L
    assert ft.layers_of(M, "mamba") == list(range(0, 17, 2))        # 9
    assert ft.layers_of(M, "attn_window") == list(range(1, 16, 2))  # 8
    assert ft.layers_of(M, "attn_full") == [17]
    assert ft.layers_of(M, "gmu") == list(range(18, 32, 2))         # 7
    assert ft.layers_of(M, "attn_cross") == list(range(19, 32, 2))  # 7
    assert ft.last_cached_layer(M) == 17
    small = dict(M, num_hidden_layers=8)
    assert [ft.layer_kind(small, i) for i in range(8)] == [
        "mamba", "attn_window", "mamba", "attn_window", "mamba",
        "attn_full", "gmu", "attn_cross"]


def test_sizes_by_hand():
    assert ft.ssm_widths(M) == (5120, 16, 160, 4)
    # in 2560·10240, x 5120·192, dt 160·5120, out 5120·2560: ISSUE 38's
    # 41.24 M less the convolution, A_log, D and the two biases
    assert ft.ssm_proj_params(M) == 26214400 + 983040 + 819200 + 13107200
    assert ft.ssm_conv_flops(M, L) == 2 * L * 5120 * 4
    # Wqkv 2560·5120 and out 2560·2560; a cross layer's Wq 2560·2560
    assert ft.attn_proj_params(M, "attn_window") == 13107200 + 6553600
    assert ft.attn_proj_params(M, "attn_cross") == 2 * 6553600
    assert ft.attn_proj_params(M, "attn_full", cache_only=True) == 6553600
    assert ft.gmu_params(M) == 2 * 13107200
    assert ft.dense_mlp_params(M) == 3 * 2560 * 10240 == 78643200


def test_the_scan_is_counted_as_the_recurrence():
    """9 operations a (channel, state) element a token, an exponential
    one of them; u in bfloat16, Δ and m in float32, B and C, a token; the
    state in and out, A and D once."""
    assert ft.ssm_core_flops(M, 1) == 9 * 5120 * 16 == 737280
    assert ft.ssm_core_flops(M, L) == L * 737280
    assert ft.ssm_core_bytes(M, L) == L * (5120 * 10 + 128) \
        + 3 * 4 * 5120 * 16 + 4 * 5120
    flops, nbytes = ft.ssm_core_call_work(M, 8, 2)
    assert flops == 2 * 9 * (8 + 1) * ft.ssm_core_flops(M, L)
    assert nbytes == 2 * 9 * 9 * ft.ssm_core_bytes(M, L)
    # bound by bytes on a chip of 197 TFLOP/s and 819 GB/s
    assert nbytes / 819e9 > 10 * flops / 197e12


def test_visible_pairs_under_the_window():
    # a target token r sees own-frame tokens from r − 511 on and
    # conditioning tokens only where r < 511
    assert ft.visible_pairs(M, "attn_window", 2) == int(2559.5 * L)
    by_hand = sum(L - max(r - 511, 0) for r in range(L))
    assert ft.visible_pairs(M, "attn_window", 1) == by_hand
    assert ft.visible_pairs(M, "attn_full", 2) == L * 2 * L
    assert ft.visible_pairs(M, "attn_cross", 2) == L * 2 * L
    assert ft.visible_keys(M, "attn_window", 2) == L + 511
    assert ft.visible_keys(M, "attn_window", 1) == L
    assert ft.visible_keys(M, "attn_cross", 2) == 2 * L
    # both maps of 20 pairs: scores 64 wide, values 128
    assert ft.attn_flops(M, "attn_full", 2) == 2 * 20 * 2 * L * 2 * L * 192
    assert ft.attn_bytes(M, "attn_window", 2) == 2 * 64 * (
        3 * 40 * L + 2 * 20 * (L + 511))


def test_a_token_needs_eight_gigaflops():
    """ISSUE 38's count: 8.006 GFLOP a target token, the MLP 62.9 % of
    it, cross 13.3, Mamba 9.3, window 7.9, GMU 4.6, full 2.1."""
    mixers = {k: sum(ft.mixer_flops(M, i, 2) for i in ft.layers_of(M, k))
              for k in ft.KINDS}
    mlp = 32 * 2 * L * ft.dense_mlp_params(M)
    total = sum(mixers.values()) + mlp
    assert total / L == pytest.approx(8.006e9, rel=1e-4)
    assert mlp / total == pytest.approx(0.629, abs=5e-4)
    for kind, share in (("attn_cross", 0.133), ("mamba", 0.093),
                        ("attn_window", 0.079), ("gmu", 0.046),
                        ("attn_full", 0.021)):
        assert mixers[kind] / total == pytest.approx(share, abs=5e-4)


def test_the_once_a_call_pass_stops_at_the_last_cached_layer():
    """17 whole layers (9 Mamba, 8 window over one frame) and layer 17's
    key and value projections; no gated memory unit, no cross layer."""
    by_hand = 9 * ft.mixer_flops(M, 0, 1) + 8 * ft.mixer_flops(M, 1, 1) \
        + 2 * L * 6553600 + 17 * 2 * L * ft.dense_mlp_params(M)
    adapters = 2 * L * 2560 * (48 + 144 * 16) + 4 * 2560 * 2560
    assert ft.frame_pass_flops(M, 1) == by_hand + adapters
    assert ft.frame_pass_flops(M, 1) / ft.frame_pass_flops(M, 2) \
        == pytest.approx(0.508, abs=1e-3)
    step = 2 * (ft.frame_pass_flops(M, 2) + 2 * L * 2560 * 48)
    assert step == pytest.approx(65.7e12, rel=1e-3)
    assert ft.per_view_step(M, 8) == step + 2 * ft.frame_pass_flops(M, 1) / 8


def test_attention_work_by_stamp():
    L2 = 2 * L
    flops, nbytes = ft.attn_call_work(M, 8, 2, "attn_cross")
    assert flops == 2 * 7 * 8 * ft.attn_flops(M, "attn_cross", 2)
    assert nbytes == 2 * 7 * 8 * 2 * 64 * (3 * 40 * L + 2 * 20 * L2)
    # layer 17's once-a-call attention feeds nothing: its steps alone
    assert ft.attn_call_work(M, 8, 2, "attn_full")[0] \
        == 2 * 8 * ft.attn_flops(M, "attn_full", 2)
    # the window binds in the steps and in the once-a-call pass
    assert ft.attn_call_work(M, 8, 2, "attn_window")[0] == 2 * 8 * (
        8 * ft.attn_flops(M, "attn_window", 2)
        + ft.attn_flops(M, "attn_window", 1))
    # a frame no longer than the window: those passes are stamped full
    tiny = dict(M, side=64)                      # 256 tokens a frame
    assert ft.attn_call_work(tiny, 8, 2, "attn_window") == (0, 0)
    assert ft.attn_call_work(tiny, 8, 2, "attn_full")[0] == 2 * (
        8 * 9 * ft.attn_flops(tiny, "attn_full", 2)
        + 8 * ft.attn_flops(tiny, "attn_full", 1))

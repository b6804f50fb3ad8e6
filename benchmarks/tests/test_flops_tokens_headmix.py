"""flops_tokens_headmix.py against a hand count at the published sizes
(ISSUE 47's arithmetic)."""
import pytest

import flops_tokens_headmix as ft

M = {"hidden_size": 3072, "intermediate_size": 12288, "num_hidden_layers": 5,
     "num_key_value_heads": 8, "head_dim": 128, "num_experts": 256,
     "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
     "shared_expert_intermediate_size": 1024, "sliding_window": 512,
     "layer_types": (["full_attention"] + ["sliding_attention"] * 3) * 12,
     "mlp_layer_types": ["dense"] + ["sparse"] * 47,
     "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
     "held_experts": [0, 128], "patch_size": 4, "side": 256}
L = 4096


def test_sizes_by_hand():
    assert ft.tokens_per_frame(M) == L
    assert [ft.heads(M, i) for i in range(5)] == [48, 72, 72, 72, 48]
    assert [ft.windowed(M, i) for i in range(5)] == [False, True, True, True,
                                                    False]
    assert ft.expert_layers(M) == [1, 2, 3, 4]
    # q 3072·6144 + o 6144·3072 + gate 3072·48 + k, v 2·3072·1024
    assert ft.attn_proj_params(M, 0) == (
        2 * 18874368 + 147456 + 2 * 3145728) == 44187648
    # q, o 3072·9216 each + gate 3072·72 + k, v
    assert ft.attn_proj_params(M, 1) == (
        2 * 28311552 + 221184 + 6291456) == 63135744
    assert ft.expert_params(M) == 3 * 3072 * 1024 == 9437184
    assert ft.shared_params(M) == 9437184
    assert ft.dense_mlp_params(M) == 3 * 3072 * 12288 == 113246208
    assert ft.router_params(M) == 786432
    assert ft.expected_held_per_token(M) == 5.0


def test_visible_pairs_are_the_frame_rule_and_the_one_sided_window():
    # a full layer: every key of both frames; the once-a-call pass: its own
    assert ft.visible_pairs(M, 0, 2) == L * 2 * L
    assert ft.visible_pairs(M, 0, 1) == L * L
    # a window layer, a step: target r sees its frame's tokens after it and
    # the 511 behind it, across the frame's edge where they are cached
    step = sum(L - max(r - 511, 0) + max(511 - r, 0) for r in range(L))
    assert ft.visible_pairs(M, 1, 2) == step
    assert 2559 < step / L < 2561                 # "2560 keys a query"
    # the once-a-call pass: no frame behind it
    once = sum(L - max(r - 511, 0) for r in range(L))
    assert ft.visible_pairs(M, 1, 1) == once < step
    # of the cached frame only its last 511 keys are seen by anyone
    assert ft.visible_keys(M, 1, 2) == L + 511
    assert ft.visible_keys(M, 1, 1) == L == ft.visible_keys(M, 0, 1)
    assert ft.visible_keys(M, 0, 2) == 2 * L


def test_attention_counts_each_layers_own_heads():
    assert ft.attn_flops(M, 0, 2) == 2 * 48 * L * 2 * L * 256
    assert ft.attn_flops(M, 1, 2) == 2 * 72 * ft.visible_pairs(M, 1, 2) * 256
    # ISSUE 47: 201 MFLOP a token in a full layer, 94 in a window layer
    assert round(ft.attn_flops(M, 0, 2) / L / 1e6) == 201
    assert round(ft.attn_flops(M, 1, 2) / L / 1e6) == 94
    # q and o for the layer's heads, k and v once a key/value head
    assert ft.attn_bytes(M, 0, 2) == 2 * 128 * (2 * 48 * L + 2 * 8 * 2 * L)
    assert ft.attn_bytes(M, 1, 2) == 2 * 128 * (2 * 72 * L
                                                + 2 * 8 * (L + 511))
    # a call of 16 steps over 2 rows: the window stamp holds the three
    # window layers' steps AND their once-a-call pass; the full stamp the
    # two full layers' steps and layer 0's once-a-call pass (layer 4 is the
    # last: its once-a-call attention feeds nothing)
    wf, wb = ft.attn_call_work(M, 16, 2, window=True)
    assert wf == 2 * 3 * (16 * ft.attn_flops(M, 1, 2)
                          + ft.attn_flops(M, 1, 1))
    assert wb == 2 * 3 * (16 * ft.attn_bytes(M, 1, 2)
                          + ft.attn_bytes(M, 1, 1))
    ff, _ = ft.attn_call_work(M, 16, 2, window=False)
    assert ff == 2 * (16 * 2 * ft.attn_flops(M, 0, 2)
                      + ft.attn_flops(M, 0, 1))
    assert ft.expert_passes(M, 16) == 16 * 4 + 3


def test_a_step_by_hand():
    """ISSUE 47's shares of a guided step, per target token over layers
    0-4: projections 554 MFLOP, attention 684, routed + shared experts
    460, the dense MLP 226: 1.93 GFLOP."""
    proj = 2 * (2 * ft.attn_proj_params(M, 0) + 3 * ft.attn_proj_params(M, 1)
                + 4 * ft.router_params(M))
    attn = sum(ft.attn_flops(M, i, 2) for i in range(5)) / L
    experts = 4 * 2 * (5 * ft.expert_params(M) + ft.shared_params(M))
    dense = 2 * ft.dense_mlp_params(M)
    assert round(proj / 1e6) == 562          # the issue's 554 + the routers
    assert round(attn / 1e6) == 686          # the issue's 684 at 2560.0 keys
    assert round(experts / 1e6) == 453       # the issue's 460 less them
    assert round(dense / 1e6) == 226
    adapters = 2 * L * 3072 * (48 + 2304) + 2 * 2 * 3072 * 3072
    trunk = L * (proj + attn + experts + dense)
    assert ft.frame_pass_flops(M, 2) == pytest.approx(trunk + adapters,
                                                      rel=1e-12)
    assert 1.92e9 < trunk / L < 1.94e9
    # the once-a-call pass: layers 0-3 whole, layer 4's k and v alone
    once = sum(ft.layer_flops(M, i, 1, 5.0) for i in range(4)) \
        + 2 * L * 2 * 3072 * 1024 + adapters
    assert ft.frame_pass_flops(M, 1) == once
    out = 2 * L * 3072 * 48
    assert ft.per_view_step(M, 16) == 2 * (trunk + adapters + out) \
        + 2 * once / 16
    # 15.8 TFLOP of trunk a step; 16.7 with the adapters and a sixteenth of
    # the once-a-call pass: 85 ms at the chip's peak
    assert 15.7e12 < 2 * trunk < 15.9e12
    assert 16.5e12 < ft.per_view_step(M, 16) < 17.0e12
    # a run whose tokens had fewer held choices needs less
    assert ft.per_view_step(M, 16, 2.5) < ft.per_view_step(M, 16)

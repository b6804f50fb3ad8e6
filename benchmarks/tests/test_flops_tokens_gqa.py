"""flops_tokens_gqa.py against a hand count at the published sizes."""
import flops_tokens_gqa as ft

PERIOD = [0, 1, 1, 1]
M = {"hidden_size": 2560, "num_hidden_layers": 12, "num_attention_heads": 28,
     "num_key_value_heads": 4, "head_dim": 128,
     "rope_layout": PERIOD * 13, "sliding_window_layout": PERIOD * 13,
     "sliding_window_size": 4096, "moe_num_primary_experts": 64,
     "moe_num_active_primary_experts": 6, "moe_ffn_hidden_size": 768,
     "held_experts": [0, 64], "patch_size": 4, "side": 256}
L = 4096


def test_sizes_by_hand():
    assert ft.tokens_per_frame(M) == L
    # q 2560·3584 + k, v 2·2560·512 + o 3584·2560 + router 2560·64
    assert ft.layer_dense_params(M) == (
        9175040 + 2 * 1310720 + 9175040 + 163840) == 21135360
    assert ft.expert_params(M) == 3 * 2560 * 768 == 5898240
    assert ft.held_assignments(M, L) == 6 * L       # every expert is held


def test_visible_pairs_are_the_frame_rule_and_the_window():
    # a full layer: every key of both frames; the once-a-call pass: its own
    assert ft.visible_pairs(M, 0, 2) == L * 2 * L
    assert ft.visible_pairs(M, 0, 1) == L * L == ft.visible_pairs(M, 1, 1)
    # a window layer: target r sees its frame and the cached c > r, 6143.5
    # of 8192 keys on average
    pairs = ft.visible_pairs(M, 1, 2)
    assert pairs == L * L + L * (L - 1) // 2 == int(6143.5 * L)
    # the cached key 0 is seen by no target query
    assert ft.visible_keys(M, 1, 2) == 2 * L - 1
    assert ft.visible_keys(M, 0, 2) == 2 * L


def test_attention_counts_only_what_is_visible():
    assert ft.attn_flops(M, 0, 2) == 2 * 28 * L * 2 * L * 256
    assert ft.attn_flops(M, 1, 2) / ft.attn_flops(M, 0, 2) == 6143.5 / 8192
    # q and o for 28 heads, k and v once a key/value head
    assert ft.attn_bytes(M, 0, 2) == 2 * 128 * (2 * 28 * L + 2 * 4 * 2 * L)
    # a call: 12 steps over 4 rows; window = the 9 window layers' steps,
    # full = the 3 full layers' steps + 11 layers of the once-a-call pass
    wf, wb = ft.attn_call_work(M, 12, 4, window=True)
    assert wf == 4 * 12 * 9 * ft.attn_flops(M, 1, 2)
    assert wb == 4 * 12 * 9 * ft.attn_bytes(M, 1, 2)
    ff, _ = ft.attn_call_work(M, 12, 4, window=False)
    assert ff == 4 * (12 * 3 * ft.attn_flops(M, 0, 2)
                      + 11 * ft.attn_flops(M, 0, 1))


def test_a_step_by_hand():
    """ISSUE 30's shares: per token-layer projections 42 MFLOP, attention
    117 full / 88 window, experts 71."""
    assert round(2 * ft.layer_dense_params(M) / 1e6) == 42
    assert round(ft.attn_flops(M, 0, 2) / L / 1e6) == 117
    assert round(ft.attn_flops(M, 1, 2) / L / 1e6) == 88
    assert round(2 * 6 * ft.expert_params(M) / 1e6) == 71
    adapters = 2 * L * 2560 * (48 + 2304) + 2 * 2 * 2560 * 2560
    trunk = (3 * ft.attn_flops(M, 0, 2) + 9 * ft.attn_flops(M, 1, 2)
             + 12 * (2 * L * 21135360 + 2.0 * 6 * L * 5898240))
    assert ft.frame_pass_flops(M, 2) == trunk + adapters
    # the once-a-call pass: 11 whole layers and the last one's k and v
    once = 11 * (ft.attn_flops(M, 0, 1) + 2 * L * 21135360
                 + 2.0 * 6 * L * 5898240) + 2 * L * 2560 * 1024 + adapters
    assert ft.frame_pass_flops(M, 1) == once
    out = 2 * L * 2560 * 48
    assert ft.per_view_step(M, 12) == 2 * (trunk + adapters + out) \
        + 2 * once / 12
    # 2 views a step: 40.9 TFLOP of trunk (208 MFLOP a token-layer), 43.8
    # with the adapters and a twelfth of the once-a-call pass: 0.22 s at
    # the chip's peak
    assert 43e12 < 2 * ft.per_view_step(M, 12) < 45e12

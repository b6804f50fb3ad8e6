"""flops_tokens_scmoe.py against a hand count at the cell's shapes."""
import flops_tokens_scmoe as ft

M = {"hidden_size": 6144, "num_layers": 4, "num_hidden_layers": 4,
     "num_attention_heads": 64, "q_lora_rank": 1536, "kv_lora_rank": 512,
     "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
     "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
     "moe_intermediate_size": 2048, "n_routed_experts": 512,
     "zero_expert_num": 256, "moe_topk": 12, "num_experts_per_tok": 12,
     "held_experts": [0, 16], "patch_size": 4, "side": 256}
L = 4096


def test_sizes_by_hand():
    assert ft.tokens_per_frame(M) == L and ft.router_width(M) == 768
    # q_a 6144·1536, q_b 1536·12288 ONCE, kv_a 6144·576, o 8192·6144
    assert ft.mla_proj_params(M) == 9437184 + 18874368 + 3538944 + 50331648 \
        == 82182144
    assert ft.mla_proj_params(M, cache_only=True) == 3538944
    assert ft.kv_up_params(M) == 512 * 64 * 256 == 8388608   # a KEY token
    # ISSUE 44's 90.57 M an attention is these two (and the norms)
    assert ft.mla_proj_params(M) + ft.kv_up_params(M) == 90570752
    assert ft.dense_mlp_params(M) == 3 * 6144 * 12288 == 226492416
    assert ft.router_params(M) == 6144 * 768 == 4718592
    assert ft.expert_params(M) == 3 * 6144 * 2048 == 37748736
    assert ft.expected_held_per_token(M) == 0.25             # 12 × 16 / 768


def test_a_layer_step_by_hand():
    """One row of a step through one double layer: two attentions (their
    projections a query token, kv_b over 8192 key tokens, 4096 × 8192
    pairs of 64 heads at 192 + 128), two dense MLPs, the router, a quarter
    of a held assignment a token."""
    pairs = 2 * 64 * L * 2 * L * 320
    assert ft.mla_core_flops(M, L, 2 * L) == pairs == 1374389534720
    one = 2 * L * 82182144 + 2 * 2 * L * 8388608 + pairs
    assert ft.attention_flops(M, 2) == one
    want = 2 * one + 2 * 2 * L * 226492416 + 2 * L * 4718592 \
        + 2 * (L * 0.25) * 37748736
    assert ft.layer_flops(M, 2, 0.25) == want
    # ISSUE 44's split of a layer-step (two rows), in TFLOP: 5.5 of pairs,
    # 3.8 of projections (it counted q_b twice: 4.1), 7.4 of dense MLPs,
    # 0.15 of experts
    assert round(2 * 2 * pairs / 1e12, 1) == 5.5
    assert round(2 * 2 * 2 * L * 226492416 / 1e12, 1) == 7.4
    assert round(2 * 2 * (L * 0.25) * 37748736 / 1e12, 2) == 0.15
    assert round(2 * 2 * (2 * L * 82182144 + 4 * L * 8388608) / 1e12, 1) \
        == 3.2


def test_the_once_a_call_pass_runs_of_the_last_layer_what_its_latents_need():
    once = ft.attention_flops(M, 1) + 2 * L * 226492416 + 2 * L * 3538944
    assert ft.layer_flops(M, 1, 0.25, cache_only=True) == once
    adapters = 2 * L * 6144 * (48 + 144 * 16) + 4 * 6144 * 6144
    assert ft.frame_pass_flops(M, 1) == 3 * ft.layer_flops(M, 1, 0.25) \
        + once + adapters
    assert ft.frame_pass_flops(M, 2, 0.5) == 4 * ft.layer_flops(M, 2, 0.5) \
        + adapters
    step = 2 * (ft.frame_pass_flops(M, 2) + 2 * L * 6144 * 48)
    assert ft.per_view_step(M, 8) == step + 2 * ft.frame_pass_flops(M, 1) / 8
    # about 72 TFLOP a view-step (66 of the step, 6 of the once-a-call
    # pass spread over 8): over a third of a second of the chip's peak
    assert 71e12 < ft.per_view_step(M, 8) < 72e12
    # the run's own held rows move it by the experts' part alone
    assert ft.per_view_step(M, 8, 0.0) < ft.per_view_step(M, 8, 1.0)
    assert ft.per_view_step(M, 8, 1.0) - ft.per_view_step(M, 8, 0.0) \
        < 0.04 * ft.per_view_step(M, 8)


def test_a_calls_work_under_the_two_stamps():
    flops, nbytes = ft.mla_core_call_work(M, 8, 2)
    assert flops == 2 * (8 * 8 * ft.mla_core_flops(M, L, 2 * L)
                         + 7 * ft.mla_core_flops(M, L, L))
    # q in and o out, k and v in: 64 heads × 320 lanes × 2 bytes a token
    assert ft.mla_core_bytes(M, L, 2 * L) == 2 * 64 * 3 * L * 320
    assert nbytes == 2 * (8 * 8 * ft.mla_core_bytes(M, L, 2 * L)
                          + 7 * ft.mla_core_bytes(M, L, L))
    # every step's four branches, and the once-a-call pass's first three
    assert ft.expert_passes(M, 8) == 8 * 4 + 3 == 35
    assert ft.moe_experts_flops(M, 2048) == 2 * 2048 * 37748736
    assert ft.moe_experts_bytes(M, 2048, 16) == 2 * (
        16 * 37748736 + 2048 * (2 * 6144 + 4 * 2048))
    assert ft.moe_experts_flops(M, 0) == 0 == ft.moe_experts_bytes(M, 0, 0)

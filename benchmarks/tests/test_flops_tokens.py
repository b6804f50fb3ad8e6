"""flops_tokens.py against a hand count at the published sizes."""
import flops_tokens

M = {"hidden_size": 4096, "num_hidden_layers": 6, "num_attention_heads": 32,
     "q_lora_rank": 1024, "kv_lora_rank": 256, "qk_nope_head_dim": 64,
     "qk_rope_head_dim": 64, "v_head_dim": 128, "n_routed_experts": 128,
     "num_experts_per_tok": 4, "n_shared_experts": 1,
     "moe_intermediate_size": 2048, "held_experts": [0, 32],
     "patch_size": 4, "side": 128}


def test_sizes_by_hand():
    assert flops_tokens.tokens_per_frame(M) == 1024
    # q_a 4096·1024 + q_b 1024·4096 + kv_a 4096·320 + o 4096·4096
    # + router 4096·128 + shared 3·4096·2048
    assert flops_tokens.layer_dense_params(M) == (
        4194304 + 4194304 + 1310720 + 16777216 + 524288 + 25165824)
    assert flops_tokens.kv_up_params(M) == 256 * 32 * 192 == 1572864
    assert flops_tokens.expert_params(M) == 25165824
    # 1024 tokens × top-4 × 32 of 128 held = 1024 assignments here
    assert flops_tokens.expected_held_assignments(M, 1024) == 1024.0


def test_a_step_row_by_hand():
    core = 2 * 32 * 1024 * 2048 * (128 + 128)
    assert flops_tokens.mla_core_flops(M, 1024, 2048) == core == 34359738368
    layer = (2 * 1024 * 52166656            # dense, the row's own tokens
             + 2 * 2048 * 1572864           # kv_b over [cache ; own]
             + core
             + 2 * 1024 * 25165824)         # 1024 held assignments
    assert layer == 199179108352
    adapters = 2 * 1024 * 4096 * (48 + 2304) + 2 * 2 * 4096 * 4096
    assert flops_tokens.frame_pass_flops(M, 2) == 6 * layer + adapters
    # the once-a-call frame sees its own 1024 keys only
    once = flops_tokens.frame_pass_flops(M, 1)
    assert flops_tokens.frame_pass_flops(M, 2) - once == 6 * (
        2 * 1024 * 1572864 + core // 2)


def test_per_view_step_is_two_rows_and_the_spread_pass():
    out = 2 * 1024 * 4096 * 48
    step = 2 * (flops_tokens.frame_pass_flops(M, 2) + out)
    once = 2 * flops_tokens.frame_pass_flops(M, 1)
    assert flops_tokens.per_view_step(M, 32) == step + once / 32
    # the issue counted 9.4 TFLOP a step of 4 views from the same shapes
    # (without the adapters)
    assert 9.4e12 < 4 * step < 9.8e12


def test_expert_bytes_count_each_hit_experts_weights_once():
    nbytes = flops_tokens.moe_experts_bytes(M, 8192, 32)
    assert nbytes == 2 * (32 * 25165824 + 8192 * (2 * 4096 + 4 * 2048))
    # weights dominate at 256 rows an expert: 1.61 GB of 1.88 GB
    assert 0.8 < 2 * 32 * 25165824 / nbytes < 0.9
    assert flops_tokens.moe_experts_flops(M, 8192) == 2 * 8192 * 25165824

"""flops_tokens_kda.py against a hand count at the cell's shapes."""
import flops_tokens_kda as ft

M = {"hidden_size": 2304, "num_hidden_layers": 5, "num_attention_heads": 32,
     "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
     "v_head_dim": 128,
     "linear_attn_config": {
         "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
         "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                        21, 22, 23, 25, 26],
         "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4},
     "first_k_dense_replace": 1, "intermediate_size": 9216,
     "num_experts": 256, "num_experts_per_token": 8, "num_shared_experts": 1,
     "moe_intermediate_size": 1024, "held_experts": [0, 128],
     "patch_size": 4, "side": 256}
L = 4096


def test_layers_by_kind():
    assert ft.tokens_per_frame(M) == L
    assert ft.layers_of(M, full=False) == [0, 1, 2, 4]     # KDA
    assert ft.layers_of(M, full=True) == [3]               # one latent layer
    assert ft.expert_layers(M) == [1, 2, 3, 4]             # layer 0 is dense
    # every step's pass of the four, the once-a-call pass of layers 1-3
    # (the last layer leaves its state and runs no feed-forward)
    assert ft.expert_passes(M, 16) == 16 * 4 + 3


def test_sizes_by_hand():
    # q, k, v 3·2304·4096; the decay's pair 2304·128 + 128·4096; β 2304·32;
    # the gate's pair; o 4096·2304: ISSUE 34's 39.5 M less the convolutions
    cache_only = 3 * 9437184 + 294912 + 524288 + 73728
    assert ft.kda_proj_params(M, cache_only=True) == cache_only == 29204480
    assert ft.kda_proj_params(M) == cache_only + 294912 + 524288 + 9437184 \
        == 39460864
    assert ft.kda_conv_flops(M, L) == 2 * L * 3 * 4096 * 4
    # q 2304·6144, kv_a 2304·576, o 4096·2304; kv_b 512·8192 a KEY token
    assert ft.mla_proj_params(M) == 14155776 + 1327104 + 9437184
    assert ft.mla_proj_params(M, cache_only=True) == 1327104
    assert ft.kv_up_params(M) == 4194304
    assert ft.dense_mlp_params(M) == 3 * 2304 * 9216 == 63700992
    assert ft.expert_params(M) == 3 * 2304 * 1024 == 7077888
    assert ft.moe_dense_params(M) == 2304 * 256 + 7077888
    assert ft.expected_held_per_token(M) == 4.0    # 8 × 128 / 256


def test_the_scan_is_counted_in_its_chunked_form():
    """A chunk of 64 tokens of one head of 128: three (64, 128)·(128, 128)
    products against the state and four triangles of 64²/2 × 128 — 2²²
    multiply-adds; without the output two products and two triangles."""
    assert 3 * 64 * 128 * 128 + 4 * (64 * 64 // 2) * 128 == 2 ** 22
    assert ft.kda_core_flops(M, 64) == 2 * 32 * 2 ** 22
    assert ft.kda_core_flops(M, L) == 64 * ft.kda_core_flops(M, 64) \
        == 17179869184                                     # 17.2 GFLOP a row
    assert ft.kda_core_flops(M, 65) == 2 * ft.kda_core_flops(M, 64)
    assert ft.kda_core_flops(M, 64, output=False) == 2 * 32 * (
        2 * 64 * 128 * 128 + 2 * (64 * 64 // 2) * 128)
    # q, k, v in and o out in bfloat16, g and β in float32, the state in
    # and out in float32
    assert ft.kda_core_bytes(M, L) == 32 * (
        L * (4 * 128 * 2 + 128 * 4 + 4) + 2 * 128 * 128 * 4)
    # a call of 16 steps over 4 rows: layers 0-2 whole in the once-a-call
    # pass, layer 4 there for its state alone
    flops, nbytes = ft.kda_core_call_work(M, 16, 4)
    assert flops == 4 * (4 * 16 * ft.kda_core_flops(M, L)
                         + 3 * ft.kda_core_flops(M, L)
                         + ft.kda_core_flops(M, L, output=False))
    assert nbytes == 4 * (4 * 16 * ft.kda_core_bytes(M, L)
                          + 3 * ft.kda_core_bytes(M, L)
                          + ft.kda_core_bytes(M, L, output=False))
    # the scan is memory-bound at the chip's ridge (240 FLOP a byte): its
    # roofline share is read against bytes / 819 GB/s
    assert flops / nbytes < 240


def test_latent_attention_counts_one_layer_at_its_two_widths():
    assert ft.mla_core_flops(M, L, 2 * L) == 2 * 32 * L * 2 * L * (192 + 128)
    assert ft.mla_core_bytes(M, L, 2 * L) == 2 * 32 * 3 * L * (192 + 128)
    flops, nbytes = ft.mla_core_call_work(M, 16, 4)
    # one latent layer, not five; it is not the last, so its once-a-call
    # pass attends too
    assert flops == 4 * (16 * ft.mla_core_flops(M, L, 2 * L)
                         + ft.mla_core_flops(M, L, L))
    assert nbytes == 4 * (16 * ft.mla_core_bytes(M, L, 2 * L)
                          + ft.mla_core_bytes(M, L, L))
    # where the latent layer is the last, its once-a-call pass leaves the
    # latent and attends to nothing
    last = dict(M, num_hidden_layers=4)
    assert ft.mla_core_call_work(last, 16, 4)[0] == 4 * 16 \
        * ft.mla_core_flops(M, L, 2 * L)


def test_a_step_by_hand():
    """ISSUE 34's split of a step (4 rows of 4096 target tokens): the KDA
    layers' own work 5.3 TFLOP, the experts 4.6, the latent layer 3.8, the
    dense MLP 2.1: 15.5-16 in all."""
    tokens = 4 * L
    kda = 4 * (tokens * 2 * 39460864 + 4 * ft.kda_conv_flops(M, L)
               + 4 * ft.kda_core_flops(M, L))
    assert round(kda / 1e12, 2) == 5.45   # 5.17 of projections, 0.27 of scan
    experts = 4 * tokens * 2 * (ft.moe_dense_params(M) + 4 * 7077888)
    assert round(experts / 1e12, 1) == 4.7
    latent = tokens * 2 * ft.mla_proj_params(M) \
        + 2 * tokens * 2 * ft.kv_up_params(M) \
        + 4 * ft.mla_core_flops(M, L, 2 * L)
    assert round(latent / 1e12, 1) == 3.8
    dense = tokens * 2 * 63700992
    assert round(dense / 1e12, 1) == 2.1
    adapters = 2 * L * 2304 * (48 + 2304) + 2 * 2 * 2304 * 2304
    assert 4 * ft.frame_pass_flops(M, 2) == kda + experts + latent + dense \
        + 4 * adapters
    # the once-a-call pass: layers 0-3 whole against their own frame, of
    # layer 4 the projections its cache needs and the scan for its state
    once = sum(ft.layer_flops(M, i, 1, 4.0) for i in range(4)) \
        + 2 * L * 29204480 + ft.kda_conv_flops(M, L) \
        + ft.kda_core_flops(M, L, output=False) + adapters
    assert ft.frame_pass_flops(M, 1) == once
    out = 2 * L * 2304 * 48
    assert ft.per_view_step(M, 16) == 2 * (ft.frame_pass_flops(M, 2) + out) \
        + 2 * once / 16
    # 2 views a step: 16.3 TFLOP with the adapters, 17.1 with a sixteenth
    # of the once-a-call pass (two rows): 87 ms at the chip's peak
    assert 16.2e12 < 4 * ft.frame_pass_flops(M, 2) < 16.4e12
    assert 17.0e12 < 2 * ft.per_view_step(M, 16) < 17.2e12
    # the run's own held assignments stand in for the even 4 a token
    assert ft.per_view_step(M, 16, 4.0) == ft.per_view_step(M, 16)
    assert ft.per_view_step(M, 16, 2.0) < ft.per_view_step(M, 16)

"""flops.py against counts made by hand."""
import flops

BASE = dict(ch=128, ch_mult=[1, 2, 2, 4], emb_ch=512, num_res_blocks=2,
            attn_resolutions=[8, 16, 32], side=128)


def test_resblock_by_hand():
    # 128 → 256 channels at 64², embedding 512, one frame:
    pix = 64 * 64
    conv1 = 2 * 9 * 128 * 256 * pix
    film = 2 * 512 * (2 * 256) * pix
    conv2 = 2 * 9 * 256 * 256 * pix
    skip = 2 * 128 * 256 * pix
    assert flops.resblock(128, 256, 64, 512) == conv1 + film + conv2 + skip
    assert flops.resblock(256, 256, 64, 512) == (
        2 * 9 * 256 * 256 * pix * 2 + film)


def test_attention_by_hand():
    # 256 channels at 32²: L = 1024 tokens, self attention of one frame.
    L, C = 1024, 256
    qkv = 3 * 2 * C * C * L
    scores = 2 * L * L * C
    values = 2 * L * L * C
    assert flops.attention(C, 32) == qkv + scores + values
    # Cross attention over one other frame costs the same.
    assert flops.attention(C, 32, kv_frames=1) == flops.attention(C, 32)


def test_forward_structure():
    parts = dict(flops.forward_parts(BASE))
    # base128 attends at 32² (level 2) and 16² (level 3) only.
    attn = sorted(k for k in parts if k.endswith(".self"))
    assert all(k.startswith(("down2", "down3", "up2", "up3", "middle"))
               for k in attn)
    assert len(attn) == 2 * 2 + 1 + 2 * 3
    with_pose = flops.forward(BASE)
    assert with_pose - flops.forward(BASE, pose=False) == sum(
        v for k, v in parts.items() if k.startswith("pose_conv"))
    assert flops.per_unit(BASE, "denoise") == 2 * flops.forward(BASE, False)
    # paper256: a guided view-step is 24.2 TFLOP, so 5.33 of them a second
    # on one v5e chip are 65.5 % of 197 TFLOP/s by this count.
    paper = dict(ch=256, ch_mult=[1, 2, 2, 4, 4], emb_ch=1024,
                 num_res_blocks=3, attn_resolutions=[8, 16, 32], side=256)
    assert 24.1e12 < flops.per_unit(paper, "denoise") < 24.3e12

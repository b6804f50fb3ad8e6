"""Operations the X-UNet needs, counted from shapes (multiply-add = 2).

`sizes`: ch, ch_mult, emb_ch, num_res_blocks, attn_resolutions, side.
Everything is per batch ROW of F = 2 frames (conditioning view + target).
Counted: convolutions, dense layers (FiLM projections per pixel, skip
projections, q/k/v), attention products. Not counted: normalisation,
activations, resampling, posenc (no matmul). A guided denoise step is
2 × forward (conditional and unconditional row).
The pose-embedding convolutions depend on the cameras only; a sampler
needs them once per request, so `forward(..., pose=False)` leaves them
out of a denoise step.
"""

from __future__ import annotations

F = 2  # frames per row


def conv(cin, cout, res, k=3):
    return 2 * k * k * cin * cout * res * res


def dense(cin, cout, res):
    return 2 * cin * cout * res * res


def resblock(cin, cout, res, emb_ch):
    """One frame: conv, FiLM projection of the per-pixel embedding, conv,
    and the skip projection where the width changes."""
    n = conv(cin, cout, res) + dense(emb_ch, 2 * cout, res) \
        + conv(cout, cout, res)
    return n + (dense(cin, cout, res) if cin != cout else 0)


def attention(c, res, kv_frames=1):
    """One frame's queries (L = res² tokens) against kv_frames × L keys:
    q, k, v projections, scores, weighted values."""
    L, Lk = res * res, kv_frames * res * res
    return 2 * c * c * (L + 2 * Lk) + 2 * 2 * L * Lk * c


def forward_parts(sizes, pose=True):
    """[(label, operations)] for one row (both frames)."""
    ch, mult, emb = sizes["ch"], sizes["ch_mult"], sizes["emb_ch"]
    nrb, side = sizes["num_res_blocks"], sizes["side"]
    attn = set(sizes["attn_resolutions"])
    levels = len(mult)
    parts = [("logsnr_emb", 2 * 2 * emb * emb)]
    if pose:
        parts += [(f"pose_conv_{i}", F * conv(144, emb, side // 2 ** i))
                  for i in range(levels)]
    parts.append(("stem", F * conv(3, ch, side)))

    def block(label, cin, cout, res):
        parts.append((label + ".res", F * resblock(cin, cout, res, emb)))
        if res in attn:
            parts.append((label + ".self", F * attention(cout, res)))
            parts.append((label + ".cross", F * attention(cout, res, F - 1)))

    skips, c, res = [ch], ch, side
    for lvl in range(levels):
        for b in range(nrb):
            block(f"down{lvl}.{b}", c, ch * mult[lvl], res)
            c = ch * mult[lvl]
            skips.append(c)
        if lvl != levels - 1:
            res //= 2
            parts.append((f"down{lvl}.trans", F * resblock(c, c, res, emb)))
            skips.append(c)
    block("middle", c, c, res)
    for lvl in reversed(range(levels)):
        for b in range(nrb + 1):
            block(f"up{lvl}.{b}", c + skips.pop(), ch * mult[lvl], res)
            c = ch * mult[lvl]
        if lvl != 0:
            res *= 2
            parts.append((f"up{lvl}.trans", F * resblock(c, c, res, emb)))
    parts.append(("out", F * conv(ch, 3, side)))
    return parts


def forward(sizes, pose=True):
    return sum(n for _, n in forward_parts(sizes, pose))


def per_unit(sizes, mode):
    """Operations of one unit of a cell's rate: a guided denoise step of
    one view (`denoise`)."""
    if mode == "denoise":
        return 2 * forward(sizes, pose=False)
    raise ValueError(f"unknown flops mode {mode!r}")

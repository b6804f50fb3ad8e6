"""The trunks' attention layers, compiled for a described v5e at their
cells' shapes: what each writes under its attention stamp beside the
`flash_fwd` calls (tests/test_tpu_compile.py's fixtures and readers; a
file of its own so that `--dist loadfile` can run it beside that one)."""

import jax
import numpy as np
import pytest

from test_tpu_compile import (  # noqa: F401 — the two fixtures as well
    BF16, _entry_writes, _pallas, v5e, v5e_devices)


def _attention_layer(v5e, preset, i, rows, L, cache=None, published=None):
    """The compiled text of layer `i` of `preset` over `rows` rows of `L`
    tokens of the SECOND frame, its parameters with what the trunk derives
    from them once a call, from a cache of the first frame's entries
    (`cache(tokens config)` → their shapes behind the rows, bfloat16) or,
    for a layer that reads what an earlier one of the pass publishes, those
    keys and values (`published(tokens config)`, likewise)."""
    from novel_view_synthesis_3d_tpu.config import get_preset
    from novel_view_synthesis_3d_tpu.models import (
        build_denoiser, token_denoiser)

    cfg = get_preset(preset)
    model = build_denoiser(cfg.model)
    k = cfg.model.tokens

    def S(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def made():
        p = model.init({"params": jax.random.PRNGKey(0)})["params"][
            f"layer_{i}"]
        derive = getattr(model.layer, "derive", None)
        return p if derive is None else token_denoiser.laid_over(
            p, derive(i, p))

    def rows_of(shapes):
        """A (nested) list of shape tails as tuples of shapes."""
        if shapes is None:
            return None
        if callable(shapes):
            shapes = shapes(k)
        return tuple(rows_of(s) for s in shapes) if isinstance(
            shapes, list) else S((rows,) + shapes)

    def layer(p, h, c, kv):
        more = ({} if kv is None else {"kv": kv},) \
            if model.layer.publishes else ()
        return model.layer(i, p, h, model.layer.tables(np.arange(L) + L),
                           c, *more)[:2]

    params = jax.tree.map(lambda a: S(a.shape, a.dtype),
                          jax.eval_shape(made))
    return jax.jit(layer).lower(
        params, S((rows, L, k.hidden_size)), rows_of(cache),
        rows_of(published)).compile().as_text(), k


def test_ms4_layer_hands_the_attention_its_operands_where_they_lie(
        v5e, monkeypatch):
    """A `Mistral4Layer` of `ms4_denoiser128` at the cell's shape (8 rows
    of 1024 tokens on a 1024-token latent cache), compiled for the chip:
    under `lk.mla_core` the only write of q's size is the `flash_fwd`
    custom-call — no `copy`, `transpose` or `reshape` re-lays q, the keys
    or the values for it (a head is a 128-lane block of the (B, L, H·128)
    arrays the projections' products leave), and the kernel writes o
    (B, L, H·128) as `o`'s product reads it."""
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    rows, L = 8, 1024
    text, k = _attention_layer(
        v5e, "ms4_denoiser128", 0, rows, L,
        lambda k: [(L, k.kv_lora_rank), (L, k.qk_rope_head_dim)])
    q_bytes = rows * L * k.num_attention_heads * k.qk_head_dim * 2
    core = [(op, name, size) for op, kind, name, size in _entry_writes(text)
            if kind == "mla_core"]
    assert [op for op, _, size in core if size >= q_bytes // 2] == [
        "custom-call"], core
    assert not [c for c in core if c[0] in ("copy", "transpose", "reshape")
                and c[2] >= q_bytes // 64], core
    (call,) = [c for c in core if c[0] == "custom-call"]
    assert "flash_fwd" in call[1]
    assert call[2] == rows * L * k.num_attention_heads * k.v_head_dim * 2
    # and nothing anywhere re-lays an array of the keys' or the values'
    # size (2 × q's): they leave their products as the kernel reads them
    assert not [w for w in _entry_writes(text)
                if w[0] in ("copy", "transpose", "reshape")
                and w[3] >= q_bytes], [
        w for w in _entry_writes(text) if w[3] >= q_bytes]


def test_st21_windowed_layer_writes_q_sized_arrays_in_its_calls_alone(
        v5e, monkeypatch):
    """A windowed layer of `st21_denoiser256` at the cell's shape (4 rows
    of 4096 tokens on a 4096-token key/value cache, window 4096): under
    `lk.attn_window` nothing of q's size is written but by the kernel
    calls — one a query block, each its (B, 1024, H·128) slab — and at
    most the one concatenation of the slabs; no `copy`, `transpose` or
    `reshape` of q's size. (The rotated keys, 4 heads against q's 28, are
    still re-laid once on their way out of the rotary's 4-D arrays: a
    `reshape` of 2/7 of q's bytes.)"""
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    rows, L = 4, 4096
    text, k = _attention_layer(
        v5e, "st21_denoiser256", 1, rows, L,
        lambda k: [(L, k.num_key_value_heads, k.head_dim)] * 2)
    assert k.sliding_window_layout[1] and k.sliding_window_size == L
    q_bytes = rows * L * k.num_attention_heads * k.head_dim * 2
    core = [(op, name, size) for op, kind, name, size in _entry_writes(text)
            if kind == "attn_window"]
    calls = [c for c in core if c[0] == "custom-call"]
    assert len(calls) == 4 and all("flash_fwd" in c[1] for c in calls), core
    assert sum(c[2] for c in calls) == q_bytes
    others = [c for c in core if c[0] != "custom-call"
              and c[2] >= q_bytes // 2]
    assert len(others) <= 1 and all(
        "concatenate" in name for _, name, _ in others), others
    assert not [c for c in core if c[0] in ("copy", "transpose", "reshape")
                and c[2] >= q_bytes // 2], core


def _shared_part_layer_writes(text, k, rows, L, calls):
    """What a latent layer at heads of 128 + 64 on 128 may write, compiled
    for the chip, over `rows` rows of `L` queries on 2·`L` keys, `calls`
    attentions in the layer. Under `lk.mla_core`: each kernel call's o,
    (rows, L, H·128), and NOTHING else of a size worth counting — no `pad`
    (the head is handed over as 128 lanes of its own and 64 all heads
    share, two operands: no 192 → 256), no `copy`, no `transpose` — but
    the shared key part laid twice side by side, (rows, 2L, 128): 1/H of
    the keys. Under `lk.mla_proj`: no `copy` or `pad` of the keys' or the
    values' size, nor of q's nope or rotary operand's (a trunk that rotates
    lays its two tables over the heads, float32 (L, H·64), a constant of
    the positions and no function of q: `…/tile`, not counted)."""
    NH = k.num_attention_heads
    assert (k.qk_nope_head_dim, k.qk_rope_head_dim, k.v_head_dim) == (
        128, 64, 128)
    writes = list(_entry_writes(text))
    assert not [w for w in writes if w[0] == "transpose"], writes
    o_bytes = rows * L * NH * 128 * 2
    shared_bytes = rows * 2 * L * 128 * 2
    core = [(op, size) for op, kind, _, size in writes if kind == "mla_core"]
    assert [c for c in core if c[0] == "custom-call"] == [
        ("custom-call", o_bytes)] * calls, core
    assert all(size <= shared_bytes for op, size in core
               if op != "custom-call"), core
    assert sum(size for op, size in core if op != "custom-call") \
        <= 2 * calls * shared_bytes, core
    assert not [c for c in core if c[0] in ("pad", "transpose")], core
    # q's rotary operand is half of o's bytes, its nope operand and the
    # keys' and values' products o's and twice o's
    assert [(op, name, size) for op, kind, name, size in writes
            if kind == "mla_proj" and size >= o_bytes // 2
            and op in ("copy", "reshape", "pad", "slice", "concatenate")
            and not name.endswith("/tile")] == [], writes


def test_kl48_latent_layer_writes_o_and_nothing_else_of_qs_size(
        v5e, monkeypatch):
    """The latent layer of `kl48_denoiser256` at the cell's shape (4 rows
    of 4096 tokens on a 4096-token latent cache; 32 heads of 128 + 64 on
    128): every operand reaches the kernel as its product leaves it, and
    the per-head pad 192 → 256 of q and of the keys — four passes until
    PR 45 — is gone (`_shared_part_layer_writes`)."""
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    rows, L = 4, 4096
    text, k = _attention_layer(
        v5e, "kl48_denoiser256", 3, rows, L,
        lambda k: [(L, k.kv_lora_rank), (L, k.qk_rope_head_dim)])
    assert k.is_full_attention(3)
    _shared_part_layer_writes(text, k, rows, L, calls=1)


def test_lcf_double_layer_writes_o_and_nothing_else_of_qs_size(
        v5e, monkeypatch):
    """A double layer of `lcf_denoiser256` at the cell's shape (2 rows of
    4096 tokens, each attention on its own 4096-token latent cache; 64
    heads of 128 + 64 on 128, rotary): BOTH attentions hand the kernel
    their operands where the products (and the rotary operand's x·c +
    x'·s) write them (`_shared_part_layer_writes`)."""
    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    rows, L = 2, 4096
    text, k = _attention_layer(
        v5e, "lcf_denoiser256", 0, rows, L,
        lambda k: [[(L, k.kv_lora_rank), (L, k.qk_rope_head_dim)]] * 2)
    _shared_part_layer_writes(text, k, rows, L, calls=2)


@pytest.mark.parametrize("kind,calls", [("attn_window", 8),
                                        ("attn_full", 2),
                                        ("attn_cross", 2)])
def test_p4f_layer_hands_the_kernel_whole_pairs_and_norms_them_in_place(
        kind, calls, v5e, monkeypatch):
    """A differential-attention layer of `p4f_denoiser256` at the cell's
    shape (2 rows of 4096 tokens; a window layer on its 511-row tail, the
    full layer on a 4096-row cache, a cross layer on the 8192 published
    rows): each map is a kernel call — one a query block where the window
    binds — over the pair's 128 lanes whole, and under the layer's
    attention stamp nothing of q's size is written beside them: no map is
    sliced out, padded or re-laid (a window layer pads its keys' and
    values' TOKEN axis, 4607 rows, to the block: 9/16 of q's bytes each).
    Behind the
    kernel, A¹V − λA²V and the pair-wise norm write no float32 array of
    their operand's size: they stay in the fusions that feed `o`'s
    product."""
    import re

    monkeypatch.setattr(_pallas, "use_interpret", lambda: False)
    rows, L = 2, 4096
    from novel_view_synthesis_3d_tpu.config import get_preset

    k = get_preset("p4f_denoiser256").model.tokens
    i = next(i for i in range(k.num_hidden_layers)
             if k.layer_kind(i) == kind)
    kv = k.num_key_value_heads * k.head_dim
    text, _ = _attention_layer(
        v5e, "p4f_denoiser256", i, rows, L,
        cache={"attn_window": lambda k: [(k.sliding_window - 1, kv)] * 2,
               "attn_full": lambda k: [(L, kv)] * 2,
               "attn_cross": None}[kind],
        published=(lambda k: [(2 * L, kv)] * 2)
        if kind == "attn_cross" else None)
    width = k.num_attention_heads * k.head_dim
    q_bytes = rows * L * width * 2
    core = [(op, name, size) for op, stamp, name, size
            in _entry_writes(text) if stamp == kind]
    kernels = [c for c in core if c[0] == "custom-call"]
    assert len(kernels) == calls and all(
        "flash_fwd" in c[1] for c in kernels), core
    assert sum(c[2] for c in kernels) == 2 * q_bytes
    assert not [c for c in core if c[0] != "custom-call"
                and c[2] >= q_bytes * 3 // 4], core
    entry = text[text.index("ENTRY"):]
    assert not re.findall(
        rf"= f32\[{rows},{L},{width}\]\S* (?!parameter)", entry)


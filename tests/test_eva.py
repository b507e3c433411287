"""The byte-level trunk with chunked linear attention (`models/eva.py`, one
pipeline stage) against its plain reference
(`chipbench/architectures/eva_decoder/reference.py`, which imports nothing
of the program), its kernel against the dense definition and against plain
causal attention, its tokenizer and its slab shapes: at tiny sizes on the
CPU, seeded."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pathway_tpu.models import eva, trunk
from pathway_tpu.models.tokenizer import (
    PACK_MAX_SEGMENTS, ByteTokenizer, HashTokenizer, encode_batch, pack_batch,
)
from pathway_tpu.ops.kernels import eva_attention as kernel

WINDOW, CHUNK = 32, 4


def tiny_model(**changes) -> dict:
    """A configuration's `model` group at toy widths, under the keys the
    architecture's three files read."""
    model = {
        "name": "tiny-eva", "attention_class": "eva", "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 4, "intermediate_size": 160,
        "window_size": WINDOW, "chunk_size": CHUNK, "hidden_act": "silu",
        "rms_norm_eps": 1e-5, "rope_theta": 100000, "norm_add_unit_offset": True,
        "fp32_skip_add": True, "vocab_size": 320, "num_hidden_layers": 32,
        "layers": 3, "pp_size": 2, "max_len": 256, "pooling": "mean",
        "dtype": "float32", "param_dtype": "float32",
    }
    model.update(changes)
    return model


STORE = {"max_len": 256}


def text_of(n_bytes: int, seed: int) -> str:
    """A text of exactly `n_bytes` bytes: with `<bos>` it is n_bytes + 1 tokens."""
    rng = np.random.default_rng([n_bytes, seed])
    return "".join(rng.choice(list("abcdefghij klmnop"), size=n_bytes))


def program_encoder(model: dict, seed: int):
    from chipbench.architectures.eva_decoder import program
    from pathway_tpu.models import minilm

    minilm._model_cache.clear()
    return program.embedder(model, STORE, seed).encoder


def reference_vectors(model: dict, seed: int, texts: list) -> np.ndarray:
    from chipbench.architectures.eva_decoder.reference import Encoder

    return Encoder(model, seed, max_len=STORE["max_len"]).embed(texts)


# documents of one window (20 tokens), of two (45), of one token more than two
# (65) and of four (128); packed they share a row, and all but the longest
# start off the window grid
TEXTS = [text_of(19, 0), text_of(44, 1), text_of(64, 2), text_of(127, 3)]


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense", "kernel-interpreted"])
def test_the_packed_program_agrees_with_the_plain_reference(use_flash):
    """f32 program against the f32 reference at `highest`: what separates
    them is the order of the sums (the program's online softmax and packed
    rows), a few ulps of 1e-7 through three layers: 2e-5 on a unit vector's
    components leaves a factor of ten."""
    model = tiny_model()
    enc = program_encoder(model, seed=7)
    ids, seg, slots = pack_batch(enc.tokenizer, TEXTS, max_len=256, token_budget=128)
    assert ids.shape == (1, 384)  # one row, longest first: slots 0, 128, 193 and 238
    starts = {int(np.flatnonzero(seg[r] == s + 1)[0]) for r, s in slots}
    assert any(s % WINDOW for s in starts)
    pooled = eva.forward(
        enc.lm.params, enc.config, jnp.asarray(ids, jnp.int32), None,
        seg=jnp.asarray(seg, jnp.int32), max_segments=PACK_MAX_SEGMENTS,
        use_flash=use_flash,
    )
    got = np.stack([np.asarray(pooled)[r, s] for r, s in slots])
    want = reference_vectors(model, 7, TEXTS)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(np.linalg.norm(got, axis=1), 1.0, atol=1e-5)


def test_the_unpacked_form_is_the_packed_one_and_row_groups_change_nothing(monkeypatch):
    """`encode` (one text a row, the read-back's path) against the
    reference, once whole and once as four groups of two rows."""
    model = tiny_model()
    enc = program_encoder(model, seed=11)
    want = reference_vectors(model, 11, TEXTS)
    np.testing.assert_allclose(enc.encode(TEXTS), want, atol=2e-5)
    ids, mask = encode_batch(enc.tokenizer, TEXTS, max_len=256)
    assert ids.shape == (8, 128)
    monkeypatch.setattr(trunk, "row_chunks", lambda rows, length, cap: 4)
    grouped = eva.forward(enc.lm.params, enc.config, jnp.asarray(ids, jnp.int32),
                          jnp.asarray(mask, jnp.int32))
    np.testing.assert_allclose(np.asarray(grouped)[:4], want, atol=2e-5)


def _operands(b: int, l: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    c = eva.TINY
    make = lambda: jnp.asarray(rng.normal(size=(b, l, c.hidden)), jnp.float32)  # noqa: E731
    layer = {"phi": jnp.asarray(rng.normal(size=(c.heads, c.head_dim)), jnp.float32),
             "mu": jnp.asarray(rng.normal(size=(c.heads, c.head_dim)), jnp.float32)}
    return make() * c.head_dim ** -0.5, make(), make(), layer


def _slab(rows, length: int) -> np.ndarray:
    """Segment ids of a slab: each row its documents' lengths, packed from
    slot 0, the rest padding."""
    seg = np.zeros((len(rows), length), np.int32)
    for r, lengths in enumerate(rows):
        at = 0
        for s, n in enumerate(lengths):
            seg[r, at:at + n] = s + 1
            at += n
    return seg


# name: (rows of document lengths, slots a row, block, summary tile, sub-tile)
KERNEL_CASES = {
    # documents of 70 and 37 tokens in one row (the second starts at slot 70,
    # off the window and the chunk grid) and one of four whole windows, over
    # every tiling a row of 128 slots allows
    "tiles-16-8": ([[70, 37], [128]], 128, 16, 8, 8),
    "tiles-32-16": ([[70, 37], [128]], 128, 32, 16, 16),
    "tiles-128-32": ([[70, 37], [128]], 128, 128, 32, 32),
    # what the kinds bring (windows of 32 are two blocks of 16)
    "a-document-starts-inside-a-block": ([[21, 60]], 96, 16, 8, 8),
    "a-window-boundary-inside-a-query-block": ([[8, 100]], 112, 16, 8, 8),
    "interior-to-every-key-block-but-the-diagonal": ([[128]], 128, 16, 8, 4),
    "a-padding-tail-inside-the-last-block": ([[100]], 112, 16, 8, 8),
    "one-window-plus-one-token": ([[33]], 48, 16, 8, 8),
    "at-most-one-window": ([[20, 10], [32]], 32, 16, 8, 8),
    "two-documents-in-one-summary-tile": ([[40, 40]], 96, 16, 16, 8),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_kernel_is_its_dense_definition(case):
    """Interpreted, against `eva_attention_dense`.  f32 sums in another
    order: 1e-5 on values of order one."""
    c = eva.TINY
    rows, length, block, tile, sub = KERNEL_CASES[case]
    seg = jnp.asarray(_slab(rows, length))
    q, k, v, layer = _operands(len(rows), length)
    layout = kernel.window_layout(seg, WINDOW, CHUNK, block=block, summary_tile=tile)
    kbar = vbar = None
    if length > WINDOW:
        kbar, vbar = eva.chunk_summaries(k, v, layer, layout, c)
    else:
        assert "chunk_start" not in layout  # nothing to summarise: no summary operand
    if case.startswith("tiles-"):
        # the pooling kernel (a chunk's rows copied from the 8-row tile it begins
        # in, the others weighted 0) is the gather's sums: the held slots agree
        pooled = eva.chunk_summaries(k, v, layer, layout, c, fused=True)
        held = np.asarray(layout["chunk_code"]) >= 0
        assert held.sum() == 16 + 8 + 24  # windows that another follows: 2, 1 and 3
        for got, want in zip(pooled, (kbar, vbar)):
            np.testing.assert_allclose(np.asarray(got)[held], np.asarray(want)[held], atol=1e-5)
        # the blocks a block of queries meets: the window's first to the diagonal
        key_lo = np.asarray(layout["key_lo"])
        assert (key_lo <= np.arange(128 // block)).all()
        assert key_lo[1, -1] == (96 // block)  # the fourth window's first slot
    dense = kernel.eva_attention_dense(q, k, v, kbar, vbar, layout, c.heads)
    fused = kernel.eva_attention(
        q, k, v, kbar, vbar, layout, c.heads, window=WINDOW, block=block,
        summary_tile=tile, sub_tile=sub, head_block=2, interpret=True,
    )
    real = np.asarray(seg) > 0  # padding comes out finite, and is not read
    np.testing.assert_allclose(np.asarray(fused)[real], np.asarray(dense)[real], atol=1e-5)
    assert np.isfinite(np.asarray(fused)).all()
    # the case is what its name says
    kind = np.asarray(layout["kind"]).reshape(len(rows), length // block, -1)
    if case == "interior-to-every-key-block-but-the-diagonal":
        assert (kind[0, 1::2, 1] == kernel.INTERIOR).all() and (kind[0, :, 2] == kernel.NOTHING).all()
        assert (np.asarray(layout["sum_kind"])[0, 2:] == kernel.INTERIOR).all()
    if case == "two-documents-in-one-summary-tile":
        codes = np.asarray(layout["chunk_code"])[0, :16] // kernel.SEG_STRIDE
        assert sorted(set(codes)) == [1, 2]  # tile 0 holds both documents' summaries
        assert (kind[0, 5] == kernel.NOTHING).all()  # a block of padding meets nothing


def test_the_rope_kernel_turns_the_pairs_decoder_rope_turns():
    """`kernel.rope` (interpreted) and `eva._rotate`, its definition, against
    `trunk.rope` on the same operand in that function's [B, H, L, D]
    contract: the same pairs (x[i], x[i + d/2]), the same angles."""
    from pathway_tpu.models.trunk import rope

    c = eva.TINY
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 128, c.hidden)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, 200, size=(2, 128)), jnp.int32)
    half = c.head_dim // 2
    angle = pos[:, :, None].astype(jnp.float32) * (
        c.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    cs = (jnp.concatenate([cos, cos], -1), jnp.concatenate([-sin, sin], -1))
    want = rope(
        x.reshape(2, 128, c.heads, c.head_dim).transpose(0, 2, 1, 3), pos, c.rope_theta
    ).transpose(0, 2, 1, 3).reshape(x.shape) * 0.25
    np.testing.assert_allclose(np.asarray(eva._rotate(x, *cs, 0.25)), np.asarray(want), atol=1e-6)
    got = kernel.rope(x, *cs, scale=0.25, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("use_kernel", [False, True], ids=["dense", "kernel-interpreted"])
def test_documents_of_at_most_one_window_are_plain_causal_attention(use_kernel):
    """Ties the new attention to the old path: with no document over a
    window there is nothing to summarise, and each document's context is
    `flash_attention._reference_attention(causal=True)` of it alone."""
    from pathway_tpu.ops.kernels.flash_attention import _reference_attention

    c = eva.TINY
    lengths = [32, 20, 31, 7]  # 90 of 96 slots; starts 0, 32, 52, 83
    seg = np.zeros((1, 96), np.int32)
    at = 0
    for s, n in enumerate(lengths):
        seg[0, at:at + n] = s + 1
        at += n
    q, k, v, layer = _operands(1, 96, seed=3)
    layout = kernel.window_layout(jnp.asarray(seg), WINDOW, CHUNK, block=32, summary_tile=8)
    assert (np.asarray(layout["chunk_code"]) == -1).all()  # no chunk is ever summarised
    kbar, vbar = eva.chunk_summaries(k, v, layer, layout, c)
    if use_kernel:
        got = kernel.eva_attention(q, k, v, kbar, vbar, layout, c.heads, window=WINDOW,
                                   block=32, summary_tile=8, head_block=2, interpret=True)
    else:
        got = kernel.eva_attention_dense(q, k, v, kbar, vbar, layout, c.heads)
    heads = lambda a: a.reshape(1, -1, c.heads, c.head_dim).transpose(0, 2, 1, 3)  # noqa: E731
    at = 0
    for n in lengths:
        alone = _reference_attention(
            heads(q[:, at:at + n]), heads(k[:, at:at + n]), heads(v[:, at:at + n]),
            jnp.ones((1, n), jnp.int32), 1.0, True,
        ).transpose(0, 2, 1, 3).reshape(1, n, c.hidden)
        np.testing.assert_allclose(np.asarray(got[:, at:at + n]), np.asarray(alone), atol=1e-5)
        at += n


def test_one_token_past_a_window_sees_every_summary_of_that_window_and_itself():
    """A document of W + 1 tokens: its last query's window holds only
    itself, so its context is one softmax over its own key and exactly
    W / c summaries, written out here by hand; the W queries before it see
    no summary."""
    c = eva.TINY
    n = WINDOW + 1
    seg = np.zeros((1, 64), np.int32)
    seg[0, 5:5 + n] = 1  # from slot 5: off every grid
    q, k, v, layer = _operands(1, 64, seed=5)
    layout = kernel.window_layout(jnp.asarray(seg), WINDOW, CHUNK, block=32, summary_tile=8)
    held = np.asarray(layout["chunk_code"])[0] >= 0
    assert held.sum() == WINDOW // CHUNK
    np.testing.assert_array_equal(
        np.asarray(layout["chunk_start"])[0][held], 5 + CHUNK * np.arange(WINDOW // CHUNK))
    kbar, vbar = eva.chunk_summaries(k, v, layer, layout, c)
    last = 5 + WINDOW
    hd = c.head_dim
    for h in range(c.heads):
        cols = slice(h * hd, (h + 1) * hd)
        # the summaries by the definition: softmax over a chunk of k . phi / sqrt(hd)
        kc = np.asarray(k)[0, 5:5 + WINDOW, cols].reshape(-1, CHUNK, hd)
        vc = np.asarray(v)[0, 5:5 + WINDOW, cols].reshape(-1, CHUNK, hd)
        a = np.asarray(jax.nn.softmax(jnp.asarray(kc @ np.asarray(layer["phi"])[h]) * hd ** -0.5, axis=1))
        kb = (a[:, :, None] * kc).sum(1) + np.asarray(layer["mu"])[h]
        vb = (a[:, :, None] * vc).sum(1)
        np.testing.assert_allclose(np.asarray(kbar)[0, held][:, cols], kb, atol=1e-5)
        keys = np.concatenate([np.asarray(k)[0, last:last + 1, cols], kb])
        values = np.concatenate([np.asarray(v)[0, last:last + 1, cols], vb])
        p = np.asarray(jax.nn.softmax(jnp.asarray(keys @ np.asarray(q)[0, last, cols])))
        want = p @ values
        for fused in (False, True):
            got = (
                kernel.eva_attention(q, k, v, kbar, vbar, layout, c.heads, window=WINDOW,
                                     block=32, summary_tile=8, head_block=2, interpret=True)
                if fused else kernel.eva_attention_dense(q, k, v, kbar, vbar, layout, c.heads)
            )
            np.testing.assert_allclose(np.asarray(got)[0, last, cols], want, atol=1e-5)
    # and what the program counts of it
    keys, summaries = eva.scored_pairs(np.array([n]), WINDOW, CHUNK)
    assert (int(keys[0]), int(summaries[0])) == (WINDOW * (WINDOW + 1) // 2 + 1, WINDOW // CHUNK)


def test_the_byte_tokenizer_is_the_references_rule_cut_at_max_len():
    from chipbench.architectures.eva_decoder.reference import byte_ids

    tok = ByteTokenizer()
    for text in ["", "a", "two words", "naïve café ≠ ascii", text_of(300, 0)]:
        for max_len in (None, 1, 8, 256):
            want = byte_ids(text, max_len if max_len is not None else 1 << 30)
            assert list(tok.encode(text, max_len)) == want
    ids = tok.encode("ab")
    assert list(ids) == [1, 64 + ord("a"), 64 + ord("b")] and tok.decode(ids) == "ab"
    assert tok.count_tokens("naïve") == 6
    # the configuration picks it, by the module's rule; the others keep theirs
    from pathway_tpu.models import moe_mla, transformer
    from pathway_tpu.models.trunk import model_module

    assert isinstance(eva.tokenizer(eva.TINY), ByteTokenizer)
    for module, config in ((transformer, transformer.MINILM_L6), (moe_mla, moe_mla.TINY)):
        made = model_module(config).tokenizer(config)
        assert isinstance(made, HashTokenizer) and made.vocab_size == config.vocab_size
        assert model_module(config) is module


@pytest.mark.parametrize("n,want", [
    (1, 128), (90, 128), (129, 256), (600, 640), (1024, 1024), (1025, 2048), (6626, 7168),
    (6717, 7168), (10423, 11264), (10669, 11264),
])
def test_a_rows_length_comes_in_the_kernels_tiles(n, want):
    shapes = eva.tokenizer(eva.EvaConfig()).shapes
    assert shapes.seq_bucket(n) == want
    assert kernel.supports(want, 32, 128, 2048, 16)
    # and the layout cuts every such row into whole blocks (640 slots: five of 128)
    layout = kernel.window_layout(np.ones((1, want), np.int32), 2048, 16, xp=np)
    block = kernel.row_block(want)
    assert want % block == 0 and block % kernel.LANES == 0
    assert layout["kind"].shape == (1, want // block * kernel.token_steps(want, 2048))
    assert shapes.seq_bucket(n, maximum=8192) == min(want, 8192)


def test_slab_shapes_of_a_batch_of_pages():
    shapes = eva.tokenizer(eva.EvaConfig()).shapes
    assert [shapes.row_bucket(r) for r in (1, 2, 3, 5, 8, 9, 17)] == [1, 2, 4, 8, 8, 16, 24]
    # two pages are one row of all their tokens; the budget is the floor
    assert shapes.slab_length([3893, 6672], 256) == 11264
    assert shapes.slab_length([40, 50], 256) == 256
    assert shapes.slab_length([9000, 9000], 256) == 16384  # a row group, then a second row
    assert shapes.slab_length([20000], 256) == 20480
    assert shapes.slab_length([65] * 64, 256) == 3072  # 32 documents a row: two rows


def test_files_whose_byte_lengths_jitter_compile_their_slab_once():
    """Pages of one word count differ by tens of bytes from file to file:
    every batch of two takes the same slab, and the packed program is
    traced once."""
    enc = program_encoder(tiny_model(), seed=3)
    shapes = set()
    for i, (a, b) in enumerate([(150, 95), (143, 99), (158, 90), (139, 101)]):
        ids, seg, slots = pack_batch(
            enc.tokenizer, [text_of(a, i), text_of(b, i)], max_len=256, token_budget=64,
        )
        shapes.add(ids.shape)
        out = enc.lm.encode_packed(ids, seg, PACK_MAX_SEGMENTS)
        assert np.isfinite(np.asarray(out)[0, :2]).all()
    assert shapes == {(1, 256)}
    assert enc.lm._packed_jit._cache_size() == 1


def test_the_step_kinds_are_what_the_segment_ids_imply_and_the_counters_count_them(monkeypatch):
    """A slab listed by hand.  Windows of 32, blocks of 16, summary tiles of
    8 (what a window owns), sub-tiles of 8.  Document A: slots 0-69 (windows
    at 0, 32, 64), B: 70-106 (windows at 70, 102), padding from 107.  Block
    4 holds A's end and B's start, block 6 B's window boundary and the first
    padding, block 7 padding alone; A's first two windows and B's first get
    summaries: tiles 0, 1 and 2."""
    from functools import partial

    from pathway_tpu.internals import tracing

    N, I, E = kernel.NOTHING, kernel.INTERIOR, kernel.EDGE
    seg = _slab([[70, 37]], 128)
    tiles = dict(block=16, summary_tile=8)
    for xp in (np, jnp):  # the host's count and the program's layout are one function
        layout = kernel.window_layout(xp.asarray(seg), WINDOW, CHUNK, xp=xp, **tiles)
        # step 0 is the diagonal, step j the j-th block to the left
        assert np.asarray(layout["kind"]).reshape(8, 3).tolist() == [
            [E, N, N],  # a window's first block
            [E, I, N],  # its second: the first is all one window
            [E, N, N],
            [E, I, N],
            [E, N, N],  # A's last 6 tokens and B's first 10: nothing of B's to the left
            [E, E, N],  # all B's first window, but block 4 is not
            [E, E, E],  # B's boundary: its first window began in block 4
            [N, N, N],  # padding
        ]
        assert np.asarray(layout["key_lo"]).tolist() == [[0, 0, 2, 2, 4, 4, 4, 7]]
        assert np.asarray(layout["sum_kind"]).tolist() == [[N, N, I, I, E, N, E, N]]
        lo, hi = np.asarray(layout["sum_lo"])[0], np.asarray(layout["sum_hi"])[0]
        assert [(int(a), int(b)) for a, b in zip(lo, hi) if b > a] == [(0, 1), (0, 1), (0, 2), (2, 3)]
    # 7 diagonals of 3 sub-tiles of 8 x 8, 5 blocks to the left, 5 summary tiles
    met, unmasked = kernel.met_pairs(layout, sub_tile=8, **tiles)
    assert met == 7 * 3 * 64 + 5 * 256 + 5 * 8 * 16
    assert unmasked == 2 * 256 + 2 * 8 * 16
    # the mask lets through no pair that the steps do not meet
    code = np.asarray(layout["code"])[0]
    at = np.arange(128)
    scored = ((code[:, None] == code[None, :]) & (at[None, :] <= at[:, None]) & (code[:, None] > 0)).sum()
    assert scored < 7 * 3 * 64 + 5 * 256
    # and the program's counters are these counts, once a head and layer
    enc = program_encoder(tiny_model(), seed=5)
    monkeypatch.setattr(kernel, "window_layout", partial(kernel.window_layout, **tiles))
    monkeypatch.setattr(kernel, "met_pairs", partial(kernel.met_pairs, sub_tile=8, **tiles))
    before = tracing.spans_status()["totals"]
    out = enc.lm.encode_packed(np.where(seg > 0, 70, 0).astype(np.int32), seg, PACK_MAX_SEGMENTS)
    assert np.isfinite(np.asarray(out)[0, :2]).all()
    after = tracing.spans_status()["totals"]
    count = lambda name: after[name]["count"] - before.get(name, {"count": 0})["count"]  # noqa: E731
    per_pair = enc.config.heads * enc.config.layers
    assert count("eva.met_pairs") == met * per_pair
    assert count("eva.unmasked_pairs") == unmasked * per_pair
    keys, summaries = eva.scored_pairs(np.array([70, 37]), WINDOW, CHUNK)
    assert count("eva.scored_pairs") == int(keys.sum() + summaries.sum()) * per_pair
    assert count("eva.scored_pairs") < count("eva.met_pairs")


def test_the_counters_count_what_the_mask_lets_through():
    """`eva.tokens`, `eva.scored_pairs`, `eva.summary_pairs` and
    `eva.docs_multi_window` of a packed batch, from the segment lengths on
    the host, against the dense definition's own mask."""
    from pathway_tpu.internals import tracing

    enc = program_encoder(tiny_model(), seed=5)
    ids, seg, _ = pack_batch(enc.tokenizer, TEXTS, max_len=256, token_budget=128)
    before = tracing.spans_status()["totals"]
    enc.lm.encode_packed(ids, seg, PACK_MAX_SEGMENTS)
    after = tracing.spans_status()["totals"]
    count = lambda name: after[name]["count"] - before.get(name, {"count": 0})["count"]  # noqa: E731
    layout = kernel.window_layout(jnp.asarray(seg, jnp.int32), WINDOW, CHUNK)
    code = np.asarray(layout["code"])
    real = code > 0
    at = np.arange(code.shape[1])
    keys = ((code[:, :, None] == code[:, None, :]) & (at[None, None, :] <= at[None, :, None])
            & real[:, :, None]).sum()
    chunk_code = np.asarray(layout["chunk_code"])[:, None, :]
    own = (code - code % kernel.SEG_STRIDE)[:, :, None]
    summaries = ((chunk_code >= own) & (chunk_code < code[:, :, None]) & real[:, :, None]).sum()
    per_pair = enc.config.heads * enc.config.layers
    assert count("eva.tokens") == real.sum() == 20 + 45 + 65 + 128
    assert count("eva.scored_pairs") == (keys + summaries) * per_pair
    assert count("eva.summary_pairs") == summaries * per_pair
    assert count("eva.docs_multi_window") == 3
    # the flops the utilisation gauge takes for a document are those pairs' too
    flops = enc.config.active_flops_per_token(128.0) * 128
    proj = 4 * 64 * 64 + 3 * 64 * 160
    k128, s128 = eva.scored_pairs(128, WINDOW, CHUNK)
    assert flops == pytest.approx(2.0 * 3 * (proj * 128 + 2 * 64 * (k128 + s128)))

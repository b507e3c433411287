"""Chunked linear attention (EVA, prefill form) for packed slabs, as a Pallas
TPU kernel: `segment_attention.py`'s sibling for rows of thousands of slots.

A document's positions are cut into windows of `W` tokens and chunks of
`c`, both counted from the document's first token.  Query i in window w
scores, in ONE softmax, the keys t <= i of its own window exactly, and one
summary (k-bar_j, v-bar_j) for every chunk j of the document's earlier
windows.  A document may start at any slot of a row, so a window is no
fixed range of slots: `window_layout` works out, from the segment ids alone
and once for all layers, where each document's windows and summaries lie,
which blocks each block of queries has to meet and what each meeting needs.
Three kernels share it: `rope` turns q and k where their matmuls left them,
`pool_chunks` sums the chunks into their summaries
(`models/eva.py::chunk_summaries` weighs them), and `eva_attention`, the
rest of this note, scores them:

  * one grid step is one slab row, `HEAD_BLOCK` heads, one block of
    `block` query rows and ONE block of keys: step 0 the block's own (the
    diagonal, and with it the summaries its documents own), step j the
    j-th block to its left, as far as its earliest window's first slot
    (at most W/block + 1 in all).  Neither the scores nor the mask ever
    reach HBM, and a row may be any number of blocks long;
  * a block of queries' whole key set is bounded (W + block token slots
    and its documents' summaries), so its scores stay in VMEM (4 MB a
    head at the ingest slab) and the softmax is the plain one, in two
    passes: every step keeps its block's scores, masked, and each row's
    maximum so far; the block's last step takes the exponentials against
    the rows' maxima, sums them and multiplies them into v.  A row's
    running state is never rescaled (the online form's `alpha * acc`, and
    its maximum, normaliser and `alpha` re-broadcast once a key block,
    were half of this kernel's time: 4.74 ms a layer at the ingest slab
    against 2.44, PERF.md section 6, PR 39), and maxima and sums stay one
    value a lane until their pass ends: no reduction across lanes inside
    a step;
  * what a step does is data, a KIND a (row, query block, step) read from
    SMEM before the step (`PrefetchScalarGridSpec`), as the blocks are:
    NOTHING (no key of the block counts: the step maps to the block already
    resident and computes nothing), INTERIOR (every pair counts: query and
    key block lie in one window of one document, the key block left of the
    diagonal: no mask is built, no `where` runs) or EDGE (the mask).  On
    the diagonal only the sub-tiles of `SUB_TILE` at or under it are
    scored; the summaries come in tiles of `SUMMARY_TILE`, what a window
    owns, and a block of queries that sees all of every tile it meets (its
    own document's earlier windows) needs no mask either;
  * q, k, v [B, L, H*D] and the summaries [B, C, H*D] are read where their
    matmuls left them, heads contiguous, and the context is written
    straight into [B, L, H*D] for the out-projection;
  * the mask of a (query block, key block) pair is computed once and
    shared by the step's heads.  A token key is seen iff it lies in the
    query's document and window (one integer a token: `segment * SEG_STRIDE
    + window`) and not after it in the row; a summary iff it belongs to the
    query's document and to an earlier window.

Numerics are the dense definition's (`eva_attention_dense`): q arrives
scaled, scores accumulate in f32 from operands in the compute dtype, the
softmax is f32 over tokens and summaries together, p is cast to the
compute dtype for `p @ v`, which accumulates in f32 and is normalised
there.  Rows with segment 0 (padding) come out finite.
"""

from __future__ import annotations

import functools
import math

from pathway_tpu.ops.kernels import kernel_call
from pathway_tpu.ops.kernels.flash_attention import NEG_INF

LANES = 128
# rows of a block of queries, and of a block of token keys: the key tile.
# `models/eva.py` buckets a row's length to whole tiles
KEY_TILE = 512
SUMMARY_TILE = 128  # summaries a tile: what a window of 2048 owns in chunks of 16
SUB_TILE = 128  # on the diagonal, the sub-tiles above it are skipped, not masked
HEAD_BLOCK = 4  # heads a grid step takes: 512 lanes of q, k, v and the output
VMEM_LIMIT = 64 << 20  # of v5e's 128 MiB: the blocks, a row's summaries, a step's scores
# a token's code is `segment * SEG_STRIDE + window`; a row holds at most
# tokenizer.PACK_MAX_SEGMENTS documents and a document far fewer windows
SEG_STRIDE = 1 << 16
# a step's kind: what its (query block, key block or summary tiles) pair needs
NOTHING, INTERIOR, EDGE = 0, 1, 2


def row_block(length: int, block: int = KEY_TILE) -> int:
    """Rows of a block of queries and of keys in a row of `length`: the key
    tile, the whole row where it is shorter, and the largest tile that
    divides a row of 640, 768 or 896 slots."""
    return length if length <= block else math.gcd(length, block)


def supports(length: int, heads: int, head_dim: int, window: int, chunk: int) -> bool:
    """Static shapes the compiled kernel's tiling covers: heads of whole
    128-lane tiles, a row of whole lanes, windows of whole blocks."""
    block = row_block(length)
    return (
        head_dim % LANES == 0
        and heads % HEAD_BLOCK == 0
        and block % LANES == 0
        and (length <= window or (window % block == 0 and window % chunk == 0))
    )


def summary_slots(length: int, window: int, chunk: int, tile: int = SUMMARY_TILE) -> int:
    """Summary slots of a row of `length`: none where no document can pass
    one window; else a slot for every whole chunk the row could hold, in
    whole tiles (only chunks of windows that a later window follows get
    one, and they are packed to the front)."""
    if length <= window:
        return 0
    return -(-(length // chunk) // tile) * tile


def token_steps(length: int, window: int, block: int = KEY_TILE) -> int:
    """Key blocks a block of queries can have to meet: a window's blocks
    and the diagonal, or the whole row where that is shorter."""
    block = row_block(length, block)
    return min(window // block + 1, length // block)


def _positions(seg, at, xp):
    """A token's position in its document: `trunk.packed_positions`,
    and its like on the host (jax.numpy's `accumulate` is a loop a slot)."""
    import numpy as np

    if xp is not np:
        from pathway_tpu.models.trunk import packed_positions

        return packed_positions(seg)
    starts = np.concatenate([np.ones_like(seg[:, :1], dtype=bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    return at - np.maximum.accumulate(np.where(starts, at, 0), axis=1)


def window_layout(seg, window: int, chunk: int, *, block: int = KEY_TILE,
                  summary_tile: int = SUMMARY_TILE, xp=None):
    """Where a slab's windows and summaries lie.  seg: [B, L] int32, 1..S
    per packed document, 0 = padding.  Returns a dict of int32 arrays:

      pos [B, L]         a token's position in its document
      code [B, L]        segment * SEG_STRIDE + window; 0 for padding
      chunk_start [B, C] first slot of the chunk a summary slot holds (the
                         chunks of every window that a later window of the
                         same document follows, in row order), L - chunk
                         where the slot is empty
      chunk_code [B, C]  segment * SEG_STRIDE + window of that chunk, -1
                         where the slot is empty
      key_lo [B, L/block]               first key block a block of queries meets
      kind [B, L/block * token_steps]   what step j of a block of queries,
                         the one that meets key block `own - j`, needs:
                         NOTHING (left of `key_lo`, or every query padding),
                         INTERIOR (queries and keys fill one window of one
                         document, j > 0: every pair counts) or EDGE
      sum_lo, sum_hi [B, L/block]       its summary tiles, [lo, hi)
      sum_kind [B, L/block]             NOTHING (none), INTERIOR (every
                         query sees all of every tile) or EDGE

    C is `summary_slots(L, ...)`; with C == 0 the summary entries are
    absent.  `xp` is the array module: jax.numpy, or numpy where the host
    counts what the kernel will meet (`met_pairs`)."""
    if xp is None:
        import jax.numpy as xp

    b, l = seg.shape
    block = row_block(l, block)
    n_q = l // block
    at = xp.broadcast_to(xp.arange(l, dtype=xp.int32)[None, :], (b, l))
    pos = _positions(seg, at, xp)
    real = seg > 0
    win = pos // window
    code = xp.where(real, seg * SEG_STRIDE + win, 0)
    window_first = at - pos % window  # the slot of a token's window's first token
    key_lo = xp.where(real, window_first, at).reshape(b, n_q, block).min(-1) // block
    # a block of one code is one window of one document, without padding
    codes = code.reshape(b, n_q, block)
    low, high = codes.min(-1), codes.max(-1)
    whole = (low == high) & (low > 0)
    own = xp.arange(n_q, dtype=xp.int32)[:, None]
    met = own - xp.arange(token_steps(l, window, block), dtype=xp.int32)[None, :]  # [n_q, steps]
    left = xp.maximum(met, 0)
    alike = (met < own) & whole[:, :, None] & whole[:, left] & (low[:, :, None] == low[:, left])
    kind = xp.where(alike, INTERIOR, EDGE)
    kind = xp.where((met >= key_lo[:, :, None]) & (high[:, :, None] > 0), kind, NOTHING)
    layout = {
        "pos": pos,
        "code": code,
        "key_lo": key_lo,
        "kind": kind.reshape(b, -1).astype(xp.int32),
    }
    slots = summary_slots(l, window, chunk, summary_tile)
    if not slots:
        return layout
    # a chunk gets a summary iff its window is followed by another window of
    # the same document: the first token of that one sits `window` slots on
    follows = window_first + window
    followed = xp.take_along_axis(seg, xp.minimum(follows, l - 1), axis=1)
    followed = real & (follows < l) & (followed == seg)
    starts = followed & (pos % chunk == 0)
    # the starts' slots, in row order, packed to the front
    order = xp.sort(xp.where(starts, at, l), axis=1)[:, :slots]
    if order.shape[1] < slots:
        order = xp.pad(order, ((0, 0), (0, slots - order.shape[1])), constant_values=l)
    held = order < l
    start = xp.where(held, order, l - chunk)
    chunk_code = xp.where(held, xp.take_along_axis(code, start, axis=1), -1)
    layout["chunk_start"] = start
    layout["chunk_code"] = chunk_code
    # a query's summaries are those of its document's earlier windows:
    # `win * window / chunk` slots from the document's first
    before = xp.cumsum(starts, axis=1, dtype=xp.int32) - starts  # starts left of a slot
    doc_first = xp.take_along_axis(before, at - pos, axis=1)
    sees = real & (win > 0)
    lo = xp.where(sees, doc_first, slots)
    hi = xp.where(sees, doc_first + win * (window // chunk), 0)
    lo = lo.reshape(b, n_q, block).min(-1) // summary_tile
    hi = -(-hi.reshape(b, n_q, block).max(-1) // summary_tile)
    # no mask where the block's one code sees every slot of every tile it meets
    tiles = chunk_code.reshape(b, slots // summary_tile, summary_tile)
    tile = xp.arange(slots // summary_tile, dtype=xp.int32)
    outside = (tile < lo[:, :, None]) | (tile >= hi[:, :, None])  # [B, n_q, tiles]
    seen = (tiles.min(-1)[:, None, :] >= (low - low % SEG_STRIDE)[:, :, None]) & (
        tiles.max(-1)[:, None, :] < low[:, :, None]
    )
    plain = whole & (outside | seen).all(-1)
    layout["sum_lo"] = lo
    layout["sum_hi"] = hi
    layout["sum_kind"] = xp.where(hi > lo, xp.where(plain, INTERIOR, EDGE), NOTHING).astype(xp.int32)
    return layout


def met_pairs(layout, *, block: int = KEY_TILE, summary_tile: int = SUMMARY_TILE,
              sub_tile: int = SUB_TILE):
    """((query, key or summary) pairs the kernel's steps score in one head
    of one layer, those of them scored without a mask), from a layout's
    kinds: a token step meets a whole block pair, the diagonal's only the
    sub-tiles at or under it, a summary step whole tiles."""
    kind = layout["kind"]
    b, l = layout["code"].shape
    block = row_block(l, block)
    sub = math.gcd(sub_tile, block)
    steps = kind.shape[1] // (l // block)
    diagonal = kind.reshape(b, -1, steps)[:, :, 0] != NOTHING
    groups = block // sub
    met = int((kind != NOTHING).sum() - diagonal.sum()) * block * block
    met += int(diagonal.sum()) * sub * sub * (groups * (groups + 1) // 2)
    plain = int((kind == INTERIOR).sum()) * block * block
    if "sum_kind" in layout:
        tiles = (layout["sum_hi"] - layout["sum_lo"]).clip(0)
        met += int(tiles.sum()) * summary_tile * block
        plain += int((tiles * (layout["sum_kind"] == INTERIOR)).sum()) * summary_tile * block
    return met, plain


def eva_attention_dense(q, k, v, kbar, vbar, layout, heads: int):
    """The numerical definition, the path off the TPU and the tests'
    reference of the kernel (operands in its layouts): q (scaled), k, v
    [B, L, H*D]; kbar, vbar [B, C, H*D] or None; `layout` from
    `window_layout`.  Writes the f32 scores [B, H, L, L + C]."""
    import jax.numpy as jnp

    b, l, _ = q.shape
    split = lambda a: a.reshape(a.shape[0], a.shape[1], heads, -1)  # noqa: E731
    code = layout["code"]
    at = jnp.arange(l)
    s = jnp.einsum("bqhd,bkhd->bhqk", split(q), split(k),
                   preferred_element_type=jnp.float32)
    see = (code[:, :, None] == code[:, None, :]) & (at[None, None, :] <= at[None, :, None])
    values = split(v)
    if kbar is not None:
        s = jnp.concatenate([s, jnp.einsum(
            "bqhd,bkhd->bhqk", split(q), split(kbar), preferred_element_type=jnp.float32,
        )], axis=-1)
        own = code - code % SEG_STRIDE  # the document's window 0
        chunk_code = layout["chunk_code"][:, None, :]
        see = jnp.concatenate(
            [see, (chunk_code >= own[:, :, None]) & (chunk_code < code[:, :, None])], axis=-1
        )
        values = jnp.concatenate([values, split(vbar)], axis=1)
    s = jnp.where(see[:, None], s, NEG_INF)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), values,
                     preferred_element_type=jnp.float32)
    return ctx.reshape(b, l, -1).astype(q.dtype)


SUMMARY_CHUNKS = 16  # chunks a step of `pool_chunks` pools
ROW_TILE = 8  # rows of an HBM tile: where a copy of rows may begin


def _pool_kernel(start_ref, a_ref, mu_ref, k_hbm, v_hbm, kbar_ref, vbar_ref,
                 kbuf, vbuf, sems, *, span: int, head_dim: int):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t = pl.program_id(0), pl.program_id(1)
    n = kbar_ref.shape[1]  # chunks of this step

    def copies(j):
        at = pl.multiple_of(start_ref[b, t * n + j], ROW_TILE)
        into = pl.ds(j * span, span)
        return (
            pltpu.make_async_copy(k_hbm.at[b, pl.ds(at, span), :], kbuf.at[into, :], sems.at[0, j]),
            pltpu.make_async_copy(v_hbm.at[b, pl.ds(at, span), :], vbuf.at[into, :], sems.at[1, j]),
        )

    for j in range(n):  # a chunk's rows lie anywhere in the row: one copy a chunk
        for copy in copies(j):
            copy.start()
    for j in range(n):
        for copy in copies(j):
            copy.wait()
    for c0 in range(0, kbuf.shape[1], head_dim):
        weight = a_ref[0, :, c0 // head_dim:c0 // head_dim + 1]  # [n * span, 1] f32
        for buf, out_ref, offset in ((kbuf, kbar_ref, mu_ref[0, :, c0:c0 + head_dim]),
                                     (vbuf, vbar_ref, None)):
            rows = buf[:, c0:c0 + head_dim].astype(jnp.float32) * weight
            pooled = rows.reshape(n, span, head_dim).sum(axis=1)
            if offset is not None:
                pooled = pooled + offset
            out_ref[0, :, c0:c0 + head_dim] = pooled.astype(out_ref.dtype)


def pool_span(chunk: int) -> int:
    """Rows copied for a chunk of `chunk` rows that may begin anywhere: a
    copy begins on a tile of ROW_TILE rows, so up to ROW_TILE rows more."""
    return -(-chunk // ROW_TILE) * ROW_TILE + ROW_TILE


def pool_chunks(k, v, weights, mu, start, *, interpret=None):
    """(kbar, vbar) [B, C, H*D]: slot j of row b is the weighted sum of the
    `span` rows of k (plus mu) and of v from `start[b, j]`: kbar = sum_t
    weights[b, j * span + t, h] * k[b, start + t, head h] + mu[h].  k, v
    [B, L, H*D]; weights [B, C * span, H] f32; mu [H, D]; start [B, C]
    int32 (SMEM), multiples of ROW_TILE: a chunk's rows begin anywhere in
    its row, a copy on a tile's first, so the caller copies `pool_span`
    rows from the tile the chunk begins in and weighs the others 0.  k and
    v stay in HBM, each chunk is one copy into VMEM; XLA's gather of the
    same rows and its passes over them took 4.1 ms a layer at the ingest
    slab, 65 ms a dispatch."""
    return kernel_call("eva_pool_chunks", _pool, interpret=interpret)(k, v, weights, mu, start)


def _pool(k, v, weights, mu, start, *, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, width = k.shape
    slots = start.shape[1]
    heads, head_dim = mu.shape
    span = weights.shape[1] // slots
    n = math.gcd(SUMMARY_CHUNKS, slots)
    out_block = pl.BlockSpec((1, n, width), lambda i, t, *_: (i, t, 0), memory_space=pltpu.VMEM)
    out = jax.ShapeDtypeStruct((b, slots, width), k.dtype)
    return pl.pallas_call(
        functools.partial(_pool_kernel, span=span, head_dim=head_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, slots // n),
            in_specs=[
                pl.BlockSpec((1, n * span, heads), lambda i, t, *_: (i, t, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, width), lambda i, t, *_: (0, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[out_block, out_block],
            scratch_shapes=[
                pltpu.VMEM((n * span, width), k.dtype),
                pltpu.VMEM((n * span, width), v.dtype),
                pltpu.SemaphoreType.DMA((2, n)),
            ],
        ),
        out_shape=[out, out],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        name="eva_pool_chunks",
        interpret=interpret,
    )(start, weights, mu.astype(jnp.float32).reshape(1, 1, width), k, v)


ROPE_ROWS = 256  # rows of a slab a step of `rope` turns: 2 MB of bf16 at 4096 wide


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, head_dim: int, scale: float):
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    cos, sin = cos_ref[0], sin_ref[0]  # [rows, head_dim] f32
    for c0 in range(0, x_ref.shape[2], head_dim):
        x = x_ref[0, :, c0:c0 + head_dim].astype(jnp.float32)
        out = x * cos + pltpu.roll(x, head_dim // 2, axis=1) * sin
        o_ref[0, :, c0:c0 + head_dim] = (out * scale).astype(o_ref.dtype)


def rope(x, cos, sin, *, scale: float = 1.0, interpret=None):
    """RoPE on x [B, L, H*D] where its matmul left it, heads contiguous:
    the pair (x[i], x[i + D/2]) of every head turned by the row's angle,
    in f32, times `scale`.  cos, sin: [B, L, D] f32 = [cos | cos], [-sin |
    sin].  XLA does the same sums, but lays the slab out token-minor for
    them and copies it back for the attention kernel, five passes of f32
    a layer at the ingest slab; here a head's half-turn is one rotation of
    its lanes."""
    return kernel_call("eva_rope", _rope, scale=float(scale), interpret=interpret)(x, cos, sin)


def _rope(x, cos, sin, *, scale: float, interpret: bool):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, width = x.shape
    head_dim = cos.shape[2]
    rows = math.gcd(ROPE_ROWS, l)  # a row is whole lanes long: 128 or 256
    block = lambda cols: pl.BlockSpec(  # noqa: E731
        (1, rows, cols), lambda i, r: (i, r, 0), memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        functools.partial(_rope_kernel, head_dim=head_dim, scale=scale),
        grid=(b, l // rows),
        in_specs=[block(width), block(head_dim), block(head_dim)],
        out_specs=block(width),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        name="eva_rope",
        interpret=interpret,
    )(x, cos, sin)


def _kernel(*refs, head_dim: int, n_tok: int, sub: int, tile: int, summaries: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if summaries:
        (kind_ref, key_lo, sum_kind, sum_lo, sum_hi, codeq_ref, codek_ref, q_ref, k_ref, v_ref,
         codes_ref, kbar_ref, vbar_ref, o_ref, m_scr, l_scr, acc_scr, s_scr, v_scr, t_scr) = refs
    else:
        (kind_ref, key_lo, codeq_ref, codek_ref, q_ref, k_ref, v_ref,
         o_ref, m_scr, l_scr, acc_scr, s_scr, v_scr) = refs
    b, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    block = q_ref.shape[1]
    heads = q_ref.shape[2] // head_dim
    lanes = m_scr.shape[2]
    head = lambda h: slice(h * head_dim, (h + 1) * head_dim)  # noqa: E731
    # the diagonal's rows go in groups of `sub`, each against the keys up to
    # its own last row: (first row, last row + 1 = keys)
    groups = [(r0, r0 + sub) for r0 in range(0, block, sub)]

    def columns(s):
        return [s[:, at:at + lanes] for at in range(0, s.shape[1], lanes)]

    def score(h, r0, r1, keys, see):
        """First pass: rows [r0, r1) of head h against `keys` [n, D]: the
        scores, masked where `see` says so, and into the rows' maxima, a
        lane at a time."""
        s = jax.lax.dot_general(
            q_ref[0, r0:r1, head(h)], keys,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if see is not None:
            s = jnp.where(see, s, NEG_INF)
        m_scr[h, r0:r1] = functools.reduce(jnp.maximum, columns(s), m_scr[h, r0:r1])
        return s

    def weigh(h, r0, r1, s, values):
        """Second pass: the kept scores' exponentials against the rows'
        maxima, summed a lane at a time, and times `values` [n, D] into the
        context."""
        m = m_scr[h, r0:r1]
        p = [jnp.exp(column - m) for column in columns(s)]
        l_scr[h, r0:r1] += functools.reduce(jnp.add, p)
        p = (jnp.concatenate(p, axis=1) if len(p) > 1 else p[0]).astype(values.dtype)
        acc_scr[r0:r1, head(h)] += jnp.dot(p, values, preferred_element_type=jnp.float32)

    def summary_tiles(visit):
        """`visit(t, at)` for the block's tiles of summaries, [sum_lo,
        sum_hi) of the row's: tile t begins at slot `at`."""
        def a_tile(t, carry):
            visit(t, pl.multiple_of(t * tile, tile))
            return carry

        jax.lax.fori_loop(sum_lo[b, qi], sum_hi[b, qi], a_tile, 0)

    kind = kind_ref[b, qi * n_tok + j]

    @pl.when(j == 0)
    def _begin():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)

    # every query that is no padding meets itself: a block that meets
    # anything meets its diagonal, and summaries only with it
    @pl.when((j == 0) & (kind != NOTHING))
    def _diagonal():
        row = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        see = (codeq_ref[0] == codek_ref[0]) & (col <= row)
        v_scr[0] = v_ref[0]
        for h in range(heads):
            for r0, r1 in groups:
                s_scr[0, h, r0:r1, :r1] = score(h, r0, r1, k_ref[0, :r1, head(h)], see[r0:r1, :r1])

    for a_kind in (INTERIOR, EDGE):
        @pl.when((j > 0) & (kind == a_kind))
        def _left():
            see = None if a_kind == INTERIOR else codeq_ref[0] == codek_ref[0]
            v_scr[j] = v_ref[0]
            for h in range(heads):
                s_scr[j, h] = score(h, 0, block, k_ref[0, :, head(h)], see)

        if summaries:
            @pl.when((j == 0) & (sum_kind[b, qi] == a_kind))
            def _summaries():
                code = codeq_ref[0]  # [block, 1]
                own = code - code % SEG_STRIDE

                def visit(t, at):
                    see = None
                    if a_kind == EDGE:
                        chunk_code = codes_ref[0, t]  # [1, tile]
                        see = (chunk_code >= own) & (chunk_code < code)
                    for h in range(heads):
                        t_scr[t, h] = score(h, 0, block, kbar_ref[0, pl.ds(at, tile), head(h)], see)

                summary_tiles(visit)

    @pl.when(j == n_tok - 1)
    def _finish():
        for h in range(heads):  # a row's maximum: the lanes' maxima, in every lane
            m_scr[h] = jnp.broadcast_to(jnp.max(m_scr[h], axis=1, keepdims=True), m_scr.shape[1:])
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        @pl.when(kind_ref[b, qi * n_tok] != NOTHING)
        def _diagonal():
            for h in range(heads):
                for r0, r1 in groups:
                    weigh(h, r0, r1, s_scr[0, h, r0:r1, :r1], v_scr[0, :r1, head(h)])

        for step in range(1, n_tok):
            @pl.when(kind_ref[b, qi * n_tok + step] != NOTHING)
            def _left():
                for h in range(heads):
                    weigh(h, 0, block, s_scr[step, h], v_scr[step, :, head(h)])

        if summaries:
            @pl.when(sum_kind[b, qi] != NOTHING)
            def _summaries():
                def visit(t, at):
                    for h in range(heads):
                        weigh(h, 0, block, t_scr[t, h], vbar_ref[0, pl.ds(at, tile), head(h)])

                summary_tiles(visit)

        for h in range(heads):
            l = jnp.sum(l_scr[h], axis=1, keepdims=True)
            # a block of padding alone met nothing: zeros, not 0 / 0
            o_ref[0, :, head(h)] = (
                acc_scr[:, head(h)] / jnp.where(l > 0.0, l, 1.0)
            ).astype(o_ref.dtype)


def eva_attention(q, k, v, kbar, vbar, layout, heads: int, *, window: int,
                  block: int = KEY_TILE, summary_tile: int = SUMMARY_TILE,
                  sub_tile: int = SUB_TILE, head_block: int = HEAD_BLOCK, interpret=None):
    """The fused kernel.  q (scaled), k, v: [B, L, H*D]; kbar, vbar:
    [B, C, H*D], or None where `summary_slots` is 0; `layout`:
    `window_layout(seg, window, chunk, block=, summary_tile=)` of the same
    tiles.  Returns the context [B, L, H*D] in q's dtype.  `block`,
    `summary_tile`, `sub_tile` and `head_block` are for tests: the
    interpreter takes any tile.  Called, like `rope` and `pool_chunks`,
    through `kernel_call`: once a program (the package's note)."""
    names = ("code", "kind", "key_lo")
    if kbar is not None:
        names += ("chunk_code", "sum_kind", "sum_lo", "sum_hi")
    call = kernel_call(
        "eva_attention", _attend, heads=heads, window=window, block=block,
        summary_tile=summary_tile, sub_tile=sub_tile, head_block=head_block,
        interpret=interpret,
    )
    return call(q, k, v, kbar, vbar, {name: layout[name] for name in names})


def _attend(q, k, v, kbar, vbar, layout, *, heads, window, block, summary_tile, sub_tile,
            head_block, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, width = q.shape
    head_dim = width // heads
    block = row_block(l, block)
    sub = math.gcd(sub_tile, block)
    summaries = kbar is not None
    if (l > block and window % block) or heads % head_block:
        raise ValueError(
            f"eva_attention: unsupported shape L={l} heads={heads} window={window} "
            f"block={block}"
        )
    n_q = l // block
    n_tok = token_steps(l, window, block)
    cols = head_block * head_dim
    # maxima and sums are kept a lane apart: as many lanes as every tile has
    lanes = math.gcd(LANES, sub, summary_tile if summaries else sub)

    # index maps: grid indices, then the scalars prefetched to SMEM.  A step
    # that meets nothing maps to the last block met, already resident
    def queries(i, g, qi, j, *_):
        return (i, qi, g)

    def keys(i, g, qi, j, kind, key_lo, *_):
        return (i, jnp.maximum(qi - j, key_lo[i, qi]), g)

    def key_codes(i, g, qi, j, kind, key_lo, *_):
        return (i, 0, jnp.maximum(qi - j, key_lo[i, qi]))

    vmem = pltpu.VMEM
    code = layout["code"]
    scalars = [layout["kind"], layout["key_lo"]]
    operands = [code[:, :, None], code[:, None, :], q, k, v]
    in_specs = [
        pl.BlockSpec((1, block, 1), lambda i, g, qi, j, *_: (i, qi, 0), memory_space=vmem),
        pl.BlockSpec((1, 1, block), key_codes, memory_space=vmem),
        pl.BlockSpec((1, block, cols), queries, memory_space=vmem),
        pl.BlockSpec((1, block, cols), keys, memory_space=vmem),
        pl.BlockSpec((1, block, cols), keys, memory_space=vmem),
    ]
    scratch = [
        pltpu.VMEM((head_block, block, lanes), jnp.float32),  # maxima
        pltpu.VMEM((head_block, block, lanes), jnp.float32),  # sums
        pltpu.VMEM((block, cols), jnp.float32),  # context
        pltpu.VMEM((n_tok, head_block, block, block), jnp.float32),  # a step's scores
        pltpu.VMEM((n_tok, block, cols), v.dtype),  # and its values
    ]
    if summaries:
        # a row's summaries stay resident a head block long; a tile's codes
        # are a leading index away
        slots = kbar.shape[1]
        scalars += [layout["sum_kind"], layout["sum_lo"], layout["sum_hi"]]
        operands += [
            layout["chunk_code"].reshape(b, slots // summary_tile, 1, summary_tile), kbar, vbar,
        ]
        resident = pl.BlockSpec((1, slots, cols), lambda i, g, qi, j, *_: (i, 0, g),
                                memory_space=vmem)
        in_specs += [
            pl.BlockSpec((1, slots // summary_tile, 1, summary_tile),
                         lambda i, g, qi, j, *_: (i, 0, 0, 0), memory_space=vmem),
            resident, resident,
        ]
        scratch.append(  # a tile's scores
            pltpu.VMEM((slots // summary_tile, head_block, block, summary_tile), jnp.float32)
        )
    kernel = functools.partial(
        _kernel, head_dim=head_dim, n_tok=n_tok, sub=sub, tile=summary_tile,
        summaries=summaries,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, heads // head_block, n_q, n_tok),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block, cols), queries, memory_space=vmem),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, l, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        name="eva_attention",
        interpret=interpret,
    )(*scalars, *operands)

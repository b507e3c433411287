"""Chunked linear attention (EVA, prefill form) for packed slabs, as a Pallas
TPU kernel: `segment_attention.py`'s sibling for rows of thousands of slots.

A document's positions are cut into windows of `W` tokens and chunks of
`c`, both counted from the document's first token.  Query i in window w
scores, in ONE softmax, the keys t <= i of its own window exactly, and one
summary (k-bar_j, v-bar_j) for every chunk j of the document's earlier
windows.  A document may start at any slot of a row, so a window is no
fixed range of slots: `window_layout` works out, from the segment ids alone
and once for all layers, where each document's windows and summaries lie
and which blocks each block of queries has to meet.  Three kernels share it:
`rope` turns q and k where their matmuls left them, `pool_chunks` sums the
chunks into their summaries (`models/eva.py::chunk_summaries` weighs them),
and `eva_attention`, the rest of this note, scores them:

  * one grid step is one slab row, `HEAD_BLOCK` heads, one block of
    `block` query rows and ONE block of keys; the key blocks are the last
    grid axis and the running maximum, normaliser and context stay in VMEM
    across them (online softmax), so a row may be any number of blocks
    long and neither the scores nor the mask ever reach HBM;
  * a block of queries only meets the key blocks from its earliest
    window's first slot to its own diagonal (at most W/block + 1), then the
    blocks of summaries its documents own; which ones is data, read from
    SMEM before the step (`PrefetchScalarGridSpec`): a step that has
    nothing to meet maps to the block already resident and computes nothing;
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

from pathway_tpu.ops.kernels.flash_attention import NEG_INF

LANES = 128
# rows of a block of queries, and of a block of token keys: the key tile.
# `models/eva.py` buckets a row's length to whole tiles
KEY_TILE = 512
SUMMARY_TILE = 256  # summaries a step meets
HEAD_BLOCK = 4  # heads a grid step takes: 512 lanes of q, k, v and the output
# a token's code is `segment * SEG_STRIDE + window`; a row holds at most
# tokenizer.PACK_MAX_SEGMENTS documents and a document far fewer windows
SEG_STRIDE = 1 << 16


def supports(length: int, heads: int, head_dim: int, window: int, chunk: int) -> bool:
    """Static shapes the compiled kernel's tiling covers: heads of whole
    128-lane tiles, a row of whole key tiles (or one tile of whole lanes),
    windows of whole key tiles."""
    block = min(KEY_TILE, length)
    return (
        head_dim % LANES == 0
        and heads % HEAD_BLOCK == 0
        and length % block == 0
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


def window_layout(seg, window: int, chunk: int, *, block: int = KEY_TILE,
                  summary_tile: int = SUMMARY_TILE):
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
      sum_lo, sum_hi [B, L/block]       its summary blocks, [lo, hi)

    C is `summary_slots(L, ...)`; with C == 0 the summary entries are
    absent."""
    import jax.numpy as jnp

    from pathway_tpu.models.transformer import _packed_positions

    b, l = seg.shape
    block = min(block, l)
    at = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None, :], (b, l))
    pos = _packed_positions(seg)
    real = seg > 0
    win = pos // window
    code = jnp.where(real, seg * SEG_STRIDE + win, 0)
    window_first = at - pos % window  # the slot of a token's window's first token
    layout = {
        "pos": pos,
        "code": code,
        "key_lo": jnp.where(real, window_first, at).reshape(b, l // block, block).min(-1)
        // block,
    }
    slots = summary_slots(l, window, chunk, summary_tile)
    if not slots:
        return layout
    # a chunk gets a summary iff its window is followed by another window of
    # the same document: the first token of that one sits `window` slots on
    follows = window_first + window
    followed = jnp.take_along_axis(seg, jnp.minimum(follows, l - 1), axis=1)
    followed = real & (follows < l) & (followed == seg)
    starts = followed & (pos % chunk == 0)
    # the starts' slots, in row order, packed to the front
    order = jnp.sort(jnp.where(starts, at, l), axis=1)[:, :slots]
    if order.shape[1] < slots:
        order = jnp.pad(order, ((0, 0), (0, slots - order.shape[1])), constant_values=l)
    held = order < l
    start = jnp.where(held, order, l - chunk)
    layout["chunk_start"] = start
    layout["chunk_code"] = jnp.where(
        held, jnp.take_along_axis(code, start, axis=1), -1
    )
    # a query's summaries are those of its document's earlier windows:
    # `win * window / chunk` slots from the document's first
    before = jnp.cumsum(starts, axis=1, dtype=jnp.int32) - starts  # starts left of a slot
    doc_first = jnp.take_along_axis(before, at - pos, axis=1)
    sees = real & (win > 0)
    lo = jnp.where(sees, doc_first, slots)
    hi = jnp.where(sees, doc_first + win * (window // chunk), 0)
    lo = lo.reshape(b, l // block, block).min(-1) // summary_tile
    hi = -(-hi.reshape(b, l // block, block).max(-1) // summary_tile)
    layout["sum_lo"] = jnp.minimum(lo, slots // summary_tile - 1)
    layout["sum_hi"] = hi
    return layout


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
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, width = k.shape
    slots = start.shape[1]
    heads, head_dim = mu.shape
    span = weights.shape[1] // slots
    n = math.gcd(SUMMARY_CHUNKS, slots)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
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
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, width = x.shape
    head_dim = cos.shape[2]
    rows = math.gcd(ROPE_ROWS, l)  # a row is whole lanes long: 128 or 256
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block = lambda cols: pl.BlockSpec(  # noqa: E731
        (1, rows, cols), lambda i, r: (i, r, 0), memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        functools.partial(_rope_kernel, head_dim=head_dim, scale=float(scale)),
        grid=(b, l // rows),
        in_specs=[block(width), block(head_dim), block(head_dim)],
        out_specs=block(width),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        name="eva_rope",
        interpret=interpret,
    )(x, cos, sin)


def _kernel(*refs, head_dim: int, n_tok: int, block: int, summaries: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if summaries:
        (key_lo, sum_lo, sum_hi, codeq_ref, codek_ref, q_ref, k_ref, v_ref,
         codes_ref, kbar_ref, vbar_ref, o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        (key_lo, codeq_ref, codek_ref, q_ref, k_ref, v_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
    b, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    heads = q_ref.shape[2] // head_dim

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def meet(see, keys_ref, values_ref):
        """One online-softmax step of every head of the block over one
        block of keys.  A masked score is NEG_INF: while a row has met
        nothing its maximum is NEG_INF too and a masked key weighs 1, which
        the first key it does meet wipes out (alpha = 0), and every real
        query meets itself."""
        for h in range(heads):
            c0 = h * head_dim
            s = jax.lax.dot_general(
                q_ref[0, :, c0:c0 + head_dim], keys_ref[0, :, c0:c0 + head_dim],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            s = jnp.where(see, s, NEG_INF)
            m_prev = m_scr[h, :, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_scr[h, :, 0:1] + jnp.sum(p, axis=1, keepdims=True)
            values = values_ref[0, :, c0:c0 + head_dim]
            acc_scr[:, c0:c0 + head_dim] = alpha * acc_scr[:, c0:c0 + head_dim] + jnp.dot(
                p.astype(values.dtype), values, preferred_element_type=jnp.float32
            )
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    kb = key_lo[b, qi] + j

    @pl.when((j < n_tok) & (kb <= qi))
    def _tokens():
        row = qi * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        col = kb * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        meet((codeq_ref[0] == codek_ref[0]) & (col <= row), k_ref, v_ref)

    if summaries:
        @pl.when((j >= n_tok) & (sum_lo[b, qi] + j - n_tok < sum_hi[b, qi]))
        def _summaries():
            code = codeq_ref[0]  # [block, 1]
            chunk_code = codes_ref[0]  # [1, tile]
            own = code - code % SEG_STRIDE
            meet((chunk_code >= own) & (chunk_code < code), kbar_ref, vbar_ref)

    @pl.when(j == pl.num_programs(3) - 1)
    def _write():
        for h in range(heads):
            c0 = h * head_dim
            o_ref[0, :, c0:c0 + head_dim] = (
                acc_scr[:, c0:c0 + head_dim] / l_scr[h, :, 0:1]
            ).astype(o_ref.dtype)


def eva_attention(q, k, v, kbar, vbar, layout, heads: int, *, window: int,
                  block: int = KEY_TILE, summary_tile: int = SUMMARY_TILE,
                  head_block: int = HEAD_BLOCK, interpret=None):
    """The fused kernel.  q (scaled), k, v: [B, L, H*D]; kbar, vbar:
    [B, C, H*D], or None where `summary_slots` is 0; `layout`:
    `window_layout(seg, window, chunk, block=, summary_tile=)` of the same
    tiles.  Returns the context [B, L, H*D] in q's dtype.  `block`,
    `summary_tile` and `head_block` are for tests: the interpreter takes
    any tile."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, width = q.shape
    head_dim = width // heads
    block = min(block, l)
    summaries = kbar is not None
    if l % block or (l > block and window % block) or heads % head_block:
        raise ValueError(
            f"eva_attention: unsupported shape L={l} heads={heads} window={window} "
            f"block={block}"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_q = l // block
    n_tok = min(window // block + 1, n_q)  # a window's blocks and the diagonal
    n_sum = kbar.shape[1] // summary_tile if summaries else 0
    cols = head_block * head_dim

    # index maps: grid indices, then the scalars prefetched to SMEM
    def queries(i, g, qi, j, *_):
        return (i, qi, g)

    def keys(i, g, qi, j, key_lo, *_):
        return (i, jnp.minimum(key_lo[i, qi] + j, qi), g)

    def key_codes(i, g, qi, j, key_lo, *_):
        return (i, 0, jnp.minimum(key_lo[i, qi] + j, qi))

    def summary_block(i, qi, j, sum_lo, sum_hi):
        last = jnp.maximum(sum_hi[i, qi] - 1, sum_lo[i, qi])
        return jnp.clip(sum_lo[i, qi] + j - n_tok, sum_lo[i, qi], last)

    def summary_rows(i, g, qi, j, key_lo, sum_lo, sum_hi):
        return (i, summary_block(i, qi, j, sum_lo, sum_hi), g)

    def summary_codes(i, g, qi, j, key_lo, sum_lo, sum_hi):
        return (i, 0, summary_block(i, qi, j, sum_lo, sum_hi))

    vmem = pltpu.VMEM
    code = layout["code"]
    scalars = [layout["key_lo"]]
    operands = [code[:, :, None], code[:, None, :], q, k, v]
    in_specs = [
        pl.BlockSpec((1, block, 1), lambda i, g, qi, j, *_: (i, qi, 0), memory_space=vmem),
        pl.BlockSpec((1, 1, block), key_codes, memory_space=vmem),
        pl.BlockSpec((1, block, cols), queries, memory_space=vmem),
        pl.BlockSpec((1, block, cols), keys, memory_space=vmem),
        pl.BlockSpec((1, block, cols), keys, memory_space=vmem),
    ]
    if summaries:
        scalars += [layout["sum_lo"], layout["sum_hi"]]
        operands += [layout["chunk_code"][:, None, :], kbar, vbar]
        in_specs += [
            pl.BlockSpec((1, 1, summary_tile), summary_codes, memory_space=vmem),
            pl.BlockSpec((1, summary_tile, cols), summary_rows, memory_space=vmem),
            pl.BlockSpec((1, summary_tile, cols), summary_rows, memory_space=vmem),
        ]
    kernel = functools.partial(
        _kernel, head_dim=head_dim, n_tok=n_tok, block=block, summaries=summaries,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, heads // head_block, n_q, n_tok + n_sum),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block, cols), queries, memory_space=vmem),
            scratch_shapes=[
                pltpu.VMEM((head_block, block, LANES), jnp.float32),  # running max
                pltpu.VMEM((head_block, block, LANES), jnp.float32),  # normaliser
                pltpu.VMEM((block, cols), jnp.float32),  # context
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, l, width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        name="eva_attention",
        interpret=interpret,
    )(*scalars, *operands)

"""Causal grouped-query attention for packed slabs whose rows are thousands
of slots long, global or behind a sliding window, with or without a sink
logit, as ONE Pallas TPU kernel: `mla_attention.py`'s sibling for key axes
of many tiles and for key/value heads that several query heads share.

Two layouts of a head, over 128-wide values; `group = heads / kv_heads`
query heads read ONE key/value head:

  * two operands (heads of 192, MiMo-V2.5's): a score is `q_nope .
    k_nope` (128 wide) plus `q_rope . k_rope` (64 wide, rotated);
  * one operand (heads of 128, Laguna-XS.2's): a score is `q . k`, 128
    wide, the rotated dims inside it wherever the caller turned them (the
    first 64 of a global layer's head, all 128 of a window layer's), so
    `q_rope` and `k_rope` are None; a group may be any size a step's
    heads divide (6 or 8), and the device ops are named
    `laguna_attention_global` / `laguna_attention_window`.
  Token i sees token j iff
both lie in the same document and `j <= i` (global) or `i - window < j <=
i` (window): documents are contiguous in a row, so the distance in
positions is the distance in slots.  With a sink, a learned logit `b_h` a
query head joins the softmax's denominator and mixes nothing:

    p_ij = exp(s_ij - m) / (sum_j exp(s_ij - m) + exp(b_h - m))

  * a grid step is one slab row, `head_block` query heads of one group and
    one block of `block` query rows; neither the scores nor the mask ever
    reach HBM, so a row may be any number of blocks long;
  * a window layer takes ONE step a block of queries, over every key its
    window reaches: its own block of keys and the `views - 1` before it
    (`window_tiling`: 2 for a window of 128 over blocks of 128, 5 for
    512), each a view of the same arrays.  The step's scores, [stacked
    rows, views x block] f32, stay in VMEM and the softmax over them is
    plain: one maximum, one sum, one `p @ v`, one division, no running
    state.  A sink joins the maximum and the sum: m = max(b_h, max_j
    s_ij), l = exp(b_h - m) + sum_j exp(s_ij - m);
  * a global layer walks its key blocks as the last grid axis, the running
    maximum, normaliser and context kept in VMEM across them (online
    softmax; a sink is where they begin, m = b_h and l = 1, instead of
    (-inf, 0)).  A block of queries only meets the key blocks from
    `key_lo`, the first block of its earliest document, to its own
    diagonal.  Which block that is is data, read from SMEM before the step
    (`PrefetchScalarGridSpec`): a step past the diagonal maps to the block
    already resident and computes nothing;
  * the step's heads share the key and value blocks it loaded and the
    mask it computed: the keys of a global layer are read once for 8 query
    heads, not once a head.  Their queries are laid one under the other in
    VMEM once a step, each head's row as [nope | its rope part] (or its one
    operand), so a score is ONE product 256 (128) deep against [k_nope |
    k_rope] (k), and a pass of
    the softmax takes PASS_ROWS rows of them at a time: the eight heads of
    a window layer's step are one pass of [1024, views x 128] scores, not
    eight products that each pay the MXU's fill and drain;
  * operands are read where their matmuls left them, heads contiguous:
    `q_nope` [B, L, H*128], `q_rope` [B, L, H*64], `k_nope`, `v` [B, L,
    KV*128], and the context is written straight into [B, L, H*128] for the
    out-projection (or the gate before it).  Every load, matmul and store is
    a full 128-lane tile: two heads' rope queries share a tile, a head is
    picked out of it by
    zeroing the other's lanes, against the group's rope key laid twice
    along the lanes (`mla_attention.py`'s way; the products that drop out
    are exact zeros).

`rope` turns the 64-wide rope parts where their matmuls left them.

Numerics are the dense definition's (`hybrid_attention_dense`): q arrives
scaled and rotated, scores accumulate in f32 from operands in the compute
dtype, the softmax is f32, p is cast to the compute dtype for `p @ v`,
which accumulates in f32 and is normalised there.  Rows with segment 0
(padding) come out finite.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

from pathway_tpu.ops.kernels import kernel_call
from pathway_tpu.ops.kernels.flash_attention import NEG_INF

LANES = 128
NOPE_DIM = 128  # the tiling below is written for these three widths
ROPE_DIM = 64
V_DIM = 128
# query heads a grid step takes: 8 heads are 1,024 lanes of q_nope and of
# the output, 512 of q_rope; one group of a window layer, half a group of
# a global one
HEAD_BLOCK = 8
# rows of a block of queries and of keys, by kind.  A global layer's row
# state (maximum, normaliser, context) is rescaled once a key block, four
# 128-lane passes a row whatever the block's width: blocks of 1,024 score
# 171 G pairs/s at the ingest slab where blocks of 512 score 108 and of 256
# 76 (chip runs, PR 36), and eight heads' state still fits VMEM beside one
# head's [1024, 1024] scores.  A window layer's block is 128 rows: a query
# block meets the blocks its window reaches, two for a window of 128, of
# which half the pairs count (blocks of 256 meet four times the pairs that
# count and are no faster)
GLOBAL_BLOCK = 1024
WINDOW_BLOCK = 128
# keys a window layer's step scores at most (views x block): its eight
# heads' scores are then [1024, 1024] f32 at most, the global kind's size
WINDOW_KEYS = 1024
# rows of the step's stacked heads that one softmax pass takes: the eight
# heads of a window layer's step at once (two views, [1024, 256] scores: as
# eight products of 128 x 128, each paying a fill and a drain, a layer took
# 13.8 ms at the ingest slab for 7.6; five views, [1024, 640]: 4.1 ms a
# layer at a row of 23,552 slots, where passes of 512 rows take 4.3 and of
# 256 4.5; TPU v5e), one head of a global layer's ([1024, 1024])
PASS_ROWS = 1024
VMEM_LIMIT = 64 * 1024 * 1024


def block_rows(length: int, window: Optional[int]) -> int:
    """Rows of a block of queries (and of keys) for a row of `length`."""
    return min(length, GLOBAL_BLOCK if window is None else WINDOW_BLOCK)


def window_tiling(length: int, window: int, block: Optional[int] = None) -> tuple:
    """(rows of a block, blocks of queries, key views a step) of a window
    layer over a row of `length`: a block of queries takes its own block
    of keys and every block the `window - 1` slots before its first row
    reach into, no more than the row has.  `block` is for tests."""
    block = block_rows(length, window) if block is None else min(block, length)
    n_q = length // block
    return block, n_q, min(-(-(window - 1) // block) + 1, n_q)


def supports(length: int, heads: int, kv_heads: int, nope_dim: int, rope_dim: int,
             v_dim: int, window: Optional[int] = None) -> bool:
    """Static shapes the compiled kernel's tiling covers: (nope, rope, v)
    = (128, 64, 128), two operands, a step's heads an even number; or
    (128, 0, 128), one operand (`rope_dim` 0: the rotated dims are inside
    the 128); a window whose step scores at most `WINDOW_KEYS` keys."""
    block = block_rows(length, window)
    group = heads // max(kv_heads, 1)
    split = (nope_dim, rope_dim, v_dim) == (NOPE_DIM, ROPE_DIM, V_DIM)
    return (
        (split or (nope_dim, rope_dim, v_dim) == (NOPE_DIM, 0, V_DIM))
        and kv_heads > 0
        and heads % kv_heads == 0
        and group % min(HEAD_BLOCK, group) == 0
        and (not split or min(HEAD_BLOCK, group) % 2 == 0)  # two heads' rope queries a tile
        and length % block == 0
        and block % LANES == 0
        and (window is None or window_tiling(length, window)[2] * block <= WINDOW_KEYS)
    )


def key_lo(seg, pos, block: int):
    """[B, L / block] int32: the first key block a block of queries of a
    global layer meets, its earliest document's first.  seg, pos: [B, L],
    the segment ids and `trunk.packed_positions(seg)`."""
    import jax.numpy as jnp

    b, l = seg.shape
    at = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None, :], (b, l))
    first = jnp.where(seg > 0, at - pos, at)  # the slot of the document's first token
    return first.reshape(b, l // block, block).min(-1) // block


def hybrid_attention_dense(q_nope, q_rope, k_nope, k_rope, v, seg, *, kv_heads: int,
                           window: Optional[int] = None, sink=None):
    """The numerical definition, the path off the TPU and the tests'
    reference of the kernel (operands in its layouts; one operand: q_rope
    and k_rope None).  Writes the f32 scores [B, H, L, L]."""
    import jax.numpy as jnp

    b, l, _ = q_nope.shape
    group = q_nope.shape[2] // k_nope.shape[2]  # the two operands' widths are alike
    q = lambda a: a.reshape(b, l, kv_heads, group, -1)  # noqa: E731
    kv = lambda a: a.reshape(b, l, kv_heads, -1)  # noqa: E731
    s = jnp.einsum(
        "bqngd,bknd->bngqk", q(q_nope), kv(k_nope), preferred_element_type=jnp.float32
    )
    if q_rope is not None:
        s = s + jnp.einsum(
            "bqngd,bknd->bngqk", q(q_rope), kv(k_rope), preferred_element_type=jnp.float32
        )
    at = jnp.arange(l)
    see = (seg[:, :, None] == seg[:, None, :]) & (at[None, None, :] <= at[None, :, None])
    if window is not None:
        see = see & (at[None, :, None] - at[None, None, :] < window)
    s = jnp.where(see[:, None, None], s, NEG_INF)
    m = s.max(-1, keepdims=True)
    if sink is not None:
        logit = sink.astype(jnp.float32).reshape(1, kv_heads, group, 1, 1)
        m = jnp.maximum(m, logit)
    p = jnp.exp(s - m)
    denom = p.sum(-1, keepdims=True)
    if sink is not None:
        denom = denom + jnp.exp(logit - m)
    ctx = jnp.einsum(
        "bngqk,bknd->bqngd", p.astype(v.dtype), kv(v), preferred_element_type=jnp.float32
    ) / denom.transpose(0, 3, 1, 2, 4)
    return ctx.reshape(b, l, -1).astype(q_nope.dtype)


def _kernel(*refs, block: int, window: Optional[int], views: int, has_sink: bool,
            split: bool):
    """Both kinds' body: a global layer's step of its walk from `key_lo`
    (online softmax), or a window layer's one step over its `views` key
    blocks (plain softmax)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    lo_ref = sink_ref = None
    if window is None:  # the scalar prefetched to SMEM
        lo_ref, refs = refs[0], refs[1:]
    segq_ref, code_refs, refs = refs[0], refs[1:1 + views], refs[1 + views:]
    if has_sink:
        sink_ref, refs = refs[0], refs[1:]
    n_qs, per_view = (2, 3) if split else (1, 2)  # q (, q_rope); k (, k_rope), v
    q_refs, refs = refs[:n_qs], refs[n_qs:]
    key_refs = [refs[i * per_view:(i + 1) * per_view] for i in range(views)]
    o_ref, q_scr, *state = refs[views * per_view:]
    b, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    heads = q_refs[0].shape[2] // NOPE_DIM
    if window is not None:
        _stack_queries(q_scr, q_refs, heads, block)
        _window_step(segq_ref, code_refs, sink_ref, key_refs, o_ref, q_scr, qi=qi,
                     heads=heads, block=block, window=window)
        return
    m_scr, l_scr, acc_scr = state
    (view,), (segk_ref,) = key_refs, code_refs
    a_pass = min(heads, max(PASS_ROWS // block, 1))  # heads whose rows one pass takes

    @pl.when(j == 0)
    def _init():
        # the step's queries, once for all its key blocks
        _stack_queries(q_scr, q_refs, heads, block)
        if has_sink:
            for h in range(heads):
                m_scr[h * block:(h + 1) * block] = jnp.broadcast_to(
                    sink_ref[h:h + 1, :], (block, LANES)
                )
            l_scr[...] = jnp.ones_like(l_scr)
        else:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kb = lo_ref[b, qi] + j

    @pl.when(kb <= qi)
    def _meet():
        # one online-softmax step of every head of the block over the
        # step's keys.  A masked score is NEG_INF: while a row has met
        # nothing (no sink) its maximum is NEG_INF too and a masked key
        # weighs 1, which the first key it does meet wipes out (alpha = 0),
        # and every query meets itself
        keys, values = _keys(view), view[-1][0]
        row = qi * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
        col = kb * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        see = (segq_ref[0] == segk_ref[0]) & (col <= row)
        see = jnp.concatenate([see] * a_pass, axis=0) if a_pass > 1 else see
        for r0 in range(0, heads * block, a_pass * block):
            rows = slice(r0, r0 + a_pass * block)
            s = jax.lax.dot_general(
                q_scr[rows], keys, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            s = jnp.where(see, s, NEG_INF)
            m_prev = m_scr[rows, 0:1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_scr[rows, 0:1] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[rows] = alpha * acc_scr[rows] + jnp.dot(
                p.astype(values.dtype), values, preferred_element_type=jnp.float32
            )
            m_scr[rows] = jnp.broadcast_to(m_new, (a_pass * block, LANES))
            l_scr[rows] = jnp.broadcast_to(l_new, (a_pass * block, LANES))

    @pl.when(j == pl.num_programs(3) - 1)
    def _write():
        for h in range(heads):
            rows = slice(h * block, (h + 1) * block)
            o_ref[0, :, h * V_DIM:(h + 1) * V_DIM] = (
                acc_scr[rows] / l_scr[rows, 0:1]
            ).astype(o_ref.dtype)


def _keys(view):
    """A view's keys [block, 256] ([k_nope | k_rope]), or [block, 128] for
    one operand; `view` is its (k_nope, (k_rope,) v) refs."""
    import jax.numpy as jnp

    kn, *kr, _ = view
    return jnp.concatenate([kn[0], kr[0][0]], axis=1) if kr else kn[0]


def _stack_queries(q_scr, q_refs, heads: int, block: int):
    """The step's queries one head under the other: a head's rows [nope |
    its rope part, the tile's other head zeroed], or its one operand."""
    import jax
    import jax.numpy as jnp

    lane_half = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // ROPE_DIM
    for h in range(heads):
        rows = slice(h * block, (h + 1) * block)
        q_scr[rows, :NOPE_DIM] = q_refs[0][0, :, h * NOPE_DIM:(h + 1) * NOPE_DIM]
        if len(q_refs) == 2:
            tile = q_refs[1][0, :, (h // 2) * LANES:(h // 2 + 1) * LANES]
            q_scr[rows, NOPE_DIM:] = jnp.where(lane_half == h % 2, tile, jnp.zeros_like(tile))


def _window_step(segq_ref, code_refs, sink_ref, key_refs, o_ref, q_scr, *, qi, heads: int,
                 block: int, window: int):
    """Every head of a block of queries over every key its window reaches:
    the views one after the other along the key axis, the earliest first
    (a view before block 0 shows block 0, masked), the scores of a pass's
    heads [rows, views x block] f32 in VMEM, one softmax over them."""
    import jax
    import jax.numpy as jnp

    views = len(key_refs)
    a_pass = min(heads, max(PASS_ROWS // block, 1))  # heads whose rows one pass takes
    keys = jnp.concatenate([_keys(view) for view in key_refs], axis=0)
    values = jnp.concatenate([view[-1][0] for view in key_refs], axis=0)
    codes = jnp.concatenate([code[0] for code in code_refs], axis=1)
    width = views * block
    row = qi * block + jax.lax.broadcasted_iota(jnp.int32, (block, width), 0)
    col = (qi - views + 1) * block + jax.lax.broadcasted_iota(jnp.int32, (block, width), 1)
    see = (segq_ref[0] == codes) & (col <= row) & (row - col < window) & (col >= 0)
    see = jnp.concatenate([see] * a_pass, axis=0) if a_pass > 1 else see
    for h0 in range(0, heads, a_pass):
        rows = slice(h0 * block, (h0 + a_pass) * block)
        s = jax.lax.dot_general(
            q_scr[rows], keys, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # every query sees itself, so a row's maximum is a score it sees
        s = jnp.where(see, s, NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        if sink_ref is not None:
            logit = jnp.concatenate([
                jnp.broadcast_to(sink_ref[h:h + 1, 0:1], (block, 1))
                for h in range(h0, h0 + a_pass)
            ], axis=0)
            m = jnp.maximum(logit, m)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        if sink_ref is not None:
            l = jnp.exp(logit - m) + l
        ctx = jnp.dot(p.astype(values.dtype), values, preferred_element_type=jnp.float32) / l
        for h in range(h0, h0 + a_pass):
            o_ref[0, :, h * V_DIM:(h + 1) * V_DIM] = (
                ctx[(h - h0) * block:(h - h0 + 1) * block].astype(o_ref.dtype)
            )


def hybrid_attention(q_nope, q_rope, k_nope, k_rope, v, seg, lo, *, kv_heads: int,
                     window: Optional[int] = None, sink=None,
                     block: Optional[int] = None, interpret=None):
    """The fused kernel.  q_nope [B, L, H*128], q_rope [B, L, H*64] (scaled,
    rotated); k_nope, v [B, L, KV*128]; k_rope [B, L, KV*64] (rotated); seg
    [B, L] int32, 1..S per packed document, 0 = padding; lo: `key_lo(seg,
    pos, block)` of a global layer, None for a window layer (its steps
    need no data); sink [H] or None.  One operand: q_nope and k_nope
    are q [B, L, H*128] (scaled) and k [B, L, KV*128], both rotated where
    they are, q_rope and k_rope None.  Returns the context [B, L, H*128] in
    q_nope's dtype.  The device op is named by layout and kind
    (`op_name`).  `block` is for tests: the interpreter takes any tile."""
    name = op_name(q_rope is not None, window)
    call = kernel_call(
        name, _attend, kv_heads=kv_heads, window=window, block=block, interpret=interpret,
    )
    return call(q_nope, q_rope, k_nope, k_rope, v, seg, lo, sink)


def op_name(split: bool, window: Optional[int]) -> str:
    """The device op of a layout and a kind: `hybrid_attention_window` /
    `_global` (two operands), `laguna_attention_window` / `_global` (one)."""
    return ("hybrid_attention_" if split else "laguna_attention_") + (
        "global" if window is None else "window"
    )


def _attend(q_nope, q_rope, k_nope, k_rope, v, seg, lo, sink, *, kv_heads: int,
            window: Optional[int], block: Optional[int], interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, width = q_nope.shape
    split = q_rope is not None
    heads = width // NOPE_DIM
    group = heads // kv_heads
    head_block = min(HEAD_BLOCK, group)
    block = block_rows(l, window) if block is None else min(block, l)
    if l % block or heads % kv_heads or group % head_block or (split and head_block % 2):
        raise ValueError(
            f"hybrid_attention: unsupported shape L={l} heads={heads} "
            f"kv_heads={kv_heads} block={block}"
        )
    n_q = l // block
    blocks_a_group = group // head_block
    # index maps: grid indices, then (a global layer) the scalar prefetched
    # to SMEM.  A window layer: ONE step a block of queries, its key views
    # `qi - views + 1 .. qi` clamped at block 0; a global layer: a step a
    # key block, from `key_lo` to the diagonal (past it: the diagonal again)
    if window is None:
        views, n_k = 1, n_q
        at = [lambda i, qi, j, lo: jnp.minimum(lo[i, qi] + j, qi)]
    else:
        _, _, views = window_tiling(l, window, block)
        n_k = 1
        at = [
            lambda i, qi, j, back=views - 1 - view: jnp.maximum(qi - back, 0)
            for view in range(views)
        ]

    def queries(i, g, qi, j, *lo):
        return (i, qi, g)

    vmem = pltpu.VMEM
    seg = seg.astype(jnp.int32)
    if split:
        # the group's rope key laid twice along the lanes: [B, L, KV*128]
        k_rope2 = jnp.concatenate(
            [k_rope.reshape(b, l, kv_heads, ROPE_DIM)] * 2, axis=-1
        ).reshape(b, l, kv_heads * LANES)
        q_ops, key_ops = [q_nope, q_rope], [k_nope, k_rope2, v]
        q_specs = [
            pl.BlockSpec((1, block, head_block * NOPE_DIM), queries, memory_space=vmem),
            pl.BlockSpec((1, block, head_block * ROPE_DIM), queries, memory_space=vmem),
        ]
    else:
        q_ops, key_ops = [q_nope], [k_nope, v]
        q_specs = [
            pl.BlockSpec((1, block, head_block * NOPE_DIM), queries, memory_space=vmem),
        ]
    key_widths = [NOPE_DIM, LANES, V_DIM] if split else [NOPE_DIM, V_DIM]
    operands = [seg[:, :, None]] + [seg[:, None, :]] * views
    in_specs = [
        pl.BlockSpec((1, block, 1), lambda i, g, qi, j, *lo: (i, qi, 0), memory_space=vmem),
        *[
            pl.BlockSpec((1, 1, block), lambda i, g, qi, j, *lo, kb=kb: (i, 0, kb(i, qi, j, *lo)),
                         memory_space=vmem)
            for kb in at
        ],
    ]
    if sink is not None:
        operands.append(jnp.broadcast_to(sink.astype(jnp.float32)[:, None], (heads, LANES)))
        in_specs.append(
            pl.BlockSpec((head_block, LANES), lambda i, g, qi, j, *lo: (g, 0), memory_space=vmem)
        )
    operands += q_ops + key_ops * views
    in_specs += q_specs + [
        pl.BlockSpec(
            (1, block, w),
            lambda i, g, qi, j, *lo, kb=kb: (i, kb(i, qi, j, *lo), g // blocks_a_group),
            memory_space=vmem,
        )
        for kb in at for w in key_widths
    ]
    kernel = functools.partial(
        _kernel, block=block, window=window, views=views, has_sink=sink is not None,
        split=split,
    )
    stacked = head_block * block  # the step's heads, one under the other
    scratch = [pltpu.VMEM((stacked, NOPE_DIM + (LANES if split else 0)), q_nope.dtype)]
    if window is None:
        scratch += [
            pltpu.VMEM((stacked, LANES), jnp.float32),  # running max
            pltpu.VMEM((stacked, LANES), jnp.float32),  # normaliser
            pltpu.VMEM((stacked, V_DIM), jnp.float32),  # context
        ]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(window is None),
            grid=(b, heads // head_block, n_q, n_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, block, head_block * V_DIM), queries, memory_space=vmem
            ),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, l, heads * V_DIM), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        name=op_name(split, window),
        interpret=interpret,
    )(*([lo] if window is None else []), *operands)


ROPE_ROWS = 256  # rows of a slab a step of `rope` turns


def rope_tables(pos, theta: float):
    """(cos, sin) [B, L, 128] f32 for `rope` and `rotate`: the 32 angles of
    a 64-wide rope part, position x theta^(-2i/64), laid out for the pair
    (x[i], x[i + 32]) as [cos | cos] and [-sin | sin], twice along the
    lanes (two heads share a tile)."""
    import jax.numpy as jnp

    half = ROPE_DIM // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angle = pos[:, :, None].astype(jnp.float32) * freqs  # [B, L, 32]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return (jnp.concatenate([cos, cos] * 2, -1), jnp.concatenate([-sin, sin] * 2, -1))


def rotate(x, cos, sin, scale: float = 1.0):
    """`rope`'s definition, and the path off the TPU: x [B, L, n*64], every
    64-wide part's pair (x[i], x[i + 32]) turned by the row's angle in f32,
    times `scale`.  cos, sin: `rope_tables`."""
    import jax.numpy as jnp

    b, l, _ = x.shape
    half = ROPE_DIM // 2
    parts = x.reshape(b, l, -1, ROPE_DIM).astype(jnp.float32)
    turned = jnp.roll(parts, half, axis=-1)  # [x[i + 32] | x[i]] with the signs in sin
    out = parts * cos[:, :, None, :ROPE_DIM] + turned * sin[:, :, None, :ROPE_DIM]
    return (out * scale).reshape(x.shape).astype(x.dtype)


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, scale: float):
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    cos, sin = cos_ref[0], sin_ref[0]  # [rows, 128] f32
    half = ROPE_DIM // 2
    first = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) % ROPE_DIM < half
    for c0 in range(0, x_ref.shape[2], LANES):
        x = x_ref[0, :, c0:c0 + LANES].astype(jnp.float32)
        # lane i of a part's first half takes x[i + 32], of its second x[i - 32]
        turned = jnp.where(
            first, pltpu.roll(x, LANES - half, axis=1), pltpu.roll(x, half, axis=1)
        )
        o_ref[0, :, c0:c0 + LANES] = ((x * cos + turned * sin) * scale).astype(o_ref.dtype)


def rope(x, cos, sin, *, scale: float = 1.0, interpret=None):
    """RoPE on the 64-wide rope parts x [B, L, n*64] where their matmul
    left them (n even: two parts a 128-lane tile): `rotate`, as a kernel.
    XLA does the same sums over a [.., n, 64] view whose minor axis is half
    a tile."""
    if x.shape[2] % LANES:  # an odd part has no tile to itself (one key head: tests)
        return rotate(x, cos, sin, scale)
    return kernel_call("hybrid_rope", _rope, scale=float(scale), interpret=interpret)(x, cos, sin)


def _rope(x, cos, sin, *, scale: float, interpret: bool):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, width = x.shape
    rows = math.gcd(ROPE_ROWS, l)
    block = lambda cols: pl.BlockSpec(  # noqa: E731
        (1, rows, cols), lambda i, r: (i, r, 0), memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        functools.partial(_rope_kernel, scale=scale),
        grid=(b, l // rows),
        in_specs=[block(width), block(LANES), block(LANES)],
        out_specs=block(width),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        name="hybrid_rope",
        interpret=interpret,
    )(x, cos, sin)

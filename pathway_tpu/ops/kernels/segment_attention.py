"""Segment-aware fused attention for packed slabs, as a Pallas TPU kernel.

The packed ingest path (`models/transformer.py::forward(seg=...)`) holds
several documents in one slab row and confines attention within each by
segment id.  The dense definition (`_segment_attention`) writes the f32
score matrix [B, H, L, L] to HBM and reads it back twice.  This kernel
keeps scores, mask, softmax and `p @ v` of one slab row in VMEM:

  * one grid step is one slab row and a block of `block_w` columns of the
    hidden axis (all its heads); the whole key axis (L <= 512) is one
    tile, so there is no online-softmax rescaling;
  * q, k and v are read where the QKV matmul left them — three column
    blocks of `qkv` [B, L, 3·hidden] — and the context is written
    straight into [B, L, hidden] for the out-projection: no
    [B,L,H,hd] -> [B,H,L,hd] transposes on either side;
  * every load, matmul and store is a full 128-lane tile.  A head
    narrower than 128 lanes is picked out of its tile by zeroing the
    other heads' lanes of q (the products that drop out are exact zeros,
    and the MXU pass costs what a half-filled contraction costs anyway)
    and by keeping only its lanes of `p @ v`;
  * L is padded to the tile inside the call: the blocks overrun the
    array (504 -> 512), the overrun rows of k and v are zeroed in VMEM,
    and the overrun rows of the output are never written.

Numerics are `_segment_attention`'s: token i attends to token j iff
seg[i] == seg[j] > 0; scores accumulate in f32 from operands in the
compute dtype, the softmax is f32, p is cast to the compute dtype for
`p @ v`, which accumulates in f32 and is normalised there.  Rows with
seg == 0 come out finite (a uniform mix of v), as in the dense path.
"""

from __future__ import annotations

import functools

from pathway_tpu.ops.kernels import kernel_call
from pathway_tpu.ops.kernels.flash_attention import NEG_INF

LANES = 128
# the whole key axis is one tile: scores of one head and q block are
# [block_q, L] f32 in VMEM.  Measured up to 512 (the packed path's cap)
MAX_LEN = 512


def supports(length: int, hidden: int, head_dim: int) -> bool:
    """Static shapes the kernel's tiling covers: heads that tile a
    128-lane block, a hidden axis made of whole such blocks, and a key
    axis that fits one tile."""
    return (
        length <= MAX_LEN
        and hidden % LANES == 0
        and 0 < head_dim <= LANES
        and LANES % head_dim == 0
    )


# query rows a step works on at a time.  At the e5 slab [440,16,504,64],
# whole-width steps: 128 rows 7.3 ms, 256 rows 4.96 ms, 512 rows 4.83 ms
# (chip runs, PR 28); at MiniLM's [320,12,256,32] 128 and 256 tie
BLOCK_Q = 256


def _block_w(hidden: int) -> int:
    """Columns of the hidden axis a grid step takes: the widest whole
    number of 128-lane tiles up to 512 that divides it.  At the e5 slab
    [440,16,504,64] a step of 1024 columns ran 4.96 ms, of 512 5.28 ms,
    of 256 5.75 ms, of 128 6.95 ms (chip runs, PR 28), and took 6.5 /
    4.2 / 2.9 / 2.6 s to compile, the body being unrolled over the
    step's heads: 512 keeps most of the speed for two thirds of the
    compile time and half the VMEM."""
    tiles = hidden // LANES
    widest = max(t for t in range(1, min(tiles, 4) + 1) if tiles % t == 0)
    return widest * LANES


def _kernel(segq_ref, segk_ref, q_ref, k_ref, v_ref, o_ref, *,
            head_dim: int, sm_scale: float, length: int, block_q: int):
    import jax
    import jax.numpy as jnp

    lp, width = q_ref.shape[1], q_ref.shape[2]
    lane_head = (
        jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // head_dim
    )
    if length < lp:
        # rows past the array's end hold whatever VMEM held: a zero
        # weight does not silence a NaN in v, so they are zeroed
        row_ok = jax.lax.broadcasted_iota(jnp.int32, (lp, 1), 0) < length
    sk = segk_ref[0]  # [1, lp]
    for r0 in range(0, lp, block_q):
        sq = segq_ref[0, r0:r0 + block_q, :]  # [block_q, 1]
        same = (sq == sk) & (sq > 0)  # [block_q, lp], shared by the heads
        for c0 in range(0, width, LANES):
            q = q_ref[0, r0:r0 + block_q, c0:c0 + LANES]
            k = k_ref[0, :, c0:c0 + LANES]
            v = v_ref[0, :, c0:c0 + LANES]
            if length < lp:
                k = jnp.where(row_ok, k, jnp.zeros_like(k))
                v = jnp.where(row_ok, v, jnp.zeros_like(v))
            out = jnp.zeros((block_q, LANES), jnp.float32)
            for h in range(LANES // head_dim):
                mine = lane_head == h
                s = jax.lax.dot_general(
                    jnp.where(mine, q, jnp.zeros_like(q)), k,
                    dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * sm_scale
                s = jnp.where(same, s, NEG_INF)
                p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
                denom = jnp.sum(p, axis=1, keepdims=True)  # >= 1
                pv = jnp.dot(
                    p.astype(v.dtype), v, preferred_element_type=jnp.float32
                )
                out = jnp.where(mine, pv / denom, out)
            o_ref[0, r0:r0 + block_q, c0:c0 + LANES] = out.astype(o_ref.dtype)


def segment_attention(qkv, seg, heads: int, *, interpret=None):
    """Fused packed-slab attention.  qkv: [B, L, 3·hidden] as the QKV
    matmul leaves it (q | k | v along the last axis, heads contiguous
    within each); seg: [B, L] int, 1..S per packed document, 0 = padding.
    Returns the context [B, L, hidden] in qkv's dtype."""
    call = kernel_call("segment_attention", _attend, heads=heads, interpret=interpret)
    return call(qkv, seg)


def _attend(qkv, seg, *, heads: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, three_hidden = qkv.shape
    hidden = three_hidden // 3
    head_dim = hidden // heads
    if not supports(l, hidden, head_dim):
        raise ValueError(
            f"segment_attention: unsupported shape L={l} hidden={hidden} "
            f"head_dim={head_dim}"
        )
    lp = -(-l // LANES) * LANES
    block_w = _block_w(hidden)
    n_w = hidden // block_w

    # the segment ids once as a column and once as a row, padded to the
    # tile with 0: the overrun tokens attend to nothing and nothing
    # attends to them
    seg = jnp.pad(seg.astype(jnp.int32), ((0, 0), (0, lp - l)))
    seg_q = seg[:, :, None]
    seg_k = seg[:, None, :]

    def column(part: int):
        return pl.BlockSpec(
            (1, lp, block_w), lambda i, j: (i, 0, part * n_w + j),
            memory_space=pltpu.VMEM,
        )

    kernel = functools.partial(
        _kernel, head_dim=head_dim, sm_scale=1.0 / float(np.sqrt(head_dim)),
        length=l, block_q=min(lp, BLOCK_Q),
    )
    return pl.pallas_call(
        kernel,
        grid=(b, n_w),
        in_specs=[
            pl.BlockSpec((1, lp, 1), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, lp), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            column(0), column(1), column(2),
        ],
        out_specs=pl.BlockSpec(
            (1, lp, block_w), lambda i, j: (i, 0, j), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((b, l, hidden), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        name="segment_attention",
        interpret=interpret,
    )(seg_q, seg_k, qkv, qkv, qkv)

"""Causal grouped-query attention for packed slabs whose rows fit one tile
of keys (L <= 512), as a Pallas TPU kernel: `mla_attention.py`'s sibling
for heads of ONE 128-wide part (the rotated dims lie inside it) that share
key/value heads, as compressed convolutional attention's do
(`models/zaya.py`: 8 query heads over 2 key/value heads).

A query head's score is `q . k`, 128 deep, over 128-wide values; `group =
heads / kv_heads` query heads read ONE key/value head.  Token i sees token
j iff both lie in the same document and j <= i.

  * one grid step is one slab row and one key/value head with its `group`
    query heads; the whole key axis is one tile, so there is no
    online-softmax rescaling (`mla_attention.py`'s shape: the ingest slab's
    rows are 504 slots, which `hybrid_attention.py`'s blocks of whole
    lanes do not divide, and its score is 256 deep);
  * the group's query heads share the key and value tile and the mask: a
    block of query rows of all of them is laid one under the other, so a
    score is ONE product [group x block, 128] x [128, keys] and the mix
    one more, not a pair a head (`hybrid_attention.py`'s way);
  * operands are read where the program left them, heads contiguous: q
    [B, L, H*128], k and v [B, L, KV*128], and the context is written
    straight into [B, L, H*128] for the out-projection;
  * causal: a block of query rows only meets the keys up to its own last
    row; L is padded to the tile inside the call as in `mla_attention`:
    the blocks overrun the array, the overrun rows of the keys and values
    are zeroed in VMEM and the overrun rows of the output never written.

Numerics are the dense definition's (`cca_attention_dense`): q arrives
scaled, normalised and rotated, scores accumulate in f32 from operands in
the compute dtype, the softmax is f32, p is cast to the compute dtype for
`p @ v`, which accumulates in f32 and is normalised there.  Rows with
seg == 0 come out finite (a uniform mix of v over the keys they met).
"""

from __future__ import annotations

import functools

from pathway_tpu.ops.kernels import kernel_call
from pathway_tpu.ops.kernels.flash_attention import NEG_INF
from pathway_tpu.ops.kernels.mla_attention import LANES, MAX_LEN, _block_q

HEAD_DIM = 128  # the tiling below is written for this width


def supports(length: int, heads: int, kv_heads: int, head_dim: int) -> bool:
    """Static shapes the kernel's tiling covers."""
    return (
        length <= MAX_LEN
        and head_dim == HEAD_DIM
        and kv_heads > 0
        and heads % kv_heads == 0
    )


def cca_attention_dense(q, k, v, seg, *, kv_heads: int):
    """The numerical definition, the path off the TPU and the tests'
    reference of the kernel (operands in its layouts).  Writes the f32
    scores [B, H, L, L]."""
    import jax.numpy as jnp

    b, l, _ = q.shape
    qh = q.reshape(b, l, kv_heads, -1, HEAD_DIM)
    kh = k.reshape(b, l, kv_heads, HEAD_DIM)
    vh = v.reshape(b, l, kv_heads, HEAD_DIM)
    s = jnp.einsum("bqngd,bknd->bngqk", qh, kh, preferred_element_type=jnp.float32)
    at = jnp.arange(l)
    see = (
        (seg[:, :, None] == seg[:, None, :])
        & (seg[:, :, None] > 0)
        & (at[None, None, :] <= at[None, :, None])
    )
    s = jnp.where(see[:, None, None], s, NEG_INF)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    denom = p.sum(-1, keepdims=True)  # >= 1
    ctx = jnp.einsum(
        "bngqk,bknd->bqngd", p.astype(v.dtype), vh, preferred_element_type=jnp.float32
    ) / denom.transpose(0, 3, 1, 2, 4)
    return ctx.reshape(b, l, -1).astype(q.dtype)


def _kernel(segq_ref, segk_ref, q_ref, k_ref, v_ref, o_ref, *, length: int, block_q: int):
    import jax
    import jax.numpy as jnp

    lp = q_ref.shape[1]
    group = q_ref.shape[2] // HEAD_DIM
    for r0 in range(0, lp, block_q):
        kend = r0 + block_q  # causal: later keys are masked for every row
        sq = segq_ref[0, r0:r0 + block_q, :]  # [block_q, 1]
        sk = segk_ref[0, :, :kend]  # [1, kend]
        row = r0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, kend), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, kend), 1)
        see = (sq == sk) & (sq > 0) & (col <= row)  # shared by the group's heads
        see = jnp.concatenate([see] * group, axis=0) if group > 1 else see
        k = k_ref[0, :kend, :]
        v = v_ref[0, :kend, :]
        if length < kend:
            # rows past the array's end hold whatever VMEM held: a zero
            # weight does not silence a NaN, so they are zeroed
            row_ok = jax.lax.broadcasted_iota(jnp.int32, (kend, 1), 0) < length
            k = jnp.where(row_ok, k, jnp.zeros_like(k))
            v = jnp.where(row_ok, v, jnp.zeros_like(v))
        # the group's heads, one under the other: [group x block_q, 128]
        q = jnp.concatenate(
            [q_ref[0, r0:r0 + block_q, h * HEAD_DIM:(h + 1) * HEAD_DIM] for h in range(group)],
            axis=0,
        )
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = jnp.where(see, s, NEG_INF)
        p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
        denom = jnp.sum(p, axis=1, keepdims=True)  # >= 1
        pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        out = (pv / denom).astype(o_ref.dtype)
        for h in range(group):
            o_ref[0, r0:r0 + block_q, h * HEAD_DIM:(h + 1) * HEAD_DIM] = (
                out[h * block_q:(h + 1) * block_q]
            )


def cca_attention(q, k, v, seg, *, interpret=None):
    """The fused kernel.  q [B, L, H*128] (scaled, normalised, rotated); k,
    v [B, L, KV*128] (k rotated); seg [B, L] int, 1..S per packed
    document, 0 = padding.  Returns the context [B, L, H*128] in q's
    dtype.  The device op is `cca_attention`."""
    return kernel_call("cca_attention", _attend, interpret=interpret)(q, k, v, seg)


def _attend(q, k, v, seg, *, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, width = q.shape
    heads, kv_heads = width // HEAD_DIM, k.shape[2] // HEAD_DIM
    if not supports(l, heads, kv_heads, HEAD_DIM) or v.shape != k.shape:
        raise ValueError(
            f"cca_attention: unsupported shape L={l} q={q.shape} k={k.shape} v={v.shape}"
        )
    group = heads // kv_heads
    lp = -(-l // LANES) * LANES
    seg = jnp.pad(seg.astype(jnp.int32), ((0, 0), (0, lp - l)))

    def heads_block(n: int):
        return pl.BlockSpec(
            (1, lp, n * HEAD_DIM), lambda i, g: (i, 0, g), memory_space=pltpu.VMEM
        )

    def row_block(shape):
        return pl.BlockSpec(shape, lambda i, g: (i, 0, 0), memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_kernel, length=l, block_q=_block_q(lp)),
        grid=(b, kv_heads),
        in_specs=[
            row_block((1, lp, 1)), row_block((1, 1, lp)),
            heads_block(group), heads_block(1), heads_block(1),
        ],
        out_specs=heads_block(group),
        out_shape=jax.ShapeDtypeStruct((b, l, heads * HEAD_DIM), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        name="cca_attention",
        interpret=interpret,
    )(seg[:, :, None], seg[:, None, :], q, k, v)

"""Causal, segment-aware latent attention (MLA, prefill form) for packed
slabs, as a Pallas TPU kernel: `segment_attention.py`'s sibling for heads
whose scores are the sum of two products.

A head's score is `q_nope . k_nope` (128 wide, the head's own keys) plus
`q_rope . k_rope` (64 wide, ONE rope key shared by every head), over
128-wide values.  The dense definition (`models/mla.py::
_mla_segment_attention`) writes f32 scores [B, H, L, L] to HBM: 3.6 GB at
the ingest slab [56, 64, 504, 504].  This kernel keeps scores, mask,
softmax and `p @ v` of one slab row in VMEM:

  * one grid step is one slab row and `HEAD_BLOCK` heads; the whole key
    axis (L <= 512) is one tile, so there is no online-softmax rescaling;
  * the operands are read where their matmuls left them: `q_nope`,
    `k_nope`, `v` [B, L, H*128] and `q_rope` [B, L, H*64], heads
    contiguous, and the context is written straight into [B, L, H*128]
    for the out-projection; no [B,L,H,d] -> [B,H,L,d] transposes;
  * every load, matmul and store is a full 128-lane tile.  Two heads'
    rope queries share a tile: a head is picked out of it by zeroing the
    other's lanes, against the shared key laid twice along the lanes
    (`k_rope2` [B, L, 128] = [k_rope | k_rope]), so the products that drop
    out are exact zeros;
  * causal: a block of query rows only meets the keys up to its own last
    row, so the blocks above the diagonal are never computed;
  * L is padded to the tile inside the call as in `segment_attention`:
    the blocks overrun the array, the overrun rows of the keys and values
    are zeroed in VMEM and the overrun rows of the output never written.

Numerics are the dense definition's: token i attends to token j iff
seg[i] == seg[j] > 0 and j <= i; scores accumulate in f32 from operands in
the compute dtype, the softmax is f32, p is cast to the compute dtype for
`p @ v`, which accumulates in f32 and is normalised there.  Rows with
seg == 0 come out finite (a uniform mix of v over the keys it met).
"""

from __future__ import annotations

import functools

from pathway_tpu.ops.kernels import kernel_call
from pathway_tpu.ops.kernels.flash_attention import NEG_INF

LANES = 128
MAX_LEN = 512  # the whole key axis is one tile, as in segment_attention
NOPE_DIM = 128  # the tiling below is written for these three widths
ROPE_DIM = 64
V_DIM = 128
# heads a grid step takes: 4 heads are 512 lanes of q_nope, k_nope, v and
# the output and 256 of q_rope, the width `segment_attention._block_w`
# settled on (compile time and VMEM against speed)
HEAD_BLOCK = 4
BLOCK_Q = 256  # query rows at a time; also the causal skip's granularity


def _block_q(lp: int) -> int:
    """Query rows a step works on at a time: BLOCK_Q where it divides the
    padded key axis (512, 256), else one 128-lane tile's worth (384, 128)."""
    return BLOCK_Q if lp % BLOCK_Q == 0 else LANES


def supports(length: int, heads: int, nope_dim: int, rope_dim: int, v_dim: int) -> bool:
    """Static shapes the kernel's tiling covers."""
    return (
        length <= MAX_LEN
        and (nope_dim, rope_dim, v_dim) == (NOPE_DIM, ROPE_DIM, V_DIM)
        and heads % HEAD_BLOCK == 0
    )


def _kernel(segq_ref, segk_ref, qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, *,
            sm_scale: float, length: int, block_q: int):
    import jax
    import jax.numpy as jnp

    lp = qn_ref.shape[1]
    heads = qn_ref.shape[2] // NOPE_DIM
    lane_half = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // ROPE_DIM
    for r0 in range(0, lp, block_q):
        kend = r0 + block_q  # causal: later keys are masked for every row
        sq = segq_ref[0, r0:r0 + block_q, :]  # [block_q, 1]
        sk = segk_ref[0, :, :kend]  # [1, kend]
        row = r0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, kend), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, kend), 1)
        see = (sq == sk) & (sq > 0) & (col <= row)  # shared by the heads
        kr = kr_ref[0, :kend, :]  # [kend, 128] = [k_rope | k_rope]
        if length < kend:
            # rows past the array's end hold whatever VMEM held: a zero
            # weight does not silence a NaN, so they are zeroed
            row_ok = jax.lax.broadcasted_iota(jnp.int32, (kend, 1), 0) < length
            kr = jnp.where(row_ok, kr, jnp.zeros_like(kr))
        for h in range(heads):
            c0 = h * NOPE_DIM
            qn = qn_ref[0, r0:r0 + block_q, c0:c0 + NOPE_DIM]
            kn = kn_ref[0, :kend, c0:c0 + NOPE_DIM]
            v = v_ref[0, :kend, c0:c0 + V_DIM]
            if length < kend:
                kn = jnp.where(row_ok, kn, jnp.zeros_like(kn))
                v = jnp.where(row_ok, v, jnp.zeros_like(v))
            t0 = (h // 2) * LANES  # the tile this head's rope query shares
            qr = qr_ref[0, r0:r0 + block_q, t0:t0 + LANES]
            qr = jnp.where(lane_half == h % 2, qr, jnp.zeros_like(qr))
            contract = (((1,), (1,)), ((), ()))
            s = jax.lax.dot_general(
                qn, kn, dimension_numbers=contract,
                preferred_element_type=jnp.float32,
            ) + jax.lax.dot_general(
                qr, kr, dimension_numbers=contract,
                preferred_element_type=jnp.float32,
            )
            s = jnp.where(see, s * sm_scale, NEG_INF)
            p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
            denom = jnp.sum(p, axis=1, keepdims=True)  # >= 1
            pv = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            o_ref[0, r0:r0 + block_q, c0:c0 + V_DIM] = (pv / denom).astype(o_ref.dtype)


def mla_segment_attention(q_nope, q_rope, k_nope, k_rope, v, seg, *,
                          sm_scale: float, interpret=None):
    """Fused causal packed-slab latent attention.  q_nope, k_nope, v:
    [B, L, H*128]; q_rope: [B, L, H*64]; k_rope: [B, L, 64], one key for
    all heads (both already rotated); seg: [B, L] int, 1..S per packed
    document, 0 = padding.  Returns the context [B, L, H*128] in
    q_nope's dtype."""
    call = kernel_call(
        "mla_segment_attention", _attend, sm_scale=float(sm_scale), interpret=interpret
    )
    return call(q_nope, q_rope, k_nope, k_rope, v, seg)


def _attend(q_nope, q_rope, k_nope, k_rope, v, seg, *, sm_scale: float, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, width = q_nope.shape
    heads = width // NOPE_DIM
    if not supports(l, heads, NOPE_DIM, q_rope.shape[2] // heads, v.shape[2] // heads):
        raise ValueError(
            f"mla_segment_attention: unsupported shape L={l} heads={heads} "
            f"q_rope={q_rope.shape} v={v.shape}"
        )
    lp = -(-l // LANES) * LANES
    n_blocks = heads // HEAD_BLOCK

    seg = jnp.pad(seg.astype(jnp.int32), ((0, 0), (0, lp - l)))
    k_rope2 = jnp.concatenate([k_rope, k_rope], axis=-1)  # [B, L, 128]

    def heads_block(per_head: int):
        return pl.BlockSpec(
            (1, lp, HEAD_BLOCK * per_head), lambda i, j: (i, 0, j),
            memory_space=pltpu.VMEM,
        )

    def row_block(shape):
        return pl.BlockSpec(shape, lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM)

    kernel = functools.partial(
        _kernel, sm_scale=sm_scale, length=l, block_q=_block_q(lp),
    )
    return pl.pallas_call(
        kernel,
        grid=(b, n_blocks),
        in_specs=[
            row_block((1, lp, 1)), row_block((1, 1, lp)),
            heads_block(NOPE_DIM), heads_block(ROPE_DIM), heads_block(NOPE_DIM),
            row_block((1, lp, LANES)), heads_block(V_DIM),
        ],
        out_specs=heads_block(V_DIM),
        out_shape=jax.ShapeDtypeStruct((b, l, heads * V_DIM), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        name="mla_segment_attention",
        interpret=interpret,
    )(seg[:, :, None], seg[:, None, :], q_nope, q_rope, k_nope, k_rope2, v)

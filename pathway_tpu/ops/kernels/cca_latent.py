"""Compressed convolutional attention's latent, from the fused projection's
output to `cca_attention`'s three operands, as ONE Pallas TPU kernel
(`models/zaya.py::latent_dense` is its numerical definition, step by
step): the group means, two causal convolutions along a packed row, the
heads' L2 normalisation, the key's temperature, RoPE on the first 64 dims
of a head, and the value shift.

All of it is float32 element-wise work on `[q~ | k~ | v~]` [B, L, (heads +
2 kv_heads) x 128] with one small matmul a head in its middle.  Left to
XLA it is a dozen fusions a layer, each of which reads and writes a float32
[slots, 1024-1280] array in HBM (0.3 ms apiece at the ingest slab).  Here a
grid step reads its operands once and writes q, k and v once:

  * one grid step is one slab row and one key/value head's group: its
    `group = heads / kv_heads` query heads, its key head and its value
    head, read from the ONE `qkv` array through three views (the query
    heads' columns, the key head's, the value head's), a head at a time;
  * the step holds the whole row (L <= 512, as `cca_attention.py`), so
    every look-back, the deepest two rows (conv0's tap behind conv1's),
    lies inside the block: no halo, no second pass.  A row's predecessor
    is a sublane roll of the block; whether it is the SAME DOCUMENT's is
    `own_row`, the second copy of `zaya.own_past`'s seam rule, which the
    three look-backs (conv0, conv1, the value shift) all go through;
  * conv1 is one [rows, taps x 128] x [taps x 128, 128] product a head on
    the MXU, the taps side by side, and its float32 accumulator is kept
    (the dense definition leaves the product in the compute dtype and casts
    it up: one rounding fewer here, never one more);
  * RoPE is `hybrid_attention._rope_kernel`'s idiom on a 128-wide head:
    two lane rolls and a select make the partner of every rotated dim, a
    second select lets the other 64 lanes through;
  * L is padded to the tile inside the call as in `cca_attention`: the
    blocks overrun the array, `seg` is padded with zeros (so nothing looks
    back into the overrun) and the overrun rows are never written.

Everything else is the definition's arithmetic where the definition has
it: float32 for the element-wise work, the compute dtype for the matmul's
operands, the same `1e-12` under the root.
"""

from __future__ import annotations

import functools

from pathway_tpu.ops.kernels import kernel_call
from pathway_tpu.ops.kernels.cca_attention import HEAD_DIM  # a head is one 128-lane tile
from pathway_tpu.ops.kernels.hybrid_attention import LANES, ROPE_DIM, VMEM_LIMIT
from pathway_tpu.ops.kernels.mla_attention import MAX_LEN

MAX_TAPS = 4  # the look-backs are unrolled, a roll and a select a tap


def supports(length: int, heads: int, kv_heads: int, head_dim: int, rotary_dim: int,
             taps0: int, taps1: int) -> bool:
    """Static shapes the kernel's tiling covers."""
    return (
        length <= MAX_LEN
        and head_dim == HEAD_DIM
        and rotary_dim == ROPE_DIM
        and kv_heads > 0
        and heads % kv_heads == 0
        and 1 <= taps0 <= MAX_TAPS
        and 1 <= taps1 <= MAX_TAPS
    )


def own_row(seg, n: int):
    """THE seam rule inside the kernel (`zaya.own_past` holds the first
    copy; tests/test_zaya.py pins the two against each other): whether row
    t-n of the block is row t's own document's.  seg: [rows, lanes] int32,
    a row's segment id along its lanes, 0 = padding; the block begins a
    slab row, so its first n rows have no past."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    row = jax.lax.broadcasted_iota(jnp.int32, seg.shape, 0)
    return (pltpu.roll(seg, n, axis=0) == seg) & (seg > 0) & (row >= n)


def _own_past(x, own, n: int):
    """Row t-n of the own document, or zero: x [rows, 128] f32; own: the
    step's `own_row` masks by look-back."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    if n == 0:
        return x
    return jnp.where(own[n], pltpu.roll(x, n, axis=0), jnp.zeros_like(x))


def _means(q_raw, k_raw):
    """`zaya.group_means` on a group's heads: q_raw a list of [rows, 128]
    f32, k_raw one -> (mq a head, mk)."""
    total = q_raw[0]
    for q in q_raw[1:]:
        total = total + q
    return [(q + k_raw) * 0.5 for q in q_raw], (total / len(q_raw) + k_raw) * 0.5


def _unit(x):
    """A head's vector L2-normalised: x [rows, 128] f32."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + 1e-12)


def _turned(x, cos, sin):
    """RoPE (rotate-half) on the first ROPE_DIM lanes of a head, the others
    as they are: x [rows, 128] f32; cos, sin: `rope_tables`' rows."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    half = ROPE_DIM // 2
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    # lane i of the rotated part's first half takes x[i + 32], of its second x[i - 32]
    partner = jnp.where(
        lane < half, pltpu.roll(x, LANES - half, axis=1), pltpu.roll(x, half, axis=1)
    )
    return jnp.where(lane < ROPE_DIM, x * cos + partner * sin, x)


def _shifted(v, own, shift):
    """The value shift: a head of the second half takes the row before's.
    v [rows, 128] in the compute dtype; shift: whether this step's head is
    of that half (a traced scalar)."""
    import jax.numpy as jnp

    past = _own_past(v.astype(jnp.float32), own, 1).astype(v.dtype)  # exact: a select
    return jnp.where(shift, past, v)


def _kernel(seg_ref, cos_ref, sin_ref, q_ref, k_ref, v_ref, w0q_ref, w0k_ref, b0q_ref,
            b0k_ref, w1q_ref, w1k_ref, b1q_ref, b1k_ref, scale_ref,
            qo_ref, ko_ref, vo_ref, *, first_shifted: int):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32, dt = jnp.float32, qo_ref.dtype
    rows = q_ref.shape[1]
    group = q_ref.shape[2] // HEAD_DIM
    taps0 = w0q_ref.shape[0]
    taps1 = w1q_ref.shape[1] // HEAD_DIM
    seg = jnp.broadcast_to(seg_ref[0], (rows, LANES))
    # the seam, once a step for every head and tap: by rows looked back
    own = {n: own_row(seg, n) for n in range(1, max(taps0, taps1, 2))}
    cos, sin = cos_ref[0], sin_ref[0]

    def head(a):
        return slice(a * HEAD_DIM, (a + 1) * HEAD_DIM)

    def convolved(x, w0, b0, w1, b1):
        # conv0: depthwise; tap j reads the row taps-1-j back
        c0 = None
        for j in range(taps0):
            term = w0[j:j + 1, :] * _own_past(x, own, taps0 - 1 - j)
            c0 = term if c0 is None else c0 + term
        c0 = b0 + c0
        # conv1: the head's channels mix among themselves, the taps side by side
        taps = jnp.concatenate(
            [_own_past(c0, own, taps1 - 1 - j).astype(dt) for j in range(taps1)], axis=1
        )
        return jnp.dot(taps, w1, preferred_element_type=f32) + b1

    k_raw = k_ref[0].astype(f32)
    q_raw = [q_ref[0, :, head(a)].astype(f32) for a in range(group)]
    mq, mk = _means(q_raw, k_raw)
    for a in range(group):
        c1 = convolved(q_raw[a], w0q_ref[:, head(a)], b0q_ref[:, head(a)],
                       w1q_ref[a], b1q_ref[:, head(a)])
        qo_ref[0, :, head(a)] = _turned(_unit(c1 + mq[a]), cos, sin).astype(dt)
    c1 = convolved(k_raw, w0k_ref[...], b0k_ref[...], w1k_ref[0], b1k_ref[...])
    # k carries the score's sqrt(head_dim) and the temperature
    ko_ref[0] = _turned(_unit(c1 + mk) * scale_ref[0], cos, sin).astype(dt)
    vo_ref[0] = _shifted(v_ref[0], own, pl.program_id(1) >= first_shifted)


def cca_latent(qkv, seg, rope, layer, *, heads: int, kv_heads: int, interpret=None):
    """The fused kernel.  qkv [B, L, (heads + 2 kv_heads) x 128] as the
    projection leaves it (columns: the query heads, the key heads, the
    value heads); seg [B, L] int, 1..S per packed document, 0 = padding;
    rope: `rope_tables` of the slab's positions; layer: the layer's
    `conv0_w` [taps0, (heads + kv) x 128], `conv0_b`, `conv1_w` [heads +
    kv, taps1 x 128, 128], `conv1_b` and `tau` [kv].  Returns (q [B, L,
    heads x 128], k, v [B, L, kv x 128]) in qkv's dtype, as `cca_attention`
    reads them.  The device op is `cca_latent`."""
    call = kernel_call("cca_latent", _latent, heads=heads, kv_heads=kv_heads,
                       interpret=interpret)
    return call(qkv, seg, *rope, layer["conv0_w"], layer["conv0_b"], layer["conv1_w"],
                layer["conv1_b"], layer["tau"])


def _latent(qkv, seg, cos, sin, conv0_w, conv0_b, conv1_w, conv1_b, tau, *,
            heads: int, kv_heads: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, width = qkv.shape
    n, hd = heads + kv_heads, HEAD_DIM
    taps0, taps1 = conv0_w.shape[0], conv1_w.shape[1] // hd
    if (
        not supports(l, heads, kv_heads, hd, ROPE_DIM, taps0, taps1)
        or width != (n + kv_heads) * hd
        or conv0_w.shape != (taps0, n * hd)
        or conv1_w.shape != (n, taps1 * hd, hd)
    ):
        raise ValueError(
            f"cca_latent: unsupported shape L={l} qkv={qkv.shape} heads={heads} "
            f"kv_heads={kv_heads} conv0={conv0_w.shape} conv1={conv1_w.shape}"
        )
    group = heads // kv_heads
    lp = -(-l // LANES) * LANES
    f32, dt = jnp.float32, qkv.dtype
    seg = jnp.pad(seg.astype(jnp.int32), ((0, 0), (0, lp - l)))[:, :, None]
    conv0_w, conv1_w = conv0_w.astype(f32), conv1_w.astype(dt)
    conv0_b, conv1_b = conv0_b.astype(f32)[None, :], conv1_b.astype(f32)[None, :]
    scale = jnp.broadcast_to((hd ** 0.5 * tau.astype(f32))[:, None, None], (kv_heads, 1, LANES))
    vmem = pltpu.VMEM

    def row_block(cols: int):  # a slab row's lanes, whatever the step's group
        return pl.BlockSpec((1, lp, cols), lambda i, g: (i, 0, 0), memory_space=vmem)

    def heads_block(count: int, first: int):
        """`count` heads of the step's group from head `first` x count on,
        of a [B, L, heads x 128] array."""
        return pl.BlockSpec((1, lp, count * hd), lambda i, g: (i, 0, first + g), memory_space=vmem)

    def channels(lead: int, count: int, first: int):  # of a [lead, heads x 128] vector
        return pl.BlockSpec((lead, count * hd), lambda i, g: (0, first + g), memory_space=vmem)

    def matrices(count: int, first: int):  # of conv1's [heads, taps1 x 128, 128]
        return pl.BlockSpec(
            (count, taps1 * hd, hd), lambda i, g: (first + g, 0, 0), memory_space=vmem
        )

    # a view's `first` counts blocks of its own width: the key head g is
    # block heads + g of 128 lanes, the query heads of group g are block g
    # of group x 128
    return pl.pallas_call(
        functools.partial(_kernel, first_shifted=kv_heads - kv_heads // 2),
        grid=(b, kv_heads),
        in_specs=[
            row_block(1), row_block(LANES), row_block(LANES),
            heads_block(group, 0), heads_block(1, heads), heads_block(1, n),
            channels(taps0, group, 0), channels(taps0, 1, heads),
            channels(1, group, 0), channels(1, 1, heads),
            matrices(group, 0), matrices(1, heads),
            channels(1, group, 0), channels(1, 1, heads),
            pl.BlockSpec((1, 1, LANES), lambda i, g: (g, 0, 0), memory_space=vmem),
        ],
        out_specs=[heads_block(group, 0), heads_block(1, 0), heads_block(1, 0)],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, heads * hd), dt),
            jax.ShapeDtypeStruct((b, l, kv_heads * hd), dt),
            jax.ShapeDtypeStruct((b, l, kv_heads * hd), dt),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        name="cca_latent",
        interpret=interpret,
    )(seg, cos, sin, qkv, qkv, qkv, conv0_w, conv0_w, conv0_b, conv0_b,
      conv1_w, conv1_w, conv1_b, conv1_b, scale)

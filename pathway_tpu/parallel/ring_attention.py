"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has no sequence parallelism at all (SURVEY §5: long documents
are chunked in Python, splitters.py) — this is a new, TPU-first capability:
sequences shard over an `sp` mesh axis so context length scales with the
number of chips, with KV blocks rotating around the ICI ring (ring
attention) or heads resharding via all-to-all (Ulysses).

Both functions are written to run INSIDE `shard_map` over the `sp` axis:
inputs are the per-device sequence chunks. Online-softmax accumulation makes
the ring mathematically exact (same numbers as full attention), not an
approximation. Collectives are XLA (`ppermute` / `all_to_all`), so the same
code runs on the CPU test mesh and on ICI.
"""

from __future__ import annotations

import functools

import numpy as np

NEG_INF = -1e30


def ring_attention(q, k, v, kv_mask, *, axis_name: str = "sp",
                   causal: bool = False, sm_scale=None):
    """Exact attention over a sequence sharded on `axis_name`.

    q, k, v: [B, H, C, D] — the local chunk (C = L / sp).
    kv_mask: [B, C] local chunk of the padding mask (1 = valid).
    Returns [B, H, C, D]: this device's chunk of the attention output.

    Each of the sp steps attends q against the currently-held KV chunk and
    then rotates K/V/mask one hop around the ring (lax.ppermute), carrying
    flash-style running (max, normalizer, accumulator) — the [L, L] score
    matrix never exists, and each hop's compute overlaps the next hop's
    ICI transfer under XLA latency hiding.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, h, c, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(d))
    sp = int(lax.axis_size(axis_name))  # static under shard_map
    my = lax.axis_index(axis_name)
    rot = [(i, (i + 1) % sp) for i in range(sp)]

    q32 = q.astype(jnp.float32)
    q_pos = my * c + lax.broadcasted_iota(jnp.int32, (c, 1), 0)  # [C,1]

    def one_chunk(k_chunk, v_chunk, kvm, src_chunk, m, l, acc):
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", q32, k_chunk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [B,H,C,C]
        s = s + (1.0 - kvm[:, None, None, :].astype(jnp.float32)) * NEG_INF
        if causal:
            k_pos = src_chunk * c + lax.broadcasted_iota(
                jnp.int32, (1, c), 1
            )  # [1,C]
            s = jnp.where(
                (q_pos >= k_pos)[None, None, :, :], s, NEG_INF
            )
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_chunk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    def step(s, carry):
        m, l, acc, k_c, v_c, kvm = carry
        src_chunk = (my - s) % sp

        def compute(args):
            m, l, acc = args
            return one_chunk(k_c, v_c, kvm, src_chunk, m, l, acc)

        def skip(args):
            return args

        if causal:
            # a chunk strictly in this device's future is fully masked —
            # skip its FLOPs entirely (the ring still rotates)
            m, l, acc = lax.cond(
                src_chunk > my, skip, compute, (m, l, acc)
            )
        else:
            m, l, acc = compute((m, l, acc))

        if s != sp - 1:  # the last step's rotation would be discarded
            k_c = lax.ppermute(k_c, axis_name, rot)
            v_c = lax.ppermute(v_c, axis_name, rot)
            kvm = lax.ppermute(kvm, axis_name, rot)
        return m, l, acc, k_c, v_c, kvm

    m0 = jnp.full((b, h, c, 1), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, c, 1), dtype=jnp.float32)
    acc0 = jnp.zeros((b, h, c, d), dtype=jnp.float32)
    # constants are unvarying on the sp axis; mark them device-varying so
    # both lax.cond branches agree on varying-axis types
    m0, l0, acc0 = (
        lax.pcast(x, axis_name, to="varying") for x in (m0, l0, acc0)
    )
    carry = (m0, l0, acc0, k, v, kv_mask)
    for s in range(sp):  # sp is static under shard_map; unroll the ring
        carry = step(s, carry)
    m, l, acc, _, _, _ = carry
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).astype(q.dtype)


def ulysses_attention(q, k, v, kv_mask, *, axis_name: str = "sp",
                      causal: bool = False, sm_scale=None,
                      use_flash=None):
    """Ulysses-style sequence parallelism: all-to-all reshard so each device
    holds ALL positions for H/sp heads, run full (flash) attention locally,
    then reshard back to sequence-sharded layout. Cheaper than the ring when
    heads >= sp and the interconnect favors few large transfers.

    q, k, v: [B, H, C, D] sequence-sharded chunks; heads must divide by sp.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    sp = int(lax.axis_size(axis_name))  # static under shard_map
    b, h, c, d = q.shape
    if h % sp != 0:
        raise ValueError(f"heads {h} not divisible by sp axis {sp}")

    # [B,H,C,D] -> [B,H/sp,L,D]: split heads, gather sequence
    def to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    full_mask = lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)

    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if use_flash:
        from pathway_tpu.ops.kernels import flash_attention

        out = flash_attention(qh, kh, vh, full_mask, causal=causal,
                              sm_scale=sm_scale)
    else:
        from pathway_tpu.ops.kernels.flash_attention import (
            _reference_attention,
        )

        if sm_scale is None:
            sm_scale = 1.0 / float(np.sqrt(d))
        out = _reference_attention(qh, kh, vh, full_mask, sm_scale, causal)
    return to_seq(out.astype(q.dtype))

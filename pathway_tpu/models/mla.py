"""Latent attention (MLA) in its prefill form, below the trunks that run it
(`moe_mla`: a sublayer of A.X-K1's layer; `longcat`: two of LongCat-Flash's
double layer): the attention half of a sublayer without its residual
(`_attention`), its dense numerical definition (`_mla_segment_attention`)
and the gate between that and the kernel `ops/kernels/mla_attention.py`
(`packed_attention_fused`).

A sublayer, x [B, L, hidden], every norm RMSNorm, no biases:

  h = norm(x); c_q = norm(h W_qa) * q_scale; q = c_q W_qb -> heads of
  [nope | rope]; [c_kv | k_rope] = h W_kva; c_kv = norm(c_kv) * kv_scale;
  c_kv W_kvb -> heads of [k_nope | v]; RoPE (the trunk's ladder, YaRN's
  or the plain one, on interleaved pairs) on q_rope and on k_rope, which
  all heads share; positions restart at every segment; score =
  (q_nope.k_nope + q_rope.k_rope) * sm_scale; token i sees j iff same
  segment and j <= i; f32 softmax; out = concat_heads(p v) W_o

The two LoRA scales are LongCat-Flash's `mla_scale_q_lora` /
`mla_scale_kv_lora` (sqrt(hidden / rank)); a scale of 1.0 (A.X-K1's) is
not applied at all, so that trunk's program is what it was.  A scale is
folded into its norm's f32 weights, so it adds no rounding of its own.
The up-projections are kept as separate matrices per part (`wq_b_nope` /
`wq_b_rope`, `wk_b` / `wv_b`: the published matrices' columns, regrouped
once at init), so that every operand of the kernel leaves its matmul in
the layout the kernel reads.  The configuration is any trunk's with
`heads`, `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`,
`kv_lora_rank`, `rope_theta`, `norm_eps` and `sm_scale`.
"""

from __future__ import annotations

from typing import Optional

from pathway_tpu.models.trunk import rms_norm, rope


def _mla_segment_attention(q_nope, q_rope, k_nope, k_rope, v, seg, sm_scale, heads):
    """Dense causal latent attention with a pairwise same-segment mask:
    the numerical definition, the path off the TPU and the tests'
    reference of `ops/kernels/mla_attention.py` (operands in its layouts).
    Writes the f32 scores [B, H, L, L]: 3.6 GB at the ingest slab."""
    import jax.numpy as jnp

    from pathway_tpu.ops.kernels.flash_attention import NEG_INF

    b, l, _ = q_nope.shape
    split = lambda a: a.reshape(b, l, heads, -1)  # noqa: E731
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", split(q_nope), split(k_nope),
        preferred_element_type=jnp.float32,
    ) + jnp.einsum(
        "bqhd,bkd->bhqk", split(q_rope), k_rope,
        preferred_element_type=jnp.float32,
    )
    at = jnp.arange(l)
    see = (
        (seg[:, None, :, None] == seg[:, None, None, :])
        & (seg[:, None, :, None] > 0)
        & (at[None, None, None, :] <= at[None, None, :, None])
    )
    s = jnp.where(see, s * sm_scale, NEG_INF)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / (p.sum(-1, keepdims=True) + 1e-30)
    ctx = jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), split(v),
        preferred_element_type=jnp.float32,
    )
    return ctx.reshape(b, l, -1).astype(q_nope.dtype)


def packed_attention_fused(config, length: int, use_flash: Optional[bool] = None) -> bool:
    """Whether a slab of `length` tokens runs the fused kernel or the dense
    definition: the backend and the static shape, as
    `transformer.packed_attention_fused` decides for the encoders (its
    measured floor for heads of 64 lanes and more, L > 32, is taken over;
    below it a row's scores are a few kilobytes).  The launch site asks
    again to count the batch.  `use_flash` overrides (tests)."""
    if use_flash is not None:
        return use_flash
    import jax

    from pathway_tpu.ops.kernels.mla_attention import supports

    return (
        jax.default_backend() == "tpu"
        and length > 32
        and supports(length, config.heads, config.qk_nope_head_dim,
                     config.qk_rope_head_dim, config.v_head_dim)
    )


def _scaled(norm_weight, scale: float):
    return norm_weight if scale == 1.0 else norm_weight * scale


def _attention(x, layer, config, pos, seg, fused: bool, freqs,
               q_scale: float = 1.0, kv_scale: float = 1.0):
    """The attention half of a sublayer, without the residual.  x: [B, L,
    h]; `layer`: the sublayer's norms ("ln1" its input's, "q_ln", "kv_ln")
    and matrices; `freqs` [rope / 2]: the trunk's rotation ladder (None:
    the plain ladder of `rope_theta`)."""
    from pathway_tpu.ops.kernels.mla_attention import mla_segment_attention

    c = config
    b, l, _ = x.shape
    dt = x.dtype
    h = rms_norm(x, layer["ln1"], c.norm_eps)
    c_q = rms_norm(h @ layer["wq_a"].astype(dt), _scaled(layer["q_ln"], q_scale), c.norm_eps)
    q_nope = c_q @ layer["wq_b_nope"].astype(dt)
    q_rope = c_q @ layer["wq_b_rope"].astype(dt)
    kv_a = h @ layer["wkv_a"].astype(dt)
    c_kv = rms_norm(kv_a[..., : c.kv_lora_rank], _scaled(layer["kv_ln"], kv_scale), c.norm_eps)
    k_nope = c_kv @ layer["wk_b"].astype(dt)
    v = c_kv @ layer["wv_b"].astype(dt)

    def rotate(a, n_heads: int):
        # one "batch" a token, so that the rotation needs no transposes
        flat = a.reshape(b * l, n_heads, 1, c.qk_rope_head_dim)
        out = rope(flat, pos.reshape(b * l, 1), c.rope_theta, freqs=freqs,
                    interleaved=True)
        return out.reshape(b, l, n_heads * c.qk_rope_head_dim)

    q_rope = rotate(q_rope, c.heads)
    k_rope = rotate(kv_a[..., c.kv_lora_rank:], 1)
    if fused:
        ctx = mla_segment_attention(
            q_nope, q_rope, k_nope, k_rope, v, seg, sm_scale=c.sm_scale
        )
    else:
        ctx = _mla_segment_attention(
            q_nope, q_rope, k_nope, k_rope, v, seg, c.sm_scale, c.heads
        )
    return ctx @ layer["wo"].astype(dt)

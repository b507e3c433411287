"""Long-context forward: the whole transformer under sequence parallelism.

Runs the full layer stack inside one `shard_map` over the mesh's `sp` axis:
activations stay sequence-sharded end to end ([B, L/sp, H] per device),
attention is exact ring attention (parallel/ring_attention.py) or Ulysses
all-to-all, and everything else (layernorm, QKV/MLP matmuls) is local
per-token work. Context length scales linearly with the number of chips —
a capability the reference does not have at all (SURVEY §5: it chunks long
documents in Python instead).

Params are replicated over sp (they're O(H^2); activations at long L are the
memory problem sequence parallelism solves). Combine with tp/dp axes by
nesting this shard_map in a pjit over the remaining axes.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from pathway_tpu.models.transformer import TransformerConfig, _layer_norm


def _local_forward(params, config: TransformerConfig, ids, mask,
                   *, axis_name: str, attn: str, use_flash):
    """Body run per-device inside shard_map. ids/mask: [B, C] local chunk."""
    import jax.numpy as jnp
    from jax import lax

    from pathway_tpu.parallel.ring_attention import (
        ring_attention,
        ulysses_attention,
    )

    compute_dtype = (
        jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
    )
    b, c = ids.shape
    my = lax.axis_index(axis_name)
    # global positions of this chunk for the positional table
    pos = my * c + jnp.arange(c)
    x = params["embed"][ids] + params["pos_embed"][pos][None, :, :]
    x = x.astype(compute_dtype)

    heads, hd = config.heads, config.head_dim
    for layer in params["layers"]:
        y = _layer_norm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
        qkv = (
            y @ layer["qkv"].astype(compute_dtype)
            + layer["qkv_b"].astype(compute_dtype)
        )
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, c, heads, hd).transpose(0, 2, 1, 3)
        k = k.reshape(b, c, heads, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, c, heads, hd).transpose(0, 2, 1, 3)
        if attn == "ring":
            ctx = ring_attention(
                q, k, v, mask, axis_name=axis_name, causal=config.causal
            )
        else:
            ctx = ulysses_attention(
                q, k, v, mask, axis_name=axis_name, causal=config.causal,
                use_flash=use_flash,
            )
        ctx = ctx.astype(compute_dtype)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, c, config.hidden)
        x = x + (
            ctx @ layer["out"].astype(compute_dtype)
            + layer["out_b"].astype(compute_dtype)
        )
        y = _layer_norm(x, layer["ln2"]["scale"], layer["ln2"]["bias"])
        y = (
            y @ layer["up"].astype(compute_dtype)
            + layer["up_b"].astype(compute_dtype)
        )
        y = y * 0.5 * (1.0 + jnp.tanh(0.7978845608 * (y + 0.044715 * y**3)))
        x = x + (
            y @ layer["down"].astype(compute_dtype)
            + layer["down_b"].astype(compute_dtype)
        )

    x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    if config.pooling == "none":
        return jnp.einsum(
            "blh,vh->blv", x.astype(jnp.float32), params["embed"]
        )
    # mean pooling needs the cross-chunk sums: two tiny psums
    m = mask[:, :, None].astype(x.dtype)
    local_sum = (x * m).sum(1)
    local_cnt = m.sum(1)
    pooled = lax.psum(local_sum, axis_name) / (
        lax.psum(local_cnt, axis_name) + 1e-9
    )
    pooled = pooled.astype(jnp.float32)
    return pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-9)


def sequence_parallel_forward(params, config: TransformerConfig, ids, mask,
                              mesh, *, axis_name: str = "sp",
                              attn: str = "ring",
                              use_flash: Optional[bool] = None):
    """Jit-compile and run the transformer with sequences sharded over
    `axis_name` of `mesh`. ids, mask: [B, L] with L divisible by the axis
    size. Returns logits [B, L, V] (pooling='none') or pooled [B, H]."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    assert attn in ("ring", "ulysses"), attn
    l = ids.shape[1]
    sp = mesh.shape[axis_name]
    if l % sp != 0:
        raise ValueError(f"sequence length {l} not divisible by sp={sp}")

    body = functools.partial(
        _local_forward, config=config, axis_name=axis_name, attn=attn,
        use_flash=use_flash,
    )
    if config.pooling == "none":
        out_spec = P(None, axis_name, None)
    else:
        out_spec = P(None, None)
    fn = shard_map(
        lambda p, i, m: body(p, ids=i, mask=m),
        mesh=mesh,
        in_specs=(P(), P(None, axis_name), P(None, axis_name)),
        out_specs=out_spec,
        check_vma=False,
    )
    return jax.jit(fn)(params, ids, mask)

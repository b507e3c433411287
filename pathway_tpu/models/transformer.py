"""Pure-JAX transformer: encoder (bidirectional) and decoder (causal), one
parameterization.

This is the data-plane model the LLM xpack runs on TPU — the counterpart of
the reference's torch models behind SentenceTransformerEmbedder
(xpacks/llm/embedders.py:342), CrossEncoderReranker (rerankers.py:163) and
HFPipelineChat (llms.py:456).

TPU-first choices:
  * bf16 activations/matmuls (MXU native), f32 params + layernorm stats;
  * static shapes everywhere — batches arrive bucketed from the tokenizer;
  * tensor parallel over heads/mlp via PartitionSpecs on a ("dp","tp") mesh
    (param_sharding_rules); batch (dp) sharding on inputs. XLA inserts the
    all-reduces after attention out-proj / mlp down-proj;
  * decode uses a KV cache carried as an explicit pytree through lax.scan.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import numpy as np

from pathway_tpu.models.trunk import (  # noqa: F401  (`tokenizer`: model_module's)
    TransformerLM,
    attention,
    mesh_axis,
    packed_positions,
    tokenizer,
)


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 30522
    hidden: int = 384
    layers: int = 6
    heads: int = 12
    mlp_dim: int = 1536
    max_len: int = 512
    causal: bool = False
    pooling: str = "mean"  # mean | cls | none
    dtype: str = "bfloat16"
    # "pre" = GPT-style pre-LN (default, trains stably from scratch);
    # "post" = BERT/MiniLM layout (embedding LayerNorm, residual-then-LN,
    # erf GELU) — required for loading real HF encoder checkpoints
    norm_style: str = "pre"

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


# MiniLM-L6-class config (the reference's default embedder model family)
MINILM_L6 = TransformerConfig(
    vocab_size=30522, hidden=384, layers=6, heads=12, mlp_dim=1536
)


def init_params(rng, config: TransformerConfig) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    h, mlp, v = config.hidden, config.mlp_dim, config.vocab_size
    keys = jax.random.split(rng, 4 + config.layers)
    scale = 0.02

    def dense(key, shape):
        return jax.random.normal(key, shape, dtype=jnp.float32) * scale

    params: Dict[str, Any] = {
        "embed": dense(keys[0], (v, h)),
        "pos_embed": dense(keys[1], (config.max_len, h)),
        "ln_f": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
        "layers": [],
    }
    for i in range(config.layers):
        k = jax.random.split(keys[4 + i], 6)
        params["layers"].append(
            {
                "ln1": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
                "ln2": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
                "qkv": dense(k[0], (h, 3 * h)),
                "qkv_b": jnp.zeros((3 * h,)),
                "out": dense(k[1], (h, h)),
                "out_b": jnp.zeros((h,)),
                "up": dense(k[2], (h, mlp)),
                "up_b": jnp.zeros((mlp,)),
                "down": dense(k[3], (mlp, h)),
                "down_b": jnp.zeros((h,)),
            }
        )
    return params


def param_sharding_rules(config: TransformerConfig, mesh) -> Dict[str, Any]:
    """PartitionSpecs for tensor parallelism on the mesh's 'tp' axis:
    qkv/up column-sharded, out/down row-sharded (Megatron-style), embeddings
    vocab-sharded. Scaling-book recipe: annotate, let XLA place collectives."""
    from jax.sharding import PartitionSpec as P

    tp = "tp" if "tp" in mesh.axis_names else None
    rules = {
        "embed": P(tp, None),
        "pos_embed": P(None, None),
        "ln_f": {"scale": P(None), "bias": P(None)},
        "layers": [
            {
                "ln1": {"scale": P(None), "bias": P(None)},
                "ln2": {"scale": P(None), "bias": P(None)},
                "qkv": P(None, tp),
                "qkv_b": P(tp),
                "out": P(tp, None),
                "out_b": P(None),
                "up": P(None, tp),
                "up_b": P(tp),
                "down": P(tp, None),
                "down_b": P(None),
            }
            for _ in range(config.layers)
        ],
    }
    return rules


def _layer_norm(x, scale, bias, eps=1e-6):
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    out = (x32 - mean) * (1.0 / jnp.sqrt(var + eps))
    return (out * scale + bias).astype(x.dtype)


def _segment_attention(q, k, v, seg, sm_scale):
    """Dense attention with a pairwise same-segment mask for packed
    ragged batches. q,k,v: [B,H,L,D]; seg: [B,L] int32, 1..S per packed
    document, 0 = padding. Mirrors `_reference_attention`'s numerics
    (f32 scores, NEG_INF additive mask, +1e-30 softmax denominator) so a
    doc packed with neighbors attends over exactly the tokens it would
    see alone. Pad rows produce finite garbage that per-segment pooling
    never reads.

    This is the numerical definition, the path off the TPU and the
    tests' reference. On the TPU the packed path runs
    `ops/kernels/segment_attention.py` instead (`packed_attention_fused`
    decides): this dense form writes the f32 scores [B,H,L,L] to HBM and
    reads them back twice, which at the e5 ingest slab [440,16,504,64]
    took 40.1 ms a layer, transposes included, against 5.3 ms for the
    kernel, and at MiniLM's [320,12,256,32] 3.56 against 1.00 ms (chip
    runs, PR 28; PERF.md section 6 has every shape)."""
    import jax.numpy as jnp

    from pathway_tpu.ops.kernels.flash_attention import NEG_INF

    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    same = (seg[:, None, :, None] == seg[:, None, None, :]) & (
        seg[:, None, :, None] > 0
    )
    s = jnp.where(same, s, NEG_INF)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / (p.sum(-1, keepdims=True) + 1e-30)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


def packed_attention_fused(config: TransformerConfig, length: int,
                           use_flash: Optional[bool] = None) -> bool:
    """Whether a packed slab of `length` tokens runs the fused kernel
    (`ops/kernels/segment_attention.py`) or `_segment_attention`. Decided
    from what the code can see: the backend and the static shape. The
    choice is the same for every batch of one compiled shape, so the
    launch site (`ops/knn.py`) asks again to count it. `use_flash`
    overrides (tests run the kernel interpreted on the CPU)."""
    if use_flash is not None:
        return use_flash
    import jax

    from pathway_tpu.ops.kernels.segment_attention import supports

    if jax.default_backend() != "tpu" or not supports(
        length, config.hidden, config.head_dim
    ):
        return False
    # Kernel / dense with its transposes, one layer, [B,H,L,hd] (chip
    # runs, PR 28): [440,16,504,64] 5.24 / 40.05 ms, [320,12,256,32] 1.01 /
    # 3.56 ms, [8,16,504,64] 120 / 387 us, [64,16,128,64] 140 / 200 us,
    # [32,16,64,64] 65 / 92 us, [32,12,256,32] 111 / 115 us.  Dense wins
    # where a row's work is a few latency-bound matmul pairs: 32-wide
    # heads on a key axis of one 128-lane tile ([64,12,128,32] 79 / 38 us,
    # [32,12,64,32] 42 / 19 us, [8,12,32,32] 10 / 10 us) and, by the launch
    # floor's margin, L 32 at any width ([8,16,32,64] 15 / 10 us).
    if config.head_dim >= 64:
        return length > 32
    return length > 128


def _fused_segment_attention(qkv, seg, heads: int, mesh=None):
    """The fused kernel over qkv [B, L, 3·hidden] as the QKV matmul left
    it. Inside a jit that spans a mesh it runs per device under
    shard_map, like `trunk.attention`'s flash kernel: slab rows over 'dp' when
    they divide (pack_batch_dp pads replicas to a common block), the
    hidden axis whole on every device."""
    from pathway_tpu.ops.kernels.segment_attention import segment_attention

    if mesh is None:
        return segment_attention(qkv, seg, heads)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    dp = mesh_axis(mesh, "dp", qkv.shape[0])
    return shard_map(
        lambda qkv, seg: segment_attention(qkv, seg, heads),
        mesh=mesh,
        in_specs=(P(dp, None, None), P(dp, None)),
        out_specs=P(dp, None, None),
        check_vma=False,
    )(qkv, seg)


def forward(
    params,
    config: TransformerConfig,
    ids,
    mask,
    *,
    return_hidden: bool = False,
    use_flash: Optional[bool] = None,
    seg=None,
    max_segments: int = 0,
    mesh=None,
):
    """Encoder/decoder forward. ids, mask: [B, L] int32. Returns pooled
    embeddings [B, H] (pooling != none), else logits [B, L, V]. `mesh`:
    the mesh the caller's jit spans (sharded params or inputs), so the
    flash kernel can run per device.

    Packed mode (seg is not None): rows hold several concatenated docs
    distinguished by segment ids; attention is confined within segments,
    positions restart per segment, and pooling returns [B, max_segments,
    H] — one L2-normalized vector per packed doc slot. mask is ignored
    (seg > 0 is the validity mask); causal packed decode is unsupported.
    `use_flash` means there what it means unpacked: None decides from
    backend and shape (`packed_attention_fused`), True and False force
    the fused kernel and the dense path."""
    import jax
    import jax.numpy as jnp

    compute_dtype = jnp.bfloat16 if config.dtype == "bfloat16" else jnp.float32
    post_ln = config.norm_style == "post"
    b, l = ids.shape
    if seg is not None:
        if config.causal:
            raise ValueError("packed segment batching requires a bidirectional encoder")
        pos = packed_positions(seg)
        x = params["embed"][ids] + params["pos_embed"][pos]
        fused = packed_attention_fused(config, l, use_flash)
    else:
        x = params["embed"][ids] + params["pos_embed"][:l][None, :, :]
    if post_ln and "type_embed" in params:
        x = x + params["type_embed"][0][None, None, :]
    if post_ln and "embed_ln" in params:
        x = _layer_norm(
            x, params["embed_ln"]["scale"], params["embed_ln"]["bias"],
            eps=1e-12,
        )
    x = x.astype(compute_dtype)
    eps = 1e-12 if post_ln else 1e-6

    heads, hd = config.heads, config.head_dim
    for layer in params["layers"]:
        if post_ln:
            y = x  # BERT: attention reads the residual stream directly
        else:
            y = _layer_norm(x, layer["ln1"]["scale"], layer["ln1"]["bias"])
        qkv = (
            y @ layer["qkv"].astype(compute_dtype)
            + layer["qkv_b"].astype(compute_dtype)
        )
        if seg is not None and fused:
            # q, k, v read in place and ctx written in [B, L, hidden]
            ctx = _fused_segment_attention(qkv, seg, heads, mesh)
        else:
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, l, heads, hd).transpose(0, 2, 1, 3)
            k = k.reshape(b, l, heads, hd).transpose(0, 2, 1, 3)
            v = v.reshape(b, l, heads, hd).transpose(0, 2, 1, 3)
            if seg is not None:
                ctx = _segment_attention(q, k, v, seg, 1.0 / np.sqrt(hd))
            else:
                ctx = attention(
                    q, k, v, mask, config.causal, use_flash, mesh
                )
            ctx = ctx.astype(compute_dtype)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, l, config.hidden)
        attn_out = (
            ctx @ layer["out"].astype(compute_dtype)
            + layer["out_b"].astype(compute_dtype)
        )
        if post_ln:
            x = _layer_norm(
                x + attn_out, layer["ln1"]["scale"], layer["ln1"]["bias"],
                eps=eps,
            ).astype(compute_dtype)
            y = x
        else:
            x = x + attn_out
            y = _layer_norm(x, layer["ln2"]["scale"], layer["ln2"]["bias"])
        y = (
            y @ layer["up"].astype(compute_dtype)
            + layer["up_b"].astype(compute_dtype)
        )
        if post_ln:
            # exact erf GELU (BERT convention), in f32 for checkpoint parity
            y32 = y.astype(jnp.float32)
            y = (y32 * 0.5 * (1.0 + jax.scipy.special.erf(
                y32 * 0.7071067811865476
            ))).astype(compute_dtype)
        else:
            y = y * 0.5 * (
                1.0 + jnp.tanh(0.7978845608 * (y + 0.044715 * y**3))
            )
        mlp_out = (
            y @ layer["down"].astype(compute_dtype)
            + layer["down_b"].astype(compute_dtype)
        )
        if post_ln:
            x = _layer_norm(
                x + mlp_out, layer["ln2"]["scale"], layer["ln2"]["bias"],
                eps=eps,
            ).astype(compute_dtype)
        else:
            x = x + mlp_out

    if not post_ln:
        x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    if return_hidden or config.pooling == "none":
        logits = jnp.einsum(
            "blh,vh->blv", x.astype(jnp.float32), params["embed"]
        )
        return logits
    if seg is not None:
        # per-segment mean pooling: one-hot the segment ids and contract
        # the token axis on the MXU — [B, L, H] x [B, L, S] -> [B, S, H].
        # Same dtype discipline as the classic branch (sum in x.dtype,
        # normalize in f32); empty slots pool to the zero vector.
        oh = (
            seg[:, :, None] == jnp.arange(1, max_segments + 1)[None, None, :]
        ).astype(x.dtype)
        pooled = jnp.einsum("blh,bls->bsh", x, oh) / (
            oh.sum(axis=1)[:, :, None] + 1e-9
        )
    elif config.pooling == "cls":
        pooled = x[:, 0, :]
    else:  # mean over valid tokens
        m = mask[:, :, None].astype(x.dtype)
        pooled = (x * m).sum(1) / (m.sum(1) + 1e-9)
    # L2-normalize (SentenceTransformer convention)
    pooled = pooled.astype(jnp.float32)
    pooled = pooled / (
        jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-9
    )
    return pooled


LM = TransformerLM  # `model_module(config).LM`

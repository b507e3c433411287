"""Byte-level trunk with chunked linear attention (EVA; EvaByte's published
sizes are the defaults), as ONE pipeline stage holds it: the document
store's embedder on the ingest path, for documents far longer than a row of
512 word tokens.

A text is its UTF-8 bytes (`tokenizer.ByteTokenizer`: `<bos>`, then 64 +
byte).  A stage holds the embedding and `layers` of the model's layers; the
later layers lie on further chips, and what this stage computes is pooled.

Per layer, x [T, hidden] float32 (`fp32_skip_add`: the residual sums are
float32), norm(x) = x / rms(x) * (1 + w) (`norm_add_unit_offset`), no biases:

  h = norm1(x); q, k, v = h W_q, h W_k, h W_v in heads of `head_dim`; RoPE
  (`trunk.rope`'s pairs (x[i], x[i + d/2]), the whole head, positions
  restart at every document) on q and k
  the document's positions are cut into windows of `window_size` and chunks
  of `chunk_size`, both counted from its first token; for head h and every
  chunk j: a_t = softmax over t in j of (k_t . phi_h) s; kbar_j = sum a_t
  k_t + mu_h; vbar_j = sum a_t v_t   (phi, mu: `adaptive_phi`,
  `adaptive_mu_k`; s = head_dim^-1/2)
  query i in window w scores the keys t <= i of its own window and kbar_j of
  every chunk of the document's windows before w, in ONE softmax at scale
  s; the output is the weighted sum of those v_t and vbar_j
  x += ctx W_o;  h = norm2(x);  x += (silu(h W_g) * (h W_u)) W_d

then a final norm, the mean over a document's tokens and L2 normalisation,
as `transformer.forward` pools.  A document of at most one window is plain
causal attention.  This is the prefill form: no output head, no decode
state for the windows and summaries, no generation (PERF.md section 7).

Program shape: the matmuls compute in `dtype` (bfloat16) from parameters
resident in `param_dtype`, with f32 accumulation; the chunk softmax, the
attention softmax and the norms are f32.  Where each document's windows
and summaries lie is worked out once for all layers from the segment ids
(`ops/kernels/eva_attention.py::window_layout`); on the TPU the attention
is that module's Pallas kernel, elsewhere its dense definition.  Row
lengths come in pairs of the kernel's key tiles (`trunk.seq_bucket`), so
a run of files whose byte lengths jitter compiles its slab shapes once.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import numpy as np

from pathway_tpu.models.trunk import (
    CHUNK_TOKENS, PackedTrunk, PackedTrunkLM, _dtype, _normal, one_chip_only,
    pooled_by_row_groups, rms_norm, slab_shapes,
)
from pathway_tpu.ops.kernels import eva_attention as kernel


@dataclasses.dataclass(frozen=True)
class EvaConfig:
    vocab_size: int = 320  # 64 specials + 256 bytes
    hidden: int = 4096
    layers: int = 16  # of the model's 32: one of two pipeline stages
    heads: int = 32
    mlp_dim: int = 11008
    window_size: int = 2048
    chunk_size: int = 16
    rope_theta: float = 100000.0
    norm_eps: float = 1e-5
    max_len: int = 8192  # bytes
    dtype: str = "bfloat16"  # what the matmuls compute in
    param_dtype: str = "bfloat16"  # what the parameters are resident in
    pooling: str = "mean"
    causal: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    def active_flops_per_token(self, seq: float) -> float:
        """Forward FLOPs one token of a `seq`-token document needs on this
        stage (`internals/costmodel.py` multiplies by the real tokens): the
        seven matrices of a layer, and the scores and mixes of the keys and
        summaries a token meets on average."""
        proj = 4 * self.hidden * self.hidden + 3 * self.hidden * self.mlp_dim
        tokens, summaries = scored_pairs(seq, self.window_size, self.chunk_size)
        met = (tokens + summaries) / max(seq, 1.0)
        return 2.0 * self.layers * (proj + 2 * self.hidden * met)


TINY = EvaConfig(
    hidden=64, layers=3, heads=4, mlp_dim=160, window_size=32, chunk_size=4,
    max_len=256, dtype="float32", param_dtype="float32",
)


def scored_pairs(tokens, window: int, chunk: int):
    """((query, key) pairs, (query, summary) pairs) the attention of one
    document of `tokens` tokens scores in one head of one layer: the
    triangle of every window, and for a query in window w the `w * window /
    chunk` summaries of the windows before it.  Counts, not a shape: numpy
    arrays pass through."""
    full, rest = np.divmod(tokens, window)
    keys = full * (window * (window + 1) // 2) + rest * (rest + 1) // 2
    per_window = window // chunk
    # windows 1 .. full-1 are whole, window `full` holds the rest
    summaries = per_window * (window * (full * (full - 1) // 2) + rest * full)
    return keys, summaries


def tokenizer(config: EvaConfig):
    """The tokenizer a configuration of this module reads texts with, and
    the slab shapes its kernel takes (`minilm.SentenceEncoder`)."""
    from pathway_tpu.models.tokenizer import ByteTokenizer

    return ByteTokenizer(
        vocab_size=config.vocab_size,
        shapes=slab_shapes(kernel.LANES, 2 * kernel.KEY_TILE, CHUNK_TOKENS),
    )


def init_params(rng, config: EvaConfig) -> Dict[str, Any]:
    """Random weights, made leaf by leaf in float32 and kept in
    `param_dtype` (chipbench's reference repeats the recipe from the
    configuration file's `init`, not from here): the key split into 2 +
    layers; key 0 the embedding ~ N(0, 1); layer i splits key 2+i into 9:
    W_q, W_k, W_v, W_o, gate, up, down ~ N(0, 1/fan_in), then
    `adaptive_phi`, `adaptive_mu_k` [heads, head_dim] ~ N(0, 1/head_dim).
    Norm offsets 0 (a scale of one).  `wo` and `down`, the two matrices
    whose products are added to the f32 residual, are kept transposed
    ([hidden, n]): the layout the TPU compiler gives them for that matmul.
    As drawn it copied both in every layer of every dispatch, and under
    the row-group loop lifted those copies out of it: 2 GB alive at once
    beside the parameters in the read-back's program."""
    import jax
    import jax.numpy as jnp

    c = config
    h, f = c.hidden, c.mlp_dim

    def dense(key, shape, fan_in):
        return _normal(tuple(shape), fan_in, c.param_dtype)(key)

    keys = jax.random.split(rng, 2 + c.layers)
    params: Dict[str, Any] = {
        "embed": dense(keys[0], (c.vocab_size, h), 1),
        "ln_f": jnp.zeros((h,)),
        "layers": [],
    }
    for i in range(c.layers):
        k = jax.random.split(keys[2 + i], 9)
        params["layers"].append({
            "ln1": jnp.zeros((h,)), "ln2": jnp.zeros((h,)),
            "wq": dense(k[0], (h, h), h), "wk": dense(k[1], (h, h), h),
            "wv": dense(k[2], (h, h), h), "wo": dense(k[3], (h, h), h).T,
            "gate": dense(k[4], (h, f), h), "up": dense(k[5], (h, f), h),
            "down": dense(k[6], (f, h), f).T,
            "phi": dense(k[7], (c.heads, c.head_dim), c.head_dim),
            "mu": dense(k[8], (c.heads, c.head_dim), c.head_dim),
        })
    return params


# what `one_chip_only` says of this trunk: module, what it holds, what is not built
_ONE_CHIP = ("eva", "one pipeline stage", "the hand-over between stages")


def param_sharding_rules(config: EvaConfig, mesh):
    one_chip_only(mesh, *_ONE_CHIP)


def packed_attention_fused(config: EvaConfig, length: int,
                           use_flash: Optional[bool] = None) -> bool:
    """Whether a slab of `length` slots runs the fused kernel or its dense
    definition: the backend and the static shape, as
    `transformer.packed_attention_fused` decides for the encoders.  The
    launch site asks again to count the batch.  `use_flash` overrides
    (tests run the kernel interpreted on the CPU)."""
    if use_flash is not None:
        return use_flash
    import jax

    return jax.default_backend() == "tpu" and kernel.supports(
        length, config.heads, config.head_dim, config.window_size, config.chunk_size
    )


def chunk_summaries(k, v, layer, layout, config: EvaConfig, fused: bool = False):
    """(kbar, vbar) [B, C, hidden]: for every chunk that `layout` gave a
    slot, the softmax-weighted sum of its `chunk_size` keys (plus mu) and
    values, a_t = softmax over the chunk of (k_t . phi) s, in f32.  k
    (rotated), v: [B, L, hidden].  An empty slot reads the row's last
    chunk and is seen by nobody.  `fused`: the sums are `kernel.pool_chunks`'
    (each chunk's rows copied from the tile they begin in, the rows before
    and after the chunk weighted 0); else a gather of the rows, products
    and sums: the definition, and the path off the TPU."""
    import jax
    import jax.numpy as jnp

    c = config
    b, l, _ = k.shape
    start = layout["chunk_start"]  # [B, C]
    span = kernel.pool_span(c.chunk_size) if fused else c.chunk_size
    first = jnp.minimum(start // kernel.ROW_TILE * kernel.ROW_TILE, l - span) if fused else start
    at, offset = jnp.arange(span), (start - first)[:, :, None]
    rows = (first[:, :, None] + at).reshape(b, -1, 1)

    def chunks(a):  # [B, L, n] -> [B, C, span, n]: each slot's run of rows, one gather
        taken = jnp.take_along_axis(a, rows, axis=1)
        return taken.reshape(b, start.shape[1], span, a.shape[2])

    # a head's phi meets the hidden axis as the matmuls left it, through a
    # block-diagonal [hidden, H] of ones: a thin matmul, no relayout into heads
    own = jnp.repeat(jnp.eye(c.heads, dtype=jnp.float32), c.head_dim, axis=0)
    z = jnp.dot(
        k, (own * layer["phi"].astype(jnp.float32).reshape(-1, 1)).astype(k.dtype),
        preferred_element_type=jnp.float32,
    ) * c.head_dim ** -0.5  # [B, L, H]
    inside = (at >= offset) & (at < offset + c.chunk_size)
    a = jax.nn.softmax(jnp.where(inside[..., None], chunks(z), -jnp.inf), axis=2)
    if fused:
        return kernel.pool_chunks(
            k, v, a.reshape(b, -1, c.heads), layer["mu"], first
        )
    a = jnp.dot(a, own.T, precision=jax.lax.Precision.HIGHEST)  # [B, C, chunk, hidden], exact
    kbar = (a * chunks(k)).sum(2) + layer["mu"].astype(jnp.float32).reshape(-1)
    vbar = (a * chunks(v)).sum(2)
    return kbar.astype(k.dtype), vbar.astype(k.dtype)


def _into_hidden(a, w_t):
    """a [B, L, n] @ W for a W kept as its transpose [hidden, n] (`wo`,
    `down`: `init_params`)."""
    import jax

    return jax.lax.dot_general(a, w_t, (((2,), (1,)), ((), ())))


def _rotate(x, cos, sin, scale: float):
    """`kernel.rope`'s definition, and the path off the TPU: x [B, L,
    hidden], cos, sin [B, L, head_dim] = [cos | cos], [-sin | sin];
    `trunk.rope`'s pairs (x[i], x[i + d/2]) without its [B, H, L, D]
    contract's two transposes a slab."""
    import jax.numpy as jnp

    b, l, _ = x.shape
    heads = x.reshape(b, l, -1, cos.shape[2]).astype(jnp.float32)
    out = heads * cos[:, :, None] + jnp.roll(heads, cos.shape[2] // 2, axis=-1) * sin[:, :, None]
    return (out * scale).reshape(x.shape).astype(x.dtype)


def _attention(x, layer, config: EvaConfig, layout, rope, fused: bool):
    """The attention half of a layer, without the residual.  x: [B, L, h]
    float32; rope: (cos, sin) of `_rotate`."""
    c = config
    dt = _dtype(c.dtype)
    h = rms_norm(x, 1.0 + layer["ln1"], c.norm_eps).astype(dt)
    rotate = kernel.rope if fused else _rotate
    q = rotate(h @ layer["wq"].astype(dt), *rope, scale=c.head_dim ** -0.5)
    k = rotate(h @ layer["wk"].astype(dt), *rope, scale=1.0)
    v = h @ layer["wv"].astype(dt)
    kbar = vbar = None
    if "chunk_start" in layout:
        kbar, vbar = chunk_summaries(k, v, layer, layout, c, fused)
    if fused:
        ctx = kernel.eva_attention(
            q, k, v, kbar, vbar, layout, c.heads, window=c.window_size
        )
    else:
        ctx = kernel.eva_attention_dense(q, k, v, kbar, vbar, layout, c.heads)
    return _into_hidden(ctx, layer["wo"].astype(dt))


def _trunk(params, config: EvaConfig, ids, seg, max_segments: int, fused: bool):
    """ids, seg: [B, L] -> pooled unit vectors [B, max_segments, hidden] f32."""
    import jax
    import jax.numpy as jnp

    c = config
    dt = _dtype(c.dtype)
    layout = kernel.window_layout(seg, c.window_size, c.chunk_size)
    half = c.head_dim // 2
    freqs = c.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angle = layout["pos"][:, :, None].astype(jnp.float32) * freqs  # [B, L, half]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    rope = (jnp.concatenate([cos, cos], -1), jnp.concatenate([-sin, sin], -1))
    x = params["embed"][ids].astype(jnp.float32)
    for layer in params["layers"]:
        x = x + _attention(x, layer, c, layout, rope, fused).astype(jnp.float32)
        h = rms_norm(x, 1.0 + layer["ln2"], c.norm_eps).astype(dt)
        mlp = (jax.nn.silu(h @ layer["gate"].astype(dt)) * (h @ layer["up"].astype(dt)))
        x = x + _into_hidden(mlp, layer["down"].astype(dt)).astype(jnp.float32)
    x = rms_norm(x, 1.0 + params["ln_f"], c.norm_eps).astype(dt)
    # per-segment mean pooling on the MXU, as transformer.forward pools; the
    # sum over a page's thousands of tokens stays f32
    oh = (seg[:, :, None] == jnp.arange(1, max_segments + 1)[None, None, :]).astype(dt)
    pooled = jnp.einsum("blh,bls->bsh", x, oh, preferred_element_type=jnp.float32)
    pooled = pooled / (oh.sum(axis=1, dtype=jnp.float32)[:, :, None] + 1e-9)
    return pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-9)


def forward(
    params,
    config: EvaConfig,
    ids,
    mask,
    *,
    use_flash: Optional[bool] = None,
    seg=None,
    max_segments: int = 0,
    mesh=None,
):
    """`transformer.forward`'s contract for this trunk.  ids, mask: [B, L]
    int32 -> pooled unit vectors [B, hidden]; packed (seg is not None): [B,
    max_segments, hidden], one per packed document, mask ignored.  The
    unpacked form IS the packed one with one segment a row, so the two
    cannot drift.  A slab over `trunk.CHUNK_TOKENS` slots runs as equal
    groups of rows inside the one program (a round of eight queries of
    7,168 slots is four groups of two)."""
    import jax.numpy as jnp

    one_chip_only(mesh, *_ONE_CHIP)
    packed = seg is not None
    if not packed:
        seg, max_segments = (mask > 0).astype(jnp.int32), 1
    fused = packed_attention_fused(config, ids.shape[1], use_flash)
    pooled, _ = pooled_by_row_groups(
        lambda ids, seg: (_trunk(params, config, ids, seg, max_segments, fused), {}), ids, seg
    )
    return pooled if packed else pooled[:, 0, :]


def _count_batch(config: EvaConfig, ids, seg, lengths) -> None:
    """`eva.*`: what a packed batch's attention scores (`scored_pairs`) and
    what the kernel's steps meet to score it (`kernel.met_pairs`)."""
    from pathway_tpu.internals import tracing

    c = config
    seg = np.asarray(seg)
    keys, summaries = scored_pairs(lengths, c.window_size, c.chunk_size)
    a_pair = c.heads * c.layers  # a pair is counted once a head and layer
    tracing.add("eva.tokens", n=int(lengths.sum()))
    tracing.add("eva.scored_pairs", n=int(keys.sum() + summaries.sum()) * a_pair)
    tracing.add("eva.summary_pairs", n=int(summaries.sum()) * a_pair)
    tracing.add("eva.docs_multi_window", n=int((lengths > c.window_size).sum()))
    # what the kernel's steps make of them: the block pairs and summary
    # tiles they score, and those of them that needed no mask
    met, unmasked = kernel.met_pairs(
        kernel.window_layout(seg.astype(np.int32), c.window_size, c.chunk_size, xp=np)
    )
    tracing.add("eva.met_pairs", n=met * a_pair)
    tracing.add("eva.unmasked_pairs", n=unmasked * a_pair)


PACKED = PackedTrunk("_fwd_packed_eva", lambda config: _ONE_CHIP, count_batch=_count_batch)

LM = PackedTrunkLM

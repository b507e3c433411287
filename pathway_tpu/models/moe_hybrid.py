"""Hybrid-attention MoE trunk: sliding-window layers beside global
grouped-query ones over a bias-corrected sigmoid router (the MiMo-V2
family's layer; MiMo-V2.5's published sizes are the defaults), as ONE
expert-parallel rank runs it: the document store's embedder on the ingest
path, for whole documents of thousands of word tokens.

The layers are NOT alike, and nothing here assumes they are: a layer is
global or window by the published pattern (`layer_pattern`: 0 global, 1
window; five window layers a global one), dense or expert by
`first_k_dense`; the two kinds of attention have their own key/value head
counts, RoPE ladders and sink, so parameters, the kernel's gate, the
counters and the costs go by layer kind.  What one rank of `ep_size`
holds of a layer: attention, norms and router whole (they are replicated)
and `experts_held` of the `n_routed_experts` routed experts, from
`expert_offset`; the held experts' partial sum (plus the residual) goes on
to the next layer, and nothing stands in for the absent ranks, their
exchange or the layers held on further chips.

Per layer, x [T, hidden], every norm RMSNorm, pre-norm, no biases:

  h = norm(x); one fused matrix gives `heads` query heads of `head_dim`,
  kv key heads of `head_dim` and kv value heads of `v_head_dim`, kv =
  `kv_heads_global` or `kv_heads_window`: heads / kv query heads share
  one key/value head.  RoPE (rotate-half) on the first `rotary_dim` dims
  of a head, theta by kind; positions restart at every document
  s_ij = q_i . k_j / sqrt(head_dim); token i sees j iff same document and
  j <= i (global) or i - window < j <= i (window).  A kind with a sink: a
  learned logit b_h a query head joins the softmax's denominator and
  mixes nothing.  Values are scaled by `value_scale` before the mix
  x += concat_heads(p v) W_o
  h = norm(x); the leading dense layers: x += (silu(h W_g) * (h W_u)) W_d
  the others: s = sigmoid(h W_r); I = top-k(s + beta) (the selection bias
  chooses, it never weighs); w_e = s_e / sum_{i in I} s_i;
  x += sum_{e in I, e held} w_e FFN_e(h)

then a final norm, the mean over a document's tokens and L2
normalisation, as `transformer.forward` pools.  Prefill form: no head, no
cache, no generation, none of the multi-token-prediction layers (PERF.md
section 7).

Program shape.  The fused matrix is kept as one matrix a part (`wq_nope`,
`wq_rope`, `wk_nope`, `wk_rope`, `wv`: the published matrix's columns,
regrouped once at init), so that every operand of the attention kernel
(`ops/kernels/hybrid_attention.py`, one kernel for both kinds) leaves its
matmul in the layout the kernel reads; off the TPU and on shapes its
tiling does not cover, its dense definition runs.  The expert layer IS
`moe_mla.held_experts` over `moe_mla.route` (adapted there: the selection
bias; no shared expert beside it), with its counters `moe.*`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import numpy as np

from pathway_tpu.models.decoder import _rms_norm
from pathway_tpu.models.eva import row_bucket  # rows of thousands of slots: a power of two up to 8
from pathway_tpu.models.moe_mla import (
    MoeMlaLM,
    _dtype,
    _normal,
    _swiglu,
    document_lengths,
    held_experts,
    layer_pass_lists,
    pooled_by_row_groups,
)
from pathway_tpu.models.transformer import _one_chip_only, _packed_positions
from pathway_tpu.ops.kernels import hybrid_attention as kernel

# MiMo-V2.5's `hybrid_layer_pattern` (0 global, 1 window), 48 layers
PUBLISHED_PATTERN = (0, 1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7


@dataclasses.dataclass(frozen=True)
class MoeHybridConfig:
    # `vocab_size` is the rows of the embedding this rank holds (a sliced
    # vocabulary is a smaller vocabulary: the tokenizer draws from it)
    vocab_size: int = 19072
    hidden: int = 4096
    layers: int = 7  # the first of the pattern: one dense layer and a whole period
    layer_pattern: tuple = PUBLISHED_PATTERN
    first_k_dense: int = 1
    heads: int = 64
    kv_heads_global: int = 4
    kv_heads_window: int = 8
    head_dim: int = 192
    rotary_dim: int = 64  # the first dims of a head (`partial_rotary_factor` 0.334)
    v_head_dim: int = 128
    window: int = 128
    rope_theta_global: float = 10_000_000.0
    rope_theta_window: float = 10_000.0
    sink_global: bool = False
    sink_window: bool = True
    value_scale: float = 0.707
    dense_mlp_dim: int = 16384
    expert_mlp_dim: int = 2048
    n_routed_experts: int = 256
    experts_per_token: int = 8
    routed_scaling_factor: float = 1.0
    experts_held: int = 16
    expert_offset: int = 0
    norm_eps: float = 1e-5
    max_len: int = 16384
    dtype: str = "bfloat16"  # what the matmuls compute in
    param_dtype: str = "bfloat16"  # what the parameters are resident in
    pooling: str = "mean"
    causal: bool = True

    @property
    def nope_dim(self) -> int:
        return self.head_dim - self.rotary_dim

    def is_window(self, layer: int) -> bool:
        return bool(self.layer_pattern[layer])

    def kv_heads(self, window: bool) -> int:
        return self.kv_heads_window if window else self.kv_heads_global

    def has_sink(self, window: bool) -> bool:
        return self.sink_window if window else self.sink_global

    @property
    def window_layers(self) -> int:
        return sum(self.is_window(i) for i in range(self.layers))

    def active_flops_per_token(self, seq: float) -> float:
        """Forward FLOPs one token of a `seq`-token document needs on this
        rank (`internals/costmodel.py` multiplies by the real tokens): by
        layer kind the attention matrices and the keys a token meets
        (half the document, or the window), the dense layers, and for an
        expert layer the router and the expected held pairs."""
        h, total = self.hidden, 0.0
        a_pair = self.heads * (self.head_dim + self.v_head_dim)
        for i in range(self.layers):
            window = self.is_window(i)
            kv = self.kv_heads(window)
            total += h * (
                self.heads * self.head_dim + kv * (self.head_dim + self.v_head_dim)
            ) + self.heads * self.v_head_dim * h
            met = float(scored_pairs(seq, self.window if window else None)) / max(seq, 1.0)
            total += a_pair * met
            if i < self.first_k_dense:
                total += 3 * h * self.dense_mlp_dim
            else:
                held = self.experts_per_token * self.experts_held / self.n_routed_experts
                total += h * self.n_routed_experts + held * 3 * h * self.expert_mlp_dim
        return 2.0 * total


TINY = MoeHybridConfig(
    vocab_size=512, hidden=64, layers=4, layer_pattern=(0, 1, 0, 1), heads=4,
    kv_heads_global=1, kv_heads_window=2, window=16, dense_mlp_dim=128,
    expert_mlp_dim=32, n_routed_experts=16, experts_per_token=4, experts_held=4,
    max_len=256, dtype="float32", param_dtype="float32",
)


def scored_pairs(tokens, window: Optional[int]):
    """(query, key) pairs the attention of one document of `tokens` tokens
    scores in one query head of one layer: the triangle (global), or the
    triangle of the first `window` tokens and `window` keys a token after
    them.  Counts, not a shape: numpy arrays pass through."""
    if window is None:
        return tokens * (tokens + 1) // 2
    first = np.minimum(tokens, window)
    return first * (first + 1) // 2 + (tokens - first) * window


# -- slab shapes: what `tokenizer.pack_batch` and `encode_batch` ask ---------------

# token slots the trunk takes at a time: a slab over this runs as equal
# groups of rows, one after the other inside the one program
# (`moe_mla.pooled_by_row_groups`).  What bounds it is the bytes of a row group's
# activations at this width, not a count of slots: the widest arrays are
# the dense layer's [slots, 16384] gate and up and the heads' [slots,
# 12288] queries, in bf16 80 KB a slot, 2.0 GB at 24,576 slots, which two
# dispatches in flight hold twice beside 6.7 GB of parameters and a 1.1 GB
# store on a 16 GB chip.  `moe_mla.CHUNK_TOKENS` (16,384) was set at a width
# of 7168 and a dense layer of 18,432 and would cut this trunk's dispatch
# of 24,504 tokens into two rows with 25% padding
ROW_TOKENS = 24576


def seq_bucket(n: int, maximum: Optional[int] = None) -> int:
    """A row's length: whole lanes up to one block of the global kind,
    whole such blocks above, so that both kinds' tilings divide it and
    documents whose lengths jitter by a few words compile one slab.
    `maximum` caps it, on the same grid."""
    step = kernel.LANES if n <= kernel.GLOBAL_BLOCK else kernel.GLOBAL_BLOCK
    if maximum is not None:
        n = min(n, maximum)
    return -(-max(n, 1) // step) * step


def slab_length(lengths, budget: int, max_len: int = 0) -> int:
    """The row length of a packed batch of documents `lengths` tokens
    long.  A window layer costs a token the same wherever its row ends and
    a global layer only meets a document's own blocks, so a batch takes as
    few rows as it can: one of all its tokens up to a row group of the
    trunk (ROW_TOKENS slots: documents of 8,502 and 16,002 tokens are one
    row of 24,576 slots, 0.3% of them padding, where two rows of 16,384
    would pad 25%), a row holding at most PACK_MAX_SEGMENTS documents, and
    never less than the budget or the longest document."""
    from pathway_tpu.models.tokenizer import PACK_MAX_SEGMENTS

    rows = -(-len(lengths) // PACK_MAX_SEGMENTS)
    a_row = min(-(-sum(lengths) // rows), ROW_TOKENS)
    return seq_bucket(max(budget, max(lengths), a_row))


def tokenizer(config: MoeHybridConfig):
    """The tokenizer a configuration of this module reads texts with (one
    hashed id a word, from the rows of the embedding held here), and the
    slab shapes its kernel takes (`minilm.SentenceEncoder`)."""
    from pathway_tpu.models.tokenizer import HashTokenizer, SlabShapes

    return HashTokenizer(
        vocab_size=config.vocab_size,
        shapes=SlabShapes(seq_bucket, row_bucket, slab_length),
    )


# how the selection bias and the sinks are drawn (random weights stand in
# for trained ones: both non-zero, so that a program that left either out
# would not agree with the reference): beta ~ N(0, 0.02^2) beside sigmoid
# scores whose eighth and ninth largest of 256 lie 0.005 apart; b_h ~ N(4,
# 1), a sink that takes about a third of a flat window's mass
BIAS_STD = 0.02
SINK_MEAN = 4.0


def init_params(rng, config: MoeHybridConfig) -> Dict[str, Any]:
    """Random weights, made leaf by leaf in float32 and kept in
    `param_dtype` (chipbench's reference repeats the recipe from the
    configuration file's `init`, not from here): the key split into 2 +
    layers; key 0 the embedding ~ N(0, 1); layer i splits key 2+i into 6:
    0 the fused matrix [hidden, heads x head_dim + kv x head_dim + kv x
    v_head_dim] ~ N(0, 1/hidden) (columns: the query heads, then the key
    heads, each [rotary | rest], then the value heads), regrouped here
    part by part; 1 W_o; 2 the sinks [heads] ~ N(SINK_MEAN, 1), float32,
    on the kinds that have one; a dense layer: 3 gate, 4 up, 5 down; an
    expert layer: 3 the router, 4 the selection bias [n_routed_experts] ~
    N(0, BIAS_STD^2), float32, and expert e (its global index) takes
    `fold_in(key 5, e)` split into 3, so a rank's experts are the uncut
    model's.  Norm scales 1."""
    import jax
    import jax.numpy as jnp

    c = config
    h, heads, rot, nope = c.hidden, c.heads, c.rotary_dim, c.nope_dim

    def dense(key, shape, fan_in=None):
        return _normal(tuple(shape), shape[-2] if fan_in is None else fan_in, c.param_dtype)(key)

    def split_heads(w, n: int):
        """Columns [n x (rotary | rest)] -> (rotary parts, rest parts)."""
        w = w.reshape(h, n, c.head_dim)
        return w[:, :, :rot].reshape(h, n * rot), w[:, :, rot:].reshape(h, n * nope)

    keys = jax.random.split(rng, 2 + c.layers)
    params: Dict[str, Any] = {
        "embed": dense(keys[0], (c.vocab_size, h), fan_in=1),
        "ln_f": jnp.ones((h,)),
        "layers": [],
    }
    for i in range(c.layers):
        k = jax.random.split(keys[2 + i], 6)
        window = c.is_window(i)
        kv = c.kv_heads(window)
        q_cols, k_cols = heads * c.head_dim, kv * c.head_dim
        fused = dense(k[0], (h, q_cols + k_cols + kv * c.v_head_dim))
        wq_rope, wq_nope = split_heads(fused[:, :q_cols], heads)
        wk_rope, wk_nope = split_heads(fused[:, q_cols:q_cols + k_cols], kv)
        layer = {
            "ln1": jnp.ones((h,)), "ln2": jnp.ones((h,)),
            "wq_nope": wq_nope, "wq_rope": wq_rope,
            "wk_nope": wk_nope, "wk_rope": wk_rope,
            "wv": fused[:, q_cols + k_cols:],
            "wo": dense(k[1], (heads * c.v_head_dim, h)),
        }
        if c.has_sink(window):
            layer["sink"] = SINK_MEAN + jax.random.normal(k[2], (heads,), dtype=jnp.float32)
        if i < c.first_k_dense:
            f = c.dense_mlp_dim
            layer.update(
                gate=dense(k[3], (h, f)), up=dense(k[4], (h, f)), down=dense(k[5], (f, h)),
            )
        else:
            f = c.expert_mlp_dim
            held = [
                jax.random.split(jax.random.fold_in(k[5], c.expert_offset + e), 3)
                for e in range(c.experts_held)
            ]
            layer.update(
                router=dense(k[3], (h, c.n_routed_experts)),
                router_bias=BIAS_STD * jax.random.normal(
                    k[4], (c.n_routed_experts,), dtype=jnp.float32
                ),
                experts_gate=jnp.stack([dense(ke[0], (h, f)) for ke in held]),
                experts_up=jnp.stack([dense(ke[1], (h, f)) for ke in held]),
                experts_down=jnp.stack([dense(ke[2], (f, h)) for ke in held]),
            )
        params["layers"].append(layer)
    return params


# what `_one_chip_only` says of this trunk: module, what it holds, what is not built
_ONE_CHIP = ("moe_hybrid", "one expert-parallel rank", "the exchange across ranks")


def param_sharding_rules(config: MoeHybridConfig, mesh):
    _one_chip_only(mesh, *_ONE_CHIP)


def packed_attention_fused(config: MoeHybridConfig, length: int,
                           use_flash: Optional[bool] = None) -> bool:
    """Whether a slab of `length` slots runs the fused kernel or its dense
    definition: the backend and the static shape, for every kind of layer
    the configuration has (one row length serves them all, so they go
    together), as `transformer.packed_attention_fused` decides for the
    encoders.  The launch site asks again to count the batch.  `use_flash`
    overrides (tests run the kernel interpreted on the CPU)."""
    if use_flash is not None:
        return use_flash
    import jax

    c = config
    kinds = {c.is_window(i) for i in range(c.layers)}
    return jax.default_backend() == "tpu" and all(
        kernel.supports(
            length, c.heads, c.kv_heads(window), c.nope_dim, c.rotary_dim,
            c.v_head_dim, c.window if window else None,
        )
        for window in kinds
    )


def _attention(x, layer, config: MoeHybridConfig, window: bool, seg, rope, lo, fused: bool):
    """The attention half of a layer of one kind, without the residual.
    x: [B, L, h]; rope: (cos, sin) of the kind's ladder; lo: the kind's
    `kernel.key_lo`."""
    c = config
    dt = x.dtype
    h = _rms_norm(x, layer["ln1"], c.norm_eps)
    rotate = kernel.rope if fused else kernel.rotate
    scale = c.head_dim ** -0.5
    q_nope = (h @ layer["wq_nope"].astype(dt)) * scale
    q_rope = rotate(h @ layer["wq_rope"].astype(dt), *rope, scale=scale)
    k_nope = h @ layer["wk_nope"].astype(dt)
    k_rope = rotate(h @ layer["wk_rope"].astype(dt), *rope)
    v = (h @ layer["wv"].astype(dt)) * c.value_scale
    kind = dict(
        kv_heads=c.kv_heads(window), window=c.window if window else None,
        sink=layer.get("sink"),
    )
    if fused:
        ctx = kernel.hybrid_attention(q_nope, q_rope, k_nope, k_rope, v, seg, lo, **kind)
    else:
        ctx = kernel.hybrid_attention_dense(q_nope, q_rope, k_nope, k_rope, v, seg, **kind)
    return ctx @ layer["wo"].astype(dt)


def _trunk(params, config: MoeHybridConfig, ids, seg, max_segments: int, fused: bool):
    """ids, seg: [B, L] -> (pooled unit vectors [B, max_segments, hidden]
    f32, `moe_mla._trunk`'s statistics of the expert layers)."""
    import jax.numpy as jnp

    c = config
    b, l = ids.shape
    dt = _dtype(c.dtype)
    pos = _packed_positions(seg)
    valid = (seg > 0).reshape(-1)
    # what differs by kind and not by layer, once: the ladder's angles and
    # the first key block a block of queries meets
    by_kind = {}
    for window in sorted({c.is_window(i) for i in range(c.layers)}):
        theta = c.rope_theta_window if window else c.rope_theta_global
        span = c.window if window else None
        by_kind[window] = (
            kernel.rope_tables(pos, theta),
            kernel.key_lo(seg, pos, span, kernel.block_rows(l, span)) if fused else None,
        )
    x = params["embed"][ids].astype(dt)
    stats = layer_pass_lists(c.experts_held)
    for i, layer in enumerate(params["layers"]):
        window = c.is_window(i)
        x = x + _attention(x, layer, c, window, seg, *by_kind[window], fused)
        h = _rms_norm(x, layer["ln2"], c.norm_eps)
        if "router" in layer:
            routed, counts, over, more = held_experts(
                h.reshape(b * l, c.hidden), valid, layer, c, with_stats=True
            )
            for name, value in dict(more, expert_tokens=counts, overflow=over).items():
                stats[name].append(value[None])
            x = x + routed.reshape(b, l, c.hidden)
        else:
            x = x + _swiglu(h, layer["gate"], layer["up"], layer["down"])
    x = _rms_norm(x, params["ln_f"], c.norm_eps)
    # per-segment mean pooling on the MXU, as transformer.forward pools; the
    # sum over a document's thousands of tokens stays f32
    oh = (seg[:, :, None] == jnp.arange(1, max_segments + 1)[None, None, :]).astype(dt)
    pooled = jnp.einsum("blh,bls->bsh", x, oh, preferred_element_type=jnp.float32)
    pooled = pooled / (oh.sum(axis=1, dtype=jnp.float32)[:, :, None] + 1e-9)
    pooled = pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-9)
    return pooled, {name: jnp.concatenate(parts) for name, parts in stats.items()}


def forward(
    params,
    config: MoeHybridConfig,
    ids,
    mask,
    *,
    use_flash: Optional[bool] = None,
    seg=None,
    max_segments: int = 0,
    mesh=None,
    with_stats: bool = False,
):
    """`transformer.forward`'s contract for this trunk.  ids, mask: [B, L]
    int32 -> pooled unit vectors [B, hidden]; packed (seg is not None): [B,
    max_segments, hidden], one per packed document, mask ignored.  The
    unpacked form IS the packed one with one segment a row, so the two
    cannot drift.  A slab over ROW_TOKENS slots runs as equal groups of
    rows inside the one program (a round of eight queries of 16,384 slots
    is eight groups of one).  `with_stats`: as `moe_mla.forward`."""
    import jax.numpy as jnp

    _one_chip_only(mesh, *_ONE_CHIP)
    packed = seg is not None
    if not packed:
        seg, max_segments = (mask > 0).astype(jnp.int32), 1
    fused = packed_attention_fused(config, ids.shape[1], use_flash)
    pooled, stats = pooled_by_row_groups(
        lambda ids, seg: _trunk(params, config, ids, seg, max_segments, fused),
        ids, seg, ROW_TOKENS,
    )
    if not packed:
        pooled = pooled[:, 0, :]
    if not with_stats:
        return pooled
    return pooled, dict(stats, tokens=(seg > 0).sum(dtype=jnp.int32))


class MoeHybridLM(MoeMlaLM):
    """`MoeMlaLM` for this trunk: its entry points and its routing
    statistics (`moe.*`), the packed program under a name of its own, and
    what the attention of each packed batch scores, by kind, counted into
    the span record (`hybrid.*`, internals/tracing.py) from the segment
    lengths, on the host."""

    def _packed_program(self):
        config = self.config

        def _fwd_packed_moe_hybrid(params, ids, seg, max_segments):
            import jax.numpy as jnp

            return forward(
                params, config, ids.astype(jnp.int32), None,
                seg=seg.astype(jnp.int32), max_segments=max_segments,
                with_stats=True,
            )

        return _fwd_packed_moe_hybrid

    def encode_packed(self, ids, seg, max_segments: int, *, params=None,
                      mesh=None):
        _one_chip_only(mesh, *_ONE_CHIP)
        from pathway_tpu.internals import tracing

        c = self.config
        lengths = document_lengths(seg, max_segments)
        # a pair is counted once a query head and layer
        global_pairs = int(scored_pairs(lengths, None).sum()) * c.heads * (
            c.layers - c.window_layers
        )
        window_pairs = int(scored_pairs(lengths, c.window).sum()) * c.heads * c.window_layers
        tracing.add("hybrid.tokens", n=int(lengths.sum()))
        tracing.add("hybrid.scored_pairs", n=global_pairs + window_pairs)
        tracing.add("hybrid.global_pairs", n=global_pairs)
        tracing.add("hybrid.window_pairs", n=window_pairs)
        tracing.add("hybrid.docs_over_window", n=int((lengths > c.window).sum()))
        return super().encode_packed(ids, seg, max_segments, params=params)


LM = MoeHybridLM

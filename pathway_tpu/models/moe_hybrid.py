"""Hybrid-attention MoE trunk: sliding-window layers beside global
grouped-query ones over a sigmoid router, as ONE chip of a deployment runs
it (one expert-parallel rank, or stage 0 of a pipeline): the document
store's embedder on the ingest path, for whole documents of thousands of
word tokens.  Two models' layers are this trunk: the MiMo-V2 family's
(MiMo-V2.5's published sizes are the defaults) and the Laguna family's
(`chipbench/configs/laguna-xs2-pp8-docstore.json`).

The layers are NOT alike, and nothing here assumes they are: a layer is
global or window by the published pattern (`layer_pattern`: 0 global, 1
window), dense or expert by `first_k_dense`; the two kinds of attention
have their own query and key/value head counts, rotated dims, RoPE
ladders (plain, or YaRN with its attention factor on the global kind) and
sink, so parameters, the kernel's gate, the counters and the costs go by
layer kind.  What the chip holds of a layer: attention, norms, router and a
shared expert whole, and `experts_held` of the `n_routed_experts` routed
experts, from `expert_offset`; the held experts' partial sum (plus the
residual) goes on to the next layer, and nothing stands in for the absent
ranks, their exchange or the layers held on further chips.

Per layer, x [T, hidden], every norm RMSNorm, pre-norm, no biases; H and
kv are the layer kind's query and key/value head counts:

  h = norm(x); one fused matrix gives H query heads of `head_dim`, kv key
  heads of `head_dim` and kv value heads of `v_head_dim`: H / kv query
  heads share one key/value head.  RoPE (rotate-half) on the first
  `rotary(kind)` dims of a head, the kind's ladder; a YaRN ladder's
  attention factor multiplies its cos and sin; positions restart at every
  document
  s_ij = q_i . k_j / sqrt(head_dim); token i sees j iff same document and
  j <= i (global) or i - window < j <= i (window).  A kind with a sink: a
  learned logit b_h a query head joins the softmax's denominator and
  mixes nothing.  Values are scaled by `value_scale` before the mix
  with a gate: head i's context times sigmoid(h W_g)_i
  x += concat_heads(p v) W_o
  h = norm(x); the leading dense layers: x += (silu(h W_g) * (h W_u)) W_d
  the others: s = sigmoid(h W_r); I = top-k(s + beta) (a selection bias,
  where the model has one, chooses and never weighs); w_e = factor * s_e /
  sum_{i in I} s_i; x += sum_{e in I, e held} w_e FFN_e(h) (+ FFN_shared(h)
  where the model has a shared expert)

then a final norm, the mean over a document's tokens and L2
normalisation, as `transformer.forward` pools.  Prefill form: no head, no
cache, no generation, none of the multi-token-prediction layers (PERF.md
section 7).

Program shape.  Heads of 192 (MiMo's: 128 + 64 rotated) keep the fused
matrix as one matrix a part (`wq_nope`, `wq_rope`, `wk_nope`, `wk_rope`,
`wv`: the published matrix's columns, regrouped once at init), so that
every operand of the attention kernel leaves its matmul in the layout the
kernel reads.  Heads as wide as their values (Laguna's 128, `whole_heads`)
keep `wq`, `wk`, `wv` whole: RoPE turns the rotated dims in place and the
kernel reads q and k as one operand each.  One kernel for both layouts and
both kinds (`ops/kernels/hybrid_attention.py`); off the TPU and on shapes
its tiling does not cover, its dense definition runs.  The expert layer
IS `experts.held_experts` over `experts.route` (adapted there: the
selection bias; any k and any share held), the shared expert
`experts.swiglu`, with the counters `moe.*`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import numpy as np

from pathway_tpu.models.experts import count_stats, held_experts, layer_pass_lists, swiglu
from pathway_tpu.models.trunk import (
    PackedTrunk,
    PackedTrunkLM,
    _dtype,
    _normal,
    one_chip_only,
    packed_positions,
    pooled_by_row_groups,
    rms_norm,
    slab_shapes,
    yarn_ladder,
)
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
    # by kind where a model's kinds differ (None: the global kind's):
    # query heads and rotated dims of a window layer's head
    heads_window: Optional[int] = None
    rotary_dim_window: Optional[int] = None
    # the global kind's ladder as YaRN: (factor, original_max_len,
    # beta_fast, beta_slow, attention_factor); (): the plain ladder
    yarn_global: tuple = ()
    head_gate: bool = False  # a head-wise sigmoid gate on the attention's output
    selection_bias: bool = True  # `noaux_tc`'s beta beside the router
    shared_mlp_dim: int = 0  # a shared expert beside the routed ones (0: none)
    # the published depth, where the experts' down projection is drawn at
    # its residual-output scale (0: the fan-in scale)
    depth: int = 0
    pp_size: int = 1  # a pipeline of this many stages, this chip stage 0 (1: none)

    @property
    def nope_dim(self) -> int:
        return self.head_dim - self.rotary_dim

    @property
    def whole_heads(self) -> bool:
        """q . k as wide as v (128: one operand, rotated in place), not
        cut into [rest | rotary] parts (192)."""
        return self.head_dim == self.v_head_dim

    def is_window(self, layer: int) -> bool:
        return bool(self.layer_pattern[layer])

    def kv_heads(self, window: bool) -> int:
        return self.kv_heads_window if window else self.kv_heads_global

    def q_heads(self, window: bool) -> int:
        return self.heads_window if window and self.heads_window else self.heads

    def rotary(self, window: bool) -> int:
        return self.rotary_dim_window if window and self.rotary_dim_window else self.rotary_dim

    def yarn(self, window: bool) -> tuple:
        return () if window else self.yarn_global

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
        for i in range(self.layers):
            window = self.is_window(i)
            kv, heads = self.kv_heads(window), self.q_heads(window)
            total += h * (
                heads * self.head_dim + kv * (self.head_dim + self.v_head_dim)
            ) + heads * self.v_head_dim * h + h * heads * self.head_gate
            met = float(scored_pairs(seq, self.window if window else None)) / max(seq, 1.0)
            total += heads * (self.head_dim + self.v_head_dim) * met
            if i < self.first_k_dense:
                total += 3 * h * self.dense_mlp_dim
            else:
                held = self.experts_per_token * self.experts_held / self.n_routed_experts
                total += h * self.n_routed_experts + held * 3 * h * self.expert_mlp_dim
                total += 3 * h * self.shared_mlp_dim
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
# (`trunk.pooled_by_row_groups`).  What bounds it is the bytes of a row group's
# activations at this width, not a count of slots: the widest arrays are
# the dense layer's [slots, 16384] gate and up and the heads' [slots,
# 12288] queries, in bf16 80 KB a slot, 2.0 GB at 24,576 slots, which two
# dispatches in flight hold twice beside 6.7 GB of parameters and a 1.1 GB
# store on a 16 GB chip.  `trunk.CHUNK_TOKENS` (16,384) was set at a width
# of 7168 and a dense layer of 18,432 and would cut this trunk's dispatch
# of 24,504 tokens into two rows with 25% padding
ROW_TOKENS = 24576


def tokenizer(config: MoeHybridConfig):
    """The tokenizer a configuration of this module reads texts with (one
    hashed id a word, from the rows of the embedding held here), and the
    slab shapes its kernel takes (`minilm.SentenceEncoder`)."""
    from pathway_tpu.models.tokenizer import HashTokenizer

    return HashTokenizer(
        vocab_size=config.vocab_size,
        shapes=slab_shapes(kernel.LANES, kernel.GLOBAL_BLOCK, ROW_TOKENS),
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
    layers; key 0 the embedding ~ N(0, 1); layer i splits key 2+i into 6
    (10 with `whole_heads`): 0 the fused matrix [hidden, H x head_dim + kv
    x head_dim + kv x v_head_dim] ~ N(0, 1/hidden) (columns: the query
    heads, then the key heads, each [rotary | rest], then the value
    heads), regrouped here part by part, or kept whole as `wq`, `wk`,
    `wv`; 1 W_o; 2 the sinks [H] ~ N(SINK_MEAN, 1), float32, on the kinds
    that have one; a dense layer: 3 gate, 4 up, 5 down; an expert layer: 3
    the router, 4 the selection bias [n_routed_experts] ~ N(0,
    BIAS_STD^2), float32, where the model has one, and expert e (its
    global index) takes `fold_in(key 5, e)` split into 3, so a rank's
    experts are the uncut model's (the down projection ~ N(0, 1/(2 x depth
    x expert_mlp_dim)) where `depth` is set); 6 the output gate W_g
    [hidden, H] ~ N(0, 1/hidden) where the model has one; 7, 8, 9 the
    shared expert's gate, up and down.  Norm scales 1."""
    import jax
    import jax.numpy as jnp

    c = config
    h, rot, nope = c.hidden, c.rotary_dim, c.nope_dim

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
        k = jax.random.split(keys[2 + i], 10 if c.whole_heads else 6)
        window = c.is_window(i)
        kv, heads = c.kv_heads(window), c.q_heads(window)
        q_cols, k_cols = heads * c.head_dim, kv * c.head_dim
        fused = dense(k[0], (h, q_cols + k_cols + kv * c.v_head_dim))
        layer = {"ln1": jnp.ones((h,)), "ln2": jnp.ones((h,))}
        if c.whole_heads:
            layer.update(wq=fused[:, :q_cols], wk=fused[:, q_cols:q_cols + k_cols])
        else:
            wq_rope, wq_nope = split_heads(fused[:, :q_cols], heads)
            wk_rope, wk_nope = split_heads(fused[:, q_cols:q_cols + k_cols], kv)
            layer.update(wq_nope=wq_nope, wq_rope=wq_rope, wk_nope=wk_nope, wk_rope=wk_rope)
        layer.update(
            wv=fused[:, q_cols + k_cols:], wo=dense(k[1], (heads * c.v_head_dim, h)),
        )
        if c.has_sink(window):
            layer["sink"] = SINK_MEAN + jax.random.normal(k[2], (heads,), dtype=jnp.float32)
        if c.head_gate:
            layer["head_gate"] = dense(k[6], (h, heads))
        if i < c.first_k_dense:
            f = c.dense_mlp_dim
            layer.update(
                gate=dense(k[3], (h, f)), up=dense(k[4], (h, f)), down=dense(k[5], (f, h)),
            )
        else:
            f = c.expert_mlp_dim
            down_fan_in = 2 * c.depth * f if c.depth else f
            held = [
                jax.random.split(jax.random.fold_in(k[5], c.expert_offset + e), 3)
                for e in range(c.experts_held)
            ]
            layer.update(
                router=dense(k[3], (h, c.n_routed_experts)),
                experts_gate=jnp.stack([dense(ke[0], (h, f)) for ke in held]),
                experts_up=jnp.stack([dense(ke[1], (h, f)) for ke in held]),
                experts_down=jnp.stack(
                    [dense(ke[2], (f, h), fan_in=down_fan_in) for ke in held]
                ),
            )
            if c.selection_bias:
                layer["router_bias"] = BIAS_STD * jax.random.normal(
                    k[4], (c.n_routed_experts,), dtype=jnp.float32
                )
            if c.shared_mlp_dim:
                fs = c.shared_mlp_dim
                layer.update(
                    shared_gate=dense(k[7], (h, fs)), shared_up=dense(k[8], (h, fs)),
                    shared_down=dense(k[9], (fs, h)),
                )
        params["layers"].append(layer)
    return params


def _one_chip(config: MoeHybridConfig) -> tuple:
    """What `one_chip_only` says of this trunk: module, what the chip
    holds, what is not built."""
    if config.pp_size > 1:
        return ("moe_hybrid", f"stage 0 of {config.pp_size}", "the hand-over between stages")
    return ("moe_hybrid", "one expert-parallel rank", "the exchange across ranks")


def param_sharding_rules(config: MoeHybridConfig, mesh):
    one_chip_only(mesh, *_one_chip(config))


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
    # one operand: the rotated dims are inside it (`kernel.supports`' rope 0)
    qk = (c.head_dim, 0) if c.whole_heads else (c.nope_dim, c.rotary_dim)
    return jax.default_backend() == "tpu" and all(
        kernel.supports(
            length, c.q_heads(window), c.kv_heads(window), *qk,
            c.v_head_dim, c.window if window else None,
        )
        for window in kinds
    )


def rope_ladder(config: MoeHybridConfig, window: bool) -> tuple:
    """A kind's ladder for heads rotated in place (`whole_heads`):
    (frequencies [rotary / 2] f32, the attention factor that multiplies
    cos and sin): YaRN's (`trunk.yarn_ladder`) where the kind has one,
    else theta^(-2i/rotary) and 1."""
    c = config
    rot = c.rotary(window)
    theta = c.rope_theta_window if window else c.rope_theta_global
    if c.yarn(window):
        factor, original, fast, slow, attention_factor = c.yarn(window)
        return yarn_ladder(rot, theta, factor, original, fast, slow), float(attention_factor)
    return (theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)).astype(np.float32), 1.0


def rope_angles(pos, freqs, attention_factor: float = 1.0):
    """(cos, sin) [B, L, rotary / 2] f32 of positions pos [B, L], each
    times the attention factor."""
    import jax.numpy as jnp

    angle = pos[:, :, None].astype(jnp.float32) * jnp.asarray(freqs)
    return jnp.cos(angle) * attention_factor, jnp.sin(angle) * attention_factor


def rotate_heads(x, cos, sin, head_dim: int, scale: float = 1.0):
    """x [B, L, n x head_dim]: the first 2 x cos.shape[-1] dims of every
    head turned rotate-half, pair (x[i], x[i + rot/2]), in f32; the whole
    head times `scale`."""
    import jax.numpy as jnp

    b, l, _ = x.shape
    half = cos.shape[-1]
    heads = x.reshape(b, l, -1, head_dim).astype(jnp.float32)
    a, r = heads[..., :half], heads[..., half:2 * half]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    out = jnp.concatenate([a * c - r * s, a * s + r * c, heads[..., 2 * half:]], axis=-1)
    return (out * scale).reshape(x.shape).astype(x.dtype)


def _attention(x, layer, config: MoeHybridConfig, window: bool, seg, rope, lo, fused: bool):
    """The attention half of a layer of one kind, without the residual.
    x: [B, L, h]; rope: (cos, sin) of the kind's ladder; lo: a global
    layer's `kernel.key_lo` (None for a window layer)."""
    import jax
    import jax.numpy as jnp

    c = config
    b, l, _ = x.shape
    dt = x.dtype
    h = rms_norm(x, layer["ln1"], c.norm_eps)
    scale = c.head_dim ** -0.5
    if c.whole_heads:
        q = rotate_heads(h @ layer["wq"].astype(dt), *rope, c.head_dim, scale=scale)
        k = rotate_heads(h @ layer["wk"].astype(dt), *rope, c.head_dim)
        operands = (q, None, k, None)
    else:
        rotate = kernel.rope if fused else kernel.rotate
        operands = (
            (h @ layer["wq_nope"].astype(dt)) * scale,
            rotate(h @ layer["wq_rope"].astype(dt), *rope, scale=scale),
            h @ layer["wk_nope"].astype(dt),
            rotate(h @ layer["wk_rope"].astype(dt), *rope),
        )
    v = (h @ layer["wv"].astype(dt)) * c.value_scale
    kind = dict(
        kv_heads=c.kv_heads(window), window=c.window if window else None,
        sink=layer.get("sink"),
    )
    if fused:
        ctx = kernel.hybrid_attention(*operands, v, seg, lo, **kind)
    else:
        ctx = kernel.hybrid_attention_dense(*operands, v, seg, **kind)
    if "head_gate" in layer:  # a head's context times its sigmoid gate, in f32
        gate = jax.nn.sigmoid(jnp.dot(
            h, layer["head_gate"].astype(dt), preferred_element_type=jnp.float32
        ))
        ctx = ctx.reshape(b, l, gate.shape[-1], -1).astype(jnp.float32) * gate[..., None]
        ctx = ctx.reshape(b, l, -1).astype(dt)
    return ctx @ layer["wo"].astype(dt)


def _trunk(params, config: MoeHybridConfig, ids, seg, max_segments: int, fused: bool):
    """ids, seg: [B, L] -> (pooled unit vectors [B, max_segments, hidden]
    f32, the expert layers' statistics: `experts.layer_pass_lists`)."""
    import jax.numpy as jnp

    c = config
    b, l = ids.shape
    dt = _dtype(c.dtype)
    pos = packed_positions(seg)
    valid = (seg > 0).reshape(-1)
    # what differs by kind and not by layer, once: the ladder's angles and
    # the first key block a block of queries of a global layer meets
    by_kind = {}
    for window in sorted({c.is_window(i) for i in range(c.layers)}):
        theta = c.rope_theta_window if window else c.rope_theta_global
        by_kind[window] = (
            rope_angles(pos, *rope_ladder(c, window)) if c.whole_heads
            else kernel.rope_tables(pos, theta),
            kernel.key_lo(seg, pos, kernel.block_rows(l, None)) if fused and not window else None,
        )
    x = params["embed"][ids].astype(dt)
    stats = layer_pass_lists(c)
    for i, layer in enumerate(params["layers"]):
        window = c.is_window(i)
        x = x + _attention(x, layer, c, window, seg, *by_kind[window], fused)
        h = rms_norm(x, layer["ln2"], c.norm_eps)
        if "router" in layer:
            routed, counts, over, more = held_experts(
                h.reshape(b * l, c.hidden), valid, layer, c, with_stats=True
            )
            for name, value in dict(more, expert_tokens=counts, overflow=over).items():
                stats[name].append(value[None])
            x = x + routed.reshape(b, l, c.hidden)
            if "shared_gate" in layer:
                x = x + swiglu(h, layer["shared_gate"], layer["shared_up"], layer["shared_down"])
        else:
            x = x + swiglu(h, layer["gate"], layer["up"], layer["down"])
    x = rms_norm(x, params["ln_f"], c.norm_eps)
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

    one_chip_only(mesh, *_one_chip(config))
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


def _count_batch(config: MoeHybridConfig, ids, seg, lengths) -> None:
    """`hybrid.*`: what a packed batch's attention scores, by kind, and
    what the kernel's window steps meet to score it."""
    from pathway_tpu.internals import tracing

    c = config
    # a pair is counted once a query head (of its kind) and layer
    global_pairs = int(scored_pairs(lengths, None).sum()) * c.q_heads(False) * (
        c.layers - c.window_layers
    )
    window_pairs = (
        int(scored_pairs(lengths, c.window).sum()) * c.q_heads(True) * c.window_layers
    )
    tracing.add("hybrid.tokens", n=int(lengths.sum()))
    tracing.add("hybrid.scored_pairs", n=global_pairs + window_pairs)
    tracing.add("hybrid.global_pairs", n=global_pairs)
    tracing.add("hybrid.window_pairs", n=window_pairs)
    # the kernel's window steps: every block of queries of the slab
    # against its key views, whole blocks (the tiling's count whichever
    # path ran; over `hybrid.window_pairs`, the tiling's padding)
    rows, length = np.shape(ids)
    block, n_q, views = kernel.window_tiling(length, c.window)
    tracing.add("hybrid.window_met_pairs", n=rows * n_q * views * block * block
                * c.q_heads(True) * c.window_layers)
    tracing.add("hybrid.docs_over_window", n=int((lengths > c.window).sum()))


PACKED = PackedTrunk(
    "_fwd_packed_moe_hybrid", _one_chip, count_batch=_count_batch, count_stats=count_stats
)

LM = PackedTrunkLM

"""Latent-attention + shared-expert MoE trunk (the DeepSeek-V3 family's
layer; A.X-K1's published sizes are the defaults), as ONE expert-parallel
rank runs it: the document store's embedder on the ingest path.

What one rank of `ep_size` holds of a layer: attention, norms, router and
the shared expert whole (they are replicated), and `experts_held` of the
`n_routed_experts` routed experts, from `expert_offset`.  The router keeps
its published width and its experts per token; the rank computes its own
experts' part of the result for the tokens routed to them, and that
partial sum (plus the shared expert and the residual) goes on to the next
layer.  Nothing stands in for the absent ranks or their exchange.

Per layer, x [T, hidden], every norm RMSNorm, no biases:

  h = norm(x); c_q = norm(h W_qa); q = c_q W_qb -> heads of [nope | rope]
  [c_kv | k_rope] = h W_kva; c_kv = norm(c_kv); c_kv W_kvb -> heads of
  [k_nope | v]; RoPE (YaRN ladder, interleaved pairs) on q_rope and on
  k_rope, which all heads share; positions restart at every segment
  score = (q_nope.k_nope + q_rope.k_rope) * scale; token i sees j iff same
  segment and j <= i; f32 softmax; x += concat_heads(p v) W_o
  h = norm(x); the leading dense layers: x += (silu(h W_g) * (h W_u)) W_d
  the others: s = sigmoid(h W_r); I = top-k(s); w_e = factor * s_e /
  sum_{i in I} s_i; x += sum_{e in I, e held} w_e FFN_e(h) + FFN_shared(h)

then a final norm, the mean over a segment's tokens and L2 normalisation,
as `transformer.forward` pools.  This is the prefill form of latent
attention: no head, no latent cache, no generation (PERF.md section 7).

Program shape: the q and kv up-projections are kept as separate matrices
per part (`wq_b_nope` / `wq_b_rope`, `wk_b` / `wv_b`: the published
matrices' columns, regrouped once at init), so that every operand of the
attention kernel (`ops/kernels/mla_attention.py`) leaves its matmul in the
layout the kernel reads.  The held experts run as grouped matmuls
(`jax.lax.ragged_dot`, on the TPU the device op `ragged-dot`) over a
static buffer of the selected (token, held expert) pairs sorted by expert,
each expert's group begun on a tile of the grouped matmul where the buffer
has the room; pairs beyond the buffer are counted (`overflow`), never
dropped silently.  What the path costs follows the pairs the slab holds:
the results return to their tokens in one gather a token, the few tokens
with several pairs summed first in a compact list, or, with every expert
held, each token's k rows gathered, weighted and summed in one pass
(`held_experts`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import weakref
from collections import deque
from typing import Any, Dict, Optional

import numpy as np

from pathway_tpu.models.decoder import _rms_norm, _rope
from pathway_tpu.models.transformer import (  # noqa: F401  (`tokenizer`: model_module's)
    TransformerLM,
    _packed_positions,
    _one_chip_only,
    tokenizer,
)


@dataclasses.dataclass(frozen=True)
class MoeMlaConfig:
    # `vocab_size` is the rows of the embedding this rank holds (a sliced
    # vocabulary is a smaller vocabulary: the tokenizer draws from it)
    vocab_size: int = 20480
    hidden: int = 7168
    layers: int = 6
    first_k_dense: int = 1
    heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    dense_mlp_dim: int = 18432
    expert_mlp_dim: int = 2048
    n_routed_experts: int = 192
    experts_per_token: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    experts_held: int = 12
    expert_offset: int = 0
    rope_theta: float = 10000.0
    rope_factor: float = 32.0
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    max_len: int = 512
    dtype: str = "bfloat16"  # what the matmuls compute in
    param_dtype: str = "bfloat16"  # what the parameters are resident in
    pooling: str = "mean"
    causal: bool = True

    @property
    def expert_layers(self) -> int:
        return self.layers - self.first_k_dense

    @property
    def sm_scale(self) -> float:
        """(nope + rope)^-0.5 times YaRN's mscale squared."""
        m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
        return float((self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m)

    def active_flops_per_token(self, seq: float) -> float:
        """Forward FLOPs one token of a `seq`-token document needs on this
        rank (`internals/costmodel.py` multiplies by the real tokens): the
        five attention matrices, causal attention within the document (half
        the square), the dense layers, and for an expert layer the router,
        the shared expert and the expected held pairs."""
        h, heads = self.hidden, self.heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        proj = (
            h * self.q_lora_rank + self.q_lora_rank * heads * qk
            + h * (self.kv_lora_rank + self.qk_rope_head_dim)
            + self.kv_lora_rank * heads * (self.qk_nope_head_dim + self.v_head_dim)
            + heads * self.v_head_dim * h
        )
        attn = heads * (qk + self.v_head_dim) * seq / 2.0
        held = self.experts_per_token * self.experts_held / self.n_routed_experts
        expert = 3 * h * self.expert_mlp_dim
        moe = h * self.n_routed_experts + (self.n_shared_experts + held) * expert
        dense = 3 * h * self.dense_mlp_dim
        return 2.0 * (
            self.layers * (proj + attn)
            + self.first_k_dense * dense + self.expert_layers * moe
        )


TINY = MoeMlaConfig(
    vocab_size=512, hidden=128, layers=3, heads=4, q_lora_rank=48,
    kv_lora_rank=32, dense_mlp_dim=256, expert_mlp_dim=64,
    n_routed_experts=16, experts_per_token=4, experts_held=4, max_len=128,
    dtype="float32", param_dtype="float32",
)


def _dtype(name: str):
    import jax.numpy as jnp

    return jnp.bfloat16 if name == "bfloat16" else jnp.float32


@functools.lru_cache(maxsize=None)
def _normal(shape: tuple, fan_in: int, store: str):
    """The program that makes one leaf from a key: N(0, 1/fan_in) drawn in
    float32, kept in `store`.  One program a shape, so the float32 draw
    never reaches HBM."""
    import jax
    import jax.numpy as jnp

    def make(key):
        w = jax.random.normal(key, shape, dtype=jnp.float32) / np.sqrt(fan_in)
        return w.astype(_dtype(store))

    return jax.jit(make)


def init_params(rng, config: MoeMlaConfig) -> Dict[str, Any]:
    """Random weights, made leaf by leaf in float32 and kept in
    `param_dtype`.  The recipe (chipbench's reference repeats it from the
    configuration file's `init`, not from here): split the key into
    2 + layers; key 0 the embedding ~ N(0, 1); layer i splits key 2+i into
    10; every matrix ~ N(0, 1/fan_in), so that activations keep unit
    scale and the router's logits have a spread of order 1; expert e of a
    layer (its global index) takes `fold_in(key 9, e)` split into 3, so a
    rank's experts are the uncut model's."""
    import jax
    import jax.numpy as jnp

    c = config
    h, heads = c.hidden, c.heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    kv = c.qk_nope_head_dim + c.v_head_dim

    def dense(key, shape, fan_in=None):
        return _normal(tuple(shape), shape[-2] if fan_in is None else fan_in, c.param_dtype)(key)

    def per_head(w, widths):
        """Columns [heads x sum(widths)] regrouped part by part."""
        parts = jnp.split(
            w.reshape(w.shape[0], heads, sum(widths)), np.cumsum(widths)[:-1], axis=2
        )
        return [p.reshape(w.shape[0], -1) for p in parts]

    keys = jax.random.split(rng, 2 + c.layers)
    params: Dict[str, Any] = {
        "embed": dense(keys[0], (c.vocab_size, h), fan_in=1),
        "ln_f": jnp.ones((h,)),
        "layers": [],
    }
    for i in range(c.layers):
        k = jax.random.split(keys[2 + i], 10)
        wq_b_nope, wq_b_rope = per_head(
            dense(k[1], (c.q_lora_rank, heads * qk)),
            (c.qk_nope_head_dim, c.qk_rope_head_dim),
        )
        wk_b, wv_b = per_head(
            dense(k[3], (c.kv_lora_rank, heads * kv)),
            (c.qk_nope_head_dim, c.v_head_dim),
        )
        layer = {
            "ln1": jnp.ones((h,)), "ln2": jnp.ones((h,)),
            "q_ln": jnp.ones((c.q_lora_rank,)),
            "kv_ln": jnp.ones((c.kv_lora_rank,)),
            "wq_a": dense(k[0], (h, c.q_lora_rank)),
            "wq_b_nope": wq_b_nope, "wq_b_rope": wq_b_rope,
            "wkv_a": dense(k[2], (h, c.kv_lora_rank + c.qk_rope_head_dim)),
            "wk_b": wk_b, "wv_b": wv_b,
            "wo": dense(k[4], (heads * c.v_head_dim, h)),
        }
        if i < c.first_k_dense:
            f = c.dense_mlp_dim
            layer.update(
                gate=dense(k[5], (h, f)), up=dense(k[6], (h, f)),
                down=dense(k[7], (f, h)),
            )
        else:
            f, fs = c.expert_mlp_dim, c.expert_mlp_dim * c.n_shared_experts
            held = [
                jax.random.split(jax.random.fold_in(k[9], c.expert_offset + e), 3)
                for e in range(c.experts_held)
            ]
            layer.update(
                router=dense(k[5], (h, c.n_routed_experts)),
                shared_gate=dense(k[6], (h, fs)), shared_up=dense(k[7], (h, fs)),
                shared_down=dense(k[8], (fs, h)),
                experts_gate=jnp.stack([dense(ke[0], (h, f)) for ke in held]),
                experts_up=jnp.stack([dense(ke[1], (h, f)) for ke in held]),
                experts_down=jnp.stack([dense(ke[2], (f, h)) for ke in held]),
            )
        params["layers"].append(layer)
    return params


# what `_one_chip_only` says of this trunk: module, what it holds, what is not built
_ONE_CHIP = ("moe_mla", "one expert-parallel rank", "the exchange across ranks")


def param_sharding_rules(config: MoeMlaConfig, mesh):
    _one_chip_only(mesh, *_ONE_CHIP)


def yarn_ladder(dim: int, base: float, factor: float, original_max_len: int,
                beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's frequency ladder [dim / 2] for a rotated part `dim` wide, as
    the DeepSeek family and HF's `yarn` rope type compute it: the plain
    ladder base^(-2i/dim) where a pair turns more than `beta_fast` times
    over the original length, the ladder divided by `factor` where it
    turns less than `beta_slow` times, a linear ramp between.  How the
    pairs are laid out (interleaved, rotate-half) is the caller's."""
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_at(n_rot: float) -> float:
        return dim * math.log(original_max_len / (n_rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def yarn_freqs(config: MoeMlaConfig) -> np.ndarray:
    """`yarn_ladder` of this trunk's rope part (interleaved pairs)."""
    c = config
    return yarn_ladder(c.qk_rope_head_dim, c.rope_theta, c.rope_factor,
                       c.rope_original_max_len, c.rope_beta_fast, c.rope_beta_slow)


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


def packed_attention_fused(config: MoeMlaConfig, length: int,
                           use_flash: Optional[bool] = None) -> bool:
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


def _attention(x, layer, config: MoeMlaConfig, pos, seg, fused: bool, freqs):
    """The attention half of a layer, without the residual.  x: [B, L, h]."""
    from pathway_tpu.ops.kernels.mla_attention import mla_segment_attention

    c = config
    b, l, _ = x.shape
    dt = x.dtype
    h = _rms_norm(x, layer["ln1"], c.norm_eps)
    c_q = _rms_norm(h @ layer["wq_a"].astype(dt), layer["q_ln"], c.norm_eps)
    q_nope = c_q @ layer["wq_b_nope"].astype(dt)
    q_rope = c_q @ layer["wq_b_rope"].astype(dt)
    kv_a = h @ layer["wkv_a"].astype(dt)
    c_kv = _rms_norm(kv_a[..., : c.kv_lora_rank], layer["kv_ln"], c.norm_eps)
    k_nope = c_kv @ layer["wk_b"].astype(dt)
    v = c_kv @ layer["wv_b"].astype(dt)

    def rotate(a, n_heads: int):
        # one "batch" a token, so that the rotation needs no transposes
        flat = a.reshape(b * l, n_heads, 1, c.qk_rope_head_dim)
        out = _rope(flat, pos.reshape(b * l, 1), c.rope_theta, freqs=freqs,
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


def _swiglu(h, gate, up, down):
    import jax

    dt = h.dtype
    return (jax.nn.silu(h @ gate.astype(dt)) * (h @ up.astype(dt))) @ down.astype(dt)


def route(h, router, config, bias=None):
    """h: [T, hidden] -> (experts [T, k] int32, weights [T, k] f32): top-k
    over all sigmoid scores (no group limit), the chosen scores normalised
    to sum to one and scaled.  `bias` [n_routed_experts], where a model has
    one (`e_score_correction_bias`, `topk_method` "noaux_tc"), is added to
    the scores for the selection alone: it says which experts, never how
    much of each.  Without one this is plain top-k, as it was.  The logits
    are f32: products of the compute dtype's operands, accumulated in
    f32.  `config`: any trunk's with `experts_per_token` and
    `routed_scaling_factor` (`models/moe_hybrid.py` routes here too)."""
    import jax
    import jax.numpy as jnp

    logits = jnp.dot(h, router.astype(h.dtype), preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    if bias is None:
        top, experts = jax.lax.top_k(scores, config.experts_per_token)
    else:
        _, experts = jax.lax.top_k(
            scores + bias.astype(jnp.float32), config.experts_per_token
        )
        top = jnp.take_along_axis(scores, experts, axis=-1)
    weights = config.routed_scaling_factor * top / top.sum(-1, keepdims=True)
    return experts.astype(jnp.int32), weights


# a tile of the TPU's grouped matmul: it works a tile and a group at a time
# (12 groups of 256 rows took the time of 12 of 512, and 12 of 384, every
# second of which lies across two tiles, half as much again: chip runs, PR
# 33), so the buffer's rows come in whole tiles and a group begins one
PAIR_ROWS = 512


def pair_capacity(tokens: int, config) -> int:
    """Rows of the static buffer of (token, held expert) pairs for a slab
    of `tokens` slots, from the slots, k = `experts_per_token` and the
    share held / routed = `experts_held` / `n_routed_experts`, for any k
    and share.  The most pairs a slab can put on held experts is `most` =
    tokens x min(k, held).  Up to 4,096 rows that many: small batches
    cannot overflow.  Above that one row a token slot.  The expected load
    is tokens x k x held / routed: where that is under the rows (half a
    row a token at a sixteenth of the experts held, top-8; the slab's
    padding routes nothing), the rows themselves are the room that lets
    every group begin a tile (`held_experts`), and a skewed router's pairs
    still fit one after the other.  Where it fills them (every expert held:
    k pairs a token), the rows are `most` and a tile a held expert, which
    is the most that beginning each group on a tile can take: no pair can
    fall beyond the buffer (top-1 of 16: 28,224 slots -> 36,864 rows; top-8
    of 256: 23,552 -> 319,488).  Rounded up to whole tiles: at 14,112
    rows, which 512 does not divide, the TPU's grouped matmul took 8.6 ms
    where it takes 2.5 at 14,336 (chip runs, PR 30)."""
    most = tokens * min(config.experts_per_token, config.experts_held)
    rows = min(most, max(tokens, 4096))
    if tokens * config.experts_per_token * config.experts_held >= rows * config.n_routed_experts:
        rows = most + config.experts_held * PAIR_ROWS
    return -(-rows // PAIR_ROWS) * PAIR_ROWS


def combine_rows(tokens: int, config) -> int:
    """Slots of the compact list of tokens with two or more pairs in the
    buffer (`held_experts`' return), for any k and share.  At k = 1 there
    are no such tokens and no list.  A slot a token (the tokens are the
    list, which cannot spill): a slab of at most 4,096 token slots (the
    buffer's least size), whose program stays as small as it was, which is
    what the search programs' query slabs are loaded for; and a share where
    a token expects a held pair or more (k x held / routed >= 1: every
    expert held at top-8 gives every real token 8).  Otherwise an eighth
    of the token slots in whole tiles: 2,048 for 14,112, where k x held /
    routed = 1/2 (a sixteenth of the experts held, top-8) gives about 915
    such tokens."""
    if config.experts_per_token == 1:
        return 0
    if (tokens <= 4096
            or config.experts_per_token * config.experts_held >= config.n_routed_experts):
        return tokens
    return -(-tokens // (8 * PAIR_ROWS)) * PAIR_ROWS


def returns_fused(config) -> bool:
    """Whether `held_experts` returns the pairs in one fused weighted sum at
    the tokens' side: k > 1 with every routed expert held.  Then every real
    token has k pairs and the buffer is k rows a token and a tile a held
    expert (13.6 rows a token slot at top-8 of 256), so a pass over the
    buffer costs many over the tokens, and a loop bounded by the busiest
    token always makes k passes.  Where a sixteenth of the experts is held
    (about a row a token, 1-8 pairs) the compact list and the buffer-side
    weight stay; at k = 1 there is no sum."""
    return config.experts_per_token > 1 and config.experts_held >= config.n_routed_experts


def held_experts(h, valid, layer, config, capacity: Optional[int] = None,
                 *, listed: Optional[int] = None, with_stats: bool = False,
                 routing=None):
    """The routed experts' part of an expert layer that this rank
    computes.  h: [T, hidden] (normed), valid: [T] bool (padding routes
    nothing).  Returns (y [T, hidden], tokens per held expert
    [experts_held] int32, pairs selected and held but beyond the buffer
    () int32), and with `with_stats` a fourth: {"multi_pair_tokens",
    "combine_spills", "groups_aligned", "groups_packed", "group_rows",
    "group_pad_rows"}, each () int32, and "fused_returns" (1) where the
    return is fused.  `capacity` and `listed` override `pair_capacity` and
    `combine_rows` (tests).  `config`: this module's
    or another trunk's with the same routing fields, k =
    `experts_per_token`, `experts_held` of `n_routed_experts` from
    `expert_offset`, any k and any share (`models/moe_hybrid.py`: a
    sixteenth of 256 experts at width 4096 under a selection bias
    `layer["router_bias"]`, or all 256 of width 512 at top-8;
    `models/zaya.py`: all 16 at top-1); the shared expert, where a model
    has one, is the caller's.  `routing`: (experts [T, k] int32, weights [T, k] f32) from
    a trunk whose router is not `route`'s matrix and sigmoid
    (`models/zaya.py`: an MLP over a state carried from layer to layer, a
    softmax, and a choice beyond the routed experts, "skip", which is an
    expert nobody holds); None: `route(h, layer["router"], ...)`.

    Selected pairs on held experts are sorted by expert into a buffer of
    `capacity` rows, and three grouped matmuls (gate, up, down) run over
    the groups' rows.  The TPU's grouped matmul works a 512-row tile and a
    group at a time, so a group of 476 rows that begins in the middle of a
    tile costs two tiles' time: 12 such groups one after the other took
    6.74 ms for the three matmuls whatever the buffer's size (14,336 rows
    or 7,168), and 4.17 ms with every group moved to a tile's first row
    (chip runs, PR 33).  So each group begins a tile where the buffer has
    the room (12 to 17 of its 28 tiles at the ingest slab), and otherwise
    the groups follow each other as the overflow count assumes: the layout
    is data (`sizes`), not a second program.  Which pair a buffer row
    holds follows from its group's shift and the end of its pairs laid
    along the rows by a running sum of their steps at the groups' ends:
    no [experts_held, rows] mask (84 M elements a layer at 256 held) and
    no search.  A search of the ends with its gathers of a group's values
    took 31 ms a layer-pass at 319,488 rows, a quarter of the Laguna
    cell's busy time: on the TPU a gather of one element costs about 10
    ns (chip runs, PR 44).  A pair's row is its rank in the sorted order
    plus its group's shift, read through the fused [experts_held, pairs]
    compare that also counts the groups.  `group_rows` counts the rows the
    grouped matmuls run over, `group_pad_rows` those of them that hold no
    pair.

    With every expert held at k > 1 (`returns_fused`: 319,488 buffer rows
    for 23,552 token slots at top-8 of 256) a token's k rows come back in
    one fused weighted sum, the weight put on at the token's side, in slot
    order and in the compute dtype: a pass over the buffer to put the
    weights on, their one-element gathers and a loop of k passes over the
    tokens took 55 ms a dispatch of four expert layers on a TPU v5e, 18.5%
    of the Laguna cell's busy time, and the fused sum takes about half of
    it; `fused_returns` counts such passes.  Otherwise the results go back
    to their tokens in one gather a token.  A pair's weight is put on its
    row in the buffer.  A token with one pair reads that row, a token with
    none a zero: at k = 1 that is the whole return, one inverse permutation.
    The tokens with two or more (8% at k x held / routed = 1/2: a sixteenth
    of the experts held, top-8) are first summed, in the compute dtype and
    in their slots' order, in a list of `listed` slots appended to the
    buffer, and read their sum.  More such tokens than slots is seen in the
    input: then the others' further pairs are added pass by
    pass over every token, and no pair is dropped.  Where the list has a
    slot a token (a small slab, or a share at which every token expects
    several pairs: `combine_rows`), the tokens are the list, their sums
    are `y`, one gather a pass and k passes at most, and nothing can
    spill: the search programs' query slabs stay as small
    as they were (a program's load from the compile cache took 0.28 s for
    0.15 with the list built there too, chip runs, PR 33).  At [14112,
    7168] the return took 7.1 ms as one pass over every token for every
    held pair the busiest token has (4 or 5 of its 8; 1.5 ms a pass in the
    program) and takes 3.8 (chip runs, PR 33); a scatter-add of the
    buffer's rows took 14.0 ms (chip runs, PR 30)."""
    import jax
    import jax.numpy as jnp

    c = config
    t, k, n_held = h.shape[0], c.experts_per_token, c.experts_held
    capacity = pair_capacity(t, c) if capacity is None else capacity
    listed = combine_rows(t, c) if listed is None else listed
    if routing is None:
        routing = route(h, layer["router"], c, layer.get("router_bias"))
    experts, weights = routing
    local = experts - c.expert_offset
    held = (local >= 0) & (local < n_held) & valid[:, None]
    group = jnp.where(held, local, n_held).reshape(-1)  # [T*k]; n_held = not ours
    member = group[None, :] == jnp.arange(n_held, dtype=jnp.int32)[:, None]
    counts = jnp.sum(member, axis=1, dtype=jnp.int32)
    tiles = -(-counts // PAIR_ROWS) * PAIR_ROWS
    aligned = tiles.sum() <= capacity
    ends = jnp.minimum(jnp.cumsum(jnp.where(aligned, tiles, counts)), capacity)
    sizes = jnp.diff(ends, prepend=0)  # the groups as the grouped matmul sees them
    starts = ends - sizes
    kept = jnp.minimum(counts, sizes)  # the pairs of each that the buffer holds
    overflow = counts.sum() - kept.sum()
    order = jnp.argsort(group, stable=True)  # held pairs first, by expert
    first = jnp.cumsum(counts) - counts  # a group's first pair in that order
    shift = starts - first  # how far down the buffer from there its rows lie
    dt = h.dtype

    def of(per_group, mask):  # mask: [experts_held, n] bool, one group a column at most
        return jnp.sum(jnp.where(mask, per_group[:, None], 0), axis=0)

    # the pair a buffer row holds, if any: its group's shift and the end of
    # its pairs laid along the rows, a running sum of their steps at the
    # groups' ends (a row at or past the last end reads the last group's)
    at = jnp.arange(capacity, dtype=jnp.int32)
    per_group = jnp.stack([shift, starts + kept], axis=1)
    steps = jnp.zeros((capacity, 2), jnp.int32).at[ends[:-1]].add(
        per_group[1:] - per_group[:-1], mode="drop"
    )
    along = per_group[0] + jnp.cumsum(steps, axis=0)
    filled = at < along[:, 1]
    pair = jnp.where(filled, order[jnp.clip(at - along[:, 0], 0, t * k - 1)], 0)
    rows = h[pair // k]  # [capacity, hidden]
    with jax.named_scope("expert_matmul"):
        gate = jax.lax.ragged_dot(rows, layer["experts_gate"].astype(dt), sizes)
        up = jax.lax.ragged_dot(rows, layer["experts_up"].astype(dt), sizes)
        out = jax.lax.ragged_dot(
            jax.nn.silu(gate) * up, layer["experts_down"].astype(dt), sizes
        )
    fused = returns_fused(c)
    if not fused:
        # a pair's weight goes on here.  The other rows hold no pair or were
        # never written: a zero weight does not silence what they hold
        weight = weights.reshape(-1)[pair].astype(dt)
        out = jnp.where(filled[:, None], weight[:, None] * out, jnp.zeros_like(out))
    # a pair's row in the buffer; each token's pairs that are in it moved
    # to the front of its k slots
    nth_sorted = jnp.argsort(order).astype(jnp.int32)
    mine = held & (nth_sorted < of(first + kept, member)).reshape(t, k)
    row = (nth_sorted + of(shift, member)).reshape(t, k)
    nth = jnp.cumsum(mine, axis=1) - 1
    slot = mine[:, :, None] & (nth[:, :, None] == jnp.arange(k)[None, None, :])
    row_of = jnp.sum(jnp.where(slot, row[:, :, None], 0), axis=1)  # [T, k]
    pairs_of = jnp.sum(mine, axis=1, dtype=jnp.int32)  # [T]
    multi = pairs_of > 1
    n_multi = jnp.sum(multi, dtype=jnp.int32)

    def nth_pair(rows_of, j, has):
        at_j = jax.lax.dynamic_slice_in_dim(rows_of, j, 1, axis=1)[:, 0]
        return jnp.where(has[:, None], out[at_j], jnp.zeros((), dt))

    def summed(rows_of, pairs):
        """Each entry's pairs, in the compute dtype as the residual stream
        is, in their slots' order: as many passes as the busiest has."""
        return jax.lax.fori_loop(
            1, jnp.max(pairs),
            lambda j, acc: acc + nth_pair(rows_of, j, j < pairs),
            nth_pair(rows_of, 0, 0 < pairs),
        )

    if k == 1:  # a pair a token at most: one inverse permutation
        y = jnp.where(mine, out[row[:, 0]], jnp.zeros((), dt))
    elif fused:
        # a token's k rows gathered, weighted and summed in one expression,
        # in slot order; the mask after the product, so that a row no pair
        # filled is never read into `y`
        w_of = jnp.sum(jnp.where(slot, weights[:, :, None], 0.0), axis=1).astype(dt)

        def term(j):
            return jnp.where((j < pairs_of)[:, None], w_of[:, j, None] * out[row_of[:, j]],
                             jnp.zeros((), dt))

        y = term(0)
        for j in range(1, k):
            y = y + term(j)
    elif listed >= t:  # a slot a token: the tokens are the list
        y = summed(row_of, pairs_of)
    else:
        # the tokens with two or more pairs, in the list's slots (the search
        # compares every slot with every token: 0.02 ms where the binary
        # search's loop took 0.21, chip runs, PR 33)
        listed_at = jnp.cumsum(multi, dtype=jnp.int32) - 1
        in_list = multi & (listed_at < listed)
        token_of = jnp.minimum(
            jnp.searchsorted(
                listed_at, jnp.arange(listed, dtype=jnp.int32), method="compare_all"
            ),
            t - 1,
        )
        comb = summed(
            row_of[token_of],
            jnp.where(jnp.arange(listed) < n_multi, pairs_of[token_of], 0),
        )
        y = jnp.concatenate([out, comb])[
            jnp.where(in_list, capacity + listed_at, row_of[:, 0])
        ]
        y = jnp.where((pairs_of > 0)[:, None], y, jnp.zeros_like(y))
        # more such tokens than slots: the others' further pairs, in as
        # many passes over every token as the busiest of them has pairs
        spilled = multi & ~in_list
        y = jax.lax.fori_loop(
            1, jnp.where(n_multi > listed, jnp.max(pairs_of), 1),
            lambda j, y: y + nth_pair(row_of, j, spilled & (j < pairs_of)), y,
        )
    if not with_stats:
        return y, counts, overflow
    stats = {
        "multi_pair_tokens": n_multi,
        "combine_spills": (n_multi > listed).astype(jnp.int32),
        "groups_aligned": aligned.astype(jnp.int32),
        "groups_packed": 1 - aligned.astype(jnp.int32),
        "group_rows": ends[-1],
        "group_pad_rows": ends[-1] - kept.sum(),
    }
    if fused:
        stats["fused_returns"] = jnp.int32(1)
    return y, counts, overflow, stats


# what `held_experts` counts of a pass (a row group's pass through one expert
# layer) beside the tokens per expert and the overflow: each a counter
# `moe.<name>`.  "fused_returns" is in the statistics only where the return
# is fused (`returns_fused`): elsewhere its counter reads 0 and the program
# has no output that always reads 0
LAYER_PASS_STATS = (
    "multi_pair_tokens", "combine_spills", "groups_aligned", "groups_packed",
    "group_rows", "group_pad_rows", "fused_returns",
)

def layer_pass_lists(config) -> dict:
    """What a trunk gathers of its expert layers' passes, a list a name
    that begins empty: "expert_tokens" [layers, experts_held], "overflow"
    and each of LAYER_PASS_STATS that `held_experts` gives under `config`
    [layers]."""
    import jax.numpy as jnp

    stats = {"expert_tokens": [jnp.zeros((0, config.experts_held), jnp.int32)]}
    for name in ("overflow",) + LAYER_PASS_STATS:
        if name != "fused_returns" or returns_fused(config):
            stats[name] = [jnp.zeros((0,), jnp.int32)]
    return stats


# token slots the trunk takes at a time.  A slab's rows do not see each
# other (attention stays inside a row, routing inside a token), so a slab
# over this runs as equal groups of rows, one after the other inside the
# one program: the activations of a 28k-token ingest slab, 2.7 GB, halve,
# which is what lets two dispatches be in flight beside the parameters and
# the store on a 16 GB chip (PERF.md section 6, PR 30), while a group still
# hands each held expert hundreds of rows
CHUNK_TOKENS = 16384


def row_chunks(rows: int, length: int, cap: Optional[int] = None) -> int:
    """Into how many equal groups of rows a [rows, length] slab is cut:
    the fewest whose groups hold at most `cap` token slots (CHUNK_TOKENS; a
    trunk of another width states its own: `moe_hybrid.ROW_TOKENS`)."""
    cap = CHUNK_TOKENS if cap is None else cap
    for n in range(1, rows + 1):
        if rows % n == 0 and rows // n * length <= cap:
            return n
    return rows


def pooled_by_row_groups(trunk, ids, seg, cap: Optional[int] = None):
    """`trunk(ids, seg) -> (pooled [rows, S, hidden], statistics)` over a
    slab cut into `row_chunks` groups of rows, one after the other inside
    the one program; the groups' statistics summed."""
    import jax

    b, l = ids.shape
    n = row_chunks(b, l, cap)
    if n == 1:
        return trunk(ids, seg)
    pooled, stats = jax.lax.map(
        lambda part: trunk(*part), (ids.reshape(n, b // n, l), seg.reshape(n, b // n, l))
    )
    return (
        pooled.reshape(b, *pooled.shape[2:]),
        {name: per_group.sum(0) for name, per_group in stats.items()},
    )


def document_lengths(seg, max_segments: int) -> np.ndarray:
    """Tokens of each document of a packed batch, int64, from its segment
    ids on the host (seg: [rows, L], 1..max_segments per packed document,
    0 = padding): a row's documents are its runs of one segment id.  What
    the trunks count their batches' tokens and scored pairs from."""
    seg = np.asarray(seg)
    rows = np.arange(seg.shape[0])[:, None] * (int(max_segments) + 1)
    lengths = np.bincount((rows + seg)[seg > 0])
    return lengths[lengths > 0].astype(np.int64)


def _trunk(params, config: MoeMlaConfig, ids, seg, max_segments: int, fused: bool):
    """ids, seg: [B, L] -> (pooled unit vectors [B, max_segments, hidden]
    f32, {"expert_tokens": tokens per held expert [expert layers,
    experts_held], "overflow" and each of LAYER_PASS_STATS [expert layers]})."""
    import jax.numpy as jnp

    c = config
    b, l = ids.shape
    dt = _dtype(c.dtype)
    pos = _packed_positions(seg)
    freqs = jnp.asarray(yarn_freqs(c))
    valid = (seg > 0).reshape(-1)
    x = params["embed"][ids].astype(dt)
    stats = layer_pass_lists(c)
    for layer in params["layers"]:
        x = x + _attention(x, layer, c, pos, seg, fused, freqs)
        h = _rms_norm(x, layer["ln2"], c.norm_eps)
        if "router" in layer:
            routed, counts, over, more = held_experts(
                h.reshape(b * l, c.hidden), valid, layer, c, with_stats=True
            )
            for name, value in dict(more, expert_tokens=counts, overflow=over).items():
                stats[name].append(value[None])
            x = x + routed.reshape(b, l, c.hidden) + _swiglu(
                h, layer["shared_gate"], layer["shared_up"], layer["shared_down"]
            )
        else:
            x = x + _swiglu(h, layer["gate"], layer["up"], layer["down"])
    x = _rms_norm(x, params["ln_f"], c.norm_eps)
    # per-segment mean pooling on the MXU, as transformer.forward pools
    oh = (seg[:, :, None] == jnp.arange(1, max_segments + 1)[None, None, :]).astype(dt)
    pooled = jnp.einsum("blh,bls->bsh", x, oh) / (oh.sum(axis=1)[:, :, None] + 1e-9)
    pooled = pooled.astype(jnp.float32)
    pooled = pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-9)
    return pooled, {name: jnp.concatenate(parts) for name, parts in stats.items()}


def forward(
    params,
    config: MoeMlaConfig,
    ids,
    mask,
    *,
    use_flash: Optional[bool] = None,
    seg=None,
    max_segments: int = 0,
    mesh=None,
    with_stats: bool = False,
):
    """`transformer.forward`'s contract for the causal trunk.  ids, mask:
    [B, L] int32 -> pooled unit vectors [B, hidden]; packed (seg is not
    None): [B, max_segments, hidden], one per packed document, mask
    ignored.  The unpacked form IS the packed one with one segment a row,
    so the two cannot drift.  `with_stats` also returns {"expert_tokens":
    [expert layers, experts_held], "overflow": [expert layers], "tokens":
    (), and [expert layers] each of LAYER_PASS_STATS}: the real tokens each
    held expert saw, the selected held pairs that did not fit the buffer
    (they must be 0), the real tokens, and what `held_experts` met, summed
    over the row groups."""
    import jax.numpy as jnp

    _one_chip_only(mesh, *_ONE_CHIP)
    packed = seg is not None
    if not packed:
        seg, max_segments = (mask > 0).astype(jnp.int32), 1
    fused = packed_attention_fused(config, ids.shape[1], use_flash)
    pooled, stats = pooled_by_row_groups(
        lambda ids, seg: _trunk(params, config, ids, seg, max_segments, fused), ids, seg
    )
    if not packed:
        pooled = pooled[:, 0, :]
    if not with_stats:
        return pooled
    return pooled, dict(stats, tokens=(seg > 0).sum(dtype=jnp.int32))


# the models whose statistics a reading of the span record first brings up
# to date.  Weak: the record outlives a model and may not keep one (and
# its parameters) alive
_LIVE: "weakref.WeakSet[MoeMlaLM]" = weakref.WeakSet()


def _count_finished() -> None:
    """Before a reading of the record: count what the device has finished,
    never waiting (a /status request must not hang behind a dispatch)."""
    for lm in list(_LIVE):
        lm.count_stats(wait=False)


class MoeMlaLM(TransformerLM):
    """`TransformerLM` for this trunk: the same entry points, and the packed
    encode's routing statistics folded into the span record's counters
    (`moe.*`, internals/tracing.py) once the device has produced them."""

    def __init__(self, config: MoeMlaConfig, params=None, seed: int = 0):
        import jax

        super().__init__(config, params=params, seed=seed)
        self._packed_jit = jax.jit(self._packed_program(), static_argnums=(3,))
        self._stats: deque = deque()  # of dispatches not yet counted
        from pathway_tpu.internals import tracing

        _LIVE.add(self)
        tracing.on_read(_count_finished)

    def _packed_program(self):
        """The packed program, under the name the device trace knows it by
        (a trunk that shares the counters brings its own: `moe_hybrid`, `zaya`)."""
        config = self.config

        def _fwd_packed_moe_mla(params, ids, seg, max_segments):
            import jax.numpy as jnp

            return forward(
                params, config, ids.astype(jnp.int32), None,
                seg=seg.astype(jnp.int32), max_segments=max_segments,
                with_stats=True,
            )

        return _fwd_packed_moe_mla

    def encode_packed(self, ids, seg, max_segments: int, *, params=None,
                      mesh=None):
        _one_chip_only(mesh, *_ONE_CHIP)
        pooled, stats = self._packed_jit(
            self.params if params is None else params, ids, seg, int(max_segments)
        )
        self._stats.append(stats)
        self.count_stats(wait=False)
        return pooled

    def count_stats(self, wait: bool = True) -> None:
        """Adds the finished dispatches' statistics to the counters: with
        `wait=False` (the dispatch thread after a launch, a reading of the
        record) only what the device has already produced, in dispatch
        order, so neither ever blocks on the device."""
        from pathway_tpu.internals import tracing

        k = self.config.experts_per_token
        while self._stats:
            try:
                stats = self._stats.popleft()
            except IndexError:  # another thread counted it
                return
            if not wait and not stats["tokens"].is_ready():
                self._stats.appendleft(stats)
                return
            per_expert = np.asarray(stats["expert_tokens"])
            layers = per_expert.shape[0]
            tracing.add("moe.pairs_routed", n=int(stats["tokens"]) * k * layers)
            tracing.add("moe.pairs_held", n=int(per_expert.sum()))
            tracing.add("moe.expert_tokens_max", n=int(per_expert.max(axis=1).sum()))
            tracing.add(
                "moe.expert_tokens_mean", n=int(round(per_expert.mean(axis=1).sum()))
            )
            tracing.add("moe.overflow_pairs", n=int(np.asarray(stats["overflow"]).sum()))
            for name in LAYER_PASS_STATS:
                tracing.add("moe." + name, n=int(np.asarray(stats.get(name, 0)).sum()))
            self._count_more(stats)

    def _count_more(self, stats) -> None:
        """A trunk's own counters from a finished dispatch's statistics
        (`models/zaya.py`: the tokens that chose to skip)."""


LM = MoeMlaLM

"""Shortcut-connected MoE trunk (the LongCat-Flash family's double layer;
LongCat-Flash-Omni's published language-model sizes are the defaults), as
ONE expert-parallel rank runs it: the document store's embedder on the
ingest path.

What one rank of `ep_size` holds of a double layer: both latent-attention
sublayers, both dense FFNs, the norms and the router whole (they are
replicated), and `experts_held` of the `n_routed_experts` routed experts,
from `expert_offset`.  The router keeps its published width (the routed
experts and `zero_experts` zero-compute ones) and its experts per token;
the rank computes its own experts' part for the tokens routed to them and
the zero-compute experts' part for every token, and that partial sum goes
on.  Nothing stands in for the absent ranks or their exchange.

Per double layer, x [T, hidden], every norm RMSNorm, no biases:

  a0 = x + MLA_0(norm_in0(x))
  h0 = norm_post0(a0)
  m  = MoE(h0)                          the shortcut branch: read here ...
  b0 = a0 + FFN_0(h0)
  a1 = b0 + MLA_1(norm_in1(b0))
  x' = a1 + FFN_1(norm_post1(a1)) + m   ... and joined here

MLA_i is `mla._attention` with q scaled by sqrt(hidden / q_lora_rank) and
the normed latent by sqrt(hidden / kv_lora_rank) (`mla_scale_q_lora`,
`mla_scale_kv_lora`), plain RoPE of theta `rope_theta` (no YaRN ladder)
on interleaved pairs, softmax scale (nope + rope)^-0.5, causal within a document whose
positions restart; FFN_i is SwiGLU of `ffn_dim`.  MoE: p = softmax(h0
W_r) in float32 over all `n_routed_experts + zero_experts` outputs; I =
top-k of p + beta (beta selects and never weighs); w_e = factor * p_e,
not renormalised; MoE(h0) = sum_{e in I, e held} w_e FFN_e(h0) + sum_{e
in I, e >= n_routed_experts} w_e h0: a zero-compute expert is the
identity, computed on every rank for its own tokens and never held.

then a final norm, the mean over a document's tokens and L2
normalisation, as `moe_mla` pools.  Prefill form of one stage: no head,
no latent cache, no generation (PERF.md section 7).

Program shape: the attention kernel is `ops/kernels/mla_attention.py`
(off the TPU its dense definition), gated by `packed_attention_fused`;
the routed experts are `experts.held_experts` over `experts.route`'s
softmax choice, handed in (`routing=`): an id at or above the routed
experts' count is nobody's; the zero-compute part is
`experts.zero_expert_part`.  Counters: `moe.*` and `longcat.zero_pairs`,
the selected pairs on zero-compute experts.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import numpy as np

from pathway_tpu.models.experts import (
    count_stats,
    held_experts,
    layer_pass_lists,
    route,
    swiglu,
    zero_expert_part,
)
from pathway_tpu.models.mla import _attention, packed_attention_fused
from pathway_tpu.models.trunk import (  # noqa: F401  (`tokenizer`: model_module's)
    PackedTrunk,
    PackedTrunkLM,
    _dtype,
    _normal,
    one_chip_only,
    packed_positions,
    pooled_by_row_groups,
    rms_norm,
    tokenizer,
)


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    # `vocab_size` is the rows of the embedding this rank holds (a sliced
    # vocabulary is a smaller vocabulary: the tokenizer draws from it)
    vocab_size: int = 16384
    hidden: int = 6144
    layers: int = 4  # double layers: stage 0 of seven holds 4 of 28
    heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 12288
    expert_mlp_dim: int = 2048
    n_routed_experts: int = 512
    zero_experts: int = 256  # the router has this many outputs more: identities
    experts_per_token: int = 12
    routed_scaling_factor: float = 6.0
    experts_held: int = 16
    expert_offset: int = 0
    rope_theta: float = 10_000_000.0
    norm_eps: float = 1e-5
    max_len: int = 512
    dtype: str = "bfloat16"  # what the matmuls compute in
    param_dtype: str = "bfloat16"  # what the parameters are resident in

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_experts

    @property
    def sm_scale(self) -> float:
        return float((self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5)

    @property
    def q_scale(self) -> float:
        """`mla_scale_q_lora`: q times sqrt(hidden / q_lora_rank)."""
        return math.sqrt(self.hidden / self.q_lora_rank)

    @property
    def kv_scale(self) -> float:
        """`mla_scale_kv_lora`: the normed latent times sqrt(hidden / kv_lora_rank)."""
        return math.sqrt(self.hidden / self.kv_lora_rank)

    def active_flops_per_token(self, seq: float) -> float:
        """Forward FLOPs one token of a `seq`-token document needs on this
        rank (`internals/costmodel.py` multiplies by the real tokens): in
        each double layer the two attention sublayers' five matrices and
        causal attention within the document (half the square), the two
        dense FFNs, the router and the expected held pairs (k x held / the
        router's outputs)."""
        h, heads = self.hidden, self.heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        proj = (
            h * self.q_lora_rank + self.q_lora_rank * heads * qk
            + h * (self.kv_lora_rank + self.qk_rope_head_dim)
            + self.kv_lora_rank * heads * (self.qk_nope_head_dim + self.v_head_dim)
            + heads * self.v_head_dim * h
        )
        attn = heads * (qk + self.v_head_dim) * seq / 2.0
        held = self.experts_per_token * self.experts_held / self.router_outputs
        moe = h * self.router_outputs + held * 3 * h * self.expert_mlp_dim
        return 2.0 * self.layers * (2 * (proj + attn) + 2 * 3 * h * self.ffn_dim + moe)


TINY = LongcatConfig(
    vocab_size=512, hidden=128, layers=2, heads=4, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    ffn_dim=192, expert_mlp_dim=64, n_routed_experts=16, zero_experts=8,
    experts_per_token=6, experts_held=4, max_len=128,
    dtype="float32", param_dtype="float32",
)

# the selection bias beta ~ N(0, BIAS_STD^2), float32, beside softmax
# probabilities over 768 outputs whose twelfth and thirteenth largest lie
# about 0.00016 apart (and the twelfth about 0.007): it moves the selection
# of most tokens without deciding it (random weights stand in for trained
# ones, each away from its neutral value)
BIAS_STD = 0.001


def init_params(rng, config: LongcatConfig) -> Dict[str, Any]:
    """Random weights, made leaf by leaf in float32 and kept in
    `param_dtype`.  The recipe (chipbench's reference repeats it from the
    configuration file's `init`, not from here): split the key into 2 +
    layers; key 0 the embedding ~ N(0, 1); double layer i splits key 2+i
    into 6: 0 and 1 the two attention sublayers, each split into 5 (W_qa,
    W_qb, W_kva, W_kvb, W_o); 2 and 3 the two dense FFNs, each split into
    3 (gate, up, down); 4 split into 2: the router [hidden, routed + zero
    experts] and beta [routed + zero] ~ N(0, BIAS_STD^2) in float32; 5:
    expert e (its global index) takes `fold_in(key 5, e)` split into 3, so
    a rank's experts are the uncut model's.  Every matrix ~ N(0,
    1/fan_in), norm scales 1."""
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

    def attention(key):
        k = jax.random.split(key, 5)
        wq_b_nope, wq_b_rope = per_head(
            dense(k[1], (c.q_lora_rank, heads * qk)),
            (c.qk_nope_head_dim, c.qk_rope_head_dim),
        )
        wk_b, wv_b = per_head(
            dense(k[3], (c.kv_lora_rank, heads * kv)),
            (c.qk_nope_head_dim, c.v_head_dim),
        )
        return {
            "ln1": jnp.ones((h,)), "q_ln": jnp.ones((c.q_lora_rank,)),
            "kv_ln": jnp.ones((c.kv_lora_rank,)),
            "wq_a": dense(k[0], (h, c.q_lora_rank)),
            "wq_b_nope": wq_b_nope, "wq_b_rope": wq_b_rope,
            "wkv_a": dense(k[2], (h, c.kv_lora_rank + c.qk_rope_head_dim)),
            "wk_b": wk_b, "wv_b": wv_b,
            "wo": dense(k[4], (heads * c.v_head_dim, h)),
        }

    def ffn(key):
        k = jax.random.split(key, 3)
        return {
            "ln": jnp.ones((h,)), "gate": dense(k[0], (h, c.ffn_dim)),
            "up": dense(k[1], (h, c.ffn_dim)), "down": dense(k[2], (c.ffn_dim, h)),
        }

    keys = jax.random.split(rng, 2 + c.layers)
    params: Dict[str, Any] = {
        "embed": dense(keys[0], (c.vocab_size, h), fan_in=1),
        "ln_f": jnp.ones((h,)),
        "layers": [],
    }
    f = c.expert_mlp_dim
    for i in range(c.layers):
        k = jax.random.split(keys[2 + i], 6)
        kr = jax.random.split(k[4], 2)
        held = [
            jax.random.split(jax.random.fold_in(k[5], c.expert_offset + e), 3)
            for e in range(c.experts_held)
        ]
        params["layers"].append({
            "attn": [attention(k[0]), attention(k[1])],
            "ffn": [ffn(k[2]), ffn(k[3])],
            "router": dense(kr[0], (h, c.router_outputs)),
            "router_bias": BIAS_STD * jax.random.normal(
                kr[1], (c.router_outputs,), dtype=jnp.float32
            ),
            "experts_gate": jnp.stack([dense(ke[0], (h, f)) for ke in held]),
            "experts_up": jnp.stack([dense(ke[1], (h, f)) for ke in held]),
            "experts_down": jnp.stack([dense(ke[2], (f, h)) for ke in held]),
        })
    return params


# what `one_chip_only` says of this trunk: module, what it holds, what is not built
_ONE_CHIP = ("longcat", "one expert-parallel rank", "the expert exchange across ranks")


def param_sharding_rules(config: LongcatConfig, mesh):
    one_chip_only(mesh, *_ONE_CHIP)


def _double_layer(x, layer, config: LongcatConfig, pos, seg, fused: bool, valid):
    """One double layer.  x: [B, L, hidden] -> (x' without the held
    experts' part, what every rank computes alike; the held experts' part
    [B*L, hidden], this rank's share of the shortcut branch; the pass's
    statistics: `held_experts`' and "zero_pairs", the real tokens'
    selected pairs on zero-compute experts)."""
    import jax.numpy as jnp

    c = config
    b, l, _ = x.shape
    attn, ffn = layer["attn"], layer["ffn"]
    scales = (c.q_scale, c.kv_scale)
    a0 = x + _attention(x, attn[0], c, pos, seg, fused, None, *scales)
    h0 = rms_norm(a0, ffn[0]["ln"], c.norm_eps)
    flat = h0.reshape(b * l, c.hidden)
    experts, weights = route(
        flat, layer["router"], c, layer["router_bias"], softmax=True, normalise=False
    )
    routed, counts, over, stats = held_experts(
        flat, valid, layer, c, with_stats=True, routing=(experts, weights)
    )
    zero = zero_expert_part(flat, experts, weights, c.n_routed_experts)
    b0 = a0 + swiglu(h0, ffn[0]["gate"], ffn[0]["up"], ffn[0]["down"])
    a1 = b0 + _attention(b0, attn[1], c, pos, seg, fused, None, *scales)
    h1 = rms_norm(a1, ffn[1]["ln"], c.norm_eps)
    alike = a1 + swiglu(h1, ffn[1]["gate"], ffn[1]["up"], ffn[1]["down"]) + zero.reshape(x.shape)
    stats = dict(
        stats, expert_tokens=counts, overflow=over,
        zero_pairs=jnp.sum(valid[:, None] & (experts >= c.n_routed_experts), dtype=jnp.int32),
    )
    return alike, routed, stats


def _trunk(params, config: LongcatConfig, ids, seg, max_segments: int, fused: bool):
    """ids, seg: [B, L] -> (pooled unit vectors [B, max_segments, hidden]
    f32, the MoE branches' statistics (`experts.layer_pass_lists`) and
    "zero_pairs" [layers])."""
    import jax.numpy as jnp

    c = config
    b, l = ids.shape
    dt = _dtype(c.dtype)
    pos = packed_positions(seg)
    valid = (seg > 0).reshape(-1)
    x = params["embed"][ids].astype(dt)
    stats = dict(layer_pass_lists(c), zero_pairs=[jnp.zeros((0,), jnp.int32)])
    for layer in params["layers"]:
        alike, routed, more = _double_layer(x, layer, c, pos, seg, fused, valid)
        for name, value in more.items():
            stats[name].append(value[None])
        x = alike + routed.reshape(b, l, c.hidden)
    x = rms_norm(x, params["ln_f"], c.norm_eps)
    # per-segment mean pooling on the MXU, as transformer.forward pools
    oh = (seg[:, :, None] == jnp.arange(1, max_segments + 1)[None, None, :]).astype(dt)
    pooled = jnp.einsum("blh,bls->bsh", x, oh) / (oh.sum(axis=1)[:, :, None] + 1e-9)
    pooled = pooled.astype(jnp.float32)
    pooled = pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-9)
    return pooled, {name: jnp.concatenate(parts) for name, parts in stats.items()}


def forward(
    params,
    config: LongcatConfig,
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
    cannot drift.  `with_stats`: as `moe_mla.forward`, and "zero_pairs"
    [layers]."""
    import jax.numpy as jnp

    one_chip_only(mesh, *_ONE_CHIP)
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


def _count_stats(config: LongcatConfig, stats) -> None:
    """`moe.*` (a zero-compute pair is routed and not held) and the
    selected pairs on zero-compute experts."""
    from pathway_tpu.internals import tracing

    count_stats(config, stats)
    tracing.add("longcat.zero_pairs", n=int(np.asarray(stats["zero_pairs"]).sum()))


PACKED = PackedTrunk("_fwd_packed_longcat", lambda config: _ONE_CHIP, count_stats=_count_stats)

LM = PackedTrunkLM

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

Program shape: the attention half is `mla._attention` (the kernel
`ops/kernels/mla_attention.py` or its dense definition), with both LoRA
scales 1.0, over the up-projections kept as separate matrices per part
(`wq_b_nope` / `wq_b_rope`, `wk_b` / `wv_b`: the published matrices'
columns, regrouped once at init).  The expert layer is `experts.held_experts`
over `experts.route` (grouped matmuls over a static buffer of the held
pairs; here a sixteenth of the experts at top-8, whose return sums the
few tokens with several pairs in a compact list), the shared expert
`experts.swiglu`, with the counters `moe.*`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import numpy as np

from pathway_tpu.models.experts import count_stats, held_experts, layer_pass_lists, swiglu
from pathway_tpu.models.mla import (  # noqa: F401  (`_mla_segment_attention`: the kernel's tests read it here)
    _attention,
    _mla_segment_attention,
    packed_attention_fused,
)
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
    yarn_ladder,
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


# what `one_chip_only` says of this trunk: module, what it holds, what is not built
_ONE_CHIP = ("moe_mla", "one expert-parallel rank", "the exchange across ranks")


def param_sharding_rules(config: MoeMlaConfig, mesh):
    one_chip_only(mesh, *_ONE_CHIP)


def yarn_freqs(config: MoeMlaConfig) -> np.ndarray:
    """`yarn_ladder` of this trunk's rope part (interleaved pairs)."""
    c = config
    return yarn_ladder(c.qk_rope_head_dim, c.rope_theta, c.rope_factor,
                       c.rope_original_max_len, c.rope_beta_fast, c.rope_beta_slow)


def _trunk(params, config: MoeMlaConfig, ids, seg, max_segments: int, fused: bool):
    """ids, seg: [B, L] -> (pooled unit vectors [B, max_segments, hidden]
    f32, {"expert_tokens": tokens per held expert [expert layers,
    experts_held], "overflow" and each of LAYER_PASS_STATS [expert layers]})."""
    import jax.numpy as jnp

    c = config
    b, l = ids.shape
    dt = _dtype(c.dtype)
    pos = packed_positions(seg)
    freqs = jnp.asarray(yarn_freqs(c))
    valid = (seg > 0).reshape(-1)
    x = params["embed"][ids].astype(dt)
    stats = layer_pass_lists(c)
    for layer in params["layers"]:
        x = x + _attention(x, layer, c, pos, seg, fused, freqs)
        h = rms_norm(x, layer["ln2"], c.norm_eps)
        if "router" in layer:
            routed, counts, over, more = held_experts(
                h.reshape(b * l, c.hidden), valid, layer, c, with_stats=True
            )
            for name, value in dict(more, expert_tokens=counts, overflow=over).items():
                stats[name].append(value[None])
            x = x + routed.reshape(b, l, c.hidden) + swiglu(
                h, layer["shared_gate"], layer["shared_up"], layer["shared_down"]
            )
        else:
            x = x + swiglu(h, layer["gate"], layer["up"], layer["down"])
    x = rms_norm(x, params["ln_f"], c.norm_eps)
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


PACKED = PackedTrunk("_fwd_packed_moe_mla", lambda config: _ONE_CHIP, count_stats=count_stats)

LM = PackedTrunkLM

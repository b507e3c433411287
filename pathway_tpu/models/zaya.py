"""Compressed-convolutional-attention MoE trunk (the ZAYA1 family's layer;
ZAYA1-8B's published sizes are the defaults), as ONE pipeline stage runs
it: the document store's embedder on the ingest path, every expert and the
whole vocabulary held here, the depth cut.

Three things no other trunk here does.  Operations look one and two slots
BACK ALONG A PACKED ROW outside attention (two causal convolutions, a
shifted value): in a slab the slot before a document's first token is
another document's last, so the seam is cut from `seg` by ONE helper,
`own_past`, which every such operation goes through.  A SECOND STREAM is
carried through the layer loop beside the residual: the router's state of
layer l enters layer l+1's router.  And a sublayer's output is MERGED with
the residual by learned scales where the others add.

Per layer, x [T, hidden] the residual stream, r_prev [T, router_hidden]
the router state of the layer before (zeros into layer 0), every norm
RMSNorm, no biases on the projections; t-1 of a document's first token is
a zero row:

  h = norm(x); [q~ | k~ | v~] = h W_qkv: `heads` query heads, `kv_heads`
  key heads and as many value heads, all `head_dim` wide; the second half
  of the value heads are the token BEFORE's (v_t = [v~_t,0 | v~_(t-1),1])
  query head i reads key/value head g = i // (heads / kv_heads);
  mq_i = (q~_i + k~_g) / 2; mk_g = (mean of q~_i over the group + k~_g) / 2
  c = conv1(conv0([q~ | k~])) along the sequence, both causal: conv0
  depthwise (`conv_taps0` taps a channel, a bias), conv1 grouped, a group a
  head (`conv_taps1` taps of [head_dim, head_dim] a head, a bias)
  q_i = c_i + mq_i, k_g = c_g + mk_g; each head L2-normalised and times
  sqrt(head_dim); k_g times a learned temperature tau_g; then RoPE
  (rotate-half) on the first `rotary_dim` dims of a head, positions
  restarting at every document
  s_ij = q_i . k_j / sqrt(head_dim); token i sees j <= i of its own
  document; out = heads(softmax(s) v) W_o
  x <- alpha_r * x + alpha_o * out (learned vectors a sublayer)
  h = norm(x); r = h W_down + gamma * r_prev (what layer l+1 receives);
  z = norm(r); z = gelu(z W_1); z = gelu(z W_2); logits = z W_3: the
  routed experts and one more choice, "skip"; p = softmax(logits) in
  float32; e = argmax(p + beta): beta selects and never weighs
  out = p_e * FFN_e(h) for a routed e, 0 for "skip"; merged as above

then a final norm, the mean over a document's tokens and L2
normalisation, as `transformer.forward` pools.  Prefill form of one stage:
no head (tied, on the last stage), no cache, no decode state of the
convolutions and the shift, no hand-over of (x, r) to the next stage
(PERF.md section 7).

Program shape.  The expert layer IS `experts.held_experts`, which takes
this router's choice from here (`routing=`): "skip" is an expert index
nobody holds, so it is routed, not held, and computes nothing.  The
attention kernel is `ops/kernels/cca_attention.py` (off the TPU its dense
definition); RoPE's tables and rotation are `hybrid_attention.py`'s.  The
means, convolutions, normalisation, RoPE and the value shift are float32
element-wise work between the projection and that kernel: on the fused
path ONE more kernel, `ops/kernels/cca_latent.py`, which reads the
projection's output once and writes the attention kernel's three operands
(left to XLA they are a dozen fusions a layer, each through HBM); off it
`latent_dense`, the same steps in jax.numpy and the kernel's numerical
definition, where `conv1` is one batched matmul over the heads with the
taps side by side.  One gate, `packed_attention_fused`, decides both
kernels.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import numpy as np

from pathway_tpu.models.experts import count_stats, held_experts, layer_pass_lists
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
from pathway_tpu.ops.kernels import cca_attention as kernel
from pathway_tpu.ops.kernels import cca_latent as latent
from pathway_tpu.ops.kernels.hybrid_attention import ROPE_DIM, rope_tables, rotate


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    hidden: int = 2048
    layers: int = 20  # stage 0 of two: the first half of the published 40
    depth: int = 40  # the published depth over all stages: `init_params` draws the experts' way out by it
    heads: int = 8
    kv_heads: int = 2
    head_dim: int = 128
    rotary_dim: int = 64  # the first dims of a head (`partial_rotary_factor` 0.5)
    conv_taps0: int = 2  # `cca_time0`: the depthwise convolution
    conv_taps1: int = 2  # `cca_time1`: the grouped one, a group a head
    rope_theta: float = 5_000_000.0
    expert_mlp_dim: int = 2048
    n_routed_experts: int = 16  # the router has one output more: "skip"
    experts_per_token: int = 1
    router_hidden: int = 256
    experts_held: int = 16
    expert_offset: int = 0
    norm_eps: float = 1e-5
    max_len: int = 512
    dtype: str = "bfloat16"  # what the matmuls compute in
    param_dtype: str = "bfloat16"  # what the parameters are resident in
    pooling: str = "mean"
    causal: bool = True

    def active_flops_per_token(self, seq: float) -> float:
        """Forward FLOPs one token of a `seq`-token document needs here
        (`internals/costmodel.py` multiplies by the real tokens): the
        projections, the grouped convolution, causal attention within the
        document (half the square), the router's MLP and the one expert a
        token takes, by the share of the experts held."""
        h, hd, rh = self.hidden, self.head_dim, self.router_hidden
        proj = h * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * h
        conv = (self.heads + self.kv_heads) * self.conv_taps1 * hd * hd
        attn = self.heads * 2 * hd * seq / 2.0
        router = h * rh + 2 * rh * rh + rh * (self.n_routed_experts + 1)
        held = self.experts_per_token * self.experts_held / self.n_routed_experts
        return 2.0 * self.layers * (
            proj + conv + attn + router + held * 3 * h * self.expert_mlp_dim
        )


TINY = ZayaConfig(
    vocab_size=512, hidden=64, layers=3, heads=4, expert_mlp_dim=32,
    n_routed_experts=8, router_hidden=32, experts_held=8, max_len=128,
    dtype="float32", param_dtype="float32",
)

# token slots the trunk takes at a time (`trunk.pooled_by_row_groups`):
# at this width a 28k-slot ingest slab's widest arrays (the pairs' buffer
# and the experts' gate and up, [slots + a tile an expert, 2048] in bf16)
# are 0.15 GB each, so the whole slab is one group and an expert sees all
# of a dispatch's tokens that chose it, about 1,300; `trunk.CHUNK_TOKENS`
# was set at a width of 7168 and would halve that
ROW_TOKENS = 32768

# how the learned scalars and vectors that have a neutral value are drawn
# (random weights stand in for trained ones: each away from its neutral
# value, so that a program that left the mechanism out would not agree with
# the reference): the key temperature tau ~ N(1.5, 0.25^2) (neutral 1), the
# merge's alpha_r, alpha_o ~ N(1, 0.1^2) a channel (neutral 1), the
# carry's gamma ~ N(0.5, 0.1^2) (neutral 0), the selection bias beta ~
# N(0, 0.05^2) beside softmax probabilities whose two largest of 17 lie
# about 0.1 apart (neutral 0), the convolutions' biases ~ N(0, 0.1^2)
TAU_MEAN, TAU_STD = 1.5, 0.25
ALPHA_STD = 0.1
GAMMA_MEAN, GAMMA_STD = 0.5, 0.1
BETA_STD = 0.05
CONV_BIAS_STD = 0.1


def init_params(rng, config: ZayaConfig) -> Dict[str, Any]:
    """Random weights, made leaf by leaf in float32 and kept in
    `param_dtype` (the matrices) or float32 (the scalars and vectors);
    chipbench's reference repeats the recipe from the configuration file's
    `init`, not from here.  The key split into 2 + layers; key 0 the
    embedding ~ N(0, 1); layer i splits key 2+i into 15: 0 the fused
    W_qkv [hidden, (heads + 2 kv) x head_dim] ~ N(0, 1/hidden) (columns:
    the query heads, the key heads, the value heads); 1 W_o; 2 conv0's
    weight [taps0, channels] ~ N(0, 1/taps0) and 3 its bias; 4 conv1's
    weight [heads + kv, taps1 x head_dim, head_dim] ~ N(0, 1/(taps1 x
    head_dim)) (rows: tap by tap, the oldest first) and 5 its bias; 6 tau
    [kv]; 7 the merges' scales [4, hidden]: alpha_r, alpha_o of the
    attention sublayer, then of the expert sublayer; 8 W_down [hidden,
    router_hidden]; 9 gamma; 10 W_1, 11 W_2 [router_hidden, router_hidden]
    ~ N(0, 2/router_hidden) (a GELU halves the variance); 12 W_3
    [router_hidden, routed + 1] ~ N(0, 4/router_hidden), so that the
    logits have a spread of order 1; 13 beta [routed + 1]; expert e (its
    global index) takes `fold_in(key 14, e)` split into 3, so a share's
    experts are the uncut model's: gate, up ~ N(0, 1/hidden), down ~
    N(0, 1/(2 x depth x expert_mlp_dim)), the residual-output scale of a
    deep stack, and why only here: random experts are sixteen unrelated
    functions, so a token whose two best choices lie within bf16 rounding
    swaps its whole expert output, not a neighbour's near-equal one as
    under trained weights; at the fan-in scale that swap is a tenth of the
    stream, every later choice of the token follows it, and a 14-token
    query's vector is then the routing's accident (PERF.md section 6, PR
    42).  Norm scales 1; the constants above say how the vectors with a
    neutral value are drawn."""
    import jax
    import jax.numpy as jnp

    c = config
    h, hd, rh, n = c.hidden, c.head_dim, c.router_hidden, c.heads + c.kv_heads

    def dense(key, shape, fan_in=None):
        return _normal(tuple(shape), shape[-2] if fan_in is None else fan_in, c.param_dtype)(key)

    def vector(key, shape, mean: float, std: float):
        return mean + std * jax.random.normal(key, shape, dtype=jnp.float32)

    keys = jax.random.split(rng, 2 + c.layers)
    params: Dict[str, Any] = {
        "embed": dense(keys[0], (c.vocab_size, h), fan_in=1),
        "ln_f": jnp.ones((h,)),
        "layers": [],
    }
    for i in range(c.layers):
        k = jax.random.split(keys[2 + i], 15)
        f = c.expert_mlp_dim
        held = [
            jax.random.split(jax.random.fold_in(k[14], c.expert_offset + e), 3)
            for e in range(c.experts_held)
        ]
        params["layers"].append({
            "ln1": jnp.ones((h,)), "ln2": jnp.ones((h,)), "router_ln": jnp.ones((rh,)),
            "wqkv": dense(k[0], (h, (c.heads + 2 * c.kv_heads) * hd)),
            "wo": dense(k[1], (c.heads * hd, h)),
            "conv0_w": vector(k[2], (c.conv_taps0, n * hd), 0.0, c.conv_taps0 ** -0.5),
            "conv0_b": vector(k[3], (n * hd,), 0.0, CONV_BIAS_STD),
            "conv1_w": dense(k[4], (n, c.conv_taps1 * hd, hd)),
            "conv1_b": vector(k[5], (n * hd,), 0.0, CONV_BIAS_STD),
            "tau": vector(k[6], (c.kv_heads,), TAU_MEAN, TAU_STD),
            "alpha": vector(k[7], (4, h), 1.0, ALPHA_STD),
            "router_down": dense(k[8], (h, rh)),
            "gamma": vector(k[9], (), GAMMA_MEAN, GAMMA_STD),
            "router_w1": dense(k[10], (rh, rh), fan_in=rh // 2),
            "router_w2": dense(k[11], (rh, rh), fan_in=rh // 2),
            "router_w3": dense(k[12], (rh, c.n_routed_experts + 1), fan_in=rh // 4),
            "router_bias": vector(k[13], (c.n_routed_experts + 1,), 0.0, BETA_STD),
            "experts_gate": jnp.stack([dense(ke[0], (h, f)) for ke in held]),
            "experts_up": jnp.stack([dense(ke[1], (h, f)) for ke in held]),
            "experts_down": jnp.stack(
                [dense(ke[2], (f, h), fan_in=2 * c.depth * f) for ke in held]
            ),
        })
    return params


# what `one_chip_only` says of this trunk: module, what it holds, what is not built
_ONE_CHIP = ("zaya", "stage 0 of two", "the hand-over of the two streams between stages")


def param_sharding_rules(config: ZayaConfig, mesh):
    one_chip_only(mesh, *_ONE_CHIP)


def packed_attention_fused(config: ZayaConfig, length: int,
                           use_flash: Optional[bool] = None) -> bool:
    """Whether a slab of `length` slots runs the two fused kernels (the
    latent's, then the attention's) or the dense definition of both: the
    backend and the static shape, as `moe_mla.packed_attention_fused`
    decides (its floor, L > 32, is taken over: below it a row's scores are
    a few kilobytes).  A shape either kernel cannot take runs both dense.
    The launch site asks again to count the batch.  `use_flash` overrides
    (tests run the kernels interpreted on the CPU)."""
    if use_flash is not None:
        return use_flash
    import jax

    c = config
    return (
        jax.default_backend() == "tpu"
        and length > 32
        and c.rotary_dim == ROPE_DIM
        and kernel.supports(length, c.heads, c.kv_heads, c.head_dim)
        and latent.supports(
            length, c.heads, c.kv_heads, c.head_dim, c.rotary_dim, c.conv_taps0, c.conv_taps1
        )
    )


def own_past(x, seg, n: int):
    """THE seam rule: for every slot t of a packed slab, row t-n of its own
    document, or a zero row where the document had not begun (the slot n
    before may be another document's, or none: a row's first slots).  x:
    [B, L, ...], seg: [B, L], 1..S per packed document, 0 = padding.  Both
    convolutions and the value shift look back through here and nowhere
    else, in the dense definition; the fused path's kernel holds the second
    copy, `cca_latent.own_row` (a sublane roll of a slab row's block and
    the same comparison of `seg`), and tests/test_zaya.py pins the two
    against each other.  Rows are whole under a row group, so a group's
    first slot is a row's first slot."""
    import jax.numpy as jnp

    if n == 0:
        return x
    wide = [(0, 0), (n, 0)]
    same = (jnp.pad(seg, wide)[:, :-n] == seg) & (seg > 0)
    past = jnp.pad(x, wide + [(0, 0)] * (x.ndim - 2))[:, :-n]
    return jnp.where(same.reshape(same.shape + (1,) * (x.ndim - 2)), past, jnp.zeros((), x.dtype))


def _unit(x):
    """Each head's vector L2-normalised (float32)."""
    import jax

    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-12)


def group_means(q_raw, k_raw):
    """q_raw [B, L, kv, group, hd], k_raw [B, L, kv, 1, hd] -> (mq, mk): a
    query head's mean with its key head, and a key head's with the mean of
    its group's query heads; what joins the convolutions' output."""
    return (q_raw + k_raw) * 0.5, (q_raw.mean(axis=3, keepdims=True) + k_raw) * 0.5


def _merge(x, out, scales):
    """alpha_r * x + alpha_o * out; scales: [2, hidden] float32."""
    import jax.numpy as jnp

    merged = scales[0] * x.astype(jnp.float32) + scales[1] * out.astype(jnp.float32)
    return merged.astype(x.dtype)


def latent_dense(qkv, layer, config: ZayaConfig, seg, rope):
    """The compressed latent's numerical definition, the path off the TPU
    and the tests' reference of `cca_latent`: qkv [B, L, (heads + 2 kv) x
    head_dim] as the projection leaves it -> (q [B, L, heads x head_dim],
    k, v [B, L, kv x head_dim]) in its dtype, as the attention reads
    them.  rope: `rope_tables` of the slab's positions."""
    import jax.numpy as jnp

    c = config
    b, l, _ = qkv.shape
    dt, f32 = qkv.dtype, jnp.float32
    hd, kv, group = c.head_dim, c.kv_heads, c.heads // c.kv_heads
    n = c.heads + kv
    qk = qkv[..., : n * hd].astype(f32)
    # the value shift: the second half of the value heads are the token before's
    now = (kv - kv // 2) * hd
    v = qkv[..., n * hd :]
    v = jnp.concatenate([v[..., :now], own_past(v[..., now:], seg, 1)], axis=-1)
    # the means of a query head and its key head, before the convolutions
    q_raw = qk[..., : c.heads * hd].reshape(b, l, kv, group, hd)
    k_raw = qk[..., c.heads * hd :].reshape(b, l, kv, 1, hd)
    mq, mk = group_means(q_raw, k_raw)
    # conv0: depthwise; tap j reads the row taps-1-j back
    t0, t1 = c.conv_taps0, c.conv_taps1
    c0 = layer["conv0_b"] + sum(
        layer["conv0_w"][j] * own_past(qk, seg, t0 - 1 - j) for j in range(t0)
    )
    # conv1: a head's channels mix among themselves; the taps side by side
    # make it one batched matmul over the heads
    c0 = c0.astype(dt).reshape(b, l, n, hd)
    taps = jnp.concatenate([own_past(c0, seg, t1 - 1 - j) for j in range(t1)], axis=-1)
    # (the product leaves in the compute dtype, as the projections do: the
    # CPU backend has no batched bf16 x bf16 = f32 matmul)
    c1 = jnp.einsum("blnc,ncd->blnd", taps, layer["conv1_w"].astype(dt)).astype(f32)
    c1 = c1 + layer["conv1_b"].reshape(n, hd)
    q = c1[:, :, : c.heads].reshape(b, l, kv, group, hd) + mq
    k = c1[:, :, c.heads :].reshape(b, l, kv, 1, hd) + mk
    # unit heads; the score's sqrt(head_dim) x 1 / sqrt(head_dim) cancel on
    # q, and k carries its own sqrt(head_dim) and the temperature
    q = _unit(q).reshape(b, l, c.heads, hd)
    k = (_unit(k) * (hd ** 0.5 * layer["tau"])[:, None, None]).reshape(b, l, kv, hd)

    def turned(a):  # RoPE on the first rotary_dim dims of every head
        heads = a.shape[2]
        first = rotate(a[..., : c.rotary_dim].reshape(b, l, heads * c.rotary_dim), *rope)
        first = first.reshape(b, l, heads, c.rotary_dim)
        return jnp.concatenate([first, a[..., c.rotary_dim :]], -1).reshape(b, l, heads * hd)

    return turned(q).astype(dt), turned(k).astype(dt), v


def _attention(x, layer, config: ZayaConfig, seg, rope, fused: bool):
    """The attention sublayer, without the merge.  x: [B, L, hidden];
    rope: `rope_tables` of the slab's positions; fused: both kernels, or
    the dense definition of both."""
    c = config
    dt = x.dtype
    h = rms_norm(x, layer["ln1"], c.norm_eps)
    qkv = h @ layer["wqkv"].astype(dt)
    if fused:
        q, k, v = latent.cca_latent(qkv, seg, rope, layer, heads=c.heads, kv_heads=c.kv_heads)
        ctx = kernel.cca_attention(q, k, v, seg)
    else:
        q, k, v = latent_dense(qkv, layer, c, seg, rope)
        ctx = kernel.cca_attention_dense(q, k, v, seg, kv_heads=c.kv_heads)
    return ctx @ layer["wo"].astype(dt)


def route(h, r_prev, layer, config: ZayaConfig):
    """h: [T, hidden] (normed), r_prev: [T, router_hidden] f32 -> (experts
    [T, 1] int32, weights [T, 1] f32, r [T, router_hidden] f32): the
    router's state with the layer before's carried in, an MLP over its
    norm, a softmax over the routed experts and "skip" (index
    `n_routed_experts`) in float32, and the largest of p + beta, weighed
    by its p."""
    import jax
    import jax.numpy as jnp

    c = config
    dt, f32 = h.dtype, jnp.float32

    def linear(a, w):
        return jnp.dot(a.astype(dt), w.astype(dt), preferred_element_type=f32)

    r = linear(h, layer["router_down"]) + layer["gamma"] * r_prev
    z = rms_norm(r, layer["router_ln"], c.norm_eps)
    z = jax.nn.gelu(linear(z, layer["router_w1"]), approximate=False)
    z = jax.nn.gelu(linear(z, layer["router_w2"]), approximate=False)
    p = jax.nn.softmax(linear(z, layer["router_w3"]), axis=-1)
    experts = jnp.argmax(p + layer["router_bias"], axis=-1).astype(jnp.int32)[:, None]
    return experts, jnp.take_along_axis(p, experts, axis=-1), r


def _trunk(params, config: ZayaConfig, ids, seg, max_segments: int, fused: bool):
    """ids, seg: [B, L] -> (pooled unit vectors [B, max_segments, hidden]
    f32, the expert layers' statistics (`experts.layer_pass_lists`) and "skipped"
    [layers]: the real tokens that chose to skip)."""
    import jax.numpy as jnp

    c = config
    b, l = ids.shape
    dt = _dtype(c.dtype)
    rope = rope_tables(packed_positions(seg), c.rope_theta)
    valid = (seg > 0).reshape(-1)
    x = params["embed"][ids].astype(dt)
    r = jnp.zeros((b * l, c.router_hidden), jnp.float32)  # the second stream
    stats = dict(layer_pass_lists(c), skipped=[jnp.zeros((0,), jnp.int32)])
    for layer in params["layers"]:
        x = _merge(x, _attention(x, layer, c, seg, rope, fused), layer["alpha"][:2])
        h = rms_norm(x, layer["ln2"], c.norm_eps).reshape(b * l, c.hidden)
        experts, weights, r = route(h, r, layer, c)
        routed, counts, over, more = held_experts(
            h, valid, layer, c, with_stats=True, routing=(experts, weights)
        )
        skipped = jnp.sum(valid & (experts[:, 0] == c.n_routed_experts), dtype=jnp.int32)
        for name, value in dict(
            more, expert_tokens=counts, overflow=over, skipped=skipped
        ).items():
            stats[name].append(value[None])
        x = _merge(x, routed.reshape(b, l, c.hidden), layer["alpha"][2:])
    x = rms_norm(x, params["ln_f"], c.norm_eps)
    # per-segment mean pooling on the MXU, as transformer.forward pools; the
    # sum over a document's tokens stays f32
    oh = (seg[:, :, None] == jnp.arange(1, max_segments + 1)[None, None, :]).astype(dt)
    pooled = jnp.einsum("blh,bls->bsh", x, oh, preferred_element_type=jnp.float32)
    pooled = pooled / (oh.sum(axis=1, dtype=jnp.float32)[:, :, None] + 1e-9)
    pooled = pooled / (jnp.linalg.norm(pooled, axis=-1, keepdims=True) + 1e-9)
    return pooled, {name: jnp.concatenate(parts) for name, parts in stats.items()}


def forward(
    params,
    config: ZayaConfig,
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
    whole rows inside the one program.  `with_stats`: as
    `moe_mla.forward`, and "skipped" [layers]."""
    import jax.numpy as jnp

    one_chip_only(mesh, *_ONE_CHIP)
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


def _count_batch(config: ZayaConfig, ids, seg, lengths) -> None:
    """`zaya.*`: what a packed batch is made of."""
    from pathway_tpu.internals import tracing

    c = config
    tracing.add("zaya.tokens", n=int(lengths.sum()))
    # a pair: one query against one key in one query head of one layer
    tracing.add(
        "zaya.scored_pairs",
        n=int((lengths * (lengths + 1) // 2).sum()) * c.heads * c.layers,
    )
    # tokens whose t-1 was cut: a document's first
    tracing.add("zaya.seam_tokens", n=len(lengths))


def _count_stats(config: ZayaConfig, stats) -> None:
    """`moe.*` ("skip" is routed and not held) and the skipped tokens."""
    from pathway_tpu.internals import tracing

    count_stats(config, stats)
    tracing.add("zaya.skipped_tokens", n=int(np.asarray(stats["skipped"]).sum()))


PACKED = PackedTrunk(
    "_fwd_packed_zaya", lambda config: _ONE_CHIP, count_batch=_count_batch,
    count_stats=_count_stats,
)

LM = PackedTrunkLM

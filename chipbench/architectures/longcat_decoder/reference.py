"""The plain reference of a shortcut-connected MoE decoder trunk (the
LongCat-Flash family's double layer) used as an embedder, as ONE
expert-parallel rank holds it: weights from the seed, the double layer as
the LongcatFlash modelling publishes it, causal attention within a text,
mean pooling, L2 normalisation.

Per double layer, x [tokens, hidden], every norm RMSNorm (scale one), no
biases:

  a0 = x + MLA_0(norm(x)); h0 = norm(a0)
  m  = MoE(h0)
  b0 = a0 + FFN_0(h0)
  a1 = b0 + MLA_1(norm(b0))
  x' = a1 + FFN_1(norm(a1)) + m

  MLA(h): c_q = norm(h W_qa) * sqrt(hidden / q_lora_rank)
  (`mla_scale_q_lora`); q = c_q W_qb -> heads of [nope | rope];
  [c_kv | k_rope] = h W_kva; c_kv = norm(c_kv) * sqrt(hidden /
  kv_lora_rank) (`mla_scale_kv_lora`); c_kv W_kvb -> heads of [k_nope | v];
  RoPE on q_rope and on k_rope (one rope key for all heads): pair (x[2i],
  x[2i+1]) turned by position x theta^(-2i/rope), theta = `rope_theta`;
  score = (q_nope.k_nope + q_rope.k_rope) * (nope + rope)^-0.5; token i
  sees j <= i; softmax; out = heads(p v) W_o
  FFN(h) = (silu(h W_g) * (h W_u)) W_d, `ffn_hidden_size` wide
  MoE(h0): p = softmax(h0 W_r) over the `n_routed_experts` +
  `zero_expert_num` outputs; I = the `moe_topk` largest of p + beta (beta
  selects, never weighs); w_e = routed_scaling_factor * p_e (not
  renormalised); MoE(h0) = sum_{e in I, e < n_routed_experts, e held} w_e
  FFN_e(h0) + sum_{e in I, e >= n_routed_experts} w_e h0 (a zero-compute
  expert is the identity; every rank computes those for its own tokens)

Routed experts that this rank does not hold (`expert_offset` .. +
`experts_held` of `n_routed_experts`) add nothing, here as in the program,
and that partial sum goes on to the next double layer.  No head, no cache,
no generation; double layers beyond `layers` lie on the next stages.

Float32 arithmetic with every matmul at `highest` precision; jax.numpy
only; no kernels, no packing: attention and the dense layers one text at
a time, padded to 32 slots or whole 128s so that few shapes compile;
imports nothing of the
program (chipbench/reference.py says what a reference is).  The weights
are made again from the seed by the recipe the configuration's `init`
states, one double layer at a time when it is reached, rounded to the
`param_dtype` the configuration states and converted to float32 where the
layer uses them.  A held expert's FFN runs on the tokens that chose it,
gathered across a group of texts on the host's say.
"""

from __future__ import annotations

import functools
import json
import math

import numpy as np

from chipbench.reference import fake_low, token_ids, weight_seed

TEXTS_AT_ONCE = 64  # texts taken through a double layer before the next is made
EXPERT_ROWS = 256  # a held expert's gathered rows come in multiples of this
_SHAPE_KEYS = (
    "hidden_size", "layers", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "ffn_hidden_size",
    "expert_ffn_hidden_size", "n_routed_experts", "zero_expert_num", "moe_topk",
    "routed_scaling_factor", "mla_scale_q_lora", "mla_scale_kv_lora", "experts_held",
    "expert_offset", "vocab_held", "rope_theta", "rms_norm_eps", "bias_std",
    "param_dtype",
)

# what is written down here, and nothing else under the same keys
_READINGS = {"attention_method": "MLA", "zero_expert_type": "identity",
             "norm_topk_prob": False, "router_bias": False, "attention_bias": False,
             "hidden_act": "silu", "pooling": "mean"}


def _shape_keys(model: dict) -> dict:
    for key, reading in _READINGS.items():
        if model.get(key, reading) != reading:
            raise ValueError(f"{key} {model[key]!r}: the reference is written for {reading!r}")
    if "rope_scaling" in model:
        raise ValueError("rope_scaling: the reference is written for plain RoPE")
    return {k: model[k] for k in _SHAPE_KEYS}


def padded_length(n: int) -> int:
    """A text's slots: 32 for a probe, whole 128s above."""
    return 32 if n <= 32 else -(-n // 128) * 128


def _stored(w, model: dict):
    import jax.numpy as jnp

    return w.astype(jnp.bfloat16 if model["param_dtype"] == "bfloat16" else jnp.float32)


def _keys(model: dict, seed: int):
    import jax

    return jax.random.split(jax.random.PRNGKey(weight_seed(seed)), 2 + model["layers"])


def make_embedding(model: dict, seed: int):
    import jax
    import jax.numpy as jnp

    shape = (model["vocab_held"], model["hidden_size"])
    draw = jax.jit(lambda key: _stored(jax.random.normal(key, shape, dtype=jnp.float32), model))
    return draw(_keys(model, seed)[0])


def make_layer(model: dict, seed: int, i: int, experts=None) -> dict:
    """Double layer i by the recipe of the configuration's `init`, leaf by
    leaf: every matrix ~ N(0, 1/fan_in) in float32, then rounded to
    `param_dtype`; beta stays float32.  In the published layouts: W_qb's
    columns are heads of [nope | rope], W_kvb's heads of [k_nope | v].
    `experts`: global indices of the routed experts to make (default: the
    ones held); they come stacked, "experts_index" beside them."""
    import jax
    import jax.numpy as jnp

    m = model
    d, heads = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    kv = m["qk_nope_head_dim"] + m["v_head_dim"]
    rope, vd = m["qk_rope_head_dim"], m["v_head_dim"]
    ql, kl = m["q_lora_rank"], m["kv_lora_rank"]
    f, fe = m["ffn_hidden_size"], m["expert_ffn_hidden_size"]
    outputs = m["n_routed_experts"] + m["zero_expert_num"]

    def normal(key, shape, fan_in):
        w = jax.random.normal(key, shape, dtype=jnp.float32) / np.sqrt(fan_in)
        return _stored(w, m)

    def attention(key):
        k = jax.random.split(key, 5)
        return {
            "wq_a": normal(k[0], (d, ql), d), "wq_b": normal(k[1], (ql, heads * qk), ql),
            "wkv_a": normal(k[2], (d, kl + rope), d),
            "wkv_b": normal(k[3], (kl, heads * kv), kl),
            "wo": normal(k[4], (heads * vd, d), heads * vd),
        }

    def ffn(key):
        k = jax.random.split(key, 3)
        return {"gate": normal(k[0], (d, f), d), "up": normal(k[1], (d, f), d),
                "down": normal(k[2], (f, d), f)}

    k = jax.random.split(_keys(m, seed)[2 + i], 6)
    kr = jax.random.split(k[4], 2)
    if experts is None:
        experts = range(m["expert_offset"], m["expert_offset"] + m["experts_held"])
    made = [jax.random.split(jax.random.fold_in(k[5], e), 3) for e in experts]
    return {
        "attn": [attention(k[0]), attention(k[1])],
        "ffn": [ffn(k[2]), ffn(k[3])],
        "router": normal(kr[0], (d, outputs), d),
        "beta": m["bias_std"] * jax.random.normal(kr[1], (outputs,), dtype=jnp.float32),
        "experts_index": jnp.asarray(list(experts), jnp.int32),
        "experts_gate": jnp.stack([normal(ke[0], (d, fe), d) for ke in made]),
        "experts_up": jnp.stack([normal(ke[1], (d, fe), d) for ke in made]),
        "experts_down": jnp.stack([normal(ke[2], (fe, d), fe) for ke in made]),
    }


@functools.lru_cache(maxsize=4)
def _functions(model_json: str, lower_precision):
    """The jitted pieces of a double layer for one model (its shape keys as
    JSON, to be a cache's key) and one precision."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_json)
    heads, eps = m["num_attention_heads"], float(m["rms_norm_eps"])
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    d, rank = m["hidden_size"], m["kv_lora_rank"]
    q_scale = math.sqrt(d / m["q_lora_rank"]) if m["mla_scale_q_lora"] else 1.0
    kv_scale = math.sqrt(d / rank) if m["mla_scale_kv_lora"] else 1.0
    scale = (nope + rope) ** -0.5
    n_routed, factor = m["n_routed_experts"], float(m["routed_scaling_factor"])
    freqs = float(m["rope_theta"]) ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    hi = jax.lax.Precision.HIGHEST

    def linear(x, w):
        """w: as `prepare` left it."""
        if lower_precision:
            x = fake_low(x, -1, lower_precision)
        return jnp.matmul(x, w.astype(jnp.float32), precision=hi)

    @jax.jit
    def rounded(w):  # a control's matrix, per output channel, once a layer
        return fake_low(w.astype(jnp.float32), w.ndim - 2, lower_precision)

    def prepare(w):
        """A made double layer as its linear layers use it: for a control
        every matrix rounded, in float32; otherwise as made."""
        if not lower_precision:
            return w
        out = {k: v if k in ("beta", "experts_index") else rounded(v)
               for k, v in w.items() if k not in ("attn", "ffn")}
        for part in ("attn", "ffn"):
            out[part] = [{k: rounded(v) for k, v in sub.items()} for sub in w[part]]
        return out

    def norm(x):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)

    def rotate(x):
        """x [L, ..., rope]: pair (x[2i], x[2i+1]) turned by position * freqs[i]."""
        angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
        shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (rope // 2,)
        cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)

    def swiglu(h, w):
        return linear(jax.nn.silu(linear(h, w["gate"])) * linear(h, w["up"]), w["down"])

    def attention(h, n_real, w):
        """h [L, hidden] (normed), the first n_real slots a text."""
        l = h.shape[0]
        q = linear(norm(linear(h, w["wq_a"])) * q_scale, w["wq_b"]).reshape(l, heads, nope + rope)
        kv_a = linear(h, w["wkv_a"])
        k_rope = rotate(kv_a[:, rank:])  # [L, rope], shared by the heads
        kv = linear(norm(kv_a[:, :rank]) * kv_scale, w["wkv_b"]).reshape(l, heads, nope + vd)
        s = jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope], precision=hi)
        s = s + jnp.einsum("qhd,kd->hqk", rotate(q[..., nope:]), k_rope, precision=hi)
        cols = jnp.arange(l)
        see = (cols[None, :] <= cols[:, None]) & (cols[None, :] < n_real)
        p = jax.nn.softmax(jnp.where(see[None], s * scale, -1e30), axis=-1)
        ctx = jnp.einsum("hqk,khd->qhd", p, kv[..., nope:], precision=hi)
        return linear(ctx.reshape(l, heads * vd), w["wo"])

    def open_layer(x, n_real, w):
        """Everything of a double layer before the held experts: (a0, h0,
        the zero-compute experts' part of the branch, the chosen experts
        [L, k] and their weights)."""
        a0 = x + attention(norm(x), n_real, w["attn"][0])
        h0 = norm(a0)
        p = jax.nn.softmax(linear(h0, w["router"]), axis=-1)
        _, chosen = jax.lax.top_k(p + w["beta"], m["moe_topk"])
        weights = factor * jnp.take_along_axis(p, chosen, axis=-1)
        zero = jnp.sum(jnp.where(chosen >= n_routed, weights, 0.0), axis=-1)
        return a0, h0, zero[:, None] * h0, chosen, weights

    def expert(h_rows, gate, up, down):
        return swiglu(h_rows, {"gate": gate, "up": up, "down": down})

    def close_layer(a0, h0, shortcut, n_real, w):
        """The rest of the double layer, the whole branch joined last."""
        ffn = w["ffn"]
        b0 = a0 + swiglu(h0, ffn[0])
        a1 = b0 + attention(norm(b0), n_real, w["attn"][1])
        return a1 + swiglu(norm(a1), ffn[1]) + shortcut

    def pool(x, n_real):
        keep = (jnp.arange(x.shape[0]) < n_real)[:, None].astype(jnp.float32)
        pooled = (norm(x) * keep).sum(0) / n_real
        return pooled / jnp.linalg.norm(pooled)

    return {"prepare": prepare, "open": jax.jit(open_layer), "expert": jax.jit(expert),
            "close": jax.jit(close_layer), "pool": jax.jit(pool)}


def double_layer(fns, states: list, lengths: list, w: dict) -> list:
    """Double layer `w` over a group of texts (their states [L, hidden],
    padded, and real lengths).  Each held expert runs once over the rows,
    of every text of the group, that chose it, gathered on the host's say
    (their count padded to a multiple of EXPERT_ROWS, so that few shapes
    compile), and its weighted output is added to those rows' branch."""
    import jax.numpy as jnp

    opened = [fns["open"](x, n, w) for x, n in zip(states, lengths)]
    h0 = jnp.concatenate([o[1] for o in opened])
    branch = jnp.concatenate([o[2] for o in opened])
    chosen = np.concatenate([np.asarray(o[3]) for o in opened])
    weights = np.concatenate([np.asarray(o[4]) for o in opened])
    real = np.concatenate([np.arange(len(x)) < n for x, n in zip(states, lengths)])
    for e, index in enumerate(np.asarray(w["experts_index"])):
        hit = (chosen == index) & real[:, None]
        rows = np.flatnonzero(hit.any(1))
        if not len(rows):
            continue
        padded = -(-len(rows) // EXPERT_ROWS) * EXPERT_ROWS
        at = np.zeros(padded, np.int32)
        at[: len(rows)] = rows
        row_weights = np.zeros(padded, np.float32)
        row_weights[: len(rows)] = (weights * hit)[rows].sum(1)
        out = fns["expert"](h0[at], w["experts_gate"][e], w["experts_up"][e], w["experts_down"][e])
        branch = branch.at[at].add(jnp.asarray(row_weights)[:, None] * out)
    ends = np.cumsum([len(x) for x in states])[:-1]
    return [
        fns["close"](o[0], o[1], part, n, w)
        for o, part, n in zip(opened, jnp.split(branch, ends), lengths)
    ]


class Encoder:
    """texts -> [n, hidden] float64 unit vectors: a group of texts through
    double layer i, then layer i+1 is made."""

    def __init__(self, model: dict, seed: int, *, max_len: int):
        self.model = _shape_keys(model)
        self.seed = int(seed)
        self.max_len = int(max_len)
        self._known: dict = {}  # (lower_precision, text) -> its vector

    def embed(self, texts: list, *, lower_precision=None) -> np.ndarray:
        """lower_precision: None, "int8" or "fp8" (the control): every
        linear layer's weights and activations, the router's too.  A
        text's vector is computed once a precision and kept: the
        comparison asks for the same documents again for every control."""
        known = self._known
        fresh = [t for t in dict.fromkeys(texts) if (lower_precision, t) not in known]
        for text, vec in zip(fresh, self._embed(fresh, lower_precision)):
            known[lower_precision, text] = vec
        out = np.zeros((len(texts), self.model["hidden_size"]), dtype=np.float64)
        for i, text in enumerate(texts):
            out[i] = known[lower_precision, text]
        return out

    def _embed(self, texts: list, lower_precision) -> np.ndarray:
        import jax.numpy as jnp

        m = self.model
        if not texts:
            return np.zeros((0, m["hidden_size"]), dtype=np.float64)
        fns = _functions(json.dumps(m, sort_keys=True), lower_precision)
        embedding = make_embedding(m, self.seed)
        out = []
        for lo in range(0, len(texts), TEXTS_AT_ONCE):
            lengths, states = [], []
            for text in texts[lo : lo + TEXTS_AT_ONCE]:
                ids = token_ids(text, m["vocab_held"], self.max_len)
                padded = np.zeros(padded_length(len(ids)), dtype=np.int32)
                padded[: len(ids)] = ids
                lengths.append(len(ids))
                states.append(embedding[jnp.asarray(padded)].astype(jnp.float32))
            for i in range(m["layers"]):
                w = fns["prepare"](make_layer(m, self.seed, i))
                states = double_layer(fns, states, lengths, w)
                del w
            out += [np.asarray(fns["pool"](x, n), dtype=np.float64)
                    for x, n in zip(states, lengths)]
        return np.stack(out)

    def free(self) -> None:
        self._known = {}

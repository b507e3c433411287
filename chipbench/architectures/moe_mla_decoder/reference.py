"""The plain reference of a latent-attention (MLA) + shared-expert MoE
decoder trunk used as an embedder, as ONE expert-parallel rank holds it:
weights from the seed, the layers as the DeepSeek-V3 family publishes
them, causal attention within a text, mean pooling, L2 normalisation.

Per layer, x [tokens, hidden], every norm RMSNorm (scale one), no biases:

  h = norm(x); c_q = norm(h W_qa); q = c_q W_qb -> heads of [nope | rope]
  [c_kv | k_rope] = h W_kva; c_kv = norm(c_kv); c_kv W_kvb -> heads of
  [k_nope | v]; RoPE on q_rope and on k_rope (one rope key for all heads):
  YaRN's frequency ladder, interleaved pairs
  score = (q_nope.k_nope + q_rope.k_rope) * (nope + rope)^-0.5 * m^2,
  m = 0.1 ln(factor) + 1; token i sees j <= i; softmax; x += heads(p v) W_o
  h = norm(x); the first `first_k_dense_replace` layers:
  x += (silu(h W_g) * (h W_u)) W_d; the others: s = sigmoid(h W_r);
  I = the `num_experts_per_tok` largest of all s (`topk_method` "none": no
  group limit, no correction bias); w_e = routed_scaling_factor * s_e /
  sum_{i in I} s_i; x += sum_{e in I, e held} w_e FFN_e(h) + FFN_shared(h)

Experts that this rank does not hold (`expert_offset` .. + `experts_held`
of `n_routed_experts`) add nothing, here as in the program, and that
partial sum goes on to the next layer.  No head, no cache, no generation.

Float32 arithmetic with every matmul at `highest` precision; jax.numpy
only; no kernels, packing or batching tricks; imports nothing of the
program (chipbench/reference.py says what a reference is).  The weights
are made again from the seed by the recipe the configuration's `init`
states and rounded to the `param_dtype` the configuration states.  They
are held in that type, which is exact for values already rounded to it,
and converted to float32 where a layer uses them: 4.0B parameters would
be 16 GB as float32 arrays.  An expert's FFN runs on the tokens that chose
it, gathered on the host's say (their count padded to a multiple of 256
rows, so that few shapes compile).
"""

from __future__ import annotations

import functools
import json
import math

import numpy as np

from chipbench.reference import fake_low, token_ids, weight_seed

EXPERT_ROW_BUCKET = 256
_SHAPE_KEYS = (
    "hidden_size", "layers", "first_k_dense_replace", "num_attention_heads",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "intermediate_size", "moe_intermediate_size",
    "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
    "routed_scaling_factor", "experts_held", "expert_offset", "vocab_held",
    "rope_theta", "rope_scaling", "rms_norm_eps", "param_dtype",
)


# what is written down here, and nothing else under the same keys
_READINGS = {"scoring_func": "sigmoid", "topk_method": "none", "norm_topk_prob": True,
             "hidden_act": "silu", "pooling": "mean"}


def _shape_keys(model: dict) -> dict:
    for key, reading in _READINGS.items():
        if model.get(key, reading) != reading:
            raise ValueError(f"{key} {model[key]!r}: the reference is written for {reading!r}")
    return {k: model[k] for k in _SHAPE_KEYS}


def _stored(w, model: dict):
    import jax.numpy as jnp

    return w.astype(jnp.bfloat16 if model["param_dtype"] == "bfloat16" else jnp.float32)


def make_params(model: dict, seed: int) -> dict:
    """The recipe of the configuration's `init`, leaf by leaf: every matrix
    ~ N(0, 1/fan_in) in float32 (the embedding ~ N(0, 1)), then rounded to
    `param_dtype`.  In the published layouts: W_qb's columns are heads of
    [nope | rope], W_kvb's heads of [k_nope | v]."""
    import jax
    import jax.numpy as jnp

    m = model
    d, heads = m["hidden_size"], m["num_attention_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    kv = m["qk_nope_head_dim"] + m["v_head_dim"]

    def normal(key, shape, fan_in):
        w = jax.random.normal(key, shape, dtype=jnp.float32) / np.sqrt(fan_in)
        return _stored(w, m)

    keys = jax.random.split(jax.random.PRNGKey(weight_seed(seed)), 2 + m["layers"])
    params = {"embed": normal(keys[0], (m["vocab_held"], d), 1), "layers": []}
    for i in range(m["layers"]):
        k = jax.random.split(keys[2 + i], 10)
        layer = {
            "wq_a": normal(k[0], (d, m["q_lora_rank"]), d),
            "wq_b": normal(k[1], (m["q_lora_rank"], heads * qk), m["q_lora_rank"]),
            "wkv_a": normal(k[2], (d, m["kv_lora_rank"] + m["qk_rope_head_dim"]), d),
            "wkv_b": normal(k[3], (m["kv_lora_rank"], heads * kv), m["kv_lora_rank"]),
            "wo": normal(k[4], (heads * m["v_head_dim"], d), heads * m["v_head_dim"]),
        }
        if i < m["first_k_dense_replace"]:
            f = m["intermediate_size"]
            layer.update(
                gate=normal(k[5], (d, f), d), up=normal(k[6], (d, f), d),
                down=normal(k[7], (f, d), f),
            )
        else:
            f = m["moe_intermediate_size"]
            fs = f * m["n_shared_experts"]
            layer.update(
                router=normal(k[5], (d, m["n_routed_experts"]), d),
                shared_gate=normal(k[6], (d, fs), d),
                shared_up=normal(k[7], (d, fs), d),
                shared_down=normal(k[8], (fs, d), fs),
                experts=[],
            )
            for e in range(m["experts_held"]):
                ke = jax.random.split(jax.random.fold_in(k[9], m["expert_offset"] + e), 3)
                layer["experts"].append({
                    "gate": normal(ke[0], (d, f), d), "up": normal(ke[1], (d, f), d),
                    "down": normal(ke[2], (f, d), f),
                })
        params["layers"].append(layer)
    return params


def yarn_freqs(model: dict) -> np.ndarray:
    """[rope_dim / 2] rotation frequencies: the plain ladder theta^(-2i/dim)
    for the pairs that turn more than `beta_fast` times over
    `original_max_position_embeddings`, the ladder over `factor` for those
    that turn less than `beta_slow` times, a linear ramp in between."""
    rs = model["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r}: only yarn is written down here")
    dim, base = model["qk_rope_head_dim"], float(model["rope_theta"])
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_turning(n_rot: float) -> float:
        span = rs["original_max_position_embeddings"] / (n_rot * 2 * math.pi)
        return dim * math.log(span) / (2 * math.log(base))

    low = max(math.floor(pair_turning(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain / rs["factor"]) * ramp + plain * (1.0 - ramp)


def softmax_scale(model: dict) -> float:
    rs = model["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5 * m * m


@functools.lru_cache(maxsize=4)
def _functions(model_json: str, lower_precision):
    """The jitted pieces of a layer for one model (its shape keys as JSON,
    to be a cache's key) and one precision."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_json)
    heads, eps = m["num_attention_heads"], float(m["rms_norm_eps"])
    nope, rope, vd = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    rank = m["kv_lora_rank"]
    freqs = jnp.asarray(yarn_freqs(m), jnp.float32)
    scale = softmax_scale(m)
    hi = jax.lax.Precision.HIGHEST

    def linear(x, w):
        w = w.astype(jnp.float32)
        if lower_precision:
            x, w = fake_low(x, -1, lower_precision), fake_low(w, 0, lower_precision)
        return jnp.matmul(x, w, precision=hi)

    def norm(x):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)

    def rotate(x):
        """x [..., L, rope]: pair (x[2i], x[2i+1]) turned by position * freqs[i]."""
        angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freqs
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)

    def swiglu(h, gate, up, down):
        return linear(jax.nn.silu(linear(h, gate)) * linear(h, up), down)

    def attention(x, mask, w):
        n, l, _ = x.shape
        h = norm(x)
        q = linear(norm(linear(h, w["wq_a"])), w["wq_b"]).reshape(n, l, heads, nope + rope)
        q = q.transpose(0, 2, 1, 3)  # [n, heads, l, nope + rope]
        kv_a = linear(h, w["wkv_a"])
        k_rope = rotate(kv_a[..., rank:])  # [n, l, rope], shared by the heads
        kv = linear(norm(kv_a[..., :rank]), w["wkv_b"]).reshape(n, l, heads, nope + vd)
        kv = kv.transpose(0, 2, 1, 3)
        s = jnp.einsum("nhqd,nhkd->nhqk", q[..., :nope], kv[..., :nope], precision=hi)
        s = s + jnp.einsum("nhqd,nkd->nhqk", rotate(q[..., nope:]), k_rope, precision=hi)
        at = jnp.arange(l)
        see = (at[None, :] <= at[:, None])[None, None] & (mask[:, None, None, :] > 0)
        p = jax.nn.softmax(jnp.where(see, s * scale, -1e30), axis=-1)
        ctx = jnp.einsum("nhqk,nhkd->nhqd", p, kv[..., nope:], precision=hi)
        return x + linear(ctx.transpose(0, 2, 1, 3).reshape(n, l, heads * vd), w["wo"])

    def dense_layer(x, mask, w):
        x = attention(x, mask, w)
        return x + swiglu(norm(x), w["gate"], w["up"], w["down"])

    def expert_layer_open(x, mask, w):
        """Everything of an expert layer but its routed experts: (x with
        attention and the shared expert added, the normed h the experts
        read, the chosen experts [.., k] and their weights)."""
        x = attention(x, mask, w)
        h = norm(x)
        s = jax.nn.sigmoid(linear(h, w["router"]))
        top, chosen = jax.lax.top_k(s, m["num_experts_per_tok"])
        weights = m["routed_scaling_factor"] * top / top.sum(-1, keepdims=True)
        x = x + swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"])
        return x, h, chosen, weights

    def expert_rows(x_flat, h_flat, rows, row_weights, w):
        """x_flat[rows] += row_weights * FFN_e(h_flat[rows]); padding rows
        carry weight 0."""
        out = swiglu(h_flat[rows], w["gate"], w["up"], w["down"])
        return x_flat.at[rows].add(row_weights[:, None] * out)

    def pool(x, mask):
        x = norm(x)
        keep = mask[:, :, None].astype(jnp.float32)
        pooled = (x * keep).sum(1) / keep.sum(1)
        return pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True)

    return {
        "dense_layer": jax.jit(dense_layer),
        "expert_layer_open": jax.jit(expert_layer_open),
        "expert_rows": jax.jit(expert_rows),
        "pool": jax.jit(pool),
    }


class Encoder:
    """texts -> [n, hidden] float64 unit vectors, a block of texts at a time
    through every layer."""

    def __init__(self, model: dict, seed: int, *, max_len: int, block: int = 8):
        self.model = _shape_keys(model)
        self.max_len = int(max_len)
        self.block = int(block)
        self.params = make_params(self.model, seed)
        self._known: dict = {}  # (lower_precision, text) -> its vector

    def _routed(self, fns, x, h, chosen, weights, mask, layer):
        """Adds the held experts' parts: for each, the tokens that chose it."""
        import jax.numpy as jnp

        m = self.model
        n, l, d = x.shape
        chosen = np.asarray(chosen).reshape(n * l, -1) - m["expert_offset"]
        weights = np.asarray(weights).reshape(n * l, -1)
        real = np.asarray(mask).reshape(-1) > 0
        x_flat, h_flat = x.reshape(n * l, d), h.reshape(n * l, d)
        for e, w in enumerate(layer["experts"]):
            hit = (chosen == e) & real[:, None]
            rows = np.flatnonzero(hit.any(1))
            if not len(rows):
                continue
            padded = -(-len(rows) // EXPERT_ROW_BUCKET) * EXPERT_ROW_BUCKET
            idx = np.zeros(padded, np.int32)
            idx[: len(rows)] = rows
            wts = np.zeros(padded, np.float32)
            wts[: len(rows)] = (weights * hit)[rows].sum(1)
            x_flat = fns["expert_rows"](x_flat, h_flat, jnp.asarray(idx), jnp.asarray(wts), w)
        return x_flat.reshape(n, l, d)

    def embed(self, texts: list, *, lower_precision=None) -> np.ndarray:
        """lower_precision: None, "int8" or "fp8" (the control): every
        linear layer's weights and activations, the router's too.  A text's
        vector is computed once a precision and kept: the comparison asks
        for the same documents again for every control (a pass over them
        takes the CPU rehearsal two minutes)."""
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
        fns = _functions(json.dumps(self.model, sort_keys=True), lower_precision)
        encoded = [token_ids(t, m["vocab_held"], self.max_len) for t in texts]
        width = -(-max(len(e) for e in encoded) // 8) * 8  # one compile
        out = np.zeros((len(texts), m["hidden_size"]), dtype=np.float64)
        for lo in range(0, len(encoded), self.block):
            rows = encoded[lo : lo + self.block]
            ids = np.zeros((self.block, width), dtype=np.int32)
            mask = np.zeros((self.block, width), dtype=np.int32)
            for i, e in enumerate(rows):
                ids[i, : len(e)] = e
                mask[i, : len(e)] = 1
            mask[len(rows):, 0] = 1  # filler rows pool over one pad token
            mask_d = jnp.asarray(mask)
            x = self.params["embed"][jnp.asarray(ids)].astype(jnp.float32)
            for layer in self.params["layers"]:
                if "router" in layer:
                    x, h, chosen, weights = fns["expert_layer_open"](x, mask_d, layer_no_experts(layer))
                    x = self._routed(fns, x, h, chosen, weights, mask, layer)
                else:
                    x = fns["dense_layer"](x, mask_d, layer)
            vecs = np.asarray(fns["pool"](x, mask_d), dtype=np.float64)
            out[lo : lo + len(rows)] = vecs[: len(rows)]
        return out

    def free(self) -> None:
        self.params = None
        self._known = {}


def layer_no_experts(layer: dict) -> dict:
    return {k: v for k, v in layer.items() if k != "experts"}

"""The plain reference of a hybrid-attention MoE decoder trunk used as an
embedder, as ONE expert-parallel rank holds it: weights from the seed, the
layers as the MiMo-V2 family publishes them, causal attention within a
text, mean pooling, L2 normalisation.

Per layer, x [tokens, hidden], every norm RMSNorm (scale one), no biases.
A layer is global or window by `hybrid_layer_pattern` (0 / 1), dense or
expert by `moe_layer_freq` (0 / 1):

  h = norm(x); h W_qkv -> `num_attention_heads` query heads of `head_dim`,
  kv key heads of `head_dim`, kv value heads of `v_head_dim`, kv =
  `num_key_value_heads` (global) or `swa_num_key_value_heads` (window);
  query head i reads key/value head i // (heads / kv).  RoPE, rotate-half,
  on the first `rotary_dim` dims of a head: pair (x[i], x[i + rotary/2])
  turned by position x theta^(-2i/rotary), theta = `rope_theta` (global)
  or `swa_rope_theta` (window)
  s_ij = q_i . k_j / sqrt(head_dim); token i sees j <= i (global) or
  i - `sliding_window` < j <= i (window); a kind with a sink
  (`add_swa_attention_sink_bias` / `add_full_attention_sink_bias`):
  p_ij = exp(s_ij - m) / (sum_j exp(s_ij - m) + exp(b_h - m));
  x += heads(p (`attention_value_scale` v)) W_o
  h = norm(x); a dense layer: x += (silu(h W_g) * (h W_u)) W_d; an expert
  layer: s = sigmoid(h W_r); I = the `num_experts_per_tok` largest of
  s + beta (`topk_method` "noaux_tc": the bias selects and never weighs;
  `n_group` 1: no group limit); w_e = s_e / sum_{i in I} s_i
  (`norm_topk_prob`, `routed_scaling_factor` null = 1);
  x += sum_{e in I, e held} w_e FFN_e(h)

Experts that this rank does not hold (`expert_offset` .. + `experts_held`
of `n_routed_experts`) add nothing, here as in the program, and that
partial sum goes on to the next layer.  No head, no cache, no generation,
no multi-token-prediction layers.

Float32 arithmetic with every matmul at `highest` precision; jax.numpy
only; no kernels, no packing, one text at a time; imports nothing of the
program (chipbench/reference.py says what a reference is).  The weights
are made again from the seed by the recipe the configuration's `init`
states, one layer at a time, rounded to the `param_dtype` the
configuration states and converted to float32 where the layer uses them.
Attention runs a block of query rows at a time, so that a document of
16,384 tokens fits: a global layer scores the block against every key of
the text, a window layer against the `sliding_window` + block keys that
end with the block.  An expert's FFN runs on the tokens that chose it,
gathered on the host's say (their count padded to a multiple of 512 rows,
so that few shapes compile).
"""

from __future__ import annotations

import functools
import json

import numpy as np

from chipbench.reference import fake_low, token_ids, weight_seed

EXPERT_ROW_BUCKET = 512
QUERY_BLOCK = 256  # query rows scored at a time (a global layer: [64, 256, 16384] f32 scores, 1 GB)
TEXTS_AT_ONCE = 8  # texts taken through a layer before the next is made
_SHAPE_KEYS = (
    "hidden_size", "layers", "hybrid_layer_pattern", "moe_layer_freq",
    "num_attention_heads", "num_key_value_heads", "swa_num_key_value_heads",
    "head_dim", "rotary_dim", "v_head_dim", "sliding_window", "rope_theta",
    "swa_rope_theta", "add_swa_attention_sink_bias", "add_full_attention_sink_bias",
    "attention_value_scale", "intermediate_size", "moe_intermediate_size",
    "n_routed_experts", "num_experts_per_tok", "experts_held", "expert_offset",
    "vocab_held", "layernorm_epsilon", "max_len", "param_dtype", "bias_std",
    "sink_mean",
)

# what is written down here, and nothing else under the same keys
_READINGS = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
             "topk_group": 1, "n_shared_experts": None, "routed_scaling_factor": None,
             "norm_topk_prob": True, "hidden_act": "silu", "pooling": "mean",
             "attention_projection_layout": "fused_qkv"}


def _shape_keys(model: dict) -> dict:
    for key, reading in _READINGS.items():
        if model.get(key, reading) != reading:
            raise ValueError(f"{key} {model[key]!r}: the reference is written for {reading!r}")
    return {k: model[k] for k in _SHAPE_KEYS}


def padded_length(n: int) -> int:
    """A text's slots: whole query blocks (whole 32s up to two), so that
    few shapes compile."""
    step = 32 if n <= 2 * QUERY_BLOCK else QUERY_BLOCK
    return -(-n // step) * step


def is_window(model: dict, layer: int) -> bool:
    return bool(model["hybrid_layer_pattern"][layer])


def is_dense(model: dict, layer: int) -> bool:
    return not model["moe_layer_freq"][layer]


def kv_heads(model: dict, window: bool) -> int:
    return model["swa_num_key_value_heads" if window else "num_key_value_heads"]


def has_sink(model: dict, window: bool) -> bool:
    return model["add_swa_attention_sink_bias" if window else "add_full_attention_sink_bias"]


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
    return _stored(jax.random.normal(_keys(model, seed)[0], shape, dtype=jnp.float32), model)


def make_layer(model: dict, seed: int, i: int, experts=None) -> dict:
    """Layer i by the recipe of the configuration's `init`, leaf by leaf:
    every matrix ~ N(0, 1/fan_in) in float32, then rounded to
    `param_dtype`; the sinks and the selection bias stay float32.  In the
    published layout: the fused matrix's columns are the query heads, then
    the key heads, then the value heads.  `experts`: global indices of the
    routed experts to make (default: the ones held)."""
    import jax
    import jax.numpy as jnp

    m = model
    d, heads = m["hidden_size"], m["num_attention_heads"]
    window = is_window(m, i)
    kv = kv_heads(m, window)

    def normal(key, shape, fan_in):
        w = jax.random.normal(key, shape, dtype=jnp.float32) / np.sqrt(fan_in)
        return _stored(w, m)

    k = jax.random.split(_keys(m, seed)[2 + i], 6)
    fused = heads * m["head_dim"] + kv * (m["head_dim"] + m["v_head_dim"])
    layer = {
        "wqkv": normal(k[0], (d, fused), d),
        "wo": normal(k[1], (heads * m["v_head_dim"], d), heads * m["v_head_dim"]),
    }
    if has_sink(m, window):
        layer["sink"] = m["sink_mean"] + jax.random.normal(k[2], (heads,), dtype=jnp.float32)
    if is_dense(m, i):
        f = m["intermediate_size"]
        layer.update(
            gate=normal(k[3], (d, f), d), up=normal(k[4], (d, f), d),
            down=normal(k[5], (f, d), f),
        )
    else:
        f = m["moe_intermediate_size"]
        layer["router"] = normal(k[3], (d, m["n_routed_experts"]), d)
        layer["router_bias"] = m["bias_std"] * jax.random.normal(
            k[4], (m["n_routed_experts"],), dtype=jnp.float32
        )
        if experts is None:
            experts = range(m["expert_offset"], m["expert_offset"] + m["experts_held"])
        layer["experts"] = {}
        for e in experts:
            ke = jax.random.split(jax.random.fold_in(k[5], e), 3)
            layer["experts"][e] = {
                "gate": normal(ke[0], (d, f), d), "up": normal(ke[1], (d, f), d),
                "down": normal(ke[2], (f, d), f),
            }
    return layer


@functools.lru_cache(maxsize=4)
def _functions(model_json: str, lower_precision):
    """The jitted pieces of a layer for one model (its shape keys as JSON,
    to be a cache's key) and one precision."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_json)
    heads, hd, vd = m["num_attention_heads"], m["head_dim"], m["v_head_dim"]
    rot, span = m["rotary_dim"], m["sliding_window"]
    eps = float(m["layernorm_epsilon"])
    hi = jax.lax.Precision.HIGHEST

    def linear(x, w):
        """w: as `prepare` left it."""
        if lower_precision:
            x = fake_low(x, -1, lower_precision)
        return jnp.matmul(x, w, precision=hi)

    def matrix(w):
        w = w.astype(jnp.float32)
        return fake_low(w, 0, lower_precision) if lower_precision else w

    as_used = jax.jit(matrix)

    def prepare(layer):
        """A made layer as its linear layers use it: every matrix float32,
        for a control rounded per output channel; the sinks and the
        selection bias as they are."""
        return jax.tree_util.tree_map(lambda w: as_used(w) if w.ndim == 2 else w, layer)

    def norm(x):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)

    def rotate(x, theta: float):
        """x [L, n, head_dim]: the first `rot` dims of every head, pair
        (x[i], x[i + rot/2]) turned by position * theta^(-2i/rot)."""
        half = rot // 2
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs  # [L, half]
        cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
        a, b = x[..., :half], x[..., half:rot]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, x[..., rot:]], axis=-1)

    def attention(x, n, w, window: bool):
        """x [L, hidden], the first n slots a text: the attention half of a
        layer of one kind with its residual, a block of query rows at a
        time."""
        l = x.shape[0]
        kv = kv_heads(m, window)
        group = heads // kv
        theta = float(m["swa_rope_theta" if window else "rope_theta"])
        qkv = linear(norm(x), w["wqkv"])
        q = rotate(qkv[:, : heads * hd].reshape(l, heads, hd), theta) * hd ** -0.5
        k = rotate(qkv[:, heads * hd : (heads + kv) * hd].reshape(l, kv, hd), theta)
        v = qkv[:, (heads + kv) * hd :].reshape(l, kv, vd) * m["attention_value_scale"]
        block = min(QUERY_BLOCK, l)
        # the keys a block of queries may see: all of them, or the window
        # before its first row and the block itself (the text padded in
        # front by a window, so that every block's keys are one slice)
        front = span if window else 0
        width = front + block if window else l
        k_all = jnp.pad(k, ((front, 0), (0, 0), (0, 0)))
        v_all = jnp.pad(v, ((front, 0), (0, 0), (0, 0)))
        sink = w["sink"].reshape(kv, group, 1, 1) if "sink" in w else None

        def one_block(r0):
            rows = r0 + jnp.arange(block)
            first = r0 if window else 0  # slot of the slice's first key, less `front`
            ks = jax.lax.dynamic_slice_in_dim(k_all, first, width, axis=0)
            vs = jax.lax.dynamic_slice_in_dim(v_all, first, width, axis=0)
            cols = first - front + jnp.arange(width)
            qb = jax.lax.dynamic_slice_in_dim(q, r0, block, axis=0)
            qb = qb.reshape(block, kv, group, hd)
            s = jnp.einsum("qngd,knd->ngqk", qb, ks, precision=hi)
            see = (cols[None, :] <= rows[:, None]) & (cols[None, :] >= 0) & (cols[None, :] < n)
            if window:
                see = see & (rows[:, None] - cols[None, :] < span)
            s = jnp.where(see[None, None], s, -1e30)
            top = s.max(-1, keepdims=True)
            if sink is not None:
                top = jnp.maximum(top, sink)
            p = jnp.exp(s - top)
            denom = p.sum(-1, keepdims=True)
            if sink is not None:
                denom = denom + jnp.exp(sink - top)
            out = jnp.einsum("ngqk,knd->qngd", p / denom, vs, precision=hi)
            return out.reshape(block, heads * vd)

        out = jax.lax.map(one_block, jnp.arange(0, l, block)).reshape(l, heads * vd)
        return x + linear(out, w["wo"])

    def swiglu(h, gate, up, down):
        return linear(jax.nn.silu(linear(h, gate)) * linear(h, up), down)

    def dense_mlp(x, w):
        return x + swiglu(norm(x), w["gate"], w["up"], w["down"])

    def routed(x, w):
        """(the normed h the experts read, the chosen experts [L, k], their
        weights): the bias enters the selection alone."""
        h = norm(x)
        s = jax.nn.sigmoid(linear(h, w["router"]))
        _, chosen = jax.lax.top_k(s + w["router_bias"], m["num_experts_per_tok"])
        top = jnp.take_along_axis(s, chosen, axis=-1)
        return h, chosen, top / top.sum(-1, keepdims=True)

    def expert_rows(x, h, rows, row_weights, w):
        """x[rows] += row_weights * FFN_e(h[rows]); padding rows carry
        weight 0."""
        out = swiglu(h[rows], w["gate"], w["up"], w["down"])
        return x.at[rows].add(row_weights[:, None] * out)

    def pool(x, n):
        keep = (jnp.arange(x.shape[0]) < n)[:, None].astype(jnp.float32)
        pooled = (norm(x) * keep).sum(0) / n
        return pooled / jnp.linalg.norm(pooled)

    return {
        "prepare": prepare,
        "attention": jax.jit(attention, static_argnames=("window",)),
        "dense_mlp": jax.jit(dense_mlp),
        "routed": jax.jit(routed),
        "expert_rows": jax.jit(expert_rows),
        "pool": jax.jit(pool),
    }


def add_experts(fns, x, n, layer: dict):
    """x plus the made experts' parts of an expert layer: for each, the
    real tokens that chose it."""
    import jax.numpy as jnp

    h, chosen, weights = fns["routed"](x, layer)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    real = np.arange(x.shape[0]) < n
    for e, w in layer["experts"].items():
        hit = (chosen == e) & real[:, None]
        rows = np.flatnonzero(hit.any(1))
        if not len(rows):
            continue
        padded = -(-len(rows) // EXPERT_ROW_BUCKET) * EXPERT_ROW_BUCKET
        idx = np.zeros(padded, np.int32)
        idx[: len(rows)] = rows
        wts = np.zeros(padded, np.float32)
        wts[: len(rows)] = (weights * hit)[rows].sum(1)
        x = fns["expert_rows"](x, h, jnp.asarray(idx), jnp.asarray(wts), w)
    return x


def run_layer(fns, model: dict, i: int, x, n, layer: dict):
    x = fns["attention"](x, n, layer, window=is_window(model, i))
    if is_dense(model, i):
        return fns["dense_mlp"](x, layer)
    return add_experts(fns, x, n, layer)


class Encoder:
    """texts -> [n, hidden] float64 unit vectors: a group of texts through
    layer i, then layer i+1 is made."""

    def __init__(self, model: dict, seed: int, *, max_len: int):
        self.model = _shape_keys(model)
        self.seed = int(seed)
        self.max_len = min(int(max_len), int(self.model["max_len"]))
        self._known: dict = {}  # (lower_precision, text) -> its vector

    def embed(self, texts: list, *, lower_precision=None) -> np.ndarray:
        """lower_precision: None, "int8" or "fp8" (the control): every
        linear layer's weights and activations, the router's too.  A text's
        vector is computed once a precision and kept: the comparison asks
        for the same documents again for every control."""
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
                states = [run_layer(fns, m, i, x, n, w) for x, n in zip(states, lengths)]
            out += [np.asarray(fns["pool"](x, n), dtype=np.float64)
                    for x, n in zip(states, lengths)]
        return np.stack(out)

    def free(self) -> None:
        self._known = {}

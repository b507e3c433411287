"""The plain reference of a Laguna-family decoder trunk used as an
embedder, as stage 0 of a pipeline holds it: weights from the seed, the
layers as the configuration's keys give them, causal attention within a
text, mean pooling, L2 normalisation.

Per layer, x [tokens, hidden], every norm RMSNorm (scale one), no biases
(`attention_bias` false).  A layer is full or sliding by `layer_types`,
dense or sparse by `mlp_layer_types`; H is the layer's entry of
`num_attention_heads_per_layer`, kv = `num_key_value_heads`:

  h = norm(x); h W_qkv -> H query heads, kv key heads and kv value heads,
  all of `head_dim`; query head i reads key/value head i // (H / kv).
  RoPE, rotate-half, on the first `partial_rotary_factor` x head_dim dims
  of a head: pair (x[i], x[i + rot/2]) turned by position x freq_i, by
  the kind's `rope_parameters`: "default" freq_i = theta^(-2i/rot);
  "yarn" HF's YaRN ladder over the rot dims (`factor`,
  `original_max_position_embeddings`, `beta_fast`, `beta_slow`) with cos
  and sin times `attention_factor`
  s_ij = q_i . k_j / sqrt(head_dim); token i sees j <= i (full) or
  i - `sliding_window` < j <= i (sliding); p = softmax(s)
  o_i = sum_j p_ij v_j a head; head i's o times sigmoid(h W_g)_i
  (`gating`, head-wise: W_g [hidden, H]); x += concat_heads(o) W_o
  h = norm(x); a dense layer: x += (silu(h W_g) * (h W_u)) W_d; a sparse
  layer: s = sigmoid(h W_r) over `num_experts`; I = the
  `num_experts_per_tok` largest; w_e = `moe_routed_scaling_factor` x s_e
  / sum_{i in I} s_i; x += sum_{e in I, e held} w_e FFN_e(h) + FFN_s(h),
  FFN_s the shared expert (`shared_expert_intermediate_size`), unweighted

Experts that this chip does not hold (`expert_offset` .. + `experts_held`
of `num_experts`) add nothing, here as in the program.  No head, no cache,
no generation; stage 0's output is normed and pooled.

Float32 arithmetic with every matmul at `highest` precision; jax.numpy
only; no kernels, no packing, one text at a time; imports nothing of the
program (chipbench/reference.py says what a reference is).  The weights
are made again from the seed by the recipe the configuration's `init`
states, one layer at a time (the whole stage in float32 is 14.7 GB),
rounded to the `param_dtype` the configuration states and converted to
float32 where the layer uses them.  Attention runs a block of query rows
at a time, so that an 8,002-token text of 64 heads fits: a full layer
scores the block against every key of the text, a sliding layer against
the `sliding_window` + block keys that end with the block.  The held
experts run in one batched call a text and layer, each on the rows of the
tokens that chose it, gathered on the host's say (the busiest expert's
count rounded up to a power of four, so that few shapes compile).
"""

from __future__ import annotations

import functools
import json
import math

import numpy as np

from chipbench.reference import fake_low, token_ids, weight_seed

QUERY_BLOCK = 256  # query rows scored at a time (a full layer: [48, 256, 8192] f32, 0.4 GB)
LONG_SLOTS = 2048  # the least padded length of a text longer than a query block
TEXTS_AT_ONCE = 16  # texts taken through a layer before the next is made
_SHAPE_KEYS = (
    "hidden_size", "layers", "layer_types", "mlp_layer_types",
    "num_attention_heads_per_layer", "num_key_value_heads", "head_dim",
    "sliding_window", "rope_parameters", "intermediate_size", "moe_intermediate_size",
    "shared_expert_intermediate_size", "num_experts", "num_experts_per_tok",
    "moe_routed_scaling_factor", "experts_held", "expert_offset", "vocab_held",
    "rms_norm_eps", "max_len", "param_dtype", "num_hidden_layers",
)

# what is written down here, and nothing else under the same keys
_READINGS = {"hidden_act": "silu", "pooling": "mean", "attention_bias": False,
             "gating": True, "gate_form": "head-wise", "scoring_func": "sigmoid",
             "moe_apply_router_weight_on_input": False}


def _shape_keys(model: dict) -> dict:
    for key, reading in _READINGS.items():
        if model.get(key, reading) != reading:
            raise ValueError(f"{key} {model[key]!r}: the reference is written for {reading!r}")
    return {k: model[k] for k in _SHAPE_KEYS}


def padded_length(n: int, max_len: int) -> int:
    """A text's slots: whole 32s up to a query block; above it 2,048 or
    8,192 (LONG_SLOTS times a power of four, no more than `max_len` in
    whole query blocks), so that few large shapes compile.  Every program
    of a layer compiles once a length and a precision, and a program with
    `highest` matmuls of a few thousand rows takes the TPU's compiler 4-12
    s (a compile for a described v5e, PR 44): at a power of two a length
    (five above a block for the cell's 14 to 8,002 tokens) the compiles
    of a run with its controls outlast the harness's 1,150 s.  The padding
    costs the cell's texts about twice their slots."""
    if n <= QUERY_BLOCK:
        return -(-n // 32) * 32
    size = LONG_SLOTS
    while size < n:
        size *= 4
    return min(size, -(-max_len // QUERY_BLOCK) * QUERY_BLOCK)


def is_window(model: dict, layer: int) -> bool:
    return model["layer_types"][layer] == "sliding_attention"


def is_dense(model: dict, layer: int) -> bool:
    return model["mlp_layer_types"][layer] == "dense"


def rope_of(model: dict, window: bool) -> dict:
    return model["rope_parameters"]["sliding_attention" if window else "full_attention"]


def rotary_dims(model: dict, window: bool) -> int:
    return int(model["head_dim"] * rope_of(model, window)["partial_rotary_factor"])


def ladder(model: dict, window: bool) -> tuple:
    """(frequencies [rot / 2] float32, the factor on cos and sin) of a
    kind: HF's `default` and `yarn` rope types, the latter with its ramp
    over the rotated dims (`_compute_yarn_parameters`, truncated bounds)."""
    r = rope_of(model, window)
    dim, base = rotary_dims(model, window), float(r["rope_theta"])
    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if r["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    if r["rope_type"] != "yarn":
        raise ValueError(f"rope_type {r['rope_type']!r}: the reference knows default and yarn")

    def correction_dim(rotations: float) -> float:
        original = r["original_max_position_embeddings"]
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(r["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(r["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extrapolate = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    freqs = plain / r["factor"] * (1.0 - extrapolate) + plain * extrapolate
    return freqs.astype(np.float32), float(r["attention_factor"])


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


@functools.lru_cache(maxsize=8)
def _expert_maker(d: int, f: int, down_fan_in: int, param_dtype: str):
    """One program that makes experts from their indices: expert e takes
    fold_in(key, e) split into 3, gate and up ~ N(0, 1/d), down ~ N(0,
    1/down_fan_in), in float32, then rounded to `param_dtype`."""
    import jax
    import jax.numpy as jnp

    store = jnp.bfloat16 if param_dtype == "bfloat16" else jnp.float32

    def one(key, e):
        ke = jax.random.split(jax.random.fold_in(key, e), 3)

        def normal(k, shape, fan_in):
            return (jax.random.normal(k, shape, dtype=jnp.float32) / np.sqrt(fan_in)).astype(store)

        return {"gate": normal(ke[0], (d, f), d), "up": normal(ke[1], (d, f), d),
                "down": normal(ke[2], (f, d), down_fan_in)}

    return jax.jit(jax.vmap(one, in_axes=(None, 0)))


def make_layer(model: dict, seed: int, i: int, experts=None) -> dict:
    """Layer i by the recipe of the configuration's `init`, leaf by leaf:
    every matrix ~ N(0, 1/fan_in) in float32, the routed experts' down
    projection at the residual-output scale of `num_hidden_layers`, then
    rounded to `param_dtype`.  In the published layout: the fused matrix's
    columns are the query heads, then the key heads, then the value heads.
    `experts`: global indices of the routed experts to make (default: the
    ones held), stacked in that order."""
    import jax
    import jax.numpy as jnp

    m = model
    d, hd = m["hidden_size"], m["head_dim"]
    heads, kv = m["num_attention_heads_per_layer"][i], m["num_key_value_heads"]

    def normal(key, shape, fan_in):
        w = jax.random.normal(key, shape, dtype=jnp.float32) / np.sqrt(fan_in)
        return _stored(w, m)

    k = jax.random.split(_keys(m, seed)[2 + i], 10)
    layer = {
        "wqkv": normal(k[0], (d, (heads + 2 * kv) * hd), d),
        "wo": normal(k[1], (heads * hd, d), heads * hd),
        "wg": normal(k[6], (d, heads), d),
    }
    if is_dense(m, i):
        f = m["intermediate_size"]
        layer.update(
            gate=normal(k[3], (d, f), d), up=normal(k[4], (d, f), d),
            down=normal(k[5], (f, d), f),
        )
        return layer
    f, fs = m["moe_intermediate_size"], m["shared_expert_intermediate_size"]
    if experts is None:
        experts = range(m["expert_offset"], m["expert_offset"] + m["experts_held"])
    make = _expert_maker(d, f, 2 * m["num_hidden_layers"] * f, m["param_dtype"])
    layer.update(
        router=normal(k[3], (d, m["num_experts"]), d),
        experts=make(k[5], jnp.asarray(list(experts), jnp.int32)),
        expert_ids=np.asarray(list(experts), np.int32),
        shared={"gate": normal(k[7], (d, fs), d), "up": normal(k[8], (d, fs), d),
                "down": normal(k[9], (fs, d), fs)},
    )
    return layer


@functools.lru_cache(maxsize=4)
def _functions(model_json: str, lower_precision):
    """The jitted pieces of a layer for one model (its shape keys as JSON,
    to be a cache's key) and one precision."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_json)
    hd, kv, span = m["head_dim"], m["num_key_value_heads"], m["sliding_window"]
    eps = float(m["rms_norm_eps"])
    hi = jax.lax.Precision.HIGHEST

    def linear(x, w):
        """w: as `prepare` left it."""
        if lower_precision:
            x = fake_low(x, -1, lower_precision)
        return jnp.matmul(x, w, precision=hi)

    def matrix(w):
        w = w.astype(jnp.float32)
        return fake_low(w, -2, lower_precision) if lower_precision else w

    as_used = jax.jit(matrix)

    def prepare(layer):
        """A made layer as its linear layers use it: every matrix float32
        (the stacked experts' too), for a control rounded per output
        channel."""
        ids = layer.pop("expert_ids", None)
        out = jax.tree_util.tree_map(as_used, layer)
        if ids is not None:
            out["expert_ids"] = ids
        return out

    def norm(x):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)

    def rotate(x, freqs, factor: float, rot: int):
        """x [L, n, head_dim]: the first `rot` dims of every head, pair
        (x[i], x[i + rot/2]) turned by position * freqs_i; cos and sin
        times `factor`."""
        half = rot // 2
        angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(freqs)
        cos = (jnp.cos(angle) * factor)[:, None, :]
        sin = (jnp.sin(angle) * factor)[:, None, :]
        a, b = x[..., :half], x[..., half:rot]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, x[..., rot:]], axis=-1)

    def attention(x, n, w, window: bool, heads: int):
        """x [L, hidden], the first n slots a text: the attention half of a
        layer of one kind with its residual, a block of query rows at a
        time."""
        l = x.shape[0]
        group = heads // kv
        freqs, factor = ladder(m, window)
        rot = rotary_dims(m, window)
        h = norm(x)
        qkv = linear(h, w["wqkv"])
        q = rotate(qkv[:, : heads * hd].reshape(l, heads, hd), freqs, factor, rot) * hd ** -0.5
        k = rotate(qkv[:, heads * hd : (heads + kv) * hd].reshape(l, kv, hd), freqs, factor, rot)
        v = qkv[:, (heads + kv) * hd :].reshape(l, kv, hd)
        gate = jax.nn.sigmoid(linear(h, w["wg"]))  # [L, heads]
        block = min(QUERY_BLOCK, l)
        # the keys a block of queries may see: all of them, or the window
        # before its first row and the block itself (the text padded in
        # front by a window, so that every block's keys are one slice)
        front = span if window else 0
        width = front + block if window else l
        k_all = jnp.pad(k, ((front, 0), (0, 0), (0, 0)))
        v_all = jnp.pad(v, ((front, 0), (0, 0), (0, 0)))

        def one_block(r0):
            rows = r0 + jnp.arange(block)
            first = r0 if window else 0  # slot of the slice's first key, less `front`
            ks = jax.lax.dynamic_slice_in_dim(k_all, first, width, axis=0)
            vs = jax.lax.dynamic_slice_in_dim(v_all, first, width, axis=0)
            cols = first - front + jnp.arange(width)
            qb = jax.lax.dynamic_slice_in_dim(q, r0, block, axis=0).reshape(block, kv, group, hd)
            s = jnp.einsum("qngd,knd->ngqk", qb, ks, precision=hi)
            see = (cols[None, :] <= rows[:, None]) & (cols[None, :] >= 0) & (cols[None, :] < n)
            if window:
                see = see & (rows[:, None] - cols[None, :] < span)
            s = jnp.where(see[None, None], s, -1e30)
            p = jnp.exp(s - s.max(-1, keepdims=True))
            out = jnp.einsum("ngqk,knd->qngd", p / p.sum(-1, keepdims=True), vs, precision=hi)
            return out.reshape(block, heads, hd)

        out = jax.lax.map(one_block, jnp.arange(0, l, block)).reshape(l, heads, hd)
        out = (out * gate[:, :, None]).reshape(l, heads * hd)
        return x + linear(out, w["wo"])

    def swiglu(h, gate, up, down):
        return linear(jax.nn.silu(linear(h, gate)) * linear(h, up), down)

    def dense_mlp(x, w):
        return x + swiglu(norm(x), w["gate"], w["up"], w["down"])

    def routed(x, w):
        """(the normed h the experts read, x plus the shared expert, the
        chosen experts [L, k], their weights)."""
        h = norm(x)
        s = jax.nn.sigmoid(linear(h, w["router"]))
        top, chosen = jax.lax.top_k(s, m["num_experts_per_tok"])
        weights = m["moe_routed_scaling_factor"] * top / top.sum(-1, keepdims=True)
        shared = w["shared"]
        return h, x + swiglu(h, shared["gate"], shared["up"], shared["down"]), chosen, weights

    def expert_out(h_rows, row_weights, experts):
        """row_weights[e] * FFN_e(h_rows[e]) for every held expert e at
        once, [experts, rows, hidden]: a program a row count, whatever the
        text's length; padding rows carry weight 0."""
        out = jax.vmap(swiglu)(h_rows, experts["gate"], experts["up"], experts["down"])
        return row_weights[:, :, None] * out

    expert_out = jax.jit(expert_out)
    gather = jax.jit(lambda h, rows: h[rows])
    scatter_add = jax.jit(lambda x, rows, out: x.at[rows.reshape(-1)].add(
        out.reshape(-1, x.shape[1])))

    def expert_rows(x, h, rows, row_weights, experts):
        """x[rows[e]] += row_weights[e] * FFN_e(h[rows[e]]): three programs,
        so that the experts' own compiles once a row count."""
        return scatter_add(x, rows, expert_out(gather(h, rows), row_weights, experts))

    def pool(x, n):
        keep = (jnp.arange(x.shape[0]) < n)[:, None].astype(jnp.float32)
        pooled = (norm(x) * keep).sum(0) / n
        return pooled / jnp.linalg.norm(pooled)

    return {
        "prepare": prepare,
        "attention": jax.jit(attention, static_argnames=("window", "heads")),
        "dense_mlp": jax.jit(dense_mlp),
        "routed": jax.jit(routed),
        "expert_rows": expert_rows,
        "pool": jax.jit(pool),
    }


def add_experts(fns, x, n, layer: dict):
    """x plus the shared expert and the made experts' parts of a sparse
    layer: for each, the real tokens that chose it."""
    import jax.numpy as jnp

    h, x, chosen, weights = fns["routed"](x, layer)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    real = np.arange(x.shape[0]) < n
    ids = layer["expert_ids"]
    hits = [(chosen == e) & real[:, None] for e in ids]
    rows = [np.flatnonzero(hit.any(1)) for hit in hits]
    busiest = max(len(r) for r in rows)
    if not busiest:
        return x
    cap = 16
    while cap < busiest:  # powers of four: few programs, at most 4x the rows
        cap *= 4
    idx = np.zeros((len(ids), cap), np.int32)
    wts = np.zeros((len(ids), cap), np.float32)
    for j, (hit, r) in enumerate(zip(hits, rows)):
        idx[j, : len(r)] = r
        wts[j, : len(r)] = (weights * hit)[r].sum(1)
    return fns["expert_rows"](x, h, jnp.asarray(idx), jnp.asarray(wts), layer["experts"])


def run_layer(fns, model: dict, i: int, x, n, layer: dict):
    x = fns["attention"](
        x, n, layer, window=is_window(model, i),
        heads=model["num_attention_heads_per_layer"][i],
    )
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
        linear layer's weights and activations, the router's and the
        gate's too.  A text's vector is computed once a precision and
        kept: the comparison asks for the same documents again for every
        control."""
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
                padded = np.zeros(padded_length(len(ids), self.max_len), dtype=np.int32)
                padded[: len(ids)] = ids
                lengths.append(len(ids))
                states.append(embedding[jnp.asarray(padded)].astype(jnp.float32))
            for i in range(m["layers"]):
                w = fns["prepare"](make_layer(m, self.seed, i))
                states = [run_layer(fns, m, i, x, n, w) for x, n in zip(states, lengths)]
                del w
            out += [np.asarray(fns["pool"](x, n), dtype=np.float64)
                    for x, n in zip(states, lengths)]
        return np.stack(out)

    def free(self) -> None:
        self._known = {}

"""The plain reference of a compressed-convolutional-attention MoE decoder
trunk (the ZAYA1 family's layer) used as an embedder, as ONE pipeline stage
holds it: weights from the seed, the layer as the configuration's file
writes it down (`assumed` lists each form no published key pins), causal
attention within a text, mean pooling, L2 normalisation.

Per layer, x [tokens, hidden] the residual stream and r_prev [tokens,
`router_hidden_size`] the router state of the layer before (zeros into
layer 0); every norm RMSNorm (scale one), no biases on the projections;
row t-1 of a text's first token is a zero row:

  h = norm(x); h W_qkv -> `num_attention_heads` query heads q~, kv =
  `num_key_value_heads` key heads k~ and kv value heads, all `head_dim`
  wide; the second half of the value heads are the token before's (kv 2:
  head 0's value is h_t W_v0, head 1's h_(t-1) W_v1)
  query head i reads key/value head g = i // (heads / kv);
  mq_i = (q~_i + k~_g) / 2; mk_g = (mean of q~_i over group g + k~_g) / 2
  c = conv1(conv0([q~ | k~])), both causal along the sequence: conv0
  depthwise, `cca_time0` taps a channel and a bias; conv1 grouped, a group
  a head, `cca_time1` taps of [head_dim, head_dim] a head and a bias
  q_i = c_i + mq_i, k_g = c_g + mk_g; each head's vector L2-normalised
  and times sqrt(head_dim); k_g times a learned temperature tau_g; RoPE
  after that, rotate-half on the first `rotary_dim` dims of a head: pair
  (x[i], x[i + rotary/2]) turned by position x theta^(-2i/rotary), theta =
  `rope_parameters.hybrid.rope_theta`
  s_ij = q_i . k_j / sqrt(head_dim); token i sees j <= i; p = softmax(s);
  out = heads(p v) W_o; x <- alpha_r * x + alpha_o * out
  h = norm(x); r = h W_down + gamma * r_prev (layer l+1 receives r);
  z = norm(r); z = gelu(z W_1); z = gelu(z W_2) (the exact GELU); logits
  = z W_3 [`num_experts` + 1]: the experts and "skip"; p = softmax(logits);
  e = argmax(p + beta) (the bias selects and never weighs);
  out = p_e (silu(h G_e) * (h U_e)) D_e for an expert e that is held, 0
  for "skip" and for an expert held elsewhere; x <- alpha_r' * x +
  alpha_o' * out

Experts that this chip does not hold (`expert_offset` .. + `experts_held`
of `num_experts`; the cell holds all) add nothing, here as in the program.
No head, no cache, no generation; layers beyond `layers` lie on the next
stage.

Float32 arithmetic with every matmul at `highest` precision; jax.numpy
only; no kernels, no packing (so no seam between documents can exist
here), one text at a time; imports nothing of the program
(chipbench/reference.py says what a reference is).  The weights are made
again from the seed by the recipe the configuration's `init` states, one
layer at a time when the layer is reached, rounded to the `param_dtype`
the configuration states and converted to float32 where the layer uses
them.  Attention runs a block of query rows at a time.  Every made expert
runs on every token of the text and the token's choice picks one: the
plainest form, sixteen times the FLOPs of the chosen ones alone.
"""

from __future__ import annotations

import functools
import json

import numpy as np

from chipbench.reference import fake_low, token_ids, weight_seed

QUERY_BLOCK = 128  # query rows scored at a time: a padded text is 32 slots or whole 128s
TEXTS_AT_ONCE = 64  # texts taken through a layer before the next is made
_SHAPE_KEYS = (
    "hidden_size", "layers", "num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
    "rotary_dim", "cca_time0", "cca_time1", "rope_parameters", "rms_norm_eps",
    "moe_intermediate_size", "num_experts", "router_hidden_size", "experts_held",
    "expert_offset", "vocab_held", "max_len", "param_dtype", "tau_mean", "tau_std",
    "alpha_std", "gamma_mean", "gamma_std", "beta_std", "conv_bias_std",
)

# what is written down here, and nothing else under the same keys
_READINGS = {"hidden_act": "silu", "pooling": "mean", "sliding_window": None,
             "num_experts_per_tok": 1, "attention_bias": False}


def _shape_keys(model: dict) -> dict:
    for key, reading in _READINGS.items():
        if model.get(key, reading) != reading:
            raise ValueError(f"{key} {model[key]!r}: the reference is written for {reading!r}")
    kinds = set(model.get("layer_types", ["hybrid"])[: model["layers"]])
    if kinds != {"hybrid"}:
        raise ValueError(f"layer_types {sorted(kinds)}: the reference is written for 'hybrid'")
    return {k: model[k] for k in _SHAPE_KEYS}


def padded_length(n: int) -> int:
    """A text's slots: 32 for a probe, whole 128s above, so that few shapes
    compile."""
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
    """Layer i by the recipe of the configuration's `init`, leaf by leaf:
    every matrix ~ N(0, gain/fan_in) in float32, then rounded to
    `param_dtype`; the vectors and scalars stay float32.  `experts`: global
    indices of the routed experts to make (default: the ones held); they
    come stacked, "experts_index" beside them."""
    import jax
    import jax.numpy as jnp

    m = model
    d, hd, rh = m["hidden_size"], m["head_dim"], m["router_hidden_size"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    n, f, routed = heads + kv, m["moe_intermediate_size"], m["num_experts"]

    def normal(key, shape, fan_in):
        w = jax.random.normal(key, shape, dtype=jnp.float32) / np.sqrt(fan_in)
        return _stored(w, m)

    def vector(key, shape, mean, std):
        return mean + std * jax.random.normal(key, shape, dtype=jnp.float32)

    k = jax.random.split(_keys(m, seed)[2 + i], 15)
    t0, t1 = m["cca_time0"], m["cca_time1"]
    if experts is None:
        experts = range(m["expert_offset"], m["expert_offset"] + m["experts_held"])
    made = [jax.random.split(jax.random.fold_in(k[14], e), 3) for e in experts]
    return {
        "wqkv": normal(k[0], (d, (heads + 2 * kv) * hd), d),
        "wo": normal(k[1], (heads * hd, d), heads * hd),
        "conv0_w": vector(k[2], (t0, n * hd), 0.0, t0 ** -0.5),
        "conv0_b": vector(k[3], (n * hd,), 0.0, m["conv_bias_std"]),
        "conv1_w": normal(k[4], (n, t1 * hd, hd), t1 * hd),
        "conv1_b": vector(k[5], (n * hd,), 0.0, m["conv_bias_std"]),
        "tau": vector(k[6], (kv,), m["tau_mean"], m["tau_std"]),
        "alpha": vector(k[7], (4, d), 1.0, m["alpha_std"]),
        "router_down": normal(k[8], (d, rh), d),
        "gamma": vector(k[9], (), m["gamma_mean"], m["gamma_std"]),
        "router_w1": normal(k[10], (rh, rh), rh // 2),
        "router_w2": normal(k[11], (rh, rh), rh // 2),
        "router_w3": normal(k[12], (rh, routed + 1), rh // 4),
        "router_bias": vector(k[13], (routed + 1,), 0.0, m["beta_std"]),
        "experts_index": jnp.asarray(list(experts), jnp.int32),
        "experts_gate": jnp.stack([normal(ke[0], (d, f), d) for ke in made]),
        "experts_up": jnp.stack([normal(ke[1], (d, f), d) for ke in made]),
        "experts_down": jnp.stack([normal(ke[2], (f, d), 2 * m["num_hidden_layers"] * f) for ke in made]),
    }


# the leaves that are linear layers' matrices: rounded per output channel
# for a control (the contraction is axis 0 of a matrix, axis 1 of a stack)
_MATRICES = ("wqkv", "wo", "conv1_w", "router_down", "router_w1", "router_w2",
             "router_w3", "experts_gate", "experts_up", "experts_down")


@functools.lru_cache(maxsize=4)
def _functions(model_json: str, lower_precision):
    """The jitted pieces of a layer for one model (its shape keys as JSON,
    to be a cache's key) and one precision."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_json)
    heads, kv, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    group, n = heads // kv, heads + kv
    rot = m["rotary_dim"]
    t0, t1 = m["cca_time0"], m["cca_time1"]
    theta = float(m["rope_parameters"]["hybrid"]["rope_theta"])
    eps = float(m["rms_norm_eps"])
    hi = jax.lax.Precision.HIGHEST

    def low(x):
        return fake_low(x, -1, lower_precision) if lower_precision else x

    def linear(x, w):
        """w: as `prepare` left it."""
        return jnp.matmul(low(x), w, precision=hi)

    def matrix(w):
        w = w.astype(jnp.float32)
        return fake_low(w, w.ndim - 2, lower_precision) if lower_precision else w

    as_used = jax.jit(matrix)

    def prepare(layer):
        """A made layer as its linear layers use it: every matrix float32,
        for a control rounded per output channel; the rest as it is."""
        return {k: as_used(w) if k in _MATRICES else w for k, w in layer.items()}

    def norm(x):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)

    def unit(x):
        return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-12)

    def back(a, j: int):
        """Row t-j of a [L, ...] for every t, zero rows before the text."""
        if j == 0:
            return a
        return jnp.pad(a, [(j, 0)] + [(0, 0)] * (a.ndim - 1))[:-j]

    def rotate(x):
        """x [L, heads, head_dim]: the first `rot` dims of every head, pair
        (x[i], x[i + rot/2]) turned by position * theta^(-2i/rot)."""
        half = rot // 2
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs  # [L, half]
        cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
        a, b = x[..., :half], x[..., half:rot]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, x[..., rot:]], axis=-1)

    def merge(x, out, scales):
        return scales[0] * x + scales[1] * out

    def attention(x, n_real, w):
        """x [L, hidden], the first n_real slots a text: the attention
        sublayer merged into x, a block of query rows at a time."""
        l = x.shape[0]
        qkv = linear(norm(x), w["wqkv"])
        qk = qkv[:, : n * hd]
        v = qkv[:, n * hd :].reshape(l, kv, hd)
        now = kv - kv // 2  # value heads of the token itself; the rest are t-1's
        v = jnp.concatenate([v[:, :now], back(v[:, now:], 1)], axis=1)
        q_raw = qk[:, : heads * hd].reshape(l, kv, group, hd)
        k_raw = qk[:, heads * hd :].reshape(l, kv, 1, hd)
        mq = (q_raw + k_raw) / 2
        mk = (q_raw.mean(axis=2, keepdims=True) + k_raw) / 2
        c0 = w["conv0_b"] + sum(w["conv0_w"][j] * back(qk, t0 - 1 - j) for j in range(t0))
        c0 = c0.reshape(l, n, hd)
        c1 = w["conv1_b"].reshape(n, hd) + sum(
            jnp.einsum(
                "lnc,ncd->lnd", low(back(c0, t1 - 1 - j)),
                w["conv1_w"][:, j * hd : (j + 1) * hd], precision=hi,
            )
            for j in range(t1)
        )
        q = c1[:, :heads].reshape(l, kv, group, hd) + mq
        k = c1[:, heads:].reshape(l, kv, 1, hd) + mk
        q = rotate((unit(q) * hd ** 0.5).reshape(l, heads, hd)).reshape(l, kv, group, hd)
        k = unit(k) * hd ** 0.5 * w["tau"][:, None, None]
        k = rotate(k.reshape(l, kv, hd))
        block = min(QUERY_BLOCK, l)
        cols = jnp.arange(l)

        def one_block(r0):
            rows = r0 + jnp.arange(block)
            qb = jax.lax.dynamic_slice_in_dim(q, r0, block, axis=0)
            s = jnp.einsum("qngd,knd->ngqk", qb, k, precision=hi) / hd ** 0.5
            see = (cols[None, :] <= rows[:, None]) & (cols[None, :] < n_real)
            s = jnp.where(see[None, None], s, -1e30)
            p = jnp.exp(s - s.max(-1, keepdims=True))
            p = p / p.sum(-1, keepdims=True)
            return jnp.einsum("ngqk,knd->qngd", p, v, precision=hi).reshape(block, heads * hd)

        out = jax.lax.map(one_block, jnp.arange(0, l, block)).reshape(l, heads * hd)
        return merge(x, linear(out, w["wo"]), w["alpha"][:2])

    def experts(x, r_prev, w):
        """The expert sublayer merged into x, and the router's state."""
        h = norm(x)
        r = linear(h, w["router_down"]) + w["gamma"] * r_prev
        z = jax.nn.gelu(linear(norm(r), w["router_w1"]), approximate=False)
        z = jax.nn.gelu(linear(z, w["router_w2"]), approximate=False)
        p = jax.nn.softmax(linear(z, w["router_w3"]), axis=-1)
        chosen = jnp.argmax(p + w["router_bias"], axis=-1)  # the last is "skip"
        weight = jnp.take_along_axis(p, chosen[:, None], axis=-1)[:, 0]

        def one_expert(out, e):
            index, gate, up, down = e
            y = linear(jax.nn.silu(linear(h, gate)) * linear(h, up), down)
            return out + jnp.where(chosen == index, weight, 0.0)[:, None] * y, None

        out, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(x),
            (w["experts_index"], w["experts_gate"], w["experts_up"], w["experts_down"]),
        )
        return merge(x, out, w["alpha"][2:]), r

    def layer(x, r, n_real, w):
        return experts(attention(x, n_real, w), r, w)

    def pool(x, n_real):
        keep = (jnp.arange(x.shape[0]) < n_real)[:, None].astype(jnp.float32)
        pooled = (norm(x) * keep).sum(0) / n_real
        return pooled / jnp.linalg.norm(pooled)

    return {"prepare": prepare, "layer": jax.jit(layer), "pool": jax.jit(pool)}


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
        grouped convolution's too.  A text's vector is computed once a
        precision and kept: the comparison asks for the same documents
        again for every control."""
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
                x = embedding[jnp.asarray(padded)].astype(jnp.float32)
                states.append((x, jnp.zeros((len(padded), m["router_hidden_size"]), jnp.float32)))
            for i in range(m["layers"]):
                w = fns["prepare"](make_layer(m, self.seed, i))
                states = [fns["layer"](x, r, n, w) for (x, r), n in zip(states, lengths)]
            out += [np.asarray(fns["pool"](x, n), dtype=np.float64)
                    for (x, _), n in zip(states, lengths)]
        return np.stack(out)

    def free(self) -> None:
        self._known = {}

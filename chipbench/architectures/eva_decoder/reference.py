"""The plain reference of a byte-level decoder trunk with chunked linear
attention (EVA) used as an embedder, as ONE pipeline stage holds it: the
embedding and the stage's layers, weights from the seed, causal within a
text, mean pooling, L2 normalisation.

A text is its UTF-8 bytes: ids = [<bos> = 1] + [64 + byte ...], cut to
`max_len`.  Per layer, x [tokens, hidden], norm(x) = x / rms(x) * (1 + w)
with w = 0, no biases, residual sums in float32:

  h = norm(x); q, k, v = h W_q, h W_k, h W_v -> heads of `head_dim`; RoPE
  (theta `rope_theta`, rotate-half pairs (x[i], x[i + d/2]), the whole
  head) on q and k, positions from 0
  windows of `window_size` positions, chunks of `chunk_size`; for head h
  and every chunk j that lies in a window before the text's last:
    a_t = softmax over t in j of (k_t . phi_h) * s,  s = head_dim^-1/2
    kbar_j = sum_t a_t k_t + mu_h;   vbar_j = sum_t a_t v_t
  query i in window w: ONE softmax at scale s over the keys t <= i of
  window w and the kbar_j of every chunk of windows 0 .. w-1; the output
  is the weighted sum of those v_t and vbar_j;  x += heads(out) W_o
  h = norm(x); x += (silu(h W_g) * (h W_u)) W_d

After the last layer held: norm, mean over the text's tokens, unit length.
No output head, no decode state, no generation.

Float32 arithmetic with every matmul at `highest` precision; jax.numpy
only; no kernels, no packing: one text at a time, padded with masked slots
to the next of a few lengths so that few shapes compile, its attention a
dense [heads, L, L + chunks] score matrix built a group of heads at a time.
Imports nothing of the program (chipbench/reference.py says what a
reference is).  The weights are made again from the seed by the recipe the
configuration's `init` states, one layer at a time: a group of texts passes
layer i before layer i+1 is made, so that 16 x 0.81 GB of float32 never sit
on the chip together.  They are rounded to the `param_dtype` the
configuration states, which is what the program keeps.
"""

from __future__ import annotations

import functools
import json

import numpy as np

from chipbench.reference import fake_low, weight_seed

BOS_ID, BYTE_OFFSET = 1, 64
HEAD_GROUP = 4  # heads whose scores are held at once: 0.9 GB at 7,168 slots
# texts whose hidden states are held at once (117 MB each at 7,168 slots); each
# such group makes the layers again, a few seconds of 16 x 0.2 B random numbers
TEXTS_AT_ONCE = 16
_SHAPE_KEYS = (
    "hidden_size", "num_attention_heads", "intermediate_size", "window_size",
    "chunk_size", "rope_theta", "rms_norm_eps", "vocab_size", "layers",
    "param_dtype", "max_len",
)
# what is written down here, and nothing else under the same keys
_READINGS = {"attention_class": "eva", "hidden_act": "silu", "pooling": "mean",
             "norm_add_unit_offset": True, "fp32_skip_add": True}


def _shape_keys(model: dict) -> dict:
    for key, reading in _READINGS.items():
        if model.get(key, reading) != reading:
            raise ValueError(f"{key} {model[key]!r}: the reference is written for {reading!r}")
    return {k: model[k] for k in _SHAPE_KEYS}


def byte_ids(text: str, max_len: int) -> list:
    """[<bos>] + one id a byte of the text's UTF-8, cut to max_len."""
    return ([BOS_ID] + [BYTE_OFFSET + b for b in text.encode("utf-8")])[:max_len]


def padded_length(n: int) -> int:
    """The length a text of n tokens is padded to: whole 128s up to 1,024,
    whole 1,024s above."""
    step = 128 if n <= 1024 else 1024
    return -(-n // step) * step


def _stored(w, model: dict):
    import jax.numpy as jnp

    if model["param_dtype"] == "bfloat16":
        w = w.astype(jnp.bfloat16)
    return w.astype(jnp.float32)


def _keys(model: dict, seed: int):
    import jax

    return jax.random.split(jax.random.PRNGKey(weight_seed(seed)), 2 + model["layers"])


def make_embedding(model: dict, seed: int):
    """[vocab_size, hidden] ~ N(0, 1): key 0 of the seed's split."""
    import jax
    import jax.numpy as jnp

    shape = (model["vocab_size"], model["hidden_size"])
    return _stored(jax.random.normal(_keys(model, seed)[0], shape, dtype=jnp.float32), model)


def make_layer(model: dict, seed: int, i: int) -> dict:
    """Layer i by the recipe of the configuration's `init`: key 2+i split
    into 9; W_q, W_k, W_v, W_o, gate, up, down ~ N(0, 1/fan_in), phi and mu
    [heads, head_dim] ~ N(0, 1/head_dim); each drawn in float32 and rounded
    to `param_dtype`."""
    import jax
    import jax.numpy as jnp

    d, f, heads = model["hidden_size"], model["intermediate_size"], model["num_attention_heads"]
    hd = d // heads
    k = jax.random.split(_keys(model, seed)[2 + i], 9)

    def normal(key, shape, fan_in):
        return _stored(jax.random.normal(key, shape, dtype=jnp.float32) / np.sqrt(fan_in), model)

    return {
        "wq": normal(k[0], (d, d), d), "wk": normal(k[1], (d, d), d),
        "wv": normal(k[2], (d, d), d), "wo": normal(k[3], (d, d), d),
        "gate": normal(k[4], (d, f), d), "up": normal(k[5], (d, f), d),
        "down": normal(k[6], (f, d), f),
        "phi": normal(k[7], (heads, hd), hd), "mu": normal(k[8], (heads, hd), hd),
    }


@functools.lru_cache(maxsize=4)
def _functions(model_json: str, lower_precision):
    """The jitted pieces for one model (its shape keys as JSON, to be a
    cache's key) and one precision."""
    import jax
    import jax.numpy as jnp

    m = json.loads(model_json)
    heads, eps = m["num_attention_heads"], float(m["rms_norm_eps"])
    hd = m["hidden_size"] // heads
    window, chunk = m["window_size"], m["chunk_size"]
    scale = hd ** -0.5
    freqs = jnp.asarray(
        float(m["rope_theta"]) ** (-np.arange(0, hd, 2, dtype=np.float64) / hd), jnp.float32
    )
    hi = jax.lax.Precision.HIGHEST
    group = HEAD_GROUP if heads % HEAD_GROUP == 0 else 1

    def linear(x, w):
        if lower_precision:
            x, w = fake_low(x, -1, lower_precision), fake_low(w, 0, lower_precision)
        return jnp.matmul(x, w, precision=hi)

    def norm(x):
        return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)

    def rotate(x):
        """x [heads, L, hd]: pair (x[i], x[i + hd/2]) turned by position * freqs[i]."""
        angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freqs
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        a, b = x[..., : hd // 2], x[..., hd // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)

    def attention(x, n, w):
        """x [L, hidden], the first n slots a text: the attention half of a
        layer with its residual."""
        l = x.shape[0]
        h = norm(x)
        split = lambda a: a.reshape(l, heads, hd).transpose(1, 0, 2)  # noqa: E731
        q, k, v = (split(linear(h, w[name])) for name in ("wq", "wk", "wv"))
        q, k = rotate(q), rotate(k)
        at = jnp.arange(l)
        # a chunk's summary is used by the windows after its own: only whole
        # chunks of whole windows ever are, and every slot of those is real
        n_chunks = l // chunk
        chunk_window = (jnp.arange(n_chunks) * chunk) // window
        same_window = (at[None, :] // window == at[:, None] // window)
        see_keys = same_window & (at[None, :] <= at[:, None]) & (at[None, :] < n)
        see_summaries = chunk_window[None, :] < (at[:, None] // window)
        see = jnp.concatenate([see_keys, see_summaries], axis=1)  # [L, L + chunks]

        def some_heads(args):
            q, k, v, phi, mu = args  # [group, L, hd], [group, hd]
            by_chunk = lambda a: a[:, : n_chunks * chunk].reshape(group, n_chunks, chunk, hd)  # noqa: E731
            kc, vc = by_chunk(k), by_chunk(v)
            a = jax.nn.softmax(
                jnp.einsum("gjtd,gd->gjt", kc, phi, precision=hi) * scale, axis=-1
            )
            kbar = jnp.einsum("gjt,gjtd->gjd", a, kc, precision=hi) + mu[:, None, :]
            vbar = jnp.einsum("gjt,gjtd->gjd", a, vc, precision=hi)
            s = jnp.einsum(
                "gqd,gkd->gqk", q, jnp.concatenate([k, kbar], axis=1), precision=hi
            ) * scale
            p = jax.nn.softmax(jnp.where(see[None], s, -1e30), axis=-1)
            return jnp.einsum(
                "gqk,gkd->gqd", p, jnp.concatenate([v, vbar], axis=1), precision=hi
            )

        grouped = lambda a: a.reshape(heads // group, group, *a.shape[1:])  # noqa: E731
        out = jax.lax.map(
            some_heads, (grouped(q), grouped(k), grouped(v), grouped(w["phi"]), grouped(w["mu"]))
        )
        out = out.reshape(heads, l, hd).transpose(1, 0, 2).reshape(l, heads * hd)
        return x + linear(out, w["wo"])

    def layer(x, n, w):
        x = attention(x, n, w)
        h = norm(x)
        return x + linear(jax.nn.silu(linear(h, w["gate"])) * linear(h, w["up"]), w["down"])

    def pool(x, n):
        keep = (jnp.arange(x.shape[0]) < n)[:, None].astype(jnp.float32)
        pooled = (norm(x) * keep).sum(0) / n
        return pooled / jnp.linalg.norm(pooled)

    return {"layer": jax.jit(layer), "pool": jax.jit(pool)}


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
        linear layer's weights and activations.  A text's vector is
        computed once a precision and kept: the comparison asks for the
        same documents again for every control."""
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
                ids = byte_ids(text, self.max_len)
                padded = np.zeros(padded_length(len(ids)), dtype=np.int32)
                padded[: len(ids)] = ids
                lengths.append(len(ids))
                states.append(embedding[jnp.asarray(padded)])
            for i in range(m["layers"]):
                w = make_layer(m, self.seed, i)
                states = [fns["layer"](x, n, w) for x, n in zip(states, lengths)]
            out += [np.asarray(fns["pool"](x, n), dtype=np.float64)
                    for x, n in zip(states, lengths)]
        return np.stack(out)

    def free(self) -> None:
        self._known = {}

"""The plain reference of a BERT-style encoder: weights from the seed,
encoder, mean pooling.  `norm_style` "pre" (tanh GELU, a final LayerNorm)
and "post" (erf GELU, as published BERT) are the two layouts it knows.

Departure from the published BERT layout, shared with the program's
random-weight path and stated in the e5 configuration file: no embedding
LayerNorm and no token-type embedding (the program creates neither when it
makes random weights).  Imports nothing of the program
(chipbench/reference.py says what a reference is).
"""

from __future__ import annotations

import functools

import numpy as np

from chipbench.reference import fake_low, token_ids, weight_seed

INIT_SCALE = 0.02


@functools.lru_cache(maxsize=2)
def _make_params_fn(model_items: tuple):
    import jax
    import jax.numpy as jnp

    model = dict(model_items)
    h, mlp, v = model["hidden"], model["mlp_dim"], model["vocab_size"]
    layers, positions = model["layers"], model["max_position_embeddings"]

    def make(key):
        keys = jax.random.split(key, 4 + layers)

        def dense(k, shape):
            return jax.random.normal(k, shape, dtype=jnp.float32) * INIT_SCALE

        params = {
            "embed": dense(keys[0], (v, h)),
            "pos": dense(keys[1], (positions, h)),
            "layers": [],
        }
        for i in range(layers):
            k = jax.random.split(keys[4 + i], 6)
            params["layers"].append(
                {
                    "qkv": dense(k[0], (h, 3 * h)),
                    "out": dense(k[1], (h, h)),
                    "up": dense(k[2], (h, mlp)),
                    "down": dense(k[3], (mlp, h)),
                }
            )
        return params

    return jax.jit(make)


def make_params(model: dict, seed: int):
    """All weights in one jitted call on the device.  Biases start at zero
    and LayerNorm at scale one, bias zero, so they are not carried."""
    import jax

    fn = _make_params_fn(tuple(sorted(_shape_keys(model).items())))
    return fn(jax.random.PRNGKey(weight_seed(seed)))


def _shape_keys(model: dict) -> dict:
    keys = (
        "hidden", "mlp_dim", "vocab_size", "layers", "heads",
        "max_position_embeddings", "norm_style",
    )
    return {k: model[k] for k in keys}


def _layer_norm(x, eps: float):
    import jax.numpy as jnp

    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps)


@functools.lru_cache(maxsize=8)
def _forward_fn(model_items: tuple, lower_precision):
    import jax
    import jax.numpy as jnp

    model = dict(model_items)
    heads = model["heads"]
    head_dim = model["hidden"] // heads
    post = model["norm_style"] == "post"
    eps = 1e-12 if post else 1e-6

    def linear(x, w):
        if lower_precision:
            x, w = fake_low(x, -1, lower_precision), fake_low(w, 0, lower_precision)
        return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)

    def gelu(y):
        if post:  # BERT: exact erf
            return y * 0.5 * (1.0 + jax.scipy.special.erf(y * 0.7071067811865476))
        return y * 0.5 * (1.0 + jnp.tanh(0.7978845608 * (y + 0.044715 * y**3)))

    def attention(y, layer, mask):
        b, l, _ = y.shape
        q, k, v = jnp.split(linear(y, layer["qkv"]), 3, axis=-1)
        q = q.reshape(b, l, heads, head_dim).transpose(0, 2, 1, 3)
        k = k.reshape(b, l, heads, head_dim).transpose(0, 2, 1, 3)
        v = v.reshape(b, l, heads, head_dim).transpose(0, 2, 1, 3)
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k, precision=jax.lax.Precision.HIGHEST
        ) / np.sqrt(head_dim)
        s = jnp.where(mask[:, None, None, :] > 0, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum(
            "bhqk,bhkd->bhqd", p, v, precision=jax.lax.Precision.HIGHEST
        )
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, l, heads * head_dim)
        return linear(ctx, layer["out"])

    def forward(params, ids, mask):
        l = ids.shape[1]
        x = params["embed"][ids] + params["pos"][:l][None, :, :]
        for layer in params["layers"]:
            if post:
                x = _layer_norm(x + attention(x, layer, mask), eps)
                x = _layer_norm(
                    x + linear(gelu(linear(x, layer["up"])), layer["down"]), eps
                )
            else:
                x = x + attention(_layer_norm(x, eps), layer, mask)
                y = gelu(linear(_layer_norm(x, eps), layer["up"]))
                x = x + linear(y, layer["down"])
        if not post:
            x = _layer_norm(x, eps)
        m = mask[:, :, None].astype(jnp.float32)
        pooled = (x * m).sum(1) / m.sum(1)
        return pooled / jnp.linalg.norm(pooled, axis=-1, keepdims=True)

    return jax.jit(forward)


class Encoder:
    """texts -> [n, hidden] float64 unit vectors, a block at a time."""

    def __init__(self, model: dict, seed: int, *, max_len: int, block: int = 32):
        self.model = _shape_keys(model)
        self.max_len = int(max_len)
        self.block = int(block)
        self.params = make_params(model, seed)

    def embed(self, texts: list, *, lower_precision=None) -> np.ndarray:
        """lower_precision: None, "int8" or "fp8" (the control)."""
        if not texts:
            return np.zeros((0, self.model["hidden"]), dtype=np.float64)
        fn = _forward_fn(tuple(sorted(self.model.items())), lower_precision)
        encoded = [
            token_ids(t, self.model["vocab_size"], self.max_len) for t in texts
        ]
        # one padded length for every block: one compile
        width = -(-max(len(e) for e in encoded) // 8) * 8
        out = np.zeros((len(texts), self.model["hidden"]), dtype=np.float64)
        for lo in range(0, len(encoded), self.block):
            rows = encoded[lo : lo + self.block]
            ids = np.zeros((self.block, width), dtype=np.int32)
            mask = np.zeros((self.block, width), dtype=np.int32)
            for i, e in enumerate(rows):
                ids[i, : len(e)] = e
                mask[i, : len(e)] = 1
            mask[len(rows):, 0] = 1  # filler rows pool over one pad token
            vecs = np.asarray(fn(self.params, ids, mask), dtype=np.float64)
            out[lo : lo + len(rows)] = vecs[: len(rows)]
        return out

    def free(self) -> None:
        self.params = None

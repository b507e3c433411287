"""The plain reference of the fixture architecture `cls_encoder`: a pre-LN
encoder (tanh GELU, a final LayerNorm) whose vector is its first token's,
in float32 at `highest`.  Imports nothing of the program."""

import functools

import numpy as np

from chipbench.reference import fake_low, token_ids, weight_seed


def make_params(model: dict, seed: int):
    import jax
    import jax.numpy as jnp

    h, mlp, layers = model["hidden"], model["mlp_dim"], model["layers"]

    def dense(k, shape):
        return jax.random.normal(k, shape, dtype=jnp.float32) * 0.02

    keys = jax.random.split(jax.random.PRNGKey(weight_seed(seed)), 4 + layers)
    params = {
        "embed": dense(keys[0], (model["vocab_size"], h)),
        "pos": dense(keys[1], (model["max_position_embeddings"], h)),
        "layers": [],
    }
    for i in range(layers):
        k = jax.random.split(keys[4 + i], 6)
        params["layers"].append({
            "qkv": dense(k[0], (h, 3 * h)), "out": dense(k[1], (h, h)),
            "up": dense(k[2], (h, mlp)), "down": dense(k[3], (mlp, h)),
        })
    return params


@functools.lru_cache(maxsize=4)
def _forward_fn(heads: int, lower_precision):
    import jax
    import jax.numpy as jnp

    highest = jax.lax.Precision.HIGHEST

    def norm(x):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + 1e-6)

    def linear(x, w):
        if lower_precision:
            x, w = fake_low(x, -1, lower_precision), fake_low(w, 0, lower_precision)
        return jnp.matmul(x, w, precision=highest)

    def attention(y, layer, mask):
        b, l, h = y.shape
        q, k, v = (
            t.reshape(b, l, heads, h // heads).transpose(0, 2, 1, 3)
            for t in jnp.split(linear(y, layer["qkv"]), 3, axis=-1)
        )
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=highest) / np.sqrt(h // heads)
        p = jax.nn.softmax(jnp.where(mask[:, None, None, :] > 0, s, -1e30), axis=-1)
        mix = jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=highest)
        return linear(mix.transpose(0, 2, 1, 3).reshape(b, l, h), layer["out"])

    def forward(params, ids, mask):
        x = params["embed"][ids] + params["pos"][: ids.shape[1]][None, :, :]
        for layer in params["layers"]:
            x = x + attention(norm(x), layer, mask)
            y = linear(norm(x), layer["up"])
            y = y * 0.5 * (1.0 + jnp.tanh(0.7978845608 * (y + 0.044715 * y**3)))
            x = x + linear(y, layer["down"])
        first = norm(x)[:, 0, :]
        return first / jnp.linalg.norm(first, axis=-1, keepdims=True)

    return jax.jit(forward)


class Encoder:
    """texts -> [n, hidden] float64 unit vectors, a block at a time."""

    def __init__(self, model: dict, seed: int, *, max_len: int, block: int = 32):
        self.model, self.max_len, self.block = model, int(max_len), int(block)
        self.params = make_params(model, seed)

    def embed(self, texts: list, *, lower_precision=None) -> np.ndarray:
        out = np.zeros((len(texts), self.model["hidden"]), dtype=np.float64)
        if not texts:
            return out
        fn = _forward_fn(self.model["heads"], lower_precision)
        encoded = [token_ids(t, self.model["vocab_size"], self.max_len) for t in texts]
        width = -(-max(len(e) for e in encoded) // 8) * 8
        for lo in range(0, len(encoded), self.block):
            rows = encoded[lo : lo + self.block]
            ids = np.zeros((self.block, width), dtype=np.int32)
            mask = np.zeros((self.block, width), dtype=np.int32)
            for i, e in enumerate(rows):
                ids[i, : len(e)] = e
                mask[i, : len(e)] = 1
            mask[len(rows):, 0] = 1
            out[lo : lo + len(rows)] = np.asarray(fn(self.params, ids, mask))[: len(rows)]
        return out

    def free(self) -> None:
        self.params = None

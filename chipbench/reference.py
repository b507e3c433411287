"""What every architecture's plain reference shares: the tokenizer rule,
the seed's fold, and rounding to the precisions of the controls.

A configuration's reference itself is its architecture's own file,
`chipbench/architectures/<a>/reference.py`: straightforward jax.numpy in
float32 with matmuls at `highest` precision, no kernels, no packing, no
cache, no batching tricks.  Neither this file nor that one imports
anything of the program or takes anything the program has made: the
weights are made again from the seed by the recipe the configuration file
states (`init`), and the tokenizer is the hashing rule the file states.

`lower_precision="int8"` (or "fp8") is the control of "How correct is
decided": the same computation with every linear layer's weights (per
output channel) and activations (per token) rounded by `fake_low` to int8
(or fp8 e4m3), the nearest precision below the bf16 the configurations
state.
"""

from __future__ import annotations

import re
import zlib

import numpy as np

PAD_ID, CLS_ID, SEP_ID, RESERVED = 0, 1, 2, 4
_WORD = re.compile(r"[A-Za-z0-9]+|[^\sA-Za-z0-9]")


def token_ids(text: str, vocab_size: int, max_len: int) -> list:
    """[CLS] + one hashed id a word + [SEP], cut to max_len (the cut may
    drop the [SEP], as the configuration file states)."""
    ids = [CLS_ID]
    ids += [
        RESERVED + zlib.crc32(t.encode()) % (vocab_size - RESERVED)
        for t in _WORD.findall(text.lower())
    ]
    ids.append(SEP_ID)
    return ids[:max_len]


def weight_seed(seed: int) -> int:
    """--seed may exceed 32 signed bits; the weights' key takes it folded."""
    return int(seed) % (2**31 - 1)


def fake_low(x, axis: int, kind: str):
    """x rounded to `kind` ("int8" or "fp8", e4m3) and back, scaled along
    `axis` (per token for activations, per output channel for weights)."""
    import jax.numpy as jnp

    top = 127.0 if kind == "int8" else 448.0
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    if kind == "int8":
        return jnp.round(x / scale) * scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def round_vectors(vecs: np.ndarray, kind: str) -> np.ndarray:
    """Unit vectors as an index of a lower precision would hold them."""
    import jax.numpy as jnp

    if kind == "bf16":
        return np.asarray(jnp.asarray(vecs, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32), np.float64)
    return np.asarray(fake_low(jnp.asarray(vecs, jnp.float32), -1, kind), np.float64)

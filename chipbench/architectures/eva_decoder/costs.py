"""What one pipeline stage of a byte-level trunk with chunked linear
attention costs, from shapes and token counts: what the algorithm needs,
never what a kernel happens to execute (padding, masked pairs of a block,
blocks met and found empty are not work).  Imports nothing of the program.

**The token count.**  The harness counts a document as `words + 2` tokens
(`harness.py`: one word is one token, [CLS] and [SEP]).  This model reads
bytes.  The functions that take `tokens` turn that count into bytes by the
generator's own vocabulary: a word of `chipbench.traffic.vocabulary(n)` is
on average 6.413 letters, a document is its words joined by single spaces,
and `<bos>` takes the place of the space the last word lacks, so

    bytes = (tokens - 2) x (1 + mean letters of the vocabulary) = 7.413 a word

cut to `max_len`.  `chipbench/tests/test_eva_decoder.py` pins the rule to
the true byte count of 64 generated pages within 0.5%.  The kernel's
roofline does not use it: the program counts the pairs it scored.
"""

from __future__ import annotations

import functools

PARAM_BYTES = {"bfloat16": 2, "float32": 4}
VOCABULARY_WORDS = 32768  # `vocabulary_words` of every traffic mix there is


@functools.lru_cache(maxsize=None)
def bytes_per_word(vocabulary_words: int = VOCABULARY_WORDS) -> float:
    from chipbench import traffic

    words = traffic.vocabulary(vocabulary_words)
    return 1.0 + sum(len(w) for w in words) / len(words)


def byte_tokens(model: dict, tokens: int) -> float:
    """The model's tokens of a document the harness counts as `tokens`
    (words + 2): its bytes and `<bos>`, cut to `max_len`."""
    return min(max(tokens - 2, 0) * bytes_per_word(), float(model["max_len"]))


def _layer_matrices(model: dict) -> int:
    """q, k, v, o and the SwiGLU's gate, up, down."""
    d = model["hidden_size"]
    return 4 * d * d + 3 * d * model["intermediate_size"]


def scored_pairs(model: dict, n: float) -> float:
    """(query, key) and (query, summary) pairs one head of one layer scores
    for a document of n tokens: the triangle of every window, and for a
    query in window w the w x window / chunk summaries before it."""
    window, chunk = model["window_size"], model["chunk_size"]
    full, rest = divmod(n, window)
    keys = full * window * (window + 1) / 2 + rest * (rest + 1) / 2
    summaries = (window // chunk) * (window * full * (full - 1) / 2 + rest * full)
    return keys + summaries


def flops(model: dict, tokens: int) -> float:
    """Forward FLOPs of one document on this stage: the seven matrices of
    every layer held, and the attention's scores and mixes.  Norms,
    softmax, RoPE, the chunk summaries (two products a token and head),
    pooling and the embedding gather are left out."""
    n = byte_tokens(model, tokens)
    return 2.0 * model["layers"] * _layer_matrices(model) * n + eva_attention_flops(
        model, scored_pairs(model, n) * model["num_attention_heads"] * model["layers"]
    )


def layer_params(model: dict) -> int:
    """Parameters of the layers held: the matrices, `adaptive_phi` and
    `adaptive_mu_k`, two norm offsets a layer."""
    d = model["hidden_size"]
    return model["layers"] * (_layer_matrices(model) + 2 * d + 2 * d)


def weight_bytes(model: dict) -> float:
    """Bytes of the layer weights one run of the program has to read once,
    in the type they are resident and computed in.  The embedding is
    gathered, not streamed, and is left out."""
    return float(PARAM_BYTES[model["param_dtype"]] * layer_params(model))


def activation_bytes(model: dict, tokens: int) -> float:
    """The least a document's activations move through HBM: its hidden
    states written and read once per layer, in bf16."""
    return float(2 * 2 * byte_tokens(model, tokens) * model["hidden_size"] * model["layers"])


def resident_param_bytes(model: dict) -> int:
    """Bytes of the parameters as the program keeps them on the chip: the
    embedding, the final norm and the layers, in `param_dtype`."""
    d = model["hidden_size"]
    return PARAM_BYTES[model["param_dtype"]] * (
        model["vocab_size"] * d + d + layer_params(model)
    )


def embed_dim(model: dict) -> int:
    """Width of the vectors the store holds."""
    return model["hidden_size"]


def dry_cut(model: dict) -> dict:
    """The CPU rehearsal's sizes: one layer, and texts cut to 64 bytes (a
    quarter of a million byte tokens through a 4096-wide layer is beyond a
    CPU's quarter of an hour; the tier-1 tests run the windows and the
    summaries at toy widths).  Every width stays as published."""
    return dict(model, layers=1, max_len=64)


# -- the kernel's own work (chipbench/readers/op_roofline.py) ---------------------


def eva_attention_flops(model: dict, pairs: float) -> float:
    """Scores and mix of `pairs` scored pairs, a pair being one query
    against one key or summary in one head of one layer (the program's
    counter `eva.scored_pairs`): two products of `head_dim`."""
    head_dim = model["hidden_size"] // model["num_attention_heads"]
    return 4.0 * head_dim * pairs


def eva_attention_bytes(model: dict, pairs: float, runs: int) -> float:
    """What the attention reads and writes once: a query row in, a context
    row out and a key and a value row, in bf16, for every token of every
    layer.  A token of a whole window scores (window + 1) / 2 keys, so the
    tokens are taken as pairs over that (a lower bound: short documents
    have more tokens a pair).  `runs` does not enter: no weights."""
    per_token = (model["window_size"] + 1) / 2.0
    head_dim = model["hidden_size"] // model["num_attention_heads"]
    return 2.0 * 4 * head_dim * pairs / per_token

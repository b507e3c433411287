"""What one pipeline stage of a compressed-convolutional-attention MoE
trunk costs (every expert and the whole vocabulary held, the depth cut),
from shapes and token counts: what the algorithm needs, never what a
kernel happens to execute (padding, masked pairs of a block, a buffer's
empty rows are not work).  Imports nothing of the program."""

from __future__ import annotations

PARAM_BYTES = {"bfloat16": 2, "float32": 4}


def _attention_params(model: dict) -> int:
    """The fused projection (query, key and value heads), W_o and the
    grouped convolution's matrices: what a token multiplies through."""
    d, hd = model["hidden_size"], model["head_dim"]
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    return (
        d * (heads + 2 * kv) * hd + heads * hd * d
        + (heads + kv) * model["cca_time1"] * hd * hd
    )


def _router_params(model: dict) -> int:
    """W_down, the MLP's two square matrices and its output (the experts
    and "skip")."""
    d, rh = model["hidden_size"], model["router_hidden_size"]
    return d * rh + 2 * rh * rh + rh * (model["num_experts"] + 1)


def _expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def held_pairs_per_token(model: dict) -> float:
    """Expected (token, held expert) pairs a token: its experts per token
    times the share of the routed experts held here, as if no token
    skipped (the share that does is the router's, not a shape:
    `zaya.skipped_token_share` reports it)."""
    return model["num_experts_per_tok"] * model["experts_held"] / model["num_experts"]


def scored_pairs(tokens: int) -> int:
    """(query, key) pairs one query head of one layer scores for a
    document of `tokens` tokens: the causal triangle."""
    return tokens * (tokens + 1) // 2


def matrix_flops_per_token(model: dict) -> float:
    """Forward FLOPs a token in the matrices of every layer held: the
    projections and the grouped convolution, the router's MLP, and the one
    expert a token takes by the share held."""
    a_layer = (
        _attention_params(model) + _router_params(model)
        + held_pairs_per_token(model) * _expert_params(model)
    )
    return 2.0 * model["layers"] * a_layer


def flops(model: dict, tokens: int) -> float:
    """Forward FLOPs of one document of `tokens` real tokens on this
    stage: the matrices and the attention's scores and mixes of the pairs
    that count.  Norms, the depthwise convolution, softmax, RoPE, argmax,
    pooling and the embedding gather are left out."""
    tokens = min(int(tokens), model["max_len"])
    pairs = model["layers"] * model["num_attention_heads"] * scored_pairs(tokens)
    return float(tokens) * matrix_flops_per_token(model) + cca_attention_flops(model, pairs)


def layer_params(model: dict) -> int:
    """Parameters of the layers as held here: the matrices above, the held
    experts, and the vectors (three norms, the convolutions' depthwise
    taps and two biases, tau, the merges' four scales, gamma, beta)."""
    d, hd = model["hidden_size"], model["head_dim"]
    channels = (model["num_attention_heads"] + model["num_key_value_heads"]) * hd
    vectors = (
        2 * d + model["router_hidden_size"] + (model["cca_time0"] + 2) * channels
        + model["num_key_value_heads"] + 4 * d + 1 + model["num_experts"] + 1
    )
    return model["layers"] * (
        _attention_params(model) + _router_params(model)
        + model["experts_held"] * _expert_params(model) + vectors
    )


def weight_bytes(model: dict) -> float:
    """Bytes of the layer weights one run of the program has to read once,
    in the type the matrices are resident and computed in (the vectors,
    float32 in the program, are a few kilobytes a layer and counted
    alike).  The embedding is gathered, not streamed, and is left out."""
    return float(PARAM_BYTES[model["param_dtype"]] * layer_params(model))


def activation_bytes(model: dict, tokens: int) -> float:
    """The least a document's activations move through HBM: its hidden
    states written and read once per layer, in bf16."""
    tokens = min(int(tokens), model["max_len"])
    return float(2 * 2 * tokens * model["hidden_size"] * model["layers"])


def resident_param_bytes(model: dict) -> int:
    """Bytes of the parameters as the program keeps them on the chip: the
    held rows of the embedding, the final norm and the layers, in
    `param_dtype`."""
    d = model["hidden_size"]
    return PARAM_BYTES[model["param_dtype"]] * (
        model["vocab_held"] * d + d + layer_params(model)
    )


def embed_dim(model: dict) -> int:
    """Width of the vectors the store holds."""
    return model["hidden_size"]


def dry_cut(model: dict) -> dict:
    """The CPU rehearsal's sizes: two layers (the router's state is
    carried once) and texts cut to 32 tokens (a file of 64 documents of
    hundreds of tokens through sixteen 2048-wide experts a layer, and the
    reference's float32 passes that run every expert on every token, are
    beyond a CPU's quarter of an hour).  Every width, every expert, both
    convolutions and the whole vocabulary stay as published."""
    return dict(model, layers=2, max_len=32)


# -- the kernels' own work (chipbench/readers/op_roofline.py) -------------------


def cca_attention_flops(model: dict, pairs: float) -> float:
    """Score and mix of `pairs` scored pairs, a pair being one query
    against one key in one query head of one layer: 2 x head_dim each, 512
    at the published size."""
    return 4.0 * model["head_dim"] * pairs


def cca_attention_bytes(model: dict, pairs: float, runs: int) -> float:
    """What the attention reads and writes once, in bf16: a query head's
    row in and its context row out for every (token, query head), and a
    key and a value row for every (token, key/value head), which `group`
    query heads share.  The (token, query head)s are taken as the pairs
    over the most keys a token meets on average, half the longest document
    a row may hold (a lower bound on the bytes: shorter documents have
    more tokens a pair)."""
    group = model["num_attention_heads"] / model["num_key_value_heads"]
    a_token = 2 * model["head_dim"] * (1.0 + 1.0 / group)
    return 2.0 * a_token * pairs / ((model["max_len"] + 1) / 2.0)


def expert_matmul_flops(model: dict, pairs: int) -> float:
    """The three matrices of an expert for `pairs` (token, held expert)
    pairs actually routed here (the tokens that skipped are none)."""
    return float(2 * pairs * _expert_params(model))


def expert_matmul_bytes(model: dict, pairs: int, runs: int) -> float:
    """The held experts' weights of every layer once a run of the program,
    and a pair's row read and its result written, bf16."""
    weights = PARAM_BYTES[model["param_dtype"]] * (
        model["layers"] * model["experts_held"] * _expert_params(model)
    )
    return float(runs * weights + 2 * 2 * pairs * model["hidden_size"])

"""What stage 0 of a Laguna-family trunk costs (window layers beside full
ones with their own query heads, a head-wise output gate, every routed
expert held beside a shared one), from shapes and token counts: what the
algorithm needs, never what a kernel happens to execute (padding, masked
pairs of a block, a buffer's empty rows are not work).  By layer kind: the
layers are not alike.  Imports nothing of the program."""

from __future__ import annotations

PARAM_BYTES = {"bfloat16": 2, "float32": 4}


def layer_kinds(model: dict) -> list:
    """(window, dense, query heads) of each layer held: `layer_types`,
    `mlp_layer_types` and `num_attention_heads_per_layer`, the first
    `layers` of each."""
    n = model["layers"]
    return [
        (w == "sliding_attention", d == "dense", heads)
        for w, d, heads in zip(model["layer_types"][:n], model["mlp_layer_types"][:n],
                               model["num_attention_heads_per_layer"][:n])
    ]


def _attention_params(model: dict, heads: int) -> int:
    """The fused matrix (query, key and value heads), W_o and the gate."""
    d, hd, kv = model["hidden_size"], model["head_dim"], model["num_key_value_heads"]
    return d * (heads + 2 * kv) * hd + heads * hd * d + d * heads


def _expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def _shared_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["shared_expert_intermediate_size"]


def _sparse_layers(model: dict) -> int:
    return sum(not dense for _, dense, _ in layer_kinds(model))


def held_pairs_per_token(model: dict) -> float:
    """Expected (token, held expert) pairs a token: its experts per token
    times the share of the routed experts held here (8 with all held)."""
    return model["num_experts_per_tok"] * model["experts_held"] / model["num_experts"]


def scored_pairs(model: dict, tokens: int, window: bool) -> int:
    """(query, key) pairs one query head of one layer scores for a
    document of `tokens` tokens: the triangle (full), or the triangle of
    the first `sliding_window` tokens and `sliding_window` keys a token
    after them."""
    if not window:
        return tokens * (tokens + 1) // 2
    first = min(tokens, model["sliding_window"])
    return first * (first + 1) // 2 + (tokens - first) * model["sliding_window"]


def _pairs(model: dict, tokens: int, window: bool) -> int:
    """Scored pairs of one document over every query head (of its kind)
    and every layer of one kind: the program's `hybrid.global_pairs` /
    `.window_pairs`."""
    return sum(
        heads * scored_pairs(model, tokens, window)
        for w, _, heads in layer_kinds(model) if w == window
    )


def matrix_flops_per_token(model: dict) -> float:
    """Forward FLOPs a token in the matrices: by layer kind the attention
    matrices and the gate, the dense layer's SwiGLU, a sparse layer's
    router, shared expert and the held experts' expected pairs."""
    d, total = model["hidden_size"], 0.0
    for _, dense, heads in layer_kinds(model):
        total += _attention_params(model, heads)
        if dense:
            total += 3 * d * model["intermediate_size"]
        else:
            total += (
                d * model["num_experts"] + _shared_params(model)
                + held_pairs_per_token(model) * _expert_params(model)
            )
    return 2.0 * total


def flops(model: dict, tokens: int) -> float:
    """Forward FLOPs of one document of `tokens` real tokens on this
    stage: the matrices and the attention's scores and mixes of the pairs
    that count, by kind.  Norms, softmax, RoPE, the gate's sigmoid, top-k,
    pooling and the embedding gather are left out."""
    tokens = min(int(tokens), model["max_len"])
    return (
        float(tokens) * matrix_flops_per_token(model)
        + global_attention_flops(model, _pairs(model, tokens, False))
        + window_attention_flops(model, _pairs(model, tokens, True))
    )


def layer_params(model: dict) -> int:
    """Parameters of the layers as held here: attention and gate by kind,
    the dense layer's SwiGLU, a sparse layer's router, shared expert and
    held experts, and the two norms a layer."""
    d, total = model["hidden_size"], 0
    for _, dense, heads in layer_kinds(model):
        total += _attention_params(model, heads) + 2 * d
        if dense:
            total += 3 * d * model["intermediate_size"]
        else:
            total += (
                d * model["num_experts"] + _shared_params(model)
                + model["experts_held"] * _expert_params(model)
            )
    return total


def weight_bytes(model: dict) -> float:
    """Bytes of the layer weights one run of the program has to read once,
    in the type they are resident and computed in.  The embedding is
    gathered, not streamed, and is left out."""
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
    """The CPU rehearsal's sizes: one layer of each kind (full and dense,
    sliding and sparse, full and sparse: the kind lists are cut to those
    three), 32 of the 256 routed experts held (a 2048-wide sparse layer of
    all 256 is 0.8 G parameters, beyond a shared CPU's memory in the
    reference's float32), and texts cut to 32 tokens.  Every width, both
    head counts, both ladders, the window, the router's 256 outputs and
    its 8 a token stay as published."""
    full, window = model["num_attention_heads_per_layer"][0:2]
    return dict(
        model, layers=3,
        layer_types=["full_attention", "sliding_attention", "full_attention"],
        mlp_layer_types=["dense", "sparse", "sparse"],
        num_attention_heads_per_layer=[full, window, full],
        experts_held=32, max_len=32,
    )


# -- the kernels' own work (chipbench/readers/op_roofline.py) -------------------


def _attention_flops(model: dict, pairs: float) -> float:
    """Score and mix of `pairs` scored pairs, a pair being one query
    against one key in one query head of one layer: 2 x head_dim for the
    score and 2 x head_dim for the mix, 512 at the published size."""
    return 4.0 * model["head_dim"] * pairs


def _attention_bytes(model: dict, pairs: float, window: bool) -> float:
    """What the attention reads and writes once, in bf16: a query head's
    row in and its context row out for every (token, query head), and a
    key and a value row for every (token, key/value head), which `group`
    query heads share.  The (token, query head)s are taken as the pairs
    over the most keys a token meets on average: the window, or half the
    longest document a row may hold (a lower bound on the bytes)."""
    heads = [h for w, _, h in layer_kinds(model) if w == window]
    group = (heads[0] if heads else model["num_attention_heads"]) / model["num_key_value_heads"]
    a_token = 2 * model["head_dim"] * (1.0 + 1.0 / group)
    met = model["sliding_window"] if window else (model["max_len"] + 1) / 2.0
    return 2.0 * a_token * pairs / met


def global_attention_flops(model: dict, pairs: float) -> float:
    return _attention_flops(model, pairs)


def global_attention_bytes(model: dict, pairs: float, runs: int) -> float:
    return _attention_bytes(model, pairs, False)


def window_attention_flops(model: dict, pairs: float) -> float:
    return _attention_flops(model, pairs)


def window_attention_bytes(model: dict, pairs: float, runs: int) -> float:
    return _attention_bytes(model, pairs, True)


def expert_matmul_flops(model: dict, pairs: int) -> float:
    """The three matrices of an expert for `pairs` (token, held expert)
    pairs actually routed here."""
    return float(2 * pairs * _expert_params(model))


def expert_matmul_bytes(model: dict, pairs: int, runs: int) -> float:
    """The held experts' weights of every sparse layer once a run of the
    program, and a pair's row read and its result written, bf16."""
    weights = PARAM_BYTES[model["param_dtype"]] * (
        _sparse_layers(model) * model["experts_held"] * _expert_params(model)
    )
    return float(runs * weights + 2 * 2 * pairs * model["hidden_size"])

"""What one expert-parallel rank of a hybrid-attention MoE trunk (window
layers beside global grouped-query ones, a bias-corrected router, no
shared expert) costs, from shapes and token counts: what the algorithm
needs, never what a kernel happens to execute (padding, masked pairs of a
block, a buffer's empty rows are not work).  By layer kind: the layers are
not alike.  Imports nothing of the program."""

from __future__ import annotations

PARAM_BYTES = {"bfloat16": 2, "float32": 4}


def layer_kinds(model: dict) -> list:
    """(window, dense) of each layer held: `hybrid_layer_pattern` (0
    global, 1 window) and `moe_layer_freq` (0 dense, 1 experts), the first
    `layers` of each."""
    n = model["layers"]
    return [
        (bool(w), not e)
        for w, e in zip(model["hybrid_layer_pattern"][:n], model["moe_layer_freq"][:n])
    ]


def _kv_heads(model: dict, window: bool) -> int:
    return model["swa_num_key_value_heads" if window else "num_key_value_heads"]


def _attention_params(model: dict, window: bool) -> int:
    """The fused matrix (query heads, key heads, value heads) and W_o."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    kv = _kv_heads(model, window)
    fused = heads * model["head_dim"] + kv * (model["head_dim"] + model["v_head_dim"])
    return d * fused + heads * model["v_head_dim"] * d


def _expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def _expert_layers(model: dict) -> int:
    return sum(not dense for _, dense in layer_kinds(model))


def held_pairs_per_token(model: dict) -> float:
    """Expected (token, held expert) pairs a token: its experts per token
    times the share of the routed experts held here."""
    return model["num_experts_per_tok"] * model["experts_held"] / model["n_routed_experts"]


def scored_pairs(model: dict, tokens: int, window: bool) -> int:
    """(query, key) pairs one query head of one layer scores for a
    document of `tokens` tokens: the triangle (global), or the triangle of
    the first `sliding_window` tokens and `sliding_window` keys a token
    after them."""
    if not window:
        return tokens * (tokens + 1) // 2
    first = min(tokens, model["sliding_window"])
    return first * (first + 1) // 2 + (tokens - first) * model["sliding_window"]


def _pairs(model: dict, tokens: int, window: bool) -> int:
    """Scored pairs of one document over every query head and every layer
    of one kind: the program's `hybrid.global_pairs` / `.window_pairs`."""
    layers = sum(w == window for w, _ in layer_kinds(model))
    return layers * model["num_attention_heads"] * scored_pairs(model, tokens, window)


def matrix_flops_per_token(model: dict) -> float:
    """Forward FLOPs a token in the matrices: by layer kind the attention
    matrices, the dense layers' SwiGLU, an expert layer's router and the
    held experts' expected pairs."""
    d, total = model["hidden_size"], 0
    for window, dense in layer_kinds(model):
        total += _attention_params(model, window)
        if dense:
            total += 3 * d * model["intermediate_size"]
        else:
            total += d * model["n_routed_experts"] + held_pairs_per_token(model) * _expert_params(model)
    return 2.0 * total


def flops(model: dict, tokens: int) -> float:
    """Forward FLOPs of one document of `tokens` real tokens on this rank:
    the matrices and the attention's scores and mixes of the pairs that
    count, by kind.  Norms, softmax, RoPE, top-k, pooling and the embedding
    gather are left out."""
    tokens = min(int(tokens), model["max_len"])
    return (
        float(tokens) * matrix_flops_per_token(model)
        + global_attention_flops(model, _pairs(model, tokens, False))
        + window_attention_flops(model, _pairs(model, tokens, True))
    )


def layer_params(model: dict) -> int:
    """Parameters of the layers as held here: attention by kind, a sink a
    query head where the kind has one, the dense layers' SwiGLU, router,
    selection bias and held experts, and the two norms a layer."""
    d, total = model["hidden_size"], 0
    for window, dense in layer_kinds(model):
        total += _attention_params(model, window) + 2 * d
        if model["add_swa_attention_sink_bias" if window else "add_full_attention_sink_bias"]:
            total += model["num_attention_heads"]
        if dense:
            total += 3 * d * model["intermediate_size"]
        else:
            total += (d + 1) * model["n_routed_experts"] + model["experts_held"] * _expert_params(model)
    return total


def weight_bytes(model: dict) -> float:
    """Bytes of the layer weights one run of the program has to read once,
    in the type they are resident and computed in (the sinks and the
    selection bias, float32 in the program, are a few kilobytes and
    counted alike).  The embedding is gathered, not streamed, and is left
    out."""
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
    """The CPU rehearsal's sizes: one layer of each kind (global and
    dense, window with experts, global with experts: the pattern's lists
    are cut to those three), and texts cut to 32 tokens (a file of 64
    documents of thousands of tokens through a 4096-wide layer is beyond a
    CPU's quarter of an hour, and the reference's float32 passes over a
    hundred texts take most of the rehearsal as it is; the tier-1 tests run
    long documents at toy widths).  Every width, the window, both key/value head counts, the
    router and the experts a token stay as published."""
    return dict(
        model, layers=3, hybrid_layer_pattern=[0, 1, 0], moe_layer_freq=[0, 1, 1],
        max_len=32,
    )


# -- the kernels' own work (chipbench/readers/op_roofline.py) -------------------


def _attention_flops(model: dict, pairs: float) -> float:
    """Score and mix of `pairs` scored pairs, a pair being one query
    against one key in one query head of one layer: 2 x head_dim for the
    score and 2 x v_head_dim for the mix, 640 at the published sizes."""
    return 2.0 * (model["head_dim"] + model["v_head_dim"]) * pairs


def _attention_bytes(model: dict, pairs: float, window: bool) -> float:
    """What the attention reads and writes once, in bf16: a query head's
    row in and its context row out for every (token, query head), and a
    key and a value row for every (token, key/value head), which `group`
    query heads share.  The (token, query head)s are taken as the pairs
    over the most keys a token meets on average: the window, or half the
    longest document a row may hold (a lower bound on the bytes: shorter
    documents have more tokens a pair).  A window layer is bound by these
    bytes (128 keys a query are 82 kFLOP for 720 bytes), a global one by
    its FLOPs."""
    group = model["num_attention_heads"] / _kv_heads(model, window)
    a_token = (model["head_dim"] + model["v_head_dim"]) * (1.0 + 1.0 / group)
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
    """The held experts' weights of every expert layer once a run of the
    program, and a pair's row read and its result written, bf16."""
    weights = PARAM_BYTES[model["param_dtype"]] * (
        _expert_layers(model) * model["experts_held"] * _expert_params(model)
    )
    return float(runs * weights + 2 * 2 * pairs * model["hidden_size"])

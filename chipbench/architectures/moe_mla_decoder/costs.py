"""What one expert-parallel rank of a latent-attention + shared-expert MoE
trunk costs, from shapes and token counts: what the algorithm needs, never
what a kernel happens to execute (padding, a buffer's empty rows, weights
read again for a second group of rows are not work).  Imports nothing of
the program."""

from __future__ import annotations

PARAM_BYTES = {"bfloat16": 2, "float32": 4}


def _attention_params(model: dict) -> int:
    """MLA's five matrices: q down and up, kv down (latent + rope key) and
    up, output."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return (
        d * model["q_lora_rank"] + model["q_lora_rank"] * heads * qk
        + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
        + model["kv_lora_rank"] * heads * (model["qk_nope_head_dim"] + model["v_head_dim"])
        + heads * model["v_head_dim"] * d
    )


def _expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def _dense_layers(model: dict) -> int:
    return min(model["first_k_dense_replace"], model["layers"])


def _expert_layers(model: dict) -> int:
    return model["layers"] - _dense_layers(model)


def held_pairs_per_token(model: dict) -> float:
    """Expected (token, held expert) pairs a token: its experts per token
    times the share of the routed experts held here."""
    return model["num_experts_per_tok"] * model["experts_held"] / model["n_routed_experts"]


def flops(model: dict, tokens: int) -> float:
    """Forward FLOPs of one document of `tokens` real tokens on this rank:
    a layer's five attention matrices; causal attention within the
    document (half the square: heads x (nope + rope + v) x tokens a
    token); the dense layers' SwiGLU; an expert layer's router, shared
    experts and the held experts' expected pairs.  Norms, softmax, RoPE,
    top-k, pooling and the embedding gather are left out."""
    d = model["hidden_size"]
    dense = 2 * 3 * d * model["intermediate_size"]
    moe = 2 * (
        d * model["n_routed_experts"]
        + (model["n_shared_experts"] + held_pairs_per_token(model)) * _expert_params(model)
    )
    per_token = (
        model["layers"] * 2 * _attention_params(model)
        + _dense_layers(model) * dense + _expert_layers(model) * moe
    )
    return float(tokens) * per_token + mla_attention_flops(model, tokens)


def layer_params(model: dict) -> int:
    """Parameters of the layers as held here: attention, the dense layers'
    SwiGLU, router, shared and held experts, and the four norms a layer."""
    d = model["hidden_size"]
    norms = 2 * d + model["q_lora_rank"] + model["kv_lora_rank"]
    dense = 3 * d * model["intermediate_size"]
    moe = d * model["n_routed_experts"] + (
        model["n_shared_experts"] + model["experts_held"]
    ) * _expert_params(model)
    return (
        model["layers"] * (_attention_params(model) + norms)
        + _dense_layers(model) * dense + _expert_layers(model) * moe
    )


def weight_bytes(model: dict) -> float:
    """Bytes of the layer weights one run of the program has to read once,
    in the type they are resident and computed in.  The embedding is
    gathered, not streamed, and is left out."""
    return float(PARAM_BYTES[model["param_dtype"]] * layer_params(model))


def activation_bytes(model: dict, tokens: int) -> float:
    """The least a document's activations move through HBM: its hidden
    states written and read once per layer, in bf16."""
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
    """The CPU rehearsal's sizes: one dense and one expert layer.  Widths,
    the router and the experts a token stay as published."""
    return dict(model, layers=model["first_k_dense_replace"] + 1)


# -- the kernels' own work (chipbench/readers/op_roofline.py) -------------------


def mla_attention_flops(model: dict, tokens: int) -> float:
    """Scores and mix of one document in every layer, causal: a token
    meets half the document on average."""
    heads = model["num_attention_heads"]
    width = model["qk_nope_head_dim"] + model["qk_rope_head_dim"] + model["v_head_dim"]
    return float(model["layers"] * heads * width * tokens * tokens)


def mla_attention_bytes(model: dict, tokens: int) -> float:
    """What the attention of one document reads and writes once, bf16, in
    every layer: q (nope + rope), the heads' keys and values, the one
    shared rope key, the context."""
    heads = model["num_attention_heads"]
    nope, rope, v = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    a_token = heads * (nope + rope) + heads * (nope + v) + rope + heads * v
    return float(2 * model["layers"] * tokens * a_token)


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

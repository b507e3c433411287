"""What one expert-parallel rank of a shortcut-connected MoE trunk (the
LongCat-Flash family's double layer) costs, from shapes and token counts:
what the algorithm needs, never what a kernel happens to execute
(padding, a buffer's empty rows, weights read again for a second group of
rows are not work).  Imports nothing of the program."""

from __future__ import annotations

PARAM_BYTES = {"bfloat16": 2, "float32": 4}


def _attention_params(model: dict) -> int:
    """One MLA sublayer's five matrices: q down and up, kv down (latent +
    rope key) and up, output."""
    d, heads = model["hidden_size"], model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return (
        d * model["q_lora_rank"] + model["q_lora_rank"] * heads * qk
        + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
        + model["kv_lora_rank"] * heads * (model["qk_nope_head_dim"] + model["v_head_dim"])
        + heads * model["v_head_dim"] * d
    )


def _ffn_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["ffn_hidden_size"]


def _expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["expert_ffn_hidden_size"]


def router_outputs(model: dict) -> int:
    """The routed experts and the zero-compute ones."""
    return model["n_routed_experts"] + model["zero_expert_num"]


def held_pairs_per_token(model: dict) -> float:
    """Expected (token, held expert) pairs a token: its experts per token
    times the share of the router's outputs held here (12 x 16 / 768 =
    0.25: a third of the picks are zero-compute experts, none held)."""
    return model["moe_topk"] * model["experts_held"] / router_outputs(model)


def flops(model: dict, tokens: int) -> float:
    """Forward FLOPs of one document of `tokens` real tokens on this rank:
    in each double layer both attention sublayers' five matrices, both
    dense FFNs, the router and the held experts' expected pairs; causal
    attention within the document.  Norms, softmax, RoPE, top-k, the
    zero-compute experts' weighted adds, pooling and the embedding gather
    are left out."""
    d = model["hidden_size"]
    double = (
        2 * _attention_params(model) + 2 * _ffn_params(model)
        + d * router_outputs(model) + held_pairs_per_token(model) * _expert_params(model)
    )
    return float(tokens) * 2 * model["layers"] * double + mla_attention_flops(model, tokens)


def layer_params(model: dict) -> int:
    """Parameters of the double layers as held here: two attention
    sublayers, two dense FFNs, the router (its selection bias, float32, is
    counted in `resident_param_bytes` alone), the held experts, and the
    norms: two input and two post-attention ones and each attention's two
    latent norms."""
    d = model["hidden_size"]
    norms = 4 * d + 2 * (model["q_lora_rank"] + model["kv_lora_rank"])
    double = (
        2 * _attention_params(model) + 2 * _ffn_params(model) + d * router_outputs(model)
        + model["experts_held"] * _expert_params(model) + norms
    )
    return model["layers"] * double


def weight_bytes(model: dict) -> float:
    """Bytes of the layer weights one run of the program has to read once,
    in the type they are resident and computed in.  The embedding is
    gathered, not streamed, and is left out."""
    return float(PARAM_BYTES[model["param_dtype"]] * layer_params(model))


def activation_bytes(model: dict, tokens: int) -> float:
    """The least a document's activations move through HBM: its hidden
    states written and read once per sublayer (four a double layer), in
    bf16."""
    return float(2 * 2 * tokens * model["hidden_size"] * 4 * model["layers"])


def resident_param_bytes(model: dict) -> int:
    """Bytes of the parameters as the program keeps them on the chip: the
    held rows of the embedding, the final norm and the double layers, in
    `param_dtype`, and beta in float32."""
    d = model["hidden_size"]
    return PARAM_BYTES[model["param_dtype"]] * (
        model["vocab_held"] * d + d + layer_params(model)
    ) + 4 * model["layers"] * router_outputs(model)


def embed_dim(model: dict) -> int:
    """Width of the vectors the store holds."""
    return model["hidden_size"]


def dry_cut(model: dict) -> dict:
    """The CPU rehearsal's sizes: one double layer.  Widths, the router,
    its experts a token and the experts held stay as published."""
    return dict(model, layers=1)


# -- the kernels' own work (chipbench/readers/op_roofline.py) -------------------


def mla_attention_flops(model: dict, tokens: int) -> float:
    """Scores and mix of one document in both attention sublayers of every
    double layer, causal: a token meets half the document on average."""
    heads = model["num_attention_heads"]
    width = model["qk_nope_head_dim"] + model["qk_rope_head_dim"] + model["v_head_dim"]
    return float(2 * model["layers"] * heads * width * tokens * tokens)


def mla_attention_bytes(model: dict, tokens: int) -> float:
    """What the attention of one document reads and writes once, bf16, in
    both sublayers of every double layer: q (nope + rope), the heads' keys
    and values, the one shared rope key, the context."""
    heads = model["num_attention_heads"]
    nope, rope, v = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    a_token = heads * (nope + rope) + heads * (nope + v) + rope + heads * v
    return float(2 * 2 * model["layers"] * tokens * a_token)


def expert_matmul_flops(model: dict, pairs: int) -> float:
    """The three matrices of an expert for `pairs` (token, held expert)
    pairs actually routed here."""
    return float(2 * pairs * _expert_params(model))


def expert_matmul_bytes(model: dict, pairs: int, runs: int) -> float:
    """The held experts' weights of every double layer once a run of the
    program, and a pair's row read and its result written, bf16."""
    weights = PARAM_BYTES[model["param_dtype"]] * (
        model["layers"] * model["experts_held"] * _expert_params(model)
    )
    return float(runs * weights + 2 * 2 * pairs * model["hidden_size"])

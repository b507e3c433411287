"""What a BERT-style encoder costs, from shapes and token counts: what
the algorithm needs, never what a kernel happens to execute (padding,
recomputation and layout copies are not work).  A copy of the sound parts
of pathway_tpu/internals/costmodel.py; imports nothing of the program."""

from __future__ import annotations


def flops(model: dict, tokens: int) -> float:
    """Forward FLOPs of one document of `tokens` real tokens: per layer
    and token 2*(4*h*h) for the q, k, v and output projections, 2*(2*h*ffn)
    for the MLP and 2*2*tokens*h for attention scores and mix (a document
    attends within itself).  Norms, softmax, GELU, pooling and the
    embedding gather are left out (under 2% at these widths)."""
    h, ffn, layers = model["hidden"], model["mlp_dim"], model["layers"]
    per_token = layers * (2 * (4 * h * h + 2 * h * ffn) + 4 * tokens * h)
    return float(tokens) * per_token


def layer_params(model: dict) -> int:
    """Parameters of the encoder's layers: the matrices and biases of
    attention and MLP and two LayerNorms a layer."""
    h, ffn = model["hidden"], model["mlp_dim"]
    return model["layers"] * (4 * h * h + 2 * h * ffn + 9 * h + ffn)


def weight_bytes(model: dict) -> float:
    """Bytes of the layer weights one encoder program has to read once, in
    the type it computes in (bf16): the matrices and biases of every layer.
    The embedding table is gathered, not streamed, and is left out."""
    return float(2 * layer_params(model))


def activation_bytes(model: dict, tokens: int) -> float:
    """The least a document's activations move through HBM: its hidden
    states written and read once per layer, in bf16."""
    return float(2 * 2 * tokens * model["hidden"] * model["layers"])


def resident_param_bytes(model: dict) -> int:
    """Bytes of the parameters as the program keeps them on the chip:
    float32, whatever type it computes in.  Token and position embeddings,
    the final LayerNorm and the layers."""
    h = model["hidden"]
    params = (
        model["vocab_size"] * h + model["max_position_embeddings"] * h + 2 * h
        + layer_params(model)
    )
    return 4 * params


def embed_dim(model: dict) -> int:
    """Width of the vectors the store holds."""
    return model["hidden"]


def dry_cut(model: dict) -> dict:
    """The CPU rehearsal's sizes: two layers.  Widths stay as published."""
    return dict(model, layers=2)

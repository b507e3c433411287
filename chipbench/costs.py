"""The yardstick's arithmetic: chip peaks and the work an algorithm needs.

A copy of the sound parts of pathway_tpu/internals/costmodel.py, kept here
so that a later PR can change the program and not the yardstick.  Work is
counted from shapes and token counts — what the algorithm needs, never what
a kernel happens to execute: padding, recomputation and layout copies are
not work.

Peaks: Google Cloud TPU documentation, "System architecture" page of each
TPU version (peak bf16 compute, HBM bandwidth and capacity per chip),
keyed by the `device_kind` JAX reports.  A device that is not in the table
is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    # device_kind: bf16 FLOP/s, HBM bytes/s, HBM bytes
    "TPU v5 lite": {  # cloud.google.com/tpu/docs/v5e
        "flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
    "TPU v4": {  # cloud.google.com/tpu/docs/v4
        "flops": 275e12,
        "hbm_bytes_per_s": 1228e9,
        "hbm_bytes": 32e9,
    },
    "TPU v5": {  # cloud.google.com/tpu/docs/v5p
        "flops": 459e12,
        "hbm_bytes_per_s": 2765e9,
        "hbm_bytes": 95e9,
    },
    "TPU v6 lite": {  # cloud.google.com/tpu/docs/v6e
        "flops": 918e12,
        "hbm_bytes_per_s": 1640e9,
        "hbm_bytes": 32e9,
    },
}


class UnknownDevice(LookupError):
    pass


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise UnknownDevice(
            f"device_kind {device_kind!r} has no entry in chipbench/costs.py "
            f"PEAKS (known: {sorted(PEAKS)}); add its published peaks with "
            "their source before reporting a roofline or MFU on it"
        )
    return PEAKS[device_kind]


def encoder_flops(model: dict, tokens: int) -> float:
    """Forward FLOPs of one document of `tokens` real tokens: per layer
    and token 2*(4*h*h) for the q, k, v and output projections, 2*(2*h*ffn)
    for the MLP and 2*2*tokens*h for attention scores and mix (a document
    attends within itself).  Norms, softmax, GELU, pooling and the
    embedding gather are left out (under 2% at these widths)."""
    h, ffn, layers = model["hidden"], model["mlp_dim"], model["layers"]
    per_token = layers * (2 * (4 * h * h + 2 * h * ffn) + 4 * tokens * h)
    return float(tokens) * per_token


def encoder_layer_params(model: dict) -> int:
    """Parameters of the encoder's layers: the matrices and biases of
    attention and MLP and two LayerNorms a layer."""
    h, ffn = model["hidden"], model["mlp_dim"]
    return model["layers"] * (4 * h * h + 2 * h * ffn + 9 * h + ffn)


def encoder_weight_bytes(model: dict, bytes_per_param: int = 2) -> float:
    """Bytes of the layer weights one encoder program has to read once, in
    the type it computes in (bf16): the matrices and biases of every layer.
    The embedding table is gathered, not streamed, and is left out."""
    return float(bytes_per_param * encoder_layer_params(model))


def encoder_activation_bytes(model: dict, tokens: int) -> float:
    """The least a document's activations move through HBM: its hidden
    states written and read once per layer, in bf16."""
    return float(2 * 2 * tokens * model["hidden"] * model["layers"])


def roofline_seconds(flops: float, nbytes: float, device_kind: str) -> dict:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s, and which of the two binds."""
    p = peaks(device_kind)
    compute_s = flops / p["flops"]
    memory_s = nbytes / p["hbm_bytes_per_s"]
    return {
        "seconds": max(compute_s, memory_s),
        "bound": "compute" if compute_s >= memory_s else "memory",
    }

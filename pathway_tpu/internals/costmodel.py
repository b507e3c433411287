"""Analytic FLOPs/bytes cost model — the single source of truth.

Every MFU number the program prints — the live `pathway_device_mfu_pct`
gauge, the cost ledger's shares, the capacity analysis — derives from
the formulas here.  (The benchmark's own cost functions live with it,
under ``chipbench/``, and import nothing from this module.)

Contract (documented in ARCHITECTURE.md "Device utilization"):

  * USEFUL FLOPs count real mask tokens only.  Bucketing and slab
    packing pad, but padding is not useful work; MFU judged on padded
    tokens would reward waste.
  * encoder per-token forward FLOPs at sequence length ``seq``::

        layers * (2 * (4*h*h + 2*h*ffn)   # q,k,v,o projections + MLP
                  + 2 * 2 * seq * h)      # attention scores + mix

    (matmul FLOPs = 2 * MACs; norms/softmax/gathers are <2% at MiniLM
    shapes and are deliberately excluded, matching the bench).
  * decoder FLOPs/token ~= 2 * n_params — the standard inference
    roofline count; attention against a short KV cache adds <2%.
  * peak FLOP/s, HBM bytes/s and HBM capacity come from tables of
    published per-chip numbers keyed on ``jax.devices()[0].device_kind``.
    The CPU backend has no peak: it returns 0.0 and every consumer
    reports "peak unknown -> MFU undefined" as None, never divides by it.
    An accelerator that is not in the tables raises UnknownDeviceError —
    a number printed under the name MFU, roofline or efficiency must
    never rest on a guessed peak.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional

# MiniLM-L6 geometry — the repo's ingest encoder (models/minilm.py).
MINILM_HIDDEN = 384
MINILM_MLP_DIM = 1536
MINILM_LAYERS = 6

# Published per-chip peaks, keyed on the exact `device_kind` the local
# backend reports (jax._src.test_util.is_device_tpu spells the mapping:
# v5e is "TPU v5 lite", v5p is "TPU v5", v6e is "TPU v6 lite").
# Source of every row: Google Cloud TPU documentation, "System
# architecture" page of that TPU version (peak compute per chip, bf16;
# HBM capacity and bandwidth per chip).
DEVICE_PEAK_BF16_FLOPS: Dict[str, float] = {
    "TPU v4": 275e12,  # cloud.google.com/tpu/docs/v4
    "TPU v5 lite": 197e12,  # cloud.google.com/tpu/docs/v5e
    "TPU v5": 459e12,  # cloud.google.com/tpu/docs/v5p
    "TPU v6 lite": 918e12,  # cloud.google.com/tpu/docs/v6e
}

DEVICE_HBM_BYTES_PER_SEC: Dict[str, float] = {
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5": 2765e9,
    "TPU v6 lite": 1640e9,
}

# Consumed by the memory tracker's headroom/forecast math and the PWT6xx
# capacity-planning pass (both through memtrack.hbm_capacity_bytes, the
# single resolution order).
DEVICE_HBM_BYTES: Dict[str, float] = {
    "TPU v4": 32e9,
    "TPU v5 lite": 16e9,
    "TPU v5": 95e9,
    "TPU v6 lite": 32e9,
}

# kinds that have no published peak by design: the CPU backend the tests
# run on, and a process with no usable jax backend at all
_NO_PEAK_KINDS = ("cpu", "unknown")


class UnknownDeviceError(LookupError):
    """The attached accelerator's `device_kind` is not in the peak
    tables, so no MFU / roofline / efficiency number can be derived."""


_lock = threading.Lock()
_cached_kind: Optional[str] = None


def device_kind() -> str:
    """`device_kind` of device 0, cached; "unknown" when jax or the
    backend is unavailable."""
    global _cached_kind
    with _lock:
        if _cached_kind is None:
            try:
                import jax

                _cached_kind = str(jax.devices()[0].device_kind)
            except Exception:  # noqa: BLE001 — no backend is a valid state
                _cached_kind = "unknown"
        return _cached_kind


def _lookup(table: Dict[str, float], kind: Optional[str]) -> float:
    kind = device_kind() if kind is None else kind
    if kind in table:
        return table[kind]
    if kind.lower() in _NO_PEAK_KINDS:
        return 0.0
    raise UnknownDeviceError(
        f"device_kind {kind!r} has no entry in the costmodel peak tables "
        f"(known: {sorted(table)}); add its published peaks with their "
        "source before reporting MFU, roofline or efficiency on it"
    )


def device_peak_flops(kind: Optional[str] = None) -> float:
    """Peak bf16 FLOP/s of `kind` (default: the attached chip).  0.0 on
    the CPU backend — consumers report MFU as None, not divide;
    UnknownDeviceError for an accelerator missing from the table."""
    return _lookup(DEVICE_PEAK_BF16_FLOPS, kind)


def device_capacity_known(kind: Optional[str] = None) -> bool:
    """Whether the chip table has a peak-FLOPs entry for `kind` (default:
    the attached chip).  False on CPU CI and unrecognized devices — the
    analyzer's PWT802 surfaces the gap as a finding."""
    kind = device_kind() if kind is None else kind
    return kind in DEVICE_PEAK_BF16_FLOPS


def device_hbm_bytes_per_sec(kind: Optional[str] = None) -> float:
    """HBM bytes/s of `kind` (default: the attached chip); same CPU /
    unknown-accelerator contract as `device_peak_flops`."""
    return _lookup(DEVICE_HBM_BYTES_PER_SEC, kind)


def device_hbm_bytes(kind: Optional[str] = None) -> float:
    """HBM capacity in bytes of `kind` (default: the attached chip); 0.0
    on the CPU backend — consumers report headroom as None."""
    return _lookup(DEVICE_HBM_BYTES, kind)


def encoder_param_count(
    *,
    vocab_size: int,
    hidden: int,
    layers: int,
    mlp_dim: int,
    max_len: int,
) -> int:
    """Exact parameter count of models/transformer.init_params for this
    geometry: embed (v,h) + pos_embed (max_len,h) + final LN 2h, and per
    layer two LNs (4h), qkv (3h^2)+3h, out (h^2)+h, up (h*m)+m, down
    (m*h)+h.  Kept in lockstep with init_params — the PWT699 parity gate
    compares this prediction against live leaf sizes."""
    h, m = hidden, mlp_dim
    per_layer = 4 * h * h + 2 * h * m + 9 * h + m
    return vocab_size * h + max_len * h + 2 * h + layers * per_layer


def encoder_flops_per_token(
    seq: float,
    *,
    hidden: int = MINILM_HIDDEN,
    mlp_dim: int = MINILM_MLP_DIM,
    layers: int = MINILM_LAYERS,
) -> float:
    """Forward FLOPs for ONE token of an encoder layer stack at sequence
    length `seq`: per layer, 2*(4*h*h) for the q/k/v/o projections,
    2*(2*h*ffn) for the MLP, and 2*2*seq*h for attention scores + mix."""
    h = hidden
    return layers * (2 * (4 * h * h + 2 * h * mlp_dim) + 2 * 2 * seq * h)


def encoder_flops_per_doc(
    tokens_per_doc: float,
    *,
    hidden: int = MINILM_HIDDEN,
    mlp_dim: int = MINILM_MLP_DIM,
    layers: int = MINILM_LAYERS,
) -> float:
    """Useful forward FLOPs for one document of `tokens_per_doc` REAL
    tokens (seq = tokens_per_doc: a doc attends within itself)."""
    return (
        encoder_flops_per_token(
            tokens_per_doc, hidden=hidden, mlp_dim=mlp_dim, layers=layers
        )
        * tokens_per_doc
    )


def encoder_useful_flops(
    real_tokens: int,
    rows: int,
    *,
    hidden: int = MINILM_HIDDEN,
    mlp_dim: int = MINILM_MLP_DIM,
    layers: int = MINILM_LAYERS,
) -> float:
    """Useful FLOPs of a dispatched batch: `real_tokens` mask tokens
    over `rows` documents, attention charged at the batch's average
    real sequence length (padding excluded — see module contract)."""
    if real_tokens <= 0:
        return 0.0
    seq = real_tokens / max(rows, 1)
    return real_tokens * encoder_flops_per_token(
        seq, hidden=hidden, mlp_dim=mlp_dim, layers=layers
    )


def encoder_flops_for_config(config: Any, real_tokens: int, rows: int) -> float:
    """`encoder_useful_flops` with the geometry read off a
    TransformerConfig (hidden / mlp_dim / layers attributes).  A
    configuration whose FLOPs a token are not a dense encoder's says them
    itself (`active_flops_per_token(seq)`, models/moe_mla.py: the experts
    held and selected, not every parameter)."""
    per_token = getattr(config, "active_flops_per_token", None)
    if per_token is not None:
        if real_tokens <= 0:
            return 0.0
        return real_tokens * float(per_token(real_tokens / max(rows, 1)))
    return encoder_useful_flops(
        real_tokens,
        rows,
        hidden=int(getattr(config, "hidden", MINILM_HIDDEN)),
        mlp_dim=int(getattr(config, "mlp_dim", MINILM_MLP_DIM)),
        layers=int(getattr(config, "layers", MINILM_LAYERS)),
    )


def decoder_flops_per_token(n_params: int) -> float:
    """Decoder FLOPs per generated/prefilled token ~= 2 * n_params
    (matmul MACs once through the weights)."""
    return 2.0 * float(n_params)


def mfu_pct(flops_per_sec: float, peak: Optional[float] = None) -> Optional[float]:
    """Achieved model-FLOPs utilization in percent, or None when the
    device has no peak (the CPU backend)."""
    p = device_peak_flops() if peak is None else peak
    if not p:
        return None
    return 100.0 * flops_per_sec / p


def _reset_cache_for_tests() -> None:
    global _cached_kind
    with _lock:
        _cached_kind = None

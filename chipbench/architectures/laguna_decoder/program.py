"""The program's side of a Laguna-family trunk (window layers beside
global ones, query heads and rotary width by layer kind, a head-wise
output gate, top-8 of 256 sigmoid-routed experts beside a shared one)
served as the store's embedder: the one file of this architecture that
imports pathway_tpu.  The trunk is `models/moe_hybrid.py`'s, configured by
kind."""

from __future__ import annotations

from pathway_tpu.internals import tracing
from pathway_tpu.models import minilm
from pathway_tpu.models.moe_hybrid import MoeHybridConfig
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

from chipbench import reference

# what the configuration's keys have to read for the program to be the
# model (the reference is written for the same)
READINGS = {
    "hidden_act": "silu", "pooling": "mean", "attention_bias": False, "gating": True,
    "gate_form": "head-wise", "scoring_func": "sigmoid",
    "moe_apply_router_weight_on_input": False,
}


def kinds(model: dict) -> list:
    """(window, dense) of each layer held, from `layer_types` and
    `mlp_layer_types`."""
    n = model["layers"]
    known = ({"full_attention", "sliding_attention"}, {"dense", "sparse"})
    window = model["layer_types"][:n]
    dense = model["mlp_layer_types"][:n]
    if not set(window) <= known[0] or not set(dense) <= known[1]:
        raise ValueError(f"layer kinds {sorted(set(window) | set(dense))}: unknown")
    return [(w == "sliding_attention", d == "dense") for w, d in zip(window, dense)]


def heads_by_kind(model: dict) -> dict:
    """Query heads of each kind, from `num_attention_heads_per_layer`: one
    count a kind, or the program cannot run it."""
    out: dict = {}
    for (window, _), heads in zip(kinds(model), model["num_attention_heads_per_layer"]):
        if out.setdefault(window, heads) != heads:
            raise ValueError("num_attention_heads_per_layer: one count a layer kind only")
    return out


def config_of(model: dict, store: dict) -> MoeHybridConfig:
    """The trunk's configuration of a configuration file's `model` group."""
    for key, reading in READINGS.items():
        if model[key] != reading:
            raise ValueError(f"{key} {model[key]!r}: the program runs {reading!r} only")
    layer_kinds = kinds(model)
    dense = [d for _, d in layer_kinds]
    first_k_dense = dense.index(False) if False in dense else len(dense)
    if any(dense[first_k_dense:]):
        raise ValueError("mlp_layer_types: the program runs leading dense layers only")
    heads = heads_by_kind(model)
    rope = model["rope_parameters"]
    full, swa = rope["full_attention"], rope["sliding_attention"]
    if swa["rope_type"] != "default" or full["rope_type"] != "yarn":
        raise ValueError("rope_parameters: the program runs yarn (full) beside default (sliding)")
    hd = model["head_dim"]
    max_len = min(store["max_len"], model["max_len"])
    return MoeHybridConfig(
        vocab_size=model["vocab_held"], hidden=model["hidden_size"],
        layers=model["layers"], layer_pattern=tuple(w for w, _ in layer_kinds),
        first_k_dense=first_k_dense,
        heads=heads.get(False, model["num_attention_heads"]),
        heads_window=heads.get(True), kv_heads_global=model["num_key_value_heads"],
        kv_heads_window=model["num_key_value_heads"], head_dim=hd,
        rotary_dim=int(hd * full["partial_rotary_factor"]),
        rotary_dim_window=int(hd * swa["partial_rotary_factor"]), v_head_dim=hd,
        window=model["sliding_window"],
        rope_theta_global=float(full["rope_theta"]),
        rope_theta_window=float(swa["rope_theta"]),
        yarn_global=(
            float(full["factor"]), int(full["original_max_position_embeddings"]),
            float(full["beta_fast"]), float(full["beta_slow"]),
            float(full["attention_factor"]),
        ),
        sink_global=False, sink_window=False, head_gate=True,
        value_scale=1.0, dense_mlp_dim=model["intermediate_size"],
        expert_mlp_dim=model["moe_intermediate_size"],
        shared_mlp_dim=model["shared_expert_intermediate_size"],
        n_routed_experts=model["num_experts"],
        experts_per_token=model["num_experts_per_tok"],
        routed_scaling_factor=float(model["moe_routed_scaling_factor"]),
        selection_bias=False, experts_held=model["experts_held"],
        expert_offset=model["expert_offset"], depth=model["num_hidden_layers"],
        pp_size=model["pp_size"], norm_eps=model["rms_norm_eps"], max_len=max_len,
        dtype=model["dtype"], param_dtype=model["param_dtype"],
    )


def embedder(model: dict, store: dict, seed: int):
    """What a deployment hands to `BruteForceKnnFactory(embedder=...)`."""
    config = config_of(model, store)
    return SentenceTransformerEmbedder(
        model["name"], config=config, max_len=config.max_len,
        seed=reference.weight_seed(seed),
    )


def release() -> None:
    """Drops what the program keeps of the model beyond the server's life,
    so that the reference has the chip's memory.  A run in which a routed
    (token, held expert) pair did not fit the program's buffer computed
    something else than the model: it ends here, without a result."""
    for encoder in minilm._model_cache.values():
        encoder.lm.count_stats()  # every dispatch's, waiting for the device
        # the stopped engine still holds the embedder (runner.last_engine):
        # the 7.3 GB of parameters go here, not with the cache's entry
        encoder.lm.params = None
    dropped = tracing.spans_status()["totals"].get("moe.overflow_pairs", {}).get("count", 0)
    minilm._model_cache.clear()
    if dropped:
        raise RuntimeError(
            f"moe.overflow_pairs is {dropped}: routed pairs on held experts "
            "went uncomputed"
        )

"""The program's side of a hybrid-attention MoE trunk served as the
store's embedder: the one file of this architecture that imports
pathway_tpu."""

from __future__ import annotations

from pathway_tpu.internals import tracing
from pathway_tpu.models import minilm
from pathway_tpu.models.moe_hybrid import MoeHybridConfig
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

from chipbench import reference


def embedder(model: dict, store: dict, seed: int):
    """What a deployment hands to `BruteForceKnnFactory(embedder=...)`."""
    for key, reading in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                         ("n_group", 1), ("topk_group", 1), ("n_shared_experts", None),
                         ("routed_scaling_factor", None), ("norm_topk_prob", True),
                         ("hidden_act", "silu"), ("pooling", "mean"),
                         ("attention_projection_layout", "fused_qkv")):
        if model[key] != reading:
            raise ValueError(f"{key} {model[key]!r}: the program runs {reading!r} only")
    layers = model["layers"]
    dense = [not e for e in model["moe_layer_freq"][:layers]]
    first_k_dense = dense.index(False) if False in dense else layers
    if any(dense[first_k_dense:]):
        raise ValueError("moe_layer_freq: the program runs leading dense layers only")
    max_len = min(store["max_len"], model["max_len"])
    config = MoeHybridConfig(
        vocab_size=model["vocab_held"], hidden=model["hidden_size"], layers=layers,
        layer_pattern=tuple(model["hybrid_layer_pattern"]), first_k_dense=first_k_dense,
        heads=model["num_attention_heads"],
        kv_heads_global=model["num_key_value_heads"],
        kv_heads_window=model["swa_num_key_value_heads"],
        head_dim=model["head_dim"], rotary_dim=model["rotary_dim"],
        v_head_dim=model["v_head_dim"], window=model["sliding_window"],
        rope_theta_global=float(model["rope_theta"]),
        rope_theta_window=float(model["swa_rope_theta"]),
        sink_global=model["add_full_attention_sink_bias"],
        sink_window=model["add_swa_attention_sink_bias"],
        value_scale=model["attention_value_scale"],
        dense_mlp_dim=model["intermediate_size"],
        expert_mlp_dim=model["moe_intermediate_size"],
        n_routed_experts=model["n_routed_experts"],
        experts_per_token=model["num_experts_per_tok"],
        experts_held=model["experts_held"], expert_offset=model["expert_offset"],
        norm_eps=model["layernorm_epsilon"], max_len=max_len,
        dtype=model["dtype"], param_dtype=model["param_dtype"],
    )
    return SentenceTransformerEmbedder(
        model["name"], config=config, max_len=max_len,
        seed=reference.weight_seed(seed),
    )


def release() -> None:
    """Drops what the program keeps of the model beyond the server's life,
    so that the reference has the chip's memory.  A run in which a selected
    (token, held expert) pair did not fit the program's buffer computed
    something else than the model: it ends here, without a result."""
    for encoder in minilm._model_cache.values():
        encoder.lm.count_stats()  # every dispatch's, waiting for the device
        # the stopped engine still holds the embedder (runner.last_engine):
        # the 6.7 GB of parameters go here, not with the cache's entry
        encoder.lm.params = None
    dropped = tracing.spans_status()["totals"].get("moe.overflow_pairs", {}).get("count", 0)
    minilm._model_cache.clear()
    if dropped:
        raise RuntimeError(
            f"moe.overflow_pairs is {dropped}: selected pairs on held experts "
            "went uncomputed"
        )

"""The program's side of a latent-attention + shared-expert MoE trunk
served as the store's embedder: the one file of this architecture that
imports pathway_tpu."""

from __future__ import annotations

from pathway_tpu.internals import tracing
from pathway_tpu.models import minilm
from pathway_tpu.models.moe_mla import MoeMlaConfig
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

from chipbench import reference


def embedder(model: dict, store: dict, seed: int):
    """What a deployment hands to `BruteForceKnnFactory(embedder=...)`."""
    for key, reading in (("scoring_func", "sigmoid"), ("topk_method", "none"),
                         ("norm_topk_prob", True), ("hidden_act", "silu"),
                         ("pooling", "mean")):
        if model[key] != reading:
            raise ValueError(f"{key} {model[key]!r}: the program runs {reading!r} only")
    scaling = model["rope_scaling"]
    config = MoeMlaConfig(
        vocab_size=model["vocab_held"], hidden=model["hidden_size"],
        layers=model["layers"], first_k_dense=model["first_k_dense_replace"],
        heads=model["num_attention_heads"], q_lora_rank=model["q_lora_rank"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"], v_head_dim=model["v_head_dim"],
        dense_mlp_dim=model["intermediate_size"],
        expert_mlp_dim=model["moe_intermediate_size"],
        n_routed_experts=model["n_routed_experts"],
        experts_per_token=model["num_experts_per_tok"],
        n_shared_experts=model["n_shared_experts"],
        routed_scaling_factor=model["routed_scaling_factor"],
        experts_held=model["experts_held"], expert_offset=model["expert_offset"],
        rope_theta=float(model["rope_theta"]), rope_factor=float(scaling["factor"]),
        rope_original_max_len=scaling["original_max_position_embeddings"],
        rope_beta_fast=float(scaling["beta_fast"]),
        rope_beta_slow=float(scaling["beta_slow"]),
        rope_mscale_all_dim=float(scaling["mscale_all_dim"]),
        norm_eps=model["rms_norm_eps"], max_len=store["max_len"],
        dtype=model["dtype"], param_dtype=model["param_dtype"],
    )
    return SentenceTransformerEmbedder(
        model["name"], config=config, max_len=store["max_len"],
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
        # the 8 GB of parameters go here, not with the cache's entry
        encoder.lm.params = None
    dropped = tracing.spans_status()["totals"].get("moe.overflow_pairs", {}).get("count", 0)
    minilm._model_cache.clear()
    if dropped:
        raise RuntimeError(
            f"moe.overflow_pairs is {dropped}: selected pairs on held experts "
            "went uncomputed"
        )

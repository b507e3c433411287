"""The program's side of a shortcut-connected MoE trunk (the LongCat-Flash
family's double layer) served as the store's embedder: the one file of
this architecture that imports pathway_tpu."""

from __future__ import annotations

from pathway_tpu.internals import tracing
from pathway_tpu.models import longcat, minilm
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

from chipbench import reference

# what the configuration's keys have to read for the program to be the
# model (the reference is written for the same), and the `init` constant
# that has to be one recipe with the program's for the reference (which
# reads the file) to make the program's weights
READINGS = {
    "attention_method": "MLA", "zero_expert_type": "identity", "norm_topk_prob": False,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "router_bias": False, "attention_bias": False, "hidden_act": "silu",
    "pooling": "mean", "bias_std": longcat.BIAS_STD,
}


def config_of(model: dict, store: dict) -> longcat.LongcatConfig:
    """The trunk's configuration of a configuration file's `model` group."""
    for key, reading in READINGS.items():
        if model[key] != reading:
            raise ValueError(f"{key} {model[key]!r}: the program runs {reading!r} only")
    if "rope_scaling" in model:
        raise ValueError("rope_scaling: the program runs plain RoPE only")
    return longcat.LongcatConfig(
        vocab_size=model["vocab_held"], hidden=model["hidden_size"],
        layers=model["layers"], heads=model["num_attention_heads"],
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"], v_head_dim=model["v_head_dim"],
        ffn_dim=model["ffn_hidden_size"], expert_mlp_dim=model["expert_ffn_hidden_size"],
        n_routed_experts=model["n_routed_experts"], zero_experts=model["zero_expert_num"],
        experts_per_token=model["moe_topk"],
        routed_scaling_factor=float(model["routed_scaling_factor"]),
        experts_held=model["experts_held"], expert_offset=model["expert_offset"],
        rope_theta=float(model["rope_theta"]), norm_eps=model["rms_norm_eps"],
        max_len=store["max_len"], dtype=model["dtype"], param_dtype=model["param_dtype"],
    )


def embedder(model: dict, store: dict, seed: int):
    """What a deployment hands to `BruteForceKnnFactory(embedder=...)`."""
    return SentenceTransformerEmbedder(
        model["name"], config=config_of(model, store), max_len=store["max_len"],
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
        # the 10 GB of parameters go here, not with the cache's entry
        encoder.lm.params = None
    dropped = tracing.spans_status()["totals"].get("moe.overflow_pairs", {}).get("count", 0)
    minilm._model_cache.clear()
    if dropped:
        raise RuntimeError(
            f"moe.overflow_pairs is {dropped}: selected pairs on held experts "
            "went uncomputed"
        )

"""The program's side of a compressed-convolutional-attention MoE trunk
served as the store's embedder: the one file of this architecture that
imports pathway_tpu."""

from __future__ import annotations

from pathway_tpu.internals import tracing
from pathway_tpu.models import minilm, zaya
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

from chipbench import reference

# the configuration's `init` constants and the program's own, which have
# to be one recipe for the reference (which reads the file) to make the
# program's weights
_INIT = {
    "tau_mean": zaya.TAU_MEAN, "tau_std": zaya.TAU_STD, "alpha_std": zaya.ALPHA_STD,
    "gamma_mean": zaya.GAMMA_MEAN, "gamma_std": zaya.GAMMA_STD,
    "beta_std": zaya.BETA_STD, "conv_bias_std": zaya.CONV_BIAS_STD,
}


def embedder(model: dict, store: dict, seed: int):
    """What a deployment hands to `BruteForceKnnFactory(embedder=...)`."""
    for key, reading in (("hidden_act", "silu"), ("pooling", "mean"),
                         ("sliding_window", None), ("attention_bias", False),
                         *_INIT.items()):
        if model[key] != reading:
            raise ValueError(f"{key} {model[key]!r}: the program runs {reading!r} only")
    if set(model["layer_types"][: model["layers"]]) != {"hybrid"}:
        raise ValueError("layer_types: the program runs 'hybrid' layers only")
    max_len = min(store["max_len"], model["max_len"])
    config = zaya.ZayaConfig(
        vocab_size=model["vocab_held"], hidden=model["hidden_size"],
        layers=model["layers"], depth=model["num_hidden_layers"],
        heads=model["num_attention_heads"],
        kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        rotary_dim=model["rotary_dim"], conv_taps0=model["cca_time0"],
        conv_taps1=model["cca_time1"],
        rope_theta=float(model["rope_parameters"]["hybrid"]["rope_theta"]),
        expert_mlp_dim=model["moe_intermediate_size"],
        n_routed_experts=model["num_experts"],
        experts_per_token=model["num_experts_per_tok"],
        router_hidden=model["router_hidden_size"],
        experts_held=model["experts_held"], expert_offset=model["expert_offset"],
        norm_eps=model["rms_norm_eps"], max_len=max_len,
        dtype=model["dtype"], param_dtype=model["param_dtype"],
    )
    return SentenceTransformerEmbedder(
        model["name"], config=config, max_len=max_len,
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
        # the 9.4 GB of parameters go here, not with the cache's entry
        encoder.lm.params = None
    dropped = tracing.spans_status()["totals"].get("moe.overflow_pairs", {}).get("count", 0)
    minilm._model_cache.clear()
    if dropped:
        raise RuntimeError(
            f"moe.overflow_pairs is {dropped}: routed pairs on held experts "
            "went uncomputed"
        )

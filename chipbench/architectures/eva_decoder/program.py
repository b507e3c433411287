"""The program's side of a byte-level trunk with chunked linear attention
served as the store's embedder: the one file of this architecture that
imports pathway_tpu."""

from __future__ import annotations

from pathway_tpu.models import minilm
from pathway_tpu.models.eva import EvaConfig
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

from chipbench import reference


def embedder(model: dict, store: dict, seed: int):
    """What a deployment hands to `BruteForceKnnFactory(embedder=...)`."""
    for key, reading in (("attention_class", "eva"), ("hidden_act", "silu"),
                         ("pooling", "mean"), ("norm_add_unit_offset", True),
                         ("fp32_skip_add", True)):
        if model[key] != reading:
            raise ValueError(f"{key} {model[key]!r}: the program runs {reading!r} only")
    max_len = min(store["max_len"], model["max_len"])
    config = EvaConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden_size"],
        layers=model["layers"], heads=model["num_attention_heads"],
        mlp_dim=model["intermediate_size"], window_size=model["window_size"],
        chunk_size=model["chunk_size"], rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"], max_len=max_len,
        dtype=model["dtype"], param_dtype=model["param_dtype"],
    )
    return SentenceTransformerEmbedder(
        model["name"], config=config, max_len=max_len,
        seed=reference.weight_seed(seed),
    )


def release() -> None:
    """Drops what the program keeps of the model beyond the server's life,
    so that the reference has the chip's memory."""
    for encoder in minilm._model_cache.values():
        # the stopped engine still holds the embedder (runner.last_engine):
        # the 6.5 GB of parameters go here, not with the cache's entry
        encoder.lm.params = None
    minilm._model_cache.clear()

"""The program's side of a BERT-style encoder: the one file of this
architecture that imports pathway_tpu."""

from __future__ import annotations

from pathway_tpu.models import minilm
from pathway_tpu.models.transformer import TransformerConfig
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

from chipbench import reference


def embedder(model: dict, store: dict, seed: int):
    """What a deployment hands to `BruteForceKnnFactory(embedder=...)`."""
    tconfig = TransformerConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden"],
        layers=model["layers"], heads=model["heads"], mlp_dim=model["mlp_dim"],
        max_len=model["max_position_embeddings"], causal=False,
        pooling=model["pooling"], dtype=model["dtype"],
        norm_style=model["norm_style"],
    )
    return SentenceTransformerEmbedder(
        model["name"], config=tconfig, max_len=store["max_len"],
        seed=reference.weight_seed(seed),
    )


def release() -> None:
    """Drops what the program keeps of the model beyond the server's life,
    so that the reference has the chip's memory."""
    minilm._model_cache.clear()

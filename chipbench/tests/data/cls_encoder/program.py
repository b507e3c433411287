"""The program's side of the fixture architecture `cls_encoder`: the
program's pre-LN encoder with `pooling="cls"`."""

from pathway_tpu.models import minilm
from pathway_tpu.models.transformer import TransformerConfig
from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

from chipbench import reference


def embedder(model: dict, store: dict, seed: int):
    tconfig = TransformerConfig(
        vocab_size=model["vocab_size"], hidden=model["hidden"],
        layers=model["layers"], heads=model["heads"], mlp_dim=model["mlp_dim"],
        max_len=model["max_position_embeddings"], causal=False, pooling="cls",
        dtype=model["dtype"], norm_style="pre",
    )
    return SentenceTransformerEmbedder(
        model["name"], config=tconfig, max_len=store["max_len"],
        seed=reference.weight_seed(seed),
    )


def release() -> None:
    minilm._model_cache.clear()

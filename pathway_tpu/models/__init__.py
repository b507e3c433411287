"""JAX model zoo for the LLM xpack data plane: sentence encoder
(SentenceTransformer-class), cross-encoder reranker, decoder LM
(HFPipelineChat-class). All jit-compiled, bf16 on the MXU, shardable over a
jax.sharding.Mesh."""

from pathway_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    param_sharding_rules,
)
from pathway_tpu.models.trunk import TransformerLM

__all__ = [
    "TransformerConfig",
    "TransformerLM",
    "init_params",
    "param_sharding_rules",
]

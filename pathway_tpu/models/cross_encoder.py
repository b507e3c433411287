"""Cross-encoder (query, doc) scorer on JAX/TPU.

TPU-native replacement for the reference's sentence_transformers CrossEncoder
(reference: xpacks/llm/rerankers.py CrossEncoderReranker:163 — which scores
ONE pair per call; see SURVEY.md 'batching asymmetries'). Here the whole
candidate batch scores in a single MXU pass.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np

from pathway_tpu.models.tokenizer import HashTokenizer, encode_batch
from pathway_tpu.models.transformer import TransformerConfig
from pathway_tpu.models.trunk import TransformerLM

CROSS_ENCODER_CFG = TransformerConfig(
    vocab_size=30522, hidden=384, layers=4, heads=12, mlp_dim=1536,
    pooling="cls",
)

_model_cache: dict = {}


class CrossEncoderModel:
    def __init__(
        self,
        model: str = "cross-encoder/ms-marco-MiniLM-L-6-v2",
        *,
        config: TransformerConfig | None = None,
        seed: int = 1,
        max_len: int = 256,
    ):
        import jax

        self.name = model
        self.config = config or CROSS_ENCODER_CFG
        self.max_len = min(max_len, self.config.max_len)
        self.tokenizer = HashTokenizer(vocab_size=self.config.vocab_size)
        self.lm = TransformerLM(self.config, seed=seed)
        key = jax.random.PRNGKey(seed + 1)
        self.head = (
            np.asarray(
                jax.random.normal(key, (self.config.hidden,), dtype=np.float32)
            )
            * 0.02
        )

    @classmethod
    def cached(cls, model: str = "cross-encoder/ms-marco-MiniLM-L-6-v2", **kw):
        key = (model, tuple(sorted(kw.items())))
        if key not in _model_cache:
            _model_cache[key] = cls(model, **kw)
        return _model_cache[key]

    def score(self, pairs: Sequence[Tuple[str, str]]) -> np.ndarray:
        """Scores for (query, doc) pairs, one fused batch."""
        if not pairs:
            return np.zeros((0,), dtype=np.float32)
        queries = [q for q, _ in pairs]
        docs = [d for _, d in pairs]
        ids, mask = encode_batch(
            self.tokenizer, queries, pair_texts=docs, max_len=self.max_len
        )
        pooled = np.asarray(self.lm(ids, mask))[: len(pairs)]
        return pooled @ self.head

"""Decoder-only chat model on JAX/TPU.

TPU-native replacement for the reference's local HF pipeline
(reference: xpacks/llm/llms.py HFPipelineChat:456 — torch pipeline,
batch 32). Geometry for the Private-RAG target (Mistral-7B-class) is
decoder.MISTRAL_7B_DECODER; without pretrained weights (zero egress) the
default instance is a random-weight tiny decoder that exercises the exact
compute path (tokenize → bucketed batch → jit forward → greedy decode).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from pathway_tpu.models.decoder import (
    MISTRAL_7B_DECODER,
    TINY,
    DecoderConfig,
    generate_tokens,
    init_decoder_params,
)
from pathway_tpu.models.tokenizer import HashTokenizer

_model_cache: dict = {}


class ChatModel:
    """KV-cached decoder (models/decoder.py): prefill + lax.scan decode in
    one jit — no host round trip per token (the reference loops a torch
    pipeline on CPU/GPU, llms.py:456)."""

    def __init__(
        self,
        model: str = "tiny-decoder",
        *,
        config: DecoderConfig | None = None,
        seed: int = 2,
        max_len: int = 128,
    ):
        import os

        import jax

        params = None
        tokenizer = None
        from pathway_tpu.models import hf_loader

        if hf_loader.is_decoder_checkpoint(model):
            if config is not None:
                raise ValueError(
                    "pass either a checkpoint directory (its config.json "
                    "defines the architecture) or an explicit config=, "
                    "not both"
                )
            # real weights: a local Llama/Mistral-family checkpoint dir
            # (reference: llms.py HFPipelineChat:456 loads HF weights)
            config, params = hf_loader.load_hf_decoder(model)
            tok_json = os.path.join(model, "tokenizer.json")
            if os.path.exists(tok_json):
                from pathway_tpu.models.tokenizer import FastTokenizer

                tokenizer = FastTokenizer(tok_json)
        if config is None:
            config = MISTRAL_7B_DECODER if "mistral" in model.lower() else TINY
        self.name = model
        self.config = config
        self.max_len = min(max_len, config.max_len)
        self.tokenizer = tokenizer or HashTokenizer(
            vocab_size=config.vocab_size
        )
        if params is None:
            params = init_decoder_params(jax.random.PRNGKey(seed), config)
        self.params = params

    @classmethod
    def cached(cls, model: str = "tiny-decoder", **kw) -> "ChatModel":
        key = (model, tuple(sorted(kw.items())))
        if key not in _model_cache:
            _model_cache[key] = cls(model, **kw)
        return _model_cache[key]

    def generate(
        self,
        prompts: Sequence[str],
        *,
        max_new_tokens: int = 16,
        temperature: float = 0.0,
    ) -> List[str]:
        if not prompts:
            return []
        # Leave cache room for the new tokens; when a prompt overflows the
        # budget keep its most recent tokens — the tail is what conditions
        # the reply (the reference HF pipeline truncates the same end) —
        # so encode unbounded first, then keep the tail, left-aligned.
        budget = min(self.max_len, self.config.max_len - max_new_tokens)
        if budget <= 0:
            raise ValueError(
                f"max_new_tokens ({max_new_tokens}) leaves no cache room "
                f"for any prompt token (model max_len "
                f"{self.config.max_len})"
            )
        encoded = [
            self.tokenizer.encode(t, None)[-budget:] for t in prompts
        ]
        longest = max(len(e) for e in encoded)
        ids = np.zeros((len(encoded), longest), dtype=np.int32)
        mask = np.zeros_like(ids)
        for r, e in enumerate(encoded):
            ids[r, : len(e)] = e
            mask[r, : len(e)] = 1
        tokens = generate_tokens(
            self.params, self.config, ids, mask,
            max_new_tokens=max_new_tokens, temperature=temperature,
        )
        return [
            self.tokenizer.decode(row) for row in tokens[: len(prompts)]
        ]

"""SentenceTransformer-class sentence encoder on JAX/TPU.

TPU-native replacement for the reference's torch SentenceTransformer path
(reference: xpacks/llm/embedders.py SentenceTransformerEmbedder:342 — sync
batched UDF, default batch 1024, CPU/GPU). Here batches are bucketed to
stable shapes, jit-compiled, bf16 on the MXU; with a ("dp","tp") mesh the
batch axis shards over dp.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from pathway_tpu.models.tokenizer import (
    PACK_MAX_SEGMENTS,
    encode_batch,
    pack_batch,
    pack_token_budget,
)
from pathway_tpu.models.transformer import MINILM_L6, TransformerConfig
from pathway_tpu.models.trunk import model_module

_model_cache: dict = {}


class SentenceEncoder:
    """encode(list[str]) -> np.ndarray [B, hidden] (L2-normalized)."""

    def __init__(
        self,
        model: str = "all-MiniLM-L6-v2",
        *,
        config: TransformerConfig | None = None,
        seed: int = 0,
        max_len: int = 256,
        mesh=None,
    ):
        self.name = model
        params = None
        tokenizer = None
        from pathway_tpu.models import hf_loader

        if hf_loader.is_checkpoint_dir(model):
            # real weights: local HF-checkpoint dir (safetensors/.bin/.npz
            # + vocab.txt). The random-weight hash-tokenizer path stays the
            # offline default (reference: embedders.py:342 downloads the
            # model; this environment has zero egress).
            config, params = hf_loader.load_hf_encoder(model)
            tokenizer = hf_loader.load_tokenizer(model)
        self.config = config or MINILM_L6
        self.max_len = min(max_len, self.config.max_len)
        # the configuration's module says how its model reads a text, as it
        # says which trunk runs (a checkpoint brings its own vocabulary)
        model = model_module(self.config)
        self.tokenizer = tokenizer or model.tokenizer(self.config)
        self.lm = model.LM(self.config, params=params, seed=seed)
        if mesh is not None:
            axis = "dp" if "dp" in mesh.axis_names else mesh.axis_names[0]
            n_dev = mesh.shape[axis]
            if n_dev & (n_dev - 1):
                raise ValueError(
                    f"SentenceEncoder mesh axis {axis!r} has {n_dev} "
                    f"devices, which is not a power of two: encode_batch "
                    f"buckets every batch to a power of two (minimum 8), "
                    f"so a {n_dev}-way '{axis}' shard would never divide "
                    f"the batch axis evenly. Use a power-of-two device "
                    f"count on that axis, or drop the mesh and run the "
                    f"single-device async pipeline"
                )
        self.mesh = mesh

    @classmethod
    def cached(cls, model: str = "all-MiniLM-L6-v2", **kwargs) -> "SentenceEncoder":
        key = (model, tuple(sorted(kwargs.items())))
        if key not in _model_cache:
            _model_cache[key] = cls(model, **kwargs)
        return _model_cache[key]

    @property
    def dimension(self) -> int:
        return self.config.hidden

    def encode(self, texts: Sequence[str]) -> np.ndarray:
        return self.encode_await(self.encode_submit(texts))

    def encode_submit(self, texts: Sequence[str]):
        """Async half of encode(): tokenize and ENQUEUE the device encode,
        returning an opaque handle without forcing the result. JAX
        dispatch is asynchronous, so the caller can tokenize the next
        batch while this one executes; encode_await transfers the pooled
        vectors. encode() is exactly encode_await(encode_submit(...)), so
        the two paths cannot drift numerically."""
        if not texts:
            return None
        ids, mask = encode_batch(
            self.tokenizer, list(texts), max_len=self.max_len
        )
        if self.mesh is not None:
            # data-parallel dispatch: the (bucketed, power-of-two) batch
            # axis shards over the mesh's 'dp'/first axis — XLA splits the
            # encoder across devices with no code change (scaling-book
            # recipe: annotate shardings, let the compiler place the rest)
            import jax
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            axis = "dp" if "dp" in self.mesh.axis_names else self.mesh.axis_names[0]
            n_dev = self.mesh.shape[axis]
            if ids.shape[0] % n_dev:
                raise ValueError(
                    f"encoder batch of {ids.shape[0]} rows does not divide "
                    f"over the {n_dev} devices of mesh axis {axis!r}"
                )
            sharding = NamedSharding(self.mesh, P(axis, None))
            ids = jax.device_put(ids, sharding)
            mask = jax.device_put(mask, sharding)
        return (self.lm(ids, mask, mesh=self.mesh), len(texts))

    def encode_await(self, handle) -> np.ndarray:
        """Force a handle from encode_submit: one host transfer of the
        pooled [B, hidden] block, trimmed to the real batch."""
        if handle is None:
            return np.zeros((0, self.config.hidden), dtype=np.float32)
        pooled, n = handle
        return np.asarray(pooled)[:n]

    def encode_packed(self, texts: Sequence[str]) -> np.ndarray:
        """Packed ragged encode for the ingest hot path: docs concatenate
        into token-budget slabs (tokenizer.pack_batch) so the MXU runs on
        real tokens instead of per-doc pad. Falls back to the classic
        bucketed `encode` when packing is disabled
        (PATHWAY_PACK_TOKEN_BUDGET=0) or a mesh is attached — the mesh
        path needs the power-of-two batch-axis contract that packed row
        counts do not honor."""
        budget = pack_token_budget()
        if budget <= 0 or self.mesh is not None or not texts:
            return self.encode(texts)
        ids, seg, slots = pack_batch(
            self.tokenizer,
            list(texts),
            max_len=self.max_len,
            token_budget=budget,
        )
        pooled = np.asarray(
            self.lm.encode_packed(ids, seg, PACK_MAX_SEGMENTS)
        )
        rows = np.fromiter((r for r, _ in slots), dtype=np.int64, count=len(slots))
        segs = np.fromiter((s for _, s in slots), dtype=np.int64, count=len(slots))
        return pooled[rows, segs]

    def encode_one(self, text: str) -> np.ndarray:
        return self.encode([text])[0]

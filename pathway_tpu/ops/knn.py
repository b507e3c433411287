"""Brute-force KNN as XLA matmul + top_k, mesh-shardable.

TPU-native replacement for the reference's per-worker-replicated CPU kernel
(reference: src/external_integration/brute_force_knn_integration.rs:52-110 —
O(N·d) f64 ndarray matmul + per-query top-k, full index copy per worker;
broadcast at src/engine/dataflow/operators/external_index.rs:70).

Design departures, deliberate:
  * scores are computed in f32 on the MXU, not f64;
  * the index buffer is DEVICE-RESIDENT, padded to bucketed capacities;
    adds land as batched scatter updates (one dispatch per batch) instead of
    host-buffer re-uploads;
  * `FusedEmbedSearch` runs tokenizer-output → encoder → similarity → top_k
    as ONE jit call, so a retrieval query costs a single dispatch;
  * across a mesh the index shards on the row axis; each shard computes a
    local top-k and results merge via all-gather of [Q, k] — orders of
    magnitude less traffic than gathering [N, d].
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from pathway_tpu.internals import memtrack
from pathway_tpu.internals import serving as _serving
from pathway_tpu.internals import tracing
from pathway_tpu.internals.tracing import span


def _format_rows(scores, idx, key_of_slot) -> list:
    """[(key, score)] rows from top-k output, dropping invalid slots."""
    out = []
    for scores_row, idx_row in zip(scores, idx):
        row = []
        for s, i in zip(scores_row, idx_row):
            if not np.isfinite(s):
                continue
            key = key_of_slot.get(int(i))
            if key is not None:
                row.append((key, float(s)))
        out.append(row)
    return out


def _is_device_array(x) -> bool:
    import jax

    return isinstance(x, jax.Array)


def _next_bucket(n: int, minimum: int = 8) -> int:
    """Round up to the next power of two (compile-cache friendly)."""
    b = minimum
    while b < n:
        b *= 2
    return b


@functools.lru_cache(maxsize=None)
def _compiled_search(k: int, metric: str):
    import jax
    import jax.numpy as jnp

    if jax.default_backend() == "tpu" and k <= 128:
        # fused Pallas path: stream the index through VMEM, never build
        # [Q,N]. Index rows for cos are normalized once at insert time
        # (DeviceKnnIndex), so only the [Q,d] query block is normalized here.
        from pathway_tpu.ops.kernels.knn_topk import knn_topk

        kernel_metric = "ip" if metric == "cos" else metric

        def search(index, valid, queries):
            if metric == "cos":
                queries = queries * (
                    1.0 / (jnp.linalg.norm(queries, axis=1, keepdims=True)
                           + 1e-30)
                )
            top_scores, top_idx = knn_topk(
                index, valid, queries, k, metric=kernel_metric
            )
            if metric == "l2sq":
                # kernel drops the rank-invariant -||q||^2 term; restore it
                # so scores match the dense path exactly
                sq_q = jnp.sum(queries * queries, axis=1, keepdims=True)
                top_scores = top_scores - sq_q
            # dead slots carry ~-1e30 sentinels; surface them as -inf so
            # _format_rows drops them like the dense path does
            top_scores = jnp.where(
                top_scores < -1e29, -jnp.inf, top_scores
            )
            return top_scores, top_idx

        return jax.jit(search)

    def search(index, valid, queries):
        scores = _similarity(index, valid, queries, metric)
        top_scores, top_idx = jax.lax.top_k(scores, k)
        return top_scores, top_idx

    return jax.jit(search)


def _similarity(index, valid, queries, metric: str):
    import jax.numpy as jnp

    if metric == "cos":
        index_n = index * (
            1.0 / (jnp.linalg.norm(index, axis=1, keepdims=True) + 1e-30)
        )
        queries_n = queries * (
            1.0 / (jnp.linalg.norm(queries, axis=1, keepdims=True) + 1e-30)
        )
        scores = queries_n @ index_n.T  # [q, n] on the MXU
    elif metric == "ip":
        scores = queries @ index.T
    elif metric == "l2sq":
        # -||q - x||^2 ; rank by negated squared distance
        sq_i = jnp.sum(index * index, axis=1)
        sq_q = jnp.sum(queries * queries, axis=1, keepdims=True)
        scores = 2.0 * (queries @ index.T) - sq_i[None, :] - sq_q
    else:
        raise ValueError(f"unknown metric {metric!r}")
    return jnp.where(valid[None, :], scores, -jnp.inf)


@functools.lru_cache(maxsize=None)
def _compiled_update():
    import jax

    def update(buffer, valid, slots, vectors, slot_valid):
        # batched scatter of new rows; donated buffer → in-place on device
        buffer = buffer.at[slots].set(vectors)
        valid = valid.at[slots].set(slot_valid)
        return buffer, valid

    return jax.jit(update, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _compiled_grow(new_capacity: int):
    import jax
    import jax.numpy as jnp

    def grow(buffer, valid):
        n, d = buffer.shape
        out = jnp.zeros((new_capacity, d), dtype=buffer.dtype)
        out = out.at[:n].set(buffer)
        out_valid = jnp.zeros((new_capacity,), dtype=valid.dtype)
        out_valid = out_valid.at[:n].set(valid)
        return out, out_valid

    return jax.jit(grow)


class DeviceKnnIndex:
    """Mutable KNN index with a device-resident bucketed buffer.

    Adds/removes are queued host-side and flushed as ONE batched scatter
    before the next search (the reference instead mutates a host ndarray:
    brute_force_knn_integration.rs:113-140)."""

    def __init__(
        self,
        dimensions: int,
        *,
        metric: str = "cos",
        reserved_space: int = 512,
        mesh=None,
    ):
        import jax.numpy as jnp

        self.d = dimensions
        self.metric = metric
        # mesh: shard the index rows over the mesh's first axis; searches
        # run per-shard top-k + ICI all-gather merge (sharded_knn_search)
        # instead of the reference's full-copy-per-worker replication
        self.mesh = mesh
        min_cap = 8
        if mesh is not None:
            n_dev = mesh.shape[mesh.axis_names[0]]
            if n_dev & (n_dev - 1):
                raise ValueError(
                    f"DeviceKnnIndex mesh axis {mesh.axis_names[0]!r} has "
                    f"{n_dev} devices; a power of two is required (the "
                    "index buffer is bucketed to power-of-two capacities "
                    "and shards evenly only then)"
                )
            min_cap = max(min_cap, 2 * n_dev)
        self.capacity = _next_bucket(max(reserved_space, min_cap))
        with span("setup.index_alloc", rows=self.capacity):
            self._buffer = jnp.zeros((self.capacity, self.d), dtype=jnp.float32)
            self._valid_dev = jnp.zeros((self.capacity,), dtype=bool)
            self._shard_buffers()
        self._slot_of_key: dict = {}
        self._key_of_slot: dict = {}
        self._free: list[int] = list(range(self.capacity - 1, -1, -1))
        # mesh: per-shard free buckets so dp-routed rows get slots INSIDE
        # their replica's row range (exchange<->device alignment).  The
        # flat list stays authoritative-order for shardless callers;
        # _free_set arbitrates lazily-stale entries in both structures.
        self._free_set: set | None = None
        self._free_by_shard: list | None = None
        if mesh is not None:
            self._free_set = set(self._free)
            self._rebuild_shard_buckets()
        # queued updates: slot -> (vector | None for invalidation)
        self._dirty: dict[int, np.ndarray | None] = {}
        if memtrack.ENABLED:
            self._register_memory()

    def __len__(self) -> int:
        return len(self._slot_of_key)

    # -- memory accounting (internals/memtrack.py) --------------------------

    def _mem_span(self) -> int:
        """Devices the slab spreads over: the buffer rows shard on the
        mesh's first axis (dp), so both the per-device divisor and the
        per-replica divisor are that axis size."""
        return self._shard_count() if self.mesh is not None else 1

    def _register_memory(self) -> None:
        """(Re-)register the slab's LOGICAL bytes — float32 rows + bool
        valid at the current bucketed capacity.  Upserts on the same
        owner, so _grow just calls it again after doubling."""
        span = self._mem_span()
        memtrack.tracker().register(
            "knn_index",
            self,
            self.capacity * (4 * self.d + 1),
            device_span=span,
            dp_shards=span,
            capacity=self.capacity,
            dimensions=self.d,
        )

    # -- free-slot bookkeeping (shard-aware under a mesh) -------------------

    def _shard_count(self) -> int:
        return int(self.mesh.shape[self.mesh.axis_names[0]])

    def _rebuild_shard_buckets(self) -> None:
        """Bucket the free slots by owning shard (slot // shard_rows).
        Rebuilt after _grow because the per-shard row ranges shift when
        capacity doubles.  Buckets are descending so pop() hands out the
        lowest slot in the shard first, mirroring the flat list."""
        n_dev = self._shard_count()
        shard_rows = self.capacity // n_dev
        buckets: list[list[int]] = [[] for _ in range(n_dev)]
        for slot in sorted(self._free_set, reverse=True):
            buckets[slot // shard_rows].append(slot)
        self._free_by_shard = buckets

    def _free_count(self) -> int:
        return len(self._free_set) if self._free_set is not None else len(
            self._free
        )

    def _pop_free(self, shard: int | None = None) -> int:
        if self._free_set is None:
            return self._free.pop()
        if shard is not None:
            bucket = self._free_by_shard[shard % len(self._free_by_shard)]
            while bucket:
                slot = bucket.pop()
                if slot in self._free_set:
                    self._free_set.discard(slot)
                    return slot
        # shardless callers — and a full shard bucket's overflow — take
        # the global order the flat list preserves (placement is a
        # locality optimization, never a correctness requirement)
        while True:
            slot = self._free.pop()
            if slot in self._free_set:
                self._free_set.discard(slot)
                return slot

    def _push_free(self, slot: int) -> None:
        self._free.append(slot)
        if self._free_set is not None:
            self._free_set.add(slot)
            shard_rows = self.capacity // self._shard_count()
            self._free_by_shard[slot // shard_rows].append(slot)

    def _shard_buffers(self) -> None:
        if self.mesh is None:
            return
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        axis = self.mesh.axis_names[0]
        self._buffer = jax.device_put(
            self._buffer, NamedSharding(self.mesh, P(axis, None))
        )
        self._valid_dev = jax.device_put(
            self._valid_dev, NamedSharding(self.mesh, P(axis))
        )

    def _normalize(self, vectors):
        """cos rows are normalized ONCE at insert time so searches never
        re-read the whole buffer just to normalize it."""
        if self.metric != "cos":
            return vectors
        if _is_device_array(vectors):
            import jax.numpy as jnp

            return vectors * (
                1.0 / (jnp.linalg.norm(vectors, axis=-1, keepdims=True)
                       + 1e-30)
            )
        return vectors / (
            np.linalg.norm(vectors, axis=-1, keepdims=True) + 1e-30
        )

    def add(self, key, vector) -> None:
        vector = np.asarray(vector, dtype=np.float32).reshape(-1)
        if vector.shape[0] != self.d:
            raise ValueError(
                f"vector dim {vector.shape[0]} != index dim {self.d}"
            )
        if memtrack.ENABLED and key not in self._slot_of_key:
            self._note_ingest(1)
        if _serving.ENABLED:
            # cache invalidation rides the delta stream: an insert OR an
            # update can enter any cached query's top-k → global bump
            _serving.note_index_add(1)
        slot = self._assign_slot(key)
        self._dirty[slot] = self._normalize(vector)

    def add_batch(self, keys, vectors, shards=None) -> None:
        """vectors: [B, d] array (host or device). shards (optional,
        mesh only): per-key dp-shard hints — slots are drawn from the
        owning replica's row range so engine sharding and device
        sharding agree."""
        keys = list(keys)
        if _serving.ENABLED and keys:
            _serving.note_index_add(len(keys))
        if _is_device_array(vectors):
            # keep the batch on device: assign slots, one scatter, no host
            # round trip
            self._flush()
            new = len(keys) - sum(
                1 for k in keys if k in self._slot_of_key
            )
            if memtrack.ENABLED and new:
                self._note_ingest(new)
            while self._free_count() < new:
                self._grow()
            slots = np.array(
                [
                    self._assign_slot(
                        k, None if shards is None else shards[i]
                    )
                    for i, k in enumerate(keys)
                ],
                dtype=np.int32,
            )
            slot_valid = np.ones((len(slots),), dtype=bool)
            self._buffer, self._valid_dev = _compiled_update()(
                self._buffer, self._valid_dev, slots,
                self._normalize(vectors), slot_valid
            )
            return
        vectors = self._normalize(np.asarray(vectors, dtype=np.float32))
        if memtrack.ENABLED:
            new = sum(1 for k in keys if k not in self._slot_of_key)
            if new:
                self._note_ingest(new)
        for key, vec in zip(keys, vectors):
            slot = self._assign_slot(key)
            self._dirty[slot] = vec

    def _note_ingest(self, new_rows: int) -> None:
        """Feed the ingest-rate forecaster: each new row will occupy one
        slab row of (4*d + 1) bytes, divided over the shard span."""
        memtrack.tracker().note_ingest(
            new_rows, new_rows * (4 * self.d + 1) / self._mem_span()
        )

    def _assign_slot(self, key, shard: int | None = None) -> int:
        slot = self._slot_of_key.get(key)
        if slot is None:
            if not self._free_count():
                self._grow()
            slot = self._pop_free(shard)
            self._slot_of_key[key] = slot
            self._key_of_slot[slot] = key
        return slot

    def remove(self, key) -> None:
        slot = self._slot_of_key.pop(key, None)
        if slot is None:
            return
        if _serving.ENABLED:
            # removal is monotone — it can only change cached queries
            # whose results contained this key → cluster-precise bump
            _serving.note_index_remove(key)
        del self._key_of_slot[slot]
        self._push_free(slot)
        self._dirty[slot] = None

    def _grow(self) -> None:
        new_capacity = self.capacity * 2
        self._buffer, self._valid_dev = _compiled_grow(new_capacity)(
            self._buffer, self._valid_dev
        )
        self._free.extend(range(new_capacity - 1, self.capacity - 1, -1))
        if self._free_set is not None:
            self._free_set.update(range(self.capacity, new_capacity))
        self.capacity = new_capacity
        self._shard_buffers()
        if self._free_set is not None:
            self._rebuild_shard_buckets()
        if memtrack.ENABLED:
            self._register_memory()

    def _flush(self) -> None:
        if not self._dirty:
            return
        slots = np.fromiter(self._dirty.keys(), dtype=np.int32)
        vectors = np.zeros((len(slots), self.d), dtype=np.float32)
        slot_valid = np.zeros((len(slots),), dtype=bool)
        for i, (_slot, vec) in enumerate(self._dirty.items()):
            if vec is not None:
                vectors[i] = vec
                slot_valid[i] = True
        self._buffer, self._valid_dev = _compiled_update()(
            self._buffer, self._valid_dev, slots, vectors, slot_valid
        )
        self._dirty.clear()

    # kept for backwards compatibility with callers that force a sync
    _sync_device = _flush

    @property
    def device_buffer(self):
        """Defensive copy: the live buffer is donated (freed) by the next
        flush, so handing it out would leave callers with deleted arrays on
        real accelerators."""
        import jax.numpy as jnp

        self._flush()
        return jnp.array(self._buffer, copy=True)

    @property
    def device_valid(self):
        import jax.numpy as jnp

        self._flush()
        return jnp.array(self._valid_dev, copy=True)

    def search(
        self, queries, k: int
    ) -> Tuple[np.ndarray, np.ndarray, dict]:
        """Return (scores [Q,k], slot indices [Q,k], slot->key map). Scores
        are similarity-like: higher is better for every metric."""
        queries = np.asarray(queries, dtype=np.float32)
        if queries.ndim == 1:
            queries = queries[None, :]
        q = queries.shape[0]
        if q == 0 or not self._slot_of_key:
            return (
                np.zeros((q, 0), dtype=np.float32),
                np.zeros((q, 0), dtype=np.int64),
                {},
            )
        self._flush()
        q_pad = _next_bucket(q, 1)
        k_eff = min(k, self.capacity)
        padded = np.zeros((q_pad, self.d), dtype=np.float32)
        padded[:q] = queries
        if self.mesh is not None:
            if self.metric == "cos":
                # rows are insert-normalized; normalize queries host-side so
                # the sharded kernel can use the plain inner product
                padded = padded / (
                    np.linalg.norm(padded, axis=1, keepdims=True) + 1e-30
                )
            top_scores, top_idx = sharded_knn_search(
                self.mesh,
                self._buffer,
                self._valid_dev,
                padded,
                k_eff,
                metric="ip" if self.metric == "cos" else self.metric,
            )
        else:
            fn = _compiled_search(k_eff, self.metric)
            top_scores, top_idx = fn(self._buffer, self._valid_dev, padded)
        top_scores = np.asarray(top_scores)[:q]
        top_idx = np.asarray(top_idx)[:q]
        return top_scores, top_idx, self._key_of_slot

    def search_keys(self, queries, k: int) -> list:
        """Per query: list of (key, score) with invalid slots dropped."""
        top_scores, top_idx, key_of_slot = self.search(queries, k)
        return _format_rows(top_scores, top_idx, key_of_slot)


@functools.lru_cache(maxsize=None)
def _compiled_fused_search(config, metric: str, k: int, mesh=None, n_rows: int = 0):
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.trunk import model_module

    forward = model_module(config).forward

    def fused(params, ids_mask, buffer, valid):
        # single packed input ([2,B,L], narrow wire dtype upcast here) and
        # single packed output ([Q, 2k]) — exactly one upload and one
        # fetch per query batch
        ids_mask = ids_mask.astype(jnp.int32)
        ids, mask = ids_mask[0], ids_mask[1]
        emb = forward(params, config, ids, mask, mesh=mesh)
        if mesh is not None:
            # per-shard top-k + [Q, k] all-gather merge over the sharded
            # buffer (NOT a full-buffer gather), still inside this one jit
            top_scores, top_idx = _sharded_search_body(
                mesh, n_rows, k, metric
            )(buffer, valid, emb)
        else:
            scores = _similarity(buffer, valid, emb, metric)
            top_scores, top_idx = jax.lax.top_k(scores, k)
        return jnp.concatenate(
            [top_scores, top_idx.astype(jnp.float32)], axis=1
        )

    return jax.jit(fused)


class FusedEmbedSearch:
    """tokens → encoder → similarity → top_k in ONE jit call.

    Collapses the retrieval hot path (3.4 in SURVEY.md) to a single device
    dispatch per query batch."""

    def __init__(self, encoder, index: DeviceKnnIndex, backend=None):
        self.encoder = encoder
        self.index = index
        # mesh execution backend (internals/mesh_backend.MeshBackend):
        # dp-grouped packed ingest + tp-sharded encoder params; None
        # keeps the single-device path byte-identical
        self.backend = backend
        if memtrack.ENABLED:
            # LOGICAL param bytes, keyed on the lm so encoders shared
            # between FusedEmbedSearch instances count once.  Matmul
            # params shard over tp within a replica but every dp replica
            # holds a full copy (dp_shards=1 — the PWT605 story).
            import jax

            nbytes = sum(
                int(getattr(leaf, "nbytes", 0))
                for leaf in jax.tree_util.tree_leaves(encoder.lm.params)
            )
            memtrack.tracker().register(
                "encoder_params",
                encoder.lm,
                nbytes,
                device_span=backend.tp if backend is not None else 1,
                dp_shards=1,
                model=type(encoder).__name__,
            )

    def _params(self):
        if self.backend is not None:
            return self.encoder.lm.mesh_params(self.backend.mesh)
        return self.encoder.lm.params

    def _fn(self, k: int):
        # process-global cache keyed on (config, metric, k[, mesh]): a
        # fresh FusedEmbedSearch (e.g. a rebuilt DocumentStore) reuses the
        # already compiled executable instead of retracing per instance
        return _compiled_fused_search(
            self.encoder.config,
            self.index.metric,
            k,
            mesh=self.index.mesh,
            n_rows=self.index.capacity if self.index.mesh is not None else 0,
        )

    def embed_and_add(self, keys, texts) -> None:
        """Embed a doc batch and scatter into the index, fully device-side
        (the embeddings never leave HBM). Classic synchronous entry:
        prepare (unpacked — preserves pre-pipeline behavior exactly) and
        dispatch back-to-back on the calling thread."""
        self.dispatch_batch(self.prepare_batch(keys, texts, pack=False)[0])

    def prepare_batch(self, keys, texts, *, pack: bool = True):
        """Host-side PREPARE stage of the device pipeline: tokenize (and
        pack into token-budget slabs when enabled and no mesh is
        attached) off the dispatch thread. Returns (payload, meta) —
        payload is opaque to the pipeline and consumed by dispatch_batch;
        meta carries rows/real-token/slab-token accounting for the
        pad-waste gauge."""
        from pathway_tpu.models.tokenizer import (
            PACK_MAX_SEGMENTS,
            encode_batch,
            pack_batch,
            pack_token_budget,
        )

        texts = list(texts)
        keys = list(keys)
        packable = self.index.mesh is None or self.backend is not None
        budget = pack_token_budget() if pack and packable else 0
        replica_rows = replica_real = replica_slab = None
        if budget > 0 and texts and self.backend is not None:
            # mesh backend: pack PER dp SHARD so each replica's rows land
            # on its devices under the batch NamedSharding
            from pathway_tpu.internals.mesh_backend import pack_batch_dp

            ids, seg, slots, replica_rows = pack_batch_dp(
                self.encoder.tokenizer,
                keys,
                texts,
                self.backend,
                max_len=self.encoder.max_len,
                token_budget=budget,
                max_segments=PACK_MAX_SEGMENTS,
            )
            payload = ("packed_dp", keys, ids, seg, slots)
            real, total = int(np.count_nonzero(seg)), int(seg.size)
            # per-replica token counts for the labeled pad-waste gauge
            # and the straggler detector: slab rows land on replica
            # r // block by construction (pack_batch_dp pads groups to
            # a common block)
            dp = self.backend.dp
            block = seg.shape[0] // dp
            replica_real = [
                int(np.count_nonzero(seg[r * block : (r + 1) * block]))
                for r in range(dp)
            ]
            replica_slab = [int(block * seg.shape[1])] * dp
            drained = self.backend.drained_replicas()
            for r in drained:
                # a drained replica's block is INTENTIONALLY empty (the
                # health controller routed ingest around it); count it
                # as zero slab so the pad-waste gauge and the straggler
                # detector don't read a planned drain as 100% waste/skew
                if 0 <= r < dp:
                    replica_slab[r] = replica_real[r]
        elif budget > 0 and texts:
            ids, seg, slots = pack_batch(
                self.encoder.tokenizer,
                texts,
                max_len=self.encoder.max_len,
                token_budget=budget,
                max_segments=PACK_MAX_SEGMENTS,
            )
            payload = ("packed", keys, ids, seg, slots)
            real, total = int(np.count_nonzero(seg)), int(seg.size)
        else:
            ids, mask = encode_batch(
                self.encoder.tokenizer, texts, max_len=self.encoder.max_len
            )
            payload = ("classic", keys, ids, mask, None)
            real, total = int(np.asarray(mask).sum()), int(mask.size)
        from pathway_tpu.internals import costmodel

        meta = {
            "rows": len(keys),
            "real_tokens": real,
            "slab_tokens": total,
            # exact bytes of the two packed wire arrays (ids + seg/mask)
            # for the pipeline's in-flight memory accounting
            "slab_bytes": (
                int(getattr(payload[2], "nbytes", 0))
                + int(getattr(payload[3], "nbytes", 0))
            ),
            # mask-aware useful FLOPs for the live MFU gauge
            # (internals/utilization.py); padding is not useful work
            "useful_flops": costmodel.encoder_flops_for_config(
                self.encoder.config, real, len(keys)
            ),
        }
        if replica_rows is not None:
            meta["replica_rows"] = replica_rows
        if replica_real is not None:
            meta["replica_real_tokens"] = replica_real
            meta["replica_slab_tokens"] = replica_slab
        return payload, meta

    def dispatch_batch(self, payload):
        """Device DISPATCH stage: enqueue encode (+ per-segment gather for
        packed slabs) and the index scatter; returns the embeddings handle
        (JAX dispatch is async — the caller blocks only at barriers).
        Ordering matters: the scatter donates the previous index buffer,
        so batches must dispatch in submission order."""
        from pathway_tpu.models.tokenizer import PACK_MAX_SEGMENTS
        from pathway_tpu.models.trunk import model_module

        kind, keys, ids, second, slots = payload
        shards = None
        if kind == "packed_dp":
            # dp-sharded dispatch: slab rows placed per replica, encoder
            # matmuls tp-sharded via the partition-ruled param copy
            import jax

            sharding = self.backend.batch_sharding()
            ids = jax.device_put(ids, sharding)
            second = jax.device_put(second, sharding)
            shards = [self.backend.dp_shard_of(k) for k in keys]
        # launch.encode / launch.scatter: children of the pipeline's
        # pipeline.launch span (its seq and epoch are inherited); what is
        # left of the parent is the eager glue between the two programs
        if kind in ("packed", "packed_dp"):
            with span("launch.encode"):
                pooled = self.encoder.lm.encode_packed(
                    ids, second, PACK_MAX_SEGMENTS, params=self._params(),
                    mesh=self.index.mesh,
                )
            # which attention this slab's program runs: static per shape,
            # so it is counted per batch, here, and not inside the jit
            fused = model_module(self.encoder.config).packed_attention_fused(
                self.encoder.config, ids.shape[1]
            )
            tracing.add(
                "launch.encode.attn_fused" if fused
                else "launch.encode.attn_dense"
            )
            rows = np.fromiter(
                (r for r, _ in slots), dtype=np.int64, count=len(slots)
            )
            segs = np.fromiter(
                (s for _, s in slots), dtype=np.int64, count=len(slots)
            )
            emb = pooled[rows, segs]  # device-side gather, [B, d]
        else:
            with span("launch.encode"):
                emb = self.encoder.lm(ids, second)
            emb = emb[: len(keys)]
        if keys:
            with span("launch.scatter", rows=len(keys)):
                self.index.add_batch(keys, emb, shards=shards)
        return emb

    def search_texts(self, texts, k: int) -> list:
        from pathway_tpu.models.tokenizer import encode_batch

        texts = list(texts)
        if not len(self.index):
            return [[] for _ in texts]
        self.index._flush()
        k_eff = min(k, self.index.capacity)
        import time as time_mod

        from pathway_tpu.internals import qtrace as _qtrace

        t0 = time_mod.perf_counter() if _qtrace.ENABLED else 0.0
        # ids/mask are wire-narrowed by encode_batch (one shared dtype);
        # the fused jit upcasts on device
        ids, mask = encode_batch(
            self.encoder.tokenizer, texts, max_len=self.encoder.max_len
        )
        packed = self._fn(k_eff)(
            self._params(),
            np.stack([ids, mask]),
            self.index._buffer,
            self.index._valid_dev,
        )
        packed = np.asarray(packed)[: len(texts)]
        if _qtrace.ENABLED:
            # pure device portion of the query (encode+search dispatch to
            # host materialization) into the tail-attribution window
            _qtrace.tracker().note_device_window(
                time_mod.perf_counter() - t0, source="knn_search"
            )
        if self.backend is not None:
            self.backend.note_serve_batch(len(texts))
        scores = packed[:, :k_eff]
        idx = packed[:, k_eff:].astype(np.int64)
        return _format_rows(scores, idx, self.index._key_of_slot)


def _sharded_search_body(mesh, n_rows: int, k: int, metric: str):
    """shard_map'd per-shard top-k + all-gather merge; composable inside
    a larger jit (the fused embed+search path) or jitted standalone."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    n_dev = mesh.shape[axis]
    shard_size = n_rows // n_dev
    # the per-shard pass only needs min(k, shard_size) candidates; the
    # merged pool of n_dev of those always holds >= min(k, capacity), so
    # the caller gets the full k it asked for (never clamped per shard)
    local_k = min(k, shard_size)
    k = min(k, n_rows)

    def local_search(index_shard, valid_shard, queries_rep):
        scores = _similarity(index_shard, valid_shard, queries_rep, metric)
        local_scores, local_idx = jax.lax.top_k(scores, local_k)
        # globalize slot ids, then gather candidates from every shard
        shard_id = jax.lax.axis_index(axis)
        global_idx = local_idx + shard_id * shard_size
        all_scores = jax.lax.all_gather(local_scores, axis)  # [n_dev, Q, lk]
        all_idx = jax.lax.all_gather(global_idx, axis)
        all_scores = jnp.transpose(all_scores, (1, 0, 2)).reshape(
            queries_rep.shape[0], n_dev * local_k
        )
        all_idx = jnp.transpose(all_idx, (1, 0, 2)).reshape(
            queries_rep.shape[0], n_dev * local_k
        )
        merged_scores, merged_pos = jax.lax.top_k(all_scores, k)
        merged_idx = jnp.take_along_axis(all_idx, merged_pos, axis=1)
        return merged_scores, merged_idx

    return shard_map(
        local_search,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False,
    )


@functools.lru_cache(maxsize=None)
def _compiled_sharded_search(mesh, n_rows: int, k: int, metric: str):
    """Compile-once per (mesh, capacity, k, metric): the serving hot path
    calls this per query batch and must hit jit's trace cache, exactly
    like the dense `_compiled_search`."""
    import jax

    return jax.jit(_sharded_search_body(mesh, n_rows, k, metric))


def sharded_knn_search(mesh, index, valid, queries, k: int, metric: str = "cos"):
    """Mesh-sharded search: index rows sharded over the mesh's first axis,
    per-shard top-k, then a global merge (the all-gather of [Q, k] per shard
    rides ICI; reference instead broadcast-replicates the whole index,
    external_index.rs:70)."""
    return _compiled_sharded_search(mesh, index.shape[0], k, metric)(
        index, valid, queries
    )

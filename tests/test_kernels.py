"""Pallas kernel correctness vs pure-jnp references (interpret mode on the
CPU test mesh; the identical kernels run compiled on TPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _ref_attention(q, k, v, kv_mask, causal):
    from pathway_tpu.ops.kernels.flash_attention import _reference_attention

    return _reference_attention(
        q, k, v, kv_mask, 1.0 / np.sqrt(q.shape[-1]), causal
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    from pathway_tpu.ops.kernels import flash_attention

    rng = np.random.default_rng(0)
    b, h, l, d = 2, 2, 32, 16
    q = jnp.asarray(rng.normal(size=(b, h, l, d)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, l, d)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, l, d)), dtype=jnp.float32)
    mask = np.ones((b, l), dtype=np.int32)
    mask[1, l // 2:] = 0  # ragged batch
    mask = jnp.asarray(mask)

    out = flash_attention(q, k, v, mask, causal=causal, block_q=16, block_k=16)
    ref = _ref_attention(q, k, v, mask, causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
    )


def test_flash_attention_grad_flows():
    from pathway_tpu.ops.kernels import flash_attention

    rng = np.random.default_rng(1)
    b, h, l, d = 1, 2, 16, 8
    q = jnp.asarray(rng.normal(size=(b, h, l, d)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, l, d)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, l, d)), dtype=jnp.float32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=8, block_k=8) ** 2)

    g = jax.grad(loss)(q, k, v)
    assert np.isfinite(np.asarray(g)).all()
    # grad must match the reference implementation's grad
    def ref_loss(q, k, v):
        mask = jnp.ones((b, l), dtype=jnp.int32)
        return jnp.sum(_ref_attention(q, k, v, mask, False) ** 2)

    g_ref = jax.grad(ref_loss)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(g), np.asarray(g_ref), rtol=2e-3, atol=2e-3
    )


def _slab_segments(l: int, layouts) -> np.ndarray:
    """One slab row per layout (segments, tokens used): `segments`
    documents of near-equal length packed from the row's start, the rest
    padding (seg 0); `segments` 0 is a row that is all padding."""
    seg = np.zeros((len(layouts), l), dtype=np.int32)
    for r, (segments, used) in enumerate(layouts):
        bounds = np.linspace(0, used, segments + 1).astype(int)
        for i in range(segments):
            seg[r, bounds[i]:bounds[i + 1]] = i + 1
    return seg


# (heads, L, head_dim) and the rows' (segments, tokens used)
_SEGMENT_SLABS = {
    # MiniLM's geometry: a row of one document, a row packing two
    "L256-hd32": ((4, 256, 32), [(1, 256), (2, 256)]),
    # e5's, L not a multiple of 128: 32 documents in a row, a row that is
    # all padding, two documents and trailing padding
    "L504-hd64": ((16, 504, 64), [(32, 504), (0, 0), (2, 311)]),
    # a search bucket: few short queries, the key axis padded 32 -> 128
    "L32-hd32": ((4, 32, 32), [(1, 9), (2, 32)]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("slab", sorted(_SEGMENT_SLABS))
def test_segment_attention_matches_dense_definition(slab, dtype):
    """The packed path's fused kernel against `_segment_attention`, which
    stays its numerical definition: equal on every valid token, finite
    everywhere (pooling multiplies padding by zero, and 0 * NaN is NaN)."""
    from pathway_tpu.models.transformer import _segment_attention
    from pathway_tpu.ops.kernels.segment_attention import segment_attention

    (h, l, hd), layouts = _SEGMENT_SLABS[slab]
    b, hidden = len(layouts), h * hd
    rng = np.random.default_rng(0)
    qkv = jnp.asarray(
        rng.normal(size=(b, l, 3 * hidden)), dtype=jnp.dtype(dtype)
    )
    seg = _slab_segments(l, layouts)

    out = segment_attention(qkv, jnp.asarray(seg), h, interpret=True)
    assert out.shape == (b, l, hidden) and out.dtype == qkv.dtype

    def split_heads(x):
        return x.reshape(b, l, h, hd).transpose(0, 2, 1, 3)

    q, k, v = (split_heads(x) for x in jnp.split(qkv, 3, axis=-1))
    ref = _segment_attention(q, k, v, jnp.asarray(seg), 1.0 / np.sqrt(hd))
    ref = ref.transpose(0, 2, 1, 3).reshape(b, l, hidden)
    out = np.asarray(out, dtype=np.float32)
    ref = np.asarray(ref, dtype=np.float32)
    assert np.isfinite(out).all()
    # f32: test_flash_attention_matches_reference's tolerance; bf16: both
    # sides round their output to bf16, so two of its ulps
    tol = 2e-3 if dtype == "float32" else 2.0 ** -6
    valid = seg > 0
    np.testing.assert_allclose(out[valid], ref[valid], rtol=tol, atol=tol)


def test_segment_attention_refuses_shapes_it_cannot_tile():
    from pathway_tpu.ops.kernels import segment_attention as sa

    assert sa.supports(504, 1024, 64) and sa.supports(256, 384, 32)
    assert not sa.supports(504, 64, 16)  # hidden not whole 128-lane tiles
    assert not sa.supports(1024, 1024, 64)  # key axis beyond one tile
    with pytest.raises(ValueError, match="unsupported shape"):
        sa.segment_attention(
            jnp.zeros((1, 16, 3 * 64)), jnp.ones((1, 16), jnp.int32), 4,
            interpret=True,
        )


def test_packed_attention_choice_follows_backend_and_shape(monkeypatch):
    """`packed_attention_fused`: never off the TPU; on it, both ingest
    slabs take the kernel and the measured corners stay dense."""
    from pathway_tpu.models import transformer
    from pathway_tpu.models.transformer import TransformerConfig

    e5 = TransformerConfig(hidden=1024, heads=16, layers=24, mlp_dim=4096)
    minilm = transformer.MINILM_L6
    tiny = TransformerConfig(hidden=32, heads=2, layers=1, mlp_dim=64)
    choose = transformer.packed_attention_fused
    assert not choose(e5, 504) and not choose(minilm, 256)  # the CPU here
    assert choose(tiny, 16, use_flash=True) and not choose(e5, 504, False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert choose(e5, 504) and choose(minilm, 256) and choose(e5, 64)
    assert not choose(minilm, 128) and not choose(minilm, 64)
    assert not choose(e5, 32)
    assert not choose(tiny, 256)  # hidden 32: not whole 128-lane tiles
    assert not choose(e5, 1024)  # beyond one key tile


def test_packed_forward_with_fused_kernel_pools_each_document_as_alone():
    """forward(seg=..., use_flash=True): a document packed with
    neighbours gets the vector it gets alone in a row of its own (dense
    path), as tests/test_device_pipeline.py checks for the dense path."""
    from pathway_tpu.models.transformer import (
        TransformerConfig,
        forward,
        init_params,
    )

    config = TransformerConfig(
        vocab_size=512, hidden=128, layers=2, heads=4, mlp_dim=256, max_len=64
    )
    params = init_params(jax.random.PRNGKey(0), config)
    rng = np.random.default_rng(0)
    lengths = [[7, 20, 11], [40], [3, 3, 3, 30]]
    l, max_segments = 48, 4
    ids = np.zeros((len(lengths), l), dtype=np.int32)
    seg = np.zeros((len(lengths), l), dtype=np.int32)
    docs = []
    for r, row in enumerate(lengths):
        at = 0
        for i, n in enumerate(row):
            doc = rng.integers(4, 512, size=n).astype(np.int32)
            ids[r, at:at + n], seg[r, at:at + n] = doc, i + 1
            docs.append((r, i, doc))
            at += n
    pooled = np.asarray(jax.jit(lambda p, i, s: forward(
        p, config, i, None, seg=s, max_segments=max_segments, use_flash=True,
    ))(params, ids, seg))
    assert pooled.shape == (len(lengths), max_segments, config.hidden)
    assert np.isfinite(pooled).all()
    alone_ids = np.zeros((len(docs), l), dtype=np.int32)
    alone_seg = np.zeros((len(docs), l), dtype=np.int32)
    for at, (_, _, doc) in enumerate(docs):
        alone_ids[at, :len(doc)], alone_seg[at, :len(doc)] = doc, 1
    alone = np.asarray(jax.jit(lambda p, i, s: forward(
        p, config, i, None, seg=s, max_segments=1, use_flash=False,
    ))(params, alone_ids, alone_seg))[:, 0]
    for at, (r, i, _) in enumerate(docs):
        np.testing.assert_allclose(pooled[r, i], alone[at], atol=2e-2, rtol=0)
    # empty slots pool to the zero vector
    assert not pooled[1, 1:].any()


@pytest.mark.parametrize("metric", ["cos", "ip", "l2sq"])
def test_knn_topk_matches_dense(metric):
    from pathway_tpu.ops.kernels import knn_topk

    rng = np.random.default_rng(2)
    n, d, qn, k = 300, 24, 5, 4
    index = rng.normal(size=(n, d)).astype(np.float32)
    if metric == "cos":
        index /= np.linalg.norm(index, axis=1, keepdims=True)
    valid = np.ones((n,), dtype=np.int32)
    valid[50:60] = 0  # deleted slots must never be returned
    queries = rng.normal(size=(qn, d)).astype(np.float32)
    if metric == "cos":
        queries /= np.linalg.norm(queries, axis=1, keepdims=True)

    s, i = knn_topk(
        jnp.asarray(index), jnp.asarray(valid), jnp.asarray(queries),
        k, metric=metric, block_n=128,
    )
    s, i = np.asarray(s), np.asarray(i)

    # dense reference
    if metric == "l2sq":
        dense = (
            2.0 * queries @ index.T
            - np.sum(index * index, axis=1)[None, :]
        )
    else:
        dense = queries @ index.T
    dense[:, valid == 0] = -np.inf
    ref_i = np.argsort(-dense, axis=1)[:, :k]
    for row in range(qn):
        assert set(i[row]) == set(ref_i[row])
        np.testing.assert_allclose(
            np.sort(s[row]), np.sort(dense[row, ref_i[row]]), rtol=1e-4
        )
    assert not np.isin(i, np.arange(50, 60)).any()


def test_device_knn_mesh_sharded_search_matches_dense():
    """DeviceKnnIndex with a mesh shards the buffer over the first axis and
    searches via per-shard top-k + all-gather merge (ops/knn.py
    sharded_knn_search); results must equal the dense single-device path."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from pathway_tpu.ops.knn import DeviceKnnIndex

    devices = np.array(jax.devices()[:8])
    mesh = Mesh(devices, ("knn",))
    rng = np.random.default_rng(7)
    data = rng.standard_normal((200, 16)).astype(np.float32)

    dense = DeviceKnnIndex(16, metric="cos", reserved_space=256)
    sharded = DeviceKnnIndex(16, metric="cos", reserved_space=256, mesh=mesh)
    for i, v in enumerate(data):
        dense.add(i, v)
        sharded.add(i, v)

    queries = data[:5] + 0.01 * rng.standard_normal((5, 16)).astype(np.float32)
    rows_dense = dense.search_keys(queries, 4)
    rows_sharded = sharded.search_keys(queries, 4)
    for rd, rs in zip(rows_dense, rows_sharded):
        assert [k for k, _ in rd] == [k for k, _ in rs]
        np.testing.assert_allclose(
            [s for _, s in rd], [s for _, s in rs], rtol=1e-4, atol=1e-5
        )

    # removals propagate through the sharded path too
    top_key = rows_sharded[0][0][0]
    sharded.remove(top_key)
    rows_after = sharded.search_keys(queries[:1], 4)
    assert top_key not in [k for k, _ in rows_after[0]]


def test_fused_embed_search_mesh_matches_single_device():
    """The fused tokenize->embed->search executable with a sharded buffer
    (shard_map merge inside the jit) must return the same neighbors as the
    unsharded fused path."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.models.transformer import TransformerConfig
    from pathway_tpu.ops.knn import DeviceKnnIndex, FusedEmbedSearch

    tiny = TransformerConfig(
        vocab_size=256, hidden=32, layers=1, heads=2, mlp_dim=64,
        max_len=32, dtype="float32",
    )
    enc = SentenceEncoder("fused-mesh-test", config=tiny, max_len=16, seed=9)
    mesh = Mesh(np.array(jax.devices()[:8]), ("knn",))

    docs = [f"document body {i}" for i in range(32)]
    plain = FusedEmbedSearch(
        enc, DeviceKnnIndex(enc.dimension, reserved_space=64)
    )
    sharded = FusedEmbedSearch(
        enc, DeviceKnnIndex(enc.dimension, reserved_space=64, mesh=mesh)
    )
    plain.embed_and_add(range(32), docs)
    sharded.embed_and_add(range(32), docs)

    queries = [docs[5], docs[21], "something else entirely"]
    rows_plain = plain.search_texts(queries, 3)
    rows_sharded = sharded.search_texts(queries, 3)
    for rp, rs in zip(rows_plain, rows_sharded):
        assert [k for k, _ in rp] == [k for k, _ in rs]
        np.testing.assert_allclose(
            [s for _, s in rp], [s for _, s in rs], rtol=1e-4, atol=1e-5
        )

"""Ring/Ulysses sequence parallelism vs single-device forward (8-device
CPU mesh; same collectives ride ICI on hardware)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _mesh_sp():
    devs = np.array(jax.devices())
    return Mesh(devs.reshape(len(devs)), ("sp",))


def _rand_qkv(rng, b, h, l, d):
    mk = lambda: jnp.asarray(rng.normal(size=(b, h, l, d)), dtype=jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_sp_attention_exact(causal, strategy):
    from pathway_tpu.parallel import ring_attention, ulysses_attention
    from pathway_tpu.ops.kernels.flash_attention import _reference_attention

    mesh = _mesh_sp()
    sp = mesh.shape["sp"]
    rng = np.random.default_rng(0)
    b, h, l, d = 2, 8, 8 * sp, 16
    q, k, v = _rand_qkv(rng, b, h, l, d)
    mask = np.ones((b, l), dtype=np.int32)
    mask[1, l - 5:] = 0
    mask = jnp.asarray(mask)

    fn = ring_attention if strategy == "ring" else ulysses_attention
    kwargs = {} if strategy == "ring" else {"use_flash": False}
    sharded = shard_map(
        lambda q, k, v, m: fn(q, k, v, m, causal=causal, **kwargs),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3 + (P(None, "sp"),),
        out_specs=P(None, None, "sp", None),
        check_vma=False,
    )
    out = jax.jit(sharded)(q, k, v, mask)
    ref = _reference_attention(q, k, v, mask, 1.0 / np.sqrt(d), causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("pooling", ["none", "mean"])
def test_sequence_parallel_full_forward(pooling):
    from pathway_tpu.models.long_context import sequence_parallel_forward
    from pathway_tpu.models.transformer import (
        TransformerConfig,
        forward,
        init_params,
    )

    mesh = _mesh_sp()
    sp = mesh.shape["sp"]
    config = TransformerConfig(
        vocab_size=256, hidden=32, layers=2, heads=8, mlp_dim=64,
        max_len=8 * sp, causal=(pooling == "none"), pooling=pooling,
        dtype="float32",
    )
    params = init_params(jax.random.PRNGKey(0), config)
    rng = np.random.default_rng(1)
    b, l = 2, 8 * sp
    ids = jnp.asarray(
        rng.integers(0, config.vocab_size, size=(b, l)), dtype=jnp.int32
    )
    mask = np.ones((b, l), dtype=np.int32)
    mask[0, l - 3:] = 0
    mask = jnp.asarray(mask)

    out_sp = sequence_parallel_forward(
        params, config, ids, mask, mesh, attn="ring"
    )
    out_ref = jax.jit(
        lambda p, i, m: forward(p, config, i, m, use_flash=False)
    )(params, ids, mask)
    np.testing.assert_allclose(
        np.asarray(out_sp), np.asarray(out_ref), rtol=2e-4, atol=2e-4
    )


def test_sentence_encoder_dp_mesh_matches_single_device():
    """SentenceEncoder(mesh=...) shards the batch over 'dp'; embeddings
    must match the unsharded encoder exactly (same params, same inputs)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.models.transformer import TransformerConfig

    tiny = TransformerConfig(
        vocab_size=256, hidden=32, layers=1, heads=2, mlp_dim=64,
        max_len=32, dtype="float32",
    )
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("dp",))
    plain = SentenceEncoder("dp-test", config=tiny, max_len=16, seed=5)
    sharded = SentenceEncoder("dp-test-mesh", config=tiny, max_len=16, seed=5, mesh=mesh)

    texts = [f"document number {i}" for i in range(16)]  # buckets to 16
    a = plain.encode(texts)
    b = sharded.encode(texts)
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_data_index_mesh_sharded_end_to_end():
    """DataIndex with a mesh-backed BruteForceKnn answers through the
    engine with the index sharded over 8 virtual devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    import pathway_tpu as pw
    from pathway_tpu.internals.runner import run_tables
    from pathway_tpu.stdlib.indexing.data_index import DataIndex
    from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnn

    mesh = Mesh(np.array(jax.devices()[:8]), ("knn",))
    rng = np.random.default_rng(2)
    vecs = [rng.standard_normal(16).astype(np.float32) for _ in range(24)]
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(n=int), [(i,) for i in range(24)]
    )
    docs = docs.select(
        n=pw.this.n,
        v=pw.apply_with_type(lambda i: vecs[i], np.ndarray, pw.this.n),
    )
    index = DataIndex(
        docs, BruteForceKnn(docs.v, dimensions=16, mesh=mesh)
    )
    q = pw.debug.table_from_rows(
        pw.schema_from_types(qv=np.ndarray), [(vecs[11],)]
    )
    res = index.query_as_of_now(q.qv, number_of_matches=2).select(
        m=pw.this.n
    )
    (cap,) = run_tables(res)
    ((m,),) = [(r[-1],) for r in cap.state.rows.values()]
    assert m[0] == 11  # self-match first through the sharded path

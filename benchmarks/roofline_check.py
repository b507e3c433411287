"""MiniLM-L6 ingest roofline.

Answers "is the ingest MFU the model's ceiling or the framework's fault?"
by measuring, on the real chip (it fails without one — MFU needs a peak):

  1. big-matmul probe        — fraction of peak a large, MXU-friendly
                               matmul chain reaches (random bf16 inputs,
                               data-dependent chain so XLA cannot fold)
  2. minilm-shaped matmuls   — achievable TFLOPs at d=384/ffn=1536
                               shapes: the hard ceiling for this model's
                               own arithmetic
  3. pure encoder forward    — tokens/s of the jit forward on
                               PRE-UPLOADED device ids (adds attention,
                               norms, gathers, pooling; no host
                               transfer), one dispatch per chunk
  4. fused ingest            — the bench's device phase: host tokenize +
                               upload + forward + scatter into the KNN
                               buffer (FusedEmbedSearch.embed_and_add)

Every timed region ends in a checksum readback that the computation
feeds, so async dispatch cannot flatter any stage and a folded-away
computation shows up as a non-finite or zero checksum. MFU uses the same
useful-FLOPs model as bench.py (real mask tokens). Prints ONE JSON line,
stamped with the device it ran on.
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DOCS = 16384
CHUNK = 2048

_WORDS = (
    "stream table engine incremental dataflow tensor shard mesh batch "
    "window join reduce filter index vector embed query latency commit "
    "snapshot worker collective gather scatter fuse compile kernel"
).split()


def make_docs(n, rng):
    return [" ".join(rng.choices(_WORDS, k=48)) + f" doc{i}" for i in range(n)]


def _peak():
    """Peak bf16 FLOP/s of the attached chip; every number here is a share
    of it, so a device without one (the CPU) is an error."""
    from pathway_tpu.internals import costmodel

    peak = costmodel.device_peak_flops()
    if not peak:
        raise SystemExit(
            f"roofline_check needs an accelerator with a published peak; "
            f"jax found {costmodel.device_kind()!r}"
        )
    return peak


def _readback(x) -> float:
    """Host readback of a device scalar the computation feeds: ends the
    timed region and doubles as the checksum."""
    return float(np.asarray(x))


def big_matmul_tflops():
    import jax
    import jax.numpy as jnp

    m = 8192
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (m, 4096), dtype=jnp.bfloat16)
    # near-isometry: chains of matmuls stay finite and non-zero, so the
    # compiler cannot shortcut on inf/zero saturation and the checksum
    # proves real arithmetic happened
    b = jax.random.normal(k2, (4096, 4096), dtype=jnp.bfloat16) * (
        1.0 / 64.0
    )

    chain = 128  # ~0.4s of compute per dispatch at 50% peak: the
    # per-dispatch overhead amortizes away

    @jax.jit
    def mm(x, b):
        for _ in range(chain):
            x = x @ b
        return jnp.sum(x.astype(jnp.float32))

    chk = _readback(mm(a, b))  # warm + sanity
    assert np.isfinite(chk), chk
    t0 = time.perf_counter()
    for _ in range(2):
        chk = _readback(mm(a, b))
    dt = time.perf_counter() - t0
    assert np.isfinite(chk), chk
    return 2 * chain * 2 * m * 4096 * 4096 / dt


def minilm_shaped_tflops(seq_tokens: int):
    import jax
    import jax.numpy as jnp

    h, ffn, layers = 384, 1536, 6
    rows = CHUNK * seq_tokens
    key = jax.random.PRNGKey(1)
    x0 = jax.random.normal(key, (rows, h), dtype=jnp.bfloat16) * 0.1
    wq = jax.random.normal(key, (h, h), dtype=jnp.bfloat16) * 0.05
    wup = jax.random.normal(key, (h, ffn), dtype=jnp.bfloat16) * 0.05
    wdown = jax.random.normal(key, (ffn, h), dtype=jnp.bfloat16) * 0.05

    inner = 24  # many model-passes per dispatch: amortize dispatch overhead

    @jax.jit
    def net(x):
        for _ in range(inner):
            for _ in range(layers):
                for _ in range(4):  # q, k, v, o
                    x = x @ wq
                x = (x @ wup) @ wdown
                x = x * (1.0 / 16.0)  # keep the chain finite in bf16
        return jnp.sum(x.astype(jnp.float32))

    chk = _readback(net(x0))
    assert np.isfinite(chk), chk
    t0 = time.perf_counter()
    for _ in range(2):
        chk = _readback(net(x0))
    dt = time.perf_counter() - t0
    assert np.isfinite(chk), chk
    flops = (
        2 * inner * layers
        * (4 * 2 * rows * h * h + 2 * 2 * rows * h * ffn)
    )
    return flops / dt


def pure_forward_rate(docs):
    """Forward on DEVICE-RESIDENT ids: no tokenize, no upload."""
    import jax
    import jax.numpy as jnp

    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.models.tokenizer import encode_batch

    enc = SentenceEncoder.cached("all-MiniLM-L6-v2", max_len=64)
    chunks = [docs[i : i + CHUNK] for i in range(0, N_DOCS, CHUNK)]
    encoded = []
    mask_total = 0.0
    for c in chunks:
        ids, mask = encode_batch(enc.tokenizer, c, max_len=enc.max_len)
        mask_total += float(np.asarray(mask).sum())
        encoded.append(
            (jnp.asarray(np.asarray(ids)), jnp.asarray(np.asarray(mask)))
        )
    jax.block_until_ready([x for pair in encoded for x in pair])
    tokens_per_doc = mask_total / N_DOCS

    import jax.numpy as jnp

    warm = enc.lm(*encoded[0])
    _readback(jnp.sum(warm))
    sum_jit = jax.jit(lambda x: jnp.sum(x))
    t0 = time.perf_counter()
    outs = [enc.lm(ids, mask) for ids, mask in encoded]
    # device execution is in-order: one scalar readback that depends on
    # EVERY chunk's output closes the timed region honestly
    total = _readback(sum_jit(jnp.stack([jnp.sum(o) for o in outs])))
    rate = N_DOCS / (time.perf_counter() - t0)
    assert np.isfinite(total)
    return rate, tokens_per_doc


def fused_ingest_rate(docs):
    """The bench's device phase: tokenize -> upload -> embed -> scatter."""
    import jax

    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.ops.knn import DeviceKnnIndex, FusedEmbedSearch

    encoder = SentenceEncoder.cached("all-MiniLM-L6-v2", max_len=64)
    index = DeviceKnnIndex(
        encoder.dimension, metric="cos", reserved_space=N_DOCS
    )
    fused = FusedEmbedSearch(encoder, index)

    def drain():
        # the live buffer ends the donated scatter chain
        index._flush()
        jax.block_until_ready(index._buffer)

    fused.embed_and_add(range(CHUNK), docs[:CHUNK])
    drain()
    best = 0.0
    for _ in range(2):
        t0 = time.perf_counter()
        for start in range(0, N_DOCS, CHUNK):
            fused.embed_and_add(
                range(start, start + CHUNK), docs[start : start + CHUNK]
            )
        drain()
        best = max(best, N_DOCS / (time.perf_counter() - t0))
    return best


def useful_flops_per_doc(tokens_per_doc):
    from pathway_tpu.internals import costmodel

    return costmodel.encoder_flops_per_doc(tokens_per_doc)


def main():
    import jax

    from pathway_tpu.internals import compile_cache

    compile_cache.configure()
    device = jax.devices()[0]
    rng = random.Random(7)
    docs = make_docs(N_DOCS, rng)
    peak = _peak()
    big = big_matmul_tflops()
    pure, tokens_per_doc = pure_forward_rate(docs)
    shaped = minilm_shaped_tflops(int(round(tokens_per_doc)))
    fused = fused_ingest_rate(docs)
    fpd = useful_flops_per_doc(tokens_per_doc)
    print(
        json.dumps(
            {
                "metric": "minilm_ingest_roofline",
                "platform": device.platform,
                "device_kind": device.device_kind,
                "device_count": len(jax.devices()),
                "device_peak_tflops_bf16": round(peak / 1e12, 1),
                "big_matmul_tflops": round(big / 1e12, 1),
                "big_matmul_pct_of_peak": round(100 * big / peak, 1),
                "minilm_shaped_matmul_tflops": round(shaped / 1e12, 1),
                "minilm_shaped_pct_of_peak": round(100 * shaped / peak, 1),
                "pure_forward_docs_per_sec": round(pure, 1),
                "pure_forward_mfu_pct": round(100 * pure * fpd / peak, 2),
                "fused_ingest_docs_per_sec": round(fused, 1),
                "fused_ingest_mfu_pct": round(100 * fused * fpd / peak, 2),
                "tokens_per_doc": round(tokens_per_doc, 1),
                "note": (
                    "useful-FLOPs counts real mask tokens only, matching "
                    "bench.py; every timed region ends in a checksum "
                    "readback"
                ),
            }
        )
    )


if __name__ == "__main__":
    main()

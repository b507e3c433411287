#!/usr/bin/env python3
"""chip_smoke.py — one command that proves the main path runs on the chip.

Drives the MiniLM ingest-and-retrieve server once through the entry points
a user calls, at the full width of MiniLM-L6 (6 layers, hidden 384, 12
heads, FFN 1536, vocab 30,522, bf16), in ONE process that owns the chip:

  stamp    versions, backend, device_kind, device count, compile-cache
           directory, native tokenizer / wire codec.  Not a TPU -> exit.
  sync     jax.block_until_ready on a matmul chain feeding a donated-
           buffer scatter may not return before the chain's physical
           lower bound (FLOPs / peak) has passed.
  kernels  knn_topk, flash_attention and segment_attention compiled by
           Mosaic (interpret=False) at the main path's shapes, against
           lax.top_k / _reference_attention / _segment_attention on the
           same chip.
  serve    seeded corpus -> jsonl files -> pw.io.jsonlines.read(streaming)
           -> DocumentStore(SentenceTransformerEmbedder, BruteForceKnn)
           -> DocumentStoreServer.run(threaded, with_http_server): wait on
           /v1/statistics, 32 + 64 /v1/retrieve queries whose expected
           top-1 is known, delete a file and see its doc gone; then check
           it really was the chip (buffer devices, pipeline fallbacks,
           device monitor, peak table, device memory).
  knn_route  searches through BruteForceKnnFactory WITHOUT an embedder —
           the public route that reaches the knn_topk kernel.

Weights are random (seed 0) and the tokenizer is HashTokenizer: the machine
has no network and no checkpoint.  The wall and compile seconds printed are
set-up facts of this run, not benchmark numbers.

  python chip_smoke.py              one chip
  python chip_smoke.py --chips 4    same corpus under run(mesh="dp=4");
                                    needs the one-chip run's answers file
                                    in the same --out directory
  JAX_PLATFORMS=cpu python chip_smoke.py --dry-run
                                    tiny corpus, 2 layers, kernels in
                                    interpret mode, "platform": "cpu"

No phase's failure is caught: the first one ends the run non-zero.  Stdout
is two JSON lines: the report (stamp, each phase's pass, wall and compile
seconds), then the verdict, which has exactly these keys and is the last
line: {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import faulthandler
import json
import os
import random
import statistics
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

N_SHORT_DOCS = 16384  # ~51 tokens each, as bench.py's corpus
N_SHORT_FILES = 8
N_LONG_DOCS = 256  # 300-500 words: packed slabs and queries with L > 256
K = 6
N_SEQUENTIAL = 32
N_CONCURRENT = 64
REST_PORT = 18713
STATUS_PORT = 20000  # PrometheusServer: 20000 + process_id
HARD_DEADLINE_S = 1150  # the contract allows 1200 s, compilation included
V5E_PEAK_BF16_FLOPS = 197e12

_WORDS = (
    "stream table engine incremental dataflow tensor shard mesh batch "
    "window join reduce filter index vector embed query latency commit "
    "snapshot worker collective gather scatter fuse compile kernel"
).split()


def log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# -- compile accounting ------------------------------------------------------


class CompileClock:
    """Sums jax's own backend-compile event durations (cache look-ups
    included) under whichever phase is current, from any thread."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.phase = "startup"
        self.seconds: dict = {}
        self.compiles: dict = {}
        self.cache_hits = 0

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            with self._lock:
                p = self.phase
                self.seconds[p] = self.seconds.get(p, 0.0) + duration
                self.compiles[p] = self.compiles.get(p, 0) + 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1


# -- corpus ------------------------------------------------------------------


def make_corpus(n_short: int, n_long: int) -> list:
    """Seeded corpus: bench.py's 48-word docs, then long docs of 300-500
    words.  Every doc ends in a unique token, so a doc's own text is a
    query whose top-1 must be that doc."""
    rng = random.Random(7)
    docs = [
        " ".join(rng.choices(_WORDS, k=48)) + f" doc{i}"
        for i in range(n_short)
    ]
    for j in range(n_long):
        # the first eight share one length so their query bucket compiles
        # once; the rest spread over 300-500 words
        words = 400 if j < 8 else rng.randint(300, 500)
        docs.append(" ".join(rng.choices(_WORDS, k=words)) + f" long{j}")
    return docs


def write_corpus(docs: list, n_short: int, docs_dir: str, n_files: int) -> list:
    """Short docs over `n_files` jsonl files, long docs in one more.
    Returns the list of (path, first_doc, end_doc) in write order."""
    os.makedirs(docs_dir, exist_ok=True)
    for stale in os.listdir(docs_dir):
        os.remove(os.path.join(docs_dir, stale))
    per_file = n_short // n_files
    spans = [
        (f"docs_{fi:03d}.jsonl", fi * per_file, (fi + 1) * per_file)
        for fi in range(n_files)
    ]
    spans.append((f"docs_{n_files:03d}_long.jsonl", n_short, len(docs)))
    files = []
    for name, lo, hi in spans:
        path = os.path.join(docs_dir, name)
        with open(path, "w") as f:
            for d in docs[lo:hi]:
                f.write(json.dumps({"data": d}) + "\n")
        files.append((path, lo, hi))
    return files


# -- http --------------------------------------------------------------------


def http_json(port: int, route: str, payload=None, timeout: float = 240.0):
    url = f"http://127.0.0.1:{port}{route}"
    if payload is None:
        req = urllib.request.Request(url)
    else:
        req = urllib.request.Request(
            url,
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        body = resp.read()
    return json.loads(body) if route != "/metrics" else body.decode()


def retrieve(text: str) -> list:
    return http_json(
        REST_PORT,
        "/v1/retrieve",
        {
            "query": text,
            "k": K,
            "metadata_filter": None,
            "filepath_globpattern": None,
        },
    )


def wait_for_doc_count(expected: int, server_thread, deadline_s: float) -> None:
    deadline = time.monotonic() + deadline_s
    seen = None
    while time.monotonic() < deadline:
        check(server_thread.is_alive(), "the server thread died")
        try:
            stats = http_json(REST_PORT, "/v1/statistics", {}, timeout=120.0)
        except OSError:
            time.sleep(0.5)  # the webserver is not up yet
            continue
        seen = stats.get("file_count")
        if seen == expected:
            return
        time.sleep(0.5)
    raise AssertionError(
        f"/v1/statistics reports {seen} docs, expected {expected}, "
        f"after {deadline_s:.0f}s"
    )


# -- phases ------------------------------------------------------------------


def phase_stamp(ctx: dict) -> dict:
    import jax
    import jaxlib

    from pathway_tpu import native
    from pathway_tpu.internals import compile_cache

    devices = jax.devices()
    backend = jax.default_backend()
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", "present")
    except ImportError:
        libtpu_version = None
    stamp = {
        "python": sys.version.split()[0],
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "backend": backend,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "compile_cache_dir": ctx["cache_dir"],
        "compile_cache_env": os.environ.get(compile_cache.ENV_VAR),
        "native_tokenizer": native.load() is not None,
        "native_wire_codec": native.load_wire_ext() is not None,
        "weights": "random, seed 0",
        "tokenizer": "HashTokenizer",
        "dry_run": ctx["dry"],
    }
    log(f"stamp: {json.dumps(stamp)}")
    if ctx["dry"]:
        check(backend == "cpu", "--dry-run is for JAX_PLATFORMS=cpu")
    elif backend != "tpu":
        log(f"no TPU: jax.default_backend() is {backend!r}")
        sys.exit(3)
    check(
        len(devices) >= ctx["chips"],
        f"--chips {ctx['chips']} but {len(devices)} device(s) attached",
    )
    check(stamp["native_tokenizer"], "native tokenizer did not build")
    check(stamp["native_wire_codec"], "native wire codec did not build")
    ctx["stamp"] = stamp
    return {}


def phase_sync(ctx: dict) -> dict:
    """block_until_ready must cover a donated-buffer scatter chain: the
    wait cannot be shorter than the chain's FLOPs over the chip's peak."""
    import jax
    import jax.numpy as jnp

    m, d, chain = (256, 256, 4) if ctx["dry"] else (8192, 4096, 64)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (m, d), dtype=jnp.bfloat16)
    b = jax.random.normal(k2, (d, d), dtype=jnp.bfloat16) * (1.0 / 64.0)

    def body(buf, a, b):
        x = a
        for _ in range(chain):
            x = x @ b
        # the scatter consumes the end of the chain, as the index scatter
        # consumes the encoder's output
        return buf.at[jnp.arange(8)].set(x[:8, : buf.shape[1]].astype(buf.dtype))

    step = jax.jit(body, donate_argnums=(0,))
    buf = jnp.zeros((1024, 128), dtype=jnp.float32)
    buf = jax.block_until_ready(step(buf, a, b))  # compile + warm
    float(buf[0, 0])  # the readback's own slice program compiles here
    waits = []
    for _ in range(3):
        t0 = time.perf_counter()
        buf = step(buf, a, b)
        t_dispatched = time.perf_counter()
        jax.block_until_ready(buf)
        t_ready = time.perf_counter()
        float(buf[0, 0])  # dependent readback: nothing may be left to wait for
        t_read = time.perf_counter()
        waits.append((t_dispatched - t0, t_ready - t0, t_read - t_ready))
    flops = 2.0 * chain * m * d * d
    facts = {
        "dispatch_s": round(min(w[0] for w in waits), 6),
        "block_until_ready_s": round(min(w[1] for w in waits), 6),
        "readback_after_s": round(max(w[2] for w in waits), 6),
    }
    if not ctx["dry"]:
        floor_s = flops / V5E_PEAK_BF16_FLOPS
        facts["flops_over_peak_s"] = round(floor_s, 6)
        facts["chain_tflops"] = round(flops / min(w[1] for w in waits) / 1e12, 1)
        log(f"sync: {json.dumps(facts)}")
        # the chain runs close to peak, so the wait sits just above the
        # floor; an early return would be orders of magnitude below it.
        # Half the floor separates the two without a 0.1% margin.
        check(
            min(w[1] for w in waits) >= 0.5 * floor_s,
            f"block_until_ready returned after {facts['block_until_ready_s']}s"
            f", before the chain could have run ({floor_s:.4f}s at peak)",
        )
        check(
            max(w[2] for w in waits) < 0.5 * floor_s,
            "a readback after block_until_ready still waited "
            f"{facts['readback_after_s']}s: the buffer was not ready",
        )
    return facts


def ranking_agrees(got_i, ref_i, ref_s, noise: float) -> bool:
    """Ids must agree at every rank whose reference score is separated
    from both neighbours by more than `noise`; the last rank also competes
    with an unseen k+1-th candidate, so it is not judged."""
    import numpy as np

    gap = np.abs(np.diff(ref_s, axis=1))
    separation = np.full(ref_s.shape, np.inf)
    separation[:, :-1] = gap
    separation[:, 1:] = np.minimum(separation[:, 1:], gap)
    decided = separation > noise
    decided[:, -1] = False
    return bool((got_i[decided] == ref_i[decided]).all())


def phase_kernels(ctx: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pathway_tpu.ops.kernels.flash_attention import (
        _reference_attention,
        flash_attention,
    )
    from pathway_tpu.ops.kernels.knn_topk import knn_topk

    dry = ctx["dry"]
    interpret = dry  # on the chip the kernels must go through Mosaic
    facts: dict = {"interpret": interpret}

    # knn_topk over the main path's index shape, against lax.top_k on the
    # dense scores computed on the same device
    n, d = (512, 384) if dry else (16384, 384)
    rng = np.random.default_rng(1)
    index = rng.standard_normal((n, d)).astype(np.float32)
    index /= np.linalg.norm(index, axis=1, keepdims=True)
    valid = np.ones((n,), dtype=bool)
    valid[n // 3] = False  # a dead slot must never be returned
    index_d, valid_d = jnp.asarray(index), jnp.asarray(valid)
    dense_topk = jax.jit(
        lambda x, v, q: jax.lax.top_k(
            jnp.where(
                v[None, :],
                jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST),
                -jnp.inf,
            ),
            K,
        )
    )
    knn_err = {}
    for q_n in (1, 8, 64):
        picks = rng.integers(0, n, size=q_n)
        queries = index[picks] + 0.05 * rng.standard_normal((q_n, d)).astype(
            np.float32
        )
        queries /= np.linalg.norm(queries, axis=1, keepdims=True)
        got_s, got_i = knn_topk(
            index_d, valid_d, jnp.asarray(queries), K, metric="ip",
            interpret=interpret,
        )
        ref_s, ref_i = dense_topk(index_d, valid_d, jnp.asarray(queries))
        got_s, got_i = np.asarray(got_s), np.asarray(got_i)
        ref_s, ref_i = np.asarray(ref_s), np.asarray(ref_i)
        check(got_s.shape == (q_n, K) and got_i.shape == (q_n, K),
              f"knn_topk Q={q_n}: shapes {got_s.shape} {got_i.shape}")
        check(np.isfinite(got_s).all(), f"knn_topk Q={q_n}: non-finite scores")
        err = float(np.max(np.abs(got_s - ref_s)))
        check(err < 2e-2, f"knn_topk Q={q_n}: max score error {err}")
        check(ranking_agrees(got_i, ref_i, ref_s, noise=4 * err + 1e-6),
              f"knn_topk Q={q_n}: ids differ from lax.top_k")
        check((got_i != n // 3).all(), f"knn_topk Q={q_n}: returned a dead slot")
        knn_err[f"Q={q_n}"] = round(err, 6)
    facts["knn_topk_max_score_err"] = knn_err

    # flash attention at the encoder's long-sequence shape (head_dim 32,
    # padding mask) and at a decoder shape (head_dim 128, causal)
    shapes = (
        [((1, 2, 256, 32), False), ((1, 2, 256, 128), True)]
        if dry
        else [((8, 12, 512, 32), False), ((1, 32, 1024, 128), True)]
    )
    flash_err = {}
    for (b, h, l, hd), causal in shapes:
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(l + hd), 3)
        q = jax.random.normal(kq, (b, h, l, hd), dtype=jnp.bfloat16)
        k = jax.random.normal(kk, (b, h, l, hd), dtype=jnp.bfloat16)
        v = jax.random.normal(kv, (b, h, l, hd), dtype=jnp.bfloat16)
        mask = np.ones((b, l), dtype=np.int32)
        if not causal:
            mask[-1, (3 * l) // 4:] = 0  # a padded row, as encode_batch makes
        mask = jnp.asarray(mask)
        out = flash_attention(q, k, v, mask, causal=causal, interpret=interpret)
        ref = _reference_attention(
            q, k, v, mask, 1.0 / float(np.sqrt(hd)), causal
        )
        out32 = np.asarray(out.astype(jnp.float32))
        ref32 = np.asarray(ref.astype(jnp.float32))
        check(out32.shape == (b, h, l, hd), f"flash shape {out32.shape}")
        check(np.isfinite(out32).all(), "flash attention: non-finite output")
        err = float(np.max(np.abs(out32 - ref32)))
        check(err < 5e-2, f"flash {(b, h, l, hd)} causal={causal}: err {err}")
        flash_err[f"{(b, h, l, hd)} causal={causal}"] = round(err, 5)
    facts["flash_attention_max_err"] = flash_err

    # the packed ingest path's fused kernel at the e5 slab's geometry (L
    # not a multiple of the tile, mixed segments, trailing padding, a row
    # that is all padding) and at MiniLM's, against the dense definition
    # with its scores at HIGHEST precision
    from pathway_tpu.models.transformer import _segment_attention
    from pathway_tpu.ops.kernels.segment_attention import segment_attention

    slabs = (
        [(2, 2, 40, 64), (2, 4, 256, 32)]
        if dry
        else [(8, 16, 504, 64), (8, 12, 256, 32)]
    )
    segment_err = {}
    for b, h, l, hd in slabs:
        rng = np.random.default_rng(l + hd)
        qkv = jnp.asarray(
            rng.standard_normal((b, l, 3 * h * hd)), dtype=jnp.bfloat16
        )
        seg = np.zeros((b, l), dtype=np.int32)
        for r in range(b - 1):  # row r packs r + 1 documents; the last none
            bounds = np.linspace(0, l - r * (l // 16), r + 2).astype(int)
            for i in range(r + 1):
                seg[r, bounds[i]:bounds[i + 1]] = i + 1
        seg_d = jnp.asarray(seg)
        out = segment_attention(qkv, seg_d, h, interpret=interpret)
        q, k, v = (
            x.astype(jnp.float32).reshape(b, l, h, hd).transpose(0, 2, 1, 3)
            for x in jnp.split(qkv, 3, axis=-1)
        )
        with jax.default_matmul_precision("highest"):
            ref = _segment_attention(q, k, v, seg_d, 1.0 / float(np.sqrt(hd)))
        ref32 = np.asarray(ref.transpose(0, 2, 1, 3).reshape(b, l, h * hd))
        out32 = np.asarray(out.astype(jnp.float32))
        check(out32.shape == (b, l, h * hd), f"segment shape {out32.shape}")
        check(np.isfinite(out32).all(), "segment attention: non-finite output")
        err = float(np.max(np.abs(out32 - ref32)[seg > 0]))
        check(err < 5e-2, f"segment attention {(b, h, l, hd)}: err {err}")
        segment_err[f"{(b, h, l, hd)}"] = round(err, 5)
    facts["segment_attention_max_err"] = segment_err
    return facts


def phase_serve(ctx: dict) -> dict:
    import jax
    import numpy as np

    import pathway_tpu as pw
    from pathway_tpu.engine.index_node import ExternalIndexNode
    from pathway_tpu.internals import costmodel, mesh_backend
    from pathway_tpu.internals.device_probe import device_degraded
    from pathway_tpu.internals.runner import last_engine
    from pathway_tpu.models.transformer import MINILM_L6
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        BruteForceKnnFactory,
    )
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

    dry, chips = ctx["dry"], ctx["chips"]
    n_short, n_files, n_long = (
        (64, 2, 4) if dry else (N_SHORT_DOCS, N_SHORT_FILES, N_LONG_DOCS)
    )
    n_seq, n_conc = (4, 8) if dry else (N_SEQUENTIAL, N_CONCURRENT)
    docs = make_corpus(n_short, n_long)
    doc_of_text = {t: i for i, t in enumerate(docs)}
    docs_dir = os.path.join(ctx["out"], "docs")
    files = write_corpus(docs, n_short, docs_dir, n_files)
    facts: dict = {"docs": len(docs), "files": len(files)}

    # full width always; the dry run cuts depth to keep the CPU test short
    embedder_kwargs = {"max_len": 512}
    if dry:
        import dataclasses

        embedder_kwargs["config"] = dataclasses.replace(MINILM_L6, layers=2)
    config = embedder_kwargs.get("config", MINILM_L6)
    check(
        (config.hidden, config.heads, config.mlp_dim, config.vocab_size,
         config.dtype) == (384, 12, 1536, 30522, "bfloat16"),
        f"not MiniLM-L6 width: {config}",
    )
    facts["layers"] = config.layers

    table = pw.io.jsonlines.read(
        docs_dir,
        schema=pw.schema_from_types(data=str),
        mode="streaming",
        batch_per_file=True,
        refresh_interval=0.5,
    )
    embedder = SentenceTransformerEmbedder(**embedder_kwargs)
    factory = BruteForceKnnFactory(embedder=embedder, reserved_space=len(docs))
    store = DocumentStore(table, retriever_factory=factory)
    server = DocumentStoreServer("127.0.0.1", REST_PORT, store)
    run_kwargs = {"mesh": f"dp={chips}"} if chips > 1 else {}
    t_start = time.perf_counter()
    server_thread = server.run(threaded=True, with_http_server=True, **run_kwargs)

    wait_for_doc_count(len(docs), server_thread, 900.0)
    facts["ingest_wall_s"] = round(time.perf_counter() - t_start, 2)
    log(f"serve: {len(docs)} docs through the store in {facts['ingest_wall_s']}s")

    # queries: known docs, so the expected top-1 is the doc itself.  A few
    # are long docs (L > 256 opens the flash-attention gate), and some come
    # from the file deleted below.
    doomed_path, doomed_lo, doomed_hi = files[n_files - 1]
    rng = random.Random(11)
    long_ids = list(range(n_short, n_short + min(4, n_long)))
    doomed_ids = [doomed_lo, doomed_hi - 1]
    pool = [i for i in range(n_short) if not doomed_lo <= i < doomed_hi]
    picked = rng.sample(pool, (n_seq - 3) + (n_conc - 3))
    seq_ids = doomed_ids[:1] + long_ids[:2] + picked[: n_seq - 3]
    conc_ids = doomed_ids[1:] + long_ids[2:] + picked[n_seq - 3:]

    def ask(doc_id: int) -> tuple:
        t0 = time.perf_counter()
        rows = retrieve(docs[doc_id])
        wall_ms = (time.perf_counter() - t0) * 1000.0
        check(rows, f"doc {doc_id}: empty result")
        check(len(rows) == K, f"doc {doc_id}: {len(rows)} rows, wanted {K}")
        top = rows[0]
        check(top["text"] == docs[doc_id],
              f"doc {doc_id}: top-1 is {top['text'][-24:]!r} at {top['score']}")
        check(top["score"] > 0.99 and np.isfinite(top["score"]),
              f"doc {doc_id}: top-1 cosine {top['score']}")
        answer = [[doc_of_text[r["text"]], round(r["score"], 6)] for r in rows]
        return doc_id, answer, wall_ms

    answers = {}
    seq_ms = []
    for doc_id in seq_ids:
        _, answer, wall_ms = ask(doc_id)
        answers[doc_id] = answer
        seq_ms.append(wall_ms)
    with concurrent.futures.ThreadPoolExecutor(n_conc) as pool_x:
        t0 = time.perf_counter()
        for doc_id, answer, _ in pool_x.map(ask, conc_ids):
            answers[doc_id] = answer
        conc_wall = time.perf_counter() - t0
    check(len(answers) == n_seq + n_conc, f"{len(answers)} distinct answers")
    facts["queries_answered"] = len(answers)
    facts["sequential_query_ms"] = {
        "first_cold": round(seq_ms[0], 1),
        "median": round(statistics.median(seq_ms), 2),
        "max": round(max(seq_ms), 1),
    }
    facts["concurrent_batch_wall_s"] = round(conc_wall, 2)
    log(f"serve: {len(answers)} queries answered, expected top-1 each")

    # retraction: delete one input file; a (cached) query for one of its
    # docs must stop returning it
    os.remove(doomed_path)
    wait_for_doc_count(len(docs) - (doomed_hi - doomed_lo), server_thread, 300.0)
    for doc_id in doomed_ids:
        rows = retrieve(docs[doc_id])
        check(rows, f"deleted doc {doc_id}: empty result")
        texts = [r["text"] for r in rows]
        check(docs[doc_id] not in texts, f"deleted doc {doc_id} still returned")
        check(all(doomed_lo > doc_of_text[t] or doc_of_text[t] >= doomed_hi
                  for t in texts),
              f"deleted doc {doc_id}: a doc of the deleted file is returned")
    facts["deleted_docs_gone"] = True
    log("serve: deleted file's docs are gone from the answers")

    # -- it really was the chip ---------------------------------------------
    engine = last_engine()
    (node,) = [
        n for n in engine.nodes
        if isinstance(n, ExternalIndexNode) and hasattr(n.index, "fused")
    ]
    impl = node.index
    buf = impl.knn._buffer
    platform = ctx["stamp"]["platform"]
    buf_devices = sorted(buf.devices(), key=lambda d: d.id)
    check(all(d.platform == platform for d in buf_devices),
          f"index buffer lives on {buf_devices}")
    check(len(buf_devices) == chips,
          f"index buffer spans {len(buf_devices)} device(s), wanted {chips}")
    facts["index_buffer"] = {
        "shape": list(buf.shape),
        "devices": [str(d) for d in buf_devices],
    }
    check(not impl._pipeline_broken, "the ingest pipeline fell back to sync")
    check(impl._pipeline is not None, "the ingest pipeline never started")
    pipe = impl._pipeline.stats()
    check(pipe["rows"] == len(docs), f"pipeline dispatched {pipe['rows']} rows")
    facts["pipeline"] = {k: pipe[k] for k in ("dispatched", "rows", "pad_waste_ratio")}

    metrics = http_json(STATUS_PORT, "/metrics")
    fallback_lines = [
        ln for ln in metrics.splitlines()
        if ln.startswith("pathway_device_pipeline_fallbacks_total")
    ]
    check(fallback_lines, "pathway_device_pipeline_fallbacks_total not exported")
    check(all(float(ln.split()[-1]) == 0.0 for ln in fallback_lines),
          f"pipeline fallbacks: {fallback_lines}")
    status = http_json(STATUS_PORT, "/status")
    check(status["device_pipeline"]["fallbacks"] == 0, "pipeline fallbacks != 0")
    device = status["device"]
    check(device.get("healthy") is True and device.get("state") == "healthy",
          f"/status device: {device}")
    check(device.get("probes", 0) >= 2, f"device monitor probed {device.get('probes')}x")
    check(not device_degraded(), "device_degraded() at the end of the run")
    facts["device_monitor"] = {
        k: device.get(k) for k in ("status", "probes", "rtt_ms", "flaps")
    }

    peak = costmodel.device_peak_flops()
    check(peak == (0.0 if dry else V5E_PEAK_BF16_FLOPS),
          f"costmodel peak for {costmodel.device_kind()!r} is {peak}")
    facts["peak_bf16_flops"] = peak

    param_bytes = sum(
        int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(embedder.encoder.lm.params)
    )
    index_bytes = int(buf.nbytes)
    facts["param_bytes"], facts["index_bytes"] = param_bytes, index_bytes
    if not dry:
        per_device = []
        for d in buf_devices:
            stats = d.memory_stats()
            per_device.append(
                {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use")}
            )
        facts["device_memory"] = per_device
        check(per_device[0]["peak_bytes_in_use"] > index_bytes // chips + param_bytes,
              f"device 0 peak {per_device[0]} below index + params")

    if chips > 1:
        backend = mesh_backend.active_backend()
        check(backend is not None and backend.dp == chips,
              "mesh backend is not live")
        check(impl.fused.backend is backend, "the encoder runs outside the mesh")
        shards = buf.addressable_shards
        check(len({s.device.id for s in shards}) == chips,
              f"index shards on {[s.device for s in shards]}")
        check(all(s.data.nbytes == index_bytes // chips for s in shards),
              "index shards are not a quarter of the rows each")
        spec = backend.batch_sharding().spec
        check(tuple(spec) == ("dp", None), f"batch sharding {spec}")
        replica_rows = [r["rows"] for r in impl._pipeline.replica_stats()]
        check(len(replica_rows) == chips and all(replica_rows)
              and sum(replica_rows) == len(docs),
              f"per-replica ingest rows {replica_rows}")
        facts["replica_rows"] = replica_rows
        mesh = status["mesh"]
        check(mesh["active"] and mesh["sharded_ingest"]
              and mesh["device_count"] == chips, f"/status mesh: {mesh}")
        if not dry:
            for mem in facts["device_memory"]:
                check(mem["bytes_in_use"] >= index_bytes // chips,
                      f"a chip holds less than its index shard: {mem}")

    # answers: written by the one-chip run, compared by the mesh run
    answers_path = os.path.join(ctx["out"], "answers_1chip.json")
    if chips == 1:
        with open(answers_path, "w") as f:
            json.dump({str(k): v for k, v in answers.items()}, f)
    else:
        check(os.path.exists(answers_path),
              f"{answers_path} missing: run `python chip_smoke.py` with the "
              "same --out first")
        with open(answers_path) as f:
            one_chip = {int(k): v for k, v in json.load(f).items()}
        check(sorted(one_chip) == sorted(answers), "different query sets")
        facts["answers_vs_one_chip"] = compare_answers(one_chip, answers)

    engine.terminate_flag.set()
    server_thread.join(timeout=60)
    check(not server_thread.is_alive(), "the server did not stop")
    pw.G.clear()
    return facts


def compare_answers(one_chip: dict, mesh: dict) -> dict:
    """The mesh run must give the one-chip run's answers: the same top-1,
    the same scores rank by rank to bf16 noise, and the same docs — except
    that docs whose scores differ by less than that noise may trade places
    (ranks 2-6 of this corpus sit ~1e-3 apart), the last place included."""
    tol = 2e-3
    worst = 0.0
    swaps = 0
    problems = []
    for doc_id, ref in one_chip.items():
        got = mesh[doc_id]
        if got[0][0] != ref[0][0]:
            problems.append(f"query {doc_id}: top-1 {got[0]} vs {ref[0]}")
        ref_score = {i: s for i, s in ref}
        for (gi, gs), (ri, rs) in zip(got, ref):
            worst = max(worst, abs(gs - rs))
            if abs(gs - rs) > tol:
                problems.append(f"query {doc_id}: score {gs} vs one-chip {rs}")
            if gi != ri:
                swaps += 1
                # a doc the one-chip run ranked elsewhere, or just below
                # its k-th place: its own score must be within noise
                if abs(ref_score.get(gi, ref[-1][1]) - gs) > tol:
                    problems.append(f"query {doc_id}: doc {gi} in place of {ri}")
    facts = {"queries": len(one_chip), "max_score_diff": round(worst, 6),
             "near_tie_swaps": swaps}
    check(not problems,
          f"{len(problems)} differences from the one-chip answers "
          f"({facts}); first: {problems[:3]}")
    return facts


def phase_knn_route(ctx: dict) -> dict:
    """BruteForceKnnFactory without an embedder: ops/knn._compiled_search,
    which on a TPU is the knn_topk kernel."""
    import numpy as np

    import pathway_tpu as pw
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        BruteForceKnnFactory,
    )

    n, d, q_n = (64, 384, 3) if ctx["dry"] else (4096, 384, 5)
    rng = np.random.default_rng(5)
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(doc=int, vec=np.ndarray),
        [(i, vecs[i]) for i in range(n)],
    )
    picks = [int(p) for p in rng.integers(0, n, size=q_n)]
    queries = pw.debug.table_from_rows(
        pw.schema_from_types(want=int, qvec=np.ndarray),
        [(p, vecs[p] + 0.01 * rng.standard_normal(d).astype(np.float32))
         for p in picks],
    )
    index = BruteForceKnnFactory(dimensions=d, reserved_space=n).build_index(
        docs.vec, docs
    )
    reply = index.query_as_of_now(queries.qvec, number_of_matches=3).select(
        want=pw.this.want,
        got=pw.this.doc,
        score=pw.this._pw_index_reply_score,
    )
    _keys, cols = pw.debug.table_to_dicts(reply)
    check(len(cols["want"]) == q_n, f"{len(cols['want'])} replies")
    for key, want in cols["want"].items():
        got, score = cols["got"][key], cols["score"][key]
        check(len(got) == 3, f"query for {want}: {len(got)} matches")
        check(got[0] == want and score[0] > 0.99,
              f"query for {want}: got {got} at {score}")
    pw.G.clear()
    return {"queries": q_n, "index_rows": n}


def phase_too_many_chips(ctx: dict) -> dict:
    """run(mesh=...) asking for more devices than attached must raise."""
    import jax

    from pathway_tpu.xpacks.llm.servers import BaseRestServer

    want = 2 * len(jax.devices())
    try:
        BaseRestServer("127.0.0.1", REST_PORT + 1).run(mesh=f"dp={want}")
    except ValueError as exc:
        return {"mesh": f"dp={want}", "raised": str(exc)}
    raise AssertionError(f"run(mesh='dp={want}') did not raise")


# -- driver ------------------------------------------------------------------


def fail_fast_when_the_server_thread_dies() -> None:
    """pw.run on the server thread raising (a program the compiler refuses,
    say) leaves every pending request waiting for its timeout; end the run
    at once instead, after the traceback."""
    default_hook = threading.excepthook

    def hook(args) -> None:
        default_hook(args)
        if args.thread is not None and args.thread.name == "pw-server":
            log("the server thread died; exiting")
            sys.stderr.flush()
            os._exit(1)

    threading.excepthook = hook



def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, default=1,
                        help="run the server under mesh dp=CHIPS")
    parser.add_argument("--dry-run", action="store_true",
                        help="tiny CPU run; the caller sets JAX_PLATFORMS=cpu")
    parser.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "chip_smoke"),
                        help="directory for the corpus, answers and result")
    args = parser.parse_args()
    faulthandler.dump_traceback_later(HARD_DEADLINE_S, exit=True)
    fail_fast_when_the_server_thread_dies()

    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    # the native .so files are built from the tree, into the output directory
    os.environ["PATHWAY_NATIVE_CACHE"] = os.path.join(out, "native")
    # the device monitor stays on and probes often enough to be seen twice
    os.environ.pop("PATHWAY_DEVICE_PROBE", None)
    os.environ["PATHWAY_DEVICE_PROBE_INTERVAL_S"] = "2"

    from pathway_tpu.internals import compile_cache

    clock = CompileClock()
    ctx = {
        "dry": args.dry_run,
        "chips": args.chips,
        "out": out,
        "cache_dir": compile_cache.configure(),
    }
    clock.install()

    phases = [
        ("stamp", phase_stamp),
        ("sync", phase_sync),
        ("kernels", phase_kernels),
        ("serve", phase_serve),
        ("knn_route", phase_knn_route),
    ]
    if args.chips > 1:
        phases.append(("too_many_chips", phase_too_many_chips))
    phase_reports = {}
    t_run = time.perf_counter()
    for name, fn in phases:
        clock.phase = name
        log(f"phase {name} ...")
        t0 = time.perf_counter()
        facts = fn(ctx)
        phase_reports[name] = {
            "pass": True,
            "wall_s": round(time.perf_counter() - t0, 2),
            "compile_s": round(clock.seconds.get(name, 0.0), 2),
            "compiles": clock.compiles.get(name, 0),
            **facts,
        }
        log(f"phase {name} passed: {json.dumps(phase_reports[name])}")

    stamp = ctx["stamp"]
    cache_files = sum(len(fs) for _, _, fs in os.walk(ctx["cache_dir"]))
    verdict = {
        "ok": True,
        "device": {
            "platform": stamp["platform"],
            "kind": stamp["device_kind"],
            "count": stamp["device_count"],
        },
    }
    report = {
        **verdict,
        "chips_used": args.chips,
        "note": "set-up facts of one run, not benchmark numbers",
        "stamp": stamp,
        "wall_s": round(time.perf_counter() - t_run, 2),
        "compile_s_total": round(sum(clock.seconds.values()), 2),
        "compile_cache_hits": clock.cache_hits,
        "compile_cache_files": cache_files,
        "phases": phase_reports,
    }
    with open(os.path.join(out, f"result_chips{args.chips}.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report), flush=True)
    # the last line of stdout is the verdict alone, with exactly these keys
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

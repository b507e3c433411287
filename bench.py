"""Benchmark: the REAL framework path (BASELINE.json config[0]).

Drives fs connector -> DocumentStore pipeline (parse -> split -> fused
embed+index on TPU) -> retrieve_query, i.e. the exact call stack of
SURVEY.md section 3.4 — not the raw ops. The reference runs torch
SentenceTransformer + per-worker replicated f64 ndarray KNN
(embedders.py:342, brute_force_knn_integration.rs); here document batches
hit the MXU through one jit-compiled dispatch (tokenize -> bf16 encoder ->
scatter into the device KNN buffer) and each query is a single fused
tokenize -> embed -> similarity -> top_k device call.

ONE process that needs the chip: it fails (non-zero, no value printed)
when jax finds no TPU, starts no child process, and stamps platform,
device_kind and device count on its one JSON line.

Reported:
  * docs/sec embedded+indexed through the full pipeline — every measured
    run and their median (after an identical warmup run has paid all XLA
    compiles);
  * serving p50/p90 per query through the engine (subject -> engine ->
    fused search -> subscribe) and QPS with 64 queries in flight;
  * the engine-independent device-phase ingest rate (classic and
    pipelined) and the MFU both imply.

Turning this into benchmark cells is a later issue's work.
"""

from __future__ import annotations

import json
import os
import queue
import random
import statistics
import sys
import tempfile
import threading
import time

import numpy as np

N_DOCS = 16384
N_FILES = 8
N_QUERIES = 32
N_RUNS = 3  # measured runs; all are reported, the value is their median
K = 6
METRIC = (
    "docs/sec embedded+indexed, framework path "
    "(fs connector -> DocumentStore -> fused TPU KNN)"
)
BASELINE_DOCS_PER_SEC = 10_000.0

_WORDS = (
    "stream table engine incremental dataflow tensor shard mesh batch "
    "window join reduce filter index vector embed query latency commit "
    "snapshot worker collective gather scatter fuse compile kernel"
).split()


def make_docs(n: int, rng: random.Random) -> list[str]:
    return [" ".join(rng.choices(_WORDS, k=48)) + f" doc{i}" for i in range(n)]


class _QuerySubject:
    """Feeds retrieve queries from a queue; commits per query so each one
    forms its own engine batch (serving-latency measurement)."""

    def __init__(self, q: queue.Queue):
        import pathway_tpu as pw

        base = pw.io.python.ConnectorSubject

        class Subject(base):
            def run(self) -> None:
                while True:
                    item = q.get()
                    if item is None:
                        return
                    self.next(**item)
                    self.commit()

        self.subject = Subject()


def run_pipeline(
    docs_path: str,
    query_q: queue.Queue,
    resp_q: queue.Queue,
    count_q: queue.Queue,
):
    """Build the framework graph and run it (blocks until sources close)."""
    import pathway_tpu as pw
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        BruteForceKnnFactory,
    )
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    G.clear()
    # streaming with one barrier commit per file: host parse/split of file
    # N+1 runs while the device embeds file N (async dispatch), and the
    # batch boundaries are deterministic — no autocommit alignment noise
    docs = pw.io.jsonlines.read(
        docs_path,
        schema=pw.schema_from_types(data=str),
        mode="streaming",
        batch_per_file=True,
        refresh_interval=3600.0,  # all files exist up front
    )
    embedder = SentenceTransformerEmbedder(max_len=64)
    factory = BruteForceKnnFactory(
        dimensions=embedder.get_embedding_dimension(),
        embedder=embedder,
        reserved_space=N_DOCS,
    )
    store = DocumentStore(docs, retriever_factory=factory)
    queries = pw.io.python.read(
        _QuerySubject(query_q).subject,
        schema=DocumentStore.RetrieveQuerySchema,
    )
    results = store.retrieve_query(queries)

    from time import perf_counter

    def on_change(key, row, time, is_addition):  # noqa: A002
        if is_addition:
            resp_q.put((perf_counter(), row["result"]))

    pw.io.subscribe(results, on_change=on_change)

    # passive ingest progress: chunk count via the engine itself (no device
    # sync — probing the index mid-ingest would serialize the async embeds)
    chunk_counts = store.chunked_docs.groupby().reduce(
        c=pw.reducers.count()
    )

    def on_count(key, row, time, is_addition):  # noqa: A002
        if is_addition:
            count_q.put((perf_counter(), row["c"]))

    pw.io.subscribe(chunk_counts, on_change=on_count)
    # the driver's flush timer (commits flush immediately anyway;
    # this bounds the idle-poll cadence)
    pw.run(autocommit_duration_ms=25)


def _mk_query(text: str) -> dict:
    return {
        "query": text,
        "k": K,
        "metadata_filter": None,
        "filepath_globpattern": None,
    }


def _ask(query_q, resp_q, text: str, timeout: float = 120.0):
    query_q.put(_mk_query(text))
    return resp_q.get(timeout=timeout)


def _drive(docs: list[str], docs_path: str) -> dict:
    """One full streaming run; returns timing facts."""
    query_q: queue.Queue = queue.Queue()
    resp_q: queue.Queue = queue.Queue()
    count_q: queue.Queue = queue.Queue()
    t_start = time.perf_counter()
    runner = threading.Thread(
        target=run_pipeline,
        args=(docs_path, query_q, resp_q, count_q),
        daemon=True,
    )
    runner.start()

    # wait (passively) until every chunk passed through the pipeline, then
    # one probe query forces the device queue to drain: its response marks
    # documents actually searchable — host plumbing AND device work done
    while True:
        _t, count = count_q.get(timeout=300)
        if count >= N_DOCS:
            break
    marker = docs[-1]
    t_resp, result = _ask(query_q, resp_q, marker)
    top = result.value[0] if result.value else None
    assert top and f"doc{N_DOCS - 1}" in top.get("text", ""), top
    t_ingested = t_resp

    # serving latency: sequential queries, each its own engine batch
    rng = random.Random(11)
    lat = []
    for q in make_docs(N_QUERIES, rng):
        tq = time.perf_counter()
        t_resp, _ = _ask(query_q, resp_q, q)
        lat.append((t_resp - tq) * 1000)

    # serving throughput: concurrent clients. Queries landing within one
    # commit tick share an engine batch -> ONE fused device dispatch
    n_concurrent = 64
    tq0 = time.perf_counter()
    for q in make_docs(n_concurrent, random.Random(17)):
        query_q.put(_mk_query(q))
    last = tq0
    for _ in range(n_concurrent):
        last, _ = resp_q.get(timeout=120)
    qps = n_concurrent / max(last - tq0, 1e-9)

    query_q.put(None)  # close the query subject
    # the docs source streams forever; stop the engine explicitly
    from pathway_tpu.internals.runner import last_engine

    eng = last_engine()
    if eng is not None:
        eng.terminate_flag.set()
    runner.join(timeout=60)
    return {
        "ingest_s": t_ingested - t_start,
        "serving_p50_ms": float(np.percentile(lat, 50)),
        "serving_p90_ms": float(np.percentile(lat, 90)),
        "serving_qps_64clients": qps,
    }


def _device_ingest_rate(docs: list[str]) -> dict:
    """docs/s through tokenize -> embed -> scatter alone, synced on the
    device — the ENGINE-independent rate of the ingest hot path, measured
    as an A/B:

      * classic — the synchronous per-batch path (tokenize, pad to the
        bucket, one blocking round trip per chunk), exactly what
        PATHWAY_DEVICE_PIPELINE=0 runs;
      * pipelined — the async DevicePipeline over the same fused
        prepare/dispatch split (worker-thread tokenize+pack, packed
        ragged slabs, double-buffered dispatch).

    The pipelined number is the one the MFU gap is judged on; the
    classic number stays in the artifact so the speedup is data.
    Comparing the pipelined rate with the framework number shows the
    engine's overhead.  Both arms report every measured pass."""
    import jax

    from pathway_tpu.internals.device_pipeline import (
        DevicePipeline,
        pipeline_enabled,
    )
    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.ops.knn import DeviceKnnIndex, FusedEmbedSearch

    encoder = SentenceEncoder.cached("all-MiniLM-L6-v2", max_len=64)
    chunk = N_DOCS // N_FILES

    def fresh() -> tuple:
        index = DeviceKnnIndex(
            encoder.dimension, metric="cos", reserved_space=N_DOCS
        )
        return index, FusedEmbedSearch(encoder, index)

    def drain(index):
        # the live buffer ends the donated scatter chain (chip_smoke.py's
        # sync phase checks block_until_ready covers it on the chip)
        index._flush()
        jax.block_until_ready(index._buffer)

    def classic_rates() -> list[float]:
        index, fused = fresh()
        # warmup chunk pays any residual compile
        fused.embed_and_add(range(chunk), docs[:chunk])
        drain(index)
        rates = []
        for _ in range(N_RUNS):
            t0 = time.perf_counter()
            for start in range(0, N_DOCS, chunk):
                fused.embed_and_add(
                    range(start, start + chunk), docs[start : start + chunk]
                )
            drain(index)
            rates.append(N_DOCS / (time.perf_counter() - t0))
        return rates

    def pipelined() -> tuple[list[float], float | None, dict | None]:
        from pathway_tpu.internals import utilization

        index, fused = fresh()
        pipe = DevicePipeline(
            prepare=lambda item: fused.prepare_batch(*item),
            dispatch=fused.dispatch_batch,
            quiesce=lambda: drain(index),
            name="bench-ingest",
        )
        try:
            # warmup pass pays the packed-slab compiles
            pipe.submit((range(chunk), docs[:chunk]))
            pipe.drain()
            # scope the live-MFU window to the measured runs only, so
            # the runtime gauge and the offline rate judge the SAME
            # dispatches (satellite: live-vs-offline cross-check)
            if utilization.ENABLED:
                utilization.reset_window()
            rates = []
            for _ in range(N_RUNS):
                t0 = time.perf_counter()
                for start in range(0, N_DOCS, chunk):
                    pipe.submit(
                        (
                            range(start, start + chunk),
                            docs[start : start + chunk],
                        )
                    )
                pipe.drain()
                rates.append(N_DOCS / (time.perf_counter() - t0))
            live = (
                utilization.tracker().snapshot()
                if utilization.ENABLED
                else None
            )
            return rates, pipe.stats()["pad_waste_ratio"], live
        finally:
            pipe.close()

    classic = classic_rates()
    if pipeline_enabled():
        pipe_rates, pad_waste, live = pipelined()
    else:
        pipe_rates, pad_waste, live = None, None, None
    return {
        "classic_runs": classic,
        "pipelined_runs": pipe_rates,
        "pad_waste_ratio": pad_waste,
        "live_utilization": live,
    }


def _device_stamp() -> dict:
    """platform / device_kind / device count as jax reports them; the
    process exits non-zero here when the chip is not a TPU — a CPU number
    is never printed under a device metric's name."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"bench.py needs a TPU; jax found {devices[0].platform!r} "
            f"({devices[0].device_kind})",
            file=sys.stderr,
        )
        sys.exit(3)
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def _round_all(values: list[float], digits: int = 1) -> list[float]:
    return [round(v, digits) for v in values]


def main() -> None:
    from pathway_tpu.internals import compile_cache

    cache_dir = compile_cache.configure()
    stamp = _device_stamp()
    rng = random.Random(7)
    docs = make_docs(N_DOCS, rng)
    with tempfile.TemporaryDirectory() as tmp:
        # N_FILES files, one barrier commit each: deterministic chunked
        # batches that overlap host parsing with async device embeds
        docs_path = os.path.join(tmp, "docs")
        os.makedirs(docs_path)
        per_file = N_DOCS // N_FILES
        for fi in range(N_FILES):
            with open(
                os.path.join(docs_path, f"docs_{fi:03d}.jsonl"), "w"
            ) as f:
                for d in docs[fi * per_file : (fi + 1) * per_file]:
                    f.write(json.dumps({"data": d}) + "\n")

        _drive(docs, docs_path)  # warmup pays every XLA compile
        # the measured drives must not absorb collector pauses from the
        # warmup's millions of now-dead objects: collect once, then freeze
        # survivors out of future GC scans
        import gc

        gc.collect()
        gc.freeze()
        runs = [_drive(docs, docs_path) for _ in range(N_RUNS)]
        rates = _device_ingest_rate(docs)

    ingest_runs = [N_DOCS / f["ingest_s"] for f in runs]
    docs_per_sec = statistics.median(ingest_runs)
    classic_rate = statistics.median(rates["classic_runs"])
    device_rate = (
        statistics.median(rates["pipelined_runs"])
        if rates["pipelined_runs"]
        else classic_rate
    )
    qps = statistics.median(f["serving_qps_64clients"] for f in runs)
    device_mfu = _mfu_facts(device_rate, docs)["mfu_pct"]
    payload = {
        "metric": METRIC,
        "value": round(docs_per_sec, 1),
        "unit": "docs/s",
        "value_is": f"median of {N_RUNS} measured runs",
        **stamp,
        "compile_cache_dir": cache_dir,
        "vs_baseline": round(docs_per_sec / BASELINE_DOCS_PER_SEC, 3),
        "ingest_runs_docs_per_sec": _round_all(ingest_runs),
        "serving_p50_ms_runs": _round_all(
            [f["serving_p50_ms"] for f in runs], 2
        ),
        "serving_p90_ms_runs": _round_all(
            [f["serving_p90_ms"] for f in runs], 2
        ),
        "serving_qps_64clients_runs": _round_all(
            [f["serving_qps_64clients"] for f in runs]
        ),
        "serving_qps_64clients": round(qps, 1),
        "n_docs": N_DOCS,
        **_mfu_facts(docs_per_sec, docs),
        "device_phase_docs_per_sec": round(device_rate, 1),
        "device_phase_runs_docs_per_sec": (
            _round_all(rates["pipelined_runs"])
            if rates["pipelined_runs"]
            else None
        ),
        "device_phase_docs_per_sec_classic": round(classic_rate, 1),
        "device_phase_classic_runs_docs_per_sec": _round_all(
            rates["classic_runs"]
        ),
        "device_phase_pad_waste": (
            round(rates["pad_waste_ratio"], 4)
            if rates["pad_waste_ratio"] is not None
            else None
        ),
        "mfu_pct_device_phase": device_mfu,
        "mfu_pct_device_phase_classic": _mfu_facts(classic_rate, docs)[
            "mfu_pct"
        ],
        # the runtime gauge's view of the SAME pipelined run
        # (internals/utilization.py rolling window) — live and offline
        # share one cost model, so >20% divergence means a measurement
        # problem, and the flag makes it data
        **_live_mfu_facts(rates.get("live_utilization"), device_mfu),
    }
    print(json.dumps(payload))


def _mfu_facts(docs_per_sec: float, docs: list[str]) -> dict:
    """tokens/s and achieved MFU of the ingest phase.  Tokens/doc is the
    REAL mask count from tokenizing the benchmark corpus (not max_len —
    bucketing pads, but padding is not useful work); FLOPs/token comes
    from the shared analytic model (internals/costmodel.py), the same
    one the live `pathway_device_mfu_pct` gauge uses."""
    from pathway_tpu.internals import costmodel
    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.models.tokenizer import encode_batch

    enc = SentenceEncoder.cached("all-MiniLM-L6-v2", max_len=64)
    cfg = enc.config
    sample = docs[:512]
    _ids, mask = encode_batch(
        enc.tokenizer, sample, max_len=enc.max_len
    )
    tokens_per_doc = float(np.asarray(mask, dtype=np.float64).sum()) / len(
        sample
    )
    per_token = costmodel.encoder_flops_per_token(
        tokens_per_doc,
        hidden=cfg.hidden,
        mlp_dim=cfg.mlp_dim,
        layers=cfg.layers,
    )
    tokens_per_sec = docs_per_sec * tokens_per_doc
    flops = tokens_per_sec * per_token
    # the device_kind-keyed table; an accelerator it does not list raises
    peak = costmodel.device_peak_flops()
    return {
        "tokens_per_doc": round(tokens_per_doc, 1),
        "tokens_per_sec": round(tokens_per_sec),
        "model_tflops_per_sec": round(flops / 1e12, 2),
        "mfu_pct": round(100.0 * flops / peak, 2),
        "device_peak_tflops_bf16": round(peak / 1e12),
    }


def _live_mfu_facts(live: dict | None, offline_mfu: float | None) -> dict:
    """Cross-check the live utilization tracker against this bench's
    offline device-phase MFU.  Both sides share one cost model, so a
    divergence beyond 20% means one of the measurements is lying (e.g.
    the rolling window caught warmup, or the tracker missed spans)."""
    live = live or {}
    live_mfu = live.get("mfu_pct")
    out: dict = {
        "mfu_pct_device_phase_live": (
            round(live_mfu, 2) if live_mfu is not None else None
        ),
        "tokens_per_sec_live": (
            round(live["tokens_per_sec"])
            if live.get("tokens_per_sec")
            else None
        ),
        "bound_state_live": live.get("bound_state"),
    }
    if live_mfu is not None and offline_mfu:
        ratio = abs(live_mfu - offline_mfu) / offline_mfu
        out["mfu_live_divergence"] = round(ratio, 3)
        out["mfu_live_divergence_flag"] = ratio > 0.20
    return out


if __name__ == "__main__":
    main()

"""AdaptiveRAG + CrossEncoderReranker + HybridIndex benchmark
(BASELINE config 2/3 "AdaptiveRAGQuestionAnswerer + CrossEncoderReranker
(HybridIndex BM25+KNN)"; VERDICT r4 item 7).

End-to-end through the engine: fs-less synthetic corpus -> DocumentStore
over a HybridIndex (real TPU MiniLM KNN + incremental BM25, reciprocal
rank fusion) -> retrieve k=16 -> CrossEncoder reranker on TPU -> top-4 ->
AdaptiveRAG geometric answerer with a FAKE LLM (the reference bench shape:
the answerer's cost is retrieval+rerank; the LLM is mocked so the numbers
isolate the framework path — generation itself is measured separately in
generation_bench.py).

Reports time-to-ready, query p50/p90 (sequential) and qps at 32
concurrent clients. Prints ONE JSON line, stamped with the device it ran
on. The rerank leg pays two device dispatches per wave plus host python
for BM25 + RRF + pair tokenization.
"""

from __future__ import annotations

import json
import os
import queue
import random
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_DOCS = 2048
N_QUERIES = 24
K_RETRIEVE = 16
K_FINAL = 4

_WORDS = (
    "stream table engine incremental dataflow tensor shard mesh batch "
    "window join reduce filter index vector embed query latency commit "
    "snapshot worker collective gather scatter fuse compile kernel"
).split()


def make_docs(n: int, rng: random.Random) -> list[str]:
    return [" ".join(rng.choices(_WORDS, k=40)) + f" doc{i}" for i in range(n)]


def build_and_run(doc_rows, query_q, resp_q, ready_q):
    import pathway_tpu as pw
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.stdlib.indexing.bm25 import TantivyBM25Factory
    from pathway_tpu.stdlib.indexing.hybrid_index import HybridIndexFactory
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        BruteForceKnnFactory,
    )
    from pathway_tpu.xpacks.llm.document_store import DocumentStore
    from pathway_tpu.internals.udfs import UDF
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder
    from pathway_tpu.xpacks.llm.question_answering import (
        AdaptiveRAGQuestionAnswerer,
        BaseRAGQuestionAnswerer,
    )

    class FakeChatModel(UDF):
        def __init__(self, reply_fn):
            super().__init__(return_type=str, deterministic=True)

            def chat(messages) -> str:
                return reply_fn(messages)

            self.func = chat
    from pathway_tpu.xpacks.llm.rerankers import (
        CrossEncoderReranker,
        rerank_topk_filter,
    )

    G.clear()
    embedder = SentenceTransformerEmbedder(max_len=64)
    hybrid = HybridIndexFactory(
        [
            BruteForceKnnFactory(
                dimensions=embedder.get_embedding_dimension(),
                embedder=embedder,
                reserved_space=N_DOCS,
            ),
            TantivyBM25Factory(),
        ]
    )
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(data=str), doc_rows
    )
    store = DocumentStore(docs, retriever_factory=hybrid)

    def reply(messages):
        # fake LLM: commits on the first try (the bench measures the
        # framework, not generation)
        return "answer"

    rag = AdaptiveRAGQuestionAnswerer(
        FakeChatModel(reply),
        store,
        n_starting_documents=2,
        factor=2,
        max_iterations=2,
    )

    class Subject(pw.io.python.ConnectorSubject):
        def run(self) -> None:
            while True:
                item = query_q.get()
                if item is None:
                    return
                self.next(**item)
                self.commit()

    queries = pw.io.python.read(
        Subject(), schema=BaseRAGQuestionAnswerer.AnswerQuerySchema
    )
    answers = rag.answer_query(queries)

    # the reranked-retrieval leg (retrieve k=16 -> cross-encoder -> top4)
    retrieve_q = pw.io.python.read(
        _RetrSubject(query_q2 := queue.Queue()).subject,
        schema=DocumentStore.RetrieveQuerySchema,
    )
    ready_q.put(query_q2)
    retrieved = store.retrieve_query(retrieve_q)
    reranker = CrossEncoderReranker()

    import pathway_tpu.internals.api as api

    def unpack_docs(result) -> tuple:
        return tuple(
            d.get("text", "") for d in (result.value or [])
        )

    docs_tab = retrieved.select(
        query=retrieve_q.query,  # same universe: one result row per query
        docs=api.apply_with_type(unpack_docs, tuple, pw.this.result),
    )
    flat = docs_tab.flatten(pw.this.docs)
    scored = flat.select(
        query=pw.this.query,
        doc=pw.this.docs,
        score=reranker(pw.this.docs, pw.this.query),
    )
    regrouped = scored.groupby(pw.this.query).reduce(
        pw.this.query,
        docs=pw.reducers.tuple(pw.this.doc),
        scores=pw.reducers.tuple(pw.this.score),
    )
    top = regrouped.select(
        query=pw.this.query,
        kept=rerank_topk_filter(pw.this.docs, pw.this.scores, K_FINAL),
    )

    def on_answer(key, row, time, is_addition):  # noqa: A002
        if is_addition:
            resp_q.put(("answer", time_mod(), row["result"]))

    def on_rerank(key, row, time, is_addition):  # noqa: A002
        if is_addition:
            resp_q.put(("rerank", time_mod(), row["kept"]))

    pw.io.subscribe(answers, on_change=on_answer)
    pw.io.subscribe(top, on_change=on_rerank)
    pw.run(autocommit_duration_ms=25)


def time_mod():
    return time.perf_counter()


class _RetrSubject:
    def __init__(self, q: queue.Queue):
        import pathway_tpu as pw

        class Subject(pw.io.python.ConnectorSubject):
            def run(self) -> None:
                while True:
                    item = q.get()
                    if item is None:
                        return
                    if isinstance(item, list):
                        # concurrent-client batch: one engine commit for
                        # the whole wave -> one fused device dispatch
                        for it in item:
                            self.next(**it)
                    else:
                        self.next(**item)
                    self.commit()

        self.q = q
        self.subject = Subject()


def main() -> None:
    import jax

    from pathway_tpu.internals import compile_cache

    compile_cache.configure()
    device = jax.devices()[0]
    rng = random.Random(5)
    docs = make_docs(N_DOCS, rng)
    doc_rows = [(d,) for d in docs]
    query_q: queue.Queue = queue.Queue()
    resp_q: queue.Queue = queue.Queue()
    ready_q: queue.Queue = queue.Queue()
    t0 = time.perf_counter()
    runner = threading.Thread(
        target=build_and_run,
        args=(doc_rows, query_q, resp_q, ready_q),
        daemon=True,
    )
    runner.start()
    retr_q = ready_q.get(timeout=300)

    def ask_answer(text):
        query_q.put(
            {
                "prompt": text,
                "filters": None,
                "metadata_filter": None,
                "filepath_globpattern": None,
                "model": None,
                "return_context_docs": False,
            }
        )
        kind, t, payload = resp_q.get(timeout=300)
        assert kind == "answer", kind
        return t, payload

    def ask_rerank(text):
        retr_q.put(
            {
                "query": text,
                "k": K_RETRIEVE,
                "metadata_filter": None,
                "filepath_globpattern": None,
            }
        )
        kind, t, payload = resp_q.get(timeout=300)
        assert kind == "rerank", kind
        return t, payload

    # first response marks the pipeline ready: hybrid index built, every
    # XLA compile paid (config-1's bench measures warm ingest; here the
    # time-to-ready is reported as what it is, compiles included)
    t_ing, _first = ask_rerank(docs[-1])
    ready_s = t_ing - t0

    # warmup both legs
    for q in make_docs(4, random.Random(3)):
        ask_rerank(q)
        ask_answer(q)

    lat_rerank = []
    for q in make_docs(N_QUERIES, random.Random(11)):
        tq = time.perf_counter()
        t, _ = ask_rerank(q)
        lat_rerank.append((t - tq) * 1000)
    lat_answer = []
    for q in make_docs(N_QUERIES, random.Random(12)):
        tq = time.perf_counter()
        t, _ = ask_answer(q)
        lat_answer.append((t - tq) * 1000)

    # concurrent rerank clients: one wave, one engine batch (queries
    # arriving together share the fused retrieve and the batched
    # cross-encoder pass — the reference's serving model under load)
    n_conc = 32
    wave = [
        {
            "query": q,
            "k": K_RETRIEVE,
            "metadata_filter": None,
            "filepath_globpattern": None,
        }
        for q in make_docs(n_conc, random.Random(17))
    ]
    tq0 = time.perf_counter()
    retr_q.put(wave)
    last = tq0
    for _ in range(n_conc):
        _kind, last, _ = resp_q.get(timeout=300)
    qps = n_conc / max(last - tq0, 1e-9)

    query_q.put(None)
    retr_q.put(None)
    from pathway_tpu.internals.runner import last_engine

    eng = last_engine()
    if eng is not None:
        eng.terminate_flag.set()
    runner.join(timeout=60)

    print(
        json.dumps(
            {
                "metric": (
                    "AdaptiveRAG + CrossEncoderReranker + HybridIndex "
                    "(BM25+KNN) qps/p50, fake LLM, real TPU embedder+"
                    "reranker"
                ),
                "n_docs": N_DOCS,
                "time_to_ready_s": round(ready_s, 1),
                "rerank_p50_ms": round(float(np.percentile(lat_rerank, 50)), 2),
                "rerank_p90_ms": round(float(np.percentile(lat_rerank, 90)), 2),
                "adaptive_rag_answer_p50_ms": round(
                    float(np.percentile(lat_answer, 50)), 2
                ),
                "rerank_qps_32clients": round(qps, 1),
                "k_retrieve": K_RETRIEVE,
                "k_final": K_FINAL,
                "host_cpus": os.cpu_count(),
                "platform": device.platform,
                "device_kind": device.device_kind,
                "device_count": len(jax.devices()),
            }
        )
    )


if __name__ == "__main__":
    main()

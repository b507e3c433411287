"""Serving-tier bench: micro-batched vs per-query A/B, open-loop mode,
and the ingest-vs-serve concurrent arm.

Four arms, each a subprocess (serving knobs are read at tier birth, so
every configuration gets a fresh process; the parent stays import-light
and aggregates ONE JSON line):

  per_query    closed-loop clients with PATHWAY_SERVING=0 — every REST
               request pays its own engine commit.  The baseline the
               tentpole is judged against.
  micro_batch  the same closed-loop load with the serving tier armed:
               requests park on the micro-batcher and coalesce under one
               commit per flush (internals/serving.py).  Its fields stay
               top-level in the output for bench.py back-compat, plus
               the tier's own occupancy/cache/shed status.
  open_loop    Poisson arrivals (rate derived from the measured
               micro-batch QPS) — the arrival process does not wait for
               responses, so queueing and admission control are actually
               exercised; 429s are counted, not retried.
  concurrent   ops-level ingest (FusedEmbedSearch.embed_and_add) solo,
               then with serving searches hammering the same index —
               reports the ingest rate ratio (acceptance: >= 50%).

Latency comes from the query tracer's mergeable digests — the SAME
numbers `/status "queries"` serves — cross-checked against
client-observed walls.  The parent emits `speedup` (micro-batched QPS /
per-query QPS), the key bench.py surfaces as `serving.speedup` in both
healthy and fallback artifacts.  Prints ONE JSON line.
"""

from __future__ import annotations

import json
import os
import random
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_CLIENTS = 64
N_PER_CLIENT = 12
N_WARMUP = 8
SLO_P99_MS = 2000.0
BATCH_WINDOW_MS = 3.0
MAX_BATCH = 64
OPEN_LOOP_S = 3.0
# closed-loop arms: docs behind the REST-served index, query text pool
# (pool < total queries so the result cache sees repeats)
N_DOCS_SERVE = 256
N_QUERY_POOL = 64
# concurrent arm (ops-level)
CC_DOCS = 512
CC_CHUNK = 128
CC_SERVE_THREADS = 2
CC_SERVE_BATCH = 8
CC_K = 6


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_http(port: int, timeout: float = 30.0) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/_schema", timeout=5
            ):
                return
        except Exception:
            time.sleep(0.1)
    raise TimeoutError("webserver did not come up")


_WORDS = [f"w{i:03d}" for i in range(256)]


def _doc_texts(n: int, seed: int = 7) -> list:
    rng = random.Random(seed)
    return [
        " ".join(rng.choice(_WORDS) for _ in range(10)) for _ in range(n)
    ]


def _query_pool() -> list:
    # reuse doc vocabulary so top-1 answers are stable and non-trivial
    rng = random.Random(13)
    return [
        " ".join(rng.choice(_WORDS) for _ in range(6))
        for _ in range(N_QUERY_POOL)
    ]


def _query(port: int, text: str, timeout: float = 120.0) -> float:
    """One POST /serve query; returns client-observed wall seconds."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/serve",
        data=json.dumps({"q": text}).encode(),
        headers={"Content-Type": "application/json"},
    )
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        body = json.loads(resp.read())
    wall = time.perf_counter() - t0
    got = body.get("result") if isinstance(body, dict) else body
    assert got, body  # top-1 doc text for the query
    return wall


class _Client:
    """Keep-alive closed-loop client: one persistent connection per
    client thread, so the harness measures the serving path and not a
    TCP handshake per request."""

    def __init__(self, port: int):
        import http.client

        self._mk = lambda: http.client.HTTPConnection(
            "127.0.0.1", port, timeout=120
        )
        self.conn = self._mk()

    def query(self, text: str) -> float:
        body = json.dumps({"q": text})
        headers = {"Content-Type": "application/json"}
        t0 = time.perf_counter()
        try:
            self.conn.request("POST", "/serve", body=body, headers=headers)
            resp = self.conn.getresponse()
            payload = json.loads(resp.read())
        except Exception:
            self.conn.close()
            self.conn = self._mk()
            self.conn.request("POST", "/serve", body=body, headers=headers)
            resp = self.conn.getresponse()
            payload = json.loads(resp.read())
        wall = time.perf_counter() - t0
        assert resp.status == 200, (resp.status, payload)
        got = payload.get("result") if isinstance(payload, dict) else payload
        assert got, payload
        return wall

    def close(self) -> None:
        self.conn.close()


def _serve_app(port: int):
    """REST queries answered by a fused embed+search index: each engine
    commit pays one device program, so coalescing N queries into one
    commit is exactly the dispatch amortization the serving tier sells.
    The encoder is a seeded tiny transformer (no checkpoint download) —
    the program cost is real but the arm stays CPU-cheap."""
    import pathway_tpu as pw
    from pathway_tpu.internals import qtrace
    from pathway_tpu.io.http._server import PathwayWebserver, rest_connector
    from pathway_tpu.models.transformer import TransformerConfig
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        BruteForceKnnFactory,
    )
    from pathway_tpu.xpacks.llm.embedders import SentenceTransformerEmbedder

    tiny = TransformerConfig(
        vocab_size=512, hidden=64, layers=2, heads=2, mlp_dim=128,
        max_len=32,
    )
    embedder = SentenceTransformerEmbedder(
        "serving-bench-tiny", config=tiny, max_len=16
    )
    docs = pw.debug.table_from_rows(
        pw.schema_from_types(text=str),
        [(t,) for t in _doc_texts(N_DOCS_SERVE)],
    )
    index = BruteForceKnnFactory(
        embedder=embedder, reserved_space=N_DOCS_SERVE
    ).build_index(docs.text, docs)

    webserver = PathwayWebserver("127.0.0.1", port)

    class QuerySchema(pw.Schema):
        q: str

    queries, writer = rest_connector(
        webserver=webserver,
        route="/serve",
        schema=QuerySchema,
        methods=("POST",),
        delete_completed_queries=False,
    )
    res = index.query_as_of_now(queries.q, number_of_matches=1).select(
        result=pw.this.text
    )
    writer(res)
    threading.Thread(
        target=lambda: pw.run(slo=SLO_P99_MS), daemon=True
    ).start()
    _wait_http(port)
    return qtrace


def _warm_buckets(port: int, pool: list, *, concurrent: bool = True) -> None:
    """Compile every padded query-batch bucket the measured loop can
    see (concurrent bursts cover the coalesced sizes, singles cover
    batch-1) — first compiles must not land in the digests."""
    bursts = (64, 64, 32, 16, 8, 4, 2) if concurrent else ()
    for burst in bursts:
        threads = [
            threading.Thread(target=_query, args=(port, pool[i % len(pool)]))
            for i in range(burst)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    for i in range(N_WARMUP):
        _query(port, pool[i % len(pool)])


def _wall_quantile(walls: list, q: float) -> float:
    walls = sorted(walls)
    return round(walls[min(int(q * len(walls)), len(walls) - 1)] * 1000, 3)


def _closed_loop_arm(arm: str) -> dict:
    """micro_batch: N_CLIENTS closed-loop keep-alive clients against the
    armed serving tier — concurrent queries coalesce under one commit
    and one fused program per flush.

    per_query: the baseline the ISSUE names — every query pays the full
    serial path (one request in flight, one engine flush, one device
    dispatch per query, serving tier off).  Running the baseline at high
    concurrency would let the engine driver's own commit coalescing
    batch the dispatches anyway (measured: 64 concurrent serving-off
    clients reach ~1.4k qps with 44-query device batches), which is
    precisely the behavior the serving tier makes bounded and explicit —
    so the per-query arm is sequential by construction, matching the
    'one flush per query' cost model it exists to measure.

    Latency comes from the tracer digests; serving tier status is
    attached to the micro arm."""
    port = _free_port()
    qtrace = _serve_app(port)
    from pathway_tpu.internals import runner as _runner
    from pathway_tpu.internals import serving

    if arm == "per_query":
        n_clients, n_per_client = 1, 192
    else:
        n_clients, n_per_client = N_CLIENTS, N_PER_CLIENT
    pool = _query_pool()
    try:
        _warm_buckets(port, pool, concurrent=arm != "per_query")
        qtrace.reset()  # scope the digests to the measured window
        tq = qtrace.tracker()
        tq.set_slo(SLO_P99_MS)

        walls: list = []
        walls_lock = threading.Lock()

        def client(cid: int) -> None:
            conn = _Client(port)
            mine = []
            for i in range(n_per_client):
                text = pool[(cid * n_per_client + i) % len(pool)]
                mine.append(conn.query(text))
            conn.close()
            with walls_lock:
                walls.extend(mine)

        t0 = time.perf_counter()
        clients = [
            threading.Thread(target=client, args=(c,))
            for c in range(n_clients)
        ]
        for c in clients:
            c.start()
        for c in clients:
            c.join(timeout=300)
        elapsed = time.perf_counter() - t0

        extra = {}
        if arm == "per_query":
            # transparency datum: the seed engine's own driver-loop
            # commit coalescing already amortizes dispatches when
            # clients pile up (without bounds, admission, caching, or
            # occupancy metrics) — report that concurrent serving-off
            # throughput next to the sequential per-query number so the
            # A/B hides nothing
            try:
                extra["concurrent_serving_off"] = _concurrent_pass(
                    port, pool
                )
            except Exception as exc:  # noqa: BLE001 — datum, not the arm
                extra["concurrent_serving_off"] = {"error": str(exc)}
    finally:
        eng = _runner.last_engine()
        if eng is not None:
            eng.terminate_flag.set()

    n = n_clients * n_per_client
    status = tq.status()
    total = status["stages"].get("total", {})
    out = {
        "n_clients": n_clients,
        "n_queries": n,
        "completed": status["completed"],
        "qps": round(n / max(elapsed, 1e-9), 1),
        "p50_ms": total.get("p50_ms"),
        "p95_ms": total.get("p95_ms"),
        "p99_ms": total.get("p99_ms"),
        "p999_ms": total.get("p999_ms"),
        "stage_p99_ms": {
            s: ent.get("p99_ms")
            for s, ent in status["stages"].items()
            if s != "total"
        },
        "client_wall_p50_ms": _wall_quantile(walls, 0.50),
        "client_wall_p99_ms": _wall_quantile(walls, 0.99),
        "slo_target_p99_ms": SLO_P99_MS,
        "slo_burning": status["slo"]["burning"],
        "slo_violations": status["slo"]["violations"],
        "serving": serving.serving_status(),
    }
    out.update(extra)
    return out


def _concurrent_pass(port: int, pool: list) -> dict:
    """Quick qps-only pass: N_CLIENTS keep-alive clients, no digests."""
    walls: list = []
    lock = threading.Lock()

    def client(cid: int) -> None:
        conn = _Client(port)
        mine = []
        for i in range(N_PER_CLIENT):
            mine.append(conn.query(pool[(cid + i) % len(pool)]))
        conn.close()
        with lock:
            walls.extend(mine)

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=client, args=(c,))
        for c in range(N_CLIENTS)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    elapsed = time.perf_counter() - t0
    n = N_CLIENTS * N_PER_CLIENT
    return {
        "n_clients": N_CLIENTS,
        "qps": round(n / max(elapsed, 1e-9), 1),
        "client_wall_p50_ms": _wall_quantile(walls, 0.50),
        "client_wall_p99_ms": _wall_quantile(walls, 0.99),
    }


def _open_loop_arm() -> dict:
    """Poisson arrivals at SERVING_BENCH_RATE/s for OPEN_LOOP_S seconds;
    arrivals never wait for responses (open loop), 429s counted."""
    rate = float(os.environ.get("SERVING_BENCH_RATE", "200"))
    port = _free_port()
    qtrace = _serve_app(port)
    from pathway_tpu.internals import runner as _runner
    from pathway_tpu.internals import serving

    walls: list = []
    sheds = [0]
    errors = [0]
    lock = threading.Lock()
    threads: list = []

    pool = _query_pool()

    def one(i: int) -> None:
        try:
            w = _query(port, pool[i % len(pool)])
            with lock:
                walls.append(w)
        except urllib.error.HTTPError as exc:
            with lock:
                if exc.code == 429:
                    sheds[0] += 1
                else:
                    errors[0] += 1
        except Exception:
            with lock:
                errors[0] += 1

    try:
        _warm_buckets(port, pool)
        qtrace.reset()
        tq = qtrace.tracker()
        tq.set_slo(SLO_P99_MS)
        rng = random.Random(11)
        t0 = time.perf_counter()
        deadline = t0 + OPEN_LOOP_S
        offered = 0
        next_at = t0
        while True:
            now = time.perf_counter()
            if now >= deadline:
                break
            if now < next_at:
                time.sleep(min(next_at - now, 0.005))
                continue
            th = threading.Thread(target=one, args=(offered,), daemon=True)
            th.start()
            threads.append(th)
            offered += 1
            next_at += rng.expovariate(rate)
        for th in threads:
            th.join(timeout=60)
        elapsed = time.perf_counter() - t0
    finally:
        eng = _runner.last_engine()
        if eng is not None:
            eng.terminate_flag.set()

    status = tq.status()
    total = status["stages"].get("total", {})
    tier_status = serving.serving_status()
    return {
        "arrival": "poisson",
        "offered_rate": rate,
        "offered": offered,
        "completed": len(walls),
        "shed_429": sheds[0],
        "errors": errors[0],
        "qps": round(len(walls) / max(elapsed, 1e-9), 1),
        "p50_ms": total.get("p50_ms"),
        "p99_ms": total.get("p99_ms"),
        "client_wall_p99_ms": (
            _wall_quantile(walls, 0.99) if walls else None
        ),
        "server_sheds": tier_status.get("admission", {}).get("sheds"),
    }


def _concurrent_arm() -> dict:
    """Ops-level ingest-vs-serve arbitration: FusedEmbedSearch ingest
    solo, then with CC_SERVE_THREADS query loops sharing the device.

    A single lock serializes device access exactly the way the engine
    thread does in the full system (ingest scatters donate the index
    buffer, so an unserialized concurrent search reads a donated
    buffer).  The reported ratio is the honest cost of interleaving
    serving batches into the ingest dispatch stream — the quantity the
    device-time partitioner arbitrates."""
    import numpy as np  # noqa: F401 — jax wants numpy imported first
    import jax.numpy as jnp

    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.ops.knn import DeviceKnnIndex, FusedEmbedSearch

    rng = random.Random(7)
    docs = [
        " ".join(rng.choice(_WORDS) for _ in range(24))
        for _ in range(CC_DOCS)
    ]
    queries = [
        " ".join(rng.choice(_WORDS) for _ in range(8)) for _ in range(64)
    ]
    encoder = SentenceEncoder.cached("all-MiniLM-L6-v2", max_len=64)

    def fresh():
        index = DeviceKnnIndex(
            encoder.dimension, metric="cos", reserved_space=CC_DOCS
        )
        return index, FusedEmbedSearch(encoder, index)

    def drain(index):
        index._flush()
        import numpy as _np

        _np.asarray(jnp.sum(index._buffer[:1, :4].astype(jnp.float32)))

    dev_lock = threading.Lock()

    def ingest_rate(index, fused) -> float:
        t0 = time.perf_counter()
        for start in range(0, CC_DOCS, CC_CHUNK):
            with dev_lock:
                fused.embed_and_add(
                    range(start, start + CC_CHUNK),
                    docs[start : start + CC_CHUNK],
                )
        with dev_lock:
            drain(index)
        return CC_DOCS / (time.perf_counter() - t0)

    # warmup (compiles) + solo baseline
    index, fused = fresh()
    ingest_rate(index, fused)
    index, fused = fresh()
    solo = ingest_rate(index, fused)

    # concurrent: serve threads query the same (pre-seeded) index while
    # a fresh ingest pass runs; device time shared under the lock
    index, fused = fresh()
    with dev_lock:
        fused.embed_and_add(range(CC_DOCS), docs)  # seed for searches
        drain(index)
        fused.search_texts(queries[:CC_SERVE_BATCH], CC_K)  # compile
    stop = threading.Event()
    served = [0] * CC_SERVE_THREADS

    def serve_loop(tid: int) -> None:
        n = 0
        i = tid
        while not stop.is_set():
            batch = [
                queries[(i + j) % len(queries)]
                for j in range(CC_SERVE_BATCH)
            ]
            with dev_lock:
                if stop.is_set():
                    break
                fused.search_texts(batch, CC_K)
            n += CC_SERVE_BATCH
            i += CC_SERVE_BATCH
            time.sleep(0.01)  # paced arrivals, not a lock-storm
        served[tid] = n

    servers = [
        threading.Thread(target=serve_loop, args=(t,), daemon=True)
        for t in range(CC_SERVE_THREADS)
    ]
    for s in servers:
        s.start()
    t0 = time.perf_counter()
    # ingest into the shared, already-populated index (keys overlap: the
    # adds are updates — same dispatch cost, stable capacity)
    for start in range(0, CC_DOCS, CC_CHUNK):
        with dev_lock:
            fused.embed_and_add(
                range(start, start + CC_CHUNK),
                docs[start : start + CC_CHUNK],
            )
    with dev_lock:
        drain(index)
    elapsed = time.perf_counter() - t0
    concurrent = CC_DOCS / elapsed
    stop.set()
    for s in servers:
        s.join(timeout=60)
    serve_qps = sum(served) / elapsed
    return {
        "ingest_solo_docs_per_s": round(solo, 1),
        "ingest_concurrent_docs_per_s": round(concurrent, 1),
        "ingest_ratio": round(concurrent / max(solo, 1e-9), 3),
        "serve_qps_concurrent": round(serve_qps, 1),
        "serve_threads": CC_SERVE_THREADS,
        "serve_batch": CC_SERVE_BATCH,
    }


def _run_arm(arm: str, extra_env: dict | None = None) -> dict:
    env = dict(
        os.environ,
        SERVING_BENCH_ARM=arm,
        JAX_PLATFORMS="cpu",
        PATHWAY_DEVICE_PROBE="0",
    )
    env.update(extra_env or {})
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        capture_output=True,
        timeout=420,
        text=True,
        env=env,
    )
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception:
        return {
            "error": (
                f"arm {arm} failed (rc={proc.returncode}): "
                + (proc.stderr or proc.stdout).strip()[-400:]
            )
        }


def main() -> None:
    arm = os.environ.get("SERVING_BENCH_ARM")
    if arm:
        # child: one configuration, one JSON line
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("PATHWAY_DEVICE_PROBE", "0")
        from pathway_tpu.internals import compile_cache, qtrace

        compile_cache.configure()

        if not qtrace.ENABLED:
            print(json.dumps(
                {"error": "qtrace disabled (PATHWAY_QTRACE=0)"}
            ))
            return
        if arm in ("per_query", "micro_batch"):
            print(json.dumps(_closed_loop_arm(arm)))
        elif arm == "open_loop":
            print(json.dumps(_open_loop_arm()))
        elif arm == "concurrent":
            print(json.dumps(_concurrent_arm()))
        else:
            print(json.dumps({"error": f"unknown arm {arm!r}"}))
        return

    # parent: drive the arms, aggregate one line
    window = os.environ.get(
        "PATHWAY_SERVE_BATCH_WINDOW_MS", str(BATCH_WINDOW_MS)
    )
    serve_env = {
        "PATHWAY_SERVING": "1",
        "PATHWAY_SERVE_BATCH_WINDOW_MS": window,
        "PATHWAY_SERVE_MAX_BATCH": str(MAX_BATCH),
    }
    base = _run_arm("per_query", {"PATHWAY_SERVING": "0"})
    micro = _run_arm("micro_batch", serve_env)
    rate = micro.get("qps") or base.get("qps") or 200.0
    open_loop = _run_arm(
        "open_loop",
        {**serve_env, "SERVING_BENCH_RATE": str(round(float(rate), 1))},
    )
    concurrent = _run_arm("concurrent", serve_env)

    out = {"metric": "rest_serving_latency"}
    # micro-batched arm stays top-level: bench.py and older artifact
    # readers key on qps/p50_ms/p99_ms here
    out.update(micro if "error" not in micro else {"error": micro["error"]})
    out["batch_window_ms"] = float(window)
    out["per_query"] = {
        k: base.get(k)
        for k in (
            "n_clients", "qps", "p50_ms", "p95_ms", "p99_ms",
            "client_wall_p99_ms", "completed", "concurrent_serving_off",
            "error",
        )
        if k in base
    }
    micro_qps = micro.get("qps")
    base_qps = base.get("qps")
    out["speedup"] = (
        round(micro_qps / base_qps, 2) if micro_qps and base_qps else None
    )
    out["p99_over_p50"] = (
        round(micro["p99_ms"] / micro["p50_ms"], 2)
        if micro.get("p99_ms") and micro.get("p50_ms")
        else None
    )
    out["open_loop"] = open_loop
    out["concurrent"] = concurrent
    print(json.dumps(out))


if __name__ == "__main__":
    main()

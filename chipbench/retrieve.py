"""Traffic kind `retrieve_open_loop`: callers of /v1/retrieve at one fixed
rate, over a store that set-up filled and that nothing else touches.

Set-up ingests `store_docs` documents of the `store_traffic` mix and asks
warm-up bursts until two passes in a row compile nothing, so that every
micro-batch bucket and query shape the serving tier can choose is
compiled.  The window's requests come from chipbench/loadgen.py, a process
of its own that never imports jax.  Every seed gets the same multiset of
inter-arrival gaps (the quantiles of the exponential distribution at the
mix's rate) and of query lengths, in another order; the queries differ.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from chipbench import compare, spec, traffic

HERE = os.path.dirname(os.path.abspath(__file__))


def make_requests(tr: dict, docs: list, vocab, seed: int, seconds: float, rate: float, k: int) -> list:
    """The window's requests: due times and bodies, from the seed."""
    rng = np.random.default_rng([int(seed), 3])
    n = max(8, int(round(rate * seconds)))
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate  # exponential quantiles
    gaps = gaps[rng.permutation(n)] * (seconds / gaps.sum())
    due = np.cumsum(gaps) - gaps[0]
    lo, hi = tr["query_words"]
    lengths = (lo + (np.arange(n) * (hi - lo + 1)) // n)[rng.permutation(n)]
    sources = rng.integers(0, len(docs), size=n)
    extra = rng.integers(0, len(vocab), size=(n, int(tr["seeded_words"])))
    keep = set(rng.permutation(n)[: int(tr["sample_queries"])].tolist())
    keep.add(int(np.argmax(lengths)))  # the longest is in the sample
    requests = []
    for i in range(n):
        words = docs[int(sources[i])].split(" ")
        m = min(int(lengths[i]), len(words))
        at = int(rng.integers(0, len(words) - m + 1))
        text = " ".join(words[at : at + m] + [vocab[j] for j in extra[i]])
        requests.append({
            "due_s": float(due[i]), "keep": i in keep, "source": int(sources[i]),
            "body": {"query": text, "k": k, "metadata_filter": None,
                     "filepath_globpattern": None},
        })
    return requests


def run_generator(session, server, requests: list, tr: dict, tag: str) -> tuple:
    """Starts chipbench/loadgen.py on `requests`; returns (start, results)
    once every request has its answer or its timeout.  At the schedule's
    start a marker program runs: it is the window's open on the device's
    clock."""
    schedule_path = os.path.join(session.run_dir, f"schedule_{tag}.json")
    results_path = os.path.join(session.run_dir, f"results_{tag}.json")
    start = time.monotonic() + 1.0
    with open(schedule_path, "w") as f:
        json.dump({
            "port": server.port, "route": "/v1/retrieve", "start_monotonic": start,
            "timeout_s": float(tr["timeout_s"]), "threads": int(tr["generator_threads"]),
            "requests": [{k: r[k] for k in ("due_s", "body", "keep")} for r in requests],
        }, f)
    proc = subprocess.Popen([sys.executable, os.path.join(HERE, "loadgen.py"),
                             schedule_path, results_path])
    try:
        time.sleep(max(0.0, start - time.monotonic()))
        session.host_open = session.marker.sync()
        rc = proc.wait(timeout=requests[-1]["due_s"] + float(tr["timeout_s"]) + 120.0)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        raise RuntimeError(f"the load generator exited {rc}")
    with open(results_path) as f:
        out = json.load(f)
    if out["imports_jax"]:
        raise RuntimeError("the load generator imported jax")
    return start, out["results"]


def latencies_ms(results: list, timeout_s: float) -> tuple:
    """(latency of every request in ms from its due time, failed count): a
    request that failed or was refused counts as its timeout."""
    lat, failed = [], 0
    for r in results:
        if r is None or r["status"] != 200:
            failed += 1
            lat.append(timeout_s * 1000.0)
        else:
            lat.append((r["done_s"] - r["due_s"]) * 1000.0)
    return np.array(lat), failed


def lateness_ms(results: list):
    """How late the generator sent each request it sent (send - due)."""
    return np.array([(r["sent_s"] - r["due_s"]) * 1000.0 for r in results if r is not None])


def warm_up(session, server, docs: list, vocab, tr: dict, k: int) -> int:
    """Bursts of every size class and both query-length classes, until two
    passes in a row compile nothing.  Returns the passes it took."""
    rng = np.random.default_rng(12345)
    lo, hi = tr["query_words"]

    def burst(n: int, words: int) -> None:
        texts = []
        for _ in range(n):
            w = docs[int(rng.integers(0, len(docs)))].split(" ")[:words]
            texts.append(" ".join(w + [vocab[int(rng.integers(0, len(vocab)))]]))
        if any(a is None for a in server.retrieve_round(texts)):
            raise RuntimeError("a warm-up query failed")

    quiet = passes = 0
    while quiet < 2 and passes < 12:
        before = sum(session.compiles.count.values())
        # a burst's first few requests flush alone; the rest arrive while that
        # search runs and reach the index in one engine commit (several
        # flushes of at most 64 coalesce): 6, 14-16, 28, 48-64, 100-120 fill
        # the search's batch buckets 8 to 128.  Admission refuses what is in
        # flight beyond 128 while the health controller holds pressure, so a
        # stall's queue reaches no larger bucket
        for n in (1, 6, 14, 16, 28, 48, 64, 100, 120):
            for words in (lo, hi):
                burst(n, words)
        passes += 1
        quiet = quiet + 1 if sum(session.compiles.count.values()) == before else 0
    return passes


def wait_quiet(server, deadline_s: float = 120.0) -> float:
    """Until the program's health controller has let go of the store fill's
    back-pressure.  It holds pressure for as long as its 30 s utilisation
    window still sees an ingest dispatch, and while it does the engine's
    loop sleeps up to 50 ms an iteration: queries asked then read the
    ingest's wake, not a quiescent store.  Returns the seconds waited."""
    t0 = time.monotonic()
    while True:
        health = server.status().get("health", {})
        if not health.get("enabled", False):
            return 0.0
        if not health["pressure"] and health["backpressure_scale"] >= 1.0:
            return time.monotonic() - t0
        if time.monotonic() - t0 > deadline_s:
            raise RuntimeError(f"back-pressure still held after {deadline_s:.0f}s: {health}")
        time.sleep(0.25)


def retrieve_cell(session) -> dict:
    from chipbench.harness import T_PROCESS, Server, log, memory_peak

    cell, args = session.cell, session.args
    tr = cell.traffic
    store, model = cell.config["store"], cell.config["model"]
    k = int(store["k"])
    store_mix = spec.traffic_file(tr["store_traffic"])
    corpus = traffic.Corpus(
        store_mix, args.seed,
        docs_per_file=tr.get("docs_per_file") or store_mix["docs_per_file"],
    )
    n_files = max(1, int(tr["store_docs"]) // corpus.docs_per_file)
    docs: list = []
    for i in range(n_files):
        corpus.write_file(i, os.path.join(session.docs_dir, f"a_store_{i:05d}.jsonl"))
    session.attach_device()
    server = Server(cell, args.seed, session.docs_dir, refresh_interval_s=0.05)
    log("server started")
    for i in range(n_files):  # the texts, for the queries, while the store fills
        docs += corpus.file_docs(i)
    server.wait_rows(n_files * corpus.docs_per_file, 1000.0)
    session.marker.sync()
    server.wait_ready()
    log(f"store filled: {len(docs)} documents; compiles {session.compiles.count}")
    passes = warm_up(session, server, docs, corpus.vocab, tr, k)
    log(f"warm-up: {passes} passes; compiles {session.compiles.count} "
        f"{session.compiles.seconds}")
    quiet_wait_s = wait_quiet(server)
    log(f"back-pressure of the store fill released after {quiet_wait_s:.1f}s more")

    if args.sweep:
        return sweep(session, server, docs, corpus.vocab, tr, k)

    rate = float(tr["rate_qps"])
    requests = make_requests(tr, docs, corpus.vocab, args.seed, args.seconds, rate, k)
    session.open_window(server)
    t_before = time.monotonic()
    start, results = run_generator(session, server, requests, tr, "window")
    setup_s = start - T_PROCESS
    session.close_window(server)
    window_s = time.monotonic() - start
    session.after_window(server)
    peak_bytes = memory_peak(cell.chips)
    lat, failed = latencies_ms(results, float(tr["timeout_s"]))
    late = lateness_ms(results)
    log(f"window: {len(requests)} requests at {rate} qps, {failed} failed; "
        f"p50 {np.percentile(lat, 50):.1f} ms p95 {np.percentile(lat, 95):.1f} ms; "
        f"generator late p99 {np.percentile(late, 99):.1f} ms; set-up {setup_s:.2f}s "
        f"(generator spawned {start - t_before - 1.0:+.2f}s); compiles {session.compiles.count}")
    server.stop()

    # -- the window's own answers against the plain reference ----------------------
    t_ref = time.monotonic()
    kept = [i for i, r in enumerate(requests) if r["keep"]]
    probes = [requests[i]["body"]["query"] for i in kept]
    answers = [results[i]["answer"] if results[i] else None for i in kept]
    rng = np.random.default_rng([int(args.seed), 4])
    pool = [docs[requests[i]["source"]] for i in kept]
    pool += [docs[int(g)] for g in rng.integers(0, len(docs), size=int(tr["pool_docs"]))]
    encoder = cell.arch.reference.Encoder(model, args.seed, max_len=store["max_len"])
    numbers = compare.compare([], [], probes, answers, pool, encoder.embed, k)
    control = None
    if args.control:
        from chipbench.harness import read_controls

        returned = [r["text"] for rows in answers if rows for r in rows]
        control = read_controls(encoder, [], probes, pool + returned, k)
    encoder.free()

    tokens = [min(len(r["body"]["query"].split(" ")) + 2, store["max_len"]) for r in requests]
    answered = len(requests) - failed
    return {
        "numbers": numbers, "control": control, "peak_bytes": peak_bytes,
        "attempted": len(requests), "failed": int(failed),
        "end_to_end": {
            "setup_s": setup_s,
            "retrieve_p50_ms": float(np.percentile(lat, 50)),
            "retrieve_p95_ms": float(np.percentile(lat, 95)),
        },
        "ctx": {
            "window_s": window_s, "generator_late_ms": late.tolist(),
            "queries_answered": answered, "query_tokens": tokens,
            "store_rows": len(docs), "attempted": len(requests),
        },
        "facts": {
            "window_s": window_s, "requests": len(requests), "rate_qps": rate,
            "p99_ms": float(np.percentile(lat, 99)), "max_ms": float(lat.max()),
            "generator_late_p99_ms": float(np.percentile(late, 99)),
            "warmup_passes": passes, "quiet_wait_s": quiet_wait_s,
            "reference_s": time.monotonic() - t_ref,
        },
    }


def sweep(session, server, docs, vocab, tr, k) -> dict:
    """--sweep: one set-up, a short open-loop burst at each rate.  Prints a
    table on stderr and in the line's facts; not a benchmark run."""
    from chipbench.harness import log

    args = session.args
    rows = []
    for j, rate in enumerate(float(x) for x in args.sweep.split(",")):
        requests = make_requests(tr, docs, vocab, args.seed + j, args.seconds, rate, k)
        before = server.status()["serving"]
        start, results = run_generator(session, server, requests, tr, f"sweep{j}")
        span = time.monotonic() - start
        after = server.status()["serving"]
        lat, failed = latencies_ms(results, float(tr["timeout_s"]))
        late = lateness_ms(results)
        batches = after["batches"] - before["batches"]
        row = {
            "rate_qps": rate, "requests": len(requests), "failed": failed,
            "span_s": span, "achieved_qps": (len(requests) - failed) / span,
            "p50_ms": float(np.percentile(lat, 50)), "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "late_p99_ms": float(np.percentile(late, 99)),
            "mean_batch": (after["batched_queries"] - before["batched_queries"]) / max(batches, 1),
        }
        rows.append(row)
        log("sweep " + json.dumps({a: (round(b, 2) if isinstance(b, float) else b)
                                    for a, b in row.items()}))
    server.stop()
    return {"sweep": rows}

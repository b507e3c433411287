"""Engine micro-benchmarks (CPU-side dataflow; no TPU involved).

Two claims measured, matching the reference's engine characteristics
(reference: src/engine/reduce.rs semigroup reducers are O(delta) per group
update; integration_tests/wordcount/base.py streams millions of lines):

1. group-update flatness — the cost of ONE single-row update to a group must
   not grow with the group's size (incremental accumulators, not full-group
   recompute).
2. wordcount streaming throughput — rows/s through source → groupby(word)
   → count with per-batch consolidation.

Run: python benchmarks/engine_bench.py   (prints one JSON line per metric)
"""

from __future__ import annotations

import json
import os as _os
import random
import time as _time

import pathway_tpu as pw
from pathway_tpu.debug import table_from_events
from pathway_tpu.engine.value import ref_scalar
from pathway_tpu.internals.runner import run_tables
from pathway_tpu.internals.schema import schema_from_types


def _free_port_base(n):
    """Find n consecutive free localhost ports (worker i binds base+i)."""
    import socket

    for _ in range(50):
        socks = []
        try:
            s0 = socket.socket()
            s0.bind(("127.0.0.1", 0))
            base = s0.getsockname()[1]
            socks.append(s0)
            if base + n >= 65535:
                continue
            for i in range(1, n):
                s = socket.socket()
                s.bind(("127.0.0.1", base + i))
                socks.append(s)
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError("no free ports")


# ---------------------------------------------------------------------------
# Graph builders — importable by tests (test_perf_smoke runs the static
# analyzer over each topology and checks its columnar predictions against
# the path the engine actually selects).  Every bench below builds its
# graph through one of these.
# ---------------------------------------------------------------------------


def build_reduce_graph(size, n_updates=0):
    """One big group + n single-row updates -> count/sum/max reduce."""
    schema = schema_from_types(g=str, v=int)
    events = [(2, (ref_scalar(i), ("g", i), 1)) for i in range(size)]
    for j in range(n_updates):
        events.append((4 + 2 * j, (ref_scalar(size + j), ("g", j), 1)))
    t = table_from_events(schema, events)
    return t.groupby(t.g).reduce(
        t.g,
        cnt=pw.reducers.count(),
        total=pw.reducers.sum(t.v),
        mx=pw.reducers.max(t.v),
    )


def build_wordcount_graph(n_rows, vocab=10_000, batch=200_000):
    """Streaming wordcount: source -> groupby(word) -> count."""
    rng = random.Random(7)
    words = [f"w{i}" for i in range(vocab)]
    schema = schema_from_types(word=str)
    events = []
    t = 2
    for i in range(n_rows):
        events.append((t, (ref_scalar(i), (rng.choice(words),), 1)))
        if (i + 1) % batch == 0:
            t += 2
    tab = table_from_events(schema, events)
    return tab.groupby(tab.word).reduce(tab.word, cnt=pw.reducers.count())


def build_wordcount_chain_graph(n_rows, vocab=1_000, batch=50_000):
    """Wordcount with a fusable row-wise prefix: source -> select
    (normalize) -> filter (drop negatives) -> select (reorder
    projection) -> groupby(word) -> count/sum.  The three middle ops
    form one maximal PWT501 chain; the build collapses them into a
    single FusedChainNode (analysis/fusion.py plan contract), which
    bench_fused_chain A/Bs against the classic three-node build."""
    rng = random.Random(13)
    words = [f"w{i}" for i in range(vocab)]
    schema = schema_from_types(word=str, n=int)
    events = []
    t = 2
    for i in range(n_rows):
        events.append((t, (ref_scalar(i), (rng.choice(words), i % 97), 1)))
        if (i + 1) % batch == 0:
            t += 2
    tab = table_from_events(schema, events)
    normalized = tab.select(tab.word, n=tab.n * 2)
    kept = normalized.filter(normalized.n >= 0)
    slim = kept.select(kept.n, kept.word)
    return slim.groupby(slim.word).reduce(
        slim.word,
        cnt=pw.reducers.count(),
        total=pw.reducers.sum(slim.n),
    )


def build_join_graph(n_left, n_right):
    """Small build side at t=2, one big probe-side batch at t=4 ->
    inner join -> select."""
    lschema = schema_from_types(k=int, a=int)
    rschema = schema_from_types(k=int, b=int)
    right = table_from_events(
        rschema,
        [(2, (ref_scalar("r", i), (i, i * 10), 1)) for i in range(n_right)],
    )
    left = table_from_events(
        lschema,
        [
            (4, (ref_scalar("l", i), (i % n_right, i), 1))
            for i in range(n_left)
        ],
    )
    return left.join(right, left.k == right.k).select(pw.left.a, pw.right.b)


def build_flatten_graph(n_rows, width=4):
    """Rows with `width`-element lists -> flatten."""
    schema = schema_from_types(i=int, vs=list)
    t = table_from_events(
        schema,
        [
            (2, (ref_scalar("b", i), (i, [i, i + 1, i + 2, i + 3][:width]), 1))
            for i in range(n_rows)
        ],
    )
    return t.flatten(pw.this.vs)


GRAPH_BUILDERS = {
    "reduce": lambda: build_reduce_graph(64, 4),
    "wordcount": lambda: build_wordcount_graph(256, vocab=32, batch=64),
    "wordcount_chain": lambda: build_wordcount_chain_graph(
        256, vocab=32, batch=64
    ),
    "join": lambda: build_join_graph(128, 16),
    "flatten": lambda: build_flatten_graph(64),
}


def _run_reduce(size, n_updates):
    res = build_reduce_graph(size, n_updates)
    t0 = _time.perf_counter()
    (capture,) = run_tables(res, record_stream=True)
    elapsed = _time.perf_counter() - t0
    assert list(capture.state.rows.values())[0][1] == size + n_updates
    return elapsed


def bench_group_update_flatness(sizes=(1_000, 10_000, 100_000), n_updates=200):
    """Build one group of `size` rows at t=2, then apply `n_updates`
    single-row inserts each at its own engine time. Per-update cost =
    (run with updates) - (build-only run), isolating the streaming phase."""
    per_update_ms = {}
    for size in sizes:
        build_only = _run_reduce(size, 0)
        with_updates = _run_reduce(size, n_updates)
        per_update_ms[size] = max(
            1000.0 * (with_updates - build_only) / n_updates, 1e-4
        )
    flat_ratio = per_update_ms[sizes[-1]] / per_update_ms[sizes[0]]
    print(json.dumps({
        "metric": "group_update_ms_per_delta",
        "value": round(per_update_ms[sizes[-1]], 4),
        "unit": "ms/update @ group=100k (build-time subtracted)",
        "per_size": {str(k): round(v, 4) for k, v in per_update_ms.items()},
        "large_vs_small_ratio": round(flat_ratio, 2),
    }))
    return flat_ratio


def bench_wordcount(n_rows=5_000_000, vocab=10_000, batch=200_000):
    """Streaming wordcount through the engine (TimedSource -> vector
    groupby-count -> capture), 5M rows by default to match the reference
    harness scale (reference: integration_tests/wordcount/base.py:19
    DEFAULT_INPUT_SIZE).  Batch size mirrors what a 100 ms autocommit
    produces at this throughput."""
    res = build_wordcount_graph(n_rows, vocab=vocab, batch=batch)
    t0 = _time.perf_counter()
    (capture,) = run_tables(res, record_stream=True)
    elapsed = _time.perf_counter() - t0
    total = sum(r[1] for r in capture.state.rows.values())
    assert total == n_rows
    rps = n_rows / elapsed
    print(json.dumps({
        "metric": "wordcount_rows_per_sec",
        "value": round(rps),
        "unit": "rows/s",
        "n_rows": n_rows,
        "elapsed_s": round(elapsed, 2),
    }))
    return rps


def bench_provenance(n_rows=1_000_000, vocab=10_000, batch=100_000):
    """Armed-delta of the lineage tracker on the wordcount hot path:
    the same graph run with the provenance tracker off, then armed
    (PATHWAY_PROVENANCE=1 equivalent) — the rows/s ratio IS the cost of
    recording reduce lineage + source offsets for every delta."""
    from pathway_tpu.internals import provenance

    rates = {}
    for label, armed in (("off", False), ("armed", True)):
        if armed:
            provenance.install()
        else:
            provenance.clear()
        try:
            res = build_wordcount_graph(n_rows, vocab=vocab, batch=batch)
            t0 = _time.perf_counter()
            (capture,) = run_tables(res, record_stream=True)
            elapsed = _time.perf_counter() - t0
            total = sum(r[1] for r in capture.state.rows.values())
            assert total == n_rows
            rates[label] = n_rows / elapsed
        finally:
            provenance.clear()
    delta = rates["off"] / rates["armed"] - 1.0
    print(json.dumps({
        "metric": "provenance_armed_delta",
        "value": round(delta, 4),
        "unit": "fractional slowdown, armed vs off (wordcount)",
        "rows_per_sec_off": round(rates["off"]),
        "rows_per_sec_armed": round(rates["armed"]),
        "n_rows": n_rows,
    }))
    return delta


def _node_seconds(log_path, node_types):
    """Sum per-node wall time from a PATHWAY_NODE_TIMING_LOG dump for
    the given node class names — isolates the operator under test from
    source/capture/exchange overhead shared by both paths."""
    secs = 0.0
    with open(log_path) as fh:
        for line in fh:
            ent = json.loads(line)
            if ent.get("type") in node_types:
                secs += ent["total_s"]
    return secs


def _ab_columnar(build_fn, module, flag_name, node_types):
    """Run `build_fn`'s pipeline twice — classic vs columnar build-time
    selection — returning {path: node-isolated seconds}."""
    import tempfile

    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        for label, enabled in (("classic", False), ("columnar", True)):
            log = _os.path.join(tmp, f"{label}.jsonl")
            saved_env = _os.environ.get("PATHWAY_NODE_TIMING_LOG")
            _os.environ["PATHWAY_NODE_TIMING_LOG"] = log
            saved = getattr(module, flag_name)
            setattr(module, flag_name, enabled)
            try:
                run_tables(build_fn(), record_stream=True)
            finally:
                setattr(module, flag_name, saved)
                if saved_env is None:
                    del _os.environ["PATHWAY_NODE_TIMING_LOG"]
                else:
                    _os.environ["PATHWAY_NODE_TIMING_LOG"] = saved_env
            out[label] = _node_seconds(log, node_types[label])
    return out


def bench_join_columnar(n_left=100_000, n_right=1_000):
    """Inner-join microbench, classic JoinNode vs columnar VectorJoinNode
    (engine/vector_join.py).  Shape: small build side arrives first, then
    one 100k-row probe-side batch — the delta-mode fused C pass (code
    lookup + match expansion + bucket update) is the measured kernel."""
    from pathway_tpu.engine import vector_join

    def build():
        return build_join_graph(n_left, n_right)

    secs = _ab_columnar(
        build,
        vector_join,
        "VECTOR_JOIN_ENABLED",
        {"classic": ("JoinNode",), "columnar": ("VectorJoinNode",)},
    )
    n = n_left + n_right
    ratio = secs["classic"] / secs["columnar"]
    print(json.dumps({
        "metric": "join_columnar_rows_per_sec",
        "value": round(n / secs["columnar"]),
        "unit": "rows/s through the join node (100k-row inner join)",
        "classic_rows_per_sec": round(n / secs["classic"]),
        "classic_s": round(secs["classic"], 4),
        "columnar_s": round(secs["columnar"], 4),
        "columnar_vs_classic": round(ratio, 2),
    }))
    return ratio


def bench_flatten_columnar(n_rows=100_000, width=4):
    """List-flatten microbench, classic FlattenNode vs columnar
    VectorFlattenNode (engine/vector_flatten.py): vectorized derived-key
    mixer + fused triple assembly vs per-element Python."""
    from pathway_tpu.engine import vector_flatten

    def build():
        return build_flatten_graph(n_rows, width)

    secs = _ab_columnar(
        build,
        vector_flatten,
        "VECTOR_FLATTEN_ENABLED",
        {"classic": ("FlattenNode",), "columnar": ("VectorFlattenNode",)},
    )
    ratio = secs["classic"] / secs["columnar"]
    print(json.dumps({
        "metric": "flatten_columnar_rows_per_sec",
        "value": round(n_rows / secs["columnar"]),
        "unit": f"parent rows/s through the flatten node (x{width} lists)",
        "classic_rows_per_sec": round(n_rows / secs["classic"]),
        "classic_s": round(secs["classic"], 4),
        "columnar_s": round(secs["columnar"], 4),
        "columnar_vs_classic": round(ratio, 2),
    }))
    return ratio


def bench_fused_chain(n_rows=200_000, vocab=1_000, batch=20_000):
    """Chain-fusion A/B on the wordcount_chain topology.

    Classic arm (PATHWAY_DISABLE_FUSION=1) builds the row-wise prefix as
    three nodes (RowwiseNode + FilterNode + RowwiseNode), each paying its
    own take/emit and intermediate triple materialization per batch; the
    fused arm builds the plan's single FusedChainNode.  Seconds are
    node-isolated via PATHWAY_NODE_TIMING_LOG (the groupby/capture tail
    is identical in both arms), best-of-2 interleaved runs per arm."""
    import tempfile

    from pathway_tpu.internals.parse_graph import G

    node_types = {
        "classic": ("RowwiseNode", "FilterNode"),
        "fused": ("FusedChainNode",),
    }
    secs = {}
    with tempfile.TemporaryDirectory() as tmp:
        run_no = 0
        for label, disable in (
            ("classic", "1"), ("fused", "0"),
            ("classic", "1"), ("fused", "0"),  # best-of-2 per arm
        ):
            run_no += 1
            G.clear()
            log = _os.path.join(tmp, f"timing_{run_no}.jsonl")
            saved = {
                k: _os.environ.get(k)
                for k in (
                    "PATHWAY_NODE_TIMING_LOG", "PATHWAY_DISABLE_FUSION"
                )
            }
            _os.environ["PATHWAY_NODE_TIMING_LOG"] = log
            _os.environ["PATHWAY_DISABLE_FUSION"] = disable
            try:
                res = build_wordcount_chain_graph(
                    n_rows, vocab=vocab, batch=batch
                )
                (capture,) = run_tables(res, record_stream=True)
                total = sum(r[1] for r in capture.state.rows.values())
                assert total == n_rows, (label, total, n_rows)
                node_s = _node_seconds(log, node_types[label])
                assert node_s > 0.0, (label, "no timed chain nodes")
                secs[label] = min(secs.get(label, node_s), node_s)
            finally:
                for k, v in saved.items():
                    if v is None:
                        _os.environ.pop(k, None)
                    else:
                        _os.environ[k] = v
                G.clear()
    ratio = secs["classic"] / secs["fused"]
    print(json.dumps({
        "metric": "fused_chain_rows_per_sec",
        "value": round(n_rows / secs["fused"]),
        "unit": "rows/s through the fused select|filter|select chain",
        "classic_rows_per_sec": round(n_rows / secs["classic"]),
        "classic_s": round(secs["classic"], 4),
        "fused_s": round(secs["fused"], 4),
        "fused_vs_classic": round(ratio, 2),
        "n_rows": n_rows,
    }))
    return ratio


def bench_wordcount_multiworker(n_rows=2_000_000, workers=(1, 2, 4)):
    """Same wordcount through the full multi-process data-parallel path:
    N workers, replicated fs json source (each keeps its key shard), TCP
    exchange before the reduce, per-worker csv output parts.  Reports
    rows/s at each worker count so exchange overhead is measured, not
    guessed (reference: wordcount integration harness runs under
    `pathway spawn`)."""
    import subprocess
    import sys
    import tempfile
    import textwrap

    from benchmarks.wordcount_bench import generate_input

    script = textwrap.dedent(
        """
        import os, sys, time
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import pathway_tpu as pw

        tmp = sys.argv[1]

        class InputSchema(pw.Schema):
            word: str

        words = pw.io.fs.read(
            path=os.path.join(tmp, "input"), schema=InputSchema,
            format="json", mode="static",
        )
        result = words.groupby(words.word).reduce(
            words.word, count=pw.reducers.count()
        )
        pw.io.csv.write(result, os.path.join(tmp, "out.csv"))
        t0 = time.perf_counter()
        pw.run(monitoring_level=pw.MonitoringLevel.NONE)
        print(f"ELAPSED {time.perf_counter() - t0:.3f}")
        """
    )

    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    results = {}
    with tempfile.TemporaryDirectory() as tmp:
        _os.makedirs(_os.path.join(tmp, "input"))
        generate_input(_os.path.join(tmp, "input"), n_rows)
        spath = _os.path.join(tmp, "wc.py")
        with open(spath, "w") as fh:
            fh.write(script)
        for n in workers:
            base = _free_port_base(n)
            procs = []
            t0 = _time.perf_counter()
            for wid in range(n):
                env = dict(_os.environ)
                env.update(
                    PATHWAY_PROCESSES=str(n),
                    PATHWAY_PROCESS_ID=str(wid),
                    PATHWAY_FIRST_PORT=str(base),
                    JAX_PLATFORMS="cpu",
                    PYTHONPATH=repo,
                )
                procs.append(subprocess.Popen(
                    [sys.executable, spath, tmp], env=env,
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                ))
            for wid, p in enumerate(procs):
                out, err = p.communicate(timeout=600)
                if p.returncode != 0:
                    raise RuntimeError(
                        f"worker {wid}/{n} rc={p.returncode}: "
                        f"{err.decode()[-1500:]}"
                    )
            elapsed = _time.perf_counter() - t0
            # union of per-worker part files (out.csv, out.csv.1, ...)
            import glob as glob_mod

            total = 0
            for path in glob_mod.glob(_os.path.join(tmp, "out.csv*")):
                with open(path) as fh:
                    fh.readline()
                    for line in fh:
                        if line.strip():
                            fields = line.rstrip().split(",")
                            total += int(fields[1]) * int(fields[-1])
                _os.remove(path)
            assert total == n_rows, (n, total, n_rows)
            results[n] = round(n_rows / elapsed)
    print(json.dumps({
        "metric": "wordcount_multiworker_rows_per_sec",
        "value": results[max(workers)],
        "unit": "rows/s",
        "n_rows": n_rows,
        "per_worker_count": {str(k): v for k, v in results.items()},
        # replicated readers duplicate the parse per worker; on a box with
        # fewer cores than workers the duplication shows as anti-scaling
        "host_cpus": _os.cpu_count(),
    }))
    return results



def bench_exchange(n_rows=300_000, vocab=40_000, churn_pairs=15_000):
    """Worker-to-worker shuffle microbench (engine/exchange.py).

    Two numbers:

    1. shuffle rows/s — a 2-thread-worker static wordcount whose groupby
       forces an exchange_by_key of nearly every row, A/B'd classic vs
       columnar routing by flipping exchange.VECTOR_EXCHANGE_ENABLED
       (consulted per batch, so a module-level flip is a clean A/B).
       Reported from PATHWAY_NODE_TIMING_LOG seconds isolated to the
       _ExchangeNode (end-to-end wall time is dominated by the json
       source parse; run-to-run heap noise swamps the routing delta).
    2. bytes on the wire before/after sender-side consolidation — a real
       TcpCoordinator pair ships a retraction-heavy batch raw and then
       consolidated, measured from the coordinator's own bytes_sent
       counter (the exact frames send_data produces).
    """
    import tempfile
    import threading

    from pathway_tpu.engine import exchange as exchange_mod
    from pathway_tpu.internals.config import pathway_config
    from pathway_tpu.internals.parse_graph import G

    rng = random.Random(11)

    class _WordSchema(pw.Schema):
        word: str

    secs = {}
    with tempfile.TemporaryDirectory() as tmp:
        in_dir = _os.path.join(tmp, "input")
        _os.makedirs(in_dir)
        with open(_os.path.join(in_dir, "data.jsonl"), "w") as fh:
            for _ in range(n_rows):
                fh.write(json.dumps({"word": f"w{rng.randrange(vocab)}"}))
                fh.write("\n")
        run_no = 0
        for label, enabled in (
            ("classic", False), ("columnar", True),
            ("classic", False), ("columnar", True),  # best-of-2 per path
        ):
            run_no += 1
            G.clear()
            log = _os.path.join(tmp, f"timing_{run_no}.jsonl")
            saved_env = _os.environ.get("PATHWAY_NODE_TIMING_LOG")
            _os.environ["PATHWAY_NODE_TIMING_LOG"] = log
            saved_flag = exchange_mod.VECTOR_EXCHANGE_ENABLED
            saved_threads = pathway_config.threads
            exchange_mod.VECTOR_EXCHANGE_ENABLED = enabled
            pathway_config.threads = 2
            try:
                words = pw.io.fs.read(
                    path=in_dir, schema=_WordSchema,
                    format="json", mode="static",
                )
                res = words.groupby(words.word).reduce(
                    words.word, count=pw.reducers.count()
                )
                pw.io.csv.write(
                    res, _os.path.join(tmp, f"out_{run_no}.csv")
                )
                pw.run(monitoring_level=None)
                node_s = _node_seconds(log, ("_ExchangeNode",))
                secs[label] = min(secs.get(label, node_s), node_s)
            finally:
                exchange_mod.VECTOR_EXCHANGE_ENABLED = saved_flag
                pathway_config.threads = saved_threads
                if saved_env is None:
                    del _os.environ["PATHWAY_NODE_TIMING_LOG"]
                else:
                    _os.environ["PATHWAY_NODE_TIMING_LOG"] = saved_env
                G.clear()
    rps = {k: round(n_rows / v) for k, v in secs.items()}

    # -- wire bytes: raw vs sender-consolidated ---------------------------
    from pathway_tpu.engine.exchange import TcpCoordinator
    from pathway_tpu.engine.stream import consolidate

    # retraction-heavy batch: churn_pairs rows get +1 immediately followed
    # by -1 (net zero), churn_pairs more survive — consolidation halves+
    # the row count before encoding
    deltas = []
    for i in range(churn_pairs):
        k = ref_scalar("churn", i)
        deltas.append((k, (i, f"v{i}"), 1))
        deltas.append((k, (i, f"v{i}"), -1))
        deltas.append((ref_scalar("keep", i), (i, f"v{i}"), 1))

    base = _free_port_base(2)
    coords = [None, None]

    def _mk(w):
        coords[w] = TcpCoordinator(w, 2, base, run_id="bench-exchange")

    builders = [threading.Thread(target=_mk, args=(w,)) for w in (0, 1)]
    for b in builders:
        b.start()
    for b in builders:
        b.join()
    c0 = coords[0]
    try:
        before = c0._m_bytes_sent.value
        c0.send_data(1, 7, 2, deltas)
        raw_bytes = c0._m_bytes_sent.value - before
        consolidated = consolidate(deltas)
        before = c0._m_bytes_sent.value
        c0.send_data(1, 7, 4, consolidated)
        cons_bytes = c0._m_bytes_sent.value - before
    finally:
        for c in coords:
            if c is not None:
                c.close()

    print(json.dumps({
        "metric": "exchange_throughput",
        "value": rps["columnar"],
        "unit": "rows/s through the exchange node "
                "(2-thread-worker static wordcount shuffle)",
        "classic_rows_per_sec": rps["classic"],
        "classic_s": round(secs["classic"], 4),
        "columnar_s": round(secs["columnar"], 4),
        "columnar_vs_classic": round(rps["columnar"] / rps["classic"], 2),
        "bytes_sent_raw": raw_bytes,
        "bytes_sent_consolidated": cons_bytes,
        "consolidation_bytes_ratio": round(cons_bytes / raw_bytes, 3),
        "n_rows": n_rows,
    }))
    return rps


def bench_tick_overhead(workers=(2, 4), duration_s=3.0):
    """Coordination cost per streaming tick: N workers run an idle
    streaming pipeline (10 ms autocommit) and report ticks/s plus
    agreement rounds per tick.  Flat rounds/tick across worker counts =
    the per-tick barrier does not grow with the cluster (VERDICT: replace
    blanket per-tick agreement with punctuation-driven progress)."""
    import subprocess
    import sys
    import tempfile
    import textwrap

    script = textwrap.dedent(
        """
        import os, sys, time, threading
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import pathway_tpu as pw

        duration = float(sys.argv[1])

        class Subject(pw.io.python.ConnectorSubject):
            def run(self):
                self.next(x=1)
                time.sleep(duration)

        class S(pw.Schema):
            x: int

        t = pw.io.python.read(Subject(), schema=S)
        res = t.groupby(t.x).reduce(t.x, c=pw.reducers.count())
        got = []
        pw.io.subscribe(res, on_change=lambda *a, **k: got.append(1))
        t0 = time.perf_counter()
        pw.run(
            monitoring_level=pw.MonitoringLevel.NONE,
            autocommit_duration_ms=10,
        )
        elapsed = time.perf_counter() - t0
        from pathway_tpu.internals.runner import last_engine
        eng = last_engine()
        rounds = getattr(eng.coord, "_round", 0)
        ticks = getattr(eng, "flush_ticks", 0)
        print(f"STATS elapsed={elapsed:.3f} rounds={rounds} "
              f"ticks={ticks}")
        """
    )

    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        spath = _os.path.join(tmp, "idle.py")
        with open(spath, "w") as fh:
            fh.write(script)
        for n in workers:
            base = _free_port_base(n)
            procs = []
            for wid in range(n):
                env = dict(_os.environ)
                env.update(
                    PATHWAY_PROCESSES=str(n),
                    PATHWAY_PROCESS_ID=str(wid),
                    PATHWAY_FIRST_PORT=str(base),
                    JAX_PLATFORMS="cpu",
                    PYTHONPATH=repo,
                )
                procs.append(subprocess.Popen(
                    [sys.executable, spath, str(duration_s)], env=env,
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                    text=True,
                ))
            stats = None
            for wid, p in enumerate(procs):
                o, e = p.communicate(timeout=duration_s * 10 + 120)
                if p.returncode != 0:
                    raise RuntimeError(f"worker {wid}/{n}: {e[-1500:]}")
                if wid == 0:
                    for line in o.splitlines():
                        if line.startswith("STATS"):
                            stats = dict(
                                kv.split("=") for kv in line.split()[1:]
                            )
            assert stats, "worker 0 printed no stats"
            ticks = max(int(stats["ticks"]), 1)
            out[n] = {
                "ticks_per_s": round(ticks / float(stats["elapsed"]), 1),
                "rounds_per_tick": round(int(stats["rounds"]) / ticks, 2),
            }
    print(json.dumps({
        "metric": "streaming_tick_overhead",
        "value": out[max(workers)]["rounds_per_tick"],
        "unit": "agreement rounds per tick",
        "per_worker_count": {str(k): v for k, v in out.items()},
        "host_cpus": _os.cpu_count(),
    }))
    return out


def bench_failover(kill_epoch=12, n_rows=80):
    """Live-failover recovery latency: a 2-thread-worker streaming job
    with operator snapshots takes an injected worker kill mid-run; the
    surviving worker rolls back, the runner respawns the dead slot, and
    the job finishes.  Reports the survivor's measured kill-to-rejoin
    wall time (engine.last_failover_recovery_s)."""
    import subprocess
    import sys
    import tempfile
    import textwrap

    script = textwrap.dedent(
        """
        import os, sys, time
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import pathway_tpu as pw
        from pathway_tpu.internals import faults

        pstore, kill_epoch, n_rows = (
            sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
        )

        class Subject(pw.io.python.ConnectorSubject):
            def run(self):
                for i in range(n_rows):
                    self.next(k=i % 4, v=i)
                    self.commit()
                    time.sleep(0.005)

        t = pw.io.python.read(
            Subject(), schema=pw.schema_from_types(k=int, v=int),
            name="src",
        )
        res = t.groupby(t.k).reduce(t.k, s=pw.reducers.sum(t.v))
        got = []
        pw.io.subscribe(res, on_change=lambda *a, **k: got.append(1))
        faults.install(f"kill_worker@worker=1,epoch={kill_epoch}")
        pw.run(
            monitoring_level=pw.MonitoringLevel.NONE,
            autocommit_duration_ms=15,
            persistence_config=pw.persistence.Config(
                pw.persistence.Backend.filesystem(pstore),
                snapshot_interval_ms=20,
            ),
        )
        from pathway_tpu.internals.runner import last_engine
        eng = last_engine()
        print(f"STATS failovers={eng.failover_count} "
              f"recovery_s={eng.last_failover_recovery_s}")
        """
    )
    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    with tempfile.TemporaryDirectory() as tmp:
        spath = _os.path.join(tmp, "failover.py")
        with open(spath, "w") as fh:
            fh.write(script)
        env = dict(_os.environ)
        env.update(
            PATHWAY_THREADS="2", JAX_PLATFORMS="cpu", PYTHONPATH=repo
        )
        env.pop("PATHWAY_FAULTS", None)
        proc = subprocess.run(
            [
                sys.executable, spath,
                _os.path.join(tmp, "pstore"),
                str(kill_epoch), str(n_rows),
            ],
            env=env, capture_output=True, text=True, timeout=300,
        )
    if proc.returncode != 0:
        raise RuntimeError(f"failover bench failed: {proc.stderr[-1500:]}")
    stats = None
    for line in proc.stdout.splitlines():
        if line.startswith("STATS"):
            stats = dict(kv.split("=") for kv in line.split()[1:])
    assert stats, "failover bench printed no stats"
    recovery = (
        None
        if stats["recovery_s"] == "None"
        else round(float(stats["recovery_s"]), 4)
    )
    print(json.dumps({
        "metric": "failover_recovery_s",
        "value": recovery,
        "unit": "seconds from worker kill to rejoined mesh",
        "failovers": int(stats["failovers"]),
        "host_cpus": _os.cpu_count(),
    }))
    return recovery


if __name__ == "__main__":
    import sys as _sys

    if "--sanitize" in _sys.argv:
        # arm the runtime sanitizer for every benchmark below — the
        # armed-vs-off delta on these numbers IS the sanitizer's cost
        from pathway_tpu.internals import sanitizer as _sanitizer

        _sanitizer.install()

    if "--multiworker" in _sys.argv:
        bench_wordcount_multiworker()
    elif "--tick-overhead" in _sys.argv:
        bench_tick_overhead()
    elif "--failover" in _sys.argv:
        bench_failover()
    elif "--columnar" in _sys.argv:
        bench_join_columnar()
        bench_flatten_columnar()
    elif "--exchange" in _sys.argv:
        bench_exchange()
    elif "--fusion" in _sys.argv:
        bench_fused_chain()
    elif "--provenance" in _sys.argv:
        bench_provenance()
    else:
        bench_group_update_flatness()
        bench_wordcount()
        bench_join_columnar()
        bench_flatten_columnar()
        bench_fused_chain()

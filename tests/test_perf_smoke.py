"""Build-time-selection smoke guards (`perf_smoke` marker, tier-1).

The columnar nodes only pay off if the build-time gates actually pick
them; a regression there is silent — everything still passes, just 5x
slower.  These tests build small ELIGIBLE graphs and assert, via the
per-node path counters (internals/monitoring.node_path_stats), that the
columnar implementations were selected AND processed rows.  They are
smoke tests by design: fast enough for tier-1, no timing assertions
(the rows/s claims live in benchmarks/engine_bench.py).
"""

from __future__ import annotations

import functools
import json
import os
import statistics
import subprocess
import sys

import pytest

import pathway_tpu as pw
from pathway_tpu.debug import table_from_events
from pathway_tpu.engine.engine import Engine
from pathway_tpu.engine.value import ref_scalar
from pathway_tpu.internals.monitoring import node_path_stats
from pathway_tpu.internals.runner import run_tables
from pathway_tpu.internals.schema import schema_from_types


def _columnar_stats(engine):
    return {
        s["type"]: s
        for s in node_path_stats(engine)
        if s["path"] == "columnar"
    }


@pytest.mark.perf_smoke
def test_columnar_join_and_reduce_selected_with_live_counters():
    eng = Engine()
    lschema = schema_from_types(k=int, a=int)
    rschema = schema_from_types(k=int, b=int)
    left = table_from_events(
        lschema,
        [(2, (ref_scalar("l", i), (i % 5, i), 1)) for i in range(40)],
    )
    right = table_from_events(
        rschema,
        [(2, (ref_scalar("r", i), (i, i * 10), 1)) for i in range(5)],
    )
    joined = left.join(right, left.k == right.k).select(
        pw.left.k, pw.left.a, pw.right.b
    )
    per_key = joined.groupby(pw.this.k).reduce(
        pw.this.k,
        total=pw.reducers.sum(pw.this.a),
        mean=pw.reducers.avg(pw.this.a),
        c=pw.reducers.count(),
    )
    (cap,) = run_tables(per_key, engine=eng)
    assert len(cap.state.rows) == 5

    stats = _columnar_stats(eng)
    assert "VectorJoinNode" in stats, node_path_stats(eng)
    assert "VectorReduceNode" in stats, node_path_stats(eng)
    assert stats["VectorJoinNode"]["rows_processed"] > 0
    assert stats["VectorJoinNode"]["batches_processed"] > 0
    assert stats["VectorReduceNode"]["rows_processed"] > 0
    assert stats["VectorReduceNode"]["batches_processed"] > 0


@pytest.mark.perf_smoke
def test_columnar_flatten_selected_with_live_counters():
    eng = Engine()
    schema = schema_from_types(i=int, vs=list)
    t = table_from_events(
        schema,
        [
            (2, (ref_scalar("b", i), (i, [i, i + 1, i + 2]), 1))
            for i in range(30)
        ],
    )
    (cap,) = run_tables(t.flatten(pw.this.vs), engine=eng)
    assert len(cap.state.rows) == 90

    stats = _columnar_stats(eng)
    assert "VectorFlattenNode" in stats, node_path_stats(eng)
    assert stats["VectorFlattenNode"]["rows_processed"] == 30
    assert stats["VectorFlattenNode"]["batches_processed"] > 0


@functools.lru_cache(maxsize=None)
def _overhead_readings(round_: int) -> dict:
    """arm -> [the reading of each of nine child interpreters]: tests/
    _overhead_guard.py once per string-hash seed, one after the other."""
    guard = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "_overhead_guard.py")
    readings: dict = {}
    for seed in range(9 * round_, 9 * round_ + 9):
        out = subprocess.run(
            [sys.executable, guard], capture_output=True, text=True,
            timeout=300,
            env={**os.environ, "PYTHONHASHSEED": str(seed),
                 "JAX_PLATFORMS": "cpu"},
        )
        assert out.returncode == 0, out.stderr[-2000:]
        for arm, ratio in json.loads(out.stdout.splitlines()[-1]).items():
            readings.setdefault(arm, []).append(ratio)
    return readings


@pytest.mark.perf_smoke
@pytest.mark.parametrize("arm", ["metrics", "metrics_and_span_record"])
def test_observability_overhead_under_5pct(arm):
    """The metrics layer and the span record run unconditionally, so their
    cost on the engine microbench loop (source -> 3 rowwise maps, hundreds
    of rows/tick) must stay under 5% vs `Engine(metrics=False)`.

    Two arms.  `metrics`: per-node histograms, flight recorder and the
    `engine.tick` span against the bare loop.  `metrics_and_span_record`:
    the same loop handing one batch a tick to a DevicePipeline (its spans
    on the prep and dispatch threads, the submission's epoch taken on
    this one) against the bare loop with the same pipeline and the record
    stubbed out of it.

    How it is measured (tests/_overhead_guard.py), so that it holds on a
    loaded machine and from one run of the suite to the next; a min of 5
    wall-clock timings of a 16 ms loop did neither.  The clock is the
    engine thread's own CPU time: what the loop pays, whatever else the
    machine runs.  Both engines live side by side and take turns in
    blocks of 10 ticks, which side first alternating, so every pair of
    blocks sees the same machine; a reading is the median of 40 such
    pairs' ratios, which one interrupted block cannot move.  That much
    repeats to half a point within one process and no further: a reading
    carries a part that is the process's own (the string-hash seed, and
    with it the layout of every dict the loops touch: seeds 0 to 15 read
    0.996 to 1.044 on the first arm, each steadily for as long as its
    process lived), which no repetition inside one interpreter removes.
    So the test takes one reading in each of nine child interpreters,
    hash seeds 0 to 8, and judges their median; where that is not under
    the limit (neighbours that thrash the caches raise every child's
    reading for a while: the instrumented loop touches more memory), nine
    more children with the next seeds, and the median of all eighteen.
    The limit stays 5%."""
    readings: list = []
    for round_ in range(2):
        readings += _overhead_readings(round_)[arm]
        if statistics.median(readings) < 1.05:
            break
    assert statistics.median(readings) < 1.05, (
        f"always-on observability overhead, one reading a child: "
        f"{[round(r, 4) for r in readings]}"
    )


@pytest.mark.perf_smoke
def test_tracing_overhead_under_5pct(monkeypatch):
    """Epoch tracing defaults to ON at 1-in-16 sampling, so its cost on
    top of the metrics layer must also stay under 5%: A/B of
    PATHWAY_TRACE unset (default sampling) vs =0 (off), both arms with
    metrics enabled, over the same microbench as the metrics guard."""
    import gc
    from time import perf_counter

    from pathway_tpu.engine.engine import InputQueueSource, RowwiseNode

    ROWS, TICKS, REPS = 512, 40, 5
    deltas = [(ref_scalar("k", i), (i,), 1) for i in range(ROWS)]

    def ident(keys, cols):
        return cols[0]

    def run_once(trace_default: bool) -> float:
        if trace_default:
            monkeypatch.delenv("PATHWAY_TRACE", raising=False)
        else:
            monkeypatch.setenv("PATHWAY_TRACE", "0")
        eng = Engine()  # TraceStore reads the env at construction
        src = InputQueueSource(eng)
        node = src
        for _ in range(3):
            node = RowwiseNode(eng, [node], ident)
        try:
            time = 2
            for _ in range(8):  # warmup
                src.push(time, deltas)
                eng.process_time(time)
                time += 2
            t0 = perf_counter()
            for _ in range(TICKS):
                src.push(time, deltas)
                eng.process_time(time)
                time += 2
            return perf_counter() - t0
        finally:
            eng._gc_unfreeze()

    on, off = [], []
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(REPS):
            on.append(run_once(True))
            off.append(run_once(False))
    finally:
        if gc_was_enabled:
            gc.enable()
    ratio = min(on) / min(off)
    assert ratio < 1.05, (
        f"default-sampling tracing overhead {ratio:.3f}x "
        f"(on={min(on):.4f}s off={min(off):.4f}s)"
    )


@pytest.mark.perf_smoke
def test_dump_trace_is_valid_chrome_trace(monkeypatch, tmp_path):
    """A 2-thread-worker wordcount traced at every epoch must export a
    schema-valid Chrome trace_event document with spans from BOTH
    workers and paired cross-worker flow edges (the acceptance shape of
    the tracing layer, kept in tier-1 as a smoke guard)."""
    from pathway_tpu.internals.config import pathway_config
    from pathway_tpu.internals.runner import last_engine
    from pathway_tpu.internals.tracing import validate_chrome_trace

    monkeypatch.setenv("PATHWAY_TRACE", "1")
    old = pathway_config.threads
    pathway_config.threads = 2
    try:
        t = pw.debug.table_from_markdown(
            """
            word
            the
            quick
            the
            fox
            """
        )
        counts = t.groupby(pw.this.word).reduce(
            pw.this.word, n=pw.reducers.count()
        )
        pw.io.fs.write(counts, str(tmp_path / "out.jsonl"), format="json")
        pw.run(monitoring_level=None)
    finally:
        pathway_config.threads = old

    trace = last_engine().dump_trace(str(tmp_path / "trace.json"))
    validate_chrome_trace(trace)
    import json as _json

    validate_chrome_trace(
        _json.loads((tmp_path / "trace.json").read_text())
    )
    evs = trace["traceEvents"]
    assert {e["pid"] for e in evs if e.get("cat") == "node"} == {0, 1}
    starts = [e for e in evs if e["ph"] == "s"]
    finishes = [e for e in evs if e["ph"] == "f"]
    assert starts and {e["id"] for e in starts} == {
        e["id"] for e in finishes
    }


@pytest.mark.perf_smoke
def test_columnar_exchange_selected_on_two_workers(tmp_path):
    """An eligible keyed shuffle on a 2-thread-worker graph must route
    through the columnar scatter (vectorized shard codes + C partition
    pass), proven by the exchange node's own path counter — single-worker
    runs have no exchange node at all, so this needs a real worker pair."""
    from pathway_tpu.internals.config import pathway_config
    from pathway_tpu.internals.runner import last_engine

    old = pathway_config.threads
    pathway_config.threads = 2
    try:
        t = pw.debug.table_from_markdown(
            """
            k | v
            0 | 1
            1 | 2
            0 | 3
            2 | 4
            1 | 5
            2 | 6
            """
        )
        grouped = t.groupby(pw.this.k).reduce(
            pw.this.k, total=pw.reducers.sum(pw.this.v)
        )
        pw.io.fs.write(grouped, str(tmp_path / "out.jsonl"), format="json")
        pw.run(monitoring_level=None)
    finally:
        pathway_config.threads = old

    eng = last_engine()
    stats = _columnar_stats(eng)
    assert "_ExchangeNode" in stats, node_path_stats(eng)
    assert stats["_ExchangeNode"]["rows_processed"] > 0
    assert stats["_ExchangeNode"]["batches_processed"] > 0


@pytest.mark.perf_smoke
def test_ineligible_graphs_stay_classic():
    """The gates must also say no: non-hashable join keys and
    non-vector reducers fall back to classic nodes (path counters show
    no columnar node)."""
    eng = Engine()
    schema = schema_from_types(k=pw.Json, v=int)
    events = [
        (2, (ref_scalar("j", i), (pw.Json({"k": i % 2}), i), 1))
        for i in range(6)
    ]
    t = table_from_events(schema, events)
    t2 = table_from_events(schema, list(events))
    joined = t.join(t2, t.k == t2.k).select(a=pw.left.v, b=pw.right.v)
    sorted_vals = t.groupby(t.v % 2).reduce(
        vals=pw.reducers.sorted_tuple(t.v)
    )
    run_tables(joined, sorted_vals, engine=eng)
    assert _columnar_stats(eng) == {}


@pytest.mark.perf_smoke
def test_async_device_pipeline_selected_when_enabled(monkeypatch):
    """The async ingest pipeline is selected by what the code observes:
    an eligible ingest (no factory mesh, device not degraded, no failed
    batch) MUST route through the DevicePipeline — proven by the
    pipeline's own dispatch counters, not timing (the docs/s claim is
    the benchmark's, BENCHMARK.json)."""
    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.models.transformer import TransformerConfig
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        _FusedKnnIndexImpl,
    )

    tiny = TransformerConfig(
        vocab_size=512, hidden=32, layers=1, heads=2, mlp_dim=64, max_len=32
    )
    impl = _FusedKnnIndexImpl(
        SentenceEncoder("smoke-pipeline", config=tiny, max_len=16),
        "cos",
        32,
    )
    texts = [f"alpha doc{i} bravo" for i in range(16)]
    impl.add_many(range(16), texts, [None] * 16)
    impl.drain()
    assert impl._pipeline is not None, "async ingest path not selected"
    stats = impl._pipeline.stats()
    assert stats["dispatched"] >= 1
    assert stats["rows"] == 16
    assert not impl._pipeline_broken


# ---------------------------------------------------------------------------
# static analyzer over the benchmark topologies: the graphs we publish
# numbers for must lint clean, and the analyzer's columnar predictions
# must match what the build actually selects (PWT399 drift guard)
# ---------------------------------------------------------------------------

import os as _os
import sys as _sys

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO not in _sys.path:
    _sys.path.insert(0, _REPO)


def _bench_builders():
    from benchmarks.engine_bench import GRAPH_BUILDERS

    return sorted(GRAPH_BUILDERS.items())


# keep in sync with benchmarks.engine_bench.GRAPH_BUILDERS — pytest needs
# the names at collection time, and test_builder_parametrization_is_complete
# fails loudly when a new topology is added without extending this tuple
_BUILDER_NAMES = ("flatten", "join", "reduce", "wordcount", "wordcount_chain")


@pytest.mark.perf_smoke
def test_builder_parametrization_is_complete():
    from benchmarks.engine_bench import GRAPH_BUILDERS

    assert tuple(sorted(GRAPH_BUILDERS)) == _BUILDER_NAMES


@pytest.mark.perf_smoke
@pytest.mark.parametrize("name", _BUILDER_NAMES)
def test_benchmark_graph_lints_clean_and_fusion_parity(name):
    """`pathway-tpu analyze --fail-on=error` semantics over every
    engine_bench topology: no error-severity findings, ever.  Then the
    PWT599 half of the contract: build the topology and cross-check the
    fusion plan the runner installed against the fused nodes it actually
    instantiated."""
    from benchmarks.engine_bench import GRAPH_BUILDERS
    from pathway_tpu.analysis import Severity, analyze, verify_fusion

    pw.G.clear()
    result_table = GRAPH_BUILDERS[name]()
    result = analyze(pw.G, extra_tables=(result_table,), workers=1)
    errors = [f for f in result.findings if f.severity >= Severity.ERROR]
    assert not errors, (name, result.render_text())
    (capture,) = run_tables(result_table)
    verify_fusion(capture.engine, result)
    drift = [f for f in result.findings if f.code == "PWT599"]
    assert not drift, (name, result.render_text())


@pytest.mark.perf_smoke
def test_benchmark_predictions_match_selection():
    """Prediction/selection parity on every engine_bench topology: the
    analyzer must predict the columnar path AND verify_against_plan must
    agree with the nodes the engine actually built."""
    from pathway_tpu.analysis import analyze, verify_against_plan

    expected_op = {
        "reduce": "reduce",
        "wordcount": "reduce",
        "wordcount_chain": "reduce",
        "join": "join",
        "flatten": "flatten",
    }
    for name, builder in _bench_builders():
        pw.G.clear()
        result_table = builder()
        result = analyze(pw.G, extra_tables=(result_table,), workers=1)
        preds = {
            (p["op"], p["predicted"])
            for p in result.predictions
            if p["anchored"]
        }
        assert (expected_op[name], "columnar") in preds, (name, preds)
        (capture,) = run_tables(result_table)
        verify_against_plan(capture.engine, result)
        drift = [f for f in result.findings if f.code == "PWT399"]
        assert not drift, (name, result.render_text())


@pytest.mark.perf_smoke
def test_scaling_bench_graph_lints_clean(tmp_path):
    """The scaling benchmark's wordcount pipeline (fs json read ->
    groupby(word).count -> csv write) also passes --fail-on=error and
    predicts the columnar reduce."""
    from benchmarks.scaling_bench import build_wordcount_graph
    from pathway_tpu.analysis import Severity, analyze

    in_dir = tmp_path / "input"
    in_dir.mkdir()
    (in_dir / "a.jsonl").write_text('{"word": "x"}\n{"word": "y"}\n')
    pw.G.clear()
    build_wordcount_graph(str(in_dir), str(tmp_path / "out.csv"))
    result = analyze(pw.G, workers=1)
    errors = [f for f in result.findings if f.severity >= Severity.ERROR]
    assert not errors, result.render_text()
    assert [
        (p["op"], p["predicted"]) for p in result.predictions
    ] == [("reduce", "columnar")]


@pytest.mark.perf_smoke
def test_cli_analyze_json_gate_over_example_graph(tmp_path, capsys):
    """The CI gate exactly as documented: `pathway-tpu analyze
    --fail-on=error --json` over a representative example pipeline (the
    engine_bench wordcount_chain shape) exits 0 and emits schema-stamped
    JSON with the fusion plan attached."""
    import json as _json

    from pathway_tpu.analysis import SCHEMA_VERSION
    from pathway_tpu.cli import main

    script = tmp_path / "wc_chain.py"
    script.write_text(
        "import pathway_tpu as pw\n"
        "t = pw.debug.table_from_rows(\n"
        "    pw.schema_from_types(word=str, n=int), [('a', 1), ('b', 2)]\n"
        ")\n"
        "s = t.select(word=t.word, n=t.n * 2)\n"
        "f = s.filter(s.n >= 0)\n"
        "res = f.groupby(f.word).reduce(f.word, c=pw.reducers.count())\n"
        "pw.io.subscribe(res, on_change=lambda *a, **kw: None)\n"
        "pw.run()\n"
    )
    rc = main([
        "analyze", str(script),
        "--fail-on", "error", "--json", "--mesh", "dp=1,tp=2",
    ])
    assert rc == 0
    payload = _json.loads(capsys.readouterr().out)
    assert payload["schema_version"] == SCHEMA_VERSION
    assert any(c["length"] >= 2 for c in payload["fusion"]["chains"])


@pytest.mark.perf_smoke
def test_analyzer_new_passes_overhead_under_5pct():
    """The fusion (PWT5xx) and mesh (PWT4xx) passes ride the CI gate
    (`analyze --fail-on=error --json` over every benchmark topology), so
    the gate with them enabled must cost under 5% more than without —
    same min-of-N interleaved protocol as the other overhead guards.
    Each sample is one full gate run (graph build + all passes + JSON
    serialization): that is the unit CI pays for, and the build half is
    what the new passes must stay marginal against.  gc runs between
    samples, not inside them — graph building is allocation-heavy and
    collector pauses would otherwise dominate the A/B difference."""
    import gc
    import json as _json
    from time import perf_counter

    import pathway_tpu.analysis as analysis_mod
    from benchmarks.engine_bench import GRAPH_BUILDERS
    from pathway_tpu.analysis.passes import fusion_pass, mesh_pass

    REPS = 12

    def _noop(*a, **k):
        return None

    def run_gate(with_new_passes: bool) -> float:
        analysis_mod.fusion_pass = fusion_pass if with_new_passes else _noop
        analysis_mod.mesh_pass = mesh_pass if with_new_passes else _noop
        pw.G.clear()
        gc.collect()
        t0 = perf_counter()
        tails = tuple(b() for b in GRAPH_BUILDERS.values())
        result = analysis_mod.analyze(
            pw.G, extra_tables=tails, workers=2, mesh="dp=2,tp=2"
        )
        _json.dumps(result.to_dict())
        return perf_counter() - t0

    on, off = [], []
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        run_gate(True)  # warmup both arms
        run_gate(False)
        for i in range(REPS):
            # alternate arm order so slow drift cannot bias one arm
            first = i % 2 == 0
            a = run_gate(first)
            b = run_gate(not first)
            (on if first else off).append(a)
            (off if first else on).append(b)
    finally:
        analysis_mod.fusion_pass = fusion_pass
        analysis_mod.mesh_pass = mesh_pass
        if gc_was_enabled:
            gc.enable()
        pw.G.clear()
    ratio = min(on) / min(off)
    assert ratio < 1.05, (
        f"fusion+mesh pass overhead {ratio:.3f}x "
        f"(with={min(on):.4f}s without={min(off):.4f}s)"
    )


def test_analyzer_purity_pass_overhead_under_5pct():
    """The purity pass (PWT9xx, the analyzer's 12th pass) on the same
    CI gate: its marginal cost over the other eleven passes must stay
    under 5%.  Measured separately from the fusion+mesh guard above —
    that pair already sits near its own budget, and the purity pass's
    steady-state cost is a per-code-object cache hit (purity.py
    _source_cache), which this guard is really pinning down."""
    import gc
    import json as _json
    from time import perf_counter

    import pathway_tpu.analysis as analysis_mod
    from benchmarks.engine_bench import GRAPH_BUILDERS
    from pathway_tpu.analysis.purity import purity_pass

    REPS = 12

    def _noop(*a, **k):
        return None

    def run_gate(with_purity: bool) -> float:
        analysis_mod.purity_pass = purity_pass if with_purity else _noop
        pw.G.clear()
        gc.collect()
        t0 = perf_counter()
        tails = tuple(b() for b in GRAPH_BUILDERS.values())
        result = analysis_mod.analyze(
            pw.G, extra_tables=tails, workers=2, mesh="dp=2,tp=2"
        )
        _json.dumps(result.to_dict())
        return perf_counter() - t0

    ratios = []
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        run_gate(True)  # warmup both arms (and the purity caches)
        run_gate(False)
        for _ in range(REPS):
            ratios.append(run_gate(True) / run_gate(False))
    finally:
        analysis_mod.purity_pass = purity_pass
        if gc_was_enabled:
            gc.enable()
        pw.G.clear()
    ratio = min(ratios)
    assert ratio < 1.05, (
        f"purity pass overhead {ratio:.3f}x (pair ratios "
        f"{[round(r, 3) for r in ratios]})"
    )


@pytest.mark.perf_smoke
def test_mesh_none_builds_stay_byte_identical():
    """The mesh execution backend must be FULLY dormant without a mesh:
    an activate/deactivate cycle earlier in the process cannot leave any
    residue in a mesh=None build.  Proven at three layers: the fused
    ingest still prepares classic `packed` payloads (not `packed_dp`),
    the encoder params object is the un-devices-put original, and the
    ingested index buffer is byte-identical to one built in a process
    state where the backend was never armed."""
    import numpy as np

    from pathway_tpu.analysis.mesh import MeshSpec
    from pathway_tpu.internals import mesh_backend
    from pathway_tpu.models.minilm import SentenceEncoder
    from pathway_tpu.models.transformer import TransformerConfig
    from pathway_tpu.stdlib.indexing.nearest_neighbors import (
        _FusedKnnIndexImpl,
    )

    tiny = TransformerConfig(
        vocab_size=512, hidden=32, layers=1, heads=2, mlp_dim=64, max_len=32
    )
    enc = SentenceEncoder("smoke-mesh-none", config=tiny, max_len=16)
    texts = [f"alpha doc{i} bravo charlie" for i in range(16)]
    keys = list(range(16))

    def ingest():
        impl = _FusedKnnIndexImpl(enc, "cos", 32)
        # dormant-path invariants: no adopted mesh, classic flat free
        # list, original params object, classic packed payloads
        assert impl.knn.mesh is None
        assert impl.knn._free_set is None
        assert impl.fused._params() is enc.lm.params
        payload, _meta = impl.fused.prepare_batch(keys, texts)
        assert payload[0] == "packed"
        impl.add_many(keys, texts, [None] * 16)
        impl.drain()
        return np.asarray(impl.knn._buffer.astype("float32"))[:16].copy()

    before = ingest()
    backend = mesh_backend.activate(MeshSpec.parse("dp=4,tp=2"))
    mesh_backend.deactivate()
    after = ingest()
    assert np.array_equal(before, after)
    if backend is not None:  # 8 emulated devices: the cycle really armed
        assert mesh_backend.active_backend() is None


@pytest.mark.perf_smoke
def test_run_mesh_backend_activation_overhead_under_5pct():
    """The execution backend's contribution to a mesh-armed pw.run
    (activate: build the jax Mesh + publish; deactivate in the run's
    finally) must stay marginal.  The PWT4xx lint pass predates the
    backend and runs in BOTH arms — the A/B is the same mesh-armed run
    with activation live vs stubbed to its lint-only return, so the
    ratio isolates exactly the machinery this layer added to the run
    path.  The graph is sized so a run costs ~10 ms — the budget is 5%
    of a realistic small run, not of an empty-graph floor where the
    one-time Mesh construction (~0.1 ms) would dominate any ratio.
    Same min-of-N interleaved protocol as the other guards."""
    import gc
    from time import perf_counter

    from pathway_tpu.internals import mesh_backend

    real_activate = mesh_backend.activate

    def run_once(with_backend: bool) -> float:
        mesh_backend.activate = (
            real_activate if with_backend else (lambda spec: None)
        )
        pw.G.clear()
        t = pw.debug.table_from_rows(
            pw.schema_from_types(k=int, v=int),
            [(i % 97, i) for i in range(8192)],
        )
        s = t.select(k=t.k, v=t.v * 2)
        f = s.filter(s.v >= 0)
        res = f.groupby(f.k).reduce(f.k, total=pw.reducers.sum(f.v))
        pw.io.subscribe(res, on_change=lambda *a, **kw: None)
        t0 = perf_counter()
        pw.run(mesh="dp=1,tp=1", monitoring_level=None)
        return perf_counter() - t0

    REPS = 6
    on, off = [], []
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        run_once(True)  # warmup both arms
        run_once(False)
        for i in range(REPS):
            first = i % 2 == 0  # alternate order against slow drift
            a = run_once(first)
            b = run_once(not first)
            (on if first else off).append(a)
            (off if first else on).append(b)
    finally:
        mesh_backend.activate = real_activate
        mesh_backend.deactivate()
        if gc_was_enabled:
            gc.enable()
        pw.G.clear()
    ratio = min(on) / min(off)
    assert ratio < 1.05, (
        f"mesh backend activation overhead {ratio:.3f}x "
        f"(live={min(on):.4f}s stubbed={min(off):.4f}s)"
    )


def test_fault_harness_overhead_under_5pct():
    """The chaos harness guard sits on the driver's flush hot path
    (`if faults.ACTIVE: faults.on_epoch(...)`).  Disabled — and even
    armed with directives that never match — it must cost under 5% on
    the engine microbench loop.  Same min-of-N interleaved protocol as
    the metrics guard above."""
    import gc
    from time import perf_counter

    from pathway_tpu.engine.engine import InputQueueSource, RowwiseNode
    from pathway_tpu.internals import faults

    ROWS, TICKS, REPS = 512, 40, 5
    deltas = [(ref_scalar("k", i), (i,), 1) for i in range(ROWS)]

    def ident(keys, cols):
        return cols[0]

    def run_once(armed: bool) -> float:
        if armed:
            # directives that can never fire: wrong worker, far epoch
            faults.install("kill_worker@worker=99,epoch=1000000000")
        else:
            faults.clear()
        eng = Engine(metrics=False)
        src = InputQueueSource(eng)
        node = src
        for _ in range(3):
            node = RowwiseNode(eng, [node], ident)
        try:
            time = 2
            for _ in range(8):  # warmup
                src.push(time, deltas)
                eng.process_time(time)
                time += 2
            t0 = perf_counter()
            for _ in range(TICKS):
                src.push(time, deltas)
                if faults.ACTIVE:
                    faults.on_epoch(0, time, None)
                eng.process_time(time)
                time += 2
            return perf_counter() - t0
        finally:
            eng._gc_unfreeze()

    on, off = [], []
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(REPS):
            on.append(run_once(True))
            off.append(run_once(False))
    finally:
        faults.clear()
        if gc_was_enabled:
            gc.enable()
    ratio = min(on) / min(off)
    assert ratio < 1.05, (
        f"fault-harness overhead {ratio:.3f}x "
        f"(armed={min(on):.4f}s off={min(off):.4f}s)"
    )


@pytest.mark.perf_smoke
def test_utilization_accounting_overhead_under_5pct():
    """The live-utilization hooks sit on the device pipeline's dispatch
    loop (`if utilization.ENABLED: tracker().note_*`).  Enabled at the
    default sampling (every dispatch) the full accounting — two span
    notes plus a batch note per tick — must cost under 5% on the engine
    microbench loop; disabled it is one module-attribute read.  Same
    min-of-N interleaved protocol as the metrics/fault guards above."""
    import gc
    from time import perf_counter

    from pathway_tpu.engine.engine import InputQueueSource, RowwiseNode
    from pathway_tpu.internals import utilization

    # the raw accounting is ~3us against a ~500us tick (<1%); REPS=7
    # (vs the siblings' 5) buys min-of-N margin against suite-load noise
    ROWS, TICKS, REPS = 512, 40, 7
    deltas = [(ref_scalar("k", i), (i,), 1) for i in range(ROWS)]

    def ident(keys, cols):
        return cols[0]

    def run_once(enabled: bool) -> float:
        saved = utilization.ENABLED
        utilization.ENABLED = enabled
        utilization.reset_window()
        eng = Engine(metrics=False)
        src = InputQueueSource(eng)
        node = src
        for _ in range(3):
            node = RowwiseNode(eng, [node], ident)
        try:
            time = 2
            for _ in range(8):  # warmup
                src.push(time, deltas)
                eng.process_time(time)
                time += 2
            t0 = perf_counter()
            for _ in range(TICKS):
                src.push(time, deltas)
                if utilization.ENABLED:
                    tr = utilization.tracker()
                    tr.note_span("dispatch", 0.001)
                    tr.note_span("wait", 0.001)
                    tr.note_batch(ROWS, ROWS * 20, ROWS * 32, 1e9)
                eng.process_time(time)
                time += 2
            return perf_counter() - t0
        finally:
            utilization.ENABLED = saved
            eng._gc_unfreeze()

    on, off = [], []
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(REPS):
            on.append(run_once(True))
            off.append(run_once(False))
    finally:
        from pathway_tpu.internals import utilization as _u

        _u.reset_window()
        if gc_was_enabled:
            gc.enable()
    ratio = min(on) / min(off)
    assert ratio < 1.05, (
        f"utilization accounting overhead {ratio:.3f}x "
        f"(on={min(on):.4f}s off={min(off):.4f}s)"
    )


@pytest.mark.perf_smoke
def test_memtrack_accounting_overhead_under_5pct():
    """The memory-accounting hooks sit on the same dispatch loop as the
    utilization hooks (`if memtrack.ENABLED: tracker().adjust/note_*`).
    Enabled — one in-flight adjust pair plus an ingest note per tick,
    the full per-dispatch hook cost — must stay under 5% on the engine
    microbench loop; disabled it is one module-attribute read.  Same
    min-of-N interleaved protocol as the metrics/utilization guards."""
    import gc
    from time import perf_counter

    from pathway_tpu.engine.engine import InputQueueSource, RowwiseNode
    from pathway_tpu.internals import memtrack

    # same REPS=7 margin rationale as the utilization guard above
    ROWS, TICKS, REPS = 512, 40, 7
    deltas = [(ref_scalar("k", i), (i,), 1) for i in range(ROWS)]

    def ident(keys, cols):
        return cols[0]

    def run_once(enabled: bool) -> float:
        saved = memtrack.ENABLED
        memtrack.ENABLED = enabled
        memtrack.reset_for_tests()
        eng = Engine(metrics=False)
        src = InputQueueSource(eng)
        node = src
        for _ in range(3):
            node = RowwiseNode(eng, [node], ident)
        owner = object()
        try:
            time = 2
            for _ in range(8):  # warmup
                src.push(time, deltas)
                eng.process_time(time)
                time += 2
            t0 = perf_counter()
            for _ in range(TICKS):
                src.push(time, deltas)
                if memtrack.ENABLED:
                    tr = memtrack.tracker()
                    tr.adjust("pipeline_inflight", owner, 4096.0)
                    tr.note_ingest(ROWS, ROWS * 65.0)
                    tr.adjust("pipeline_inflight", owner, -4096.0)
                eng.process_time(time)
                time += 2
            return perf_counter() - t0
        finally:
            memtrack.ENABLED = saved
            eng._gc_unfreeze()

    on, off = [], []
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(REPS):
            on.append(run_once(True))
            off.append(run_once(False))
    finally:
        memtrack.reset_for_tests()
        if gc_was_enabled:
            gc.enable()
    ratio = min(on) / min(off)
    assert ratio < 1.05, (
        f"memory accounting overhead {ratio:.3f}x "
        f"(on={min(on):.4f}s off={min(off):.4f}s)"
    )


@pytest.mark.perf_smoke
def test_health_controller_overhead_under_5pct():
    """The self-healing controller's hook sits on the driver's flush
    path (`if health.ENABLED: health.on_epoch(...)`).  Armed but idle —
    controller live, no faults, no pressure, no roll — it must cost
    under 5% on the engine microbench loop; with PATHWAY_HEALTH=0 the
    hook collapses to one module-attribute read.  Same min-of-N
    interleaved protocol as the fault/utilization/memtrack guards."""
    import gc
    from time import perf_counter

    from pathway_tpu.engine.engine import InputQueueSource, RowwiseNode
    from pathway_tpu.internals import health

    # the armed-idle hook measures ~3us against a ~600us tick (<1%);
    # TICKS=80 doubles the timed region and REPS=9 buys min-of-N margin
    # so scheduler jitter can't fake a >5% ratio
    ROWS, TICKS, REPS = 512, 80, 9
    deltas = [(ref_scalar("k", i), (i,), 1) for i in range(ROWS)]

    def ident(keys, cols):
        return cols[0]

    def run_once(enabled: bool) -> float:
        saved = health.ENABLED
        health.ENABLED = enabled
        health.reset_for_tests()
        eng = Engine(metrics=False)
        src = InputQueueSource(eng)
        node = src
        for _ in range(3):
            node = RowwiseNode(eng, [node], ident)
        try:
            time = 2
            # warmup runs the SAME hook as the measured loop: the fresh
            # controller's first paced sensor evaluation (memtrack
            # capacity probe, utilization read) must not land inside
            # the timed region — steady-state cost is what's guarded
            for _ in range(8):
                src.push(time, deltas)
                if health.ENABLED:
                    health.on_epoch(0, time, None)
                eng.process_time(time)
                time += 2
            t0 = perf_counter()
            for _ in range(TICKS):
                src.push(time, deltas)
                if health.ENABLED:
                    health.on_epoch(0, time, None)
                eng.process_time(time)
                time += 2
            return perf_counter() - t0
        finally:
            health.ENABLED = saved
            eng._gc_unfreeze()

    ratios = []
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(REPS):
            ratios.append(run_once(True) / run_once(False))
    finally:
        health.reset_for_tests()
        if gc_was_enabled:
            gc.enable()
    # paired per-rep ratios, best pair judged: each rep's armed/off runs
    # are back-to-back, so the min ratio is immune to the slow drift
    # that makes min-of-mins flap on a shared box — a systematically
    # >5% hook would push EVERY pair above threshold
    ratio = min(ratios)
    assert ratio < 1.05, (
        f"health controller overhead {ratio:.3f}x (pair ratios "
        f"{[round(r, 3) for r in ratios]})"
    )


@pytest.mark.perf_smoke
def test_health_disabled_is_single_attribute_read():
    """PATHWAY_HEALTH=0: importing the module and consulting status must
    never instantiate the controller, and the hook guard is literally
    `health.ENABLED` — a module attribute that is False."""
    import os
    import subprocess
    import sys

    code = (
        "from pathway_tpu.internals import health;"
        "assert health.ENABLED is False;"
        "assert health._CONTROLLER is None;"
        "assert health.health_metrics() is None;"
        "assert health.health_status() == {'enabled': False};"
        "assert health._CONTROLLER is None, 'status instantiated it'"
    )
    env = dict(os.environ)
    env["PATHWAY_HEALTH"] = "0"
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=env,
    )
    assert proc.returncode == 0, proc.stderr


@pytest.mark.perf_smoke
def test_qtrace_default_sampling_overhead_under_5pct():
    """Query tracing at default sampling (every query traced) on the
    serving path: per tick the microbench runs ONE full span lifecycle —
    begin, the mark chain, a device charge, finish into the digests —
    mirroring the rest connector's one-commit-per-query shape.  Ticks
    are sized at 1024 rows (~0.8 ms) to match the measured serving-path
    per-query engine cost (p50 ~1.1 ms on the CPU), so
    the ratio guards the real claim: hooks <5% of a served query.  The
    span lifecycle itself measures ~18 us.  Paired per-rep ratios with
    the min judged, as in the health-controller guard: each rep's
    on/off runs are back-to-back so slow drift cannot fake a ratio, and
    a systematically >5% hook pushes EVERY pair above threshold."""
    import gc
    from time import perf_counter

    from pathway_tpu.engine.engine import InputQueueSource, RowwiseNode
    from pathway_tpu.internals import qtrace

    ROWS, TICKS, REPS = 1024, 40, 9
    deltas = [(ref_scalar("k", i), (i,), 1) for i in range(ROWS)]

    def ident(keys, cols):
        return cols[0]

    def run_once(enabled: bool) -> float:
        saved = qtrace.ENABLED
        qtrace.ENABLED = enabled
        qtrace.reset()
        eng = Engine(metrics=False)
        src = InputQueueSource(eng)
        node = src
        for _ in range(3):
            node = RowwiseNode(eng, [node], ident)
        qn = 0

        def one_query() -> None:
            nonlocal qn
            if qtrace.ENABLED:
                tq = qtrace.tracker()
                qid = f"q{qn}"
                qn += 1
                tq.begin(qid)
                tq.mark(qid, "enqueued")
                tq.mark(qid, "picked")
                tq.mark(qid, "search_start")
                tq.note_device(qid, seconds=0.0004, replica_times=None)
                tq.mark(qid, "device_end")
                tq.mark(qid, "emitted")
                tq.finish(qid)

        try:
            time = 2
            for _ in range(8):  # warmup
                src.push(time, deltas)
                one_query()
                eng.process_time(time)
                time += 2
            t0 = perf_counter()
            for _ in range(TICKS):
                src.push(time, deltas)
                one_query()
                eng.process_time(time)
                time += 2
            return perf_counter() - t0
        finally:
            qtrace.ENABLED = saved
            eng._gc_unfreeze()

    ratios = []
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for i in range(REPS):
            first = i % 2 == 0  # alternate arm order against drift
            a = run_once(first)
            b = run_once(not first)
            on_t, off_t = (a, b) if first else (b, a)
            ratios.append(on_t / off_t)
    finally:
        from pathway_tpu.internals import qtrace as _q

        _q.reset()
        if gc_was_enabled:
            gc.enable()
    ratio = min(ratios)
    assert ratio < 1.05, (
        f"qtrace default-sampling overhead {ratio:.3f}x (pair ratios "
        f"{[round(r, 3) for r in ratios]})"
    )


@pytest.mark.perf_smoke
def test_digest_render_within_budget_of_log2():
    """The metrics histograms grew a companion t-digest; a scrape
    (percentiles + exposition render) with digest-backed quantiles must
    stay within budget of the log2 bucket walk it replaced.  The log2
    arm is the reconstructed-from-wire state (bucket counts, empty
    digest -> `percentile` takes the geometric-midpoint fallback).  A
    trickle of fresh observations lands between scrapes, as in
    production: a regression that compresses the digest on every
    percentile call (instead of only when the buffer has data and at
    most once per scrape) costs ~ms per series and fails both bounds.
    Budget: 20x the log2 walk (measured ~6x: a ~1.3k-centroid walk vs
    ~40 buckets) and 50 ms absolute for the 8-series scrape."""
    import random
    from time import perf_counter

    from pathway_tpu.internals.metrics import MetricsRegistry

    K, N, TRICKLE = 8, 10_000, 64
    rng = random.Random(11)
    vals = [rng.expovariate(1000.0) for _ in range(N)]

    def build(digest_backed: bool):
        reg = MetricsRegistry(worker="0")
        fam = reg.histogram("scrape_seconds", help="x", labels=("op",))
        hs = []
        for k in range(K):
            h = fam.labels(f"op{k}")
            for v in vals:
                h.observe(v)
            if not digest_backed:
                h.digest = type(h.digest)()  # wire-reconstructed state
            hs.append(h)
        return reg, hs

    def steady_scrape(reg, hs) -> float:
        best = None
        for _ in range(5):
            for h in hs:
                for v in vals[:TRICKLE]:
                    h.observe(v)
            t0 = perf_counter()
            for h in hs:
                h.percentile(50)
                h.percentile(99)
            reg.render()
            dt = perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    reg_d, hs_d = build(True)
    reg_l, hs_l = build(False)
    steady_scrape(reg_d, hs_d)  # warmup: absorb the first-compress cost
    steady_scrape(reg_l, hs_l)
    digest_s = steady_scrape(reg_d, hs_d)
    log2_s = steady_scrape(reg_l, hs_l)
    assert digest_s < 0.050, f"digest scrape {digest_s * 1000:.1f}ms"
    assert digest_s / log2_s < 20.0, (
        f"digest-backed scrape {digest_s / log2_s:.1f}x the log2 walk "
        f"(digest={digest_s * 1000:.2f}ms log2={log2_s * 1000:.2f}ms)"
    )


@pytest.mark.perf_smoke
def test_profiler_idle_is_noop():
    """With no capture requested the profiler must be pure state reads:
    importing internals/profiler.py and consulting its status must not
    initialize jax (the import is deferred into capture()), and the
    busy-guard check is a single attribute read."""
    import subprocess
    import sys

    code = (
        "import sys;"
        "from pathway_tpu.internals import profiler;"
        "assert profiler.capture_active() is False;"
        "assert profiler.profiler_status() == {'active': None, 'last': None};"
        "assert 'jax' not in sys.modules, 'idle profiler pulled in jax'"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr


@pytest.mark.perf_smoke
def test_serving_armed_idle_overhead_under_5pct():
    """The serving tier armed but idle — tier live, micro-batcher flush
    thread parked on its condition variable, zero queries in flight —
    must cost under 5% on the engine ingest microbench.  Each tick runs
    the real ingest-side hook (serving.note_index_add: one module-attr
    read, one None check, and when armed one cache-generation bump), so
    the guard covers both the hook and any ambient cost of the live
    flush thread.  Same paired min-of-N protocol as the health guard."""
    import gc
    from time import perf_counter

    from pathway_tpu.engine.engine import InputQueueSource, RowwiseNode
    from pathway_tpu.internals import serving

    ROWS, TICKS, REPS = 512, 80, 9
    deltas = [(ref_scalar("k", i), (i,), 1) for i in range(ROWS)]

    def ident(keys, cols):
        return cols[0]

    def run_once(armed: bool) -> float:
        saved = serving.ENABLED
        serving.ENABLED = armed
        if armed:
            serving.reset_for_tests()  # tier + parked flush machinery
        else:
            serving.shutdown()
        eng = Engine(metrics=False)
        src = InputQueueSource(eng)
        node = src
        for _ in range(3):
            node = RowwiseNode(eng, [node], ident)
        try:
            time = 2
            for _ in range(8):  # warmup outside the timed region
                src.push(time, deltas)
                serving.note_index_add(ROWS)
                eng.process_time(time)
                time += 2
            t0 = perf_counter()
            for _ in range(TICKS):
                src.push(time, deltas)
                serving.note_index_add(ROWS)
                eng.process_time(time)
                time += 2
            return perf_counter() - t0
        finally:
            serving.ENABLED = saved
            eng._gc_unfreeze()

    ratios = []
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(REPS):
            ratios.append(run_once(True) / run_once(False))
    finally:
        serving.shutdown()
        if gc_was_enabled:
            gc.enable()
    # paired per-rep ratios, best pair judged (see the health guard for
    # why min-of-pairs is drift-immune on a shared box)
    ratio = min(ratios)
    assert ratio < 1.05, (
        f"serving armed-idle overhead {ratio:.3f}x (pair ratios "
        f"{[round(r, 3) for r in ratios]})"
    )


@pytest.mark.perf_smoke
def test_serving_disabled_is_single_attribute_read():
    """PATHWAY_SERVING=0: importing the module and consulting status
    must never instantiate the tier, and the ingest hooks reduce to one
    module-attribute read against None."""
    import os
    import subprocess
    import sys

    code = (
        "from pathway_tpu.internals import serving;"
        "assert serving.ENABLED is False;"
        "assert serving._TIER is None;"
        "serving.note_index_add(4);"
        "serving.note_index_remove('k');"
        "assert serving.serving_metrics() is None;"
        "assert serving.serving_status() == {'enabled': False};"
        "assert serving._TIER is None, 'status/hooks instantiated it'"
    )
    env = dict(os.environ)
    env["PATHWAY_SERVING"] = "0"
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=env,
    )
    assert proc.returncode == 0, proc.stderr


@pytest.mark.perf_smoke
def test_costledger_armed_idle_overhead_under_5pct():
    """The cost ledger armed on an otherwise idle job — instantiated,
    exporting families, zero queries in flight — must cost under 5% on
    the device-pipeline microbench.  Each completion runs the real
    ingest hook (one module-attr read when disabled; one per-dispatch
    charge() under the ledger lock when armed).  Same paired min-of-N
    protocol as the serving guard."""
    import gc
    from time import perf_counter

    from pathway_tpu.internals import costledger
    from pathway_tpu.internals.device_pipeline import DevicePipeline

    BATCHES, REPS = 200, 9
    meta = {
        "rows": 4, "real_tokens": 64, "slab_tokens": 64,
        "slab_bytes": 256, "useful_flops": 1.0e6,
    }

    def run_once(armed: bool) -> float:
        saved = costledger.ENABLED
        costledger.ENABLED = armed
        costledger.reset_for_tests()
        if armed:
            costledger.ledger()
        pipe = DevicePipeline(
            lambda item: (item, dict(meta)),
            dispatch=lambda payload: payload,
            wait=lambda handle: None,
            name="cost-smoke",
            max_in_flight=2,
        )
        try:
            t0 = perf_counter()
            for i in range(BATCHES):
                pipe.submit(i)
            pipe.drain()
            return perf_counter() - t0
        finally:
            pipe.close()
            costledger.ENABLED = saved
            costledger.reset_for_tests()

    run_once(True), run_once(False)  # warmup (thread spin-up, imports)
    ratios = []
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        for _ in range(REPS):
            ratios.append(run_once(True) / run_once(False))
    finally:
        if gc_was_enabled:
            gc.enable()
    ratio = min(ratios)
    assert ratio < 1.05, (
        f"cost ledger armed-idle overhead {ratio:.3f}x (pair ratios "
        f"{[round(r, 3) for r in ratios]})"
    )


@pytest.mark.perf_smoke
def test_costledger_disabled_is_single_attribute_read():
    """PATHWAY_COSTLEDGER=0: importing the module must not instantiate
    the ledger or pull in jax; every hook guard is the module attribute
    and no status/metrics call materializes the singleton."""
    import os
    import subprocess
    import sys

    code = (
        "import sys;"
        "from pathway_tpu.internals import costledger;"
        "assert costledger.ENABLED is False;"
        "assert costledger._LEDGER is None;"
        "costledger.charge('ingest', device_s=1.0, docs=4);"
        "costledger.charge_search([1, 2], 0.5);"
        "costledger.note_cache_hits(['acme']);"
        "costledger.on_run_start();"
        "assert costledger.serve_device_share() is None;"
        "assert costledger.cost_metrics() is None;"
        "assert costledger.cost_status() == {'enabled': False};"
        "assert costledger._LEDGER is None, 'hooks instantiated it';"
        "assert 'jax' not in sys.modules, 'costledger pulled in jax'"
    )
    env = dict(os.environ)
    env["PATHWAY_COSTLEDGER"] = "0"
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=env,
    )
    assert proc.returncode == 0, proc.stderr


def test_sanitizer_armed_idle_overhead_under_5pct():
    """PATHWAY_SANITIZE=1 on a healthy job: every tick pays one frontier
    bookkeeping call and every TableState batch one counted multiset
    check, with no violations ever recorded.  That armed-idle cost must
    stay under 5% on the engine microbench loop — same min-of-N
    interleaved protocol as the fault-harness guard above."""
    import gc
    from time import perf_counter

    from pathway_tpu.engine.engine import InputQueueSource, RowwiseNode
    from pathway_tpu.internals import sanitizer

    ROWS, TICKS, REPS = 512, 40, 5
    deltas = [(ref_scalar("k", i), (i,), 1) for i in range(ROWS)]

    def ident(keys, cols):
        return cols[0]

    def run_once(armed: bool) -> float:
        sanitizer.clear()
        if armed:
            sanitizer.install()
        eng = Engine(metrics=False)
        src = InputQueueSource(eng)
        node = src
        for _ in range(3):
            node = RowwiseNode(eng, [node], ident)
        try:
            time = 2
            for _ in range(8):  # warmup
                src.push(time, deltas)
                eng.process_time(time)
                time += 2
            t0 = perf_counter()
            for _ in range(TICKS):
                src.push(time, deltas)
                eng.process_time(time)
                time += 2
            return perf_counter() - t0
        finally:
            eng._gc_unfreeze()

    ratios = []
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        run_once(True), run_once(False)  # warmup
        for _ in range(REPS):
            ratios.append(run_once(True) / run_once(False))
    finally:
        sanitizer.clear()
        if gc_was_enabled:
            gc.enable()
    ratio = min(ratios)
    assert ratio < 1.05, (
        f"sanitizer armed-idle overhead {ratio:.3f}x (pair ratios "
        f"{[round(r, 3) for r in ratios]})"
    )


def test_provenance_armed_idle_overhead_under_5pct(monkeypatch):
    """PATHWAY_PROVENANCE=1 with the sample stride past every bench
    epoch: rowwise maps record no edges by design and the source hook
    bails at the sampling check, so the armed-idle cost is the ACTIVE
    attribute read per hook site plus per-tick sampling/epoch
    bookkeeping.  That must stay under 5% on the engine microbench loop
    — same min-of-N interleaved protocol as the sanitizer guard above.
    (The cost of actually RECORDING lineage is the measured, sampling-
    controllable number `engine_bench --provenance` reports — not a
    guarded invariant.)"""
    import gc
    from time import perf_counter

    from pathway_tpu.engine.engine import InputQueueSource, RowwiseNode
    from pathway_tpu.internals import provenance

    monkeypatch.setenv("PATHWAY_PROVENANCE_SAMPLE", "1000000007")
    ROWS, TICKS, REPS = 512, 40, 5
    deltas = [(ref_scalar("k", i), (i,), 1) for i in range(ROWS)]

    def ident(keys, cols):
        return cols[0]

    def run_once(armed: bool) -> float:
        provenance.clear()
        if armed:
            provenance.install()
        eng = Engine(metrics=False)
        src = InputQueueSource(eng)
        node = src
        for _ in range(3):
            node = RowwiseNode(eng, [node], ident)
        try:
            time = 2
            for _ in range(8):  # warmup
                src.push(time, deltas)
                eng.process_time(time)
                time += 2
            t0 = perf_counter()
            for _ in range(TICKS):
                src.push(time, deltas)
                eng.process_time(time)
                time += 2
            return perf_counter() - t0
        finally:
            eng._gc_unfreeze()

    ratios = []
    gc.collect()
    gc_was_enabled = gc.isenabled()
    gc.disable()
    try:
        run_once(True), run_once(False)  # warmup
        for _ in range(REPS):
            ratios.append(run_once(True) / run_once(False))
    finally:
        provenance.clear()
        if gc_was_enabled:
            gc.enable()
    ratio = min(ratios)
    assert ratio < 1.05, (
        f"provenance armed-idle overhead {ratio:.3f}x (pair ratios "
        f"{[round(r, 3) for r in ratios]})"
    )


@pytest.mark.perf_smoke
def test_provenance_disabled_is_single_attribute_read():
    """PATHWAY_PROVENANCE unset/0: importing the module must not create
    the tracker; every engine hook is gated on the ACTIVE module
    attribute, and the status/metrics surfaces short-circuit without
    materializing the singleton."""
    import os
    import subprocess
    import sys

    code = (
        "import sys;"
        "from pathway_tpu.internals import provenance;"
        "provenance.install_from_env();"
        "assert provenance.ACTIVE is False;"
        "assert provenance._TRACKER is None;"
        "assert provenance.provenance_status() == {'enabled': False};"
        "assert provenance.provenance_metrics() is None;"
        "assert provenance._TRACKER is None, 'surfaces instantiated it';"
        "assert 'jax' not in sys.modules, 'provenance pulled in jax'"
    )
    env = dict(os.environ)
    env["PATHWAY_PROVENANCE"] = "0"
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=env,
    )
    assert proc.returncode == 0, proc.stderr


@pytest.mark.perf_smoke
def test_sanitizer_disabled_is_single_attribute_read():
    """PATHWAY_SANITIZE unset/0: importing the module must not create
    the tracker; every engine hook is gated on the ACTIVE module
    attribute, and the status/metrics surfaces short-circuit without
    materializing the singleton."""
    import os
    import subprocess
    import sys

    code = (
        "import sys;"
        "from pathway_tpu.internals import sanitizer;"
        "sanitizer.install_from_env();"
        "assert sanitizer.ACTIVE is False;"
        "assert sanitizer._TRACKER is None;"
        "assert sanitizer.sanitizer_status() == {'enabled': False};"
        "assert sanitizer.sanitizer_metrics() is None;"
        "assert sanitizer._TRACKER is None, 'surfaces instantiated it';"
        "assert 'jax' not in sys.modules, 'sanitizer pulled in jax'"
    )
    env = dict(os.environ)
    env["PATHWAY_SANITIZE"] = "0"
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, env=env,
    )
    assert proc.returncode == 0, proc.stderr

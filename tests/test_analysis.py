"""Build-time static analyzer (pathway_tpu/analysis/) — golden
diagnostic matrix, JSON round-trip, clean-graph guard, the pw.run
surface, the CLI surface, and the per-engine warn-once regression.

The golden file (tests/golden/analysis_matrix.json) pins (code,
severity, message) for every finding the lint-bait graph produces.
Regenerate after an intentional message change with:

    python tests/test_analysis.py --regen
"""

import json
import os
import threading
import time

import pytest

import pathway_tpu as pw
from pathway_tpu.analysis import (
    CODES,
    SCHEMA_VERSION,
    AnalysisError,
    AnalysisResult,
    Diagnostic,
    Severity,
    analyze,
    make_diag,
)
from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.runner import last_engine, run_tables

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "analysis_matrix.json")


def _sink(*tables):
    for t in tables:
        pw.io.subscribe(t, on_change=lambda *a, **k: None)


def build_lintful_graph():
    """One graph that trips every statically reachable diagnostic."""
    t = pw.debug.table_from_rows(
        pw.schema_from_types(name=str, age=int, score=float, grp=float),
        [("a", 1, 1.5, 0.5), ("b", 2, 2.5, 0.5)],
    )
    # PWT101: lossy float -> int cast
    lossy = t.select(name=t.name, age_i=pw.cast(int, t.score))
    # PWT102: str == int comparison
    bad_cmp = t.filter(t.name == t.age)
    # PWT103: arithmetic on an optional operand
    opt = pw.debug.table_from_rows(
        pw.schema_from_types(k=str, v=dt.Optionalized(dt.INT)), [("a", 1)]
    )
    arith = opt.select(k=opt.k, w=opt.v + 1)
    # PWT202: groupby on an unbounded-cardinality float key
    by_float = t.groupby(t.grp).reduce(t.grp, c=pw.reducers.count())
    # PWT303: reducer with no vector implementation
    tup = t.groupby(t.name).reduce(t.name, xs=pw.reducers.tuple(t.age))
    # PWT301 + PWT302: join keyed on an unhashable/unroutable dtype
    left = t.select(
        key=pw.apply_with_type(lambda s: [s], list, t.name), age=t.age
    )
    right = t.select(
        key=pw.apply_with_type(lambda s: [s], list, t.name), score=t.score
    )
    joined = left.join(right, left.key == right.key).select(
        left.age, right.score
    )
    # PWT305: non-deterministic UDF feeding a stateful operator
    nd = t.select(name=t.name, r=pw.apply(lambda x: x + 1, t.age))
    nd_red = nd.groupby(nd.name).reduce(nd.name, s=pw.reducers.sum(nd.r))
    # PWT306: async UDF on an exchange-crossing path
    au = t.select(name=t.name, r=pw.apply_async(lambda x: x * 2, t.age))
    au_red = au.groupby(au.name).reduce(au.name, s=pw.reducers.sum(au.r))
    # PWT201: windowby without behavior=
    ts = pw.debug.table_from_rows(
        pw.schema_from_types(at=int, v=int), [(1, 1)]
    )
    win = ts.windowby(
        ts.at, window=pw.temporal.tumbling(duration=2)
    ).reduce(c=pw.reducers.count())

    # PWT203: iterate without iteration_limit=
    def step(tab):
        return tab.select(v=pw.this.v)

    it = pw.iterate(step, tab=ts.select(v=ts.v))
    # PWT111: anchored select whose consumer reads only one column
    wide = t.select(name=t.name, age=t.age, score=t.score)
    narrow = wide.select(name=wide.name)

    # PWT401: embedder whose tiny max_batch_size buckets to 8 rows and
    # pads every doc to the bucket max (>50% predicted waste). The pass
    # reads the _pw_embedder marker, so a plain marked function works —
    # no model build, and the trace stays in this file.
    def tiny_embed(text: str) -> str:
        return text

    tiny_embed._pw_embedder = {
        "model": "tiny", "max_batch_size": 3, "max_len": 256,
        # PWT402 bait under --mesh dp=3,tp=5: 384 % 5 != 0 and dp=3 is
        # not a power of two, so both mesh-shape lints fire here
        "dimension": 384,
    }
    emb = t.select(name=t.name, e=pw.apply_with_type(tiny_embed, str, t.name))

    # PWT403 (custom branch): stateful accumulators carry no mergeable
    # partial state across dp shards
    stateful = t.groupby(t.name).reduce(
        t.name,
        m=pw.reducers.stateful_single(lambda s, v: max(s or 0, v))(t.age),
    )

    # PWT405: exclusive connector (single-worker ingest) on a >1-device
    # mesh.  Analysis never builds, so the subject never runs.
    class _NullSubject(pw.io.python.ConnectorSubject):
        def run(self):
            pass

    pinned = pw.io.python.read(
        _NullSubject(),
        schema=pw.schema_from_types(x=int),
        name="pinned_src",
    )
    pinned_sel = pinned.select(x=pinned.x)

    # PWT501+PWT503: a two-op chain whose tail fans out to two readers
    s1 = t.select(name=t.name, v=t.age + 1)
    s2 = s1.select(name=s1.name, v=s1.v * 2)
    fan_a = s2.filter(s2.v > 0)
    fan_b = s2.filter(s2.v < 100)
    # PWT501+PWT502: a select->filter chain stopped by a keyed reduce
    c1 = t.select(name=t.name, v=t.age * 3)
    c2 = c1.filter(c1.v > 0)
    chain_red = c2.groupby(c2.name).reduce(
        c2.name, s=pw.reducers.sum(c2.v)
    )

    # PWT602: an external index that exposes no embedding dimension —
    # the capacity pass cannot price it.  record_op is called directly
    # (the same annotation DataIndex._query records) so the trace stays
    # in this file and no index is actually built.
    from pathway_tpu.internals.parse_graph import record_op

    idx_unknown = t.select(name=t.name)
    record_op(
        idx_unknown, "external_index", (t,),
        index="CustomInner", dimensions=None, reserved_space=None,
        metric=None, encoder=None,
    )
    # PWT601+PWT603+PWT605 under dp=3,tp=5: 1M reserved rows at d=384
    # bucket to 2^20 rows -> ~1.6 GB of slab, overflowing the 256 MiB
    # PATHWAY_ASSUME_HBM_BYTES ceiling _analyze_lintful pins (PWT603);
    # the encoder dict replicates per dp replica (PWT605)
    idx_sized = t.select(name=t.name)
    record_op(
        idx_sized, "external_index", (t,),
        index="BruteForceKnn", dimensions=384, reserved_space=1_000_000,
        metric="cosine_similarity",
        encoder={"vocab_size": 30522, "hidden": 384, "layers": 6,
                 "mlp_dim": 1536, "max_len": 512},
    )

    # PWT901 + PWT999: reads the clock while *declaring* determinism —
    # the static half of the sanitizer's parity contract
    @pw.udf(deterministic=True)
    def clock_liar(x: int) -> float:
        return x + time.time()

    nondet_udf = t.select(name=t.name, c=clock_liar(t.age))

    # PWT902: set iteration order leaks into the output string
    def scrambled(s: str) -> str:
        return "".join(set(s))

    unordered = t.select(
        name=t.name, u=pw.apply_with_type(scrambled, str, t.name)
    )

    # PWT903: file write from a UDF feeding a stateful reduce — failover
    # replay re-runs it, duplicating the side effect
    def audit_row(v: int) -> int:
        with open("/tmp/pathway_audit.log", "a") as fh:
            fh.write(str(v))
        return v

    audited = t.select(name=t.name, a=pw.apply_with_type(audit_row, int, t.age))
    audited_red = audited.groupby(audited.name).reduce(
        audited.name, s=pw.reducers.sum(audited.a)
    )

    # PWT904: stateful combiner whose closure captures an unpicklable
    # lock — would disable the reduce node's operator snapshot
    lock = threading.Lock()

    def guarded_max(state, v):
        with lock:
            return max(state or 0, v)

    locked_red = t.groupby(t.name).reduce(
        t.name, m=pw.reducers.stateful_single(guarded_max)(t.age)
    )

    # PWT905: in-place mutation of an input row value — breaks
    # FusedChainNode batch sharing
    def mutate_row(xs) -> int:
        xs.append(0)
        return len(xs)

    mutated = left.select(n=pw.apply_with_type(mutate_row, int, left.key))

    _sink(
        lossy, bad_cmp, arith, by_float, tup, joined, nd_red, au_red,
        win, it, narrow, emb, stateful, pinned_sel, fan_a, fan_b,
        chain_red, idx_unknown, idx_sized, nondet_udf, unordered,
        audited_red, locked_red, mutated,
    )
    # PWT110: computed after the sinks, read by nobody.  Returned so the
    # caller keeps it alive — the parse graph tracks tables by weakref,
    # and an already-collected table is (correctly) not analyzed
    return t.select(doomed=t.age * 2)


def _normalized(result):
    return sorted(
        (
            {"code": f.code, "severity": str(f.severity), "message": f.message}
            for f in result.findings
        ),
        key=lambda d: (d["code"], d["message"]),
    )


def _analyze_lintful():
    dead = build_lintful_graph()
    # dp=3,tp=5 is deliberately hostile: 4 workers don't tile dp=3
    # (PWT404), 384 % 5 != 0 and 3 is not a power of two (PWT402 x2).
    # Pin the HBM ceiling so the PWT6xx capacity findings are identical
    # on every machine (the resolver would otherwise consult jax).
    prev = os.environ.get("PATHWAY_ASSUME_HBM_BYTES")
    os.environ["PATHWAY_ASSUME_HBM_BYTES"] = str(256 * 2**20)
    try:
        result = analyze(G, workers=4, mesh="dp=3,tp=5")
    finally:
        if prev is None:
            os.environ.pop("PATHWAY_ASSUME_HBM_BYTES", None)
        else:
            os.environ["PATHWAY_ASSUME_HBM_BYTES"] = prev
    del dead
    return result


# ---------------------------------------------------------------------------
# golden diagnostic matrix
# ---------------------------------------------------------------------------


def test_golden_diagnostic_matrix():
    got = _normalized(_analyze_lintful())
    with open(GOLDEN) as fh:
        want = json.load(fh)
    assert want["schema_version"] == SCHEMA_VERSION
    assert got == want["findings"], (
        "diagnostics drifted from tests/golden/analysis_matrix.json; "
        "if intentional, regenerate with `python -m tests.regen_golden`"
    )


def test_matrix_covers_enough_codes():
    codes = {f.code for f in _analyze_lintful().findings}
    assert len(codes) >= 8, codes
    assert codes <= set(CODES)
    # the mesh and fusion passes each contribute their full code family
    assert {
        "PWT402", "PWT403", "PWT404", "PWT405",
        "PWT501", "PWT502", "PWT503", "PWT504",
        "PWT601", "PWT602", "PWT603", "PWT605",
        "PWT701", "PWT802",
        "PWT901", "PWT902", "PWT903", "PWT904", "PWT905", "PWT999",
    } <= codes, codes


def test_findings_are_deterministically_ordered():
    a = [f.to_dict() for f in _analyze_lintful().sorted_findings()]
    G.clear()
    b = [f.to_dict() for f in _analyze_lintful().sorted_findings()]
    assert a == b
    codes = [f["code"] for f in a]
    assert codes == sorted(codes)


def test_every_finding_has_a_location():
    for f in _analyze_lintful().findings:
        assert f.location() != "<unknown>"
        # user code built every op in this graph, so traces point here
        assert f.trace is None or f.trace["file"].endswith(
            "test_analysis.py"
        )


# ---------------------------------------------------------------------------
# JSON round-trip
# ---------------------------------------------------------------------------


def test_json_round_trip():
    result = _analyze_lintful()
    d = result.to_dict()
    blob = json.dumps(d, sort_keys=True)
    back = AnalysisResult.from_dict(json.loads(blob))
    assert back.to_dict() == d
    assert d["schema_version"] == SCHEMA_VERSION
    assert d["summary"] == result.counts()
    assert len(d["predictions"]) == len(result.predictions)
    # the fusion plan rides along and survives the round trip
    assert d["fusion"]["enabled"] is True
    assert any(c["length"] >= 2 for c in d["fusion"]["chains"])


def test_severity_model():
    assert Severity.parse("warning") is Severity.WARNING
    assert str(Severity.ERROR) == "error"
    assert Severity.ERROR > Severity.WARNING > Severity.INFO
    for code, (sev, title) in CODES.items():
        assert code.startswith("PWT") and title


# ---------------------------------------------------------------------------
# clean graphs stay clean
# ---------------------------------------------------------------------------


def _clean_topologies():
    """Representative well-formed pipelines (the shapes
    test_engine_semantics.py exercises) — none should lint."""
    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=str, v=int, w=float),
        [("a", 1, 1.0), ("b", 2, 2.0)],
    )
    yield t.select(k=t.k, doubled=t.v * 2)
    yield t.filter(t.v > 1).select(k=pw.this.k, v=pw.this.v)
    yield t.groupby(t.k).reduce(
        t.k,
        c=pw.reducers.count(),
        s=pw.reducers.sum(t.v),
        lo=pw.reducers.min(t.w),
    )
    other = t.select(k=t.k, label=t.k + "!")
    yield t.join(other, t.k == other.k).select(t.v, other.label)
    lists = pw.debug.table_from_rows(
        pw.schema_from_types(i=int, vs=list), [(1, [1, 2])]
    )
    yield lists.flatten(pw.this.vs)
    yield pw.Table.concat_reindex(
        t.select(k=t.k, v=t.v), t.select(k=t.k, v=t.v + 10)
    )
    ts = pw.debug.table_from_rows(
        pw.schema_from_types(at=int, v=int), [(1, 1)]
    )
    yield ts.windowby(
        ts.at,
        window=pw.temporal.tumbling(duration=2),
        behavior=pw.temporal.common_behavior(cutoff=10),
    ).reduce(c=pw.reducers.count())

    def step(tab):
        return tab.select(v=pw.this.v)

    yield pw.iterate(step, iteration_limit=3, tab=ts.select(v=ts.v))


def test_clean_graphs_have_zero_findings():
    tables = list(_clean_topologies())
    _sink(*tables)
    result = analyze(G, workers=4)
    # informational fusion-chain notes (PWT501/502/503) are expected on
    # well-formed pipelines — they describe the build plan, not defects
    findings = [
        f
        for f in result.findings
        if f.code not in ("PWT501", "PWT502", "PWT503")
    ]
    assert findings == [], result.render_text()
    # the eligible ops all predict columnar
    predicted = {(p["op"], p["predicted"]) for p in result.predictions}
    assert ("join", "columnar") in predicted
    assert ("reduce", "columnar") in predicted
    assert ("flatten", "columnar") in predicted


def test_empty_graph_is_clean():
    result = analyze(G)
    assert result.findings == [] and result.predictions == []
    assert result.max_severity() is None
    assert result.render_text() == "no findings"


# ---------------------------------------------------------------------------
# serving pass (PWT7xx)
# ---------------------------------------------------------------------------


def _serving_indexed_graph(encoder):
    from pathway_tpu.internals.parse_graph import record_op

    t = pw.debug.table_from_rows(
        pw.schema_from_types(name=str), [("a",), ("b",)]
    )
    idx = t.select(name=t.name)
    record_op(
        idx, "external_index", (t,),
        index="BruteForceKnn", dimensions=32, reserved_space=64,
        metric="cosine_similarity", encoder=encoder,
    )
    _sink(idx)
    return idx


def test_pwt701_index_without_encoder_cannot_fuse_batches():
    from pathway_tpu.internals import serving

    assert serving.ENABLED  # default-on in the test env
    keep = _serving_indexed_graph(encoder=None)
    codes = {f.code for f in analyze(G, workers=1).findings}
    assert "PWT701" in codes
    del keep

    G.clear()
    keep = _serving_indexed_graph(
        encoder={"vocab_size": 512, "hidden": 32, "layers": 1,
                 "mlp_dim": 64, "max_len": 32}
    )
    codes = {f.code for f in analyze(G, workers=1).findings}
    assert "PWT701" not in codes
    del keep


def test_pwt702_batch_window_exceeding_slo(monkeypatch):
    monkeypatch.setenv("PATHWAY_SERVE_BATCH_WINDOW_MS", "50")
    keep = _serving_indexed_graph(encoder=None)
    # window 50 ms > 10 ms p99 target: unmeetable by configuration
    fs = [f for f in analyze(G, workers=1, slo=10.0).findings
          if f.code == "PWT702"]
    assert len(fs) == 1
    assert "50" in fs[0].message and "10" in fs[0].message
    # a sane target is silent
    codes = {f.code for f in analyze(G, workers=1, slo=500.0).findings}
    assert "PWT702" not in codes
    # CLI path: the env fallback carries the target when pw.run(slo=)
    # never ran
    monkeypatch.setenv("PATHWAY_SLO_P99_MS", "10")
    codes = {f.code for f in analyze(G, workers=1).findings}
    assert "PWT702" in codes
    del keep


def test_serving_pass_gated_off(monkeypatch):
    from pathway_tpu.internals import serving

    keep = _serving_indexed_graph(encoder=None)
    # a zero window disarms the batcher: nothing to lint
    monkeypatch.setenv("PATHWAY_SERVE_BATCH_WINDOW_MS", "0")
    codes = {f.code for f in analyze(G, workers=1, slo=1.0).findings}
    assert not {"PWT701", "PWT702"} & codes
    monkeypatch.delenv("PATHWAY_SERVE_BATCH_WINDOW_MS")
    # serving disabled: the pass never runs
    monkeypatch.setattr(serving, "ENABLED", False)
    codes = {f.code for f in analyze(G, workers=1, slo=1.0).findings}
    assert not {"PWT701", "PWT702"} & codes
    del keep


# ---------------------------------------------------------------------------
# cost pass (PWT8xx)
# ---------------------------------------------------------------------------


def test_pwt801_tenant_limits_without_tracing(monkeypatch):
    from pathway_tpu.internals import qtrace

    keep = _serving_indexed_graph(encoder=None)
    monkeypatch.setenv("PATHWAY_SERVE_TENANT_RATE", "5")
    monkeypatch.setattr(qtrace, "ENABLED", False)
    fs = [f for f in analyze(G, workers=1).findings if f.code == "PWT801"]
    assert len(fs) == 1
    assert "X-Tenant" in fs[0].message
    assert fs[0].details["tenant_rate_per_s"] == 5.0
    # tracing back on: the tenant rides the span, nothing to lint
    monkeypatch.setattr(qtrace, "ENABLED", True)
    codes = {f.code for f in analyze(G, workers=1).findings}
    assert "PWT801" not in codes
    # limits off: nothing to attribute against
    monkeypatch.setattr(qtrace, "ENABLED", False)
    monkeypatch.delenv("PATHWAY_SERVE_TENANT_RATE")
    codes = {f.code for f in analyze(G, workers=1).findings}
    assert "PWT801" not in codes
    del keep


def test_pwt802_ledger_without_capacity_entry(monkeypatch):
    from pathway_tpu.internals import costledger, costmodel

    keep = _serving_indexed_graph(encoder=None)
    # CPU CI: no chip-table entry -> efficiency gauges will be None
    assert not costmodel.device_capacity_known()
    fs = [f for f in analyze(G, workers=1).findings if f.code == "PWT802"]
    assert len(fs) == 1
    assert "pathway_cost_efficiency_pct" in fs[0].message
    # a known chip is silent
    monkeypatch.setattr(costmodel, "_cached_kind", "TPU v5 lite")
    codes = {f.code for f in analyze(G, workers=1).findings}
    assert "PWT802" not in codes
    # ledger disabled: the efficiency gap is moot
    monkeypatch.setattr(costmodel, "_cached_kind", "unknown")
    monkeypatch.setattr(costledger, "ENABLED", False)
    codes = {f.code for f in analyze(G, workers=1).findings}
    assert "PWT802" not in codes
    del keep


def test_cost_pass_needs_an_index():
    # no anchored external index: no serve workload, nothing to lint
    t = pw.debug.table_from_rows(
        pw.schema_from_types(name=str), [("a",)]
    )
    _sink(t)
    codes = {f.code for f in analyze(G, workers=1).findings}
    assert not {"PWT801", "PWT802"} & codes


# ---------------------------------------------------------------------------
# trace fallback: findings survive without a user frame
# ---------------------------------------------------------------------------


def test_diagnostic_without_trace_keeps_operator_location():
    d = make_diag(
        "PWT303", "reduce cannot take the columnar path: x",
        operator="reduce#7 (reduce#7 <- select#3)",
    )
    assert d.trace is None
    assert d.location() == "<reduce#7 (reduce#7 <- select#3)>"
    rendered = AnalysisResult(findings=[d]).render_text()
    assert "reduce#7" in rendered
    assert Diagnostic.from_dict(d.to_dict()) == d


def test_marker_without_user_frame_still_reported():
    # a marker recorded with no user frame (stdlib-built temporal op):
    # the finding must survive with the operator fallback
    from pathway_tpu.internals.parse_graph import MarkerSpec

    G.markers.append(MarkerSpec("windowby", {"has_behavior": False}, None))
    result = analyze(G)
    (finding,) = [f for f in result.findings if f.code == "PWT201"]
    assert finding.trace is None
    assert finding.location() == "<windowby>"


# ---------------------------------------------------------------------------
# pw.run(analysis=...) surface
# ---------------------------------------------------------------------------


def _graph_with_warning():
    t = pw.debug.table_from_rows(
        pw.schema_from_types(g=float, v=int), [(0.5, 1), (0.5, 2)]
    )
    res = t.groupby(t.g).reduce(t.g, s=pw.reducers.sum(t.v))
    _sink(res)


def test_run_analysis_strict_raises():
    _graph_with_warning()
    with pytest.raises(AnalysisError) as exc:
        pw.run(analysis="strict")
    assert any(f.code == "PWT202" for f in exc.value.result.findings)
    assert "PWT202" in str(exc.value)


def test_run_analysis_warn_executes_and_attaches():
    _graph_with_warning()
    pw.run(analysis="warn")
    eng = last_engine()
    assert eng is not None and eng.analysis is not None
    assert any(
        f["code"] == "PWT202" for f in eng.analysis["findings"]
    )


def test_run_analysis_off_and_invalid():
    _graph_with_warning()
    pw.run(analysis="off")
    assert last_engine().analysis is None
    G.clear()
    _graph_with_warning()
    with pytest.raises(ValueError):
        pw.run(analysis="nonsense")


def test_run_analysis_strict_clean_graph_executes():
    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=str, v=int), [("a", 1)]
    )
    rows = []
    pw.io.subscribe(
        t.select(k=t.k, v=t.v * 2),
        on_change=lambda key, row, time, is_addition: rows.append(row),
    )
    pw.run(analysis="strict")
    assert rows == [{"k": "a", "v": 2}]


def test_status_endpoint_carries_analysis():
    from pathway_tpu.internals.monitoring import PrometheusServer

    _graph_with_warning()
    pw.run(analysis="warn")
    eng = last_engine()
    status = PrometheusServer(eng).status_json()
    assert status["analysis"] == eng.analysis
    codes = [f["code"] for f in status["analysis"]["findings"]]
    assert "PWT202" in codes


# ---------------------------------------------------------------------------
# prediction vs built plan (PWT399 wiring)
# ---------------------------------------------------------------------------


def test_verify_against_plan_clean():
    from pathway_tpu.analysis import verify_against_plan

    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=str, v=int), [("a", 1), ("a", 2)]
    )
    red = t.groupby(t.k).reduce(t.k, s=pw.reducers.sum(t.v))
    result = analyze(G, extra_tables=(red,))
    (capture,) = run_tables(red)
    verify_against_plan(capture.engine, result)
    assert not [f for f in result.findings if f.code == "PWT399"]


def test_verify_against_plan_detects_drift():
    from pathway_tpu.analysis import verify_against_plan

    t = pw.debug.table_from_rows(
        pw.schema_from_types(k=str, v=int), [("a", 1)]
    )
    red = t.groupby(t.k).reduce(t.k, s=pw.reducers.sum(t.v))
    result = analyze(G, extra_tables=(red,))
    # sabotage the prediction: claim the gate chose classic
    for p in result.predictions:
        p["predicted"] = "classic"
    (capture,) = run_tables(red)
    verify_against_plan(capture.engine, result)
    drift = [f for f in result.findings if f.code == "PWT399"]
    assert drift and all(str(f.severity) == "error" for f in drift)


# ---------------------------------------------------------------------------
# per-engine warn-once (exchange unroutable regression)
# ---------------------------------------------------------------------------


def test_warn_once_is_per_engine(caplog):
    import logging

    from pathway_tpu.engine.engine import Engine

    e1 = Engine(worker_id=0, worker_count=1, metrics=False)
    e2 = Engine(worker_id=0, worker_count=1, metrics=False)
    with caplog.at_level(logging.WARNING, logger="pathway_tpu"):
        assert e1.warn_once("exchange_unroutable", "unroutable on e1")
        assert not e1.warn_once("exchange_unroutable", "again on e1")
        # a different engine in the same process warns independently
        assert e2.warn_once("exchange_unroutable", "unroutable on e2")
    texts = [r.getMessage() for r in caplog.records]
    assert texts.count("unroutable on e1") == 1
    assert texts.count("unroutable on e2") == 1


# ---------------------------------------------------------------------------
# CLI: pathway-tpu analyze
# ---------------------------------------------------------------------------

_CLEAN_SCRIPT = """
import pathway_tpu as pw

t = pw.debug.table_from_rows(
    pw.schema_from_types(k=str, v=int), [("a", 1)]
)
res = t.groupby(t.k).reduce(t.k, s=pw.reducers.sum(t.v))
pw.io.subscribe(res, on_change=lambda *a, **kw: None)
pw.run()
"""

_LINTY_SCRIPT = """
import pathway_tpu as pw

t = pw.debug.table_from_rows(
    pw.schema_from_types(g=float, v=int), [(0.5, 1)]
)
res = t.groupby(t.g).reduce(t.g, s=pw.reducers.sum(t.v))
pw.io.subscribe(res, on_change=lambda *a, **kw: None)
pw.run()
"""


def _write_script(tmp_path, body, name="script.py"):
    path = tmp_path / name
    path.write_text(body)
    return str(path)


def test_cli_analyze_clean(tmp_path, capsys):
    from pathway_tpu.cli import main

    script = _write_script(tmp_path, _CLEAN_SCRIPT)
    assert main(["analyze", script, "--fail-on", "warning"]) == 0
    assert "no findings" in capsys.readouterr().out


def test_cli_analyze_fail_on(tmp_path, capsys):
    from pathway_tpu.cli import main

    script = _write_script(tmp_path, _LINTY_SCRIPT)
    # PWT202 is a warning: below the error bar, at the warning bar
    assert main(["analyze", script, "--fail-on", "error"]) == 0
    assert main(["analyze", script, "--fail-on", "warning"]) == 1
    assert main(["analyze", script]) == 0  # report-only without --fail-on
    out = capsys.readouterr().out
    assert "PWT202" in out


def test_cli_analyze_json(tmp_path, capsys):
    from pathway_tpu.cli import main

    script = _write_script(tmp_path, _LINTY_SCRIPT)
    assert main(["analyze", script, "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["schema_version"] == SCHEMA_VERSION
    assert any(f["code"] == "PWT202" for f in payload["findings"])
    # and the run() call was intercepted: nothing executed, graph intact
    assert payload["predictions"]


def test_cli_analyze_broken_script(tmp_path, capsys):
    from pathway_tpu.cli import main

    script = _write_script(tmp_path, "raise RuntimeError('boom')\n")
    assert main(["analyze", script]) == 2
    assert "boom" in capsys.readouterr().err


def write_golden():
    """Regenerate tests/golden/analysis_matrix.json — shared by the
    legacy `python tests/test_analysis.py --regen` entry point and
    `python -m tests.regen_golden`."""
    G.clear()
    os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
    payload = {
        "schema_version": SCHEMA_VERSION,
        "findings": _normalized(_analyze_lintful()),
    }
    with open(GOLDEN, "w") as fh:
        json.dump(payload, fh, indent=2)
        fh.write("\n")
    G.clear()
    return GOLDEN


if __name__ == "__main__":
    import sys

    if "--regen" in sys.argv:
        print(f"wrote {write_golden()}")

"""Set-up and compilation measured inside the program: jax's compile
events by phase and by program in the span record and in /status
"compile" (internals/compile_cache.py `observe`), set-up's work as spans
(`setup.weights`, `setup.index_alloc`, `setup.native_load`,
`setup.graph_build`) and the marks `setup.at.*` (internals/tracing.py
`mark`), and the dispatch thread's waits for work in slices.  All on the
CPU; no case is a ratio of wall-clock times."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from pathway_tpu.internals import compile_cache, device_pipeline, tracing
from tests.test_spans import REPO, _pipeline, _totals


@pytest.fixture()
def record():
    compile_cache.reset_compiles()
    return tracing.reset_spans()


def _counts() -> dict:
    totals = tracing.spans_status()["totals"]
    return {
        name: totals.get(name, {"count": 0})["count"]
        for name in (*compile_cache.PHASES.values(), *compile_cache.COUNTERS.values())
    }


def _row(program: str) -> dict:
    rows = {p["program"]: p for p in compile_cache.compile_status()["programs"]}
    return rows[program]


# -- compile events ---------------------------------------------------------------


def test_a_compilation_is_counted_by_phase_by_program_and_under_its_span(record):
    import jax

    assert compile_cache.observe() is True

    def setup_trace_probe(x):
        return x * 3 + 1

    probe = jax.jit(setup_trace_probe)
    x = np.arange(8, dtype=np.float32)
    before = _counts()
    with tracing.span("pipeline.launch", seq=7, epoch=12):
        probe(x).block_until_ready()
    after = _counts()
    assert after["compile.lower"] == before["compile.lower"] + 1
    assert after["compile.backend"] == before["compile.backend"] + 1
    row = _row("setup_trace_probe")  # trace, lowering and compilation on one row
    assert (row["trace"]["count"], row["lower"]["count"], row["backend"]["count"]) == (1, 1, 1)
    assert row["trace"]["total_s"] > 0 and row["backend"]["total_s"] > 0
    (event,) = [
        e for e in compile_cache.compile_status()["recent"]
        if e["program"] == "setup_trace_probe"
    ]
    assert (event["span"], event["seq"], event["epoch"]) == ("pipeline.launch", 7, 12)
    assert event["thread"] == threading.current_thread().name
    assert event["seconds"] == pytest.approx(row["backend"]["total_s"])
    assert event["monotonic_s"] <= time.monotonic()
    # a second call of the same shape compiles nothing
    probe(x).block_until_ready()
    assert _counts() == after and _row("setup_trace_probe") == row


def test_observe_twice_registers_once(record):
    import jax

    assert [compile_cache.observe() for _ in range(3)] == [True] * 3
    before = _counts()["compile.backend"]
    jax.jit(lambda x: x - 2)(np.ones(4, np.float32)).block_until_ready()
    assert _counts()["compile.backend"] == before + 1


def test_a_trace_inside_a_trace_is_counted_once_in_the_record(record):
    """jax reports the inner jitted function by itself, before the outer
    one ends: the record takes the outermost trace's seconds alone, the
    rows keep jax's figures."""
    begin = lambda: compile_cache._on_scalar(compile_cache.TRACE_EVENT, time.time())  # noqa: E731
    end = lambda name, s: compile_cache._on_duration(  # noqa: E731
        compile_cache.TRACE_EVENT, s, fun_name=name)
    begin()  # outer
    for _ in range(200):  # as many `jnp` functions as a trunk's trace meets
        begin(), end("inner", 0.0001)
    begin(), begin(), end("innermost", 0.01), end("middle", 0.015)
    end("outer", 0.05)
    begin(), end("after", 0.004)
    compile_cache._on_scalar(compile_cache.LOWER_EVENT, time.time())  # not a trace: no entry
    end("begun_before_observe", 0.002)
    assert _row("outer")["trace"] == {"count": 1, "total_s": 0.05}
    assert _row("inner")["trace"] == {"count": 200, "total_s": pytest.approx(0.02)}
    assert _row("middle")["trace"] == {"count": 1, "total_s": 0.015}
    trace = _totals("compile.trace")
    assert trace["count"] == 205
    assert trace["total_s"] == pytest.approx(0.05 + 0.004 + 0.002)
    assert compile_cache._RECORD.here().open_traces == 0


def test_a_cache_load_goes_to_the_compilation_it_is_inside_of(record):
    compile_cache._on_duration(compile_cache.CACHE_LOAD_EVENT, 0.25)
    compile_cache._on_event("/jax/compilation_cache/cache_hits")
    compile_cache._on_duration(compile_cache.BACKEND_EVENT, 0.3, fun_name="jit(loaded)")
    compile_cache._on_event("/jax/compilation_cache/cache_misses")
    compile_cache._on_duration(compile_cache.BACKEND_EVENT, 2.0, fun_name="jit_compiled")
    compile_cache._on_duration("/jax/core/some/other_duration", 9.0, fun_name="x")
    compile_cache._on_event("/jax/some/other_event")
    assert _row("loaded")["cache_load"] == {"count": 1, "total_s": 0.25}
    assert _row("compiled")["cache_load"] == {"count": 0, "total_s": 0.0}
    loaded, compiled = compile_cache.compile_status()["recent"]
    assert (loaded["program"], loaded["cache_load_s"]) == ("loaded", 0.25)
    assert (compiled["program"], compiled["cache_load_s"], compiled["span"]) == (
        "compiled", None, None)
    counts = _counts()
    assert counts["compile.cache_hits"] == counts["compile.cache_misses"] == 1
    assert counts["compile.backend"] == 2 and counts["compile.cache_load"] == 1
    assert _totals("compile.backend")["total_s"] == pytest.approx(2.3)


def test_the_table_by_program_folds_past_its_bound_into_other(record, monkeypatch):
    monkeypatch.setattr(compile_cache, "PROGRAMS_KEPT", 3)
    for i in range(7):
        compile_cache._on_duration(
            compile_cache.LOWER_EVENT, 0.5, fun_name=f"jit(program_{i})"
        )
    compile_cache._on_duration(compile_cache.LOWER_EVENT, 0.5, fun_name="jit(program_1)")
    rows = {p["program"]: p["lower"] for p in compile_cache.compile_status()["programs"]}
    assert set(rows) == {"program_0", "program_1", "program_2", "other"}
    assert rows["other"] == {"count": 4, "total_s": 2.0}
    assert rows["program_1"] == {"count": 2, "total_s": 1.0}
    assert _totals("compile.lower")["count"] == 8


def test_rows_of_several_threads_add_up_and_the_served_table_is_bounded(record):
    def compiles() -> None:
        for i in range(20):
            compile_cache._on_duration(
                compile_cache.BACKEND_EVENT, 0.001 * (i + 1), fun_name=f"jit(p{i})"
            )

    threads = [threading.Thread(target=compiles) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    status = compile_cache.compile_status()
    # the 16 with the most seconds, most first
    assert [p["program"] for p in status["programs"]] == [f"p{i}" for i in range(19, 3, -1)]
    top = status["programs"][0]
    assert top["backend"]["count"] == 4
    assert top["backend"]["total_s"] == pytest.approx(0.08)
    assert len(status["recent"]) == compile_cache.RECENT_KEPT == 64  # of 80
    assert _totals("compile.backend")["count"] == 80


def test_status_serves_the_compile_record_beside_the_spans(record):
    from pathway_tpu.engine.engine import Engine
    from pathway_tpu.internals.monitoring import PrometheusServer

    compile_cache._on_duration(compile_cache.BACKEND_EVENT, 0.5, fun_name="jit(served)")
    eng = Engine()
    status = PrometheusServer(eng).status_json()
    assert set(status["compile"]) == {"programs", "recent"}
    (row,) = status["compile"]["programs"]
    assert row["program"] == "served" and set(row) == {
        "program", "trace", "lower", "backend", "cache_load"}
    assert status["spans"]["totals"]["compile.backend"]["count"] == 1
    json.dumps(status["compile"])
    eng._gc_unfreeze()


def _trunk(name: str):
    """(the model's module, a toy configuration its kernels tile, the calls
    of each kernel in a trace of its layers, the traces of `wrapped`)."""
    import dataclasses

    from pathway_tpu.models import eva, moe_hybrid, moe_mla, transformer

    if name == "encoder":
        config = transformer.TransformerConfig(
            vocab_size=512, hidden=128, layers=3, heads=4, mlp_dim=256, max_len=64)
        return transformer, config, {"segment_attention": 3}, 1
    if name == "moe_mla":  # the kernel's tiling is written for the published head widths
        config = dataclasses.replace(
            moe_mla.TINY, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
        return moe_mla, config, {"mla_segment_attention": 3}, 1
    if name == "eva":  # q and k are turned at two scales: two functions of `eva_rope`
        return eva, eva.TINY, {"eva_attention": 3, "eva_pool_chunks": 3, "eva_rope": 6}, 4
    # two layers of each kind; q and a window layer's k are two widths of
    # `hybrid_rope`, a global layer's one rope key head is `rotate`'s
    calls = {"hybrid_attention_global": 2, "hybrid_attention_window": 2, "hybrid_rope": 6}
    return moe_hybrid, moe_hybrid.TINY, calls, 4


@pytest.mark.parametrize("trunk", ["encoder", "moe_mla", "eva", "moe_hybrid"])
def test_status_names_each_kernel_of_a_trunk_not_wrapped_alone(trunk, record):
    """A packed trunk traced with its kernels (interpreted): /status
    "compile"."programs" has a row of each kernel's own name, with its
    calls, and `wrapped` (jax's name for every `pallas_call`'s wrapper)
    counts one trace a kernel a shape, not one a layer."""
    import jax

    from pathway_tpu.ops import kernels

    model, config, calls, traces = _trunk(trunk)
    assert compile_cache.observe()
    kernels._jitted.cache_clear()  # an earlier test's traces are not this trunk's
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), config))
    slab = jax.ShapeDtypeStruct((2, 128), np.int32)

    def program(params, ids, seg):
        return model.forward(params, config, ids, None, seg=seg, max_segments=4, use_flash=True)

    jax.jit(program).trace(params, slab, slab)
    served = {p["program"]: p["trace"]["count"] for p in compile_cache.compile_status()["programs"]}
    assert {name: served.get(name) for name in calls} == calls
    assert served["wrapped"] == traces < sum(calls.values())


_CACHED_RUN = """
import json, sys
sys.path.insert(0, {repo!r})
import numpy as np
from pathway_tpu.internals import compile_cache, tracing
assert compile_cache.configure() == {cache!r}
import jax
def cached_probe(x):
    return x * 5 - 1
jax.jit(cached_probe)(np.arange(16, dtype=np.float32)).block_until_ready()
totals = tracing.spans_status()["totals"]
print(json.dumps({{k: v["count"] for k, v in totals.items() if k.startswith("compile.")}}))
"""


def test_a_second_process_loads_what_the_first_compiled(tmp_path):
    cache = str(tmp_path / "cache")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", compile_cache.ENV_VAR: cache}
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _CACHED_RUN.format(repo=REPO, cache=cache)],
            capture_output=True, text=True, timeout=180, env=env,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
    first, second = runs
    assert (first["compile.cache_hits"], first["compile.cache_misses"]) == (0, 1)
    assert (second["compile.cache_hits"], second["compile.cache_misses"]) == (1, 0)
    assert second["compile.cache_load"] == 1 and first["compile.cache_load"] == 0
    assert first["compile.backend"] == second["compile.backend"] == 1


def test_import_and_a_pipeline_load_no_jax_and_observe_nothing():
    code = f"""
import sys
sys.path.insert(0, {REPO!r})
import pathway_tpu
from pathway_tpu.internals import compile_cache, tracing
from pathway_tpu.internals.device_pipeline import DevicePipeline
assert compile_cache.observe() is False
pipe = DevicePipeline(lambda item: (item, {{"rows": 1}}), lambda payload: None,
                      wait=lambda handle: None, name="nojax")
pipe.submit(1); pipe.drain(); pipe.close()
assert compile_cache.compile_status() == {{"programs": [], "recent": []}}
totals = tracing.spans_status()["totals"]
assert totals["setup.at.imported"]["count"] == 1
assert 0 < totals["setup.at.imported"]["total_s"] <= totals["setup.at.first_launch"]["total_s"]
assert "compile.backend" not in totals
assert "jax" not in sys.modules, "observe() or the pipeline imported jax"
print("ok")
"""
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr[-2000:]


# -- marks ------------------------------------------------------------------------


def test_a_mark_is_the_process_age_written_once(record):
    age = time.monotonic() - tracing.T_PROCESS
    tracing.mark("probe")
    time.sleep(0.01)
    tracing.mark("probe")
    entry = _totals("setup.at.probe")
    assert entry["count"] == 1
    assert age <= entry["total_s"] <= time.monotonic() - tracing.T_PROCESS - 0.01
    assert tracing.T_PROCESS <= time.monotonic()


def test_each_mark_is_written_once_however_many_dispatches_follow(record):
    pipes = [_pipeline(f"marks{i}", launch_s=0.001, max_in_flight=1) for i in range(2)]
    try:
        for pipe in pipes:
            for i in range(5):
                pipe.submit(i)
            pipe.drain()
    finally:
        for pipe in pipes:
            pipe.close()
    launch, completion = _totals("setup.at.first_launch"), _totals("setup.at.first_completion")
    assert launch["count"] == completion["count"] == 1
    assert 0 < launch["total_s"] <= completion["total_s"]
    assert _totals("pipeline.launch")["count"] == 10


def test_pw_run_marks_its_entry_and_spans_the_build(record):
    import pathway_tpu as pw

    seen = []
    for _ in range(2):
        table = pw.debug.table_from_markdown("x\n1\n2")
        pw.io.subscribe(table, on_change=lambda *a, **k: seen.append(1))
        pw.run(monitoring_level=None)
        pw.G.clear()
    assert len(seen) == 2 + 2
    assert _totals("setup.at.run")["count"] == 1
    build = _totals("setup.graph_build")
    assert build["count"] == 2 and build["open_s"] == 0.0
    # closed where the engine begins to tick: the ticks are not inside it
    assert [ev[6] for ev in record.ring if ev[0] == "engine.tick"].count("setup.graph_build") == 0


def test_a_failed_build_records_no_span_and_leaves_none_open(record):
    """`setup.graph_build` is written where the engine begins to tick: a
    run that fails on the way there has built nothing to time."""
    import pathway_tpu as pw

    table = pw.debug.table_from_markdown("x\n1")
    pw.io.subscribe(table, on_change=lambda *a, **k: None)
    with pytest.raises(Exception):
        pw.run(monitoring_level=None, mesh="no-such-axis")
    pw.G.clear()
    assert _totals("setup.at.run")["count"] == 1
    assert "setup.graph_build" not in tracing.spans_status()["totals"]
    assert tracing.current_span() is None


def test_the_first_answered_search_is_marked_once_and_the_index_allocated_once(record):
    import pathway_tpu as pw
    from pathway_tpu.internals.runner import run_tables
    from pathway_tpu.stdlib.indexing.data_index import DataIndex
    from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnn
    from tests.test_external_index_golden import _stream_vec_docs

    docs = _stream_vec_docs(
        """
        name | x | y | __time__
        far  | 0 | 1 | 2
        near | 1 | 0 | 4
        """
    )
    queries = pw.debug.table_from_markdown(
        """
        qx | qy | __time__
        1  | 0  | 2
        0  | 1  | 6
        """
    ).select(
        qv=pw.apply_with_type(
            lambda a, b: np.array([a, b], dtype=np.float32), np.ndarray,
            pw.this.qx, pw.this.qy,
        )
    )
    index = DataIndex(docs, BruteForceKnn(docs.vec, dimensions=2))
    res = index.query_as_of_now(queries.qv, number_of_matches=1).select(m=pw.this.name)
    (cap,) = run_tables(res)
    assert sorted(cap.state.rows.values()) == [(("far",),), (("far",),)]
    assert _totals("setup.at.first_search")["count"] == 1
    alloc = _totals("setup.index_alloc")
    assert alloc["count"] == 1 and alloc["rows"] >= 2


# -- set-up's spans -----------------------------------------------------------------


def test_weights_and_index_are_spanned_once_a_model_and_once_an_index(record):
    """tests/test_spans.py's small pipeline: one `setup.weights` (rows: the
    leaves) and one `setup.index_alloc` (rows: the provisioned capacity)
    each time one is made, neither waiting for the device."""
    import jax

    from pathway_tpu.stdlib.indexing.nearest_neighbors import _FusedKnnIndexImpl
    from tests.test_device_pipeline import _encoder

    encoder = _encoder("setup-tiny")
    weights = _totals("setup.weights")
    assert weights["count"] == 1
    assert weights["rows"] == len(jax.tree_util.tree_leaves(encoder.lm.params)) > 0
    assert "setup.index_alloc" not in tracing.spans_status()["totals"]
    impl = _FusedKnnIndexImpl(encoder, "cos", 32)
    try:
        alloc = _totals("setup.index_alloc")
        assert (alloc["count"], alloc["rows"]) == (1, 32)
        _encoder("setup-tiny-2")
        assert _totals("setup.weights")["count"] == 2
        assert _totals("setup.index_alloc")["count"] == 1
        # parameters handed in are not made here: no span
        type(encoder.lm)(encoder.config, params=encoder.lm.params)
        assert _totals("setup.weights")["count"] == 2
    finally:
        impl.drain()


def test_a_native_build_is_counted_where_the_compiler_ran(record, tmp_path, monkeypatch):
    from pathway_tpu import native

    monkeypatch.setenv("PATHWAY_NATIVE_CACHE", str(tmp_path))
    source = tmp_path / "probe.cpp"
    source.write_text('extern "C" int probe() { return 40; }\n')
    first = native._built(str(source), "pw_probe", ["-O0"], 120)
    assert os.path.exists(first) and _totals("setup.native_builds")["count"] == 1
    assert native._built(str(source), "pw_probe", ["-O0"], 120) == first
    assert _totals("setup.native_builds")["count"] == 1  # found, not built again


def test_loading_the_libraries_is_one_span_each(record, monkeypatch):
    from pathway_tpu import native

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_wire_ext", None)
    if native.load() is None or native.load_wire_ext() is None:
        pytest.skip("no toolchain here")
    assert _totals("setup.native_load")["count"] == 2
    native.load(), native.load_wire_ext()  # loaded: no span, no lock
    assert _totals("setup.native_load")["count"] == 2


# -- a long wait stays in a capture ---------------------------------------------------


def test_the_starved_wait_is_slices_that_sum_to_the_waited_time(record, monkeypatch):
    slice_s = 0.1
    monkeypatch.setattr(device_pipeline, "STARVED_SLICE_S", slice_s)
    born = time.perf_counter()
    pipe = _pipeline("sliced")
    try:
        time.sleep(0.65)
        submitted = time.perf_counter()
        pipe.submit(1)
        pipe.drain()
    finally:
        pipe.close()
    slices = [(t0, t1) for name, thread, t0, t1, *_ in record.ring
              if name == "pipeline.starved" and thread == "sliced-dispatch"]
    waiting = [t1 - t0 for t0, t1 in slices if t0 < submitted]
    assert len(waiting) >= 4  # 0.65 s in slices of 0.1 s, each as late as the machine is
    # the slices of the wait sum to the waited time within one slice
    assert abs(sum(waiting) - (submitted - born)) < slice_s, waiting
    # the span's count is slices now, not waits (the totals hold other
    # tests' idle pipelines too: the ring says whose a span is)
    assert _totals("pipeline.starved")["count"] >= len(slices) > 1

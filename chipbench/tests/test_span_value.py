"""`span_value`, the reader of set-up's side of the program's span record:
on two hand-made /status snapshots (what the snapshot at window open holds,
the difference over the window, `minus`, a name that has not occurred, a
program without the record), and in the traced CPU rehearsal, where it is
silent as `span_share` is."""

import json
import os

import pytest

from chipbench import spec
from chipbench.readers import span_value
from chipbench.tests.test_run_dry import E5, _dry, _line, _run
from chipbench.tests.test_span_readers import _entry

NEW = (
    "setup.trace_lower_s", "setup.backend_compile_s", "setup.cache_misses",
    "setup.weights_s", "setup.index_alloc_s", "setup.before_run_s",
    "setup.run_to_first_launch_s", "compile.backend_in_window.ingest",
)


def _ctx(trace={"busy_s": 1.0}):
    opened = {"spans": {"totals": {
        "compile.trace": _entry(700, 2.5), "compile.lower": _entry(50, 1.5),
        "compile.backend": _entry(50, 6.0), "compile.cache_misses": _entry(0),
        "setup.weights": _entry(1, 0.75, rows=100),
        "setup.at.run": _entry(1, 14.0), "setup.at.first_launch": _entry(1, 19.5),
    }}}
    closed = {"spans": {"totals": {
        **opened["spans"]["totals"],
        "compile.backend": _entry(52, 6.5), "compile.cache_misses": _entry(2),
        "setup.at.first_completion": _entry(1, 31.0),
    }}}
    return {"status_open": opened, "status_close": closed, "trace": trace}


@pytest.mark.parametrize("args,value", [
    (dict(spans=["compile.trace", "compile.lower"]), 4.0),
    (dict(spans=["compile.backend"]), 6.0),
    (dict(spans=["compile.cache_misses"], field="count"), 0.0),  # counted nothing: 0, not silent
    (dict(spans=["setup.weights"], field="rows"), 100.0),
    (dict(spans=["setup.at.run"]), 14.0),  # a mark's value is its total_s
    (dict(spans=["setup.at.first_launch"], minus=["setup.at.run"]), 5.5),
    (dict(spans=["compile.backend"], field="count", at="window"), 2.0),
    (dict(spans=["compile.backend"], at="window"), 0.5),
    (dict(spans=["compile.cache_misses"], field="count", at="window"), 2.0),
    # a name that has not occurred reads 0.0 beside one that has, on either side
    (dict(spans=["setup.weights", "setup.index_alloc"]), 0.75),
    (dict(spans=["setup.weights"], minus=["setup.native_load"]), 0.75),
    (dict(spans=["setup.at.first_completion"], at="window"), 31.0),
    # none of the names there (the parent of the PR that brought them): silent
    (dict(spans=["setup.index_alloc"]), None),
    (dict(spans=["setup.at.first_completion"]), None),
])
def test_span_value_on_hand_made_snapshots(args, value):
    got = span_value.read(_ctx(), **args)
    assert got == (None if value is None else pytest.approx(value))


@pytest.mark.parametrize("ctx", [
    dict(_ctx(), status_open=None),
    dict(_ctx(), status_open={"workers": []}),  # a program with no span record
    dict(_ctx(), status_close={"workers": []}),
    _ctx(trace=None),  # the CPU rehearsal
])
def test_span_value_is_silent_without_a_record_and_in_the_rehearsal(ctx):
    at = "window" if "spans" not in (ctx["status_close"] or {}) else "open"
    assert span_value.read(ctx, ["compile.backend"], field="count", at=at) is None


def test_span_value_refuses_another_at():
    with pytest.raises(ValueError):
        span_value.read(_ctx(), ["compile.backend"], at="close")


def test_the_new_metrics_are_read_in_every_cell_by_span_value():
    bench = spec.benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert "workloads" not in entries[name], name
        with open(os.path.join(spec.HERE, "metrics", name + ".json")) as f:
            meta = json.load(f)
        assert meta["reader"] == "span_value" and meta["moves"] == entries[name]["moves"]
        assert all(meta[k] == entries[name][k] for k in ("layer", "unit", "source", "better"))
    for w in bench["workloads"]:
        read = {m.name for m in spec.cell(w["name"]).per_layer}
        assert set(NEW) <= read, w["name"]
    assert [entries[n]["moves"] for n in NEW] == ["setup_s"] * 7 + ["ingest_docs_per_s"]


def test_the_traced_rehearsal_is_silent_on_set_up_never_0():
    """The program has the record (the line's other metrics come from the
    same snapshots), the set-up is the CPU backend's: no new metric is in
    the line, and none reads 0."""
    line = _line(_run("chipbench.run", *_dry(E5, 11, "--trace", "1")))
    assert "pipeline.pad_waste_share" in line["metrics"]
    assert not set(NEW) & set(line["metrics"])

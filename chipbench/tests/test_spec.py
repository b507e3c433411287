"""BENCHMARK.json against the contract's rules that a file can break, and
every name in it against the files the harness finds by that name."""

import json
import os
import re

import pytest

from chipbench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark()


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4
    )


def test_names_units_and_entry_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("chipbench/") and len(c["why"]) <= 200
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves",
        }
        assert m["source"] in SOURCES
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert all(NAME.match(m["name"]) and UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for group in (bench["configs"], bench["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_every_cell_finds_its_files_and_reports_enough(bench):
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m.moves in cell.end_to_end and m.moves in end_to_end
            assert callable(
                __import__(f"chipbench.readers.{m.reader}", fromlist=["read"]).read
            )
        assert cell.config["limits"]["retrievable_missing"] == 0
        if cell.traffic["kind"] == "ingest_backlog":
            assert str(cell.chips) in cell.traffic["backlog_docs_per_s"]
        else:
            assert cell.traffic["rate_qps"] > 0 and cell.traffic["store_docs"] >= 131072


def test_metric_files_agree_with_benchmark_json(bench):
    for m in bench["per_layer"]:
        with open(os.path.join(spec.HERE, "metrics", m["name"] + ".json")) as f:
            meta = json.load(f)
        for key in ("layer", "unit", "source", "moves", "better"):
            assert meta[key] == m[key], (m["name"], key)
        assert meta.get("workloads") == m.get("workloads"), m["name"]
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_configs_keep_published_widths(bench):
    minilm = spec.cell("minilm-l6.ingest-passages").config["model"]
    assert (minilm["layers"], minilm["hidden"], minilm["heads"], minilm["mlp_dim"],
            minilm["vocab_size"]) == (6, 384, 12, 1536, 30522)
    for name in ("e5-large.ingest-chunks",):
        e5 = spec.cell(name).config["model"]
        assert (e5["layers"], e5["hidden"], e5["heads"], e5["mlp_dim"],
                e5["vocab_size"], e5["max_position_embeddings"]) == (
                    24, 1024, 16, 4096, 30522, 512)

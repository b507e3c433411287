"""A whole run on the CPU at tiny sizes (`--dry`): the last line's keys,
the refusal to run without a chip, the control that has to come out as not
correct, and each fault the cells can have, planted under the harness."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
MINILM, E5 = "minilm-l6.ingest-passages", "e5-large.ingest-chunks"


def _run(module, *args, timeout=600):
    return subprocess.run(
        [sys.executable, "-m", module, *args], cwd=ROOT, env=ENV, timeout=timeout,
        capture_output=True, text=True,
    )


def _line(proc, correct=True):
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    notes = [ln for ln in proc.stderr.splitlines() if ln.startswith("[chipbench")]
    assert line["correct"] is correct, (line["compared"], notes)
    return line


def _dry(workload, seed, *more):
    return ("--workload", workload, "--seed", str(seed), "--seconds", "2", "--dry", *more)


def test_no_chip_no_result():
    proc = _run("chipbench.run", "--workload", MINILM, "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and proc.stdout == ""


def test_dry_run_end_to_end_and_control():
    line = _line(_run("chipbench.run", *_dry(MINILM, 2**31 + 77, "--trace", "0", "--control")))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["failed"] == 0 and line["attempted"] > 64
    assert set(line["metrics"]) == {"ingest_docs_per_s", "setup_s"}
    assert line["metrics"]["ingest_docs_per_s"] == {
        "value": line["metrics"]["ingest_docs_per_s"]["value"], "unit": "docs/s"}
    assert line["device"]["platform"] == "cpu" and line["facts"]["dry"] is True
    compared = line["compared"]
    assert set(compared) == {"retrievable_missing", "score_gap", "rank_gap"}
    assert all(c["value"] <= c["limit"] for c in compared.values())
    # the control: the reference in fp8 in the program's place is not correct
    control = line["control"]["encoder_fp8.index_bf16"]
    assert control["score_gap"] > compared["score_gap"]["limit"]
    assert control["score_gap"] > 3 * compared["score_gap"]["value"]


def test_dry_run_traced_reports_per_layer_metrics():
    line = _line(_run("chipbench.run", *_dry(E5, 5, "--trace", "1")))
    # no device trace on the CPU: the trace's readers stay silent, never 0
    assert set(line["metrics"]) == {
        "engine.index_node_busy_share.ingest", "pipeline.pad_waste_share",
        "device.filled_mem_gb.ingest", "compile.in_window.ingest",
    }
    assert 0 <= line["metrics"]["pipeline.pad_waste_share"]["value"] < 100


@pytest.mark.skipif(
    "minilm-l6.retrieve-steady" not in {w["name"] for w in json.load(
        open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]},
    reason="the retrieve cell is not in BENCHMARK.json",
)
def test_dry_run_of_the_retrieve_cell():
    line = _line(_run("chipbench.run", "--workload", "minilm-l6.retrieve-steady",
                      "--seed", "9", "--seconds", "3", "--dry", "--trace", "0"))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"retrieve_p50_ms", "retrieve_p95_ms", "setup_s"}
    assert line["failed"] == 0
    assert line["attempted"] == line["facts"]["requests"] == 60
    assert 0 < line["metrics"]["retrieve_p50_ms"]["value"] <= line["metrics"]["retrieve_p95_ms"]["value"]


@pytest.mark.parametrize("fault,workload,number", [
    ("state_unchanged", MINILM, "retrievable_missing"),
    ("half_batch", MINILM, "retrievable_missing"),
    ("altered_answer", MINILM, "score_gap"),
])
def test_a_planted_fault_comes_out_as_not_correct(fault, workload, number):
    line = _line(
        _run("chipbench.tests.faults", fault, *_dry(workload, 31, "--trace", "0")),
        correct=False,
    )
    c = line["compared"][number]
    assert c["value"] > c["limit"]
    assert line["device"]["platform"] == "cpu"

"""A second architecture is files only.  A copy of the benchmark gets the
fixture architecture `cls_encoder` (tests/data/cls_encoder/: the program's
pre-LN encoder with `pooling="cls"`, a reference that pools the first
token, its costs), a configuration that names it and a cell; a `--dry` run
of that cell in a child process is correct, its fp8 control is not, and
not one file that was in the copy has changed."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = {
    "name": "cls-toy.ingest-passages", "config": "cls-toy", "traffic": "ingest-passages",
    "chips": 1, "why": "fixture: an architecture that came as files only",
}
CONFIG = {
    "name": "cls-toy", "source": "chipbench/tests/test_files_only.py",
    "file": "chipbench/configs/cls-toy.json", "reduced": [], "why": "fixture",
}


def _hashes(top: str) -> dict:
    out = {}
    for folder, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", ".chipbench")]
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, top)] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_a_second_architecture_is_new_files_and_no_edit(tmp_path):
    copy = str(tmp_path / "checkout")
    shutil.copytree(
        os.path.join(ROOT, "chipbench"), os.path.join(copy, "chipbench"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    before = _hashes(copy)
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        bench_before = json.load(f)

    # -- what a PR that brings a model adds: files, and entries ---------------
    shutil.copytree(
        os.path.join(DATA, "cls_encoder"),
        os.path.join(copy, "chipbench", "architectures", "cls_encoder"),
    )
    shutil.copy(os.path.join(DATA, "cls-toy.json"), os.path.join(copy, "chipbench", "configs"))
    bench = json.loads(json.dumps(bench_before))
    bench["configs"].append(CONFIG)
    bench["workloads"].append(CELL)
    rate = next(m for m in bench["end_to_end"] if m["name"] == "ingest_docs_per_s")
    rate["workloads"].append(CELL["name"])  # the cell says which metric it reports
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)

    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL["name"], "--seed",
         str(2**31 + 5), "--seconds", "2", "--dry", "--trace", "0", "--control"],
        cwd=copy, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        timeout=600, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    compared = line["compared"]
    assert line["correct"] is True, compared
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    assert line["metrics"]["ingest_docs_per_s"]["value"] > 0
    assert all(c["value"] <= c["limit"] for c in compared.values())
    # the copy's own files ran: only its BENCHMARK.json has the cell
    assert f"unknown workload {CELL['name']!r}" in subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL["name"], "--seed", "1",
         "--seconds", "1", "--dry"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        timeout=120, capture_output=True, text=True,
    ).stderr
    # the control, the architecture's own reference in fp8 in the program's place
    control = line["control"]["encoder_fp8.index_bf16"]
    assert any(control[name] > c["limit"] for name, c in compared.items()), control
    assert control["score_gap"] > compared["score_gap"]["limit"]
    assert control["score_gap"] > 3 * compared["score_gap"]["value"]

    # -- and nothing that was there has changed ------------------------------
    after = _hashes(copy)
    changed = sorted(p for p in before if after.get(p) != before[p])
    assert changed == ["BENCHMARK.json"]
    added = sorted(set(after) - set(before))
    assert added == sorted(
        [os.path.join("chipbench", "configs", "cls-toy.json")]
        + [os.path.join("chipbench", "architectures", "cls_encoder", part + ".py")
           for part in ("costs", "program", "reference")]
    )
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        undone = json.load(f)  # take the three additions back: the rest is as it was
    assert undone["configs"].pop() == CONFIG and undone["workloads"].pop() == CELL
    rate = next(m for m in undone["end_to_end"] if m["name"] == "ingest_docs_per_s")
    assert rate["workloads"].pop() == CELL["name"]
    assert undone == bench_before

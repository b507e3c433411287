"""The architecture `eva_decoder` under the harness: the costs' rule that
turns the harness's word count into byte tokens, the kernel's work from the
program's counter, and the cell's whole `--dry` run (the program's own
files against the plain reference, the fp8 control that has to come out
further from it, the counters' metrics)."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import costs, spec, traffic
from chipbench.readers import op_roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "evabyte-pp2.ingest-pages-2"


def test_the_costs_turn_words_into_the_bytes_the_generator_writes():
    """64 generated pages of the cell's mix: the converted count of each
    file's word counts against the true bytes + `<bos>` of its texts, within
    0.5%; and the constant the costs keep is the traffic file's."""
    cell = spec.cell(CELL)
    work, model = cell.arch.costs, cell.config["model"]
    assert work.VOCABULARY_WORDS == cell.traffic["vocabulary_words"]
    assert work.bytes_per_word() == pytest.approx(7.413, abs=5e-4)
    corpus = traffic.Corpus(cell.traffic, 2**31 + 34)
    true = converted = 0
    for index in range(32):  # 2 pages a file
        for text, words in zip(corpus.file_docs(index), corpus.lengths):
            true += len(text.encode("utf-8")) + 1
            converted += work.byte_tokens(model, int(words) + 2)
    assert converted == pytest.approx(true, rel=5e-3)
    # the cut: a page past the store's limit counts the limit
    assert work.byte_tokens(model, 5000) == model["max_len"] == cell.config["store"]["max_len"]
    assert work.byte_tokens(work.dry_cut(model), 902) == 64


def test_the_costs_count_the_model_as_it_is_cut():
    cell = spec.cell(CELL)
    work, model = cell.arch.costs, cell.config["model"]
    layer = 4 * 4096 ** 2 + 3 * 4096 * 11008
    assert work.resident_param_bytes(model) == 2 * (
        320 * 4096 + 4096 + 16 * (layer + 2 * 32 * 128 + 2 * 4096)
    )
    assert 6.4e9 < work.weight_bytes(model) < 6.5e9
    assert work.embed_dim(model) == 4096
    assert work.dry_cut(model)["layers"] == 1
    # a page of 900 words: 6,672 tokens, the matmuls 96% of its FLOPs
    n = work.byte_tokens(model, 902)
    assert n == pytest.approx(6672, abs=2)
    matmuls = 2.0 * 16 * layer * n
    assert work.flops(model, 902) == pytest.approx(matmuls / 0.957, rel=0.005)
    # pairs of one head and layer: three whole windows' triangles and the
    # rest's, and 128, 256 and 384 summaries a query of windows 1, 2 and 3
    rest = n - 3 * 2048
    pairs = 3 * 2048 * 2049 / 2 + rest * (rest + 1) / 2 + 128 * (2048 * 3 + rest * 3)
    assert work.scored_pairs(model, n) == pytest.approx(pairs)
    assert work.scored_pairs(model, 100) == 100 * 101 / 2  # one window: plain causal
    assert work.eva_attention_flops(model, 10) == 10 * 4 * 128


def _status(**counts) -> dict:
    return {"spans": {"totals": {k: {"count": v} for k, v in counts.items()}}}


def test_the_kernels_roofline_reads_the_pairs_the_program_counted():
    cell = spec.cell(CELL)
    model, work = cell.config["model"], cell.arch.costs
    pairs = 3.2e11
    ctx = {
        "trace": {"ops": {"eva_attention bf16[1,11264,4096]": 2.0, "eva_rope bf16[1,11264,4096]": 0.2,
                          "fusion bf16[11264,11008]": 3.0},
                  "programs": {"jit__fwd_packed_eva": 18.0},
                  "program_runs": {"jit__fwd_packed_eva": 34}},
        "cell": cell, "arch": cell.arch, "device": {"kind": "TPU v5 lite"},
        "status_open": _status(**{"eva.scored_pairs": 1000}),
        "status_close": _status(**{"eva.scored_pairs": 1000 + int(pairs)}),
    }
    args = json.load(open(os.path.join(
        spec.HERE, "metrics", "kernels.eva_attention_roofline.json")))["args"]
    least = costs.roofline_seconds(
        work.eva_attention_flops(model, pairs), work.eva_attention_bytes(model, pairs, 34),
        "TPU v5 lite",
    )
    assert least["bound"] == "compute"
    assert op_roofline.read(ctx, **args) == pytest.approx(100.0 * least["seconds"] / 2.0)
    # silent on a program without the counter (the parent), and without a trace
    assert op_roofline.read(dict(ctx, status_close=_status()), **args) is None
    assert op_roofline.read(dict(ctx, trace=None), **args) is None


def test_dry_run_of_the_cell_is_correct_and_its_fp8_control_is_further_off(tmp_path):
    """One layer at the published widths on the CPU, texts cut to 64 bytes
    (`costs.dry_cut`): a few minutes, most of it the 4096-wide weights read
    again for every dispatch.  The cell's file is its dispatch (2 pages);
    the harness's rehearsal makes every file 64 documents, and its sample
    comes from whole files of the window, so the rehearsal runs in a copy
    of the benchmark whose configuration dispatches 64 at a time too: with
    2 a dispatch a file is 32 dispatches, 12 s here, and a window long
    enough for one leaves a backlog the read-back's first round waits out
    past the harness's 240 s a request (PERF.md section 7).  `--seconds
    0.5` makes the backlog two files."""
    import shutil

    copy = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(copy, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    path = os.path.join(copy, "chipbench", "configs", "evabyte-pp2-docstore.json")
    with open(path) as f:
        config = json.load(f)
    assert config["env"] == {"PATHWAY_INGEST_CHUNK": "2"}
    config["env"]["PATHWAY_INGEST_CHUNK"] = "64"
    with open(path, "w") as f:
        json.dump(config, f)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         str(2**31 + 34), "--seconds", "0.5", "--dry", "--trace", "1", "--control"],
        cwd=copy, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        timeout=1200, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    compared = line["compared"]
    assert compared["retrievable_missing"]["value"] == 0
    # one layer of sixteen: the fp8 control is further from the reference
    # than the program is, but not past a limit set at the cell's depth
    control = line["control"]["encoder_fp8.index_bf16"]
    assert control["score_gap"] > 3 * compared["score_gap"]["value"], (control, compared)
    # counts are the same on any backend: the counters' metric is read here
    # too (a text of 64 tokens has no summaries), the trace's stay silent
    metrics = line["metrics"]
    assert metrics["eva.summary_pair_share"]["value"] == 0.0
    assert not any("roofline" in name or "mfu" in name for name in metrics)
    assert metrics["compile.in_window.ingest"]["value"] == 0
    assert metrics["device.filled_mem_gb.ingest"]["value"] > 0.4

"""The architecture `moe_hybrid_decoder` under the harness: its costs
pinned by hand arithmetic (the rank's 6.70 GB, 1.866 GFLOP a token, the
pairs of the cell's two documents), the kernels' work from the program's
counters, and the cell's whole `--dry` run (the program's own files against
the plain reference, the fp8 control that has to come out further from it,
the counters' metrics)."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import costs, spec
from chipbench.readers import op_roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "mimo-v25-ep16.ingest-documents-2"
PROGRAM = "jit__fwd_packed_moe_hybrid"


def test_the_costs_count_the_rank_as_it_is_cut():
    cell = spec.cell(CELL)
    work, model = cell.arch.costs, cell.config["model"]
    d = 4096
    global_attention = d * (64 * 192 + 4 * 192 + 4 * 128) + 64 * 128 * d  # 89.1 M
    window_attention = d * (64 * 192 + 8 * 192 + 8 * 128) + 64 * 128 * d  # 94.4 M
    dense, expert, router = 3 * d * 16384, 3 * d * 2048, d * 256
    assert (global_attention, window_attention) == (89_128_960, 94_371_840)
    assert work.layer_kinds(model) == [(False, True)] + [(True, False)] * 4 + [
        (False, False), (True, False)]
    norms, sinks, bias = 2 * d, 64, 256
    layers = (
        (global_attention + norms + dense)  # layer 0: global, dense
        + 5 * (window_attention + norms + sinks + router + bias + 16 * expert)
        + (global_attention + norms + router + bias + 16 * expert)  # layer 5: global
    )
    assert work.layer_params(model) == layers
    assert work.resident_param_bytes(model) == 2 * (19072 * d + d + layers) == 6_703_672_960
    assert work.weight_bytes(model) == 2.0 * layers
    assert work.embed_dim(model) == 4096
    # a token: the matrices of seven layers, the router and half a held pair a layer
    per_token = 2 * (
        2 * global_attention + 5 * window_attention + dense + 6 * (router + 0.5 * expert)
    )
    assert work.held_pairs_per_token(model) == 0.5
    assert work.matrix_flops_per_token(model) == per_token == pytest.approx(1.866e9, rel=3e-4)
    # the cell's two documents: 8,500 and 16,000 words, [CLS] and [SEP]
    assert work.scored_pairs(model, 8502, False) == 8502 * 8503 // 2 == 36_146_253
    assert work.scored_pairs(model, 16002, False) == 128_040_003
    assert work.scored_pairs(model, 8502, True) == 128 * 129 // 2 + (8502 - 128) * 128 == 1_080_128
    assert work.scored_pairs(model, 16002, True) == 2_040_128
    assert work.scored_pairs(model, 100, True) == work.scored_pairs(model, 100, False) == 5050
    global_pairs = 2 * 64 * (36_146_253 + 128_040_003)
    window_pairs = 5 * 64 * (1_080_128 + 2_040_128)
    assert work.global_attention_flops(model, global_pairs) == 640.0 * global_pairs
    dispatch = work.flops(model, 8502) + work.flops(model, 16002)
    assert dispatch == 24504 * per_token + 640.0 * (global_pairs + window_pairs)
    assert dispatch == pytest.approx(59.8e12, rel=1e-3)
    assert 640.0 * global_pairs / dispatch == pytest.approx(0.225, abs=0.002)
    # a text past the store's limit counts the limit
    assert work.flops(model, 20000) == work.flops(model, 16384)
    # the rehearsal keeps a layer of each kind and every width
    cut = work.dry_cut(model)
    assert work.layer_kinds(cut) == [(False, True), (True, False), (False, False)]
    assert {k: v for k, v in cut.items() if cut[k] != model[k]}.keys() == {
        "layers", "hybrid_layer_pattern", "moe_layer_freq", "max_len"}


def _status(**counts) -> dict:
    return {"spans": {"totals": {k: {"count": v} for k, v in counts.items()}}}


@pytest.mark.parametrize("kind,bound", [("global", "compute"), ("window", "memory")])
def test_a_kinds_roofline_reads_the_pairs_the_program_counted(kind, bound):
    cell = spec.cell(CELL)
    model, work = cell.config["model"], cell.arch.costs
    pairs = {"global": 7.6e11, "window": 3.6e10}[kind]
    counter = f"hybrid.{kind}_pairs"
    ctx = {
        "trace": {"ops": {"hybrid_attention_global bf16[1,24576,8192]": 5.0,
                          "hybrid_attention_window bf16[1,24576,8192]": 0.8,
                          "hybrid_rope bf16[1,24576,4096]": 0.2,
                          "ragged-dot-none bf16[24576,2048]": 1.0},
                  "programs": {PROGRAM: 19.0}, "program_runs": {PROGRAM: 36}},
        "cell": cell, "arch": cell.arch, "device": {"kind": "TPU v5 lite"},
        "status_open": _status(**{counter: 1000}),
        "status_close": _status(**{counter: 1000 + int(pairs)}),
    }
    args = json.load(open(os.path.join(
        spec.HERE, "metrics", f"kernels.{kind}_attention_roofline.json")))["args"]
    least = costs.roofline_seconds(
        getattr(work, f"{kind}_attention_flops")(model, pairs),
        getattr(work, f"{kind}_attention_bytes")(model, pairs, 36), "TPU v5 lite",
    )
    assert least["bound"] == bound
    seconds = {"global": 5.0, "window": 0.8}[kind]  # its own ops, not the other kind's
    assert op_roofline.read(ctx, **args) == pytest.approx(100.0 * least["seconds"] / seconds)
    assert op_roofline.read(ctx, **args) < 100.0
    # silent on a program without the counter (the parent), and without a trace
    assert op_roofline.read(dict(ctx, status_close=_status()), **args) is None
    assert op_roofline.read(dict(ctx, trace=None), **args) is None


def test_dry_run_of_the_cell_is_correct_and_its_fp8_control_is_further_off(tmp_path):
    """A layer of each kind at the published widths on the CPU, texts cut
    to 32 tokens (`costs.dry_cut`).  The cell's file is its dispatch (2
    documents); the harness's rehearsal makes every file 64 documents, so it
    runs in a copy of the benchmark whose configuration dispatches 64 at a
    time too, as `test_eva_decoder.py` does (PERF.md section 7).
    `--seconds 0.5` makes the backlog two files."""
    import shutil

    copy = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "chipbench"), os.path.join(copy, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    path = os.path.join(copy, "chipbench", "configs", "mimo-v25-ep16-docstore.json")
    with open(path) as f:
        config = json.load(f)
    assert config["env"] == {"PATHWAY_INGEST_CHUNK": "2"}
    config["env"]["PATHWAY_INGEST_CHUNK"] = "64"
    with open(path, "w") as f:
        json.dump(config, f)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         str(2**31 + 36), "--seconds", "0.5", "--dry", "--trace", "1", "--control"],
        cwd=copy, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        timeout=1500, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    compared = line["compared"]
    assert compared["retrievable_missing"]["value"] == 0
    # three layers of seven: the fp8 control is further from the reference
    # than the program is
    control = line["control"]["encoder_fp8.index_bf16"]
    assert control["score_gap"] > 3 * compared["score_gap"]["value"], (control, compared)
    # counts are the same on any backend: the counters' metrics are read here
    # too, the trace's stay silent.  Texts of 32 tokens lie inside the
    # window of 128: a window layer scores what a global one does, so of
    # three layers' pairs two thirds are the two global layers'
    metrics = line["metrics"]
    assert metrics["hybrid.global_pair_share"]["value"] == pytest.approx(200 / 3)
    # the routing statistics come from the device: they reach the line where
    # the dispatches had produced them by the closing snapshot (the CPU
    # backend runs programs in no order, so the closing marker does not say)
    if "moe_hybrid.held_pair_share" in metrics:
        assert metrics["moe_hybrid.held_pair_share"]["value"] == pytest.approx(6.25, abs=1.5)
        assert 1.0 < metrics["moe_hybrid.expert_load_skew"]["value"] < 4.0
    assert not any("roofline" in name or "mfu" in name for name in metrics)
    assert metrics["compile.in_window.ingest"]["value"] == 0
    assert metrics["device.filled_mem_gb.ingest"]["value"] > 1.0

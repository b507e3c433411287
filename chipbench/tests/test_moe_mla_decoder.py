"""The architecture `moe_mla_decoder` under the harness: its cell's whole
`--dry` run (the program's own files against the plain reference, the fp8
control that has to come out as not correct, the counters' metrics), and
the two readers that came with it, on hand-made runs."""

import json
import os
import subprocess
import sys
import types

import pytest

from chipbench import costs, spec
from chipbench.readers import counter_ratio, op_roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "axk1-ep16.ingest-chunks-64"


def test_dry_run_of_the_cell_is_correct_and_its_fp8_control_is_not():
    """One dense and one expert layer at the published widths on the CPU:
    a quarter of an hour, most of it the 7168-wide matmuls.  `--seconds
    0.3` makes the backlog two files, both inside the window: with a third
    still in flight (81 s a dispatch here) the read-back's one round of
    queries has passed the harness's 240 s a request."""
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         str(2**31 + 30), "--seconds", "0.3", "--dry", "--trace", "1", "--control"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        timeout=1700, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    compared = line["compared"]
    assert compared["retrievable_missing"]["value"] == 0
    control = line["control"]["encoder_fp8.index_bf16"]
    assert control["score_gap"] > compared["score_gap"]["limit"]
    assert control["score_gap"] > 3 * compared["score_gap"]["value"]
    # counts are the same on any backend: the counters' metrics are read
    # here too, the trace's stay silent
    metrics = line["metrics"]
    assert 4.0 < metrics["moe.held_pair_share"]["value"] < 9.0
    assert 1.0 <= metrics["moe.expert_load_skew"]["value"] < 3.0
    assert not any("roofline" in name for name in metrics)
    assert metrics["device.filled_mem_gb.ingest"]["value"] > 2.0


def _status(**counts) -> dict:
    return {"spans": {"totals": {k: {"count": v} for k, v in counts.items()}}}


def test_counter_ratio_differences_the_two_snapshots():
    ctx = {
        "status_open": _status(**{"moe.pairs_held": 100, "moe.pairs_routed": 1000}),
        "status_close": _status(**{"moe.pairs_held": 725, "moe.pairs_routed": 11000}),
    }
    assert counter_ratio.read(ctx, "moe.pairs_held", "moe.pairs_routed") == 6.25
    assert counter_ratio.read(ctx, "moe.pairs_held", "moe.pairs_routed", percent=False) == 0.0625
    # a counter that first occurs inside the window counts from zero
    ctx["status_open"] = _status()
    assert counter_ratio.counted(ctx, "moe.pairs_held") == 725
    # silent: no such counter (the parent), nothing counted, no snapshots
    assert counter_ratio.read(ctx, "moe.absent", "moe.pairs_routed") is None
    assert counter_ratio.read(ctx, "moe.pairs_held", "moe.absent") is None
    ctx["status_close"] = {"device_pipeline": {}}  # a program without a span record
    assert counter_ratio.read(ctx, "moe.pairs_held", "moe.pairs_routed") is None
    assert counter_ratio.read({"status_open": None, "status_close": None}, "a", "b") is None


def test_op_roofline_reads_a_kernels_ops_against_its_own_work():
    cell = spec.cell(CELL)
    model, work = cell.config["model"], cell.arch.costs
    tokens = [352, 202, 502]
    ops = {
        "mla_segment_attention bf16[28,504,8192]": 0.004,
        "ragged-dot-none bf16[14336,2048]": 0.003,
        "ragged-dot-none bf16[14336,7168]": 0.001,
        "fusion bf16[28,504,8192]": 9.0,
    }
    ctx = {
        "trace": {"ops": ops, "programs": {"jit__fwd_packed_moe_mla": 0.5},
                  "program_runs": {"jit__fwd_packed_moe_mla": 2}},
        "cell": cell, "arch": cell.arch, "device": {"kind": "TPU v5 lite"},
        "docs_in_window": 3, "docs_per_file": 64, "tokens_per_file": tokens,
        "status_open": _status(**{"moe.pairs_held": 0}),
        "status_close": _status(**{"moe.pairs_held": 5000}),
    }
    flops = sum(work.mla_attention_flops(model, t) for t in tokens)
    nbytes = sum(work.mla_attention_bytes(model, t) for t in tokens)
    least = costs.roofline_seconds(flops, nbytes, "TPU v5 lite")["seconds"]
    got = op_roofline.read(ctx, ["mla_segment_attention"], "mla_attention")
    assert got == pytest.approx(100.0 * least / 0.004)
    least = costs.roofline_seconds(
        work.expert_matmul_flops(model, 5000), work.expert_matmul_bytes(model, 5000, 2),
        "TPU v5 lite",
    )["seconds"]
    got = op_roofline.read(ctx, ["ragged-dot"], "expert_matmul",
                           counter="moe.pairs_held", programs=["_fwd_packed_moe_mla"])
    assert got == pytest.approx(100.0 * least / 0.004)
    # silent: no such op, no such cost, no counter, no trace
    assert op_roofline.read(ctx, ["no_such_kernel"], "mla_attention") is None
    assert op_roofline.read(ctx, ["mla_segment_attention"], "no_such_cost") is None
    assert op_roofline.read(ctx, ["ragged-dot"], "expert_matmul", counter="moe.absent") is None
    bert = types.SimpleNamespace(costs=spec.Architecture("bert_encoder").costs)
    assert op_roofline.read(dict(ctx, arch=bert), ["mla_segment_attention"], "mla_attention") is None
    assert op_roofline.read(dict(ctx, trace=None), ["mla_segment_attention"], "mla_attention") is None

"""The architecture `longcat_decoder` under the harness: its reference
against the program's encoder at a small size over every padded length,
the kernels' work read against the configuration's costs, the cell's
whole `--dry` run (the program's own files against the plain reference,
the counters' metrics), and the fp8 control, which has to come out as not
correct."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import costs, spec, traffic
from chipbench.readers import counter_ratio, op_roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "longcat-flash-ep32.ingest-chunks-64"
PROGRAM = "jit__fwd_packed_longcat"


def small_model() -> dict:
    """The cell's `model` group at toy widths: the keys of the three files,
    the heads' 128 + 64 over 128 as published (so the kernel's widths),
    two double layers, 16 routed and 8 zero-compute experts at top-6, 4
    held."""
    model = dict(spec.cell(CELL).config["model"])
    model.update(
        hidden_size=96, num_attention_heads=4, q_lora_rank=40, kv_lora_rank=24,
        ffn_hidden_size=160, expert_ffn_hidden_size=48, n_routed_experts=16,
        zero_expert_num=8, moe_topk=6, layers=2, experts_held=4, vocab_held=512,
        dtype="float32", param_dtype="float32",
    )
    return model


def test_the_reference_agrees_with_the_programs_encoder_at_a_small_size():
    """Float32 on both sides: the order of the sums is what separates them
    (tests/test_longcat.py holds each mechanism and the packed path)."""
    from pathway_tpu.models import minilm

    model, store = small_model(), {"max_len": 512}
    arch = spec.cell(CELL).arch
    rng = np.random.default_rng(5)
    # 11 to 502 tokens: the reference pads to 32, 128, 384 and 512 slots
    texts = [" ".join(f"w{int(x)}" for x in rng.integers(0, 3000, size=n))
             for n in (9, 40, 77, 120, 300, 500)]
    minilm._model_cache.clear()
    encoder = arch.program.embedder(model, store, 2**31 + 5).encoder
    got = encoder.encode_packed(texts)
    want = arch.reference.Encoder(model, 2**31 + 5, max_len=512).embed(texts)
    np.testing.assert_allclose(got, want, atol=2e-5)
    arch.program.release()


def _status(**counts) -> dict:
    return {"spans": {"totals": {k: {"count": v} for k, v in counts.items()}}}


def test_the_metrics_read_the_kernels_and_counters_against_this_models_work():
    """The four metric files of the cell, through their readers, on a
    hand-made run: the attention kernel's work is two sublayers a double
    layer, the expert matmul's the pairs the program held, and the zero
    pairs' share is over every pair routed."""
    cell = spec.cell(CELL)
    model, work = cell.config["model"], cell.arch.costs
    metrics = {m.name: m for m in cell.per_layer}
    tokens = [352, 202, 502]
    ops = {
        "mla_segment_attention bf16[28,504,8192]": 0.004,
        "ragged-dot-none bf16[14336,2048]": 0.003,
        "ragged-dot-none bf16[14336,6144]": 0.001,
        "fusion bf16[28,504,12288]": 9.0,
    }
    ctx = {
        "trace": {"ops": ops, "programs": {PROGRAM: 0.5}, "program_runs": {PROGRAM: 2}},
        "cell": cell, "arch": cell.arch, "device": {"kind": "TPU v5 lite"},
        "docs_in_window": 3, "docs_per_file": 64, "tokens_per_file": tokens,
        "status_open": _status(**{"moe.pairs_held": 0, "moe.pairs_routed": 0,
                                  "longcat.zero_pairs": 0}),
        "status_close": _status(**{"moe.pairs_held": 5000, "moe.pairs_routed": 60000,
                                   "longcat.zero_pairs": 20000}),
    }
    assert metrics["longcat.zero_pair_share"].read(ctx) == pytest.approx(100 / 3)
    flops = sum(work.mla_attention_flops(model, t) for t in tokens)
    nbytes = sum(work.mla_attention_bytes(model, t) for t in tokens)
    least = costs.roofline_seconds(flops, nbytes, "TPU v5 lite")["seconds"]
    assert metrics["kernels.longcat_mla_attention_roofline"].read(ctx) == pytest.approx(
        100.0 * least / 0.004)
    least = costs.roofline_seconds(
        work.expert_matmul_flops(model, 5000), work.expert_matmul_bytes(model, 5000, 2),
        "TPU v5 lite",
    )["seconds"]
    assert metrics["kernels.longcat_expert_matmul_roofline"].read(ctx) == pytest.approx(
        100.0 * least / 0.004)
    assert metrics["programs.longcat_roofline"].read(ctx) > 0
    # silent where the parent has no such counter
    ctx["status_close"] = _status(**{"moe.pairs_routed": 60000})
    assert counter_ratio.read(ctx, "longcat.zero_pairs", "moe.pairs_routed") is None
    assert op_roofline.read(dict(ctx, trace=None), ["mla_segment_attention"], "mla_attention") is None


def test_dry_run_of_the_cell_is_correct():
    """One double layer at the published widths on the CPU, about a quarter
    of an hour.  `--seconds 0.3` makes the backlog two files, as the A.X-K1
    cell's test does.  Without `--control`: at this width the controls'
    passes take the run past the harness's 1,150 s (the next test reads
    the fp8 control the same way, on fewer documents)."""
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         str(2**31 + 50), "--seconds", "0.3", "--dry", "--trace", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        timeout=1500, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    compared = line["compared"]
    assert compared["retrievable_missing"]["value"] == 0
    # counts are the same on any backend: the counters' metrics are read
    # here too, the trace's stay silent
    metrics = line["metrics"]
    assert 25.0 < metrics["longcat.zero_pair_share"]["value"] < 42.0
    assert not any("roofline" in name for name in metrics)
    assert metrics["device.filled_mem_gb.ingest"]["value"] > 2.0


def test_the_fp8_control_is_not_correct_at_one_double_layer():
    """The harness's control (`encoder_fp8.index_bf16`: the reference in
    fp8 in the program's place, its index in bf16) through the same
    comparison, on 4 chunks of the cell's traffic, their probes and a pool
    of 12, at the rehearsal's depth: it fails one of the cell's limits or
    both (the chip's four double layers fail both: PERF.md section 6)."""
    from chipbench import compare, reference
    from chipbench.harness import dry_cut, probe_of

    cell = dry_cut(spec.cell(CELL))
    model, store = cell.config["model"], cell.config["store"]
    docs = traffic.Corpus(cell.traffic, 2**31 + 50).file_docs(2)
    own, pool = docs[:4], docs[4:16]
    probes = [probe_of(t, cell.traffic["probe_words"]) for t in own]
    encoder = cell.arch.reference.Encoder(model, 2**31 + 50, max_len=store["max_len"])
    own_rows, probe_rows = compare.control_answers(
        own, probes, pool, lambda texts: encoder.embed(texts, lower_precision="fp8"),
        store["k"], index_round=lambda v: reference.round_vectors(v, "bf16"),
    )
    numbers = compare.compare(own, own_rows, probes, probe_rows, pool, encoder.embed, store["k"])
    correct, compared = compare.verdict(numbers, cell.config["limits"])
    assert not correct, compared

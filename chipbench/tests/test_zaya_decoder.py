"""The architecture `zaya_decoder` under the harness: its reference against
the program's encoder at a small size, its costs pinned by hand arithmetic
(stage 0's 9.38 GB, 752.5 MFLOP a token, the pairs of a chunk) and against
the program's own token count, the kernels' work from the program's
counters, and the cell's whole `--dry` run (the program's own files against
the plain reference, the fp8 control that has to come out further from it,
the counters' metrics)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import costs, spec
from chipbench.readers import counter_ratio, op_roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "zaya1-pp2.ingest-chunks-64-deep"
PROGRAM = "jit__fwd_packed_zaya"


def small_model() -> dict:
    """The cell's `model` group at toy widths: the keys of the three files,
    the head's 128 with 64 rotated as published."""
    model = dict(spec.cell(CELL).config["model"])
    model.update(
        hidden_size=64, num_attention_heads=4, moe_intermediate_size=32, num_experts=8,
        experts_held=8, router_hidden_size=32, layers=2, vocab_held=512, max_len=128,
        dtype="float32", param_dtype="float32",
    )
    return model


def test_the_reference_agrees_with_the_programs_encoder_at_a_small_size():
    """Float32 on both sides: the order of the sums is what separates
    them (tests/test_zaya.py holds each mechanism and the packed path)."""
    from pathway_tpu.models import minilm

    model, store = dict(small_model(), max_len=512), {"max_len": 512}
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
    # a configuration the two sides would read differently is refused by both
    with pytest.raises(ValueError, match="sliding_window"):
        arch.reference.Encoder(dict(model, sliding_window=4096), 1, max_len=128)
    with pytest.raises(ValueError, match="tau_mean"):
        arch.program.embedder(dict(model, tau_mean=1.0), store, 1)


def test_the_costs_count_the_stage_as_it_is_cut():
    cell = spec.cell(CELL)
    work, model = cell.arch.costs, cell.config["model"]
    d = 2048
    attention = d * (8 + 2 + 2) * 128 + 8 * 128 * d + 10 * 2 * 128 * 128  # 5.57 M
    router = d * 256 + 2 * 256 * 256 + 256 * 17
    expert = 3 * d * 2048
    vectors = 2 * d + 256 + (2 + 2) * 1280 + 2 + 4 * d + 1 + 17
    assert (attention, router, expert, vectors) == (5_570_560, 659_712, 12_582_912, 17_684)
    layers = 20 * (attention + router + 16 * expert + vectors)
    assert work.layer_params(model) == layers
    assert work.resident_param_bytes(model) == 2 * (262272 * d + d + layers) == 9_377_252_128
    assert work.weight_bytes(model) == 2.0 * layers
    assert work.embed_dim(model) == 2048
    # a token: the matrices of twenty layers and one expert in each
    per_token = 2 * 20 * (attention + router + expert)
    assert work.held_pairs_per_token(model) == 1.0
    assert work.matrix_flops_per_token(model) == per_token == pytest.approx(752.5e6, rel=1e-4)
    assert 2 * 20 * expert / per_token == pytest.approx(0.669, abs=0.001)
    # a chunk of 350 words, [CLS] and [SEP]
    assert work.scored_pairs(352) == 352 * 353 // 2 == 62_128
    pairs = 20 * 8 * 62_128
    assert work.cca_attention_flops(model, pairs) == 512.0 * pairs
    assert work.flops(model, 352) == 352 * per_token + 512.0 * pairs
    assert 512.0 * pairs / work.flops(model, 352) == pytest.approx(0.0188, abs=0.0005)
    # a text past the store's limit counts the limit
    assert work.flops(model, 900) == work.flops(model, 512)
    # the rehearsal cuts depth and the texts, never a width or an expert
    cut = work.dry_cut(model)
    assert {k for k in cut if cut[k] != model[k]} == {"layers", "max_len"}


def test_the_costs_tokens_are_the_programs():
    """`zaya.tokens`, counted by the program from a packed batch, is what
    the harness hands to `costs.flops`: words + 2 a document."""
    from pathway_tpu.internals import tracing
    from pathway_tpu.models import minilm

    arch = spec.cell(CELL).arch
    minilm._model_cache.clear()
    encoder = arch.program.embedder(small_model(), {"max_len": 128}, 3).encoder
    words = (12, 50, 31, 100)
    texts = [" ".join(["word"] * n) for n in words]
    before = tracing.spans_status()["totals"].get("zaya.tokens", {"count": 0})["count"]
    encoder.encode_packed(texts)
    after = tracing.spans_status()["totals"]["zaya.tokens"]["count"]
    assert after - before == sum(n + 2 for n in words)
    arch.program.release()


def _status(**counts) -> dict:
    return {"spans": {"totals": {k: {"count": v} for k, v in counts.items()}}}


@pytest.mark.parametrize("metric,op,counter,bound", [
    ("kernels.cca_attention_roofline", "cca_attention", "zaya.scored_pairs", "memory"),
    ("kernels.zaya_expert_matmul_roofline", "ragged-dot", "moe.pairs_held", "compute"),
])
def test_a_kernels_roofline_reads_what_the_program_counted(metric, op, counter, bound):
    cell = spec.cell(CELL)
    model, work = cell.config["model"], cell.arch.costs
    n = {"zaya.scored_pairs": 4.0e11, "moe.pairs_held": 1.7e7}[counter]
    ctx = {
        "trace": {"ops": {"cca_attention bf16[56,504,1024]": 1.5,
                          "ragged-dot-none bf16[36864,2048]": 6.0,
                          "fusion bf16[56,504,2048]": 3.0},
                  "programs": {PROGRAM: 19.0}, "program_runs": {PROGRAM: 40}},
        "cell": cell, "arch": cell.arch, "device": {"kind": "TPU v5 lite"},
        "status_open": _status(**{counter: 1000}),
        "status_close": _status(**{counter: 1000 + int(n)}),
    }
    args = json.load(open(os.path.join(spec.HERE, "metrics", metric + ".json")))["args"]
    cost = args["cost"]
    least = costs.roofline_seconds(
        getattr(work, cost + "_flops")(model, n),
        getattr(work, cost + "_bytes")(model, n, 40), "TPU v5 lite",
    )
    assert least["bound"] == bound
    seconds = {"cca_attention": 1.5, "ragged-dot": 6.0}[op]  # its own ops alone
    assert op_roofline.read(ctx, **args) == pytest.approx(100.0 * least["seconds"] / seconds)
    assert op_roofline.read(ctx, **args) < 100.0
    # silent on a program without the counter (the parent), and without a trace
    assert op_roofline.read(dict(ctx, status_close=_status()), **args) is None
    assert op_roofline.read(dict(ctx, trace=None), **args) is None


def test_the_counters_metrics_read_the_shared_paths_counts():
    """The skipped share is of tokens x layers, which at top-1 is what the
    shared path counts as routed pairs."""
    ctx = {
        "status_open": _status(**{"zaya.skipped_tokens": 10, "moe.pairs_routed": 100,
                                  "moe.expert_tokens_max": 5, "moe.expert_tokens_mean": 4}),
        "status_close": _status(**{"zaya.skipped_tokens": 70, "moe.pairs_routed": 1100,
                                   "moe.expert_tokens_max": 125, "moe.expert_tokens_mean": 84}),
    }
    read = lambda name: counter_ratio.read(ctx, **json.load(open(os.path.join(  # noqa: E731
        spec.HERE, "metrics", name + ".json")))["args"])
    assert read("zaya.skipped_token_share") == pytest.approx(6.0)
    assert read("zaya.expert_load_skew") == pytest.approx(1.5)
    ctx["status_close"] = _status()
    assert read("zaya.skipped_token_share") is None


def test_dry_run_of_the_cell_is_correct_and_its_fp8_control_is_further_off():
    """Two layers at the published widths on the CPU, all 16 experts and
    the whole vocabulary, texts cut to 32 tokens (`costs.dry_cut`).  The
    cell's file is 64 documents, as the harness's rehearsal makes every
    file, so it runs in place.  `--seconds 0.5` makes the backlog two
    files."""
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         str(2**31 + 42), "--seconds", "0.5", "--dry", "--trace", "1", "--control"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        timeout=1500, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    compared = line["compared"]
    assert compared["retrievable_missing"]["value"] == 0
    # two layers of twenty: the fp8 control is further from the reference
    # than the program is, by 2.9 and 3.8 times in two rehearsals of one
    # seed (the sample follows how much the window ingested, so the
    # control's reading is not one number a seed)
    control = line["control"]["encoder_fp8.index_bf16"]
    assert control["score_gap"] > 2 * compared["score_gap"]["value"], (control, compared)
    # counts are the same on any backend: the counters' metrics are read
    # here too where the dispatches had produced them by the closing
    # snapshot (the CPU backend runs programs in no order, so the closing
    # marker does not say); the trace's stay silent
    metrics = line["metrics"]
    if "zaya.skipped_token_share" in metrics:
        assert 0.0 < metrics["zaya.skipped_token_share"]["value"] < 30.0
        assert 1.0 <= metrics["zaya.expert_load_skew"]["value"] < 8.0
    assert not any("roofline" in name or "mfu" in name for name in metrics)
    assert metrics["compile.in_window.ingest"]["value"] == 0
    assert metrics["device.filled_mem_gb.ingest"]["value"] > 1.0

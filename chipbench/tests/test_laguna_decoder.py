"""The architecture `laguna_decoder` under the harness: its reference
against the program's encoder at a small size, its costs pinned by hand
arithmetic (stage 0's 7.33 GB, 19.2 TFLOP a dispatch, the pairs of a file
by kind) and against the program's own counters, the kernels' work from
the program's counters, and the cell's whole `--dry` run (the program's
own files against the plain reference, the fp8 control that has to come
out further from it, the counters' metrics)."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import costs, spec, traffic
from chipbench.readers import counter_ratio, op_roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "laguna-xs2-pp8.ingest-code-files"
PROGRAM = "jit__fwd_packed_moe_hybrid"


def small_model() -> dict:
    """The cell's `model` group at toy widths: the keys of the three files,
    the head's 128 with 64 rotated on full layers as published, three
    layers (full dense, sliding and full sparse), 16 experts all held."""
    model = dict(spec.cell(CELL).config["model"])
    model.update(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, num_experts=16, experts_held=16,
        num_key_value_heads=2, layers=3,
        layer_types=["full_attention", "sliding_attention", "full_attention"],
        mlp_layer_types=["dense", "sparse", "sparse"],
        num_attention_heads_per_layer=[4, 8, 4], sliding_window=64, vocab_held=512,
        max_len=512, dtype="float32", param_dtype="float32",
    )
    return model


def test_the_reference_agrees_with_the_programs_encoder_at_a_small_size():
    """Float32 on both sides: the order of the sums is what separates
    them (tests/test_laguna.py holds each mechanism and the packed path)."""
    from pathway_tpu.models import minilm

    model, store = small_model(), {"max_len": 512}
    arch = spec.cell(CELL).arch
    rng = np.random.default_rng(5)
    # 11 to 502 tokens: under and over the window, the reference pads to
    # whole 32s and to 512 slots (`max_len`)
    texts = [" ".join(f"w{int(x)}" for x in rng.integers(0, 3000, size=n))
             for n in (9, 40, 77, 120, 300, 500)]
    minilm._model_cache.clear()
    encoder = arch.program.embedder(model, store, 2**31 + 5).encoder
    got = encoder.encode_packed(texts)
    want = arch.reference.Encoder(model, 2**31 + 5, max_len=512).embed(texts)
    np.testing.assert_allclose(got, want, atol=2e-5)
    arch.program.release()
    # a configuration the two sides would read differently is refused by both
    with pytest.raises(ValueError, match="gate_form"):
        arch.reference.Encoder(dict(model, gate_form="element-wise"), 1, max_len=128)
    with pytest.raises(ValueError, match="gate_form"):
        arch.program.embedder(dict(model, gate_form="element-wise"), store, 1)


def test_the_costs_count_the_stage_as_it_is_cut():
    cell = spec.cell(CELL)
    work, model = cell.arch.costs, cell.config["model"]
    d = 2048

    def attention(heads):
        return d * (heads + 16) * 128 + heads * 128 * d + d * heads

    assert (attention(48), attention(64)) == (29_458_432, 37_879_808)
    dense, router, shared, expert = 3 * d * 8192, d * 256, 3 * d * 512, 3 * d * 512
    sparse = router + shared + 256 * expert
    assert (dense, sparse) == (50_331_648, 808_976_384)
    layers = attention(48) + dense + 3 * (attention(64) + sparse) + attention(48) + sparse
    layers += 5 * 2 * d  # two norms a layer
    assert work.layer_params(model) == layers
    assert work.resident_param_bytes(model) == 2 * (100352 * d + d + layers) == 7_328_673_792
    assert work.weight_bytes(model) == 2.0 * layers
    assert work.embed_dim(model) == 2048
    # a token: every matrix of five layers and eight experts in each sparse one
    per_token = 2 * (
        attention(48) + dense + 3 * attention(64) + attention(48)
        + 4 * (router + shared + 8 * expert)
    )
    assert work.held_pairs_per_token(model) == 8.0
    assert work.matrix_flops_per_token(model) == per_token
    # the cell's file: 12 documents, 23,349 tokens, 19.2 TFLOP
    words = traffic.length_multiset(cell.traffic["length_words"], 12)
    assert words.tolist() == [212, 380, 533, 693, 873, 1081, 1332, 1650, 2077, 2703, 3791, 8000]
    tokens = [w + 2 for w in words]
    assert sum(tokens) == 23_349
    total = sum(work.flops(model, t) for t in tokens)
    assert total == pytest.approx(19.217e12, rel=1e-3)
    experts = 4 * 8 * expert * 2 * sum(tokens)
    assert experts / total == pytest.approx(0.2446, abs=0.001)
    # the pairs by kind: two full layers of 48 heads, three sliding of 64
    full = 8002 * 8003 // 2
    assert work.scored_pairs(model, 8002, False) == full
    window = 512 * 513 // 2 + (8002 - 512) * 512
    assert work.scored_pairs(model, 8002, True) == window
    assert work._pairs(model, 8002, False) == 2 * 48 * full
    assert work._pairs(model, 8002, True) == 3 * 64 * window
    assert work.global_attention_flops(model, 1.0) == 512.0
    # a window-blind trunk would score 3.77 TFLOP more
    blind = sum(
        work.window_attention_flops(model, 3 * 64 * (work.scored_pairs(model, t, False)
                                                     - work.scored_pairs(model, t, True)))
        for t in tokens
    )
    assert blind == pytest.approx(3.77e12, rel=0.01)
    # a text past the store's limit counts the limit
    assert work.flops(model, 9000) == work.flops(model, 8192)
    # the rehearsal cuts depth, the experts held and the texts, never a width
    cut = work.dry_cut(model)
    assert {k for k in cut if cut[k] != model[k]} == {
        "layers", "layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
        "experts_held", "max_len",
    }
    assert cut["num_attention_heads_per_layer"] == [48, 64, 48]


def test_the_costs_pairs_are_the_programs_counters():
    """`hybrid.global_pairs` / `.window_pairs`, counted by the program from
    a packed batch with heads by kind, are what `costs._pairs` gives for
    words + 2 tokens a document."""
    from pathway_tpu.internals import tracing
    from pathway_tpu.models import minilm

    arch, model = spec.cell(CELL).arch, small_model()
    minilm._model_cache.clear()
    encoder = arch.program.embedder(model, {"max_len": 512}, 3).encoder
    words = (12, 50, 131, 100)
    texts = [" ".join(["word"] * n) for n in words]

    def read():
        totals = tracing.spans_status()["totals"]
        return [totals.get(n, {"count": 0})["count"]
                for n in ("hybrid.global_pairs", "hybrid.window_pairs", "hybrid.tokens")]

    before = read()
    encoder.encode_packed(texts)
    after = read()
    got = [a - b for a, b in zip(after, before)]
    costs_ = arch.costs
    assert got == [
        sum(costs_._pairs(model, n + 2, False) for n in words),
        sum(costs_._pairs(model, n + 2, True) for n in words),
        sum(n + 2 for n in words),
    ]
    arch.program.release()


def _status(**counts) -> dict:
    return {"spans": {"totals": {k: {"count": v} for k, v in counts.items()}}}


@pytest.mark.parametrize("metric,op,counter,bound", [
    ("kernels.laguna_global_attention_roofline", "laguna_attention_global",
     "hybrid.global_pairs", "compute"),
    ("kernels.laguna_window_attention_roofline", "laguna_attention_window",
     "hybrid.window_pairs", "compute"),
    ("kernels.laguna_expert_matmul_roofline", "ragged-dot", "moe.pairs_held", "compute"),
])
def test_a_kernels_roofline_reads_what_the_program_counted(metric, op, counter, bound):
    cell = spec.cell(CELL)
    model, work = cell.config["model"], cell.arch.costs
    n = {"hybrid.global_pairs": 1.0e11, "hybrid.window_pairs": 6.0e10,
         "moe.pairs_held": 6.0e7}[counter]
    seconds = {"laguna_attention_global": 1.5, "laguna_attention_window": 1.2,
               "ragged-dot": 6.0}
    ctx = {
        "trace": {"ops": {"laguna_attention_global bf16[1,23552,6144]": 1.5,
                          "laguna_attention_window bf16[1,23552,8192]": 1.2,
                          "ragged-dot-none bf16[319488,512]": 6.0,
                          "fusion bf16[1,23552,2048]": 3.0},
                  "programs": {PROGRAM: 19.0}, "program_runs": {PROGRAM: 80}},
        "cell": cell, "arch": cell.arch, "device": {"kind": "TPU v5 lite"},
        "status_open": _status(**{counter: 1000}),
        "status_close": _status(**{counter: 1000 + int(n)}),
    }
    args = json.load(open(os.path.join(spec.HERE, "metrics", metric + ".json")))["args"]
    cost = args["cost"]
    least = costs.roofline_seconds(
        getattr(work, cost + "_flops")(model, n),
        getattr(work, cost + "_bytes")(model, n, 80), "TPU v5 lite",
    )
    assert least["bound"] == bound
    assert op_roofline.read(ctx, **args) == pytest.approx(100.0 * least["seconds"] / seconds[op])
    assert op_roofline.read(ctx, **args) < 100.0
    # silent on a program without the counter (the parent), and without a trace
    assert op_roofline.read(dict(ctx, status_close=_status()), **args) is None
    assert op_roofline.read(dict(ctx, trace=None), **args) is None


def test_the_counters_metrics_read_the_shared_paths_counts():
    ctx = {
        "status_open": _status(**{"moe.group_pad_rows": 100, "moe.group_rows": 1000,
                                  "moe.expert_tokens_max": 5, "moe.expert_tokens_mean": 4}),
        "status_close": _status(**{"moe.group_pad_rows": 3100, "moe.group_rows": 11000,
                                   "moe.expert_tokens_max": 125, "moe.expert_tokens_mean": 84}),
    }
    read = lambda name: counter_ratio.read(ctx, **json.load(open(os.path.join(  # noqa: E731
        spec.HERE, "metrics", name + ".json")))["args"])
    assert read("laguna.expert_pad_share") == pytest.approx(30.0)
    assert read("laguna.expert_load_skew") == pytest.approx(1.5)
    # the parent counts no padded rows: the metric is silent there
    ctx["status_close"] = _status(**{"moe.expert_tokens_max": 125,
                                     "moe.expert_tokens_mean": 84})
    assert read("laguna.expert_pad_share") is None


def test_dry_run_of_the_cell_is_correct_and_its_fp8_control_is_further_off(tmp_path):
    """Three layers at the published widths on the CPU (full dense, sliding
    and full sparse), 32 of the 256 experts held, the whole vocabulary,
    texts cut to 32 tokens (`costs.dry_cut`).  The rehearsal makes every
    file 64 documents where the cell's is 12, so it runs in a copy whose
    configuration dispatches 64, as the MiMo cell's test does.
    `--seconds 0.5` makes the backlog two files."""
    copy = tmp_path / "repo"
    shutil.copytree(ROOT, copy, ignore=shutil.ignore_patterns(
        ".git", ".scratch", "chiprun_out", ".chipbench", ".jax_cache", "__pycache__"))
    path = copy / "chipbench" / "configs" / "laguna-xs2-pp8-docstore.json"
    config = json.loads(path.read_text())
    config["env"]["PATHWAY_INGEST_CHUNK"] = "64"
    path.write_text(json.dumps(config))
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed",
         str(2**31 + 44), "--seconds", "0.5", "--dry", "--trace", "1", "--control"],
        cwd=copy, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        timeout=1500, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["device"]["platform"] == "cpu"
    compared = line["compared"]
    assert compared["retrievable_missing"]["value"] == 0
    control = line["control"]["encoder_fp8.index_bf16"]
    assert control["score_gap"] > 2 * compared["score_gap"]["value"], (control, compared)
    metrics = line["metrics"]
    if "laguna.expert_pad_share" in metrics:
        assert 0.0 < metrics["laguna.expert_pad_share"]["value"] < 100.0
        assert 1.0 <= metrics["laguna.expert_load_skew"]["value"] < 32.0
    assert not any("roofline" in name or "mfu" in name for name in metrics)
    assert metrics["compile.in_window.ingest"]["value"] == 0

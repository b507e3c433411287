"""The BERT-style encoder's cost arithmetic: against hand-worked numbers,
and against what it gave before it became the architecture's own file
(PR 29), as integers."""

import json
import os
import types

import pytest

from chipbench import spec
from chipbench.architectures.bert_encoder import costs
from chipbench.readers import filled_mem_gb

MINILM = {"hidden": 384, "mlp_dim": 1536, "layers": 6}
E5 = {"hidden": 1024, "mlp_dim": 4096, "layers": 24}


def test_flops_minilm_74_tokens():
    # per token and layer: 2*(4*384^2 + 2*384*1536) + 4*74*384
    #   = 2*1,769,472 + 113,664 = 3,652,608; six layers; 74 tokens
    assert costs.flops(MINILM, 74) == 74 * 6 * 3_652_608 == 1_621_757_952


def test_flops_e5_352_tokens():
    # 2*(4*1024^2 + 2*1024*4096) + 4*352*1024 = 25,165,824 + 1,441,792
    assert costs.flops(E5, 352) == 352 * 24 * 26_607_616 == 224_781_139_968


def test_weight_and_activation_bytes():
    # per layer 4h^2 + 2h*ffn + 9h + ffn parameters, bf16
    assert costs.weight_bytes(MINILM) == 2 * 6 * 1_774_464
    assert costs.weight_bytes(E5) == 2 * 24 * 12_596_224 == 604_618_752
    assert costs.activation_bytes(E5, 352) == 2 * 2 * 352 * 1024 * 24


def test_dry_cut_keeps_every_width():
    model = {"hidden": 1024, "mlp_dim": 4096, "layers": 24, "heads": 16}
    assert costs.dry_cut(model) == dict(model, layers=2) and model["layers"] == 24


# -- pinned at d241e38, the commit before these functions moved here: what
# costs.encoder_flops, encoder_weight_bytes, encoder_activation_bytes and
# encoder_layer_params gave for the two configurations' files, and the parameter
# count readers/filled_mem_gb.py multiplied by 4 bytes
TOKENS = (16, 64, 254, 350, 512)
PINNED = {
    "minilm-l6-docstore": {
        "flops": (342097920, 1396703232, 5987930112, 8560742400, 13287555072),
        "weight_bytes": 21293568,
        "activation_bytes": (147456, 589824, 2340864, 3225600, 4718592),
        "layer_params": 10646784,
        "filled_params": 22564608,
        "row_bytes": 4 * 384 + 1,
    },
    "e5-large-docstore": {
        "flops": (9688842240, 39057358848, 159753043968, 223435161600, 335007449088),
        "weight_bytes": 604618752,
        "activation_bytes": (1572864, 6291456, 24969216, 34406400, 50331648),
        "layer_params": 302309376,
        "filled_params": 334090240,
        "row_bytes": 4 * 1024 + 1,
    },
}


@pytest.mark.parametrize("config", sorted(PINNED))
def test_costs_are_the_integers_they_were_before_the_move(config):
    with open(os.path.join(spec.HERE, "configs", config + ".json")) as f:
        file = json.load(f)
    arch, model, pinned = spec.architecture(file), file["model"], PINNED[config]
    assert arch.costs is costs
    assert tuple(costs.flops(model, t) for t in TOKENS) == pinned["flops"]
    assert all(isinstance(costs.flops(model, t), float) for t in TOKENS)
    assert costs.weight_bytes(model) == pinned["weight_bytes"]
    assert tuple(costs.activation_bytes(model, t) for t in TOKENS) == pinned["activation_bytes"]
    assert costs.layer_params(model) == pinned["layer_params"]
    assert costs.resident_param_bytes(model) == 4 * pinned["filled_params"]
    assert costs.embed_dim(model) == model["hidden"]
    # the reader, through the cell's architecture: rows x (4h + 1) a chip + 4 x params
    for rows, chips in ((0, 1), (12288, 1), (4096 + 40960, 4)):
        ctx = {
            "status_close": {"device_pipeline": {"rows": rows}}, "arch": arch,
            "cell": types.SimpleNamespace(config=file, chips=chips),
        }
        was = (rows * pinned["row_bytes"] / chips + 4 * pinned["filled_params"]) / 1e9
        assert filled_mem_gb.read(ctx) == was


# -- and the five readers that used them, on a hand-made traced run: the floats
# they gave at d241e38, to the last bit, now through ctx["arch"]
READERS_PINNED = {
    "minilm-l6.ingest-passages": {
        "encoder_roofline": 0.0705532559302643, "ingest_mfu": 0.024651137614188735,
        "filled_mem_gb": 0.109232697, "search_roofline": 9.019702790162219,
        "serve_mfu": 1.051303971521546,
    },
    "e5-large.ingest-chunks": {
        "encoder_roofline": 1.9219132947268334, "ingest_mfu": 0.6715118740611828,
        "filled_mem_gb": 1.386938425, "search_roofline": 21.636816312639304,
        "serve_mfu": 3.6495834744210875,
    },
}


@pytest.mark.parametrize("workload", sorted(READERS_PINNED))
def test_readers_give_the_floats_they_gave_before_the_move(workload):
    from chipbench import costs as shared
    from chipbench.readers import (
        encoder_roofline, ingest_mfu, search_roofline, serve_mfu,
    )

    cell = spec.cell(workload)
    ctx = {
        "cell": cell, "arch": cell.arch, "device": {"kind": "TPU v5 lite"},
        "peaks": shared.peaks("TPU v5 lite"),
        "trace": {
            "programs": {"jit__fwd_packed(1)": 7.25, "jit_fused(2)": 3.5, "other": 1.0},
            "program_runs": {"jit__fwd_packed(1)": 16, "jit_fused(2)": 40, "other": 3},
        },
        "status_close": {"device_pipeline": {"rows": 12345}},
        "window_s": 20.75, "docs_in_window": 64 * 5 + 17, "docs_per_file": 64,
        "tokens_per_file": [16 + (37 * i) % 239 for i in range(64)],
        "queries_answered": 11987, "query_tokens": [8 + i % 19 for i in range(11987)],
    }
    assert {
        "encoder_roofline": encoder_roofline.read(ctx, ["_fwd_packed"]),
        "ingest_mfu": ingest_mfu.read(ctx),
        "filled_mem_gb": filled_mem_gb.read(ctx),
        "search_roofline": search_roofline.read(ctx, ["jit_fused"]),
        "serve_mfu": serve_mfu.read(ctx),
    } == READERS_PINNED[workload]

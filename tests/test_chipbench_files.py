"""The tier-1 guard over the benchmark's files (the driver's tests do not
run chipbench/tests/): every configuration names an architecture that has
its three files, and everything BENCHMARK.json names resolves."""

import glob
import importlib
import json
import os

import pytest

from chipbench import spec

CONFIGS = sorted(glob.glob(os.path.join(spec.HERE, "configs", "*.json")))
BENCH = spec.benchmark()


@pytest.mark.parametrize("path", CONFIGS, ids=[os.path.basename(p) for p in CONFIGS])
def test_a_configuration_names_an_architecture_with_its_three_files(path):
    config = spec._load(path)
    arch = spec.architecture(config)  # raises UnknownArchitecture otherwise
    for part in spec.ARCHITECTURE_FILES:
        assert os.path.isfile(
            os.path.join(spec.HERE, "architectures", arch.name, part + ".py")
        )
    for key in ("model", "store", "limits", "source", "reduced", "assumed",
                "departures", "guarantees", "init", "tokenizer"):
        assert key in config, (path, key)
    assert set(config["limits"]) == {"retrievable_missing", "score_gap", "rank_gap"}
    # the costs read the file's own model group without the program
    costs = arch.costs
    model = config["model"]
    assert costs.flops(model, 100) > 0 and costs.embed_dim(model) > 0
    assert costs.resident_param_bytes(model) > costs.weight_bytes(model) > 0
    cut = costs.dry_cut(model)
    assert costs.embed_dim(cut) == costs.embed_dim(model)  # never a width


@pytest.mark.parametrize("entry", BENCH["configs"], ids=[c["name"] for c in BENCH["configs"]])
def test_a_configuration_entry_points_at_its_file(entry):
    path = os.path.join(spec.ROOT, entry["file"])
    assert path in CONFIGS
    config = spec._load(path)
    assert config["name"] == entry["name"]
    assert entry["source"] == config["source"]
    assert set(entry["reduced"]) == set(config["reduced"])


@pytest.mark.parametrize("entry", BENCH["workloads"], ids=[w["name"] for w in BENCH["workloads"]])
def test_a_cell_resolves(entry):
    cell = spec.cell(entry["name"])
    assert cell.chips == entry["chips"] and cell.arch.name in spec.architectures()
    assert cell.traffic["kind"] in ("ingest_backlog", "retrieve_open_loop")
    assert str(cell.chips) in cell.traffic["backlog_docs_per_s"]
    assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for metric in cell.per_layer:
        module = importlib.import_module(f"chipbench.readers.{metric.reader}")
        assert callable(module.read)


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=[m["name"] for m in BENCH["per_layer"]])
def test_a_per_layer_metric_has_its_file_and_says_the_same(entry):
    meta = spec._load(os.path.join(spec.HERE, "metrics", entry["name"] + ".json"))
    for key in ("unit", "source", "better", "layer", "moves"):
        assert meta[key] == entry[key], (entry["name"], key)
    assert meta.get("workloads") == entry.get("workloads")
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(entry.get("workloads", [])) <= cells
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}


def test_the_catalogued_configuration_states_the_published_sizes_beside_its_cut():
    """axk1-ep16-docstore: every published number at the top level as
    published, the sizes as run in `model`, no width changed between."""
    config = spec._load(os.path.join(spec.HERE, "configs", "axk1-ep16-docstore.json"))
    model = config["model"]
    published = {k: v for k, v in config.items()
                 if isinstance(v, (int, float)) and not isinstance(v, bool)}
    assert published["num_hidden_layers"] == 61 and published["n_routed_experts"] == 192
    assert published["vocab_size"] == 163840 and published["hidden_size"] == 7168
    for key, value in published.items():
        if key in model and key != "ep_size":  # the file says why ep_size differs
            assert model[key] == value, key
    assert config["rope_scaling"] == model["rope_scaling"]
    assert (model["layers"], model["experts_held"], model["vocab_held"]) == (6, 12, 20480)
    assert model["n_routed_experts"] // model["ep_size"] == model["experts_held"]
    assert model["vocab_size"] // 8 == model["vocab_held"]
    assert model["param_dtype"] == "bfloat16"
    assert json.dumps(config).count("7168") >= 2


def test_the_byte_level_configuration_states_the_published_sizes_beside_its_cut():
    """evabyte-pp2-docstore: every published number at the top level as
    published, the sizes as run in `model`, no width changed between; the
    cut is depth, and the deployment says where the other layers lie."""
    config = spec._load(os.path.join(spec.HERE, "configs", "evabyte-pp2-docstore.json"))
    model = config["model"]
    published = {k: v for k, v in config.items()
                 if isinstance(v, (int, float)) and not isinstance(v, bool)}
    assert published == {
        "chunk_size": 16, "hidden_size": 4096, "init_std": 0.01275,
        "intermediate_size": 11008, "max_position_embeddings": 32768,
        "max_seq_length": 32768, "num_attention_heads": 32, "num_hidden_layers": 32,
        "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
        "rope_theta": 100000, "vocab_size": 320, "window_size": 2048,
    }
    for key, value in published.items():
        if key in model:
            assert model[key] == value, key
    assert (model["layers"], model["pp_size"]) == (16, 2)
    assert model["layers"] * model["pp_size"] == model["num_hidden_layers"]
    assert set(config["reduced"]) == {"layers", "filled_rows"}
    assert model["max_len"] == config["store"]["max_len"] == 8192
    assert model["param_dtype"] == "bfloat16" and config["env"] == {"PATHWAY_INGEST_CHUNK": "2"}
    # the cell: two pages a file, one file a dispatch, pages of two to four windows
    traffic = spec.cell("evabyte-pp2.ingest-pages-2").traffic
    assert traffic["docs_per_file"] == int(config["env"]["PATHWAY_INGEST_CHUNK"]) == 2
    costs = spec.architecture(config).costs
    for words in (525, 900):
        assert 1 < costs.byte_tokens(model, words + 2) / model["window_size"] < 4

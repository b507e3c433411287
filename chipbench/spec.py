"""Finds a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own:

  configuration <c>   chipbench/configs/<c>.json      (its `file` entry),
                      which names its architecture
  architecture <a>    chipbench/architectures/<a>/program.py, reference.py
                      and costs.py: how the program is built for a
                      configuration of that architecture, its plain
                      reference, and what it costs (chipbench/README.md)
  traffic mix <t>     chipbench/traffic/<t>.json
  per-layer metric m  chipbench/metrics/<m>.json, which names a reader
                      module chipbench/readers/<reader>.py

so a later PR adds a cell, a metric or a model by adding files and entries,
never by editing one that is there.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    moves: str
    reader: str
    args: dict

    def read(self, ctx: dict):
        """The reader's `read(ctx, **args)`: a number, or None where it
        finds nothing to read (the harness then leaves the metric out)."""
        module = importlib.import_module(f"chipbench.readers.{self.reader}")
        return module.read(ctx, **self.args)


ARCHITECTURE_FILES = ("program", "reference", "costs")


class UnknownArchitecture(LookupError):
    pass


@dataclasses.dataclass(frozen=True)
class Architecture:
    """The files of one architecture, each imported when first asked for:
    `program` alone imports pathway_tpu, and a run imports it only once
    its host-only work has started."""

    name: str

    def _module(self, part: str):
        return importlib.import_module(f"chipbench.architectures.{self.name}.{part}")

    @property
    def program(self):
        return self._module("program")

    @property
    def reference(self):
        return self._module("reference")

    @property
    def costs(self):
        return self._module("costs")


def architectures() -> list:
    """Names of the architectures there are: the directories under
    chipbench/architectures/ that hold the three files."""
    base = os.path.join(HERE, "architectures")
    return sorted(
        d for d in os.listdir(base)
        if all(os.path.isfile(os.path.join(base, d, part + ".py"))
               for part in ARCHITECTURE_FILES)
    )


def architecture(config: dict) -> Architecture:
    """The architecture a configuration's file names.  A missing or unknown
    name is an error, never a default."""
    name, known = config.get("architecture"), architectures()
    if name not in known:
        raise UnknownArchitecture(
            f"configuration {config.get('name')!r} names the architecture "
            f"{name!r}; chipbench/architectures/ has {known}"
        )
    return Architecture(name)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    arch: Architecture
    traffic: dict
    end_to_end: tuple  # names of the end-to-end metrics this cell reports
    per_layer: tuple  # Metric objects this cell reports


def _applies(metric: dict, workload: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or workload in cells


def _read_in(metric: dict, workload: str, end_to_end: tuple) -> bool:
    """A per-layer metric with a `workloads` list is read in those cells;
    one without is read in every cell that reports the end-to-end metric
    it moves, the cells that later PRs add too."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric["moves"] in end_to_end


def cell(workload: str) -> Cell:
    bench = benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise SystemExit(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{sorted(entries)}"
        )
    entry = entries[workload]
    config_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    end_to_end = tuple(
        m["name"] for m in bench["end_to_end"] if _applies(m, workload)
    )
    per_layer = []
    for m in bench["per_layer"]:
        if not _read_in(m, workload, end_to_end):
            continue
        meta = _load(os.path.join(HERE, "metrics", m["name"] + ".json"))
        per_layer.append(
            Metric(
                name=m["name"],
                moves=m["moves"],
                reader=meta["reader"],
                args=meta.get("args", {}),
            )
        )
    config = _load(os.path.join(ROOT, config_entry["file"]))
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config=config,
        arch=architecture(config),
        traffic=traffic_file(entry["traffic"]),
        end_to_end=end_to_end,
        per_layer=tuple(per_layer),
    )


def traffic_file(name: str) -> dict:
    return _load(os.path.join(HERE, "traffic", name + ".json"))


def units() -> dict:
    bench = benchmark()
    return {
        m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]
    }

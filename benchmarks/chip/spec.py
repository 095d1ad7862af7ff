"""The benchmark's definition, read from files by name.

`BENCHMARK.json` at the checkout root names the cells, metrics and
configurations. Each configuration is `configs/<config>.json`, each
traffic mix `traffic/<traffic>.json`, each cell's correctness limits
`limits/<cell>.json`, each per-layer metric a reader
`layer_metrics/<metric>.py`, each model family's operation count
`flops/<family>.py`, and the chips' peaks `peaks.json`. A cell, a
configuration or a metric is added by adding files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return _load(root / "BENCHMARK.json")


def peaks(device_kind: str) -> dict:
    """The peak FLOP/s, bytes/s and memory of one chip of this kind."""
    table = _load(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple     # metric entries this cell reports with --trace 0
    per_layer: tuple      # ... and with --trace 1

    def flops_module(self):
        return importlib.import_module(
            f"benchmarks.chip.flops.{self.config['family']}")


def reader(metric: str):
    """The `read(ctx)` of the per-layer metric `metric`."""
    return importlib.import_module(
        f"benchmarks.chip.layer_metrics.{metric}").read


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                       f"{[w['name'] for w in bench['workloads']]})")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    return Cell(
        name=name, chips=entry["chips"],
        config=_load(ROOT / cfg_entry["file"]),
        traffic=_load(HERE / "traffic" / f"{entry['traffic']}.json"),
        limits=_load(HERE / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))

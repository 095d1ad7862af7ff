"""The benchmark's definition, read from files by name.

`BENCHMARK.json` at the checkout root names the cells, metrics and
configurations. Each configuration is `configs/<config>.json`, each
traffic mix `traffic/<traffic>.json`, each cell's correctness limits
`limits/<cell>.json`, each per-layer metric a reader
`layer_metrics/<metric>.py`, and the chips' peaks `peaks.json`. The
configuration's `family` names the model: its data, weights and plain
networks `families/<family>/reference.py`, the program's model
`families/<family>/program.py` and its operation count
`flops/<family>.py` (see `families/__init__.py`). A cell, a
configuration, a family or a metric is added by adding files and
entries only.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
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
        return family_module(self.config["family"], "flops")


def _family_modules(family: str) -> dict:
    return {"reference": f"benchmarks.chip.families.{family}.reference",
            "program": f"benchmarks.chip.families.{family}.program",
            "flops": f"benchmarks.chip.flops.{family}"}


def family_module(family: str, part: str):
    """The `part` ("reference", "program" or "flops") of model family
    `family`."""
    return importlib.import_module(_family_modules(family)[part])


def _found(module: str) -> bool:
    if module in sys.modules:
        return True
    try:
        return importlib.util.find_spec(module) is not None
    except ModuleNotFoundError:
        return False


def _check_family(config: str, family: str):
    """Raise unless every part of `family` is there; the program's part
    is looked for, not imported."""
    wanted = _family_modules(family).values()
    missing = [m for m in wanted if not _found(m)]
    if missing:
        raise KeyError(
            f"configuration {config!r}: model family {family!r} needs "
            + ", ".join(m.replace(".", "/") + ".py" for m in wanted)
            + "; not found: "
            + ", ".join(m.replace(".", "/") + ".py" for m in missing))


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
    config = _load(ROOT / cfg_entry["file"])
    _check_family(cfg_entry["name"], config.get("family"))
    return Cell(
        name=name, chips=entry["chips"], config=config,
        traffic=_load(HERE / "traffic" / f"{entry['traffic']}.json"),
        limits=_load(HERE / "limits" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))

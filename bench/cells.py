"""Find a cell's pieces by the names in ``BENCHMARK.json``.

Nothing here is specific to one cell.  A configuration is the JSON file
that ``configs[].file`` names, a traffic mix is
``bench/traffic/<traffic>.json``, a DB generator is the module
``bench/gen/<generator>.py`` that the configuration names, and a
per-layer metric is read by ``bench/metrics/<metric>.py``.  A later cell
adds files and entries and edits none of this.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _named(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    wl = _named(spec["workloads"], name, "workload")
    cfg_entry = _named(spec["configs"], wl["config"], "config")
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{wl['traffic']}.json").read_text())
    return Cell(name=name, chips=int(wl["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def generator(name: str):
    return importlib.import_module(f"bench.gen.{name}")


def metric_reader(name: str):
    """The module that reads metric ``name``: ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

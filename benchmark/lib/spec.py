"""Finding a cell's files by the names ``BENCHMARK.json`` gives them.

A cell names a configuration (``benchmark/configs/<config>.json``) and a
traffic mix (``benchmark/traffic/<traffic>.json``).  The configuration's
``suite["family"]`` names the module that builds its providers and holds
its reference (``benchmark/suites/<family>.py``); the traffic's ``kind``
names the module that sends, times and checks one request of that kind
(``benchmark/kinds/<kind>.py``).  Each metric a cell reports is a reader of
its own (``benchmark/metrics/<metric>.py``, a function
``read(run) -> float | None``).  Adding a cell, a configuration, a kind of
traffic, a suite family or a metric adds files and entries; nothing here
changes.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    source: str
    end_to_end: bool


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(entry: dict, cell: str, e2e_names: set[str]) -> bool:
    """A metric with a ``workloads`` list is reported in those cells; a
    per-layer one without it wherever the end-to-end metric it moves is."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return "moves" not in entry or entry["moves"] in e2e_names


def cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    doc = benchmark(root)
    entry = next((w for w in doc["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in doc["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    config = json.loads((BENCH / "configs" / f"{entry['config']}.json")
                        .read_text())
    traffic = json.loads((BENCH / "traffic" / f"{entry['traffic']}.json")
                         .read_text())
    e2e = tuple(Metric(m["name"], m["unit"], m["source"], True)
                for m in doc["end_to_end"] if _reports(m, name, set()))
    e2e_names = {m.name for m in e2e}
    layer = tuple(Metric(m["name"], m["unit"], m["source"], False)
                  for m in doc["per_layer"] if _reports(m, name, e2e_names))
    return Cell(name, int(entry["chips"]), config, traffic, e2e, layer)


_MODULES: dict[Path, object] = {}


def _module(folder: str, name: str):
    """``benchmark/<folder>/<name>.py``, loaded once per process."""
    path = BENCH / folder / f"{name}.py"
    if path not in _MODULES:
        if not path.is_file():
            raise KeyError(f"no {folder} module {name!r} ({path})")
        mod_spec = importlib.util.spec_from_file_location(
            f"benchmark_{folder}_" + name.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def reader(metric: str):
    """The ``read(run)`` function of ``benchmark/metrics/<metric>.py``."""
    return _module("metrics", metric).read


def kind(name: str):
    """The traffic kind ``benchmark/kinds/<name>.py``."""
    return _module("kinds", name)


def suite(config: dict):
    """The suite family of a configuration: ``benchmark/suites/<family>.py``."""
    return _module("suites", config["suite"]["family"])

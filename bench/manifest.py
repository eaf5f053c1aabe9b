"""Find a cell's files by the names in ``BENCHMARK.json``.

Every configuration, traffic mix, cell and per-layer metric lives in a
file of its own, found by name, so a later change adds a cell by adding
files and entries and edits none that is there:

  configs      the ``file`` of the configuration's entry
  traffic      bench/traffic/<traffic>.json
  cells        bench/workloads/<cell>.json      (correctness limits)
  metrics      bench/metrics/<metric>.py        (``read(ctx)``)
  drivers      bench/drivers/<traffic kind>.py  (``run(ctx)``)
  references   bench/reference/<name>.py
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def load(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(man: dict, name: str) -> dict:
    return _named(man["workloads"], name, "workload")


def config_entry(man: dict, name: str) -> dict:
    return _named(man["configs"], name, "configuration")


def read_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def config(man: dict, name: str, root: Path = ROOT) -> dict:
    return read_json(Path(root) / config_entry(man, name)["file"])


def traffic_path(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "bench" / "traffic" / f"{name}.json"


def cell_path(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "bench" / "workloads" / f"{name}.json"


def metric_path(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "bench" / "metrics" / f"{name}.py"


def driver_path(kind: str, root: Path = ROOT) -> Path:
    return Path(root) / "bench" / "drivers" / f"{kind}.py"


def reference_path(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "bench" / "reference" / f"{name}.py"


def metrics_for(man: dict, cell_name: str, section: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
    without a ``workloads`` key, and those that list the cell."""
    return [m for m in man[section]
            if cell_name in m.get("workloads", [cell_name])]


def load_module(path: Path, name: str | None = None):
    """Import one file by path (names may hold characters a module name
    cannot)."""
    path = Path(path)
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_name = name or "bench_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(
            path.parents[1])))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

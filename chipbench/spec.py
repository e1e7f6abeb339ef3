"""BENCHMARK.json and the files it names, found by name.

A cell is one entry of ``workloads``: a configuration file under
``configs/``, a traffic mix under ``traffic/<name>.json``, and the
comparison that decides ``correct`` under ``checks/<cell>.json``. A
per-layer metric is read by ``metrics/<name>.py``; a model family's work
counts and plain reference are ``work/<family>.py`` and
``reference/<family>.py``. Adding a cell, a mix or a metric adds files
and entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    conf: dict          # configuration file, as run
    traffic: dict       # traffic mix parameters
    check: dict         # what decides `correct`, with its limits
    end_to_end: list    # metric entries of BENCHMARK.json for this cell
    per_layer: list


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, spec: dict | None = None) -> Cell:
    """The cell called ``name`` with every file it names loaded."""
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"chipbench: no workload {name!r}; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    conf_file = {c["name"]: c["file"] for c in spec["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        conf=load_json(ROOT / conf_file),
        traffic=load_json(HERE / "traffic" / f"{w['traffic']}.json"),
        check=load_json(HERE / "checks" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under chipbench, imported by path (metric
    names carry dots, so they are not importable as module names)."""
    path = HERE / kind / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod

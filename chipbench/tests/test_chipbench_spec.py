"""BENCHMARK.json keeps to its contract, and every cell, metric and family
it names resolves to its files by name."""

import importlib
import json
import re
from collections import Counter

import pytest

from chipbench import judge
from chipbench import traffic as tr
from chipbench.spec import HERE, ROOT, load_json, resolve

SPEC = load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "chipbench/run.py"]
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("entry", SPEC["configs"] + SPEC["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_units_and_single_line_texts(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")


def test_names_are_unique():
    for group in (SPEC["configs"], SPEC["workloads"], METRICS):
        counts = Counter(e["name"] for e in group)
        assert max(counts.values()) == 1, counts
    pairs = Counter((w["config"], w["traffic"]) for w in SPEC["workloads"])
    assert max(pairs.values()) == 1


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = resolve(name, SPEC)
    assert cell.conf["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == name)
    assert cell.chips in (1, 4)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
    fam = cell.conf["family"]
    importlib.import_module(f"chipbench.reference.{fam}")
    importlib.import_module(f"chipbench.work.{fam}")
    assert cell.check["compare"]
    for number, spec in cell.check["compare"].items():
        assert number in judge.NUMBERS and spec["limit"] > 0
    assert cell.traffic["new_tokens"] >= 1


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_under_paths_states_its_cut(cfg):
    conf = load_json(ROOT / cfg["file"])
    assert cfg["file"].startswith("chipbench/configs/")
    assert conf["reduced"] == cfg["reduced"]
    assert conf["assumed"] and conf["deployment"] and conf["departures"]


def test_every_metric_workload_is_a_cell():
    for m in METRICS:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"], set()).add(m["name"])
    assert all(NAME.match(n) for names in layers.values() for n in names)


@pytest.mark.parametrize("name", sorted(p.stem for p in
                                        (HERE / "traffic").glob("*.json")))
def test_every_seed_serves_the_same_lengths(name):
    mix = load_json(HERE / "traffic" / f"{name}.json")
    cycle = sum(int(c) for _, c in mix["prompt_tokens"])
    runs = []
    for seed in (0, 7, 2**33 + 5):
        it = tr.lengths(mix, seed)
        runs.append([next(it) for _ in range(3 * cycle)])
    for lens in runs:
        for k in range(3):
            assert Counter(lens[k * cycle:(k + 1) * cycle]) == Counter(
                {int(n): int(c) for n, c in mix["prompt_tokens"]})
    again = tr.lengths(mix, 0)
    assert runs[0] == [next(again) for _ in range(3 * cycle)]

"""``BENCHMARK.json`` against the contract's character rules, and the data
files every name in it leads to."""

import json
import os
import re

import pytest

from benchmark.harness.cell import REPO, ROOT, BenchmarkError, Cell, load_spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = load_spec()
STAGED = load_spec(staged=True)
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 4)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in SPEC["end_to_end"])


@pytest.mark.parametrize(
    "entry", STAGED["end_to_end"] + STAGED["per_layer"] + STAGED["workloads"]
    + SPEC["configs"], ids=lambda e: e["name"])
def test_names_units_and_text(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                and "\t" not in entry[key]
    if "bound" in entry:
        assert 0.01 <= entry["bound"] <= 0.1


def test_names_are_unique():
    for group in (METRICS, SPEC["workloads"], SPEC["configs"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("m", STAGED["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_what_its_cells_report(m):
    moved = next(e for e in STAGED["end_to_end"] if e["name"] == m["moves"])
    cells = {w["name"] for w in STAGED["workloads"]}
    reporting = set(moved.get("workloads", cells))
    assert set(m["workloads"]) <= reporting <= cells
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")


@pytest.mark.parametrize("w", STAGED["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files_and_readers(w):
    cell = Cell(w["name"], STAGED)
    assert cell.traffic["kind"] in ("train", "serve_open", "serve_closed")
    assert cell.config["reduced"] == next(
        c["reduced"] for c in SPEC["configs"] if c["name"] == w["config"])
    for key in ("source", "changed", "assumed", "reduced", "tolerances",
                "deployment"):
        assert key in cell.config
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        read, args = cell.reader(m["name"])
        assert callable(read) and isinstance(args, dict)
    for d in SPEC["paths"]:
        assert os.path.isdir(os.path.join(REPO, d))
    assert os.path.dirname(cell.config["name"]) == ""


def test_a_missing_file_or_reader_fails_loudly(tmp_path, monkeypatch):
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(BenchmarkError, match="no-such-mix"):
        Cell(spec["workloads"][0]["name"], spec)
    with pytest.raises(BenchmarkError, match="no workload"):
        Cell("no.such.cell", SPEC)
    cell = Cell(SPEC["workloads"][0]["name"], SPEC)
    with pytest.raises(BenchmarkError, match="missing benchmark file"):
        cell.reader("no.such.metric")
    import benchmark.harness.cell as cellmod
    monkeypatch.setattr(cellmod, "ROOT", str(tmp_path))
    os.makedirs(tmp_path / "metrics")
    (tmp_path / "metrics" / "m1.json").write_text("{}")
    (tmp_path / "metrics" / "m2.json").write_text('{"reader": "nope"}')
    with pytest.raises(BenchmarkError, match="names no reader"):
        cell.reader("m1")
    with pytest.raises(BenchmarkError, match="no readers/nope.py"):
        cell.reader("m2")
    with pytest.raises(BenchmarkError, match="not in benchmark/peaks.json"):
        cell.peak("TPU v99")


def test_files_under_paths_are_named_from_name_characters():
    for d in SPEC["paths"]:
        for root, dirs, files in os.walk(os.path.join(REPO, d)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(root, f)
    assert ROOT == os.path.join(REPO, "benchmark")

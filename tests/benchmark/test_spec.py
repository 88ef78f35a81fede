"""``BENCHMARK.json`` against the contract's character rules, and the data
files every name in it leads to."""

import json
import os
import re

import pytest

from benchmark.harness.cell import (REPO, ROOT, BenchmarkError, Cell,
                                    load_json, load_spec)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = load_spec()
STAGED = load_spec(staged=True)
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
#: one case a (per-layer metric, cell that lists it): a cell joins a metric
#: by one more name in its ``workloads``, and is tested like an entry of its own
PAIRS = [(m, cell) for m in STAGED["per_layer"] for cell in m["workloads"]]


def pair_id(pair):
    return f"{pair[0]['name']}-{pair[1]}"


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 4)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in SPEC["end_to_end"])


def names_units_and_text(entry):
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


@pytest.mark.parametrize(
    "entry", STAGED["end_to_end"] + STAGED["workloads"] + SPEC["configs"],
    ids=lambda e: e["name"])
def test_names_units_and_text(entry):
    names_units_and_text(entry)


@pytest.mark.parametrize("pair", PAIRS, ids=pair_id)
def test_per_layer_names_units_and_text(pair):
    m, cell = pair
    names_units_and_text(m)
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert cell in {w["name"] for w in STAGED["workloads"]}
    assert m["workloads"].count(cell) == 1
    assert "reader" in load_json("metrics", m["name"] + ".json")


def test_names_are_unique():
    for group in (METRICS, SPEC["workloads"], SPEC["configs"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("pair", PAIRS, ids=pair_id)
def test_per_layer_metric_moves_what_its_cells_report(pair):
    m, cell = pair
    moved = next(e for e in STAGED["end_to_end"] if e["name"] == m["moves"])
    cells = {w["name"] for w in STAGED["workloads"]}
    assert cell in set(moved.get("workloads", cells)) <= cells
    assert m in Cell(cell, STAGED).per_layer
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")


def test_per_layer_has_one_entry_a_metric_and_room_left():
    """A cell joins a metric it shares by its name in ``workloads``; an entry
    of its own is for a reader or arguments of its own. So no two entries
    agree in reader, arguments, layer and what they move: that rule keeps
    the list short. The one ceiling held is the contract's, here alone, so
    that a configuration can bring its entries without editing a test."""
    assert len(SPEC["per_layer"]) <= 128
    seen = {}
    for m in STAGED["per_layer"]:
        entry = load_json("metrics", m["name"] + ".json")
        key = (entry["reader"], json.dumps(entry.get("args", {}), sort_keys=True),
               m["layer"], m["moves"])
        assert key not in seen, f"{m['name']} repeats {seen[key]}"
        seen[key] = m["name"]
    # and no file under metrics/ without an entry
    listed = {m["name"] + ".json" for m in STAGED["per_layer"]}
    assert set(os.listdir(os.path.join(ROOT, "metrics"))) == listed


def test_the_names_claims_are_bounded_by_are_as_they_were():
    names = {m["name"]: m["workloads"] for m in SPEC["per_layer"]}
    for name, cells in (
            ("kernel.mla_decode_roofline_share.longdoc", ["serve-longdoc"]),
            ("kernel.mla_decode_roofline_share.longout", ["serve-longout"]),
            ("kernel.paged_roofline_share.chat", ["serve-chat"]),
            ("kernel.paged_roofline_share.doc16k", ["serve-doc16k"]),
            ("kernel.linear_decode_roofline_share.doc16k", ["serve-doc16k"]),
            ("kernel.flash_roofline_share", ["train-seq1024",
                                             "zero3-train-4chip"]),
            ("model.mfu", ["train-seq1024", "zero3-train-4chip"])):
        assert [w["traffic"] for w in SPEC["workloads"]
                if w["name"] in names[name]] == cells
    assert sorted(n for n in names if "roofline_share" in n or "mfu" in n) \
        == sorted(["kernel.mla_decode_roofline_share.longdoc",
                   "kernel.mla_decode_roofline_share.longout",
                   "kernel.paged_roofline_share.chat",
                   "kernel.paged_roofline_share.doc16k",
                   "kernel.linear_decode_roofline_share.doc16k",
                   "kernel.flash_roofline_share", "model.mfu"])


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

"""``BENCHMARK.json`` against the contract's character rules, and the data
files every name in it leads to. The rules themselves are functions of
``(spec, root)`` in ``rules.py``: here they meet today's spec, in
``test_arrival.py`` a copy that a configuration has been added to."""

import json
import os

import pytest

from benchmark.harness.cell import (REPO, ROOT, BenchmarkError, Cell,
                                    load_spec)
from tests.benchmark import rules

SPEC = load_spec()
STAGED = load_spec(staged=True)
#: one case a (per-layer metric, cell that lists it)
PAIRS = rules.pairs(STAGED)


def pair_id(pair):
    return f"{pair[0]['name']}-{pair[1]}"


def test_top_level_keys_and_limits():
    rules.top_level(SPEC, ROOT)


@pytest.mark.parametrize(
    "entry", STAGED["end_to_end"] + STAGED["workloads"] + SPEC["configs"],
    ids=lambda e: e["name"])
def test_names_units_and_text(entry):
    rules.entry_text(entry)


@pytest.mark.parametrize("pair", PAIRS, ids=pair_id)
def test_per_layer_names_units_and_text(pair):
    rules.pair_text(STAGED, ROOT, *pair)


def test_names_are_unique():
    rules.unique_names(SPEC)


@pytest.mark.parametrize("pair", PAIRS, ids=pair_id)
def test_per_layer_metric_moves_what_its_cells_report(pair):
    rules.pair_moves(STAGED, ROOT, *pair)


def test_per_layer_has_one_entry_a_metric_and_room_left():
    rules.one_entry_a_metric(SPEC, ROOT)


def test_the_names_claims_are_bounded_by_are_as_they_were():
    """The seven shares of a roofline or of the peak keep their names and
    their cells (``rules.SHARES_OF_ONE_CELL``, ``rules.SHARES_OF_TRAINING``);
    an eighth obeys the naming rule, and every roofline share's reader names
    its ``KERNEL``, counts work by ``kernels/`` and spans by ``covered``."""
    rules.share_names(SPEC, ROOT)


@pytest.mark.parametrize("w", STAGED["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files_and_readers(w):
    rules.cell_files(STAGED, ROOT, w)


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
    rules.file_names(*(os.path.join(REPO, d) for d in SPEC["paths"]))
    assert ROOT == os.path.join(REPO, "benchmark")

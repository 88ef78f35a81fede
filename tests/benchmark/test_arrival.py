"""A rehearsed arrival: what a ``model_config`` PR adds (``benchmark/README.md``,
"Adding things, by adding files"), built into a temporary copy of the
benchmark's data directories and of the spec, and held to **the rules the
tests of this directory hold today's spec to** (``rules.py``): a made-up
configuration, its traffic mix, a fifth serving cell on one chip that joins
``itl_p50_ms`` and every entry all serving cells share, and two entries of its
own, one of them a share of a roofline with a reader and a ``kernels/``
function of its own. No existing file is edited and no existing entry changed
but for the appended name. Then the refusals: each rule fails the arrival that
breaks it. PR 59 closed this door in a test (a list of seven names) and PR 40
in another (two counts); a test that holds today's size again fails here."""

import filecmp
import json
import os
import shutil
import sys

import pytest

import benchmark.harness.cell as cellmod
from benchmark.harness.cell import ROOT, Cell, load_spec
from tests.benchmark import rules
from tests.benchmark import test_cut_trace as cut_trace

CELL = "made-up.serve-made-up"
SHARE = "kernel.made_up_roofline_share.x"
SCOPE = "model.made_up_ms"
DATA = ("configs", "traffic", "metrics")

CONFIG = {
    "name": "made-up", "source": "https://example.org/made-up/config.json",
    "architecture": "gpt2", "hidden_size": 256, "num_hidden_layers": 3,
    "changed": {}, "assumed": [], "reduced": ["num_hidden_layers"],
    "deployment": "one chip of a deployment nobody runs",
    "dtype": "bfloat16", "tolerances": {"serve": {}},
    "model": {"hidden_size": 256, "num_layers": 3, "num_heads": 4,
              "name": "made-up"},
}
TRAFFIC = {
    "kind": "serve_open", "what": "made up", "rate_rps": 1.0,
    "engine": {"max_seqs": 32, "block_size": 64},
    "rehearsal": {"engine": {"max_seqs": 4, "block_size": 16}},
}
KERNELS = '''"""Operations and bytes the made-up kernel needs."""


def dispatches(rows, layers, width, act_bytes=2):
    return layers * rows * 4 * width * width, layers * rows * 2 * width * act_bytes
'''
READER = '''"""The made-up kernel against its roofline."""

from benchmark.kernels import made_up
from benchmark.readers.covered import inside
from benchmark.readers.program_spans import spans
from benchmark.readers.trace_kernel_ms import kernel_seconds

KERNEL = "made_up"


def read(ctx, layers):
    trace, peak = ctx["trace"], ctx["peak"]
    found = inside(ctx, spans("engine.dispatch"))
    if not trace or peak is None or not found:
        return None
    secs = kernel_seconds(trace, KERNEL)
    if not secs:
        return None
    rows = sum(s.attrs.get("decode_rows", 0) for s in found)
    flops, nbytes = made_up.dispatches(
        rows, layers, ctx["cell"].config["model"]["hidden_size"])
    return 100.0 * max(flops / peak["bf16_flops_per_s"],
                       nbytes / peak["hbm_bytes_per_s"]) / secs
'''
#: the files the arrival adds, by path under the copy's ``benchmark/``
ADDED = {
    "configs/made-up.json": json.dumps(CONFIG, indent=1),
    "traffic/serve-made-up.json": json.dumps(TRAFFIC, indent=1),
    f"metrics/{SHARE}.json": json.dumps(
        {"reader": "made_up_roofline", "args": {"layers": 3}}),
    f"metrics/{SCOPE}.json": json.dumps(
        {"reader": "scope_ms", "args": {"scope": "made_up",
                                        "per": "dispatches"}}),
    "readers/made_up_roofline.py": READER,
    "kernels/made_up.py": KERNELS,
}


def write(root, path, text):
    os.makedirs(os.path.dirname(os.path.join(root, path)), exist_ok=True)
    with open(os.path.join(root, path), "w") as f:
        f.write(text)


def keep(spec, root):
    """The copy's ``BENCHMARK.json`` as ``spec`` stands now."""
    with open(os.path.join(os.path.dirname(root), "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)


@pytest.fixture
def arrival(tmp_path):
    """(spec, root): today's spec and data files with the arrival added."""
    root = str(tmp_path / "benchmark")
    for d in DATA:
        shutil.copytree(os.path.join(ROOT, d), os.path.join(root, d))
    for f in ("peaks.json", "staged.json"):
        shutil.copy(os.path.join(ROOT, f), root)
    for path, text in ADDED.items():
        write(root, path, text)
    spec = load_spec()
    _, serving = rules.kinds(spec, ROOT)
    spec["configs"].append({
        "name": "made-up", "source": CONFIG["source"],
        "file": "benchmark/configs/made-up.json",
        "reduced": CONFIG["reduced"], "why": "stands for the next configuration"})
    spec["workloads"].append({
        "name": CELL, "config": "made-up", "traffic": "serve-made-up",
        "chips": 1, "why": "stands for the next serving cell"})
    next(m for m in spec["end_to_end"]
         if m["name"] == "itl_p50_ms")["workloads"].append(CELL)
    for m in spec["per_layer"]:          # every entry all serving cells share
        if serving <= set(m["workloads"]):
            m["workloads"].append(CELL)
    kernels = rules.entered(spec)["kernel.flash_roofline_share"]["layer"]
    spec["per_layer"] += [
        {"name": SHARE, "unit": "%", "better": "higher",
         "source": "device_trace", "layer": kernels, "moves": "itl_p50_ms",
         "workloads": [CELL]},
        {"name": SCOPE, "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "Model", "moves": "itl_p50_ms",
         "workloads": [CELL]}]
    keep(spec, root)
    return spec, root


def test_the_arrival_adds_files_and_appends_names_and_edits_nothing(arrival):
    spec, root = arrival
    today = load_spec()
    for d in DATA:
        same = filecmp.dircmp(os.path.join(ROOT, d), os.path.join(root, d))
        assert not same.left_only and not same.diff_files
        assert set(same.right_only) == {
            os.path.basename(p) for p in ADDED if p.startswith(d + "/")}
    for key in ("command", "paths", "run_seconds"):
        assert spec[key] == today[key]
    joined = 0
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(spec[key]) >= len(today[key])
        for old, new in zip(today[key], spec[key]):     # in place, in order
            assert {**new, "workloads": None} == {**old, "workloads": None}
            cells = old.get("workloads", [])
            assert new.get("workloads", [])[:len(cells)] == cells
            joined += new.get("workloads", []) == cells + [CELL]
    # itl_p50_ms, the five round entries, the .serve set-up entries,
    # kernel.setup_trace_s and the rest every serving cell reports
    shared = {m["name"] for m in spec["per_layer"] if CELL in m["workloads"]}
    assert set(rules.ROUND_SHARED) | {"kernel.setup_trace_s"} | {
        n for n in rules.SETUP_CORE if n.endswith(".serve")} < shared
    assert joined == len(shared) - 2 + 1       # its own two aside, itl_p50_ms


def full(spec, root):
    return rules.with_staged(spec, root)


#: every rule the tests of this directory hold the spec to, as they call it
RULES = {
    "top_level": rules.top_level,
    "entry_text": lambda spec, root: [
        rules.entry_text(e) for e in full(spec, root)["end_to_end"]
        + full(spec, root)["workloads"] + spec["configs"]],
    "unique_names": lambda spec, root: rules.unique_names(spec),
    "pair_text": lambda spec, root: [
        rules.pair_text(full(spec, root), root, *p)
        for p in rules.pairs(full(spec, root))],
    "pair_moves": lambda spec, root: [
        rules.pair_moves(full(spec, root), root, *p)
        for p in rules.pairs(full(spec, root))],
    "one_entry_a_metric": rules.one_entry_a_metric,
    "share_names": rules.share_names,
    "cell_files": lambda spec, root: [
        rules.cell_files(full(spec, root), root, w)
        for w in full(spec, root)["workloads"]],
    "file_names": lambda spec, root: rules.file_names(root),
    "counters_have_spans": lambda spec, root: rules.counters_have_spans(root),
    "setup_entries": rules.setup_entries,
    "round_entries": rules.round_entries,
}


@pytest.mark.parametrize("rule", RULES)
def test_the_arrival_passes(arrival, rule):
    RULES[rule](*arrival)


def test_the_arrival_is_found_by_the_harness(arrival):
    spec, root = arrival
    with rules.rooted(root):
        cell = Cell(CELL, spec)
        assert cell.config["name"] == "made-up" and cell.chips == 1
        assert {m["name"] for m in cell.end_to_end} == {"itl_p50_ms", "setup_s"}
        read, args = cell.reader(SHARE)
        assert read.__module__ == "benchmark.readers.made_up_roofline"
        assert args == {"layers": 3}
        # no chip's peak (a CPU rehearsal): the share is left out of the line
        assert read({"trace": {"ops": {}}, "peak": None, "cell": cell},
                    **args) is None
    # and nothing of the copy stays behind
    assert cellmod.ROOT == ROOT
    assert "benchmark.readers.made_up_roofline" not in sys.modules
    with pytest.raises(ModuleNotFoundError):
        import benchmark.kernels.made_up  # noqa: F401


def test_the_arrivals_share_is_held_to_the_cut_trace(arrival, monkeypatch):
    """The cut-trace tests take their cases from the spec: the arrival's
    reader is one of them, and reads the same share of a cut trace to 2%.
    (It counts one-token rows, the same in every step, as the five readers of
    today count contexts. Counting ``rows``, of which a mixed step has ten
    times a round's, it read 5.2% over at the cut inside a long step: the
    whole step's work over the part of its device time that is left. The
    guard holds a reader whose work lies in the mixed steps to that too.)"""
    spec, root = arrival
    before = {c[0] for c in rules.roofline_cases(load_spec(), ROOT)}
    with rules.rooted(root):
        cases = {c[0]: c[1:] for c in rules.roofline_cases(spec, root)}
        assert set(cases) == before | {f"made_up-made_up_roofline-{CELL}"}
        kernel, reader, args, cell = cases[f"made_up-made_up_roofline-{CELL}"]
        assert (kernel, args, cell) == ("made_up", {"layers": 3}, CELL)
        cell = Cell(cell, spec)
        cut_trace.same_share_of_a_cut_trace(monkeypatch, kernel, reader, args,
                                            cell)
        for cut in cut_trace.CUTS:
            for lead in (cut_trace.LEAD, 0):
                cut_trace.share_of_what_a_cut_leaves(
                    monkeypatch, kernel, reader, args, cell, cut, lead)


# -- the refusals --------------------------------------------------------------

def entry(spec, name):
    return rules.entered(spec)[name]


def rename(spec, root, old, new, **changed):
    entry(spec, old).update(name=new, **changed)
    os.rename(os.path.join(root, "metrics", old + ".json"),
              os.path.join(root, "metrics", new + ".json"))


def rewrite_reader(root, old, new):
    assert old in READER
    write(root, "readers/made_up_roofline.py", READER.replace(old, new))


def leave(spec, name):
    entry(spec, name)["workloads"].remove(CELL)


def four_chips(spec, root):
    spec["workloads"][-1]["chips"] = 4


def repeat(spec, root):
    write(root, f"metrics/{SCOPE}.json", json.dumps(
        rules.metric_file(root, "model.paged_attn_ms")))


def join(spec, name):
    entry(spec, name)["workloads"].append(CELL)


NAMING = "kernel.<kernel>_roofline_share"
#: fault -> (what a PR does, the rule that has to refuse it, what it says)
FAULTS = {
    "a-share-named-outside-kernel": (
        lambda s, r: rename(s, r, SHARE, "engine.made_up_roofline_share.x"),
        rules.share_names, NAMING),
    "a-share-with-its-suffix-run-on": (
        lambda s, r: rename(s, r, SHARE, "kernel.made_up_roofline_share_x"),
        rules.share_names, NAMING),
    "an-mfu-outside-model.mfu": (
        lambda s, r: rename(s, r, SCOPE, "model.made_up_mfu", unit="%",
                            better="higher"), rules.share_names, NAMING),
    "a-share-in-ms": (lambda s, r: entry(s, SHARE).update(unit="ms"),
                      rules.share_names, "in % and better higher"),
    "a-share-better-lower": (lambda s, r: entry(s, SHARE).update(better="lower"),
                             rules.share_names, "in % and better higher"),
    "a-share-off-the-host-clock": (
        lambda s, r: entry(s, SHARE).update(source="host_clock"),
        rules.share_names, "source host_clock"),
    "a-share-in-another-layer": (
        lambda s, r: entry(s, SHARE).update(layer="Model"), rules.share_names,
        "layer 'Kernels'"),
    "a-share-that-moves-what-its-cell-does-not-report": (
        lambda s, r: entry(s, SHARE).update(moves="train_tokens_per_s_per_chip"),
        rules.share_names, "not every cell reports"),
    "a-share-whose-reader-names-no-KERNEL": (
        lambda s, r: rewrite_reader(r, "KERNEL", "FAMILY"),
        rules.share_names, "names no KERNEL"),
    "a-share-whose-reader-counts-its-own-work": (
        lambda s, r: rewrite_reader(
            r, "from benchmark.kernels import made_up\n",
            "class made_up:\n    dispatches = staticmethod("
            "lambda rows, layers, width: (rows, rows))\n"),
        rules.share_names, "no module under kernels/"),
    "a-share-whose-reader-takes-the-window-whole": (
        lambda s, r: rewrite_reader(
            r, "from benchmark.readers.covered import inside\n",
            "inside = lambda ctx, found: found\n"), rules.share_names,
        "no count through readers/covered.py"),
    "one-of-the-seven-given-another-cell": (
        lambda s, r: join(s, "kernel.paged_roofline_share.chat"),
        rules.share_names, "serve-chat's alone"),
    "a-training-share-that-loses-a-cell": (
        lambda s, r: entry(s, "model.mfu")["workloads"].pop(),
        rules.share_names, "model.mfu lists at least"),
    "one-of-the-seven-renamed": (
        lambda s, r: rename(s, r, "kernel.flash_roofline_share",
                            "kernel.flash_roofline_share.train"),
        rules.share_names, r"\['kernel.flash_roofline_share'\]"),
    "a-cell-missing-from-a-round-entry": (
        lambda s, r: leave(s, "sched.bubble_share"), rules.round_entries,
        f"sched.bubble_share lacks .'{CELL}'"),
    "experts-time-for-a-model-without-experts": (
        lambda s, r: join(s, "moe.mixed_experts_ms"), rules.round_entries,
        "has nothing moe.mixed_experts_ms reads"),
    "a-cell-missing-from-a-serve-set-up-entry": (
        lambda s, r: leave(s, "engine.setup_load_s.serve"),
        rules.setup_entries, f"engine.setup_load_s.serve lacks .'{CELL}'"),
    "a-cell-missing-from-kernel.setup_trace_s": (
        lambda s, r: leave(s, "kernel.setup_trace_s"), rules.setup_entries,
        "kernel.setup_trace_s lists every cell"),
    "a-second-four-chip-cell-at-seven-cells": (
        four_chips, rules.top_level, "2 four-chip cells of 7"),
    "an-entry-that-repeats-a-reader-and-its-arguments": (
        repeat, rules.one_entry_a_metric,
        "model.made_up_ms repeats model.paged_attn_ms"),
    "a-metric-file-without-an-entry": (
        lambda s, r: write(r, "metrics/model.nobody_ms.json", "{}"),
        rules.one_entry_a_metric, "model.nobody_ms.json"),
    "a-129th-entry": (
        lambda s, r: s["per_layer"].extend(
            {**entry(s, SCOPE), "name": f"model.made_up_ms.{i}"}
            for i in range(129 - len(s["per_layer"]))),
        rules.one_entry_a_metric, "the contract's ceiling"),
    "a-count-no-span-stands-for": (
        lambda s, r: write(r, f"metrics/{SCOPE}.json", json.dumps(
            {"reader": "scope_ms", "args": {"scope": "made_up",
                                            "per": "decode_steps"}})),
        lambda s, r: rules.counters_have_spans(r), "no span stands for"),
    "a-cell-not-in-what-its-metrics-move": (
        lambda s, r: rules.reports(s, "itl_p50_ms").remove(CELL),
        RULES["pair_moves"], f"{CELL} does not report itl_p50_ms"),
    "a-configuration-no-cell-uses": (
        lambda s, r: s["workloads"].pop(), rules.top_level,
        "a configuration no cell uses"),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_rule_refuses(arrival, fault):
    spec, root = arrival
    break_it, rule, says = FAULTS[fault]
    rule(spec, root)                       # sound before
    break_it(spec, root)
    keep(spec, root)
    with pytest.raises(AssertionError, match=says):
        rule(spec, root)

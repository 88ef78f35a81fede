"""The rules ``BENCHMARK.json`` and the files it names obey, each a function of
``(spec, root)``: ``spec`` the benchmark's description as loaded, ``root`` the
``benchmark/`` directory its names lead into. The tests of this directory call
them with today's; ``test_arrival.py`` calls the same functions with a copy to
which a configuration has been added the way ``benchmark/README.md`` says, by
new files and appended names. So a rule holds what every spec obeys (a
ceiling of the contract, a held core of names, who must list whom) and never
today's counts: a rule that would refuse an arrival fails the rehearsal
first."""

import contextlib
import importlib
import inspect
import json
import os
import re
import sys
import types

import benchmark.harness.cell as cellmod
from benchmark.harness.cell import Cell, load_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
#: the packages a cell's files may add modules to
CODE = ("readers", "kernels")


@contextlib.contextmanager
def rooted(root):
    """The harness finding its files under ``root``: ``cell.ROOT`` and
    ``cell.REPO`` point there (``test_a_missing_file_or_reader_fails_loudly``
    redirects the first the same way), and ``root``'s ``readers/`` and
    ``kernels/`` are searched *after* the repo's own, so a copy adds modules
    and shadows none. With the repo's own ``benchmark/`` nothing changes."""
    root = str(root)
    if os.path.realpath(root) == os.path.realpath(cellmod.ROOT):
        yield
        return
    packages = [importlib.import_module(f"benchmark.{p}") for p in CODE]
    saved = cellmod.ROOT, cellmod.REPO, [list(p.__path__) for p in packages]
    cellmod.ROOT, cellmod.REPO = root, os.path.dirname(root)
    for p, sub in zip(packages, CODE):
        p.__path__.append(os.path.join(root, sub))
    importlib.invalidate_caches()
    try:
        yield
    finally:
        cellmod.ROOT, cellmod.REPO = saved[0], saved[1]
        for p, path in zip(packages, saved[2]):
            p.__path__[:] = path
        for name, mod in list(sys.modules.items()):
            if (getattr(mod, "__file__", None) or "").startswith(root + os.sep):
                del sys.modules[name]
                parent, _, leaf = name.rpartition(".")
                if parent in sys.modules:
                    vars(sys.modules[parent]).pop(leaf, None)
        importlib.invalidate_caches()


def with_staged(spec, root):
    """``spec`` with the entries of ``root``'s ``staged.json`` behind its own,
    as ``load_spec(staged=True)`` joins them."""
    with rooted(root):
        extra = load_json("staged.json")
    return {**spec, **{key: spec[key] + extra[key]
                       for key in ("workloads", "end_to_end", "per_layer")}}


def pairs(spec):
    """One case a (per-layer metric, cell that lists it): a cell joins a
    metric by one more name in its ``workloads``, and is tested like an entry
    of its own."""
    return [(m, cell) for m in spec["per_layer"] for cell in m["workloads"]]


def entered(spec):
    return {m["name"]: m for m in spec["per_layer"]}


def reports(spec, metric):
    """The cells that report an end-to-end metric."""
    moved = next(e for e in spec["end_to_end"] if e["name"] == metric)
    return moved.get("workloads", [w["name"] for w in spec["workloads"]])


def metric_file(root, name):
    with rooted(root):
        return load_json("metrics", name + ".json")


def reader_module(root, name):
    """The module a per-layer metric's file names, found as the harness finds
    it (``Cell.reader``)."""
    with rooted(root):
        return importlib.import_module(
            f"benchmark.readers.{metric_file(root, name)['reader']}")


# -- the contract's ceilings and character rules ----------------------------

def top_level(spec, root):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    path = os.path.join(os.path.dirname(str(root)), "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    assert 1 <= len(spec["configs"]) <= 24 and 1 <= len(spec["workloads"]) <= 24
    assert 1 <= len(spec["end_to_end"]) <= 16
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 4), \
        f"{four} four-chip cells of {len(spec['workloads'])}"
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in spec["end_to_end"])
    assert all(c["name"] in {w["config"] for w in spec["workloads"]}
               for c in spec["configs"]), "a configuration no cell uses"


def entry_text(entry):
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


def unique_names(spec):
    for group in (spec["end_to_end"] + spec["per_layer"], spec["workloads"],
                  spec["configs"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    mixes = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(mixes) == len(set(mixes))
    files = [c["file"] for c in spec["configs"]]
    assert len(files) == len(set(files))


def pair_text(spec, root, m, cell):
    entry_text(m)
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert cell in {w["name"] for w in spec["workloads"]}
    assert m["workloads"].count(cell) == 1
    assert "reader" in metric_file(root, m["name"])


def pair_moves(spec, root, m, cell):
    cells = {w["name"] for w in spec["workloads"]}
    assert cell in set(reports(spec, m["moves"])) <= cells, \
        f"{cell} does not report {m['moves']}, which {m['name']} moves"
    with rooted(root):
        assert m in Cell(cell, spec).per_layer
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")


def one_entry_a_metric(spec, root):
    """A cell joins a metric it shares by its name in ``workloads``; an entry
    of its own is for a reader or arguments of its own. So no two entries
    agree in reader, arguments, layer and what they move: that rule keeps
    the list short. The one ceiling held is the contract's, so that a
    configuration can bring its entries without editing a test."""
    assert 1 <= len(spec["per_layer"]) <= 128, "the contract's ceiling"
    seen = {}
    full = with_staged(spec, root)["per_layer"]
    for m in full:
        entry = metric_file(root, m["name"])
        key = (entry["reader"], json.dumps(entry.get("args", {}), sort_keys=True),
               m["layer"], m["moves"])
        assert key not in seen, f"{m['name']} repeats {seen[key]}"
        seen[key] = m["name"]
    # and no file under metrics/ without an entry
    listed = {m["name"] + ".json" for m in full}
    files = set(os.listdir(os.path.join(str(root), "metrics")))
    assert files == listed, f"files and entries differ in {files ^ listed}"


def cell_files(spec, root, w):
    """Every cell finds its configuration, its mix and a reader for each
    metric that lists it; ``spec`` may hold staged entries."""
    with rooted(root):
        cell = Cell(w["name"], spec)
        assert cell.traffic["kind"] in ("train", "serve_open", "serve_closed")
        assert cell.config["reduced"] == next(
            c["reduced"] for c in spec["configs"] if c["name"] == w["config"])
        for key in ("source", "changed", "assumed", "reduced", "tolerances",
                    "deployment"):
            assert key in cell.config
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            read, args = cell.reader(m["name"])
            assert callable(read) and isinstance(args, dict)
            inspect.signature(read).bind({}, **args)
        assert os.path.dirname(cell.config["name"]) == ""


def file_names(*dirs):
    for d in dirs:
        assert os.path.isdir(d), d
        for at, subdirs, files in os.walk(d):
            subdirs[:] = [x for x in subdirs if x != "__pycache__"]
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(at, f)


def counters_have_spans(root):
    """Every ``per`` a metric file names is a counter ``covered`` knows the
    span of: of any other, a cut trace would again divide part of a window's
    device time by a whole window's count."""
    from benchmark.readers import covered

    root = str(root)
    files = [os.path.join(root, "metrics", f)
             for f in os.listdir(os.path.join(root, "metrics"))]
    for path in files + [os.path.join(root, "staged.json")]:
        with open(path) as f:
            per = json.dumps(json.load(f)).split('"per": "')[1:]
        assert {p.split('"')[0] for p in per} <= set(covered.COUNTED_BY), \
            f"{path}: per a counter no span stands for"


# -- the names claims are bounded by -----------------------------------------

#: the shares of a roofline and of the chip's peak the driver bounds claims
#: by, with the traffic of their cells. The ones named after one cell keep
#: exactly it; the two whose one reader serves every training cell list at
#: least today's (a second entry with the same reader and arguments is
#: refused by ``one_entry_a_metric``, so a training cell joins these or
#: reports no MFU).
SHARES_OF_ONE_CELL = {
    "kernel.mla_decode_roofline_share.longdoc": ["serve-longdoc"],
    "kernel.mla_decode_roofline_share.longout": ["serve-longout"],
    "kernel.paged_roofline_share.chat": ["serve-chat"],
    "kernel.paged_roofline_share.doc16k": ["serve-doc16k"],
    "kernel.linear_decode_roofline_share.doc16k": ["serve-doc16k"],
}
SHARES_OF_TRAINING = {
    "kernel.flash_roofline_share": ["train-seq1024", "zero3-train-4chip"],
    "model.mfu": ["train-seq1024", "zero3-train-4chip"],
}
ROOFLINE = re.compile(r"^kernel\.[A-Za-z0-9_]+_roofline_share(\.[A-Za-z0-9_.\-]+)?$")
MFU = re.compile(r"^model\.mfu\.[A-Za-z0-9_.\-]+$")


def takes_from(mod, prefix):
    """Whether a module's globals hold a module ``prefix*`` or something
    defined in one: what ``from benchmark.kernels import x`` and ``from
    benchmark.readers.covered import inside`` both leave behind."""
    for v in vars(mod).values():
        name = v.__name__ if isinstance(v, types.ModuleType) \
            else getattr(v, "__module__", None)
        if isinstance(name, str) and (name + ".").startswith(prefix):
            return True
    return False


def roofline_reader(root, name):
    """A share of a roofline is read by a module under ``readers/`` that
    names its kernels' family (``KERNEL``), takes their operations and bytes
    from a function under ``kernels/`` (the yardstick, not the program) and
    its host-side counts through ``readers/covered.py`` (a trace that covers
    part of the window reads the same share)."""
    mod = reader_module(root, name)
    assert isinstance(getattr(mod, "KERNEL", None), str) and mod.KERNEL, \
        f"{mod.__name__} names no KERNEL"
    assert takes_from(mod, "benchmark.kernels."), \
        f"{mod.__name__} takes its work from no module under kernels/"
    assert takes_from(mod, "benchmark.readers.covered."), \
        f"{mod.__name__} takes no count through readers/covered.py"
    return mod


def share_names(spec, root):
    """The seven names are held letter for letter, with their cells; every
    other share of a roofline or of the peak obeys the naming rule, so the
    driver (which refuses one read over 105%) and the next reader find it."""
    by = entered(spec)
    traffic = {w["name"]: w["traffic"] for w in spec["workloads"]}
    core = {**SHARES_OF_ONE_CELL, **SHARES_OF_TRAINING}
    assert set(core) <= set(by), sorted(set(core) - set(by))
    for name, cells in SHARES_OF_ONE_CELL.items():
        assert [traffic[c] for c in by[name]["workloads"]] == cells, \
            f"{name} is {cells[0]}'s alone"
    for name, cells in SHARES_OF_TRAINING.items():
        assert set(cells) <= {traffic[c] for c in by[name]["workloads"]}, \
            f"{name} lists at least {cells}"
    kernels = by["kernel.flash_roofline_share"]["layer"]
    model = by["model.mfu"]["layer"]
    for name, m in by.items():
        if "roofline_share" not in name and "mfu" not in name:
            continue
        share = "roofline_share" in name
        if name not in core:
            assert (ROOFLINE if share else MFU).match(name), \
                f"{name}: kernel.<kernel>_roofline_share[.<suffix>] or " \
                "model.mfu.<suffix>"
            assert m["unit"] == "%" and m["better"] == "higher", \
                f"{name}: a share is in % and better higher"
            assert m["layer"] == (kernels if share else model), \
                f"{name}: layer '{kernels if share else model}'"
            assert m["source"] in (("device_trace",) if share else
                                   ("device_trace", "host_clock")), \
                f"{name}: source {m['source']}"
            assert m["workloads"], name
            assert set(m["workloads"]) <= set(reports(spec, m["moves"])), \
                f"{name}: not every cell reports {m['moves']}"
        if share:
            roofline_reader(root, name)


def roofline_cases(spec, root, moves="itl_p50_ms"):
    """(id, kernel, reader module, arguments, cell) of every roofline share
    that moves ``moves``: what the cut-trace tests hold to 2%. The reader and
    its arguments are the metric file's, the kernel the reader's ``KERNEL``,
    the cell the entry's first."""
    out = []
    for m in spec["per_layer"]:
        if "roofline_share" in m["name"] and m["moves"] == moves:
            mod = reader_module(root, m["name"])
            args = metric_file(root, m["name"]).get("args", {})
            kernel = getattr(mod, "KERNEL", None)
            reader = mod.__name__.rsplit(".", 1)[1]
            out.append((f"{kernel}-{reader}-{m['workloads'][0]}", kernel, mod,
                        args, m["workloads"][0]))
    return out


# -- who must list whom ------------------------------------------------------

def kinds(spec, root):
    """(training cells, serving cells) of ``spec``, by the ``kind`` of each
    cell's traffic file."""
    with rooted(root):
        kind = {w["name"]: load_json("traffic", w["traffic"] + ".json")["kind"]
                for w in spec["workloads"]}
    train = {c for c, k in kind.items() if k == "train"}
    return train, set(kind) - train


#: the set-up account's entries (PR 57, entered by PR 59)
SETUP_KINDS = ("setup_init_s", "setup_trace_s", "setup_lower_s", "setup_load_s",
               "setup_programs")
SETUP_CORE = tuple(f"engine.{k}.{side}" for k in SETUP_KINDS
                   for side in ("train", "serve")) + ("kernel.setup_trace_s",)


def setup_entries(spec, root):
    """The eleven entries of the set-up account move ``setup_s`` and are read
    by ``setup_builds``; a ``.train`` entry lists every training cell, a
    ``.serve`` entry every serving cell, ``kernel.setup_trace_s`` every cell:
    a cell that arrives joins them. ``engine.host_ms.train`` moves the
    training rate in every training cell."""
    by = entered(spec)
    assert set(SETUP_CORE) | {"engine.host_ms.train"} <= set(by)
    train, serve = kinds(spec, root)
    assert set(by["kernel.setup_trace_s"]["workloads"]) == train | serve, \
        "kernel.setup_trace_s lists every cell"
    for name in SETUP_CORE:
        m = by[name]
        assert m["moves"] == "setup_s", name
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert m["unit"] == ("count" if "programs" in name else "s")
        if name.endswith(".train"):
            assert set(m["workloads"]) == train, \
                f"{name} lacks {sorted(train - set(m['workloads']))}"
            assert m["layer"] == "Training engine"
        if name.endswith(".serve"):
            assert set(m["workloads"]) == serve, \
                f"{name} lacks {sorted(serve - set(m['workloads']))}"
            assert m["layer"] == "Serving engine"
        with rooted(root):
            for cell in m["workloads"]:
                read, args = Cell(cell, spec).reader(name)
                assert read.__module__ == "benchmark.readers.setup_builds"
    host = by["engine.host_ms.train"]
    assert set(host["workloads"]) == train
    assert host["moves"] == "train_tokens_per_s_per_chip"


#: a serving step by its kind (PR 40): what every serving cell reports ...
ROUND_SHARED = ("engine.round_device_ms", "engine.mixed_device_ms",
                "model.mixed_attn_ms", "sched.bubble_share",
                "sched.starved_share")
#: ... and what only a configuration that has the thing reports, told from the
#: ``model`` block of its file: keys and values of heads in the pool (no
#: ``attention: mla``, whose pool holds one latent row a token and whose write
#: is no ``kv_write`` of the mixed step's size); experts held on the chip
ROUND_OWN = {
    "engine.mixed_kv_write_ms": lambda model: model.get("attention") != "mla",
    "moe.mixed_experts_ms": lambda model: model.get("num_experts", 0) > 0,
}
ROUND_READERS = ("variant_ms", "bubble_share", "starved_share")


def round_entries(spec, root):
    """The five entries every serving cell steers by list **every** cell
    that reports ``itl_p50_ms``; the two of one scope list only cells whose
    configuration has what the scope holds; each is read by one of the three
    modules of PR 40."""
    by = entered(spec)
    serving = set(reports(spec, "itl_p50_ms"))
    for name in ROUND_SHARED:
        assert set(by[name]["workloads"]) == serving, \
            f"{name} lacks {sorted(serving - set(by[name]['workloads']))}"
    with rooted(root):
        for name, has in ROUND_OWN.items():
            for cell in by[name]["workloads"]:
                assert cell in serving, (name, cell)
                assert has(Cell(cell, spec).config["model"]), \
                    f"{cell}'s configuration has nothing {name} reads"
        for name in ROUND_SHARED + tuple(ROUND_OWN):
            assert by[name]["moves"] == "itl_p50_ms"
            for cell in by[name]["workloads"]:
                read, args = Cell(cell, spec).reader(name)
                assert read.__module__.rsplit(".", 1)[1] in ROUND_READERS

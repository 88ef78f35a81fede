"""``benchmark/readers/setup_builds.py`` against records built by hand: the
always-on account split at the window's first instant, the table it prints
once, and a program without the account reading nothing."""

import collections
import os

import pytest

from benchmark.harness.cell import ROOT, load_json, load_spec
from benchmark.readers import setup_builds
from deepspeed_tpu.utils import tracing
from tests.benchmark import rules

R = tracing.Record
S = 1_000_000_000
SITE = "deepspeed_tpu.runtime.engine.DeepSpeedEngine._fused_micro_step"


def build(id_, end_s, program, site="", caller="benchmark.harness.weights",
          trace=0.0, lower=0.0, load=0.0, cached=True, kernels=None):
    return R(id_, "compile", int((end_s - load) * S), int(end_s * S), 0,
             dict(program=program, cached=cached, site=site, caller=caller,
                  trace_s=trace, lower_s=lower, load_s=load,
                  kernels=kernels or {}))


@pytest.fixture
def account(monkeypatch):
    """A set-up of 20 s and a window that opens at 20 s: the reference's own
    program, the constructor, two package programs before the window (one a
    cache miss, with kernels), one inside it."""
    kept = collections.deque([
        build(1, 3.0, "jit(reference)", caller="benchmark.reference.gpt2",
              trace=0.5, lower=0.25, load=1.0,
              kernels={"flash_fwd": (1, 0.125)}),
        R(2, "engine.init", 4 * S, 6 * S, 0,
          {"engine": "train", "model_s": 0.5, "params_s": 1.5}),
        build(3, 5.0, "jit(build)", site="deepspeed_tpu.zero.sharded_dual_init",
              trace=0.25, lower=0.125, load=0.5),
        build(4, 12.0, "jit(fused_step)", site=SITE, trace=2.0, lower=1.0,
              load=4.0, cached=False,
              kernels={"flash_fwd": (24, 0.75), "flash_bwd": (24, 0.5)}),
        build(5, 23.0, "jit(fused_step)", site=SITE, trace=2.0, lower=1.0,
              load=0.25),
    ])
    spans = [R(6, "engine.next_batch", 20 * S, 20 * S + 5, 7, {}),
             R(8, "req.queue", 15 * S, 21 * S, 0, {"uid": 1}),  # an event
             R(7, "engine.train_batch", 20 * S, 22 * S, 0, {"step": 2}),
             kept[-1]]
    monkeypatch.setattr(tracing, "_kept", kept)
    monkeypatch.setattr(tracing, "_buf", spans)
    monkeypatch.setattr(tracing, "_small",
                        {"benchmark.harness.weights": [40, 0.02, 0.04, 0.06]})
    monkeypatch.setattr(setup_builds, "_printed", [])
    return kept


def test_the_account_is_split_where_the_window_opened(account, capsys):
    read = setup_builds.read
    assert read({}, what="trace_s") == 2.25
    assert read({}, what="lower_s") == 1.125
    assert read({}, what="load_s") == 4.5
    assert read({}, what="programs") == 2.0     # the one at 23 s is the window's
    assert read({}, what="init_s") == 2.0
    # kernel bodies count wherever they were traced, the reference's too
    assert read({}, what="kernel_trace_s") == 1.375
    with pytest.raises(ValueError, match="no quantity"):
        read({}, what="nonsense")
    out = capsys.readouterr().out
    assert out.count("package programs before the window") == 1   # printed once
    lines = [line for line in out.splitlines()
             if line.startswith("[setup_builds]")]
    assert "engine.init (train): 2.00 s (model 0.50, params 1.50)" in lines[0]
    assert ("2 package programs before the window: trace 2.25 + lower 1.12 + "
            "load 4.50 = 7.88 s, 0.88 s of it inside engine.init; 1 not "
            "cached (4.00 s of compile)") in lines[1]
    # costliest first, with its kernels; `at` counts from the constructor
    assert "+8.00  2.000  1.000  4.000  0  jit(fused_step)" in lines[3]
    assert "flash_bwd:24:0.500 flash_fwd:24:0.750" in lines[3]
    assert "+1.00" in lines[4] and "sharded_dual_init  -" in lines[4]
    assert "flash_fwd 25 binds 0.875 s, flash_bwd 24 binds 0.500 s" in out
    assert "benchmark.reference.gpt2 1 programs 1.75 s" in out
    assert "benchmark.harness.weights 40 programs 0.12 s" in out
    assert "all programs: 43, 9.75 s" in out


def test_without_a_window_or_without_the_account_nothing_is_read(
        account, monkeypatch):
    monkeypatch.setattr(tracing, "_buf", [])        # no span: no window
    assert setup_builds.read({}, what="trace_s") is None
    monkeypatch.setattr(tracing, "_buf", [account[-1]])
    assert setup_builds.read({}, what="programs") == 2.0
    # the parent of the PR that added the account has a recorder without it
    monkeypatch.delattr(tracing, "builds")
    for what in ("init_s", "trace_s", "lower_s", "load_s", "programs",
                 "kernel_trace_s"):
        assert setup_builds.read({}, what=what) is None


def test_a_run_whose_engine_left_no_init_record_reads_no_init(account):
    del account[1]
    assert setup_builds.read({}, what="init_s") is None
    assert setup_builds.read({}, what="programs") == 2.0


SETUP_METRICS = sorted(
    f[:-5] for f in os.listdir(os.path.join(ROOT, "metrics"))
    if ".setup_" in f or f == "engine.host_ms.train.json")


def test_every_metric_of_the_issue_has_its_file():
    """The twelve of PR 57's issue are there; a later ``.setup_`` file is one
    more case of the test below."""
    assert set(rules.SETUP_CORE) | {"engine.host_ms.train"} <= set(SETUP_METRICS)
    assert {m.split(".")[1] for m in SETUP_METRICS} >= set(
        rules.SETUP_KINDS) | {"host_ms"}


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_metric_file_names_a_reader_whose_arguments_fit(name, account):
    import importlib
    import inspect

    entry = load_json("metrics", name + ".json")
    read = importlib.import_module(f"benchmark.readers.{entry['reader']}").read
    inspect.signature(read).bind({}, **entry["args"])
    if entry["reader"] == "setup_builds":
        assert read({}, **entry["args"]) is not None
    else:       # engine.host_ms.train: the span the training engine records
        assert read({"spans": {}}, **entry["args"]) == 2000.0


def test_the_entries_of_the_set_up_account():
    """All twelve are entries since the per-layer list holds one entry a
    metric: eleven move ``setup_s`` (a ``.train`` and a ``.serve`` entry
    differ in ``layer``), ``engine.host_ms.train`` the training rate; who
    lists whom is ``rules.setup_entries``."""
    rules.setup_entries(load_spec(), ROOT)

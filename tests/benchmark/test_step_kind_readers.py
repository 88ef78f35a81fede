"""The readers that account a serving step by its kind (PR 40): device time
by the ragged variant that spent it (``variant_ms``), the engine's bubbles by
cause (``bubble_share``), the run-ahead rounds the device waited for
(``starved_share``). Each against a hand-built ``ctx`` and recorder, then all
of them on the CPU rehearsal of ``serve-chat``."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.cell import REPO, ROOT, Cell, load_spec
from benchmark.readers import bubble_share, starved_share, variant_ms
from deepspeed_tpu.utils import tracing
from tests.benchmark import rules

R = tracing.Record
MS = 1_000_000
CHAT = "gpt2-medium.serve-chat"
ROUND, MIXED = ("engine_v2.ragged", (64, True)), ("engine_v2.ragged", (256, True))


def op(instr, shape, opcode="fusion"):
    """A device event's name as a TPU trace gives it: the HLO line."""
    return f"%{instr} = {shape}{{1,0}} {opcode}(%p)"


#: seconds of the first device's ops over 10 decode rounds and 2 mixed steps
OPS = {
    op("fusion.187", "bf16[64,1,1024]"): (0.010, 240),        # round, model
    op("paged_decode.13", "bf16[64,16,1,64]", "custom-call"): (0.004, 240),
    op("fusion.190", "bf16[20447232,128]"): (0.014, 48),      # mixed, kv_write
    op("fusion.201", "bf16[256,16,64]"): (0.006, 48),         # mixed, paged_attn
    op("fusion.77", "bf16[256,4096]"): (0.020, 48),           # mixed, model
    op("copy-done.3", "bf16[50304,1024]", "copy-done"): (0.002, 24),   # both
    op("while.5", "(s32[], bf16[64,1024])", "while"): (0.030, 12),     # container
    op("fusion.9", "f32[8]"): (0.001, 3),                     # no noted program
}
PROGRAMS = {
    "fusion.187 bf16[64,1,1024]": {ROUND},
    "paged_decode.13 bf16[64,16,1,64]": {ROUND},
    "fusion.190 bf16[20447232,128]": {MIXED},
    "fusion.201 bf16[256,16,64]": {MIXED},
    "fusion.77 bf16[256,4096]": {MIXED},
    "copy-done.3 bf16[50304,1024]": {ROUND, MIXED},
}
SCOPES = {
    "fusion.187 bf16[64,1,1024]": "model",
    "paged_decode.13 bf16[64,16,1,64]": "paged_attn",
    "fusion.190 bf16[20447232,128]": "kv_write",
    "fusion.201 bf16[256,16,64]": "paged_attn",
    "fusion.77 bf16[256,4096]": "model",
    "copy-done.3 bf16[50304,1024]": "unscoped",
}


def dispatch(i, rows, t0, **attrs):
    return R(i, "engine.dispatch", t0, t0 + MS, 0,
             {"program": "ragged", "padded_rows": rows, **attrs})


@pytest.fixture
def recorded(monkeypatch):
    """Ten decode rounds (nine of them run ahead, two of those starved), two
    mixed steps, a verify step that is nobody's variant, and five bubbles."""
    spans = [dispatch(i, 64, i * 10 * MS, ahead=int(i > 1),
                      **({"starved": int(i in (4, 7))} if i > 1 else {}))
             for i in range(1, 11)]
    spans += [dispatch(11, 256, 200 * MS, ahead=0),
              dispatch(12, 256, 240 * MS, ahead=0),
              R(13, "engine.dispatch", 300 * MS, 301 * MS, 0,
                {"program": "verify", "padded_rows": 64, "ahead": 0})]
    spans += [R(20, "engine.bubble", 100 * MS, 104 * MS, 11, {"cause": "backlog"}),
              R(21, "engine.bubble", 236 * MS, 238 * MS, 12, {"cause": "backlog"}),
              R(22, "engine.bubble", 280 * MS, 281 * MS, 1, {"cause": "restart"}),
              R(23, "engine.bubble", 400 * MS, 900 * MS, 11, {"cause": "empty"}),
              R(24, "engine.bubble", 950 * MS, 953 * MS, 11, {"cause": "horizon"})]
    monkeypatch.setattr(tracing, "_buf", spans)
    monkeypatch.setattr(tracing, "device_programs", lambda: PROGRAMS)
    monkeypatch.setattr(tracing, "device_scopes", lambda: SCOPES)
    monkeypatch.setattr(variant_ms, "_last", (None, None))
    return spans


def ctx(ops=OPS, busy=None, window=2.0, idle=None):
    busy = busy if busy is not None else sum(
        s for n, (s, _) in ops.items() if " while(" not in n)
    return {"trace": {"ops": ops, "busy_first_s": busy, "window_s": window,
                      "idle_by_span": idle or {}},
            "counters": {}, "spans": {}, "peak": None, "cell": Cell(CHAT)}


def test_device_time_goes_to_the_variant_that_holds_the_op(recorded):
    c = ctx()
    assert variant_ms.read(c, variant="round") == pytest.approx(14.0 / 10)
    assert variant_ms.read(c, variant="mixed") == pytest.approx(40.0 / 2)
    acc = variant_ms.account(c)
    assert acc["n"] == {"round": 10, "mixed": 2}      # the verify step is neither
    assert acc["total"]["shared"] == pytest.approx(0.002)
    assert acc["total"]["other"] == pytest.approx(0.001)   # the container is left out
    # what the acceptance check adds up: rounds + mixed steps + shared = busy
    assert sum(acc["total"].values()) == pytest.approx(c["trace"]["busy_first_s"])


@pytest.mark.parametrize("variant, scope, ms", [
    ("mixed", "kv_write", 7.0), ("mixed", "paged_attn", 3.0),
    ("mixed", "model", 10.0), ("round", "paged_attn", 0.4),
    ("round", "kv_write", 0.0), ("mixed", "moe_experts", 0.0)])
def test_a_scope_of_one_variant(recorded, variant, scope, ms):
    assert variant_ms.read(ctx(), variant=variant, scope=scope) == \
        pytest.approx(ms)


def test_the_account_is_printed_and_an_all_shared_table_reads_zero(
        recorded, monkeypatch, capsys):
    assert variant_ms.read(ctx(), variant="mixed") == pytest.approx(20.0)
    out = capsys.readouterr().out
    assert "of busy: mixed alone 70.18%, shared 3.51%" in out
    assert "round = 64" in out and "(100.00%)" in out
    assert "largest: copy-done.3 copy-done bf16[50304,1024] 0.0020 s" in out
    assert "kv_write 7.000 ms (35.0%): fusion.190 fusion bf16[20447232,128]" in out
    # the head's copies grown to 12% of busy: they stay out of both variants
    heavy = dict(OPS)
    heavy[op("copy-done.3", "bf16[50304,1024]", "copy-done")] = (0.0075, 24)
    assert variant_ms.read(ctx(heavy), variant="mixed") == pytest.approx(20.0)
    assert "shared 12.00%" in capsys.readouterr().out
    # a table made all-shared tells nothing apart (the CPU client's): no
    # variant has an op of its own, and the account says where the time went
    monkeypatch.setattr(tracing, "device_programs",
                        lambda: {k: {ROUND, MIXED} for k in PROGRAMS})
    c = ctx()
    assert variant_ms.read(c, variant="mixed") == 0.0
    assert variant_ms.read(c, variant="round", scope="model") == 0.0
    assert variant_ms.account(c)["total"]["shared"] == pytest.approx(0.056)
    assert "shared 98.25%" in capsys.readouterr().out


def test_the_round_is_the_rehearsal_presets_rows_where_those_ran():
    cell = Cell(CHAT)
    assert variant_ms.round_rows(cell, {64: 800, 256: 11}) == 64
    assert variant_ms.round_rows(cell, {4: 16, 32: 10}) == 4
    assert variant_ms.round_rows(cell, {256: 3}) is None


def test_bubbles_by_cause_and_the_share_that_leaves_empty_out(recorded, capsys):
    c = ctx(idle={"sched.step": 0.0125, "gen.wait": 0.6})
    assert bubble_share.read(c) == pytest.approx(100 * 0.010 / 2.0)
    out = capsys.readouterr().out
    assert "empty 0.5000 s in 1, backlog 0.0060 s in 2" in out
    assert "horizon 0.0030 s in 1" in out and "restart 0.0010 s in 1" in out
    assert "not empty 0.0100 s of a 2.000 s window" in out
    # what the harness counts beyond the bubbles, by device op event
    assert "sched.step: 0.0125 s; beyond the bubbles 0.0025 s" in out
    assert "3.840 us a device op event (651 events)" in out


def test_starved_share_is_over_the_run_ahead_rounds_alone(recorded, capsys):
    assert starved_share.read(ctx()) == pytest.approx(100 * 2 / 9)
    assert "2 of 9 run-ahead rounds" in capsys.readouterr().out


def test_the_parent_reads_nothing(monkeypatch):
    """A program without the event, the attribute or the table (the parent of
    PR 40) leaves the new metrics out of the line and raises nothing."""
    old = [dispatch(1, 64, 0, ahead=1), dispatch(2, 256, 10 * MS, ahead=0)]
    monkeypatch.setattr(tracing, "_buf", old)
    monkeypatch.setattr(variant_ms, "_last", (None, None))
    monkeypatch.delattr(tracing, "device_programs")
    assert variant_ms.read(ctx(), variant="round") is None
    assert variant_ms.read(ctx(), variant="mixed", scope="kv_write") is None
    assert bubble_share.read(ctx()) is None
    assert starved_share.read(ctx()) is None
    monkeypatch.setattr(tracing, "_buf", [])
    assert bubble_share.read(ctx()) is None and starved_share.read(ctx()) is None
    untraced = {**ctx(), "trace": None}
    assert variant_ms.read(untraced, variant="round") is None
    assert bubble_share.read(untraced) is None


def test_every_new_metric_names_a_reader_and_its_cell():
    """``rules.round_entries``: the five every serving cell steers by list
    every cell that reports ``itl_p50_ms`` (a fifth serving cell joins them),
    the two of one scope only cells whose configuration has it."""
    rules.round_entries(load_spec(), ROOT)


def test_the_readers_on_the_rehearsal_of_serve_chat():
    """Traced ``serve-chat`` at its tiny preset in a child, as a chip run
    goes: every new metric of the cell reads a number; the by-variant
    account adds up to the trace's op table (on the CPU client, which names
    an event by the instruction alone, every op is shared and a variant's own
    time reads 0: no time read here means anything)."""
    code = (
        "import json, jax\n"
        "from benchmark import run\n"
        "from benchmark.harness.cell import Cell\n"
        "from benchmark.readers import variant_ms\n"
        "from benchmark.harness.trace import CONTAINERS, parse_op\n"
        f"cell = Cell({CHAT!r})\n"
        "out, s = run.run_cell(cell, 2**31 + 11, 2.0, 1, jax.devices(),"
        " rehearsal=True)\n"
        "acc = variant_ms.account({'trace': s, 'cell': cell})\n"
        "ops = sum(t for n, (t, _) in s['ops'].items()"
        " if parse_op(n)[1] not in CONTAINERS)\n"
        "print('RESULT', json.dumps({'metrics': out['metrics'], 'n': acc['n'],"
        " 'total': acc['total'], 'ops': ops}))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    got = json.loads(next(line[7:] for line in r.stdout.splitlines()
                          if line.startswith("RESULT ")))
    metrics = got["metrics"]
    assert {"engine.round_device_ms", "engine.mixed_device_ms",
            "model.mixed_attn_ms", "engine.mixed_kv_write_ms",
            "sched.bubble_share", "sched.starved_share"} <= set(metrics)
    assert 0 < metrics["sched.bubble_share"]["value"] < 100
    assert 0 <= metrics["sched.starved_share"]["value"] <= 100
    assert got["n"]["round"] > 0 and got["n"]["mixed"] > 0
    # the account is a partition of the op table (on the chip, where ops
    # follow each other on one line, that is the busy time to 0.1-0.7%; the
    # CPU client's thunks overlap under load, so only the partition is held)
    assert sum(got["total"].values()) == pytest.approx(got["ops"])
    assert "[bubble_share]" in r.stdout and "empty" in r.stdout
    assert "[variant_ms] rows" in r.stdout

"""Every cell end to end at its tiny ``rehearsal`` preset, through
``run.run_cell``: the code a chip run takes after its device check. No time or
rate read here means anything; the assertions are on the shape of the result
and on counts."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.cell import REPO, Cell, load_spec

SPEC = load_spec(staged=True)
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]
FOUR_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 4]


def check_result(cell, out, trace):
    assert set(out) - {"breakdown"} == {"correct", "attempted", "failed",
                                        "metrics"}
    assert out["attempted"] > 0 and out["failed"] == 0
    wanted = cell.per_layer if trace else cell.end_to_end
    listed = {m["name"]: m["unit"] for m in wanted}
    assert out["metrics"] and set(out["metrics"]) <= set(listed)
    for name, m in out["metrics"].items():
        assert m["unit"] == listed[name]
        assert isinstance(m["value"], float) and m["value"] == m["value"]
    if trace:
        # device metrics whose peak is unknown on the CPU are left out, the
        # counted ones are there
        assert not any("roofline" in n or "mfu" in n for n in out["metrics"])
        assert 1 <= len(out["breakdown"]["device_ops"]) <= 10
        assert len(out["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(out["metrics"]) == set(listed)
        assert out["metrics"]["setup_s"]["value"] > 0


def rehearse(name, devices):
    """Both runs of a cell (end-to-end, traced) at its tiny preset, in a child
    that sees exactly the cell's number of devices, as a chip run does."""
    code = (
        "import json, jax\n"
        "from benchmark import run\n"
        "from benchmark.harness.cell import Cell\n"
        "from benchmark.harness.cell import load_spec\n"
        f"cell = Cell({name!r}, load_spec(staged=True))\n"
        "for trace in (0, 1):\n"
        "    out, s = run.run_cell(cell, 2**31 + 5, 2.0, trace, jax.devices(),"
        " rehearsal=True)\n"
        "    print('RESULT', json.dumps(out))\n"
        "    if s: print('TRACED', s['busy_s'] > 0 and s['window_s'] > 0)\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "TRACED True" in r.stdout
    # the tracer's anchor was found: the readers know which spans the trace covers
    assert "[trace] device events cover" in r.stdout
    assert "no clock anchor" not in r.stdout
    outs = [json.loads(line[7:]) for line in r.stdout.splitlines()
            if line.startswith("RESULT ")]
    cell = Cell(name, SPEC)
    check_result(cell, outs[0], 0)
    check_result(cell, outs[1], 1)
    # the tiny preset is held to the full-size limits, which bf16 at two
    # layers sits far inside
    assert outs[0]["correct"] is True and outs[1]["correct"] is True
    return outs


@pytest.mark.parametrize("name", ONE_CHIP)
def test_one_chip_cell_rehearsal(name):
    _, traced = rehearse(name, 1)
    if Cell(name, SPEC).traffic["kind"] != "train":
        per_token = [m["value"] for n, m in traced["metrics"].items()
                     if n.startswith("engine.dispatches_per_token")]
        assert per_token and 0 < per_token[0] <= 1.0


@pytest.mark.parametrize("name", FOUR_CHIP)
def test_four_chip_cell_rehearsal_on_four_virtual_devices(name):
    """Four chips are for what exists only across chips: the cell lists
    metrics that no one-chip cell lists (today ZeRO's two), and reads them."""
    _, traced = rehearse(name, 4)
    on_one = {m["name"] for c in ONE_CHIP for m in Cell(c, SPEC).per_layer}
    across = {m["name"] for m in Cell(name, SPEC).per_layer} - on_one
    assert across and across <= set(traced["metrics"])
    assert all(traced["metrics"][n]["value"] >= 0 for n in across)
    if "zero.state_gib_per_chip" in across:
        assert traced["metrics"]["zero.state_gib_per_chip"]["value"] > 0


@pytest.mark.parametrize("name", [ONE_CHIP[0]] + FOUR_CHIP[:1])
def test_run_refuses_anything_but_the_cells_tpu_chips(name):
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", name, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())

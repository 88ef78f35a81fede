"""Run one cell of ``BENCHMARK.json`` once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. It needs exactly the cell's number of TPU chips (anything else:
non-zero exit, no result line), keeps JAX's compile cache at a fixed path in
the checkout, makes weights and inputs from ``--seed``, warms the cell's own
shapes, measures for ``--seconds`` and prints as its last line one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``. ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics over a window of at most ``TRACE_SECONDS``, with one
``[trace]`` line that says how much of it the device's events cover
(``device.window_s`` is the part the trace accounts for; ``device.covered_s``,
``device.traced_s`` and ``device.anchor`` say the same to the ledger).
"""

import time

_T0 = time.time()       # set-up is counted from here: before any heavy import

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: a trace of the whole run would be hundreds of MB; per-layer numbers come
#: from the first seconds of steady state
TRACE_SECONDS = 8


def run_cell(cell, seed, seconds, trace, devices, rehearsal=False):
    """The cell's runner by the ``kind`` of its traffic file; returns the
    result object without ``device``."""
    from benchmark.harness import serve, train
    from benchmark.harness.setup import setup_line
    from benchmark.harness.trace import breakdown
    from benchmark.readers import covered

    runners = {"train": train.run, "serve_open": serve.run,
               "serve_closed": serve.run}
    kind = cell.traffic["kind"]
    if kind not in runners:
        raise SystemExit(f"benchmark: traffic kind '{kind}' has no runner")
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    res = runners[kind](cell, seed, seconds, trace, devices, rehearsal)
    res["end_to_end"]["setup_s"] = res["setup_done"] - _T0
    print(setup_line(_T0, res["setup_marks"]), flush=True)

    out = {"correct": bool(res["correct"]), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": {}}
    if not trace:
        for m in cell.end_to_end:
            out["metrics"][m["name"]] = {
                "value": res["end_to_end"][m["name"]], "unit": m["unit"]}
        return out, None
    summary = res["trace"]
    ctx = {"cell": cell, "counters": res["counters"], "spans": res["spans"],
           "trace": summary, "peak": cell.peak(devices[0].device_kind)
           if devices[0].platform == "tpu" else None}
    if summary is not None:
        # the device's seconds are a share of what the trace accounts for
        print(covered.line(ctx), flush=True)
        summary["accounted_s"] = covered.accounted_s(ctx)
    for m in cell.per_layer:
        read, args = cell.reader(m["name"])
        value = read(ctx, **args)
        if value is not None:        # a reader that finds nothing says nothing
            out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    if summary is not None:
        out["breakdown"] = breakdown(summary)
    return out, summary


def device_account(summary):
    """The traced run's part of the result line's ``device``. ``busy_s`` and
    ``window_s`` are the driver's (the window as far as the trace accounts
    for it); the rest is for the ledger's reader, to tell a cut trace from an
    idle device: the seconds the device's events span, the traced window, and
    whether the clock anchor was found. Without it every reader took the
    window whole, and a cut trace reads too much work for its time."""
    lo, hi = summary["covered_ns"] or (0, 0)
    return {"busy_s": summary["busy_s"], "window_s": summary["accounted_s"],
            "covered_s": (hi - lo) / 1e9, "traced_s": summary["window_s"],
            "anchor": summary["clock_offset_ns"] is not None}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness.cell import (Cell, memory_peak_bytes, require_tpu)

    cell = Cell(args.workload)
    device = require_tpu(cell.chips)

    import jax

    from deepspeed_tpu.utils.xla_env import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    out, summary = run_cell(cell, args.seed, args.seconds, args.trace, devices)
    device["memory_peak_bytes"] = memory_peak_bytes(devices)
    if summary is not None:
        device.update(device_account(summary))
    out["device"] = device
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

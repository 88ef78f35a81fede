"""Find a cell's files by the names in ``BENCHMARK.json``; name the device."""

import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))   # benchmark/
REPO = os.path.dirname(ROOT)


class BenchmarkError(Exception):
    """A file the benchmark names is missing or malformed."""


def load_json(*parts):
    path = os.path.join(ROOT, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"missing benchmark file {path}") from None
    except json.JSONDecodeError as e:
        raise BenchmarkError(f"{path} is not JSON: {e}") from None


def load_spec(staged=False):
    """``BENCHMARK.json``; with ``staged`` also the entries of
    ``benchmark/staged.json``, which are built and rehearsed but not yet
    measured by the driver."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    if staged:
        extra = load_json("staged.json")
        for key in ("workloads", "end_to_end", "per_layer"):
            spec[key] = spec[key] + extra[key]
    return spec


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix and the
    metrics that list it."""

    def __init__(self, name, spec=None):
        spec = spec or load_spec()
        rows = [w for w in spec["workloads"] if w["name"] == name]
        if not rows:
            raise BenchmarkError(
                f"no workload '{name}' in BENCHMARK.json; it has "
                f"{[w['name'] for w in spec['workloads']]}")
        self.name = name
        self.chips = rows[0]["chips"]
        cfg = [c for c in spec["configs"] if c["name"] == rows[0]["config"]]
        if not cfg:
            raise BenchmarkError(f"workload '{name}' names config "
                                 f"'{rows[0]['config']}', which is not listed")
        path = os.path.join(REPO, cfg[0]["file"])
        self.config = load_json(os.path.relpath(path, ROOT))
        self.traffic = load_json("traffic", rows[0]["traffic"] + ".json")
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]
        self.peaks = load_json("peaks.json")

    def mix(self, rehearsal=False):
        """The traffic file's parameters; with ``rehearsal`` its tiny preset
        laid over them."""
        return {**self.traffic, **self.traffic["rehearsal"]} if rehearsal \
            else dict(self.traffic)

    def reader(self, metric):
        """(callable, args) for a per-layer metric: ``metrics/<name>.json``
        names a module under ``readers/`` with a ``read(ctx, **args)``."""
        entry = load_json("metrics", metric + ".json")
        if "reader" not in entry:
            raise BenchmarkError(f"metrics/{metric}.json names no reader")
        try:
            mod = importlib.import_module(f"benchmark.readers.{entry['reader']}")
        except ModuleNotFoundError:
            raise BenchmarkError(
                f"metrics/{metric}.json names reader '{entry['reader']}', "
                f"but there is no readers/{entry['reader']}.py") from None
        return mod.read, entry.get("args", {})

    def peak(self, device_kind):
        if device_kind not in self.peaks["devices"]:
            raise BenchmarkError(
                f"device kind '{device_kind}' is not in benchmark/peaks.json; "
                "add it with its source rather than defaulting")
        return self.peaks["devices"][device_kind]


def require_tpu(n_chips):
    """The device as JAX reports it. Exits non-zero, printing no result,
    unless JAX finds exactly ``n_chips`` TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmark: needs a TPU, JAX found platform "
                 f"'{devices[0].platform}' ({devices[0].device_kind})")
    if len(devices) != n_chips:
        sys.exit(f"benchmark: the cell needs {n_chips} chip(s), JAX found "
                 f"{len(devices)}")
    return describe_device(devices)


def describe_device(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip, as the allocator reports it
    (0 where the backend reports nothing, as on the CPU)."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

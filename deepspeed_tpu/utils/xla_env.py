"""Host-platform / XLA environment helpers shared by the driver entry points,
benches, and tests (everything that self-provisions a virtual CPU device mesh).
"""

import os

#: stability flags for the virtual CPU mesh on oversubscribed hosts:
#: - the concurrency-optimized thunk scheduler reorders independent
#:   collectives differently per device → cyclic rendezvous deadlocks
#:   (observed round 3/4); the sequential scheduler is deterministic AND
#:   faster on few-core hosts
#: - the 40 s default rendezvous termination fires spuriously when 8 device
#:   threads timeshare one vCPU under heavy programs — raise to 300 s
VIRTUAL_MESH_STABILITY_FLAGS = (
    "--xla_cpu_enable_concurrency_optimized_scheduler=false",
    "--xla_cpu_collective_call_terminate_timeout_seconds=300",
    "--xla_cpu_collective_call_warn_stuck_timeout_seconds=60",
    "--xla_cpu_collective_timeout_seconds=300",
)


def force_device_count_flags(flags: str, n: int) -> str:
    """Return ``flags`` with any existing host-platform device-count flag
    replaced by ``--xla_force_host_platform_device_count=n``."""
    kept = " ".join(
        f for f in flags.split() if "xla_force_host_platform_device_count" not in f
    )
    return (kept + f" --xla_force_host_platform_device_count={n}").strip()


def virtual_mesh_flags(flags: str, n: int) -> str:
    """Device-count flag plus the stability flags (deduplicated) — the one
    call every virtual-mesh entry point (conftest, gate, benches) should use."""
    out = force_device_count_flags(flags, n)
    for f in VIRTUAL_MESH_STABILITY_FLAGS:
        if f.split("=")[0] not in out:
            out += " " + f
    return out


#: where the persistent compile cache goes when the environment names no place:
#: one fixed path inside the checkout (the path is part of the cache's key, so
#: a directory built from a temp name, pid or time would never hit)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".dstpu_build", "jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a script; returns its
    directory. Entry points (``chip_smoke.py``, ``benchmark/run.py``, the
    examples) call this before their first compile; library code never does.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, nothing is set in
    code. Unset: ``DEFAULT_COMPILE_CACHE_DIR``. The minimum compile time for
    an entry drops from 1 s to 0 so the many small serving programs are kept
    too."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


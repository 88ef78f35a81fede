"""Host↔device transfer discipline for bench and profiling tools.

Two disciplines bound what an interrupted process can leave behind on the
host↔device link, and every bench/profiling tool in this repo uses them:

1. **Chunking** (``chunked_device_put`` / ``chunked_device_get``): never let
   more than ``MAX_INFLIGHT_BYTES`` (32 MB) of transfer be outstanding — each
   chunk is blocked on before the next is issued, so an interrupt at any
   point leaves at most one small transfer in flight.
2. **Drain-on-signal** (``install_transfer_guard``): ``timeout(1)`` and
   orchestrators send SIGTERM before SIGKILL; the guard turns SIGTERM/SIGINT
   into "drain outstanding device work (bounded), then exit" instead of
   dying with transfers queued.

Reference analogue: the AIO swapper's bounded double-buffering
(``deepspeed/runtime/swap_tensor/pipelined_optimizer_swapper.py``) applies the
same cap-in-flight principle to NVMe traffic.

Since the unified-TransferEngine refactor (docs/TRANSFER.md), the chunked
helpers here are thin delegates onto the process-wide
:class:`~deepspeed_tpu.runtime.transfer_engine.TransferEngine` staging pool —
there is exactly ONE bounded-in-flight implementation in the repo, and every
tooling transfer rides the same byte ledger (and bandwidth EMAs) as the KV
tier, swap preemption, and ZeRO offload traffic. The signal-guard semantics
below are unchanged.
"""

import signal
import sys
from typing import Any

import jax

#: hard cap on outstanding host↔device bytes for tooling transfers
#: (re-exported from the TransferEngine — the one place the cap lives)
from ..runtime.transfer_engine import MAX_INFLIGHT_BYTES, default_engine

#: how long the signal guard waits for in-flight device work before exiting
DRAIN_TIMEOUT_S = 120.0


def chunked_device_put(tree: Any, sharding=None, *,
                       limit_bytes: int = MAX_INFLIGHT_BYTES) -> Any:
    """``jax.device_put`` a pytree with bounded in-flight bytes.

    ``sharding``: None, a single Sharding applied to every leaf, or a pytree
    of Shardings matching ``tree`` (e.g. an engine's param shardings).

    Host leaves are transferred in order; whenever the running total of
    unacknowledged bytes would exceed ``limit_bytes`` the pending transfers
    are blocked on first, and leaves larger than the limit are split along
    axis 0 so no single flight exceeds the cap.  Leaves that are already
    ``jax.Array``s are resharded directly (device-side, not a host
    transfer) without chunking.  Delegates to the TransferEngine staging
    pool (``TransferEngine.put_tree``)."""
    return default_engine().put_tree(tree, sharding, limit_bytes=limit_bytes)


def chunked_device_get(tree: Any, *,
                       limit_bytes: int = MAX_INFLIGHT_BYTES) -> Any:
    """Fetch a pytree to host numpy with bounded in-flight bytes.

    Leaves larger than ``limit_bytes`` are fetched in axis-0 slices so no
    single transfer exceeds the cap (a 1 GB embedding table otherwise goes
    out as one flight).  Delegates to the
    TransferEngine (``TransferEngine.get_tree``)."""
    return default_engine().get_tree(tree, limit_bytes=limit_bytes)


_guard_installed = False


def install_transfer_guard(drain_timeout_s: float = DRAIN_TIMEOUT_S) -> None:
    """Install SIGTERM/SIGINT handlers that drain device work before exit.

    ``timeout(1)`` sends SIGTERM first; without a handler the process dies
    with its transfer queue mid-flight.  The handler blocks on outstanding async work
    in a watchdog thread (bounded by ``drain_timeout_s``), then exits 143/130
    as the signal would have.
    """
    global _guard_installed
    if _guard_installed:
        return
    _guard_installed = True

    def _handler(signum, frame):
        import threading

        print(f"[transfer-guard] signal {signum}: draining in-flight device "
              f"work (<= {drain_timeout_s:.0f}s) before exit", file=sys.stderr,
              flush=True)
        done = threading.Event()

        def _drain():
            try:
                jax.effects_barrier()
            except Exception:
                pass
            done.set()

        t = threading.Thread(target=_drain, daemon=True)
        t.start()
        done.wait(drain_timeout_s)
        print(f"[transfer-guard] drain {'complete' if done.is_set() else 'TIMED OUT'}"
              "; exiting", file=sys.stderr, flush=True)
        sys.exit(128 + signum)

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            signal.signal(sig, _handler)
        except (ValueError, OSError):  # non-main thread / unsupported
            pass

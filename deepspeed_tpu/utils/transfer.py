"""Chunked host↔device transfers for tools and checkpoint paths.

``chunked_device_put`` / ``chunked_device_get`` never let more than
``MAX_INFLIGHT_BYTES`` (32 MB) of transfer be outstanding — each chunk is
blocked on before the next is issued, so an interrupt at any point leaves at
most one small transfer in flight.

Reference analogue: the AIO swapper's bounded double-buffering
(``deepspeed/runtime/swap_tensor/pipelined_optimizer_swapper.py``) applies the
same cap-in-flight principle to NVMe traffic.

Since the unified-TransferEngine refactor (docs/TRANSFER.md), the helpers here
are thin delegates onto the process-wide
:class:`~deepspeed_tpu.runtime.transfer_engine.TransferEngine` staging pool —
there is exactly ONE bounded-in-flight implementation in the repo, and every
tooling transfer rides the same byte ledger (and bandwidth EMAs) as the KV
tier, swap preemption, and ZeRO offload traffic.
"""

from typing import Any

#: hard cap on outstanding host↔device bytes for tooling transfers
#: (re-exported from the TransferEngine — the one place the cap lives)
from ..runtime.transfer_engine import MAX_INFLIGHT_BYTES, default_engine


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

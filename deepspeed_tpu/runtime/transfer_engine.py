"""Unified async host↔device TransferEngine (docs/TRANSFER.md).

Every subsystem that moves host↔device bytes — KV demote/promote
(docs/PREFIX_CACHING.md "Two-tier cache"), swap-based preemption
(docs/SERVING.md), ZeRO offload's per-leaf gradient/parameter traffic
(docs/ZERO.md), and the tooling transfers in ``utils/transfer.py`` — goes
through ONE engine instead of carrying a private copy loop. The engine owns:

- a bounded pool of reusable host staging buffers (``acquire_staging`` /
  ``release_staging``) so steady-state paths never allocate per dispatch;
- double-buffered async D2H: ``submit_d2h`` starts ``copy_to_host_async``
  and returns an open :class:`TransferTicket`; the host sync is delayed to
  the next dispatch boundary, where ``drain_before`` materializes exactly
  the payloads that boundary depends on (the delayed-sync rule);
- batched H2D via one ``device_put`` per staged chunk (``submit_h2d``), the
  pattern the KV promote path established;
- per-direction bandwidth EMAs (``s_per_byte``) feeding the scheduler's
  swap-vs-recompute cost model;
- a byte ledger (submitted == completed + in flight, per direction) the
  ``DSTPU_SANITIZE`` checker :func:`~..analysis.sanitizer.check_transfer_ledger`
  verifies after every drain;
- an optional NVMe third tier below host RAM (:class:`NVMeStore`): prefix KV
  blocks and ZeRO optimizer shards spill to disk under the checkpoint
  layer's manifest-last + CRC durability protocol, with a 2-slot ring so a
  torn/corrupt newest write falls back to the previous complete slot.

``overlap=False`` gives the synchronous twin of every path — ``submit_d2h``
materializes immediately — so every client is A/B-testable bitwise
(reference blueprint: ZeRO-Infinity's bounded double-buffered staging,
PAPERS.md 2104.07857).

Reference analogue: ``deepspeed/runtime/swap_tensor/pipelined_optimizer_swapper.py``
(bounded double buffering) + ``deepspeed/ops/aio`` (NVMe data plane).
"""

import json
import os
from collections import OrderedDict
from typing import Any, Dict, List, Optional

import numpy as np

#: hard cap on outstanding host↔device bytes: each chunk is blocked on before
#: the next is issued, so the link never holds an unbounded queue
MAX_INFLIGHT_BYTES = 32 * 1024 * 1024

#: staging buffers kept per (shape, dtype) key — two is the double buffer
STAGING_POOL_DEPTH = 2


class TransferCorruptError(Exception):
    """An NVMe-tier read failed verification on every ring slot."""


def _nbytes(leaf) -> int:
    try:
        return int(leaf.size) * int(np.dtype(leaf.dtype).itemsize)
    except Exception:
        return 0


def blocks_crc32(arrays) -> int:
    """CRC32 over a sequence of host arrays, chained in order — the
    in-memory twin of the NVMe store's per-file ``_crc32``. Cross-engine
    KV handoff (docs/SERVING.md "Disaggregated serving") stamps every
    exported swap payload with this checksum and the importer re-verifies
    it before the blocks can reach a device pool: KV bytes are never
    trusted across an engine boundary without it, exactly like the NVMe
    tier never trusts a file past its manifest CRC."""
    import zlib

    crc = 0
    for a in arrays:
        crc = zlib.crc32(np.ascontiguousarray(a).view(np.uint8), crc)
    return crc & 0xFFFFFFFF


class TransferTicket:
    """Receipt for one submitted transfer.

    ``open`` is True while the bytes are still (possibly) in flight; the
    payload may only be read through ``TransferEngine.drain_before`` (or
    ``wait()``), which closes the ticket and settles the ledger. Reading
    ``.value`` on an open ticket is the undrained-dependent-read hazard the
    sanitizer exists to catch — under ``DSTPU_SANITIZE`` it is recorded as
    a ledger violation (and still materializes, so the failure is loud in
    the checker, not silent corruption)."""

    __slots__ = ("tid", "direction", "nbytes", "open", "buffer_key",
                 "_raw", "_result", "_engine")

    def __init__(self, engine, tid: int, direction: str, nbytes: int, raw):
        self._engine = engine
        self.tid = tid
        self.direction = direction
        self.nbytes = nbytes
        self.open = True
        #: staging-pool key this ticket pins (None when no pool buffer rides)
        self.buffer_key = None
        self._raw = raw
        self._result = None

    def wait(self):
        """Materialize this ticket's payload (closing it). Equivalent to
        ``engine.drain_before([self])[0]``."""
        return self._engine.drain_before([self])[0]

    def cancel(self):
        """Discard this transfer without reading it (the payload's owner —
        a swap entry, a host-tier block — was dropped). Settles the ledger
        into ``cancelled_bytes``; no-op on a closed ticket."""
        self._engine.cancel_ticket(self)

    @property
    def value(self):
        """The payload. On an open ticket this is an undrained dependent
        read — recorded as a ledger violation under the sanitizer."""
        if self.open:
            self._engine._record_violation(
                f"ticket {self.tid} ({self.direction}, {self.nbytes} B) "
                "read while open — dependent read without drain_before")
            return self._engine.drain_before([self])[0]
        return self._result

    def __repr__(self):  # pragma: no cover - debug aid
        state = "open" if self.open else "done"
        return (f"TransferTicket(tid={self.tid}, {self.direction}, "
                f"{self.nbytes}B, {state})")


class NVMeStore:
    """Keyed array store on NVMe under the manifest-last + CRC protocol.

    Layout per key: ``<key>.<slot>.bin`` (raw bytes) + ``<key>.<slot>.json``
    (manifest, written LAST via atomic rename, carrying the data CRC32,
    shape, dtype, and a monotonically increasing generation). ``save``
    alternates between ``ring_slots`` slots, so the previous complete
    version survives until the next one's manifest commits — a torn or
    corrupt newest write falls back one slot (``ring_fallbacks``), the same
    durable-tag discipline as the checkpoint ring
    (checkpoint_engine/native_checkpoint_engine.py). A missing manifest is
    a torn write by construction, never trusted."""

    def __init__(self, root: str, ring_slots: int = 2):
        self.root = root
        self.ring_slots = max(1, int(ring_slots))
        os.makedirs(root, exist_ok=True)
        self._gen: Dict[str, int] = {}
        self.counters = {"saves": 0, "loads": 0, "ring_fallbacks": 0,
                         "corrupt_reads": 0, "bytes_written": 0,
                         "bytes_read": 0}

    # -- protocol helpers (shared with the checkpoint layer) ------------
    @staticmethod
    def _crc32(path: str) -> int:
        from .checkpoint_engine.native_checkpoint_engine import _file_crc32

        return _file_crc32(path)

    @staticmethod
    def _manifest_dump(obj: dict, path: str) -> None:
        from .checkpoint_engine.native_checkpoint_engine import \
            _atomic_json_dump

        _atomic_json_dump(obj, path)

    def _paths(self, key: str, slot: int):
        base = os.path.join(self.root, f"{key}.{slot}")
        return base + ".bin", base + ".json"

    # -------------------------------------------------------------------
    def save(self, key: str, arr: np.ndarray) -> None:
        """Write ``arr`` under ``key``: data first, manifest LAST."""
        arr = np.ascontiguousarray(arr)
        gen = self._gen.get(key, -1) + 1
        slot = gen % self.ring_slots
        data, manifest = self._paths(key, slot)
        # remove the slot's old manifest first: if the data write below is
        # torn, a stale manifest must not validate the new bytes
        try:
            os.remove(manifest)
        except FileNotFoundError:
            pass
        with open(data, "wb") as f:
            f.write(arr.tobytes())
        self._manifest_dump({
            "crc32": self._crc32(data), "nbytes": int(arr.nbytes),
            "shape": list(arr.shape), "dtype": str(arr.dtype), "gen": gen,
        }, manifest)
        self._gen[key] = gen
        self.counters["saves"] += 1
        self.counters["bytes_written"] += int(arr.nbytes)

    def _load_slot(self, key: str, slot: int) -> Optional[np.ndarray]:
        data, manifest = self._paths(key, slot)
        try:
            with open(manifest) as f:
                meta = json.load(f)
        except (OSError, json.JSONDecodeError):
            return None  # torn write: manifest never committed
        try:
            if self._crc32(data) != meta["crc32"]:
                return None
            arr = np.fromfile(data, dtype=np.dtype(meta["dtype"]))
        except (OSError, ValueError, KeyError, TypeError):
            return None
        if arr.nbytes != meta["nbytes"]:
            return None
        return arr.reshape(meta["shape"])

    def load(self, key: str) -> np.ndarray:
        """Read ``key``'s newest complete version; fall back one ring slot
        per corrupt/torn read; raise :class:`TransferCorruptError` when no
        slot verifies."""
        slots = []
        for slot in range(self.ring_slots):
            _, manifest = self._paths(key, slot)
            try:
                with open(manifest) as f:
                    slots.append((json.load(f).get("gen", -1), slot))
            except (OSError, json.JSONDecodeError):
                continue
        first = True
        for _, slot in sorted(slots, reverse=True):  # newest gen first
            arr = self._load_slot(key, slot)
            if arr is not None:
                if not first:
                    self.counters["ring_fallbacks"] += 1
                self.counters["loads"] += 1
                self.counters["bytes_read"] += int(arr.nbytes)
                return arr
            self.counters["corrupt_reads"] += 1
            first = False
        raise TransferCorruptError(
            f"NVMe store: no complete slot verifies for key {key!r} "
            f"({len(slots)} manifest(s) found)")

    def delete(self, key: str) -> None:
        for slot in range(self.ring_slots):
            for path in self._paths(key, slot):
                try:
                    os.remove(path)
                except FileNotFoundError:
                    pass
        self._gen.pop(key, None)

    def has(self, key: str) -> bool:
        return any(os.path.exists(self._paths(key, s)[1])
                   for s in range(self.ring_slots))


class TransferEngine:
    """The single owner of host↔device byte movement (docs/TRANSFER.md)."""

    def __init__(self, *, overlap: bool = True,
                 limit_bytes: int = MAX_INFLIGHT_BYTES,
                 nvme_dir: Optional[str] = None, nvme_ring_slots: int = 2):
        self.overlap = bool(overlap)
        self.limit_bytes = int(limit_bytes)
        self.nvme = NVMeStore(nvme_dir, nvme_ring_slots) if nvme_dir else None
        self._next_tid = 0
        #: open tickets in submit order (FIFO — cap-in-flight drains oldest)
        self._open: "OrderedDict[int, TransferTicket]" = OrderedDict()
        # the byte ledger: per direction, submitted == completed + inflight
        # at every drain boundary (check_transfer_ledger)
        self.submitted_bytes = {"d2h": 0, "h2d": 0}
        self.completed_bytes = {"d2h": 0, "h2d": 0}
        self.cancelled_bytes = {"d2h": 0, "h2d": 0}
        self.inflight_bytes = {"d2h": 0, "h2d": 0}
        self.submitted_transfers = {"d2h": 0, "h2d": 0}
        #: wall seconds per byte, EMA per direction (0.0 = unmeasured);
        #: d2h is measured at the delayed sync (the blocking cost the
        #: dispatch boundary actually pays), h2d around the device_put
        self._ema_s_per_byte = {"d2h": 0.0, "h2d": 0.0}
        # staging pool: (shape, dtype) -> list of [buffer, owner_tid|None]
        self._staging: Dict[tuple, List[list]] = {}
        #: sanitizer-recorded ledger violations (read+cleared by
        #: check_transfer_ledger; recorded only under DSTPU_SANITIZE)
        self.violations: List[str] = []

    # ------------------------------------------------------------------
    # ledger / sanitizer support
    # ------------------------------------------------------------------
    def _record_violation(self, msg: str) -> None:
        from ..analysis.sanitizer import sanitize_enabled

        if sanitize_enabled():
            self.violations.append(msg)

    def ledger(self) -> Dict[str, Dict[str, int]]:
        """Snapshot of the byte ledger (for dashboards and the checker)."""
        return {
            "submitted": dict(self.submitted_bytes),
            "completed": dict(self.completed_bytes),
            "cancelled": dict(self.cancelled_bytes),
            "inflight": dict(self.inflight_bytes),
        }

    def s_per_byte(self, direction: str) -> float:
        """Bandwidth EMA (wall seconds per byte); 0.0 until measured. The
        scheduler's swap-vs-recompute cost model seeds from this, so one
        client's measured traffic prices every client's next decision."""
        return self._ema_s_per_byte[direction]

    def _note(self, direction: str, nbytes: int, dt: float) -> None:
        if nbytes <= 0 or dt <= 0.0:
            return
        spb = dt / nbytes
        prev = self._ema_s_per_byte[direction]
        self._ema_s_per_byte[direction] = spb if prev == 0.0 \
            else 0.5 * prev + 0.5 * spb

    def monitor_events(self, prefix: str, step: int = 0):
        """``(label, value, step)`` gauge tuples for MonitorMaster —
        bandwidth EMAs and cumulative ledger bytes under ``<prefix>/``."""
        out = []
        for d in ("d2h", "h2d"):
            spb = self._ema_s_per_byte[d]
            out.append((f"{prefix}/{d}_bytes_per_s",
                        (1.0 / spb) if spb > 0 else 0.0, step))
            out.append((f"{prefix}/{d}_submitted_bytes",
                        float(self.submitted_bytes[d]), step))
            out.append((f"{prefix}/{d}_completed_bytes",
                        float(self.completed_bytes[d]), step))
        if self.nvme is not None:
            for k, v in self.nvme.counters.items():
                out.append((f"{prefix}/nvme_{k}", float(v), step))
        return out

    # ------------------------------------------------------------------
    # staging pool
    # ------------------------------------------------------------------
    def _alloc_buffer(self, shape, dtype) -> np.ndarray:
        # pool-miss allocation lives OUTSIDE the hot functions: steady state
        # reuses pooled buffers and never reaches here
        return np.empty(shape, np.dtype(dtype))

    def acquire_staging(self, shape, dtype) -> np.ndarray:
        """Check a host staging buffer out of the bounded pool. A buffer is
        re-issued only after ``release_staging`` — handing out one whose
        owning ticket is still open would let an in-flight transfer read
        bytes a new client is overwriting (the hazard the ledger's
        no-reissue rule mechanizes)."""
        key = (tuple(shape), np.dtype(dtype).str)
        pool = self._staging.setdefault(key, [])
        for entry in pool:
            if entry[1] is None:
                entry[1] = True  # checked out (owner bound at submit)
                return entry[0]
        if len(pool) >= STAGING_POOL_DEPTH:
            self._record_violation(
                f"staging pool for {key} exhausted ({len(pool)} buffers all "
                "checked out) — a buffer was re-requested while its ticket "
                "is open")
        buf = self._alloc_buffer(shape, dtype)
        pool.append([buf, True])
        return buf

    def release_staging(self, buf: np.ndarray) -> None:
        """Return a staging buffer to the pool (its transfer has settled)."""
        key = (tuple(buf.shape), buf.dtype.str)
        for entry in self._staging.get(key, ()):
            if entry[0] is buf:
                entry[1] = None
                return

    def staging_buffers(self) -> int:
        return sum(len(v) for v in self._staging.values())

    # ------------------------------------------------------------------
    # D2H: async gather with the sync delayed to the dispatch boundary
    # ------------------------------------------------------------------
    def submit_d2h(self, arr) -> TransferTicket:
        """Start one device→host transfer; returns an open ticket.

        With ``overlap`` on, ``copy_to_host_async`` is dispatched and the
        host sync is DELAYED — the caller reads the payload at its next
        dispatch boundary via ``drain_before``, by which time the copy has
        long completed in the background. With ``overlap`` off (the A/B
        twin) the payload materializes here, synchronously; the bytes are
        identical either way."""
        nb = _nbytes(arr)
        if self.inflight_bytes["d2h"] + nb > self.limit_bytes:
            # cap-in-flight: settle the oldest transfers until there is room
            self.drain_oldest(nb)
        tid = self._next_tid
        self._next_tid += 1
        t = TransferTicket(self, tid, "d2h", nb, arr)
        self.submitted_bytes["d2h"] += nb
        self.submitted_transfers["d2h"] += 1
        if self.overlap and hasattr(arr, "copy_to_host_async"):
            arr.copy_to_host_async()  # dispatch-only: never blocks the step
            self.inflight_bytes["d2h"] += nb
            self._open[tid] = t
        else:
            import time

            t0 = time.perf_counter()
            t._result = np.asarray(arr)  # dstpu-lint: ignore[DSTPU001]
            self._note("d2h", nb, time.perf_counter() - t0)
            t._raw = None
            t.open = False
            self.completed_bytes["d2h"] += nb
        return t

    # ------------------------------------------------------------------
    # H2D: batched device_put (+ optional sharding), settled at submit
    # ------------------------------------------------------------------
    def submit_h2d(self, host_arr, sharding=None) -> TransferTicket:
        """Ship one host buffer to the device (one ``device_put``). The
        source buffer is safe to reuse on return (device_put snapshots host
        memory), so the ticket settles immediately — H2D needs no delayed
        sync, only the staging/batching discipline."""
        import time

        import jax

        nb = _nbytes(host_arr)
        tid = self._next_tid
        self._next_tid += 1
        t = TransferTicket(self, tid, "h2d", nb, None)
        self.submitted_bytes["h2d"] += nb
        self.submitted_transfers["h2d"] += 1
        t0 = time.perf_counter()
        if isinstance(host_arr, np.ndarray) and jax.default_backend() == "cpu":
            # the CPU client hands back a VIEW of an aligned numpy buffer
            # (whatever ``may_alias`` says), and callers refill theirs
            host_arr = host_arr.copy()
        t._result = jax.device_put(host_arr, sharding) if sharding is not None \
            else jax.device_put(host_arr)
        self._note("h2d", nb, time.perf_counter() - t0)
        t.open = False
        self.completed_bytes["h2d"] += nb
        return t

    # ------------------------------------------------------------------
    # the dispatch boundary: settle exactly what the next step depends on
    # ------------------------------------------------------------------
    def _settle(self, t: TransferTicket):
        import time

        t0 = time.perf_counter()
        # THE designed delayed sync of the engine (docs/TRANSFER.md): by the
        # dispatch boundary the async copy has completed in the background,
        # so this materialization is a wait-free view in the common case
        t._result = np.asarray(t._raw)  # dstpu-lint: ignore[DSTPU001]
        self._note("d2h", t.nbytes, time.perf_counter() - t0)
        t._raw = None
        t.open = False
        self._open.pop(t.tid, None)
        self.inflight_bytes["d2h"] -= t.nbytes
        self.completed_bytes["d2h"] += t.nbytes
        if t.buffer_key is not None:
            self.release_staging_by_key(t.buffer_key, t.tid)

    def release_staging_by_key(self, key, tid) -> None:
        for entry in self._staging.get(key, ()):
            if entry[1] == tid:
                entry[1] = None

    def drain_before(self, dependents) -> List[Any]:
        """Settle every ticket in ``dependents`` and return their payloads,
        in order. Non-ticket entries (already-host arrays, NVMe loads, raw
        device arrays from a pre-engine path) pass through unchanged — so
        client code can mix sources and still satisfy the drained-read
        rule. This is the ONE call that may precede a dependent read."""
        out = []
        for d in dependents:
            if isinstance(d, TransferTicket):
                if d.open:
                    self._settle(d)
                out.append(d._result)
            else:
                out.append(d)
        return out

    def drain_oldest(self, need_bytes: int) -> None:
        """Settle open tickets oldest-first until ``need_bytes`` fits under
        the in-flight cap."""
        while self._open and (self.inflight_bytes["d2h"] + need_bytes
                              > self.limit_bytes):
            self._settle(next(iter(self._open.values())))

    def drain_all(self) -> None:
        """Settle every open ticket (quiesce — shutdown/rebuild paths)."""
        while self._open:
            self._settle(next(iter(self._open.values())))

    def cancel_ticket(self, t: TransferTicket) -> None:
        """Drop an open transfer whose payload no longer has an owner (a
        flushed swap entry, a destroyed host-tier block). The bytes move to
        the ``cancelled`` ledger bucket — conservation stays
        submitted == completed + cancelled + inflight. No-op when closed."""
        if not t.open:
            return
        self._open.pop(t.tid, None)
        self.inflight_bytes[t.direction] -= t.nbytes
        self.cancelled_bytes[t.direction] += t.nbytes
        t.open = False
        t._raw = None
        t._result = None
        if t.buffer_key is not None:
            self.release_staging_by_key(t.buffer_key, t.tid)

    def cancel_all(self) -> None:
        """Cancel every open ticket (device-loss rebuild: the source arrays
        died with the incarnation, so settling them is neither possible nor
        wanted)."""
        while self._open:
            self.cancel_ticket(next(iter(self._open.values())))

    # ------------------------------------------------------------------
    # pytree transfers (utils/transfer.py delegates here — the repo's one
    # bounded-in-flight implementation)
    # ------------------------------------------------------------------
    def put_tree(self, tree: Any, sharding=None, *,
                 limit_bytes: Optional[int] = None) -> Any:
        """``jax.device_put`` a pytree with bounded in-flight bytes (the
        chunked_device_put contract: per-leaf shardings, axis-0 splitting of
        oversized single-device leaves, device-side reshard of jax.Array
        leaves)."""
        import jax

        limit = self.limit_bytes if limit_bytes is None else int(limit_bytes)
        leaves, treedef = jax.tree.flatten(tree)
        shard_leaves = None
        if sharding is not None and not isinstance(sharding,
                                                   jax.sharding.Sharding):
            shard_leaves = jax.tree.flatten(
                sharding,
                is_leaf=lambda s: isinstance(s, jax.sharding.Sharding))[0]
            if len(shard_leaves) != len(leaves):
                raise ValueError(
                    f"sharding pytree has {len(shard_leaves)} leaves for a "
                    f"{len(leaves)}-leaf tree")
        out = []
        pending: list = []
        inflight = 0

        def _ship(arr, sh):
            # ledger-accounted single flight (device_put snapshots the host
            # buffer, so the ticket settles at submit — the cap below tracks
            # device-side completion via block_until_ready)
            return self.submit_h2d(arr, sh)._result

        def _drain():
            nonlocal inflight
            for p in pending:
                jax.block_until_ready(p)  # dstpu-lint: ignore[DSTPU001]
            pending.clear()
            inflight = 0

        for i, leaf in enumerate(leaves):
            sh = shard_leaves[i] if shard_leaves is not None else sharding
            if isinstance(leaf, jax.Array):
                # device-side reshard, not a host transfer: no chunking
                out.append(jax.device_put(leaf, sh))
                continue
            nb = _nbytes(leaf)
            # host leaf wrap (jax arrays took the reshard branch above): a
            # list/scalar cast, not a device sync
            arr = np.asarray(leaf)  # dstpu-lint: ignore[DSTPU001]
            # chunk-split only when the leaf lands on ONE device: assembling a full unsharded copy on the default device
            # would defeat a multi-device sharding and OOM the chip that
            # sharding exists to protect
            single_dev = sh is None or len(sh.device_set) == 1
            if single_dev and nb > limit and arr.ndim >= 1 and arr.shape[0] > 1:
                rows = max(1, int(arr.shape[0] * limit / nb))
                parts = []
                for s in range(0, arr.shape[0], rows):
                    _drain()
                    # chunks ride unsharded (a chunk's row count need not
                    # divide the mesh axis); the leaf reshards device-side
                    p = _ship(arr[s:s + rows], None)
                    pending.append(p)
                    inflight += _nbytes(p)
                    parts.append(p)
                _drain()
                import jax.numpy as jnp

                chunked = jnp.concatenate(parts, axis=0)
                out.append(jax.device_put(chunked, sh)
                           if sh is not None else chunked)
                continue
            if inflight + nb > limit:
                _drain()
            p = _ship(arr, sh)
            pending.append(p)
            inflight += nb
            out.append(p)
        _drain()
        return jax.tree.unflatten(treedef, out)

    def get_tree(self, tree: Any, *,
                 limit_bytes: Optional[int] = None) -> Any:
        """Fetch a pytree to host numpy with bounded in-flight bytes (the
        chunked_device_get contract: per-leaf readiness block, axis-0 slices
        for oversized leaves)."""
        import jax

        limit = self.limit_bytes if limit_bytes is None else int(limit_bytes)
        leaves, treedef = jax.tree.flatten(tree)
        out = []
        for leaf in leaves:
            # block per leaf first: device_get of an unready array queues the
            # full transfer; readiness keeps the transfer queue to one chunk
            jax.block_until_ready(leaf)  # dstpu-lint: ignore[DSTPU001]
            nb = _nbytes(leaf)
            shape = getattr(leaf, "shape", ())
            if nb > limit and len(shape) >= 1 and shape[0] > 1:
                rows = max(1, int(shape[0] * limit / nb))
                parts = []
                for s in range(0, shape[0], rows):
                    parts.append(self.drain_before(
                        [self.submit_d2h(leaf[s:s + rows])])[0])
                out.append(np.concatenate(parts, axis=0))
            else:
                out.append(self.drain_before([self.submit_d2h(leaf)])[0])
        return jax.tree.unflatten(treedef, out)


_default: Optional[TransferEngine] = None


def default_engine() -> TransferEngine:
    """Process-wide engine for tooling transfers (utils/transfer.py)."""
    global _default
    if _default is None:
        _default = TransferEngine()
    return _default

"""Continuous-batching inference engine (FastGen equivalent).

Reference: ``deepspeed/inference/v2/engine_v2.py`` — ``InferenceEngineV2.put:107``
runs prefill+decode of mixed requests in one forward over a ragged batch;
``engine_factory.py:67 build_hf_engine``; blocked-KV flash kernels.

TPU re-design (SURVEY.md §7 "hard parts" #1): XLA needs static shapes, so the
ragged batch becomes **one fixed-shape program**:

- KV cache: one blocked pool (L, kvh, num_blocks, block_size, row — kv-head-major
  for the Pallas paged-decode kernel; ``ops/transformer/paged_attention.py`` owns
  the layout) with per-sequence block tables (reference ``BlockedKVCache``) —
  total KV memory is shared across sequences, so many short sequences fit where
  dedicated slots would not.
- every step is ONE compiled ragged forward over ``token_budget`` token-rows:
  prefill-chunk tokens and decode tokens mixed, each row with its sequence's
  block table and its own position (long prompts go through in ``prefill_chunk``
  pieces so decode latency stays bounded); a pure decode round runs the same
  program at ``max_seqs`` rows.

``put(uids, tokens)`` matches the reference surface: new sequences join, all
live sequences advance one token, and per-uid last-token logits come back.
"""

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...analysis.program_audit import audited_jit
from ...utils import tracing
from ...analysis.sanitizer import checked_cache_cls, sanitize_enabled
from ...models.transformer import sample_or_argmax
from ...ops.transformer import paged_attention
from ...resilience.errors import (ContextOverflowError, EngineUsageError,
                                  PoolExhaustedError)
from ...utils.logging import log_dist
from ..config import DeepSpeedInferenceConfig
from .ragged_manager import BlockedKVCache, DSStateManager

#: history placeholder of a position whose token was fed on the device from
#: an unfetched round; the predecessor's ``fetch`` writes the token in
FED_ON_DEVICE = -1


def feed_layout(rows: int, max_seqs: int, max_blocks_per_seq: int,
                stateful: bool, window_blocks: int = 0):
    """Where each field of a ragged step's feed lies in its ONE int32
    buffer (docs/SERVING.md "One packed feed"): ``({name: (slice, shape,
    dtype)}, length)``, a function of shapes the engine knows and nothing
    else. Every field is 32 bits wide; the two float32 sampling arrays are
    views of the same words on the host (``.view``) and bitcasts of them in
    the program. ``src_rows`` names, for a row fed on the device, its row of
    the preceding round's result (-1: the host's ``ids``); ``row_slots`` (a
    stateful model's) is empty for any other model. ``wtables`` and
    ``wbase``, a row's table of the window class of blocks (``window_blocks``
    entries) and the position its first entry starts at, are fields only of
    a model with a bounded class (``TransformerConfig.bounded_cache``):
    every other model's feed is word for word what it was."""
    T, M = rows, max_seqs
    i32, f32 = np.int32, np.float32
    fields = (("ids", (T, 1), i32), ("tables", (T, max_blocks_per_seq), i32),
              ("starts", (T,), i32), ("logit_rows", (M,), i32),
              ("slots", (M,), i32), ("seeds", (M,), i32), ("poss", (M,), i32),
              ("temps", (M,), f32), ("top_ks", (M,), i32),
              ("top_ps", (M,), f32), ("src_rows", (T,), i32),
              ("row_slots", (T if stateful else 0,), i32))
    if window_blocks:
        fields += (("wtables", (T, window_blocks), i32), ("wbase", (T,), i32))
    layout, at = {}, 0
    for name, shape, dtype in fields:
        end = at + int(np.prod(shape))
        layout[name] = (slice(at, end), shape, dtype)
        at = end
    return layout, at


#: the window fields of a feed whose layout has none (every model without a
#: bounded class of blocks): empty, and no words of the buffer
_NO_WINDOW = {"wtables": np.zeros((0, 0), np.int32),
              "wbase": np.zeros((0,), np.int32)}


class PackedFeed(NamedTuple):
    """A ragged step's feed on the host: the fields the engine fills, in
    :func:`feed_layout`'s order, each a view into ``buf``, the one int32
    array that is transferred."""
    ids: np.ndarray
    tables: np.ndarray
    starts: np.ndarray
    logit_rows: np.ndarray
    slots: np.ndarray
    seeds: np.ndarray
    poss: np.ndarray
    temps: np.ndarray
    top_ks: np.ndarray
    top_ps: np.ndarray
    src_rows: np.ndarray
    row_slots: np.ndarray
    wtables: np.ndarray
    wbase: np.ndarray
    buf: np.ndarray


def unpack_feed(feed, layout):
    """The fields of a packed feed inside the program: static slices of the
    one int32 array, the float fields bitcast back bit for bit."""
    return {name: (jax.lax.bitcast_convert_type(feed[words], jnp.float32)
                   if dtype == np.float32 else feed[words]).reshape(shape)
            for name, (words, shape, dtype) in layout.items()}


class DecodeDispatchHandle:
    """One in-flight decode round (docs/SERVING.md pipelined dispatch):
    :meth:`InferenceEngineV2.decode_dispatch` returns this instead of host
    tokens, deferring the device→host transfer so the caller can dispatch
    the NEXT round — fed on the device from this one's result rows — before
    it reads this one. :meth:`fetch` is the drain boundary: it blocks on the
    device result (the one designed transfer the synchronous path pays
    inline) and yields ``{uid: int token}``.

    The handle is single-shot state, not a future registry: at most TWO may
    be unfetched at once (the engine stages rounds from two alternating
    scratch sets), and each is fetched exactly once per dispatch."""

    __slots__ = ("uids", "span", "_dev", "_out", "_eng", "_disp", "_set",
                 "_fed")

    def __init__(self, uids: List[int], dev, eng=None, disp=None,
                 scratch_set: int = 0):
        self.uids = uids          # row order of the dispatched program
        self.span = 1             # cache positions each row advanced
        self._dev = dev           # device logits/token rows, unfetched
        self._out: Optional[Dict[int, int]] = None
        self._eng = eng           # owner: cleared of this handle at fetch
        self._disp = disp         # the dispatch's span: gets the fetched counts
        self._set = scratch_set   # which scratch set staged this round
        #: (history, index, row) of each successor row fed on the device
        #: from this round: its history entry is written at fetch
        self._fed: List[Tuple[List[int], int, int]] = []

    def fetch(self) -> Dict[int, int]:
        """Block on the in-flight program and return its sampled tokens.
        Idempotent: later calls return the cached host result."""
        if self._out is None:
            # THE deferred transfer: the synchronous twin pays this same
            # np.asarray inline inside _put_paged; here it lands only after
            # the next round was dispatched, so the device never idles on it
            with tracing.span("engine.fetch") as fetched:
                lg = np.asarray(self._dev)  # dstpu-lint: ignore[DSTPU001]
            if self._eng is not None:
                self._eng._mark_fetched(fetched)
            self._out = {uid: int(lg[i]) for i, uid in enumerate(self.uids)}
            for history, at, row in self._fed:
                # a rollback may have truncated the successor's position
                if at < len(history) and history[at] == FED_ON_DEVICE:
                    history[at] = int(lg[row])
            self._fed = []
            if self._eng is not None and self._disp is not None:
                self._eng._note_moe_rows(self._disp, lg)
            self._dev = None
        if self._eng is not None:
            if self in self._eng._unfetched:
                self._eng._unfetched.remove(self)
            self._eng = None
        return self._out


class InferenceEngineV2:
    """Continuous-batching engine over a ``TransformerLM``."""

    def __init__(self, model, params=None, *, max_seqs: Optional[int] = None,
                 max_seq_len: Optional[int] = None, prefill_chunk: int = 256,
                 dtype=jnp.float32, paged: bool = True, block_size: int = 64,
                 num_blocks: Optional[int] = None, token_budget: int = 0,
                 prefix_cache: bool = True, decode_horizon: int = 1,
                 host_tier_blocks: int = 0, transfer_overlap: bool = True,
                 nvme_tier_blocks: int = 0,
                 nvme_tier_dir: Optional[str] = None):
        if not paged:
            raise ValueError(
                "paged=False: the slot-pooled KV cache was removed (PR 32); "
                "InferenceEngineV2 serves from the blocked pool only — drop "
                "the argument")
        # the constructor's own account (docs/TRACING.md "Set-up and
        # recompiles"): one always-on `engine.init` record, three phases
        init = tracing.Phases("engine.init", engine="serve")
        self.model = model
        self.cfg = model.config
        # the sequences share one block pool, so 32 slots cost little
        if max_seqs is None:
            max_seqs = 32
        self.max_seqs = max_seqs
        self.max_seq_len = max_seq_len or model.config.max_seq_len
        self.prefill_chunk = prefill_chunk
        self.dtype = dtype
        # every engine step is ONE compiled ragged forward over
        # exactly token_budget token-rows (prefill chunks and decodes mixed —
        # reference engine_v2.py:107 put); the budget is the latency knob.
        # Default: enough rows for a full decode round plus prefill headroom
        self.token_budget = token_budget or max(max_seqs, min(prefill_chunk, 256))
        # fused multi-token decode (docs/SERVING.md): the ONE extra horizon
        # the engine may compile besides 1 — horizons are restricted to
        # {1, decode_horizon} so the compiled-program bound grows by exactly
        # one shape (fixed-shape trace discipline, see fused_cache_size)
        if decode_horizon < 1:
            raise ValueError(f"decode_horizon must be >= 1, got {decode_horizon}")
        self.decode_horizon = decode_horizon
        #: some layers keep a state slot a sequence beside the pool
        #: (``TransformerConfig.cache_kinds``): the second kind of cache
        self._stateful = bool(getattr(model.config, "holds_state", False))
        #: some layers' blocks are a bounded class, freed behind a window
        #: (``TransformerConfig.bounded_cache``): a block then no longer
        #: holds a token in every layer
        self._windowed = bool(getattr(model.config, "bounded_cache", False))
        if self._windowed and (prefix_cache or decode_horizon > 1
                               or host_tier_blocks):
            raise ValueError(
                "a model with a bounded class of KV blocks (window layers "
                f"of {model.config.sliding_window} tokens) is served with "
                "prefix_cache=False, decode_horizon=1 and no host tier: a "
                "block freed behind the window cannot be shared, copied on "
                "write, swapped or exported, and a rolled-back draft token "
                "may need a block that was freed")
        if self._stateful and (prefix_cache or decode_horizon > 1):
            raise ValueError(
                "a model with state-slot layers "
                f"({model.config.cache_kinds}) is served with "
                "prefix_cache=False and decode_horizon=1: a prefix hit hands "
                "out KV blocks without the other layers' state at that "
                "position, and a rolled-back draft token cannot be taken out "
                "of a state")
        if params is None:
            params = model.init_params(jax.random.PRNGKey(0))
        self.params = self._cast_params(params)
        init.mark("weights")
        #: rolling-weight-update tag (docs/SERVING.md engine pool): opaque
        #: label of the weights currently served, set by ``load_params``
        self.weights_version = None
        self.state = DSStateManager(max_seqs, self.max_seq_len)
        self.flush_noops = 0  # idempotent-flush debug counter (see flush())
        self.rebuilds = 0     # engine-loss hot rebuilds (see rebuild())
        #: rows deferred out of a ragged dispatch because their blocks could
        #: not be allocated (the pool served the rows that fit instead of
        #: failing the whole step) — chunked-prefill pressure diagnostics
        self.plan_deferrals = 0
        #: block allocations (every class's) / COW copies / window-class
        #: blocks freed behind their windows, as the previous dispatch saw them
        self._count_marks = (0, 0, 0)
        #: the most blocks of each class in use at any dispatch so far
        #: (``window`` stays 0 without a bounded class): what
        #: ``benchmark/class_peak.py`` sizes a cell's ``num_blocks`` from
        self.block_peaks = {"full": 0, "window": 0}
        self._ragged_fn = None
        self._cow_fn = None
        self._fused_fn = None
        self._verify_fn = None
        #: {program: what the model said of it where it was traced}
        self._program_attrs = {}
        # per-shape host scratch for the ragged/fused step inputs: reused
        # (zeroed in place) instead of np.zeros every step — the steady-state
        # decode loop must not pay a fresh allocation per dispatch. Safe to
        # reuse even if jax aliases the host buffer: every step materializes
        # its outputs (np.asarray) before the next step refills the scratch,
        # so the previous dispatch has fully consumed its inputs
        # (``decode_dispatch`` does not, and alternates between two sets).
        self._scratch: Dict[Tuple, Tuple[np.ndarray, ...]] = {}
        #: the unfetched ``decode_dispatch`` rounds, oldest first: at most
        #: two, each staged from its own scratch set
        self._unfetched: List[DecodeDispatchHandle] = []
        #: what the ragged program reads as the preceding round's result
        #: where there is none (a mixed step, a pipe restart): zeros, cached
        self._no_prev = None
        #: when the last fetch returned (tracing.clock_ns; 0: unrecorded)
        #: and why the device will have waited for the next launch, where
        #: the scheduler said (note_idle): the ends of an ``engine.bubble``
        self._fetched_ns = 0
        self._idle_cause: Optional[str] = None
        #: host arrays transferred and compiled programs called, counted
        #: where the calls are made; ``engine.dispatch`` carries what a step
        #: added since the step before (``feed_arrays``, ``launches``)
        self._feeds = self._launches = 0
        self._call_marks = (0, 0)
        self.prefix_cache = bool(prefix_cache)
        # host-RAM KV tier (docs/PREFIX_CACHING.md "Two-tier cache"): spill
        # capacity in blocks under the device pool. 0 = single-tier (the
        # pre-tier behavior, byte-identical). Needs the prefix cache: the
        # content index is what makes demoted blocks findable again.
        self.host_tier_blocks = host_tier_blocks if self.prefix_cache else 0
        # NVMe third tier below host RAM (docs/TRANSFER.md): host-LRU
        # eviction demotes prefix KV blocks to disk instead of dropping
        # them. Needs the host tier (it spills FROM it) and a directory.
        self.nvme_tier_blocks = nvme_tier_blocks \
            if (self.host_tier_blocks and nvme_tier_dir) else 0
        #: the engine's one owner of host↔device byte movement
        #: (docs/TRANSFER.md): async D2H with delayed sync, batched H2D,
        #: bandwidth EMAs, byte ledger, optional NVMe store. overlap=False
        #: is the synchronous A/B twin of every tier/swap path.
        from ...runtime.transfer_engine import TransferEngine

        self.transfer = TransferEngine(
            overlap=transfer_overlap,
            nvme_dir=nvme_tier_dir if self.nvme_tier_blocks else None)
        self._tier_gather_fn = None
        self._tier_scatter_fn = None
        #: swapped-out preemption victims: uid -> (block payloads, history,
        #: seen_tokens). Host-side cache only — engine loss, weight swaps,
        #: and flushes drop entries; the scheduler then replays from its
        #: journal exactly as before swap-preemption existed.
        self._swaps: Dict[int, Tuple] = {}
        #: uids whose swap entry arrived from ANOTHER engine via
        #: ``import_swap`` (disaggregated handoff, docs/SERVING.md) — when
        #: such an entry is dropped without being swapped in (flush, rebuild,
        #: weight swap), the import was orphaned and ``orphan_drops`` counts
        #: it; a handoff that lands via ``swap_in`` leaves no trace here
        self._swap_imports: set = set()
        self.swap_stats = {"swap_out": 0, "swap_in": 0,
                           "swap_out_blocks": 0, "swap_in_blocks": 0,
                           "swap_export": 0, "swap_import": 0,
                           "export_blocks": 0, "import_blocks": 0,
                           "orphan_drops": 0}
        # per-request sampling (docs/SAMPLING.md): duck-typed params records
        # (the engine reads .seed/.temperature/.top_k/.top_p — it never
        # imports serve) ride every greedy-mode dispatch as RUNTIME per-row
        # arrays, so sampled rows add zero compiled traces. Bias rows are
        # device-resident in a (max_seqs, V) per-SLOT pool updated only at
        # (re-)registration through one traced-slot scatter program — the
        # steady-state decode loop ships no bias bytes.
        self._sampling: Dict[int, object] = {}
        self._bias_rows: Dict[int, np.ndarray] = {}   # uid -> host row
        self._bias_slots: Dict[int, int] = {}         # uid -> installed slot
        self._bias_pool = None                        # lazy (max_seqs, V) f32
        self._bias_set_fn = None
        self._bias_zero: Optional[np.ndarray] = None
        # paged-block pool (reference BlockedKVCache): total KV memory is
        # num_blocks*block_size tokens shared across sequences instead of
        # max_seqs*max_seq_len dedicated slots
        max_blocks_per_seq = -(-self.max_seq_len // block_size)
        #: (blocks, bound, table width) of the window class, or None
        self._window_spec = None
        if self._windowed:
            # the most a sequence holds: the window, one step's tokens (a
            # chunk fits what the mixed step leaves beside its one-token
            # rows) and the two provisional tokens a pipelined round may
            # still take back, in whole blocks with both ends partial
            bound = self.cfg.sliding_window
            tile = getattr(model, "segment_tile", 1)
            chunk = min(prefill_chunk, self.token_budget if tile == 1 else
                        (self.token_budget - max_seqs) // tile * tile)
            width = min((bound + 1 + max(chunk, 1)) // block_size + 2,
                        max_blocks_per_seq)
            classes = sorted(self.cfg.class_layers)
            if num_blocks is not None and (
                    not isinstance(num_blocks, dict)
                    or not set(num_blocks) <= set(classes)):
                raise ValueError(
                    f"num_blocks={num_blocks!r}: a model with window layers "
                    f"takes a count a class of KV blocks, {{class: blocks}} "
                    f"over {classes} (a class left out gets room for "
                    "max_seqs sequences)")
            given = num_blocks or {}
            num_blocks = given.get("full", 1 + max_seqs * max_blocks_per_seq)
            self._window_spec = (given.get("window", 1 + max_seqs * width),
                                 bound, width)
        elif num_blocks is None:
            num_blocks = 1 + max_seqs * max_blocks_per_seq  # = slot capacity
        if sanitize_enabled():
            # checked mode (docs/ANALYSIS.md): the sanitizing cache
            # re-verifies refcount conservation, COW exclusivity, and
            # index↔pool consistency after every allocator op
            self.block_mgr = checked_cache_cls()(
                num_blocks, block_size, max_blocks_per_seq,
                prefix_cache=self.prefix_cache,
                host_tier_blocks=self.host_tier_blocks,
                state_slots=max_seqs if self._stateful else 0,
                window=self._window_spec,
                descs=lambda: self.state.seqs.values())
        else:
            self.block_mgr = BlockedKVCache(
                num_blocks, block_size, max_blocks_per_seq,
                prefix_cache=self.prefix_cache,
                host_tier_blocks=self.host_tier_blocks,
                state_slots=max_seqs if self._stateful else 0,
                window=self._window_spec)
        self.block_mgr.demote_fn = self._demote_block
        self._bind_nvme_tier()
        init.mark("state")
        #: the pool; of a model with a bounded class {class: pool}
        self.kv = model.init_kv_pool(self._pool_blocks(), block_size,
                                     dtype=dtype)
        #: the slot arrays of a stateful model (``init_state_cache``: a slot
        #: a sequence, row 0 the trash slot), donated to and returned by the
        #: ragged program beside the pool
        self.slot_cache = model.init_state_cache(
            max_seqs, self.max_seq_len, dtype=dtype) if self._stateful else None
        #: where a pool row splits into its key and value parts
        #: (``TransformerConfig.kv_row``; the block programs' payload
        #: format follows from it)
        self._k_width = self.cfg.kv_row[0]
        #: rows of one chunk-segment tile of the ragged program (1: a
        #: prefill chunk is one-token rows like any other). A model with
        #: tiles lays a mixed step out as ``max_seqs`` one-token rows,
        #: then the chunks' segments, each from a tile boundary
        self._seg_tile = getattr(model, "segment_tile", 1)
        #: the counts the ragged program returns behind its greedy tokens,
        #: by name (``engine.dispatch`` attrs): (rows, rows_max) of the held
        #: experts, the chosen and the context blocks of sparse attention
        self._step_counts = tuple(getattr(model, "step_counts", ()))
        self._moe_stats = bool(self._step_counts)
        if self._seg_tile > 1 and (self.token_budget - max_seqs
                                   < self._seg_tile):
            raise ValueError(
                f"token_budget {self.token_budget} leaves no segment tile "
                f"of {self._seg_tile} rows beside the {max_seqs} "
                "one-token rows of a mixed step")
        #: device bytes of one block's K+V across all layers — the unit
        #: of every tier/swap byte counter and of the scheduler's
        #: swap-vs-recompute cost model
        self.block_bytes = int(self._full_pool().nbytes) // num_blocks
        log_dist(
            f"InferenceEngineV2(paged): blocks={self._pool_blocks()}"
            f"x{block_size} "
            f"seqs<={max_seqs} ctx={self.max_seq_len} chunk={prefill_chunk} "
            f"token_budget={self.token_budget} "
            f"decode_horizon={self.decode_horizon} "
            f"prefix_cache={'on' if self.prefix_cache else 'off'} "
            f"host_tier_blocks={self.host_tier_blocks}",
            ranks=[0],
        )
        init.mark("pool")
        init.close()

    def _pool_blocks(self):
        """The block count ``init_kv_pool`` takes: one, or one a class."""
        n = self.block_mgr.num_blocks
        if not self._windowed:
            return n
        return {"full": n, "window": self.block_mgr.window.num_blocks}

    def _full_pool(self):
        """The full class's pool (the only one, for most models)."""
        return self.kv["full"] if self._windowed else self.kv

    def _cast_params(self, params):
        def cast(path, a):
            # keep weight-only-quantized leaves in their storage dtype
            # (int8 codes / fp32 group scales — ops/quantizer/woq.py)
            a = jnp.asarray(a)
            key = getattr(path[-1], "key", "") if path else ""
            if jnp.issubdtype(a.dtype, jnp.integer) or (
                    isinstance(key, str) and key.endswith("::scale")):
                return a
            return a.astype(self.dtype)

        return jax.tree_util.tree_map_with_path(cast, params)

    def load_params(self, params, version=None) -> None:
        """Hot weight swap (docs/SERVING.md engine pool rolling update):
        replace the served parameters with a new pytree of the SAME
        structure and shapes, cast exactly like construction. The compiled
        programs take params as a runtime argument, so same shapes means
        zero recompilation — the ragged/fused/verify dispatch bounds are
        untouched. The caller (the pool's drain protocol) guarantees no
        sequence is resident: KV produced under the old weights must never
        mix with logits from the new ones."""
        if self.state.n_active:
            raise EngineUsageError(
                f"load_params with {self.state.n_active} resident "
                "sequence(s) — drain the engine first (their cached KV "
                "was computed under the old weights)")
        self.params = self._cast_params(params)
        self.weights_version = version
        # the prefix content index holds KV computed under the OLD
        # weights — serving it to post-swap prompts would silently mix
        # weight versions. flush_cache drops BOTH tiers: a host-tier
        # survivor would promote stale old-weights KV straight back in.
        self.block_mgr.flush_cache()
        # swapped-out victims' KV is old-weights too: drop the payloads
        # so re-admission replays their prompts under the new weights
        # (cancelling their open tickets settles the byte ledger)
        self._drop_swaps()

    def prefix_probe(self, tokens) -> int:
        """Read-only placement probe: leading full blocks of ``tokens``
        present in this engine's prefix content index (0 with the prefix
        cache off). The router's affinity score."""
        if not self.prefix_cache:
            return 0
        return self.block_mgr.probe(tokens)

    def set_kv_owner(self, uid: int, owner: str) -> None:
        """Tag ``uid``'s KV blocks with a tenant id so the block manager can
        bill its cached prefixes against that tenant's quota."""
        self.block_mgr.set_seq_owner(uid, owner)

    def set_kv_quota(self, owner: str, max_blocks) -> None:
        """Cap ``owner``'s at-rest prefix-cache blocks (``None`` lifts the
        cap). The scheduler re-pushes quotas after every rebuild — the fresh
        block manager starts with an empty ledger."""
        self.block_mgr.set_owner_quota(owner, max_blocks)

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------
    def _get_ragged(self):
        """THE serving program: one fixed-shape ragged forward.

        Each of the ``token_budget`` rows is one token of some sequence —
        prefill-chunk tokens and decode tokens mixed freely (the reference's
        ragged batch, ``engine_v2.py:107 put`` + ``ragged/ragged_wrapper.py``).
        A row carries its sequence's block table and its own position; padding
        rows carry the all-zero table (trash block 0) and are ignored.

        TWO fixed shapes per greedy mode, ever: the full-budget mixed program
        and (when ``token_budget > max_seqs``) a ``max_seqs``-row decode
        program — a pure decode round must not pay the prefill budget's
        padded rows, which dominate steady-state serving latency. (A workload
        mixing greedy and full-logit steps holds both variants of each shape:
        ≤ 4 compiled traces, still O(1) in the load.)
        """
        if self._ragged_fn is not None:
            return self._ragged_fn
        model = self.model

        def ragged(params, pool, feed, prev, bias_pool, greedy,
                   slot_cache=None):
            # the step's whole feed is ONE int32 array (feed_layout), and the
            # run-ahead merge is the program's first line: a row fed on the
            # device takes its token from ``prev``, the preceding round's
            # unfetched result (zeros, and every src -1, where there is none)
            f = unpack_feed(feed, self._feed_layout(
                self._feed_rows(feed.shape[0]))[0])
            src = f["src_rows"][:, None]
            ids = jnp.where(src >= 0,
                            prev[jnp.maximum(src, 0)].astype(jnp.int32),
                            f["ids"])
            # ids (T, 1): every row is its own length-1 "sequence" against the
            # shared pool; only the (max_seqs,) logit_rows are projected
            # through the vocab head (reference ragged_ops/logits_gather)
            # a mixed step of a model with segment tiles: rows from max_seqs
            # on are chunk segments (see _build_ragged_step)
            # a stateful model's slot arrays ride beside the pool, each row
            # naming its sequence's slot: (logits, pool, slot arrays[, counts])
            segs = self._seg_tile > 1 and ids.shape[0] > self.max_seqs
            with self._saying("ragged"):
                lg, pool, *stats = model.forward_paged(
                    params, ids, pool, f["tables"], f["starts"],
                    logit_rows=f["logit_rows"],
                    rows_apart=self._rows_apart(ids.shape[0]),
                    **({"seg_from": self.max_seqs} if segs else {}),
                    **({"moe_stats": True} if self._moe_stats and greedy
                       else {}),
                    **({"state": slot_cache, "row_slots": f["row_slots"]}
                       if self._stateful else {}),
                    **({"window": (f["wtables"], f["wbase"])}
                       if self._windowed else {}))
            if self._stateful:
                slot_cache, *stats = stats
                pool = (pool, slot_cache)
            if greedy:
                # device-side token selection: ship (R,) token ids instead of
                # (R, V) fp32 logits — the host↔device transfer is the serving
                # loop's latency floor on remote-device transports. Sampling
                # params are RUNTIME per-row arrays (all-zero = plain argmax,
                # bit-identical to the legacy greedy program; a batch-level
                # cond inside sample_or_argmax skips the sampling math when
                # every row is greedy), so sampled traffic adds no trace.
                toks = sample_or_argmax(lg + bias_pool[f["slots"]], f["seeds"],
                                        f["poss"], f["temps"], f["top_ks"],
                                        f["top_ps"])
                # the expert counts ride behind the tokens: one array, one
                # transfer
                return (jnp.concatenate([toks, stats[0]]) if stats
                        else toks), pool
            return lg, pool

        fn = audited_jit("engine_v2.ragged", ragged, max_traces=4,
                         donate_argnums=(1, 6) if self._stateful else (1,),
                         static_argnums=(5,))
        self._ragged_fn = fn
        return fn

    def _saying(self, program: str):
        """Around the model's call where ``program`` is traced: what the
        model says of the path its shapes took (``head``: the vocabulary
        head as the ``stream``ing kernel or as ``xla``'s product) lands in
        ``_program_attrs[program]`` and from there on the program's
        ``engine.enqueue`` spans, as ``head_loss`` does in training."""
        return tracing.program_attrs(
            self._program_attrs.setdefault(program, {}))

    def _feed_layout(self, rows: int):
        """:func:`feed_layout` of this engine's ragged step of ``rows``."""
        return feed_layout(rows, self.max_seqs,
                           self.block_mgr.max_blocks_per_seq, self._stateful,
                           self._window_spec[2] if self._windowed else 0)

    def _feed_rows(self, length: int) -> int:
        """The rows of the step whose packed feed is ``length`` words: the
        ragged program has two shapes, so a feed has one of two lengths."""
        for rows in (self.max_seqs, self.token_budget):
            if self._feed_layout(rows)[1] == length:
                return rows
        raise ValueError(f"no ragged step has a feed of {length} words")

    def _prev_shape(self) -> Tuple[int]:
        """Shape of a greedy decode round's result, which the next round
        reads as ``prev``: a token a row, then the step's counts."""
        return (self.max_seqs + len(self._step_counts),)

    def _rows_apart(self, rows: int) -> bool:
        """Is the ragged program of ``rows`` padded rows the decode round's,
        in which no two rows write one pool block? The engine builds the
        steps, so it is the one who knows (``paged_attention.write_rows``):
        the ``max_seqs``-row shape is only ever given one row a sequence
        (:meth:`_build_ragged_step`, :meth:`decode_dispatch`), and a
        sequence's write block is its own, a shared prefix block being
        copied on write before the dispatch. Where the budget leaves no
        second shape, every step is built on the mixed one."""
        return self.token_budget > self.max_seqs and rows == self.max_seqs

    def lower_ragged(self, rows: int, greedy: bool = True):
        """The paged program lowered at ``rows`` token-rows, as a
        ``jax.stages.Lowered``: ``token_budget`` rows is the mixed
        prefill+decode shape, ``max_seqs`` rows the decode round. ``.compile()``
        ``.as_text()`` shows whether the paged-decode kernel is in it. Nothing
        runs and the pool is not donated."""
        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        return self._get_ragged().lower(
            self.params, self.kv, i32(self._feed_layout(rows)[1]),
            i32(*self._prev_shape()), self._bias(), greedy,
            *((self.slot_cache,) if self._stateful else ()))

    def _get_cow(self):
        """Single fixed-shape block-copy program for copy-on-write: duplicate
        pool block ``src`` into ``dst``. ``src``/``dst`` are traced scalars, so
        this compiles exactly ONCE regardless of which blocks are copied — it
        does not add to the ragged-step trace count and cannot retrace under
        load (the fixed-shape discipline; see ``ragged_cache_size``)."""
        if self._cow_fn is None:

            def cow(kv, src, dst):
                return kv.at[:, :, dst].set(kv[:, :, src])  # block axis = 2

            self._cow_fn = audited_jit("engine_v2.cow", cow,
                                       donate_argnums=(0,))
        return self._cow_fn

    # ------------------------------------------------------------------
    # host-RAM KV tier: data movement (docs/PREFIX_CACHING.md)
    # ------------------------------------------------------------------
    def _get_tier_gather(self):
        """Single fixed-shape block-gather program: pull pool block ``src``
        out as one (2, L, kvh, BS, hd) array (K stacked on V: the payload
        format, whatever the pool's own row layout is). ``src`` is a
        traced scalar — ONE compiled trace serves every demotion and
        swap-out, so tier traffic adds data movement, not programs. No
        donation: the pool stays live (the gather is dispatched alongside
        decode steps that keep consuming it)."""
        if self._tier_gather_fn is None:

            def gather(kv, src):  # a closure: this engine's own trace cache
                return paged_attention.get_block(kv, src, self._k_width)

            self._tier_gather_fn = audited_jit("engine_v2.tier_gather",
                                               gather)
        return self._tier_gather_fn

    def _get_tier_scatter(self):
        """Single fixed-shape block-scatter program: write row ``row`` of a
        staged (M, 2, L, kvh, BS, hd) batch into pool block ``dst``. Both
        indices are traced scalars and the batch capacity M is fixed
        (``max_blocks_per_seq``), so this compiles exactly ONCE — promotions
        and swap-ins of any size ride the same trace."""
        if self._tier_scatter_fn is None:

            def scatter(kv, batch, row, dst):
                blk = jax.lax.dynamic_index_in_dim(batch, row, 0,
                                                   keepdims=False)
                return paged_attention.set_block(kv, dst, blk)

            self._tier_scatter_fn = audited_jit("engine_v2.tier_scatter",
                                                scatter, donate_argnums=(0,))
        return self._tier_scatter_fn

    def _tier_buf_shape(self):
        """Shape of the fixed-capacity staging batch for promotion/swap-in —
        (max_blocks_per_seq, 2, L, kvh, BS, hd). Fixed capacity keeps the
        scatter program's batch shape constant (no retrace) and bounds
        staging memory; larger batches go in chunks. The buffer itself lives
        in the TransferEngine's bounded pool (docs/TRANSFER.md)."""
        return ((self.block_mgr.max_blocks_per_seq,)
                + paged_attention.payload_shape(self.kv, self._k_width))

    def _bind_nvme_tier(self) -> None:
        """Wire the allocator's NVMe spill hooks to the TransferEngine's
        store (no-op with the tier off)."""
        if not self.nvme_tier_blocks:
            return
        self.block_mgr.nvme_blocks = self.nvme_tier_blocks
        self.block_mgr.spill_fn = self._spill_block
        self.block_mgr.load_fn = self._load_block
        self.block_mgr.drop_fn = self._drop_block

    def _spill_block(self, hid: int, payload) -> bool:
        """Host-LRU eviction hook: demote one host-tier payload to the NVMe
        store instead of destroying it. Materializing the (long-completed)
        async gather here is the tier's designed sync — it was going to
        happen at eviction anyway; the bytes now land on disk under the
        manifest-last + CRC protocol instead of dying."""
        arr = self.transfer.drain_before([payload])[0]
        if arr is None:
            return False
        self.transfer.nvme.save(f"kvblock_{-hid}", arr)
        return True

    def _load_block(self, hid: int):
        """Promotion hook for NVMe-resident blocks; None on a corrupt file —
        the allocator drops the entry and the chain truncates there, so the
        tokens recompute through normal prefill / journal replay (the
        existing fallback paths; content is never trusted past its CRC)."""
        from ...runtime.transfer_engine import TransferCorruptError

        try:
            return self.transfer.nvme.load(f"kvblock_{-hid}")
        except TransferCorruptError:
            return None

    def _drop_block(self, hid: int) -> None:
        self.transfer.nvme.delete(f"kvblock_{-hid}")

    def _demote_block(self, block: int):
        """The allocator's ``demote_fn``: async-gather one pool block to the
        host through the TransferEngine. Dispatch-only — the gather program
        is enqueued and the device→host copy started without waiting
        (``submit_d2h`` → ``copy_to_host_async``), so demotion never blocks
        the decode dispatch behind it. The payload (an open TransferTicket)
        materializes lazily at promotion/spill time via ``drain_before``."""
        blk = self._launch(self._get_tier_gather(), self.kv, jnp.int32(block))
        return self.transfer.submit_d2h(blk)

    def _scatter_blocks(self, payloads, dsts) -> None:
        """Land host payloads in pool blocks ``dsts``: drain the payload
        tickets at this dispatch boundary (THE tier's designed sync — the
        copies were started at demotion/swap-out time and have long
        completed), stage up to ``max_blocks_per_seq`` of them in a pooled
        staging buffer, ship the batch with ONE device_put per dispatch
        chunk (never one per block), then scatter each row with the single
        compiled traced-index program."""
        if not payloads:
            return
        te = self.transfer
        buf = te.acquire_staging(self._tier_buf_shape(), self.kv.dtype)
        try:
            cap = buf.shape[0]
            scatter = self._get_tier_scatter()
            for base in range(0, len(dsts), cap):
                chunk = range(base, min(base + cap, len(dsts)))
                # payloads are TransferTickets (demote/swap-out) or host
                # arrays (NVMe loads) — drain_before settles both kinds
                vals = te.drain_before([payloads[j] for j in chunk])
                for i, v in enumerate(vals):
                    buf[i] = v
                batch = te.submit_h2d(buf).value
                self._feeds += 1
                for i, j in enumerate(chunk):
                    self.kv = self._launch(scatter, self.kv, batch,
                                           jnp.int32(i), jnp.int32(dsts[j]))
        finally:
            te.release_staging(buf)

    def _drain_promotions(self) -> None:
        """Land every queued host→device promotion before the next compiled
        step reads the pool. A content-index hit on a demoted block rekeys
        the bookkeeping synchronously (see ``BlockedKVCache._promote``) and
        queues the data movement here — batched, one ``device_put`` per
        dispatch chunk."""
        if not self.host_tier_blocks:
            return
        orders = self.block_mgr.take_promotions()
        if orders:
            self._scatter_blocks([p for p, _ in orders],
                                 [d for _, d in orders])
            if sanitize_enabled():
                from ...analysis.sanitizer import check_transfer_ledger

                check_transfer_ledger(self.transfer)

    # ------------------------------------------------------------------
    # swap-based preemption (docs/SERVING.md)
    # ------------------------------------------------------------------
    @staticmethod
    def _cancel_payloads(payloads) -> None:
        """Drop swap payloads without landing them — open TransferTickets
        settle into the ledger's cancelled bucket (host arrays pass)."""
        for p in payloads:
            cancel = getattr(p, "cancel", None)
            if cancel is not None:
                cancel()

    def _drop_swaps(self) -> None:
        """Drop every swap-store entry, cancelling its in-flight tickets.
        Imported handoff entries dropped here never reached ``swap_in`` —
        each is an orphaned export, counted in ``orphan_drops``."""
        for payloads, _, _ in self._swaps.values():
            self._cancel_payloads(payloads)
        self.swap_stats["orphan_drops"] += len(self._swap_imports)
        self._swap_imports.clear()
        self._swaps.clear()

    def swap_resident(self, uid: int) -> bool:
        """True when ``uid``'s KV is parked in the host swap store."""
        return uid in self._swaps

    def swap_out(self, uid: int) -> bool:
        """Preempt a live sequence by swapping its KV to the host instead of
        discarding it: async-gather every held block, then flush the
        sequence normally (slot + blocks reclaimed). Returns False — and
        does nothing — when swapping does not apply (tier off, unknown uid,
        pending prefill, or uncommitted speculation); the caller falls back
        to plain flush-preemption + journal replay. The swap store is a
        cache, never a source of truth: re-admission works identically if
        the entry has vanished."""
        if not self.host_tier_blocks or self._stateful or self._windowed:
            # a state slot is not swapped, nor a class whose blocks were
            # freed behind the window: recompute
            return False
        d = self.state.seqs.get(uid)
        if d is None or not d.at_rest:
            return False
        gather = self._get_tier_gather()
        # dispatch-only, like demotion: each block rides an open ticket;
        # the sync is delayed to swap-in's drain_before
        payloads = [self.transfer.submit_d2h(gather(self.kv, jnp.int32(b)))
                    for b in d.blocks]
        entry = (payloads, list(d.history), d.seen_tokens)
        self.flush(uid)
        self._swaps[uid] = entry
        self.swap_stats["swap_out"] += 1
        self.swap_stats["swap_out_blocks"] += len(payloads)
        return True

    def swap_in(self, uid: int) -> bool:
        """Re-admit a swapped-out sequence by block copy instead of prompt
        replay: allocate blocks, land the payloads (one ``device_put`` per
        dispatch chunk), restore the descriptor exactly as it was at
        swap-out, and re-register — the dedup pass folds the sequence back
        onto canonical index blocks, restoring any sharing the swap
        flattened. Returns False (with the entry consumed and all partial
        state rolled back) when no slot or not enough blocks are free; the
        caller replays the prompt instead — dropping the entry rather than
        retrying it avoids swap-thrash under sustained pressure."""
        entry = self._swaps.pop(uid, None)
        if entry is None:
            return False
        self._swap_imports.discard(uid)  # landing — the import is not orphaned
        payloads, history, seen = entry
        if not self.state.can_allocate():
            self._cancel_payloads(payloads)
            return False
        desc = self.state.get_or_create_sequence(uid)
        try:
            self.block_mgr.ensure(desc, seen)
        except (PoolExhaustedError, ContextOverflowError):
            self.block_mgr.free(desc)
            self.state.flush_sequence(uid)
            self._cancel_payloads(payloads)
            return False
        assert len(desc.blocks) == len(payloads), \
            f"uid {uid}: swap-in geometry drift"
        self._drain_promotions()  # keep pool writes in queue order
        self._scatter_blocks(payloads, desc.blocks)
        if sanitize_enabled():
            from ...analysis.sanitizer import check_transfer_ledger

            check_transfer_ledger(self.transfer)
        desc.history = list(history)
        desc.seen_tokens = seen
        desc.n_indexed = 0
        if self.prefix_cache:
            self.block_mgr.register(desc)
        if self._bias_rows:
            self._install_bias(desc)  # re-bind bias to the fresh slot
        self.swap_stats["swap_in"] += 1
        self.swap_stats["swap_in_blocks"] += len(payloads)
        return True

    # ------------------------------------------------------------------
    # cross-engine KV handoff (docs/SERVING.md "Disaggregated serving")
    # ------------------------------------------------------------------
    def export_ready(self, uid: int) -> bool:
        """True when ``uid``'s KV could be exported right now: either
        already parked in the swap store, or live and at rest (no pending
        prefill, no uncommitted speculation, holding blocks). A False here
        is a deferral signal, never an error — the disaggregated pool
        re-checks next step."""
        if self._stateful or self._windowed:
            # a state slot is not exported, nor blocks that no longer hold
            # every layer's tokens
            return False
        if uid in self._swaps:
            return True
        d = self.state.seqs.get(uid)
        return d is not None and d.at_rest

    def export_swap(self, uid: int):
        """Pull ``uid``'s at-rest KV OUT of this engine for a cross-engine
        handoff: gather every held block to the host (riding the same async
        D2H path as swap-out), materialize the payloads (the handoff's one
        designed sync — the blocks leave this process, so the tickets
        cannot stay open), flush the sequence, and return a self-describing
        payload dict stamped with a CRC32 over the block bytes — the
        importer verifies it before the KV can reach another device pool,
        the same never-trust-past-the-checksum discipline as the NVMe tier.

        Handles both residencies: a swap-store entry (preempted victim) is
        drained and exported directly; a live at-rest sequence is gathered
        then flushed. Returns ``None`` — and leaves the engine unchanged,
        except that an unsettleable swap entry is dropped — when export
        does not apply (unknown uid, pending prefill,
        uncommitted speculation): the caller falls back to journal replay,
        so like the swap store itself this path is an optimization, never
        a source of truth."""
        from ...runtime.transfer_engine import blocks_crc32

        entry = self._swaps.pop(uid, None)
        if entry is not None:
            self._swap_imports.discard(uid)
            payloads, history, seen = entry
            blocks = self.transfer.drain_before(payloads)
        else:
            d = self.state.seqs.get(uid)
            if d is None or not d.at_rest:
                return None
            gather = self._get_tier_gather()
            tickets = [self.transfer.submit_d2h(gather(self.kv,
                                                       jnp.int32(b)))
                       for b in d.blocks]
            blocks = self.transfer.drain_before(tickets)
            history, seen = list(d.history), d.seen_tokens
            self.flush(uid)
        if any(b is None for b in blocks):
            return None  # a payload failed to settle — caller replays
        nbytes = int(sum(int(b.nbytes) for b in blocks))
        self.swap_stats["swap_export"] += 1
        self.swap_stats["export_blocks"] += len(blocks)
        return {
            "uid": uid,
            "blocks": list(blocks),
            "history": list(history),
            "seen_tokens": int(seen),
            "nbytes": nbytes,
            "crc32": blocks_crc32(blocks),
            "block_shape": tuple(self._tier_buf_shape()[1:]),
            "dtype": str(np.dtype(self.kv.dtype)),
        }

    def import_swap(self, uid: int, payload) -> int:
        """Install an exported payload from ANOTHER engine into this
        engine's swap store, from where the normal ``swap_in`` re-admission
        path lands it on the device pool. Validates before anything is
        installed — a rejected import leaves this engine untouched:

        - double import (``uid`` already swap-resident) and import over a
          live sequence raise :class:`EngineUsageError` — each would make
          one uid resident in two stores, the exactly-one-owner invariant
          ``check_disagg_ownership`` enforces;
        - geometry drift (block shape/dtype vs this pool, block count vs
          ``blocks_needed(seen_tokens)``) raises :class:`EngineUsageError`
          — the pools are incompatible and a scatter would corrupt KV;
        - a CRC32 mismatch raises ``TransferCorruptError`` — the caller
          degrades the handoff to journal replay.

        Returns the payload byte count (ledger-conservation bookkeeping)."""
        from ...runtime.transfer_engine import (TransferCorruptError,
                                                blocks_crc32)

        if uid in self._swaps:
            raise EngineUsageError(
                f"uid {uid}: double import — already swap-resident here",
                uid=uid)
        if uid in self.state.seqs:
            raise EngineUsageError(
                f"uid {uid}: import over a live sequence — the uid would "
                "be resident in two stores", uid=uid)
        blocks = payload["blocks"]
        seen = int(payload["seen_tokens"])
        shape = tuple(self._tier_buf_shape()[1:])
        dtype = np.dtype(self.kv.dtype)
        need = self.block_mgr.blocks_needed(seen)
        if len(blocks) != need or len(blocks) > self.block_mgr.max_blocks_per_seq:
            raise EngineUsageError(
                f"uid {uid}: import geometry drift — {len(blocks)} blocks "
                f"for {seen} tokens (this pool needs {need}, cap "
                f"{self.block_mgr.max_blocks_per_seq})", uid=uid)
        for b in blocks:
            if tuple(b.shape) != shape or np.dtype(b.dtype) != dtype:
                raise EngineUsageError(
                    f"uid {uid}: import geometry drift — block "
                    f"{tuple(b.shape)}/{b.dtype} vs pool {shape}/{dtype}",
                    uid=uid)
        if blocks_crc32(blocks) != int(payload["crc32"]):
            raise TransferCorruptError(
                f"uid {uid}: handoff payload failed CRC verification")
        self._swaps[uid] = (list(blocks), list(payload["history"]), seen)
        self._swap_imports.add(uid)
        self.swap_stats["swap_import"] += 1
        self.swap_stats["import_blocks"] += len(blocks)
        return int(payload["nbytes"])

    def _get_fused(self):
        """THE fused decode program: one compiled ``lax.scan`` over
        ``decode_horizon`` rounds for the full ``max_seqs`` row batch
        (inactive rows carry the all-zero table → trash block 0). Compiled
        for exactly ONE horizon (the engine's ``decode_horizon``), so it adds
        exactly one shape to the compiled-program bound. Sampling params
        ride as runtime per-row arrays with the per-position key folded
        INSIDE the scan (docs/SAMPLING.md) — all-zero rows select argmax,
        bit-identical to the legacy greedy program, and no second trace
        ever exists."""
        if self._stateful or self._windowed:
            raise EngineUsageError(
                "fused multi-token decode and speculative verification are "
                "not wired for a model with state-slot layers or a bounded "
                "class of KV blocks")
        if self._fused_fn is None:
            model = self.model
            K = self.decode_horizon

            def fused(params, pool, toks, tables, starts,
                      slots, seeds, temps, top_ks, top_ps, bias_pool):
                with self._saying("fused"):
                    return model.decode_paged_multi(
                        params, pool, toks, tables, starts, K,
                        sampling=(seeds, temps, top_ks, top_ps,
                                  bias_pool[slots]))

            self._fused_fn = audited_jit("engine_v2.fused", fused,
                                         donate_argnums=(1,))
        return self._fused_fn

    def _get_verify(self):
        """THE speculative-verification program: the target model over
        ``(max_seqs, decode_horizon)`` proposed-token segments in one
        position-parallel forward, per-position target selection out
        (docs/SERVING.md). Like the fused program it is compiled for exactly
        ONE shape — the engine's ``decode_horizon`` — so it adds one trace
        to the compiled-program bound (``verify_cache_size <= 1``). Sampled
        rows get the target's own counter-based per-position sample at
        every draft position (rejection sampling's deterministic
        specialization, docs/SAMPLING.md); all-zero sampling rows select
        argmax, bit-identical to the legacy program."""
        if self._stateful or self._windowed:
            return self._get_fused()    # raises: no rollback out of a state
        if self._verify_fn is None:
            model = self.model

            def verify(params, pool, segs, tables, starts,
                       slots, seeds, temps, top_ks, top_ps, bias_pool):
                with self._saying("verify"):
                    return model.verify_paged_multi(
                        params, pool, segs, tables, starts,
                        sampling=(seeds, temps, top_ks, top_ps,
                                  bias_pool[slots]))

            self._verify_fn = audited_jit("engine_v2.verify", verify,
                                          donate_argnums=(1,))
        return self._verify_fn

    # ------------------------------------------------------------------
    # per-request sampling state (docs/SAMPLING.md)
    # ------------------------------------------------------------------
    def _bias(self):
        """Lazy device-resident (max_seqs, vocab) f32 per-SLOT bias pool.
        Zero rows are the common case and leave selection untouched
        (``argmax(lg + 0) == argmax(lg)`` bitwise on token ids)."""
        if self._bias_pool is None:
            self._bias_pool = jnp.zeros(
                (self.max_seqs, self.cfg.vocab_size), jnp.float32)
        return self._bias_pool

    def _get_bias_set(self):
        """Single traced-slot row-scatter program for the bias pool — like
        the COW program, ONE compiled trace serves every slot, so bias
        installs never add to the step-program bound."""
        if self._bias_set_fn is None:

            def setrow(bp, slot, row):
                return bp.at[slot].set(row)

            self._bias_set_fn = audited_jit("engine_v2.bias_set", setrow,
                                            donate_argnums=(0,))
        return self._bias_set_fn

    def _zero_row(self) -> np.ndarray:
        if self._bias_zero is None:
            self._bias_zero = np.zeros(self.cfg.vocab_size, np.float32)
        return self._bias_zero

    def _install_bias(self, d) -> None:
        """Scatter ``d.uid``'s pending bias row into its slot's pool row,
        once per (uid, slot) binding — re-registration after preemption,
        swap-in, or rebuild re-installs into the new slot."""
        row = self._bias_rows.get(d.uid)
        if row is None or self._bias_slots.get(d.uid) == d.slot:
            return
        self._bias_pool = self._get_bias_set()(self._bias(),
                                               jnp.int32(d.slot), row)
        self._bias_slots[d.uid] = d.slot

    def _drop_bias(self, uid: int) -> None:
        slot = self._bias_slots.pop(uid, None)
        if slot is not None and self._bias_pool is not None:
            self._bias_pool = self._get_bias_set()(
                self._bias(), jnp.int32(slot), self._zero_row())

    def set_sampling(self, uid: int, params, bias_row=None) -> None:
        """Register (or with ``params=None`` clear) a request's sampling
        state before its tokens are fed. ``params`` is duck-typed — the
        engine reads ``.seed``/``.temperature``/``.top_k``/``.top_p`` —
        so the serving layer owns the record type. ``bias_row`` is the
        combined logit-bias/processor row ((vocab,) f32) or None; it is
        installed into the slot pool at registration (and re-installed on
        every slot re-binding). Cleared automatically by :meth:`flush`;
        re-admission re-registers, which is what keeps every replay path's
        sampled continuation bitwise (the keys depend only on seed and
        absolute position, both replay-derived)."""
        if params is None:
            self._sampling.pop(uid, None)
            self._bias_rows.pop(uid, None)
            self._drop_bias(uid)
            return
        self._sampling[uid] = params
        if bias_row is None:
            self._bias_rows.pop(uid, None)
            self._drop_bias(uid)
        else:
            self._bias_rows[uid] = np.asarray(bias_row, np.float32)
            d = self.state.seqs.get(uid)
            if d is not None:
                self._install_bias(d)

    def refresh_bias(self, uid: int, bias_row) -> None:
        """Replace a resident request's bias row (dynamic logit processors
        recompute per committed token). Forces a re-scatter even when the
        slot binding is unchanged."""
        if bias_row is None:
            self._bias_rows.pop(uid, None)
            self._drop_bias(uid)
            return
        self._bias_rows[uid] = np.asarray(bias_row, np.float32)
        self._bias_slots.pop(uid, None)
        d = self.state.seqs.get(uid)
        if d is not None:
            self._install_bias(d)

    def _fill_sampling(self, d, i, slots, seeds, temps, top_ks, top_ps,
                       poss=None, pos=0) -> None:
        """Fill row ``i`` of the per-dispatch sampling scratch from
        ``d.uid``'s registered params (zeros — plain argmax — otherwise)."""
        slots[i] = d.slot
        sp = self._sampling.get(d.uid)
        if sp is not None:
            seeds[i] = sp.seed
            temps[i] = sp.temperature
            top_ks[i] = sp.top_k
            top_ps[i] = sp.top_p
            if poss is not None:
                poss[i] = pos

    def _scratch_for(self, key: Tuple, shapes,
                     dtypes=None) -> Tuple[np.ndarray, ...]:
        """Per-shape preallocated host arrays (int32 unless ``dtypes``
        overrides per buffer), zeroed in place."""
        bufs = self._scratch.get(key)
        if bufs is None:
            bufs = tuple(
                np.zeros(s, np.int32 if dtypes is None else dtypes[i])
                for i, s in enumerate(shapes))
            self._scratch[key] = bufs
        else:
            for a in bufs:
                a.fill(0)
        return bufs

    def _feed_scratch(self, key: Tuple, rows: int) -> PackedFeed:
        """The packed feed of a ragged step of ``rows`` (:func:`feed_layout`),
        zeroed in place: its fields, each a view into the one int32 buffer
        that is transferred (the float fields as ``.view(np.float32)``), so
        filling a field fills the buffer and nothing is copied on the host."""
        feed = self._scratch.get(key)
        if feed is None:
            layout, length = self._feed_layout(rows)
            buf = np.zeros(length, np.int32)
            feed = self._scratch[key] = PackedFeed(buf=buf, **{
                **_NO_WINDOW,
                **{name: buf[words].view(dtype).reshape(shape)
                   for name, (words, shape, dtype) in layout.items()}})
        else:
            feed.buf.fill(0)
        feed.src_rows.fill(-1)   # no row is fed on the device
        return feed

    def _enqueue_ragged(self, disp, rows: int, feed: np.ndarray, prev,
                        greedy: bool):
        """Hand one ragged step to the device: ONE transfer (the packed
        feed) and ONE program launch, for the decode round and the mixed
        step alike. ``prev``: the preceding round's unfetched result, which
        the program merges the fed tokens from (None: there is none).
        Returns the program's first result, still on the device."""
        fn = self._get_ragged()
        with tracing.span("engine.enqueue") as sp:
            if prev is None:
                if self._no_prev is None:
                    self._no_prev = jnp.zeros(self._prev_shape(), jnp.int32)
                prev = self._no_prev
            self._feeds += 1
            args = (self.params, self.kv, jax.device_put(feed), prev,
                    self._bias(), greedy,
                    *((self.slot_cache,) if self._stateful else ()))
            if disp.recording:
                tracing.note_program("engine_v2.ragged", fn, args,
                                     key=(rows, greedy))
            out = self._keep_caches(self._launch(fn, *args))
            sp.set(**self._program_attrs.get("ragged", {}))  # once traced
        self._count_calls(disp)
        self._note_launch(disp)
        return out

    def _launch(self, fn, *args):
        """Call a compiled program of a step, counted for ``launches``."""
        self._launches += 1
        return fn(*args)

    def note_idle(self, cause: str) -> None:
        """The scheduler's word on why the next launch will find the device
        with nothing: a barrier's reason, or ``empty`` when no request is
        live or queued, which no later reason replaces (the device had
        nothing to be given). The launch that ends the gap takes it."""
        if self._idle_cause != "empty":
            self._idle_cause = cause

    def _mark_fetched(self, fetched) -> None:
        """Where an ``engine.fetch`` span ends: a bubble can start here."""
        self._fetched_ns = fetched.end if fetched.recording else 0

    def _note_launch(self, disp) -> None:
        """Right after a step's launch returned, outside ``engine.enqueue``
        and only while recording (docs/TRACING.md). A round launched behind
        an unfetched predecessor (``ahead`` 1) says whether that one had
        already finished: ``starved`` 1, the device went dry waiting for the
        host. A launch with no step unfetched ends an ``engine.bubble``: from
        the return of the last fetch to now the engine had handed the device
        nothing. Its cause is the scheduler's (:meth:`note_idle`), else
        ``restart``: the pipe starts again with no barrier on this round."""
        if not disp.recording:
            return
        if self._unfetched:
            if disp.attrs.get("ahead"):
                disp.set(starved=int(self._unfetched[-1]._dev.is_ready()))
            return
        cause, self._idle_cause = self._idle_cause or "restart", None
        if self._fetched_ns:
            tracing.event("engine.bubble", self._fetched_ns,
                          tracing.clock_ns(), cause=cause)

    def _copy_on_write(self, d, first: int, last: int) -> None:
        """Detach a private copy of every block ``first..last`` of ``d``'s
        table that another sequence also references, before a write lands
        in it: shared blocks are immutable, a fresh ``ensure()``-allocated
        block has refcount 1 and is skipped. One fixed-shape program a copy
        (:meth:`_get_cow`)."""
        for j in range(first, last + 1):
            if self.block_mgr.refcount(d.blocks[j]) > 1:
                src, dst = self.block_mgr.copy_on_write(d, j)
                self.kv = self._launch(self._get_cow(), self.kv,
                                       jnp.int32(src), jnp.int32(dst))

    @property
    def fused_cache_size(self) -> int:
        """Number of compiled traces of the fused multi-step decode program.
        Bounded at <= 1: the engine only ever compiles its own
        ``decode_horizon`` (horizon 1 rides the ragged program). Together
        with ``ragged_cache_size <= 4`` the paged engine's total step-program
        bound is 5 — still O(1) in the load."""
        return 0 if self._fused_fn is None else self._fused_fn._cache_size()

    @property
    def verify_cache_size(self) -> int:
        """Number of compiled traces of the speculative-verification program.
        Bounded at <= 1 (one ``(max_seqs, decode_horizon)`` shape, like the
        fused program): with ``ragged_cache_size <= 4`` and
        ``fused_cache_size <= 1`` the paged engine's total step-program bound
        is 6 — still O(1) in the load, one program family per horizon."""
        return 0 if self._verify_fn is None else self._verify_fn._cache_size()

    @property
    def ragged_cache_size(self) -> int:
        """Number of compiled traces of the ragged-step program. Bounded at
        <= 4, independent of load: two shapes (the mixed-budget shape + the
        decode-round shape) × two ``greedy`` modes (``greedy`` is a
        static_argnum of the same jit, so each mode holds its own traces).
        A workload using a single greedy mode stays <= 2."""
        fn = self._ragged_fn
        return 0 if fn is None else fn._cache_size()

    def _put_paged(self, out: Dict[int, np.ndarray], greedy: bool = False,
                   max_steps: Optional[int] = None) -> None:
        """Advance pending tokens through fixed-budget ragged steps.

        Scheduling policy (the token-budget scheduler the reference hides
        behind ``query``/``can_schedule``): sequences with the fewest pending
        tokens go first — live decodes (1 token) always beat prefill chunks,
        bounding decode latency under heavy prefill (split-fuse).

        ``max_steps`` bounds how many compiled dispatches this call may run
        (``None`` drains everything; ``0`` is register-only — no dispatch).
        Chunked interleaved prefill (docs/SERVING.md) rides on ``1``: the
        scheduler advances one budget of mixed decode+prefill-chunk rows per
        iteration, so decode rounds and queued admissions never convoy
        behind a long prompt's full prefill. Partially-prefilled sequences
        simply keep their ``pending`` tail across calls."""
        # land any queued host→device promotions (admission-time prefix hits
        # on demoted blocks) before a program reads the pool
        self._drain_promotions()
        steps = 0
        while max_steps is None or steps < max_steps:
            work = [d for d in self.state.seqs.values() if d.in_flight > 0]
            if not work:
                return
            steps += 1
            self._ragged_step(work, out, greedy)

    def _ragged_step(self, work, out: Dict[int, np.ndarray],
                     greedy: bool) -> None:
        """One compiled ragged dispatch of :meth:`_put_paged`: build the
        batch, enqueue the program, fetch its one result."""
        with tracing.span("engine.dispatch", program="ragged",
                          ahead=0) as disp:
            with tracing.span("engine.build"):
                T, plan, finals, feed = self._build_ragged_step(work)
                self._count_dispatch(disp, T, plan)
            lg = self._enqueue_ragged(disp, T, feed.buf, None, greedy)
            if self.prefix_cache:
                # the step's writes are dispatched: every block it filled now
                # holds valid prefix content — publish to the content index
                # (dedup-aware: identical blocks collapse onto one copy)
                for d, _ in plan:
                    self.block_mgr.register(d)
            # THE step's one designed transfer (ships the whole batch's
            # results at once; everything above is dispatch-only): the
            # device wait
            with tracing.span("engine.fetch") as fetched:
                lg = np.asarray(lg)  # dstpu-lint: ignore[DSTPU001]
            self._mark_fetched(fetched)
            if greedy:
                self._note_moe_rows(disp, lg)
            for i, d in enumerate(finals):
                out[d.uid] = int(lg[i]) if greedy else lg[i]

    def _keep_caches(self, out):
        """Take the ragged program's donated caches back (the pool; of a
        stateful model also its slot arrays); returns its first result."""
        lg, caches = out
        if self._stateful:
            self.kv, self.slot_cache = caches
        else:
            self.kv = caches
        return lg

    def _note_moe_rows(self, disp, fetched) -> None:
        """The counts of a greedy ragged dispatch that ride behind its
        tokens (no transfer of their own), as attrs under the names the
        model gives them (``step_counts``): ``moe_rows`` / ``moe_rows_max``
        / ``moe_zero_picks`` of held experts, ``sel_blocks`` /
        ``ctx_blocks`` of sparse attention."""
        if self._moe_stats and disp.recording:
            disp.set(**{name: int(fetched[self.max_seqs + i])
                        for i, name in enumerate(self._step_counts)})

    def _count_calls(self, disp) -> None:
        """``feed_arrays`` / ``launches`` of ``engine.dispatch``: the host
        arrays transferred and the compiled programs called since the step
        before (the step's own feed and program, copy-on-write copies, tier
        promotions and demotions that landed for it), and ``one_feed``: the
        step went to the device as one buffer and one launch."""
        feeds = self._feeds - self._call_marks[0]
        launches = self._launches - self._call_marks[1]
        self._call_marks = (self._feeds, self._launches)
        disp.set(feed_arrays=feeds, launches=launches,
                 one_feed=int(feeds == 1 and launches == 1))

    def _count_dispatch(self, disp, padded_rows: int, plan,
                        fused: bool = False) -> None:
        """The counts ``engine.dispatch`` carries (docs/TRACING.md), taken
        where the batch is built. ``plan``: [(descriptor, tokens taken)]. A
        ragged step counts after its descriptors advanced; a ``fused``
        K-position program (fused decode, verify) before, and all its rows
        are decode rows. Blocks and copies are those since the previous
        dispatch. ``live_write``: the step's KV write went row by row for
        its live rows (1), or through the scatter over all its padded rows
        (0)."""
        mgr, w = self.block_mgr, self.block_mgr.window
        marks = (mgr.allocations + (w.allocations if w else 0),
                 mgr.stats["cow_copies"], w.freed_behind if w else 0)
        used = {"full": mgr.num_blocks - 1 - mgr.free_blocks}
        if w is not None:
            used["window"] = w.in_use
        self.block_peaks.update({c: max(n, self.block_peaks[c])
                                 for c, n in used.items()})
        if disp.recording:
            rows = ctx = by_row = decode = seg = dctx = dwin = 0
            for d, take in plan:
                seen = d.seen_tokens if fused else d.seen_tokens - take
                rows += take
                if self._seg_tile > 1 and not fused and take > 1:
                    seg += take
                ctx += seen + take
                by_row += take * seen + take * (take + 1) // 2
                # one token pending: a decode step (or a prompt's last token)
                if fused or take == 1:
                    decode += take
                    dctx += seen + 1        # one token: its context, and
                    if w is not None:       # what a window layer sees of it
                        dwin += min(seen + 1, w.bound)
            disp.set(padded_rows=padded_rows, rows=rows, decode_rows=decode,
                     prefill_tokens=rows - decode, seg_tokens=seg,
                     seqs=len(plan),
                     live_write=int(not fused
                                    and self._rows_apart(padded_rows)
                                    and paged_attention.writes_live_rows(
                                        self._full_pool())),
                     ctx_tokens=ctx, ctx_tokens_by_row=by_row,
                     blocks_allocated=max(0, marks[0] - self._count_marks[0]),
                     cow_copies=max(0, marks[1] - self._count_marks[1]),
                     decode_ctx_tokens=dctx,
                     # the pool as the step leaves it, every model's: the
                     # blocks held and free (of the full class, where there
                     # are two), and how often a token walks the layers
                     pool_blocks=used["full"], pool_free=mgr.free_blocks,
                     loop_steps=self.cfg.loop_steps)
            if mgr.slots is not None:
                disp.set(state_slots=mgr.slots.in_use)
            if w is not None:
                # the two classes of blocks, as the step leaves them: held
                # and free of each, the sequences that hold them (those
                # outside this step too), and what the window class freed
                # behind its sequences' windows since the step before
                # (``blocks_allocated`` counts both classes); the one-token
                # rows' contexts as a window layer sees them, summed
                # (``decode_ctx_tokens``: whole): what the decode kernel has
                # to read in this step
                disp.set(window_blocks=used["window"],
                         window_free=w.free_blocks,
                         full_blocks=used["full"], full_free=mgr.free_blocks,
                         block_seqs=w.holders,
                         freed_behind=marks[2] - self._count_marks[2],
                         decode_window_tokens=dwin)
        self._count_marks = marks

    def _build_ragged_step(self, work):
        """Plan one ragged step over ``work`` (the sequences with pending
        tokens), allocate its blocks, copy shared ones on write and fill the
        packed feed. Returns (padded rows, [(descriptor, tokens taken)],
        the sequences that yield an output, the step's :class:`PackedFeed`).
        Sequence state is advanced here; a raise leaves every descriptor
        intact."""
        work.sort(key=lambda d: (d.in_flight, d.slot))
        # decode-round fast path: when every pending item is a single
        # token and they fit in max_seqs rows, use the small compiled
        # shape — steady-state decode must not pay the prefill budget's
        # padded rows (second of the two fixed shapes, see _get_ragged)
        if (self.token_budget > self.max_seqs
                and len(work) <= self.max_seqs
                and all(d.in_flight == 1 for d in work)):
            T = self.max_seqs
        else:
            T = self.token_budget
        plan: List[Tuple] = []
        used = 0
        tile = self._seg_tile if T > self.max_seqs else 1
        # segment tiles: the first max_seqs rows are the one-token rows (there
        # are never more sequences than that), the rest whole tiles
        seg_rows = (T - self.max_seqs) // tile * tile
        for d in work:
            if tile > 1:
                if d.in_flight == 1:
                    take = 1
                else:
                    take = min(d.in_flight, self.prefill_chunk, seg_rows - used)
                    if take <= 0:
                        break
                    used += -(-take // tile) * tile
            else:
                if used >= T:
                    break
                take = min(d.in_flight, self.prefill_chunk, T - used)
                used += take
            if d.seen_tokens + take > self.max_seq_len:
                raise ContextOverflowError(
                    f"uid {d.uid}: prompt exceeds context "
                    f"({d.seen_tokens}+{take} > {self.max_seq_len})",
                    uid=d.uid)
            plan.append((d, take))
        # allocate blocks for the WHOLE step before mutating any sequence
        # state. A row whose blocks cannot be allocated is DEFERRED (its
        # tokens stay pending for a later dispatch) rather than failing
        # rows that can run — under chunked interleaved prefill, live
        # decodes must keep progressing (and freeing blocks) while a big
        # prompt waits for pool capacity. Exhaustion raises only when
        # nothing at all is dispatchable, with every descriptor's
        # pending/seen state intact (blocks already grown are kept and
        # used by the retried step, the standing retry contract).
        ready: List[Tuple] = []
        pool_exhausted: Optional[PoolExhaustedError] = None
        for d, take in plan:
            try:
                self.block_mgr.ensure(d, d.seen_tokens + take)
            except PoolExhaustedError as e:
                pool_exhausted = e
                self.plan_deferrals += 1
                continue
            ready.append((d, take))
        if not ready:
            raise pool_exhausted
        plan = ready
        if self.prefix_cache:
            # a write landing inside a block some OTHER sequence also
            # references (a full-prompt cache hit recomputes its final token
            # inside the last shared block) first detaches a private copy
            bs = self.block_mgr.block_size
            for d, take in plan:
                self._copy_on_write(
                    d, d.seen_tokens // bs,
                    min((d.seen_tokens + take - 1) // bs, len(d.blocks) - 1))
        feed = self._feed_scratch(("ragged", T), T)
        (ids, tables, starts, logit_rows, slots, seeds, poss, temps, top_ks,
         top_ps, _, row_slots, wtables, wbase, _) = feed
        window = self.block_mgr.window
        finals = []
        r = singles = 0
        seg_next = self.max_seqs       # next free segment tile's first row
        for d, take in plan:
            completes = take == d.in_flight
            if tile > 1:
                # one-token rows fill from row 0, a chunk's segment from the
                # next tile boundary (the rows between stay padding)
                if d.in_flight == 1:
                    r, singles = singles, singles + 1
                else:
                    r, seg_next = seg_next, seg_next + -(-take // tile) * tile
            # fill the first row in place, then broadcast-copy it to the
            # sequence's remaining rows — no per-row temp allocation
            r0 = r
            self.block_mgr.fill_table_row(d, tables[r0])
            if take > 1:
                tables[r0 + 1:r0 + take] = tables[r0]
            if self._stateful:
                row_slots[r0:r0 + take] = self.block_mgr.slots.begin(
                    d.uid, d.seen_tokens)
            if window is not None:
                wbase[r0:r0 + take] = window.fill_row(d.uid, wtables[r0])
                if take > 1:
                    wtables[r0 + 1:r0 + take] = wtables[r0]
            for j in range(take):
                ids[r, 0] = d.pending[j]
                starts[r] = d.seen_tokens + j
                r += 1
            if completes:
                logit_rows[len(finals)] = r - 1
                # the produced token's absolute index is the consumed
                # count — seen_tokens is pre-advance here, so the
                # counter-based key position is seen + take
                self._fill_sampling(d, len(finals), slots, seeds, temps,
                                    top_ks, top_ps, poss=poss,
                                    pos=d.seen_tokens + take)
                finals.append(d)
            if self.prefix_cache:
                d.history.extend(d.pending[:take])
            del d.pending[:take]
            d.seen_tokens += take
            if window is not None:
                # the step's tables are filled: what lies behind the window
                # of the sequence's next query goes back to the class
                window.trim(d.uid, d.seen_tokens - d.uncommitted)
        return T, plan, finals, feed

    # ------------------------------------------------------------------
    # reference surface
    # ------------------------------------------------------------------
    def put(self, batch_uids: Sequence[int], batch_tokens: Sequence[Sequence[int]],
            do_checks: bool = True, greedy: bool = False,
            max_steps: Optional[int] = None) -> Dict[int, np.ndarray]:
        """Advance the engine one step with new/continuing requests
        (reference ``engine_v2.py:107``).

        For each uid: if new (or given fresh tokens), the tokens are prefilled
        (chunked); every live sequence then yields its next-token logits.
        Returns {uid: (V,) numpy logits} — or, with ``greedy=True``,
        {uid: int token} sampled on-device (argmax), which avoids
        shipping the full logit rows to the host.

        ``max_steps`` bounds the number of compiled dispatches:
        ``None`` drains every pending token (the monolithic path), ``0``
        registers/extends sequences without dispatching (admission under
        chunked interleaved prefill — the prefix-cache lookup still runs),
        ``1`` advances one token-budget ragged step. Sequences whose prompt
        is not fully consumed keep their ``pending`` tail and yield no
        output yet; the final consumed token's dispatch returns their entry.
        """
        if do_checks and len(batch_uids) > self.state.max_seqs:
            raise EngineUsageError(
                f"batch of {len(batch_uids)} exceeds {self.state.max_seqs} slots")
        # 1. register / extend sequences
        for uid, toks in zip(batch_uids, batch_tokens):
            desc = self.state.get_or_create_sequence(uid)
            if self._stateful:
                self.block_mgr.slots.take(uid)
            if self._bias_rows:
                # (re-)bind any pending logit-bias row to this uid's slot —
                # covers fresh admission, preempt→re-admit, and post-rebuild
                # replay (cheap per-uid dict probe when no bias is registered)
                self._install_bias(desc)
            if toks is not None and len(toks):
                fresh = (self.prefix_cache and desc.seen_tokens == 0
                         and not desc.blocks and not desc.pending)
                desc.pending.extend(int(t) for t in toks)
                if fresh and len(desc.pending) > 1:
                    # prefix-cache admission: map every fully-cached prompt
                    # block into the block table and advance past those
                    # tokens — their prefill rows are never scheduled
                    skipped = self.block_mgr.lookup(desc, desc.pending)
                    if skipped:
                        desc.history.extend(desc.pending[:skipped])
                        del desc.pending[:skipped]
                        desc.seen_tokens = skipped

        out: Dict[int, np.ndarray] = {}
        # single compiled ragged program over a fixed token budget
        self._put_paged(out, greedy=greedy, max_steps=max_steps)
        return out

    def decode_step(self, tokens: Dict[int, int],
                    greedy: bool = False) -> Dict[int, np.ndarray]:
        """One continuous-batching decode step: feed each live uid its sampled
        token, get next-token logits for all of them (or, with
        ``greedy=True``, the on-device argmax token per uid)."""
        # all-or-nothing validation BEFORE any state is touched: unknown
        # uids KeyError rather than silently becoming new sequences;
        # context-full or block-pool-exhausted raises with nothing enqueued,
        # so the step can be retried verbatim after freeing capacity (blocks
        # allocated here are used by the step)
        for uid in tokens:
            d = self.state.seqs[uid]
            if d.seen_tokens + d.in_flight >= self.max_seq_len:
                raise ContextOverflowError(
                    f"uid {uid}: context full ({d.seen_tokens} >= "
                    f"{self.max_seq_len}); flush the sequence or raise "
                    "max_seq_len", uid=uid)
        for uid in tokens:
            d = self.state.seqs[uid]
            self.block_mgr.ensure(d, d.seen_tokens + d.in_flight + 1)
        # decode tokens ride the same compiled ragged program as prefill —
        # mixed arrivals and decodes in one step is the normal case
        uids = list(tokens)
        return self.put(uids, [[tokens[u]] for u in uids], greedy=greedy)

    def decode_multi(self, tokens: Dict[int, int],
                     horizon: int) -> Dict[int, List[int]]:
        """Fused multi-token greedy decode (docs/SERVING.md): feed each live
        uid its last sampled token and advance ``horizon`` rounds in ONE
        compiled dispatch — on-device argmax feeds each round's tokens back
        as the next round's inputs, and a single ``(max_seqs, horizon)``
        int32 transfer ships the results. Returns ``{uid: [t1..tK]}``; the
        last token of each list is sampled but NOT yet written to the cache
        (exactly the ``decode_step`` contract, K times over).

        Horizons are restricted to ``{1, decode_horizon}``: 1 delegates to
        the ragged decode round, ``decode_horizon`` runs the one fused
        program — the compiled-program bound grows by exactly one shape.

        Blocks for all ``horizon`` writes are pre-allocated up front and the
        step's generated tokens are NOT registered in the prefix-cache
        content index — :meth:`rollback` commits (and optionally truncates)
        them once the scheduler knows which tokens are kept, so the index
        never covers discarded overrun tokens. Validation is all-or-nothing:
        a context/pool raise leaves every descriptor intact and the step can
        be retried verbatim."""
        if horizon == 1:
            return {u: [t] for u, t in
                    self.decode_step(tokens, greedy=True).items()}
        if horizon != self.decode_horizon:
            raise ValueError(
                f"horizon {horizon} not in {{1, {self.decode_horizon}}} — "
                "fixed-shape discipline: the engine compiles exactly one "
                "fused horizon (set decode_horizon at construction)")
        if not tokens:
            return {}
        if len(tokens) > self.max_seqs:
            raise EngineUsageError(
                f"batch of {len(tokens)} exceeds {self.max_seqs} slots")
        K = horizon
        for uid in tokens:
            d = self.state.seqs[uid]  # unknown uid: loud KeyError
            if d.in_flight:
                raise EngineUsageError(
                    f"uid {uid}: {d.in_flight} pending prefill tokens — "
                    "drain before fused decode", uid=uid)
            if d.seen_tokens + K > self.max_seq_len:
                raise ContextOverflowError(
                    f"uid {uid}: fused horizon {K} exceeds context "
                    f"({d.seen_tokens}+{K} > {self.max_seq_len}); collapse "
                    "to horizon 1 or flush the sequence", uid=uid)
        # pre-allocate the WHOLE horizon's blocks before dispatch (positions
        # seen .. seen+K-1); a PoolExhaustedError here leaves seen_tokens/
        # history untouched — allocated blocks are used by the retried step
        with tracing.span("engine.dispatch", program="fused",
                          ahead=0) as disp:
            with tracing.span("engine.build"):
                self._drain_promotions()  # queued tier promotions land first
                for uid in tokens:
                    d = self.state.seqs[uid]
                    self.block_mgr.ensure(d, d.seen_tokens + K)
                descs = sorted((self.state.seqs[u] for u in tokens),
                               key=lambda d: d.slot)
                if self.prefix_cache:
                    # every block the K writes can land in
                    bs = self.block_mgr.block_size
                    for d in descs:
                        self._copy_on_write(
                            d, d.seen_tokens // bs,
                            min((d.seen_tokens + K - 1) // bs,
                                len(d.blocks) - 1))
                B = self.max_seqs
                toks, tables, starts, slots, seeds, top_ks, temps, top_ps = \
                    self._scratch_for(
                        ("fused", B),
                        ((B,), (B, self.block_mgr.max_blocks_per_seq), (B,),
                         (B,), (B,), (B,), (B,), (B,)),
                        dtypes=(np.int32,) * 6 + (np.float32, np.float32))
                for r, d in enumerate(descs):
                    toks[r] = tokens[d.uid]
                    self.block_mgr.fill_table_row(d, tables[r])  # in place, no temp
                    starts[r] = d.seen_tokens
                    # per-position keys are folded inside the scan from (seed,
                    # starts+round+1) — no per-round host state (docs/SAMPLING.md)
                    self._fill_sampling(d, r, slots, seeds, temps, top_ks, top_ps)
                self._count_dispatch(disp, B * K, [(d, K) for d in descs],
                                     fused=True)
            ys = self._enqueue_fetch(disp, "fused", self._get_fused(), (
                toks, tables, starts, slots, seeds, temps, top_ks, top_ps))
        out: Dict[int, List[int]] = {}
        for r, d in enumerate(descs):
            seq = [int(t) for t in ys[r]]
            if self.prefix_cache:
                # cache now holds the fed token plus the first K-1 samples
                d.history.append(int(tokens[d.uid]))
                d.history.extend(seq[:-1])
            d.seen_tokens += K
            d.uncommitted = K  # rollback may truncate at most this step
            out[d.uid] = seq
        return out

    def _enqueue_fetch(self, disp, program: str, fn, feed):
        """Enqueue a K-position program (fused decode, speculative verify)
        and fetch its (max_seqs, K) result: ONE designed transfer per K
        tokens, the same budget as the ragged step's."""
        with tracing.span("engine.enqueue") as sp:
            self._feeds += len(feed)
            args = (self.params, self.kv,
                    *(jnp.asarray(a) for a in feed), self._bias())
            if disp.recording:
                tracing.note_program("engine_v2." + program, fn, args)
            ys, self.kv = self._launch(fn, *args)
            sp.set(**self._program_attrs.get(program, {}))
        self._count_calls(disp)
        self._note_launch(disp)
        with tracing.span("engine.fetch") as fetched:
            ys = np.asarray(ys)  # dstpu-lint: ignore[DSTPU001]
        self._mark_fetched(fetched)
        return ys

    def verify_multi(self, tokens: Dict[int, int],
                     drafts: Dict[int, Sequence[int]]) -> Dict[int, List[int]]:
        """Speculative-decoding batch verification (docs/SERVING.md): feed
        each live uid its last sampled token plus up to ``decode_horizon-1``
        proposed draft tokens, run the target model over every proposed
        position in ONE position-parallel compiled dispatch, and return the
        per-position greedy argmax ``{uid: [g1 .. g_{len(draft)+1}]}`` —
        ``g_j`` is the model's next token after consuming the fed token and
        the first ``j-1`` drafts. The caller accepts the longest prefix with
        ``draft[j] == g_j`` (every such ``g_j`` IS the non-speculative greedy
        token, bitwise), emits the one free token at the first mismatch, and
        MUST :meth:`rollback` the rejected remainder — including the
        ``K-1-len(draft)`` padding positions this call writes — before the
        next dispatch; ``rollback`` enforces that via ``uncommitted``.

        Draft tokens are NEVER registered in the prefix-cache content index:
        like :meth:`decode_multi`, registration happens only at the
        :meth:`rollback` commit, after rejected tokens are gone.

        Validation is all-or-nothing (the ``decode_multi`` discipline): a
        context/pool raise leaves every descriptor intact so a faulted step
        retries verbatim. Blocks for the whole horizon are pre-allocated and
        shared blocks are copied-on-write before the segment lands."""
        K = self.decode_horizon
        if K <= 1:
            raise EngineUsageError(
                "verify_multi needs decode_horizon > 1 (the verification "
                "width is the engine's one compiled horizon)")
        if not tokens:
            return {}
        if len(tokens) > self.max_seqs:
            raise EngineUsageError(
                f"batch of {len(tokens)} exceeds {self.max_seqs} slots")
        for uid in tokens:
            d = self.state.seqs[uid]  # unknown uid: loud KeyError
            ds = drafts.get(uid, ())
            if len(ds) > K - 1:
                raise EngineUsageError(
                    f"uid {uid}: {len(ds)} draft tokens exceed the verify "
                    f"width {K - 1} (= decode_horizon - 1)", uid=uid)
            if d.in_flight:
                raise EngineUsageError(
                    f"uid {uid}: {d.in_flight} pending prefill tokens — "
                    "drain before speculative verification", uid=uid)
            if d.seen_tokens + K > self.max_seq_len:
                raise ContextOverflowError(
                    f"uid {uid}: verify width {K} exceeds context "
                    f"({d.seen_tokens}+{K} > {self.max_seq_len}); collapse "
                    "to horizon 1 or flush the sequence", uid=uid)
        with tracing.span("engine.dispatch", program="verify",
                          ahead=0) as disp:
            with tracing.span("engine.build"):
                self._drain_promotions()  # queued tier promotions land first
                for uid in tokens:
                    d = self.state.seqs[uid]
                    self.block_mgr.ensure(d, d.seen_tokens + K)
                descs = sorted((self.state.seqs[u] for u in tokens),
                               key=lambda d: d.slot)
                if self.prefix_cache:
                    # every block the K writes can land in
                    bs = self.block_mgr.block_size
                    for d in descs:
                        self._copy_on_write(
                            d, d.seen_tokens // bs,
                            min((d.seen_tokens + K - 1) // bs,
                                len(d.blocks) - 1))
                B = self.max_seqs
                segs, tables, starts, slots, seeds, top_ks, temps, top_ps = \
                    self._scratch_for(
                        ("verify", B, K),
                        ((B, K), (B, self.block_mgr.max_blocks_per_seq), (B,),
                         (B,), (B,), (B,), (B,), (B,)),
                        dtypes=(np.int32,) * 6 + (np.float32, np.float32))
                fed: Dict[int, List[int]] = {}
                for r, d in enumerate(descs):
                    row = [int(tokens[d.uid])] + [int(t) for t in drafts.get(d.uid, ())]
                    fed[d.uid] = row
                    for j, t in enumerate(row):  # positions past the draft stay 0
                        segs[r, j] = t           # (zeroed pad — always rolled back)
                    self.block_mgr.fill_table_row(d, tables[r])  # in place, no temp
                    starts[r] = d.seen_tokens
                    # sampled rows: each position j gets the target's own sample
                    # under key (seed, starts+j+1) — the token sequential sampled
                    # decode emits there, which is what draft prefix-matching needs
                    self._fill_sampling(d, r, slots, seeds, temps, top_ks, top_ps)
                self._count_dispatch(disp, B * K, [(d, K) for d in descs],
                                     fused=True)
            ys = self._enqueue_fetch(disp, "verify", self._get_verify(), (
                segs, tables, starts, slots, seeds, temps, top_ks, top_ps))
        out: Dict[int, List[int]] = {}
        for r, d in enumerate(descs):
            row = fed[d.uid]
            if self.prefix_cache:
                # cache now holds the fed token, the drafts, and the pad
                d.history.extend(row)
                d.history.extend([0] * (K - len(row)))
            d.seen_tokens += K
            d.uncommitted = K  # caller must commit/rollback before next step
            # outputs past the draft's +1 bonus position were computed from
            # padding — meaningless, never returned
            out[d.uid] = [int(t) for t in ys[r, :len(row)]]
        return out

    def decode_dispatch(self, tokens: Dict[int, Optional[int]],
                        prev: Optional[DecodeDispatchHandle] = None
                        ) -> DecodeDispatchHandle:
        """Dispatch ONE ragged decode round without syncing on its result
        (docs/SERVING.md pipelined dispatch). Semantically the step is
        ``decode_step(tokens, greedy=True)`` — one fed token per live uid,
        the compiled decode-round program, on-device sampling under the same
        counter-based keys — but the host returns as soon as the program is
        enqueued, handing back a :class:`DecodeDispatchHandle` whose
        :meth:`~DecodeDispatchHandle.fetch` is the deferred transfer.

        A uid whose token is ``None`` is fed ON THE DEVICE from its row of
        ``prev``, the unfetched handle of the round before: the host has not
        seen that token yet and does not need to (the sampling keys are
        seed and position). Its history entry is a placeholder until
        ``prev`` is fetched.

        Host bookkeeping advances at dispatch: ``seen_tokens``/``history``
        grow by the fed token and ``uncommitted`` grows by 1 (STACKED — with
        a round in flight a sequence carries two provisional tokens), but
        NOTHING is registered in the prefix-cache content index:
        :meth:`commit_step` publishes absorbed tokens once the scheduler has
        fetched the round and decided what is kept, so the index never
        covers a position a speculative-absorb rollback could truncate.

        Validation is all-or-nothing (the ``decode_multi`` discipline). Two
        rounds may be unfetched at once, each staged from its own scratch
        set; a third dispatch raises."""
        if not tokens:
            raise EngineUsageError("decode_dispatch with an empty feed")
        if len(self._unfetched) >= 2:
            raise EngineUsageError(
                "decode_dispatch: two rounds are already unfetched — fetch "
                "the older one first (a round is staged from one of two "
                "scratch sets, and a third would overwrite a feed still in "
                "flight)")
        if len(tokens) > self.max_seqs:
            raise EngineUsageError(
                f"batch of {len(tokens)} exceeds {self.max_seqs} slots")
        rows_of_prev: Dict[int, int] = {}
        if any(t is None for t in tokens.values()):
            if prev is None or prev._dev is None:
                raise EngineUsageError(
                    "decode_dispatch: a token of None is fed from the "
                    "unfetched handle of the preceding round, and there is "
                    "none")
            rows_of_prev = {u: i for i, u in enumerate(prev.uids)}
        for uid, tok in tokens.items():
            d = self.state.seqs[uid]  # unknown uid: loud KeyError
            if tok is None and uid not in rows_of_prev:
                raise EngineUsageError(
                    f"uid {uid}: fed from the preceding round, which has no "
                    "row for it", uid=uid)
            if d.in_flight:
                raise EngineUsageError(
                    f"uid {uid}: {d.in_flight} pending prefill tokens — "
                    "drain before pipelined decode", uid=uid)
            if d.seen_tokens + 1 > self.max_seq_len:
                raise ContextOverflowError(
                    f"uid {uid}: context full ({d.seen_tokens} >= "
                    f"{self.max_seq_len}); flush the sequence or raise "
                    "max_seq_len", uid=uid)
        with tracing.span("engine.dispatch", program="ragged", deferred=True,
                          ahead=int(bool(self._unfetched))) as disp:
            with tracing.span("engine.build"):
                self._drain_promotions()  # queued tier promotions land first
                for uid in tokens:
                    d = self.state.seqs[uid]
                    self.block_mgr.ensure(d, d.seen_tokens + 1)
                descs = sorted((self.state.seqs[u] for u in tokens),
                               key=lambda d: d.slot)
                if self.prefix_cache:
                    # the block the single write lands in
                    bs = self.block_mgr.block_size
                    for d in descs:
                        j = min(d.seen_tokens // bs, len(d.blocks) - 1)
                        self._copy_on_write(d, j, j)
                # the decode-round fast shape of the ragged program (see _put_paged):
                # a pure single-token round never pays the prefill budget's padding
                T = (self.max_seqs if self.token_budget > self.max_seqs
                     else self.token_budget)
                # the set no unfetched round was staged from
                scratch_set = (1 - self._unfetched[0]._set
                               if self._unfetched else 0)
                feed = self._feed_scratch(("dispatch", T, scratch_set), T)
                (ids, tables, starts, logit_rows, slots, seeds, poss, temps,
                 top_ks, top_ps, src_rows, row_slots, wtables, wbase,
                 _) = feed
                window = self.block_mgr.window
                for r, d in enumerate(descs):
                    tok = tokens[d.uid]
                    if tok is None:
                        src_rows[r] = rows_of_prev[d.uid]
                        tok = FED_ON_DEVICE
                    else:
                        ids[r, 0] = tok = int(tok)
                    self.block_mgr.fill_table_row(d, tables[r])  # in place, no temp
                    starts[r] = d.seen_tokens
                    if self._stateful:
                        row_slots[r] = self.block_mgr.slots.begin(
                            d.uid, d.seen_tokens)
                    if window is not None:
                        wbase[r] = window.fill_row(d.uid, wtables[r])
                        # behind the window of the oldest query the round
                        # may still be rolled back to (``commit_step``)
                        window.trim(d.uid, d.seen_tokens - d.uncommitted)
                    logit_rows[r] = r  # every row is a final: one token per uid
                    self._fill_sampling(d, r, slots, seeds, temps, top_ks, top_ps,
                                        poss=poss, pos=d.seen_tokens + 1)
                    if self.prefix_cache:
                        if tok == FED_ON_DEVICE:
                            prev._fed.append((d.history, len(d.history),
                                              rows_of_prev[d.uid]))
                        d.history.append(tok)
                    d.seen_tokens += 1
                    d.uncommitted += 1  # stacked: commit_step settles per absorb
                self._count_dispatch(disp, T, [(d, 1) for d in descs])
            lg = self._enqueue_ragged(
                disp, T, feed.buf, prev._dev if rows_of_prev else None, True)
        # no np.asarray and no register here — both are deferred: the
        # transfer to fetch(), the prefix-index publish to commit_step()
        handle = DecodeDispatchHandle([d.uid for d in descs], lg, eng=self,
                                      disp=disp, scratch_set=scratch_set)
        self._unfetched.append(handle)
        return handle

    def commit_step(self, uid: int, drop: int = 0, retain: int = 0) -> int:
        """Settle one absorbed pipelined round for ``uid`` (docs/SERVING.md):
        truncate the newest ``drop`` provisional tokens (speculative-absorb
        overrun — tokens dispatched past an EOS/stop/max_new_tokens the host
        only saw one step late, including any already-in-flight successor
        token), leave ``retain`` tokens uncommitted (the successor round
        still executing), and register prefix-cache content strictly below
        the committed boundary. ``drop=0, retain=0`` is the pure commit —
        exactly ``rollback(uid, 0)``. Idempotent on unknown uids.

        Safety of truncating under a live in-flight write: freed tail
        blocks may be re-allocated while the successor program is still
        executing, but device programs run in dispatch order and attention
        reads are length-masked, so a stale write to a re-used block's
        unread offsets is overwritten before any sequence ever reads it.
        Returns the number of block references released."""
        d = self.state.seqs.get(uid)
        if d is None:
            return 0
        if drop + retain > d.uncommitted:
            raise EngineUsageError(
                f"uid {uid}: commit_step(drop={drop}, retain={retain}) "
                f"exceeds the {d.uncommitted} provisional tokens — committed "
                "tokens are immutable (the prefix index may already cover "
                "them)", uid=uid)
        freed = 0
        if drop:
            if drop >= d.seen_tokens:
                raise ValueError(
                    f"uid {uid}: cannot roll back {drop} of {d.seen_tokens} "
                    "cached tokens (at least one must remain)")
            d.seen_tokens -= drop
            if self.prefix_cache:
                del d.history[-drop:]
            freed = self.block_mgr.rollback(d, d.seen_tokens)
        d.uncommitted = retain  # committed BEFORE register: in-flight and
        if self.prefix_cache:   # discarded tokens are never indexed
            self.block_mgr.register(d, limit=d.seen_tokens - retain)
        return freed

    def rollback(self, uid: int, n: int = 0) -> int:
        """Truncate the last ``n`` cached tokens of a live sequence and
        commit the rest — the scheduler's overrun path for fused decode
        (tokens generated past EOS/max_new_tokens/deadline are discarded).
        Truncation shrinks ``seen_tokens``/``history``, releases the
        over-allocated tail blocks refcount-exactly, and only THEN registers
        the kept full blocks in the prefix-cache content index — discarded
        tokens are never indexed. ``n=0`` is the pure commit. Idempotent on
        unknown uids (returns 0), like :meth:`flush` — so a rollback racing
        a quarantine/cancel flush is a counted no-op, never a double-free.
        Returns the number of block references released.

        ``n`` may not exceed the tokens generated by the LAST
        ``decode_multi``/``verify_multi`` dispatch (the descriptor's
        ``uncommitted`` count): committed tokens are immutable — the prefix
        index may already cover them, and truncating them would desync every
        consumer that saw them emitted. Such a request raises a typed
        :class:`EngineUsageError` instead of silently clamping at the block
        layer."""
        d = self.state.seqs.get(uid)
        if d is None:
            return 0
        freed = 0
        if n:
            if n < 0 or n >= d.seen_tokens:
                raise ValueError(
                    f"uid {uid}: cannot roll back {n} of {d.seen_tokens} "
                    "cached tokens (at least one must remain)")
            if n > d.uncommitted:
                raise EngineUsageError(
                    f"uid {uid}: rollback of {n} tokens exceeds the "
                    f"{d.uncommitted} generated by the last fused/verify "
                    "dispatch — committed tokens are immutable (the prefix "
                    "index may already cover them)", uid=uid)
            if d.in_flight:
                raise EngineUsageError(
                    f"uid {uid}: rollback with {d.in_flight} pending tokens",
                    uid=uid)
            d.seen_tokens -= n
            if self.prefix_cache:
                del d.history[-n:]
            freed = self.block_mgr.rollback(d, d.seen_tokens)
        d.uncommitted = 0  # committed BEFORE register: drafts never indexed
        if self.prefix_cache:
            self.block_mgr.register(d)
        return freed

    def flush(self, uid: int):
        """Release a sequence's slot and KV blocks. Explicitly
        idempotent: flushing an unknown uid is a counted no-op — scheduler
        cancel/preempt/complete races must never double-free blocks (a
        second ``block_mgr.free`` of the same descriptor would corrupt
        refcounts)."""
        # sampling state is per-residency: re-admission re-registers it (the
        # scheduler's _start), so dropping here keeps slot bias rows exact
        self._sampling.pop(uid, None)
        self._bias_rows.pop(uid, None)
        self._drop_bias(uid)
        if uid not in self.state.seqs:
            entry = self._swaps.pop(uid, None)
            if entry is not None:
                # cancel/expiry of a swapped-out victim: drop its payloads,
                # cancelling any still-open transfer tickets. A dropped
                # IMPORTED entry is an orphaned handoff export (the adopt
                # never landed) — counted, like rebuild's wholesale drop.
                self._cancel_payloads(entry[0])
                if uid in self._swap_imports:
                    self._swap_imports.discard(uid)
                    self.swap_stats["orphan_drops"] += 1
                return
            self.flush_noops += 1
            log_dist(f"flush({uid}): unknown uid (no-op #{self.flush_noops})",
                     ranks=[0], level=10)  # DEBUG
            return
        self.block_mgr.free(self.state.seqs[uid])
        self.state.flush_sequence(uid)

    def preempt(self, uid: int) -> int:
        """Evict a live sequence under pool pressure, reclaiming its KV
        blocks; returns how many blocks were held (scheduler metrics). With
        the prefix cache on, the victim's full blocks stay indexed (parked
        in the LRU by ``free``), so a re-admitted victim replaying its
        prompt + generated tokens maps them straight back — preemption cost
        is one tail re-prefill, not the whole prompt."""
        freed = self._blocks_held(uid)
        self.flush(uid)
        return freed

    def _blocks_held(self, uid: int) -> int:
        desc = self.state.seqs.get(uid)
        if desc is None:
            return 0
        window = self.block_mgr.window
        return len(desc.blocks) + (window.blocks_of(uid) if window else 0)

    def rebuild(self) -> None:
        """Hot rebuild after engine loss (docs/RESILIENCE.md): replace every
        piece of per-incarnation state — sequence table, block pool
        bookkeeping, device KV pool — with fresh instances of **identical
        geometry**, and keep everything else. The compiled-program caches
        (`_ragged_fn`/`_fused_fn`/`_verify_fn`/`_cow_fn`)
        survive deliberately: same shapes means the new pools re-enter the
        same traced programs, so the ragged/fused/verify bounds hold across
        incarnations with zero recompilation and a rebuild costs one pool
        allocation, not a cold start. Resident sequences are NOT migrated —
        their KV died with the device; the scheduler replays them from its
        journal through normal admission. The host KV tier and the swap
        store die with the incarnation too (both are caches of pool content
        that no longer exists — a swap-in after rebuild would resurrect KV
        from the dead device): journal replay never consults either. Open
        transfer tickets reference arrays on the dead device — they are
        cancelled wholesale (settling them is impossible), and orphaned
        NVMe-tier files (their bookkeeping dies with the block manager) are
        deleted so the store never serves a previous incarnation's KV."""
        init = tracing.Phases("engine.init", engine="serve",
                              rebuild=self.rebuilds + 1)
        self.state = DSStateManager(self.max_seqs, self.max_seq_len)
        # an in-flight dispatch died with the device: its handle can never
        # be fetched against the new incarnation (nor end a bubble)
        self._unfetched = []
        self._fetched_ns = 0
        self.transfer.cancel_all()
        self._drop_swaps()  # counts any orphaned handoff imports
        # sampling state is per-residency (slot bindings died with the state
        # manager): replay re-registers through set_sampling + put, and the
        # counter-based keys make the replayed samples bitwise identical
        self._sampling.clear()
        self._bias_rows.clear()
        self._bias_slots.clear()
        self._bias_pool = None
        self._no_prev = None
        self.rebuilds += 1
        old = self.block_mgr
        if sanitize_enabled():
            self.block_mgr = checked_cache_cls()(
                old.num_blocks, old.block_size, old.max_blocks_per_seq,
                prefix_cache=self.prefix_cache,
                host_tier_blocks=self.host_tier_blocks,
                state_slots=self.max_seqs if self._stateful else 0,
                window=self._window_spec,
                descs=lambda: self.state.seqs.values())
        else:
            self.block_mgr = BlockedKVCache(
                old.num_blocks, old.block_size, old.max_blocks_per_seq,
                prefix_cache=self.prefix_cache,
                host_tier_blocks=self.host_tier_blocks,
                state_slots=self.max_seqs if self._stateful else 0,
                window=self._window_spec)
        if self.nvme_tier_blocks:
            for hid in list(getattr(old, "_nvme", ())):
                self._drop_block(hid)
        self.block_mgr.demote_fn = self._demote_block
        self._bind_nvme_tier()
        init.mark("state")
        self.kv = self.model.init_kv_pool(self._pool_blocks(), old.block_size,
                                          dtype=self.dtype)
        if self._stateful:
            self.slot_cache = self.model.init_state_cache(
                self.max_seqs, self.max_seq_len, dtype=self.dtype)
        log_dist(
            f"InferenceEngineV2.rebuild #{self.rebuilds}: block pool "
            f"replaced ({old.num_blocks}x{old.block_size}, prefix cache "
            f"cold), compiled programs retained", ranks=[0])
        init.mark("pool")
        init.close()

    def prefill_backlog(self) -> int:
        """Pending (registered but undispatched) tokens across all resident
        sequences — the chunked-prefill backlog the scheduler trades decode
        horizon against (docs/SERVING.md). Zero on a fully-drained engine."""
        return sum(d.in_flight for d in self.state.seqs.values())

    # reference ``query``/``can_schedule`` surface
    def query(self) -> Tuple[int, int]:
        """(free sequence slots, per-sequence token capacity): the context
        length, bounded by the tokens the free block pool can still hold."""
        free_slots = self.state.max_seqs - self.state.n_active
        return free_slots, min(self.max_seq_len,
                               self.block_mgr.free_blocks
                               * self.block_mgr.block_size)

    def prefix_cache_stats(self) -> Dict[str, float]:
        """Prefix-cache effectiveness counters: lookups, hits,
        hit_rate, hit_blocks, skipped_prefill_tokens, cow_copies,
        dedup_blocks, evicted_blocks, cached_blocks, free_blocks. Empty when
        the cache is off — dashboards can key on that."""
        if not self.prefix_cache:
            return {}
        s = dict(self.block_mgr.stats)
        s["hit_rate"] = (s["hits"] / s["lookups"]) if s["lookups"] else 0.0
        s["cached_blocks"] = self.block_mgr.cached_blocks
        s["free_blocks"] = self.block_mgr.free_blocks
        # host-RAM tier + swap-preemption counters (all zero with the tier
        # off — dashboards can key on host_capacity_blocks)
        s["host_blocks"] = self.block_mgr.host_blocks
        s["host_capacity_blocks"] = self.host_tier_blocks
        s["host_bytes"] = self.block_mgr.host_blocks * self.block_bytes
        # NVMe third tier (docs/TRANSFER.md): residency + capacity gauges
        # alongside the allocator's nvme_* flow counters already in ``s``
        nvme_res = getattr(self.block_mgr, "nvme_resident_blocks", 0)
        s["nvme_blocks"] = nvme_res
        s["nvme_capacity_blocks"] = self.nvme_tier_blocks
        s["nvme_bytes"] = nvme_res * self.block_bytes
        s.update(self.swap_stats)
        s["swap_out_bytes"] = self.swap_stats["swap_out_blocks"] * self.block_bytes
        s["swap_in_bytes"] = self.swap_stats["swap_in_blocks"] * self.block_bytes
        return s

    def monitor_events(self, step: int = 0) -> List[Tuple[str, float, int]]:
        """Prefix-cache counters as ``(label, value, step)`` events for
        ``deepspeed_tpu.monitor.MonitorMaster.write_events`` — serving
        dashboards plot cache effectiveness alongside training metrics.
        TransferEngine bandwidth EMAs and ledger bytes ride along under
        ``serve/transfer/*`` (docs/TRANSFER.md)."""
        events = [(f"inference/prefix_cache/{k}", float(v), step)
                  for k, v in sorted(self.prefix_cache_stats().items())]
        events.extend(self.transfer.monitor_events("serve/transfer", step))
        return events

    def can_schedule(self, n_new: int = 1) -> bool:
        if not self.state.can_allocate(n_new):
            return False
        # a model with state-slot layers admits by state slots too
        slots = self.block_mgr.slots
        if slots is not None and slots.free_slots < n_new:
            return False
        # admit only if every new sequence can get one prefill chunk of
        # blocks (the reference consults KV block availability likewise,
        # engine_v2.py:184 query / can_schedule:184)
        per_seq = self.block_mgr.blocks_needed(
            min(self.prefill_chunk, self.max_seq_len))
        # every class of blocks has to hold the chunk: the scarcer decides
        window = self.block_mgr.window
        if window is not None and window.free_blocks < n_new * per_seq:
            return False
        return self.block_mgr.free_blocks >= n_new * per_seq

"""Rule catalog for the DSTPU hazard linter (docs/ANALYSIS.md).

Each rule mechanizes an invariant the serving/perf PRs enforce by hand —
the host-overhead and dispatch-discipline walls that the TPU concurrency
scaling work identifies as the bottleneck class (PAPERS.md): one silent
``np.zeros`` per decode step or one stray ``block_until_ready`` in the
token loop erases a fused-decode speedup, and it only surfaces weeks
later as bench noise. The linter makes the regression a CI failure with
a file:line and a fix hint instead.

Scopes are path-based (directory parts of the file under lint), so the
hot-path rules fire only where hot paths live today: the serving loops
and, since the fault-tolerant-training PR, the training micro-step loop
(``runtime/``, which contains ``zero/``).
"""

from dataclasses import dataclass
from typing import Dict, FrozenSet, Tuple


@dataclass(frozen=True)
class Rule:
    id: str
    title: str
    #: one-line remediation appended to every finding of this rule
    hint: str
    #: directory parts a file must contain for the rule to apply;
    #: empty = whole tree
    scope: Tuple[str, ...] = ()


#: functions whose bodies are the steady-state serving hot path: one
#: iteration ≈ one generated token. Host syncs and fresh allocations in
#: here multiply by tokens/second. (``step``/``_absorb*``/``_decode_sync``
#: are the scheduler's per-token loop; ``_emit_token``/``commit``/
#: ``record`` are the journal commit path riding inside it — one journal
#: sync per emitted token; the rest are the engine's.)
HOT_FUNCTIONS: FrozenSet[str] = frozenset({
    "decode_step", "decode_multi", "verify_multi", "_put_paged",
    "_absorb", "_absorb_multi", "_absorb_speculation",
    "step", "_collect_drafts", "propose",
    "_emit_token", "commit", "record",
    # pipelined dispatch (docs/SERVING.md "Pipelined dispatch"): the
    # plan/dispatch/absorb stages run once per in-flight round and the
    # whole point is keeping the host phase off the device's critical
    # path — ``fetch`` carries the round's ONE designed materialization
    # sync (suppressed at the site); everything else must stay
    # dispatch-only or pure host bookkeeping
    "_decode_sync", "decode_dispatch", "commit_step", "fetch",
    "step_dispatch", "step_absorb", "_pipeline_dispatch_stage",
    "_pipeline_absorb_stage", "_drain_inflight", "_engine_commit",
    # the training micro-step loop (ROADMAP item 3): one iteration ≈ one
    # optimizer step — host syncs/allocations here multiply by steps/second
    # exactly like the decode loop's multiply by tokens/second
    "train_batch", "step_fn", "backward", "_fused_micro_step",
    "_multi_exec_step",
    # the engine pool's per-submission placement decision (router.py) and
    # the read-only content-index probe it runs against every replica —
    # pool traffic multiplies both by requests/second × replicas
    "place", "probe", "prefix_probe",
    # KV-tier data movement (docs/PREFIX_CACHING.md "Two-tier cache"):
    # demotion/swap-out ride the decode loop and must stay dispatch-only
    # (async copy, no host sync); promotion/swap-in carry the tier's ONE
    # designed materialization sync each — anything beyond it is a
    # regression DSTPU001 should catch
    "_demote_block", "_scatter_blocks", "_drain_promotions",
    "swap_out", "swap_in", "_swap_in_readmit", "_preempt", "_swap_wins",
    # disaggregated prefill/decode handoff (docs/SERVING.md
    # "Disaggregated serving"): the export carries the handoff's ONE
    # designed materialization (drain_before, the blocks leave the
    # process); import/adopt dispatch and the per-step handoff scan must
    # otherwise stay sync- and allocation-free — handoff traffic
    # multiplies by long-prompt requests/second
    "export_swap", "import_swap", "export_ready", "detach_with_kv",
    "_dispatch_handoffs", "_handoff",
    # ZeRO gather/scatter/reduce-scatter paths (docs/ZERO.md): the host-tier
    # Adam loop carries ONE designed D2H gradient sync per leaf (suppressed at
    # the site); the offload step dispatcher and the stage-3 residency
    # gather/prefetch must otherwise stay sync- and allocation-free — every
    # stray materialization here multiplies by optimizer steps/second
    "adam_step", "_step_offload",
    "_ensure_zero3_params", "_z3_release_and_prefetch",
    # unified TransferEngine (docs/TRANSFER.md): EVERY offload/tier byte
    # rides these — submit must stay dispatch-only (the async copy), the
    # designed materialization lives ONLY in _settle / the non-overlap twin
    # (suppressed at those sites); staging acquire/release must reuse the
    # pool, never allocate per transfer
    "submit_d2h", "submit_h2d", "drain_before", "drain_oldest",
    "drain_all", "acquire_staging", "release_staging",
    "release_staging_by_key", "put_tree", "get_tree",
    "cancel_ticket", "cancel_all", "_settle",
    # TransferEngine client ports: NVMe spill/load of KV blocks and the
    # offload tier's per-leaf gradient materialization
    "_spill_block", "_load_block", "_drop_block", "_materialize",
    "_moments",
})

#: where the hot-path rules (001/002) apply — ``resilience`` joined when
#: the journal commit path (recovery.py) entered the per-token loop;
#: ``runtime`` joined with the training micro-step loop (fault-tolerant
#: training PR), discharging the docstring's tracked ROADMAP item
HOT_SCOPE = ("serve", "inference", "resilience", "runtime")
#: where the typed-error rule (003) applies — the taxonomy's home turf
TAXONOMY_SCOPE = ("serve", "inference", "resilience")
#: where the determinism rule (005) applies — scheduling/containment
#: decisions must be replayable (seeded faults, injectable clocks)
DECISION_SCOPE = ("serve", "resilience")
#: where the transfer-ticket rule (006) applies — everywhere TransferEngine
#: clients live (the engines, the tiers, the offload paths)
TRANSFER_SCOPE = ("serve", "inference", "resilience", "runtime")
#: where the exception-safety rule (007) applies — the engine/scheduler hot
#: paths whose half-mutated state the fault injector fires before
#: delegation specifically to catch
MUTATE_RAISE_SCOPE = ("serve", "inference")

#: device-sync call names (attribute or dotted) flagged by DSTPU001
SYNC_ATTRS: FrozenSet[str] = frozenset({"block_until_ready", "device_get"})
SYNC_DOTTED: FrozenSet[str] = frozenset({
    "np.asarray", "numpy.asarray", "jax.device_get",
    "jax.block_until_ready",
})

#: fresh-array constructors flagged by DSTPU002 when called as
#: ``np.<name>`` / ``numpy.<name>`` / ``jnp.<name>`` in a hot function.
#: ``asarray`` is deliberately absent: wrapping an existing buffer for
#: dispatch is the transfer itself, not a fresh allocation (it is DSTPU001
#: that polices host-side ``np.asarray`` syncs).
ALLOC_NAMES: FrozenSet[str] = frozenset({
    "zeros", "ones", "empty", "full", "array", "arange",
    "zeros_like", "ones_like", "empty_like", "full_like",
})
ARRAY_ROOTS: FrozenSet[str] = frozenset({"np", "numpy", "jnp"})

#: exception types whose raw ``raise`` DSTPU003 flags in taxonomy scope.
#: ``ValueError`` on argument validation is allowed (it is typed and
#: caller-attributable); ``AssertionError`` belongs to invariant checks.
UNTYPED_RAISES: FrozenSet[str] = frozenset({
    "RuntimeError", "Exception", "BaseException",
})

#: seeded/injectable RNG constructors exempt from DSTPU005 under
#: ``np.random.`` / ``numpy.random.``
SEEDED_RNG: FrozenSet[str] = frozenset({
    "default_rng", "Generator", "SeedSequence", "PCG64", "Philox",
})

#: DSTPU005's jax PRNG-key check (docs/SAMPLING.md): in the serving /
#: inference layers, ``jax.random.PRNGKey``/``split`` key material must be
#: replay-derivable — a constant, a carried seed, or a counter-based
#: ``fold_in(PRNGKey(seed), position)`` chain. Key material that flows
#: from wall clock, process entropy, or global RNG state makes every
#: sampled token irreproducible across preempt/re-admit, journal replay,
#: engine rebuild, pool migration, and KV swap-in — silently, because the
#: greedy paths stay bitwise.
RNG_KEY_SCOPE = ("serve", "inference", "resilience")
#: module spellings a flagged ``PRNGKey``/``split`` call may hang off
#: (plain ``random.split`` is string .split in disguise only when the
#: base is not a Name — the linter resolves dotted chains, so ``"a,b"
#: .split`` never reaches this set)
RNG_KEY_BASES: FrozenSet[str] = frozenset({
    "jax.random", "jrandom", "jr", "random",
})
#: nondeterministic key-material sources: any of these calls appearing in
#: the argument expression of a PRNGKey/split call is a finding
KEY_HAZARD_CALLS: FrozenSet[str] = frozenset({
    "time.time", "time.time_ns", "time.perf_counter", "time.monotonic",
    "os.urandom", "os.getrandom", "os.getpid",
    "uuid.uuid1", "uuid.uuid4",
    "secrets.token_bytes", "secrets.randbits", "secrets.randbelow",
    "id", "hash",
})
#: stdlib-``random`` leaves treated as hazardous key material (the jax
#: alias spelling ``random.fold_in``/``random.PRNGKey`` is NOT in here —
#: counter-based derivation is exactly the safe pattern)
STDLIB_RANDOM_LEAVES: FrozenSet[str] = frozenset({
    "random", "randint", "randrange", "getrandbits", "randbytes",
    "uniform", "choice", "gauss", "betavariate", "expovariate",
})

#: calls that settle outstanding transfer tickets (DSTPU006): the engine's
#: drain family, and wait/cancel on the ticket itself. A drain whose
#: arguments the linter cannot tie to specific tickets settles everything
#: in flight (conservative: the runtime's drain_before passes through
#: non-ticket dependents untouched, so over-approximating is safe).
DRAIN_CALLS: FrozenSet[str] = frozenset({
    "drain_before", "drain_all", "drain_oldest", "wait", "cancel",
    "cancel_all", "cancel_ticket",
})

RULES: Dict[str, Rule] = {r.id: r for r in (
    Rule(
        id="DSTPU001",
        title="host-device sync in a serving hot path",
        hint="batch the transfer (one np.asarray per step) or move it off "
             "the per-token loop; suppress only the step's single designed "
             "transfer (docs/ANALYSIS.md#dstpu001)",
        scope=HOT_SCOPE,
    ),
    Rule(
        id="DSTPU002",
        title="fresh host allocation in a steady-state step function",
        hint="reuse a per-shape preallocated scratch buffer zeroed in "
             "place (see InferenceEngineV2._scratch_for) instead of "
             "allocating per dispatch (docs/ANALYSIS.md#dstpu002)",
        scope=HOT_SCOPE,
    ),
    Rule(
        id="DSTPU003",
        title="untyped raise / string-matched exception dispatch",
        hint="raise a type from deepspeed_tpu.resilience.errors (or a "
             "named subclass) and dispatch on isinstance, never on str(e) "
             "(docs/ANALYSIS.md#dstpu003)",
        scope=TAXONOMY_SCOPE,
    ),
    Rule(
        id="DSTPU004",
        title="retrace/concretization hazard inside a jitted function",
        hint="branch with lax.cond/jnp.where, mark config args "
             "static_argnums, and keep trace-time Python (f-strings, "
             "int()/float() on traced values) out of compiled code "
             "(docs/ANALYSIS.md#dstpu004)",
        scope=(),
    ),
    Rule(
        id="DSTPU006",
        title="open TransferTicket read without a dominating drain",
        hint="settle the ticket first — te.drain_before([deps])/"
             "ticket.wait() — or move the .value read to the consumer "
             "that drains; submit_h2d tickets settle at submit and are "
             "exempt (docs/ANALYSIS.md#dstpu006)",
        scope=TRANSFER_SCOPE,
    ),
    Rule(
        id="DSTPU007",
        title="state write precedes a raise in a serving hot path",
        hint="validate every precondition before the first self.* write, "
             "or roll the writes back before re-raising — a mid-mutation "
             "raise leaves the engine half-mutated, the bug class the "
             "fault injector fires before delegation to catch "
             "(docs/ANALYSIS.md#dstpu007)",
        scope=MUTATE_RAISE_SCOPE,
    ),
    Rule(
        id="DSTPU005",
        title="nondeterminism in scheduler/resilience decision logic",
        hint="use the injectable clock (time.monotonic default), a seeded "
             "np.random.default_rng, ordered containers, and counter-based "
             "jax PRNG keys (fold_in(PRNGKey(seed), position), "
             "docs/SAMPLING.md) — decisions and sampled tokens must replay "
             "bit-for-bit (docs/ANALYSIS.md#dstpu005)",
        scope=DECISION_SCOPE,
    ),
)}

ALL_RULE_IDS = tuple(sorted(RULES))

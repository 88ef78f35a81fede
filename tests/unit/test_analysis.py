"""``deepspeed_tpu.analysis`` linter tests (docs/ANALYSIS.md): rule-by-rule
positive/negative fixtures, inline-pragma and baseline suppression (with
round-trip + stale detection), CLI exit codes, and the repo-wide tier-1
gate asserting the tree carries zero unsuppressed findings."""

import os

import pytest

from deepspeed_tpu.analysis import (apply_baseline, default_baseline_path,
                                    lint_paths, lint_source, load_baseline,
                                    save_baseline)
from deepspeed_tpu.analysis.__main__ import main as lint_main
from deepspeed_tpu.analysis.lint import _norm_path

#: fixture files land under these fake paths so the path-based rule scopes
#: (serve/inference/resilience) engage exactly as they do in the repo
SERVE = "deepspeed_tpu/serve/snippet.py"
INFER = "deepspeed_tpu/inference/v2/snippet.py"
# out of 001/002/003/005 scope (``runtime/`` joined the hot scope with the
# fault-tolerant-training PR, so ``models/`` is the cold fixture path now)
TRAIN = "deepspeed_tpu/models/snippet.py"


def rules_of(src, path=SERVE, only=None):
    return [f.rule for f in lint_source(src, path, only)]


# ---------------------------------------------------------------------------
# DSTPU001 — host syncs in hot functions
# ---------------------------------------------------------------------------

class TestHostSync:
    SYNC = """
import numpy as np
import jax

class Engine:
    def decode_step(self, lg, kv):
        jax.block_until_ready(kv)
        x = np.asarray(lg)
        return x.item()
"""

    def test_flags_sync_calls_in_hot_function(self):
        assert rules_of(self.SYNC) == ["DSTPU001"] * 3

    def test_silent_outside_hot_function(self):
        cold = self.SYNC.replace("decode_step", "warmup")
        assert rules_of(cold) == []

    def test_silent_outside_scope(self):
        assert rules_of(self.SYNC, path=TRAIN) == []

    def test_item_with_args_is_not_a_sync(self):
        src = """
class Engine:
    def decode_step(self, d):
        return d.item(0)
"""
        # only the argless ndarray accessor form is matched — `.item(k)`
        # is overwhelmingly dict-like in host code (heuristic documented
        # in docs/ANALYSIS.md)
        assert rules_of(src) == []


# ---------------------------------------------------------------------------
# DSTPU002 — fresh allocations in steady-state step functions
# ---------------------------------------------------------------------------

class TestFreshAllocation:
    def test_flags_alloc_in_hot_function(self):
        src = """
import numpy as np
import jax.numpy as jnp

class Engine:
    def _put_paged(self, out):
        ids = np.zeros((4, 1), np.int32)
        mask = jnp.ones((4,))
        return ids, mask
"""
        assert rules_of(src) == ["DSTPU002", "DSTPU002"]

    def test_silent_in_cold_function_and_for_asarray(self):
        src = """
import numpy as np

class Engine:
    def __init__(self):
        self.buf = np.zeros((4,), np.int32)   # one-time setup: fine

    def decode_step(self, toks):
        dev = jnp.asarray(toks)               # the dispatch transfer: fine
        return dev
"""
        assert rules_of(src) == []


# ---------------------------------------------------------------------------
# DSTPU003 — untyped raises / string-matched dispatch
# ---------------------------------------------------------------------------

class TestTypedErrors:
    def test_flags_untyped_raise_and_string_match(self):
        src = """
def admit(engine, uids):
    try:
        engine.put(uids)
    except RuntimeError as e:
        if "pool exhausted" in str(e):
            raise RuntimeError("capacity")
"""
        assert rules_of(src) == ["DSTPU003", "DSTPU003"]

    def test_typed_raises_are_fine(self):
        src = """
from deepspeed_tpu.resilience.errors import PoolExhaustedError

class QueueFullError(RuntimeError):
    pass

def admit(n):
    if n > 4:
        raise QueueFullError("backpressure")
    if n < 0:
        raise ValueError("bad n")        # argument validation: allowed
    raise PoolExhaustedError("full", uid=n)
"""
        assert rules_of(src) == []

    def test_silent_outside_taxonomy_scope(self):
        src = "def f():\n    raise RuntimeError('training-side raise')\n"
        assert rules_of(src, path=TRAIN) == []
        assert rules_of(src, path="deepspeed_tpu/resilience/x.py") == [
            "DSTPU003"]


# ---------------------------------------------------------------------------
# DSTPU004 — retrace hazards in jitted functions
# ---------------------------------------------------------------------------

class TestRetraceHazards:
    def test_branch_on_traced_param_via_jit_call(self):
        src = """
import jax

def build():
    def step(params, x):
        if x > 0:
            return x
        return -x
    return jax.jit(step)
"""
        assert rules_of(src, path=TRAIN) == ["DSTPU004"]

    def test_static_argnums_param_is_exempt(self):
        src = """
import jax

def build():
    def step(params, x, greedy):
        if greedy:
            return x
        return -x
    return jax.jit(step, static_argnums=(2,))
"""
        assert rules_of(src, path=TRAIN) == []

    def test_scan_body_decorator_fstring_and_concretization(self):
        src = """
import jax
from jax import lax

def build():
    def body(carry, x):
        n = int(x)
        name = f"x={n}"
        return carry, x
    lax.scan(body, 0, None)

@jax.jit
def dec(p, flag):
    if flag:
        return p
    return p
"""
        assert sorted(rules_of(src, path=TRAIN)) == ["DSTPU004"] * 3

    def test_trace_safe_tests_are_exempt(self):
        src = """
import jax

@jax.jit
def step(params, batch, mask):
    if mask is not None:              # identity: trace-safe
        params = params
    if isinstance(batch, dict):       # container introspection: static
        batch = batch["ids"]
    if batch.shape[0] > 4:            # shape: static under tracing
        batch = batch
    return batch

def plain(x):
    if x > 0:                         # not jitted: plain Python is fine
        return x
"""
        assert rules_of(src, path=TRAIN) == []

    def test_same_name_def_in_unrelated_scope_not_flagged(self):
        src = """
import jax

def other():
    def step(x):
        if x > 0:     # never jitted — sibling scope's jit must not leak
            return x
    return step

def build():
    def step(x):
        return x + 1
    return jax.jit(step)
"""
        assert rules_of(src, path=TRAIN) == []


class TestExtendedTraceContexts:
    """DSTPU004 resolution beyond jit: ``shard_map`` bodies and
    ``lax.cond``/``lax.while_loop`` callables are traced code too (the
    multi-chip lintability prerequisite, ROADMAP)."""

    def test_shard_map_body_is_traced(self):
        src = """
import jax

def build(mesh):
    def step(params, x):
        if x > 0:
            return x
        return -x
    return jax.shard_map(step, mesh=mesh, in_specs=None, out_specs=None)
"""
        assert rules_of(src, path=TRAIN) == ["DSTPU004"]

    def test_cond_branches_are_traced(self):
        src = """
from jax import lax

def build(pred, x):
    def true_fn(v):
        if v > 0:          # traced: cond branches get tracers
            return v
        return -v
    def false_fn(v):
        return float(v)    # traced: concretization hazard
    return lax.cond(pred, true_fn, false_fn, x)
"""
        assert sorted(rules_of(src, path=TRAIN)) == ["DSTPU004"] * 2

    def test_while_loop_cond_and_body_are_traced(self):
        src = """
from jax import lax

def build(x):
    def keep_going(v):
        name = f"v={v}"    # f-string at trace time
        return v < 10
    def body(v):
        if v > 0:
            return v + 1
        return v
    return lax.while_loop(keep_going, body, x)
"""
        assert sorted(rules_of(src, path=TRAIN)) == ["DSTPU004"] * 2

    def test_cond_predicate_arg_is_not_a_trace_context(self):
        src = """
from jax import lax

def build(pred, x):
    def picker(v):
        if v > 0:          # plain host helper: passed as cond's PREDICATE
            return v       # position, not a branch — must not be flagged
        return -v
    return lax.cond(picker, lambda v: v, lambda v: v, x)
"""
        assert rules_of(src, path=TRAIN) == []

    def test_non_lax_cond_name_is_not_a_trace_context(self):
        src = """
def build(scheduler, x):
    def fn(v):
        if v > 0:
            return v
        return -v
    return scheduler.cond(fn, fn, x)   # foo.cond is not lax.cond
"""
        assert rules_of(src, path=TRAIN) == []

    def test_switch_branch_list_is_traced(self):
        src = """
from jax import lax

def build(i, x):
    def a(v):
        if v > 0:          # traced: every switch branch gets tracers
            return v
        return -v
    def b(v):
        return int(v)      # traced: concretization hazard
    return lax.switch(i, [a, b], x)
"""
        assert sorted(rules_of(src, path=TRAIN)) == ["DSTPU004"] * 2

    def test_switch_branch_tuple_is_traced(self):
        src = """
import jax.lax

def build(i, x):
    def a(v):
        if v > 0:
            return v
        return -v
    return jax.lax.switch(i, (a, a), x)
"""
        # the same def reached through both tuple elements: one finding
        assert rules_of(src, path=TRAIN) == ["DSTPU004"]

    def test_switch_index_arg_is_not_a_trace_context(self):
        src = """
from jax import lax

def build(x):
    def pick(v):
        if v > 0:          # plain host helper passed as switch's INDEX
            return 1       # position, not a branch — must not be flagged
        return 0
    return lax.switch(pick, [lambda v: v, lambda v: -v], x)
"""
        assert rules_of(src, path=TRAIN) == []

    def test_fori_loop_body_is_traced(self):
        src = """
from jax import lax

def build(x):
    def body(i, v):
        if v > 0:          # traced: fori_loop bodies get tracers
            return v + i
        return v
    return lax.fori_loop(0, 8, body, x)
"""
        assert rules_of(src, path=TRAIN) == ["DSTPU004"]

    def test_fori_loop_bounds_are_not_trace_contexts(self):
        src = """
from jax import lax

def build(x):
    def lower(v):
        if v > 0:          # host helper computing a BOUND, not the body
            return 0
        return 1
    return lax.fori_loop(lower(x), 8, lambda i, v: v + i, x)
"""
        assert rules_of(src, path=TRAIN) == []

    def test_non_lax_switch_name_is_not_a_trace_context(self):
        src = """
def build(router, i, x):
    def fn(v):
        if v > 0:
            return v
        return -v
    return router.switch(i, [fn], x)   # foo.switch is not lax.switch
"""
        assert rules_of(src, path=TRAIN) == []


class TestWrappedTraceContexts:
    """DSTPU004 over rematerialization / custom-derivative wrappers
    (ISSUE 20 satellite): ``jax.checkpoint``/``jax.remat`` bodies and
    ``custom_vjp``/``custom_jvp`` rules are traced code too."""

    def test_checkpoint_body_is_traced(self):
        src = """
import jax

def build():
    def block(params, x):
        if x > 0:          # traced under remat exactly like under jit
            return x
        return -x
    return jax.checkpoint(block)
"""
        assert rules_of(src, path=TRAIN) == ["DSTPU004"]

    def test_remat_decorator_is_traced(self):
        src = """
import jax

@jax.remat
def block(params, x):
    n = int(x)             # concretization at trace time
    return params
"""
        assert rules_of(src, path=TRAIN) == ["DSTPU004"]

    def test_custom_vjp_and_defvjp_rules_are_traced(self):
        src = """
import jax

def build():
    def f(x):
        if x > 0:
            return x
        return -x
    f = jax.custom_vjp(f)
    def f_fwd(x):
        name = f"x={x}"    # f-string at trace time
        return x, x
    def f_bwd(res, g):
        return (float(g),) # concretization at trace time
    f.defvjp(f_fwd, f_bwd)
    return f
"""
        assert sorted(rules_of(src, path=TRAIN)) == ["DSTPU004"] * 3

    def test_nondiff_argnums_params_are_static(self):
        src = """
import jax

def build():
    def f(mode, x):
        if mode:           # nondiff arg: plain Python value, never traced
            return x
        return -x
    return jax.custom_jvp(f, nondiff_argnums=(0,))
"""
        assert rules_of(src, path=TRAIN) == []

    def test_audited_jit_is_a_trace_context(self):
        src = """
from deepspeed_tpu.analysis import audited_jit

def build():
    def step(params, x, greedy):
        if greedy:         # static: exempt
            return x
        if x > 0:          # traced param: flagged
            return x
        return -x
    return audited_jit("t.step", step, max_traces=2, static_argnums=(2,))
"""
        assert rules_of(src, path=TRAIN) == ["DSTPU004"]

    def test_self_checkpoint_is_not_a_trace_context(self):
        src = """
def save(self, path):
    def writer(path):
        if path:           # checkpoint SAVING, not jax.checkpoint: host code
            return path
        return "ckpt"
    return self.checkpoint(writer(path))
"""
        assert rules_of(src, path=TRAIN) == []


# ---------------------------------------------------------------------------
# DSTPU005 — nondeterminism in decision logic
# ---------------------------------------------------------------------------

class TestNondeterminism:
    BAD = """
import time, random
import numpy as np

def pick_victim(live):
    t = time.time()
    r = random.random()
    j = np.random.rand()
    for uid in set(live):
        return uid
"""

    def test_flags_wallclock_rng_and_set_iteration(self):
        assert sorted(rules_of(self.BAD)) == ["DSTPU005"] * 4

    def test_silent_outside_decision_scope(self):
        assert rules_of(self.BAD, path=TRAIN) == []

    def test_seeded_and_injectable_forms_are_fine(self):
        src = """
import time
import numpy as np

def pick_victim(live, clock=time.monotonic):
    rng = np.random.default_rng(0)
    t = clock()
    r = rng.random()
    for uid in sorted(set(live)):
        return uid
"""
        assert rules_of(src) == []


class TestRngKeyMaterial:
    """DSTPU005's jax PRNG-key check (docs/SAMPLING.md): key material in
    serve/inference must be replay-derivable — never wall clock, process
    entropy, or global RNG state."""

    BAD = """
import time, random
import numpy as np
import jax.random as jrandom

def make_keys(seed):
    k1 = jrandom.PRNGKey(int(time.time()))
    k2 = jrandom.PRNGKey(np.random.randint(0, 2**31))
    k3 = jrandom.split(jrandom.PRNGKey(hash(seed)))
    k4 = jrandom.PRNGKey(random.getrandbits(31))
    return k1, k2, k3, k4
"""

    def test_flags_entropy_sourced_keys(self):
        # k2 carries np.random.randint itself (an unseeded-global finding)
        # on top of the key-material finding, hence 5 for 4 bad keys
        assert rules_of(self.BAD, path=INFER).count("DSTPU005") >= 4

    def test_silent_outside_rng_scope(self):
        assert rules_of(self.BAD, path=TRAIN) == []

    def test_counter_based_fold_in_chain_is_fine(self):
        src = """
import jax.random as jrandom

def key_for(seed, position):
    base = jrandom.PRNGKey(seed)
    return jrandom.fold_in(base, position)

def keys_for(seed, n):
    return jrandom.split(jrandom.PRNGKey(seed), n)
"""
        assert rules_of(src, path=INFER) == []

    def test_constant_seed_and_str_split_are_fine(self):
        src = """
import jax.random as jrandom

def draft_key():
    return jrandom.PRNGKey(0)

def parse(s):
    return s.split(",")
"""
        assert rules_of(src, path=INFER) == []


# ---------------------------------------------------------------------------
# DSTPU006 — transfer-ticket discipline
# ---------------------------------------------------------------------------

class TestTransferDiscipline:
    """``submit_d2h`` ticket ``.value`` reads must be dominated by a drain
    (``drain_before``/``drain_lower_tiers``/``wait``) on every path —
    d2h results settle at drain time, not submit time (ISSUE 20)."""

    def test_flags_value_read_on_open_ticket(self):
        src = """
class Engine:
    def collect(self, blocks):
        t = self.transfer.submit_d2h(blocks)
        return t.value
"""
        assert rules_of(src) == ["DSTPU006"]

    def test_flags_direct_chained_value_read(self):
        src = """
class Engine:
    def collect(self, blocks):
        return self.transfer.submit_d2h(blocks).value
"""
        assert rules_of(src) == ["DSTPU006"]

    def test_drain_before_settles_the_ticket(self):
        src = """
class Engine:
    def collect(self, blocks):
        t = self.transfer.submit_d2h(blocks)
        self.transfer.drain_before([t])
        return t.value

    def collect_waited(self, blocks):
        t = self.transfer.submit_d2h(blocks)
        t.wait()
        return t.value
"""
        assert rules_of(src) == []

    def test_h2d_tickets_settle_at_submit(self):
        src = """
class Engine:
    def upload(self, blocks):
        return self.transfer.submit_h2d(blocks).value
"""
        assert rules_of(src) == []

    def test_returning_the_ticket_is_ownership_transfer(self):
        src = """
class Engine:
    def start(self, blocks):
        return self.transfer.submit_d2h(blocks)
"""
        assert rules_of(src) == []

    def test_drain_on_one_branch_only_still_flags(self):
        src = """
class Engine:
    def collect(self, blocks, eager):
        t = self.transfer.submit_d2h(blocks)
        if eager:
            self.transfer.drain_before([t])
        return t.value
"""
        assert rules_of(src) == ["DSTPU006"]

    def test_rebinding_discards_the_open_ticket(self):
        src = """
class Engine:
    def collect(self, blocks):
        t = self.transfer.submit_d2h(blocks)
        t = self.transfer.submit_h2d(blocks)
        return t.value
"""
        assert rules_of(src) == []

    def test_silent_outside_transfer_scope(self):
        src = """
class Engine:
    def collect(self, blocks):
        t = self.transfer.submit_d2h(blocks)
        return t.value
"""
        assert rules_of(src, path=TRAIN) == []


# ---------------------------------------------------------------------------
# DSTPU007 — mutate-before-raise in hot paths
# ---------------------------------------------------------------------------

class TestMutateBeforeRaise:
    """A typed raise reached after a ``self.*`` write on the same path
    leaves the engine half-mutated for the resilience layer's typed
    containment to retry against (ISSUE 20)."""

    def test_flags_raise_after_state_write(self):
        src = """
class Engine:
    def decode_step(self, req):
        self.active[req.rid] = req
        if req.bad:
            raise ValueError("bad request")
"""
        assert rules_of(src) == ["DSTPU007"]

    def test_validate_before_mutate_is_fine(self):
        src = """
class Engine:
    def decode_step(self, req):
        if req.bad:
            raise ValueError("bad request")
        self.active[req.rid] = req
"""
        assert rules_of(src) == []

    def test_counter_bumps_are_exempt(self):
        src = """
class Engine:
    def _put_paged(self, req):
        self.plan_deferrals += 1
        if req.bad:
            raise ValueError("bad request")
"""
        assert rules_of(src) == []

    def test_try_with_handler_is_the_rollback_idiom(self):
        src = """
class Engine:
    def decode_step(self, req):
        self.active[req.rid] = req
        try:
            if req.bad:
                raise ValueError("bad request")
        except ValueError:
            del self.active[req.rid]
            raise
"""
        assert rules_of(src) == []

    def test_sibling_branches_are_isolated(self):
        src = """
class Engine:
    def decode_step(self, req):
        if req.fresh:
            self.active[req.rid] = req
        elif req.bad:
            raise ValueError("bad request")
"""
        assert rules_of(src) == []

    def test_mutation_unioned_after_branches(self):
        src = """
class Engine:
    def decode_step(self, req):
        if req.fresh:
            self.active[req.rid] = req
        if req.bad:
            raise ValueError("bad request")
"""
        assert rules_of(src) == ["DSTPU007"]

    def test_silent_in_cold_function(self):
        src = """
class Engine:
    def setup(self, req):
        self.active[req.rid] = req
        if req.bad:
            raise ValueError("bad request")
"""
        assert rules_of(src) == []


# ---------------------------------------------------------------------------
# suppression: inline pragma + baseline
# ---------------------------------------------------------------------------

SUPPRESSIBLE = """
import numpy as np

class Engine:
    def decode_step(self, lg):
        return np.asarray(lg)
"""


class TestSuppression:
    def test_inline_pragma(self):
        tagged = SUPPRESSIBLE.replace(
            "np.asarray(lg)", "np.asarray(lg)  # dstpu-lint: ignore[DSTPU001]")
        assert [f for f in lint_source(tagged, SERVE)
                if not f.suppressed_inline] == []
        # bare `ignore` suppresses every rule on the line
        bare = SUPPRESSIBLE.replace(
            "np.asarray(lg)", "np.asarray(lg)  # dstpu-lint: ignore")
        assert all(f.suppressed_inline for f in lint_source(bare, SERVE))
        # a pragma for a different rule does NOT suppress
        wrong = SUPPRESSIBLE.replace(
            "np.asarray(lg)", "np.asarray(lg)  # dstpu-lint: ignore[DSTPU005]")
        assert [f.rule for f in lint_source(wrong, SERVE)
                if not f.suppressed_inline] == ["DSTPU001"]

    def test_baseline_round_trip(self, tmp_path):
        src_file = tmp_path / "deepspeed_tpu" / "serve" / "mod.py"
        src_file.parent.mkdir(parents=True)
        src_file.write_text(SUPPRESSIBLE)
        findings = lint_paths([str(tmp_path)])
        assert [f.rule for f in findings] == ["DSTPU001"]

        bl = tmp_path / "baseline.txt"
        n = save_baseline(str(bl), findings)
        assert n == 1
        unsup, stale = apply_baseline(findings, load_baseline(str(bl)))
        assert unsup == [] and stale == set()

        # keys survive line drift (a comment shifts everything down)...
        src_file.write_text("# a new leading comment\n" + SUPPRESSIBLE)
        drifted = lint_paths([str(tmp_path)])
        unsup, stale = apply_baseline(drifted, load_baseline(str(bl)))
        assert unsup == [] and stale == set()

        # ...but NOT edits to the flagged line itself: that needs re-review
        src_file.write_text(SUPPRESSIBLE.replace(
            "np.asarray(lg)", "np.asarray(lg[0])"))
        edited = lint_paths([str(tmp_path)])
        unsup, stale = apply_baseline(edited, load_baseline(str(bl)))
        assert [f.rule for f in unsup] == ["DSTPU001"] and len(stale) == 1

    def test_missing_baseline_is_empty(self, tmp_path):
        assert load_baseline(str(tmp_path / "nope.txt")) == set()

    def test_malformed_baseline_raises(self, tmp_path):
        bad = tmp_path / "bad.txt"
        bad.write_text("DSTPU001\tonly-two-fields\n")
        with pytest.raises(ValueError, match="malformed"):
            load_baseline(str(bad))

    def test_norm_path_is_location_independent(self):
        assert _norm_path("/a/b/deepspeed_tpu/serve/x.py") == \
            _norm_path("deepspeed_tpu/serve/x.py")
        assert _norm_path("/tmp/loose.py") == "loose.py"


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCLI:
    def _tree(self, tmp_path, src=SUPPRESSIBLE):
        f = tmp_path / "deepspeed_tpu" / "serve" / "mod.py"
        f.parent.mkdir(parents=True)
        f.write_text(src)
        return tmp_path

    def test_exit_1_on_findings_0_on_clean(self, tmp_path, capsys):
        root = self._tree(tmp_path)
        assert lint_main([str(root), "--baseline", "none"]) == 1
        out = capsys.readouterr().out
        assert "DSTPU001" in out and "hint:" in out and "mod.py" in out
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        assert lint_main([str(clean), "--baseline", "none"]) == 0

    def test_exit_2_on_usage_errors(self, tmp_path, capsys):
        assert lint_main([str(tmp_path / "missing")]) == 2
        assert lint_main([str(tmp_path), "--rules", "DSTPU999"]) == 2
        capsys.readouterr()

    def test_write_baseline_then_clean(self, tmp_path, capsys):
        root = self._tree(tmp_path)
        bl = tmp_path / "bl.txt"
        assert lint_main([str(root), "--baseline", str(bl),
                          "--write-baseline"]) == 0
        assert lint_main([str(root), "--baseline", str(bl)]) == 0
        capsys.readouterr()

    def test_rules_filter_and_json(self, tmp_path, capsys):
        root = self._tree(tmp_path)
        assert lint_main([str(root), "--baseline", "none",
                          "--rules", "DSTPU002"]) == 0  # only 001 present
        capsys.readouterr()
        assert lint_main([str(root), "--baseline", "none", "--json"]) == 1
        out = capsys.readouterr().out
        assert '"rule": "DSTPU001"' in out

    def test_list_rules(self, capsys):
        assert lint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("DSTPU001", "DSTPU002", "DSTPU003", "DSTPU004",
                    "DSTPU005", "DSTPU006", "DSTPU007"):
            assert rid in out

    def test_syntax_error_fails_loudly(self, tmp_path, capsys):
        f = tmp_path / "broken.py"
        f.write_text("def f(:\n")
        assert lint_main([str(f), "--baseline", "none"]) == 1
        assert "DSTPU000" in capsys.readouterr().out

    def test_check_programs_dry_mode(self, tmp_path, capsys):
        """``--check-programs`` (ISSUE 20 satellite): the no-retrace
        manifest consistency gate pre-commit runs — registration coverage
        and staleness from a pure AST scan, no jax import."""
        import json

        src = tmp_path / "deepspeed_tpu" / "serve" / "mod.py"
        src.parent.mkdir(parents=True)
        src.write_text(
            "from deepspeed_tpu.analysis import audited_jit\n"
            "def build(step):\n"
            "    return audited_jit('serve.step', step, max_traces=2)\n")
        man = tmp_path / "programs.json"
        man.write_text(json.dumps({"version": 1, "jax": "0.0", "programs": {
            "serve.step": {"max_traces": 2, "sites": [],
                           "variants": [{"digest": "abc"}]}}}))
        argv = [str(tmp_path), "--check-programs", "--programs", str(man)]
        assert lint_main(argv) == 0
        assert "consistent" in capsys.readouterr().out

        # an unpinned registration drifts, attributed to its file:line
        src.write_text(src.read_text().replace("serve.step", "serve.other"))
        assert lint_main(argv) == 1
        out = capsys.readouterr().out
        assert "serve.other" in out and "mod.py:3" in out   # unpinned
        assert "serve.step" in out and "stale" in out        # stale pin

        # a corrupt manifest is a loud failure, not a silent pass
        man.write_text("{not json")
        assert lint_main(argv) == 1
        assert "not valid JSON" in capsys.readouterr().out


class TestLintCache:
    """mtime-keyed finding cache (docs/ANALYSIS.md): unchanged files are
    served from the cache, edits/rule-set changes invalidate per file,
    and suppression still applies on cached findings."""

    def _tree(self, tmp_path):
        f = tmp_path / "deepspeed_tpu" / "serve" / "mod.py"
        f.parent.mkdir(parents=True)
        f.write_text(SUPPRESSIBLE)
        clean = tmp_path / "deepspeed_tpu" / "serve" / "clean.py"
        clean.write_text("x = 1\n")
        return tmp_path, f

    def test_hit_on_unchanged_miss_on_edit(self, tmp_path):
        from deepspeed_tpu.analysis.cache import LintCache, lint_paths_cached

        root, f = self._tree(tmp_path)
        cpath = str(tmp_path / "cache.json")
        cold = LintCache(cpath)
        found1 = lint_paths_cached([str(root)], None, cold)
        assert cold.hits == 0 and cold.misses == 2
        warm = LintCache(cpath)
        found2 = lint_paths_cached([str(root)], None, warm)
        assert warm.hits == 2 and warm.misses == 0
        assert ([(x.rule, x.norm_path, x.line) for x in found1]
                == [(x.rule, x.norm_path, x.line) for x in found2])
        # an edit invalidates exactly that file (mtime_ns + size key)
        f.write_text(SUPPRESSIBLE + "\n# touched\n")
        os.utime(f, ns=(1, 1))  # force a distinct mtime even on fast FS
        third = LintCache(cpath)
        lint_paths_cached([str(root)], None, third)
        assert third.hits == 1 and third.misses == 1

    def test_rule_set_change_invalidates(self, tmp_path):
        from deepspeed_tpu.analysis.cache import LintCache, lint_paths_cached

        root, _ = self._tree(tmp_path)
        cpath = str(tmp_path / "cache.json")
        lint_paths_cached([str(root)], ["DSTPU001"], LintCache(cpath))
        narrow = LintCache(cpath)
        found = lint_paths_cached([str(root)], ["DSTPU002"], narrow)
        assert narrow.misses == 2 and not found  # 001-only fixture

    def test_corrupt_cache_is_cold_not_fatal(self, tmp_path):
        from deepspeed_tpu.analysis.cache import LintCache, lint_paths_cached

        root, _ = self._tree(tmp_path)
        cpath = tmp_path / "cache.json"
        cpath.write_text("{not json")
        cache = LintCache(str(cpath))
        found = lint_paths_cached([str(root)], None, cache)
        assert cache.misses == 2 and len(found) >= 1

    def test_data_file_edit_invalidates(self, tmp_path, monkeypatch):
        """Editing a checked-in data file (baseline.txt / programs.json)
        flushes the whole cache like a linter upgrade (ISSUE 20
        satellite): a re-pin must never serve pre-re-pin findings."""
        import deepspeed_tpu.analysis.cache as cache_mod
        from deepspeed_tpu.analysis.cache import LintCache, lint_paths_cached

        pkg = tmp_path / "fakepkg"
        pkg.mkdir()
        (pkg / "lint.py").write_text("# linter source\n")
        bl = pkg / "baseline.txt"
        bl.write_text("DSTPU001\tdeepspeed_tpu/serve/mod.py\tx\n")
        monkeypatch.setattr(cache_mod, "__file__", str(pkg / "cache.py"))
        self._tree(tmp_path)
        root = tmp_path / "deepspeed_tpu"
        cpath = str(tmp_path / "cache.json")
        lint_paths_cached([str(root)], None, LintCache(cpath))
        warm = LintCache(cpath)
        lint_paths_cached([str(root)], None, warm)
        assert warm.hits == 2 and warm.misses == 0
        # a baseline re-pin (content + mtime change) = full cold cache
        bl.write_text("DSTPU001\tdeepspeed_tpu/serve/mod.py\ty\n")
        os.utime(bl, ns=(1, 1))
        cold = LintCache(cpath)
        lint_paths_cached([str(root)], None, cold)
        assert cold.hits == 0 and cold.misses == 2

    def test_cli_cache_flag_and_pragma_on_cached_findings(self, tmp_path,
                                                          capsys):
        root, _ = self._tree(tmp_path)
        cpath = str(tmp_path / "cache.json")
        argv = [str(root), "--baseline", "none", f"--cache={cpath}"]
        assert lint_main(argv) == 1        # cold: finding reported
        assert lint_main(argv) == 1        # warm: cached finding reported
        out = capsys.readouterr().out
        assert "cache 2 hits" in out
        # baseline suppression applies to cached findings (fresh each run)
        bl = tmp_path / "bl.txt"
        assert lint_main([str(root), "--baseline", str(bl),
                          "--write-baseline"]) == 0
        assert lint_main([str(root), "--baseline", str(bl),
                          f"--cache={cpath}"]) == 0
        capsys.readouterr()


# ---------------------------------------------------------------------------
# the tier-1 gate: the repo's own tree must be clean
# ---------------------------------------------------------------------------

def test_repo_tree_has_zero_unsuppressed_findings():
    """THE gate (ISSUE 5 acceptance): ``python -m deepspeed_tpu.analysis
    deepspeed_tpu/`` exits 0 — every hazard in the tree is either fixed or
    a reviewed baseline entry. A new host sync, fresh hot-path allocation,
    untyped raise, retrace hazard, or nondeterministic decision fails CI
    here with a file:line and a fix hint, not as bench noise weeks later."""
    import deepspeed_tpu

    pkg = os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))
    findings = lint_paths([pkg])
    unsup, stale = apply_baseline(findings, load_baseline(
        default_baseline_path()))
    assert not unsup, "unsuppressed lint findings:\n" + "\n".join(
        f.render() for f in unsup)
    assert not stale, f"stale baseline entries (prune them): {stale}"


def test_repo_gate_via_cli_exit_code():
    import deepspeed_tpu

    pkg = os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))
    assert lint_main([pkg, "-q"]) == 0


@pytest.mark.parametrize("name", [
    "bench_serve.py", "bench_configs.py", "scaling_model.py",
    "BENCH_SERVE.json", "BENCH_TRAIN.json", "SCALING_MODEL.json"])
def test_docs_cite_no_deleted_bench(name):
    """The programs and records from before the chip are gone (PR 32): the
    README and ``docs/`` point at ``benchmark/`` and the tests, never at
    them. (The histories — CHANGES.md, PERF.md, ROADMAP.md — may.)"""
    import glob

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    assert not os.path.exists(os.path.join(repo, name))
    docs = [os.path.join(repo, "README.md")] + sorted(
        glob.glob(os.path.join(repo, "docs", "*.md")))
    assert len(docs) > 5
    citing = [os.path.relpath(p, repo) for p in docs
              if name in open(p, encoding="utf-8").read()]
    assert not citing, f"{name} still cited by {citing}"

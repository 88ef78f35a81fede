"""Compiled-program auditor (docs/ANALYSIS.md "Program audit").

The AST linter (``lint.py``) polices *source*; nothing there can see what
XLA actually compiled. A silent extra trace, a stray host callback, or a
bf16→f32 promotion inside a steady-state program only ever surfaced as
bench noise — exactly the host-round-trip/recompile regression class the
TPU serving studies (PAPERS.md) identify as the scaling wall. This module
closes the gap at the jaxpr level:

- :func:`audited_jit` wraps ``jax.jit`` at every compiled-program build
  site (ragged decode, fused scan, verify, dispatch, COW copy, tier
  scatter/gather, train fwd/bwd). Off (``DSTPU_AUDIT`` unset) it is a
  transparent pass-through. Armed (``DSTPU_AUDIT=1``, the conftest
  default for the serve/train tier-1 modules), every *new* argument
  signature is retraced once with ``jax.make_jaxpr`` — trace only, no
  XLA compile — fingerprinted, and checked against the pinned manifest
  before the real dispatch runs.
- Each program's **structural fingerprint** is geometry-free by
  construction: the canonicalized equation-op set (recursively through
  sub-jaxprs), the deduplicated ``dtype[rank]`` input/output aval
  signatures (concrete dims collapsed — test geometry and model depth
  must not perturb the digest), the donation map, and the set of
  small→wide float ``convert_element_type`` promotions. The sha256 of
  that canonical form is the digest pinned in ``analysis/programs.json``.
- The **manifest** replaces the scattered ``*_cache_size <= N`` test
  asserts with one drift gate: an unpinned program, a digest not in the
  pinned variant list, a trace count above ``max_traces``, or a host
  callback primitive raises :class:`ProgramAuditError` with the
  registration site's ``file:line``. Re-pin workflow (mirroring
  ``baseline.txt``): run the audited suites with ``DSTPU_AUDIT=write``
  and review the ``programs.json`` diff.
- :func:`check_manifest` is the **no-retrace dry mode** for pre-commit:
  a pure AST scan for ``audited_jit("name", ...)`` registrations checked
  against the manifest for coverage and staleness — no jax import, no
  device, milliseconds.

Digest comparison is strict only when the running jax version matches the
manifest's (op decompositions differ across releases); the trace-count
bound and the host-callback hazard are enforced unconditionally.
"""

import ast
import hashlib
import json
import os
import traceback
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .lint import _dotted, _norm_path, iter_python_files

_ENV = "DSTPU_AUDIT"
_VERSION = 1

#: primitive names that re-enter the host from inside a compiled program —
#: a steady-state step carrying one of these pays a host round trip per
#: dispatch, the exact regression class the serving benches chase
HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "debug_print", "outside_call", "host_callback_call",
})

_NARROW_FLOATS = frozenset({"bfloat16", "float16"})
_WIDE_FLOATS = frozenset({"float32", "float64"})


class ProgramAuditError(AssertionError):
    """A compiled program drifted from the pinned manifest or carries a
    hazard. ``AssertionError`` subclass (like ``SanitizerError``) so the
    resilience layer's typed-``RuntimeError`` containment can never
    retry, quarantine, or shed an audit finding."""


def audit_mode() -> str:
    """``""`` off | ``"check"`` enforce the manifest | ``"write"`` re-pin."""
    v = os.environ.get(_ENV, "").strip().lower()
    if v in ("", "0", "off", "false"):
        return ""
    return "write" if v == "write" else "check"


def default_manifest_path() -> str:
    """The packaged manifest shipped next to this module."""
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "programs.json")


def _jax_version() -> str:
    import jax
    return jax.__version__


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------

def _sub_jaxprs(value):
    """Yield every (Closed)Jaxpr nested in an eqn param value."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _iter_eqns(sub)


def _aval_sig(aval) -> str:
    """``dtype[rK]`` — dims collapsed to rank so fingerprints are stable
    across test geometries (max_seqs, token_budget, model depth)."""
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", ())
    return f"{getattr(dtype, 'name', str(dtype))}[r{len(shape)}]"


def fingerprint(closed, donate: Sequence[int] = ()) -> Dict[str, object]:
    """Structural fingerprint of a traced program: canonical op set,
    deduplicated in/out aval signatures, donation map, and narrow→wide
    float promotions — plus the sha256 digest of that canonical form.
    Host-callback primitives are reported separately (``callbacks``);
    they still perturb the digest via the op set."""
    jaxpr = closed.jaxpr
    ops: Set[str] = set()
    callbacks: Set[str] = set()
    promotions: Set[str] = set()
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        ops.add(name)
        if name in HOST_CALLBACK_PRIMS or "callback" in name:
            callbacks.add(name)
        if name == "convert_element_type":
            src = getattr(getattr(eqn.invars[0], "aval", None), "dtype", None)
            dst = eqn.params.get("new_dtype")
            src_n = getattr(src, "name", str(src))
            dst_n = getattr(dst, "name", str(dst))
            if src_n in _NARROW_FLOATS and dst_n in _WIDE_FLOATS:
                promotions.add(f"{src_n}->{dst_n}")
    fp: Dict[str, object] = {
        "ops": sorted(ops),
        "in": sorted({_aval_sig(v.aval) for v in jaxpr.invars}),
        "out": sorted({_aval_sig(v.aval) for v in jaxpr.outvars}),
        "donate": sorted(int(i) for i in donate),
        "promotions": sorted(promotions),
    }
    fp["digest"] = hashlib.sha256(
        json.dumps(fp, sort_keys=True).encode()).hexdigest()[:16]
    fp["callbacks"] = sorted(callbacks)
    return fp


# ---------------------------------------------------------------------------
# the registry + manifest gate
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class _Registered:
    """One ``audited_jit`` site: the name keys the manifest, the site is
    the ``file:line`` every violation report carries."""
    name: str
    site: str
    declared_max: int


class ProgramRegistry:
    """Loads the manifest, checks observations against it (check mode),
    and merges observations back into it (write mode)."""

    def __init__(self, manifest_path: Optional[str] = None):
        self.manifest_path = manifest_path or default_manifest_path()
        self._manifest: Optional[dict] = None

    def manifest(self) -> dict:
        if self._manifest is None:
            try:
                with open(self.manifest_path, "r", encoding="utf-8") as fh:
                    self._manifest = json.load(fh)
            except (OSError, ValueError):
                self._manifest = {"version": _VERSION, "jax": None,
                                  "programs": {}}
        return self._manifest

    # -- check mode ------------------------------------------------------
    def observe(self, reg: _Registered, fp: Dict[str, object],
                mode: str) -> None:
        if mode == "write":
            self._pin(reg, fp)
            return
        entry = self.manifest().get("programs", {}).get(reg.name)
        if fp["callbacks"] and not (entry or {}).get("allow_host_callbacks"):
            raise ProgramAuditError(
                f"{reg.site}: program '{reg.name}' contains host-callback "
                f"primitive(s) {fp['callbacks']} — a steady-state program "
                "must never re-enter the host; remove the "
                "callback/debug-print or pin allow_host_callbacks with a "
                "reviewed justification (docs/ANALYSIS.md#program-audit)")
        if entry is None:
            raise ProgramAuditError(
                f"{reg.site}: program '{reg.name}' is not pinned in "
                f"{self.manifest_path} — every compiled program must be "
                "manifest-pinned; re-pin with DSTPU_AUDIT=write and review "
                "the diff (docs/ANALYSIS.md#program-audit)")
        pinned = {v["digest"]: v for v in entry.get("variants", ())}
        if (fp["digest"] not in pinned
                and self.manifest().get("jax") == _jax_version()):
            raise ProgramAuditError(
                f"{reg.site}: program '{reg.name}' drifted from the pinned "
                f"manifest — digest {fp['digest']} is not among "
                f"{sorted(pinned)} ({self._drift_summary(fp, pinned)}); "
                "if the change is intentional re-pin with DSTPU_AUDIT=write "
                "(docs/ANALYSIS.md#program-audit)")

    @staticmethod
    def _drift_summary(fp: Dict[str, object], pinned: Dict[str, dict]) -> str:
        """Name what moved relative to the nearest pinned variant."""
        best, overlap = None, -1
        for v in pinned.values():
            n = len(set(v.get("ops", ())) & set(fp["ops"]))
            if n > overlap:
                best, overlap = v, n
        if best is None:
            return "no variants pinned"
        bits = []
        new_ops = sorted(set(fp["ops"]) - set(best.get("ops", ())))
        lost_ops = sorted(set(best.get("ops", ())) - set(fp["ops"]))
        if new_ops:
            bits.append(f"new op(s) {new_ops[:4]}")
        if lost_ops:
            bits.append(f"dropped op(s) {lost_ops[:4]}")
        for k in ("in", "out", "donate", "promotions"):
            if fp[k] != best.get(k):
                bits.append(f"{k} {best.get(k)} -> {fp[k]}")
        return "; ".join(bits) or "op multiset unchanged, avals moved"

    def check_trace_count(self, reg: _Registered, n_traces: int) -> None:
        entry = self.manifest().get("programs", {}).get(reg.name)
        bound = (entry or {}).get("max_traces", reg.declared_max)
        if n_traces > bound:
            raise ProgramAuditError(
                f"{reg.site}: program '{reg.name}' holds {n_traces} compiled "
                f"traces, exceeding the pinned bound {bound} — an extra "
                "shape/dtype/static variant entered the hot path (retrace "
                "storm precursor); fix the caller or re-pin max_traces with "
                "DSTPU_AUDIT=write (docs/ANALYSIS.md#program-audit)")

    # -- write mode ------------------------------------------------------
    def _pin(self, reg: _Registered, fp: Dict[str, object]) -> None:
        """Read-merge-write the manifest: union the digest variant in,
        never lower an existing ``max_traces`` below the declared bound."""
        try:
            with open(self.manifest_path, "r", encoding="utf-8") as fh:
                man = json.load(fh)
        except (OSError, ValueError):
            man = {"version": _VERSION, "jax": None, "programs": {}}
        man["version"] = _VERSION
        man["jax"] = _jax_version()
        entry = man.setdefault("programs", {}).setdefault(reg.name, {
            "max_traces": reg.declared_max, "sites": [], "variants": []})
        entry["max_traces"] = max(entry.get("max_traces", 0),
                                  reg.declared_max)
        site_file = reg.site.rsplit(":", 1)[0]
        if site_file not in entry["sites"]:
            entry["sites"] = sorted(entry["sites"] + [site_file])
        variant = {k: fp[k] for k in ("digest", "ops", "in", "out",
                                      "donate", "promotions")}
        if all(v["digest"] != fp["digest"] for v in entry["variants"]):
            entry["variants"] = sorted(entry["variants"] + [variant],
                                       key=lambda v: v["digest"])
        tmp = self.manifest_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(man, fh, indent=1, sort_keys=True)
            fh.write("\n")
        os.replace(tmp, self.manifest_path)
        self._manifest = man


#: the process-wide registry every in-tree ``audited_jit`` site uses
GLOBAL_REGISTRY = ProgramRegistry()


# ---------------------------------------------------------------------------
# the jit wrapper
# ---------------------------------------------------------------------------

def _call_site() -> str:
    here = os.path.abspath(__file__)
    for frame in reversed(traceback.extract_stack()):
        if os.path.abspath(frame.filename) != here:
            return f"{_norm_path(frame.filename)}:{frame.lineno}"
    return "<unknown>:0"


def _leaf_key(x) -> Tuple:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(shape), str(dtype))
    if isinstance(x, (bool, int, float, str, bytes, type(None))):
        return (type(x).__name__, x)
    return (type(x).__name__,)


def _sig_key(args: tuple, kwargs: dict, static: Sequence[int]) -> Tuple:
    """Hashable dispatch-signature key (shapes/dtypes/statics) — one
    ``make_jaxpr`` capture per distinct key, mirroring jit's own cache
    granularity closely enough to bound audit overhead."""
    import jax
    parts: List[Tuple] = []
    for i, a in enumerate(args):
        if i in static:
            parts.append(("s", i, a if isinstance(
                a, (bool, int, float, str, bytes, type(None))) else repr(a)))
        else:
            leaves, treedef = jax.tree_util.tree_flatten(a)
            parts.append((treedef, tuple(_leaf_key(x) for x in leaves)))
    for k in sorted(kwargs):
        leaves, treedef = jax.tree_util.tree_flatten(kwargs[k])
        parts.append((k, treedef, tuple(_leaf_key(x) for x in leaves)))
    return tuple(parts)


class AuditedFunction:
    """The ``jax.jit`` wrapper :func:`audited_jit` returns. Transparent
    when the audit is off; armed, it fingerprints each new dispatch
    signature *before* the call (donated buffers are still alive) and
    enforces the trace-count bound after it. Exposes ``_cache_size`` and
    ``lower`` so the engines' cache-size properties and the retrace-guard
    tests see the underlying compiled function unchanged."""

    __slots__ = ("reg", "_fn", "_fun", "_static", "_donate", "_registry",
                 "_seen")

    def __init__(self, reg: _Registered, fn, fun, static: Sequence[int],
                 donate: Sequence[int], registry: ProgramRegistry):
        self.reg = reg
        self._fn = fn
        self._fun = fun
        self._static = tuple(static)
        self._donate = tuple(donate)
        self._registry = registry
        self._seen: Set[Tuple] = set()

    def __call__(self, *args, **kwargs):
        mode = audit_mode()
        if mode:
            key = _sig_key(args, kwargs, self._static)
            if key not in self._seen:
                self._seen.add(key)
                self._capture(args, kwargs, mode)
        out = self._fn(*args, **kwargs)
        if mode:
            self._registry.check_trace_count(self.reg, self._fn._cache_size())
        return out

    def _capture(self, args, kwargs, mode: str) -> None:
        import jax
        try:
            closed = jax.make_jaxpr(self._fun, static_argnums=self._static)(
                *args, **kwargs)
        except ProgramAuditError:
            raise
        except Exception as e:
            raise ProgramAuditError(
                f"{self.reg.site}: auditing program '{self.reg.name}' "
                f"failed to retrace: {type(e).__name__}: {e}") from e
        self._registry.observe(self.reg, fingerprint(closed, self._donate),
                               mode)

    def _cache_size(self) -> int:
        return self._fn._cache_size()

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    @property
    def __wrapped__(self):
        return self._fn


def audited_jit(name: str, fun, *, max_traces: int = 1,
                donate_argnums: Sequence[int] = (),
                static_argnums: Sequence[int] = (),
                registry: Optional[ProgramRegistry] = None, **jit_kwargs):
    """``jax.jit`` with a manifest-pinned identity. ``name`` keys the
    program in ``analysis/programs.json``; ``max_traces`` is the declared
    compiled-variant bound recorded at re-pin time (the manifest's value
    governs at check time). All other arguments pass through to
    ``jax.jit`` unchanged."""
    import jax
    fn = jax.jit(fun, donate_argnums=tuple(donate_argnums),
                 static_argnums=tuple(static_argnums), **jit_kwargs)
    reg = _Registered(name=name, site=_call_site(),
                      declared_max=int(max_traces))
    return AuditedFunction(reg, fn, fun, static_argnums, donate_argnums,
                           registry or GLOBAL_REGISTRY)


# ---------------------------------------------------------------------------
# manifest-backed trace bounds (replaces scattered `*_cache_size <= N`)
# ---------------------------------------------------------------------------

#: manifest program name → the engine property counting its live traces
ENGINE_TRACE_PROPS: Dict[str, str] = {
    "engine_v2.ragged": "ragged_cache_size",
    "engine_v2.fused": "fused_cache_size",
    "engine_v2.verify": "verify_cache_size",
}


def assert_trace_bounds(engine, names: Optional[Iterable[str]] = None,
                        registry: Optional[ProgramRegistry] = None
                        ) -> List[Tuple[str, int, int]]:
    """Assert every step-program trace counter of ``engine`` is within its
    manifest ``max_traces`` bound — the single manifest-backed home of the
    bound formerly copy-pasted as ``assert eng.ragged_cache_size <= 4``
    across the suite. Returns ``[(name, observed, bound), ...]`` so tests
    can additionally pin exact counts where they mean to."""
    reg = registry or GLOBAL_REGISTRY
    programs = reg.manifest().get("programs", {})
    wanted = set(names) if names is not None else None
    out: List[Tuple[str, int, int]] = []
    for name, prop in ENGINE_TRACE_PROPS.items():
        if wanted is not None and name not in wanted:
            continue
        entry = programs.get(name)
        if entry is None:
            raise ProgramAuditError(
                f"program '{name}' is missing from {reg.manifest_path} — "
                "re-pin with DSTPU_AUDIT=write")
        observed = getattr(engine, prop)
        bound = entry["max_traces"]
        if observed > bound:
            raise ProgramAuditError(
                f"{prop} = {observed} exceeds the manifest bound {bound} "
                f"for program '{name}' (re-pin only with review: "
                "docs/ANALYSIS.md#program-audit)")
        out.append((name, observed, bound))
    return out


# ---------------------------------------------------------------------------
# no-retrace dry mode (pre-commit): manifest <-> source consistency
# ---------------------------------------------------------------------------

def registered_program_names(paths: Iterable[str]
                             ) -> Dict[str, List[str]]:
    """Pure AST scan for ``audited_jit("<name>", ...)`` registration sites
    under ``paths`` — no jax import, no execution. Returns
    ``{name: [file:line, ...]}``."""
    names: Dict[str, List[str]] = {}
    for path in iter_python_files(paths):
        try:
            with open(path, "r", encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=path)
        except (OSError, SyntaxError):
            continue
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and (_dotted(node.func) or "").split(".")[-1]
                    == "audited_jit"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                names.setdefault(node.args[0].value, []).append(
                    f"{_norm_path(path)}:{node.lineno}")
    return names


def check_manifest(paths: Iterable[str],
                   manifest_path: Optional[str] = None) -> List[str]:
    """Dry manifest check: the manifest parses and is well-formed, every
    in-source ``audited_jit`` registration is pinned, and no pinned entry
    is stale (registration removed). Returns human-readable problems
    (empty = clean); never traces or imports jax."""
    mpath = manifest_path or default_manifest_path()
    problems: List[str] = []
    try:
        with open(mpath, "r", encoding="utf-8") as fh:
            man = json.load(fh)
    except OSError as e:
        return [f"{mpath}: manifest unreadable ({e}) — generate it with "
                "DSTPU_AUDIT=write"]
    except ValueError as e:
        return [f"{mpath}: manifest is not valid JSON ({e})"]
    programs = man.get("programs")
    if not isinstance(programs, dict):
        return [f"{mpath}: manifest has no 'programs' table"]
    for name, entry in sorted(programs.items()):
        if not isinstance(entry.get("max_traces"), int) \
                or entry["max_traces"] < 1:
            problems.append(f"{mpath}: program '{name}' needs an integer "
                            "max_traces >= 1")
        variants = entry.get("variants")
        if not variants or not all(isinstance(v.get("digest"), str)
                                   for v in variants):
            problems.append(f"{mpath}: program '{name}' has no pinned "
                            "digest variants — re-pin with DSTPU_AUDIT=write")
    registered = registered_program_names(paths)
    for name, sites in sorted(registered.items()):
        if name not in programs:
            problems.append(f"{sites[0]}: program '{name}' is registered "
                            f"but not pinned in {mpath} — re-pin with "
                            "DSTPU_AUDIT=write")
    for name in sorted(set(programs) - set(registered)):
        problems.append(f"{mpath}: pinned program '{name}' has no "
                        "audited_jit registration in the tree (stale — "
                        "re-pin to prune)")
    return problems

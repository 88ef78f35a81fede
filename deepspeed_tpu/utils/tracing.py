"""The program's one span recorder (docs/TRACING.md).

Spans are recorded where the work happens: the serving scheduler, the serving
engine and the training engine open them around their own phases, with counts
as attributes. The recorder follows the JAX profiler and has no switch of its
own:

- **on** exactly while a profiler session is active (``jax.profiler.
  start_trace`` / ``start_server`` capture, the benchmark's ``Tracer``):
  every :func:`span` is recorded in memory *and* is a
  ``jax.profiler.TraceAnnotation``, so it sits on the trace's clock on the
  ``/host:CPU`` plane next to the device's operations;
- **off** otherwise: :func:`span` costs one flag read and returns one shared
  no-op object; nothing is recorded.

The flag is ``TraceMe.is_enabled()`` of the installed jaxlib's profiler
bindings (``jax._src.lib._profiler``), the same flag ``TraceAnnotation``
itself consults; ``enabled`` is the only name bound to it.

One thing is kept whether or not a profiler records: what it cost to make a
program runnable. A ``jax.monitoring`` listener, which runs only when JAX
builds a program, folds the program's trace, its lowering and its backend
compile (or its load from the compile cache) into one ``compile`` record
with the package function that made the call (:func:`builds`); every kernel
is bound through :func:`pallas_call`, which puts the tracing of its body on
that record by name; and an engine's constructor writes one ``engine.init``
record (:class:`Phases`, :func:`inits`). While on, the same ``compile``
record is also a span, and :func:`note_program` remembers which jitted
programs ran, so that :func:`device_scopes` can map the device's instruction
names to the ``jax.named_scope`` they were traced under, and
:func:`device_programs` to the program variants that hold them — after the
window, never in it.

No other module of ``deepspeed_tpu`` calls ``jax.profiler`` annotations
directly.
"""

import collections
import contextlib
import contextvars
import itertools
import re
import sys
import threading
import time
from typing import Dict, Iterable, List, NamedTuple, Optional, Set

import jax
from jax._src.lib import _profiler

#: spans kept; older ones fall off (a traced window of 8 s writes ~1e3)
MAX_SPANS = 1 << 16

#: the recorder's clock, in ns. ``time.monotonic`` is the serving
#: scheduler's default clock, so request events and spans share it
clock_ns = time.monotonic_ns


class Record(NamedTuple):
    """One finished span. ``parent`` is the id of the span that was open on
    the same thread when this one started (0: none)."""
    id: int
    name: str
    start: int
    end: int
    parent: int
    attrs: dict


#: ``compile`` and ``engine.init`` records kept while the profiler is off too
#: (a set-up leaves a few hundred)
MAX_KEPT = 1 << 12

_buf: "collections.deque[Record]" = collections.deque(maxlen=MAX_SPANS)
#: the always-on store: what was built and constructed, oldest first
_kept: "collections.deque[Record]" = collections.deque(maxlen=MAX_KEPT)
_ids = itertools.count(1)
_local = threading.local()


#: True while a JAX profiler session is recording: the profiler's own flag,
#: the one ``TraceAnnotation`` consults. Everything below asks this name.
enabled = _profiler.TraceMe.is_enabled


def _stack() -> List[int]:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class _NoSpan:
    """What :func:`span` returns while off: one shared object."""
    __slots__ = ()
    recording = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


NO_SPAN = _NoSpan()


class Span:
    """An open span. ``set()`` adds attributes known only later (the counts
    of a dispatch are known once the batch is built). ``seconds`` is the
    duration after exit, for callers that feed a gauge from the same two
    clock readings (:func:`timed_span`)."""
    __slots__ = ("name", "attrs", "recording", "step", "id", "parent",
                 "start", "end", "_ann")

    def __init__(self, name: str, attrs: dict, recording: bool):
        self.name, self.attrs, self.recording = name, attrs, recording
        self.step = None
        self.start = self.end = 0

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    @property
    def seconds(self) -> float:
        return (self.end - self.start) / 1e9

    def __enter__(self):
        if self.recording:
            stack = _stack()
            self.id = next(_ids)
            self.parent = stack[-1] if stack else 0
            stack.append(self.id)
            self._ann = (
                jax.profiler.TraceAnnotation(self.name) if self.step is None
                else jax.profiler.StepTraceAnnotation(self.name,
                                                      step_num=self.step))
            self._ann.__enter__()
        self.start = clock_ns()
        return self

    def __exit__(self, *exc):
        self.end = clock_ns()
        if self.recording:
            self._ann.__exit__(*exc)
            stack = _stack()
            # an exception that skipped inner exits cannot leave them open
            while stack and stack.pop() != self.id:
                pass
            _buf.append(Record(self.id, self.name, self.start, self.end,
                               self.parent, self.attrs))
        return False


def span(name: str, **attrs):
    """Context manager around one phase. Off: the shared no-op."""
    if not enabled():
        return NO_SPAN
    return Span(name, attrs, True)


def step_span(name: str, step: int, **attrs):
    """:func:`span` around one training step, marked with
    ``jax.profiler.StepTraceAnnotation(step_num=step)`` while on."""
    if not enabled():
        return NO_SPAN
    sp = Span(name, dict(attrs, step=int(step)), True)
    sp.step = int(step)
    return sp


def timed_span(name: str, **attrs) -> Span:
    """A span whose caller needs the duration whether or not anyone records
    (a gauge fed from the same timestamps): always reads the clock, records
    only while on."""
    return Span(name, attrs, enabled())


def event(name: str, start: int, end: int, **attrs) -> None:
    """A span whose ends are known only afterwards (a request's life cycle),
    on :data:`clock_ns`. Its parent is the span open on this thread now: the
    one that caused the transition."""
    if enabled():
        stack = _stack()
        _buf.append(Record(next(_ids), name, int(start), int(end),
                           stack[-1] if stack else 0, attrs))


_PROGRAM_ATTRS = contextvars.ContextVar("dstpu_program_attrs", default=None)


@contextlib.contextmanager
def program_attrs(attrs: dict):
    """Collect into ``attrs`` what the code traced inside says of the program
    it becomes (:func:`set_program_attr`): which of its paths a shape took.
    The engine that builds a step opens this around the model's apply and
    puts ``attrs`` on the step's ``engine.enqueue`` spans."""
    token = _PROGRAM_ATTRS.set(attrs)
    try:
        yield
    finally:
        _PROGRAM_ATTRS.reset(token)


def set_program_attr(**attrs) -> None:
    """Called while a program is traced, by the code that chose; nothing
    outside a :func:`program_attrs`."""
    into = _PROGRAM_ATTRS.get()
    if into is not None:
        into.update(attrs)


def snapshot() -> List[Record]:
    """The recorded spans, oldest first."""
    return list(_buf)


def clear() -> None:
    """Forget the recorded spans and the programs noted for
    :func:`device_scopes`. The always-on records (:func:`builds`,
    :func:`inits`) stay: a set-up is not the window's to forget."""
    _buf.clear()
    _programs.clear()
    _scope_cache.clear()


# -- reading spans ---------------------------------------------------------

def descendants(spans: Iterable[Record], root: int) -> List[Record]:
    """Every span below ``root`` (children, their children...)."""
    spans = list(spans)
    kids: Dict[int, List[Record]] = {}
    for s in spans:
        kids.setdefault(s.parent, []).append(s)
    out, todo = [], [root]
    while todo:
        for s in kids.get(todo.pop(), ()):
            out.append(s)
            todo.append(s.id)
    return out


# -- builds: what it cost to make each program runnable ---------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

#: a build shorter than this that no package function asked for (the caller's
#: one-primitive eager programs) is summed by caller module, not kept
SMALL_BUILD_S = 0.010

#: {caller module: [builds, trace_s, lower_s, load_s]} of those
_small: Dict[str, List[float]] = {}
#: ns spent inside the listener, over the process: the account's own cost
_listener_ns = [0]

#: frames that are no caller: JAX's own and the wrappers it runs under
_NOT_CALLER = ("jax.", "jaxlib.", "contextlib", "functools")
#: the package's own wrapper of ``jax.jit`` (``audited_jit``): the call was
#: made by whoever called through it
_JIT_WRAPPER = "deepspeed_tpu.analysis.program_audit"


def _pending() -> list:
    """This thread's trace, lowering and kernel-bind intervals that no
    finished build has claimed yet: (kind, name, start, end, attrs), by
    ``end``; ``attrs`` is what a kernel was bound with, else None."""
    try:
        return _local.pending
    except AttributeError:
        _local.pending = []
        return _local.pending


def _site(frame):
    """(site, caller) of the build reported from ``frame``: the innermost
    function of this package on the stack, ``module.qualname`` ("": none, a
    program of the caller's own), and the module of the innermost frame that
    is not JAX's."""
    caller = ""
    while frame is not None:
        mod = frame.f_globals.get("__name__", "")
        if mod.startswith("deepspeed_tpu.") and mod != _JIT_WRAPPER \
                and frame.f_code is not _Kernel.__call__.__code__:
            return f"{mod}.{frame.f_code.co_qualname}", caller or mod
        if not caller and mod != "jax" and not mod.startswith(_NOT_CALLER):
            caller = mod
        frame = frame.f_back
    return "", caller


def _close_build(program: str, load_s: float, end: int, frame) -> None:
    """The backend compile (or the cache load) of ``program`` just ended on
    this thread: claim its lowering and its own trace from the pending
    intervals, with the kernels bound inside that trace, and keep one
    record. Inner jits' traces lie inside the program's own and are dropped
    with it, not added."""
    cached = getattr(_local, "cache_read", False)
    _local.cache_read = False
    pend = _pending()
    lower = trace = None
    for i in range(len(pend) - 1, -1, -1):
        kind, name = pend[i][0], pend[i][1]
        if lower is None and kind == "lower" and name == program:
            lower = i
        elif kind == "trace" and program.endswith(f"({name})"):
            trace = i
            break
    trace_s = lower_s = 0.0
    kernels: Dict[str, list] = {}
    kernel_attrs: Dict[str, dict] = {}
    cut = lower
    if lower is not None:
        lower_s = (pend[lower][3] - pend[lower][2]) / 1e9
    if trace is not None:
        start, stop = pend[trace][2:4]
        trace_s = (stop - start) / 1e9
        cut = trace
        while cut and pend[cut - 1][3] >= start:
            cut -= 1
            kind, name, k_start, k_stop, attrs = pend[cut]
            if kind == "kernel":
                into = kernels.setdefault(name, [0, 0.0])
                into[0] += 1
                into[1] += (k_stop - k_start) / 1e9
                if attrs:
                    kernel_attrs[name] = attrs
    if cut is not None:
        del pend[cut:]
    site, caller = _site(frame)
    big = bool(site) or trace_s + lower_s + load_s >= SMALL_BUILD_S
    if not big:
        into = _small.setdefault(caller, [0, 0.0, 0.0, 0.0])
        for i, x in enumerate((1, trace_s, lower_s, load_s)):
            into[i] += x
    keep("compile", end - int(load_s * 1e9), end, kept=big,
         program=program, cached=cached, site=site, caller=caller,
         trace_s=trace_s, lower_s=lower_s, load_s=load_s,
         kernels={k: tuple(v) for k, v in kernels.items()},
         kernel_attrs=kernel_attrs)


def _on_duration(name: str, seconds: float, **kw) -> None:
    """JAX reports, on the thread that makes a program runnable: ``_TRACE``
    for every jitted function it traces (an inner one's from inside the
    outer's), ``_LOWER`` for the program's lowering, and ``_COMPILE`` once
    it is compiled or loaded from the compile cache; a load reports
    ``_CACHE_READ`` first, from inside it. Nothing of this runs when a
    program that exists is called."""
    now = clock_ns()
    if name == _TRACE or name == _LOWER:
        pend = _pending()
        # traces nobody compiled (eval_shape); an interpreted kernel's
        # lowering alone leaves thousands behind its program's own trace
        if len(pend) > 16384:
            del pend[:8192]
        pend.append(("trace" if name == _TRACE else "lower",
                     kw.get("fun_name", ""), now - int(seconds * 1e9), now,
                     None))
    elif name == _CACHE_READ:
        _local.cache_read = True
    elif name == _COMPILE:
        _close_build(kw.get("fun_name", ""), seconds, now, sys._getframe(1))
    else:
        return
    _listener_ns[0] += clock_ns() - now


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def keep(name: str, start: int, end: int, kept: bool = True, **attrs) -> None:
    """A record of the always-on store (``kept``), on :data:`clock_ns`; while
    on, the same record is a span of the window too."""
    on = enabled()
    if not (kept or on):
        return
    stack = _stack() if on else ()
    rec = Record(next(_ids), name, int(start), int(end),
                 stack[-1] if stack else 0, attrs)
    if kept:
        _kept.append(rec)
    if on:
        _buf.append(rec)


def builds() -> List[Record]:
    """Every program this process made runnable, oldest first, profiler or
    no profiler: ``compile`` records whose span is the backend compile or
    the cache load and whose attributes are ``program`` (JAX's name of it),
    ``site``, ``caller``, ``trace_s``, ``lower_s``, ``load_s``, ``cached``
    ``kernels`` ({name: (binds, seconds)}) and ``kernel_attrs`` ({name: what
    the kernel said of itself at its bind}). A live process that
    recompiles shows it here. Short builds of the caller's own are in
    :func:`small_builds` instead."""
    return [r for r in _kept if r.name == "compile"]


def small_builds() -> Dict[str, tuple]:
    """{caller module: (builds, trace_s, lower_s, load_s)} of the builds
    under :data:`SMALL_BUILD_S` that no function of this package asked for."""
    return {k: tuple(v) for k, v in _small.items()}


def inits() -> List[Record]:
    """The ``engine.init`` records: one an engine constructed (or rebuilt)."""
    return [r for r in _kept if r.name == "engine.init"]


def listener_seconds() -> float:
    """What the account has cost this process: seconds spent inside the
    ``jax.monitoring`` listener, the stack walks included."""
    return _listener_ns[0] / 1e9


class _Kernel:
    """What :func:`pallas_call` returns: the kernel's bind, timed under a
    trace."""
    __slots__ = ("name", "call", "attrs")

    def __init__(self, name: str, call, attrs):
        self.name, self.call, self.attrs = name, call, attrs

    def __call__(self, *args):
        if not any(isinstance(a, jax.core.Tracer) for a in args):
            return self.call(*args)     # an eager call: a program of its own
        start = clock_ns()
        try:
            return self.call(*args)
        finally:
            _pending().append(("kernel", self.name, start, clock_ns(),
                               self.attrs))


def pallas_call(kernel, attrs: Optional[dict] = None, **kwargs):
    """``pl.pallas_call`` for every kernel of the package. Binding a kernel
    traces its body's Python, in every process and for every program that
    holds it, compile cache or none: under a trace the bind is timed and
    lands, by the kernel's ``name``, on the record of the program being
    traced on this thread, with ``attrs``: what is known of the call's work
    when it is bound (the flash kernels' ``pairs_computed`` beside
    ``pairs_causal``). It runs when a program is traced and never when it
    executes."""
    from jax.experimental import pallas as pl

    return _Kernel(kwargs["name"], pl.pallas_call(kernel, **kwargs), attrs)


class Phases:
    """A constructor's own account: opened where it starts, ``mark(phase)``
    where a phase ends, ``close()`` writes one always-on record whose
    attributes are the phases' seconds (``<phase>_s``). Once an engine."""
    __slots__ = ("name", "attrs", "start", "_last")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self.start = self._last = clock_ns()

    def mark(self, phase: str) -> None:
        now = clock_ns()
        self.attrs[phase + "_s"] = (now - self._last) / 1e9
        self._last = now

    def close(self) -> None:
        keep(self.name, self.start, clock_ns(), **self.attrs)


# -- device time by scope --------------------------------------------------

#: (name, key) -> (jitted function, abstract args, abstract kwargs)
_programs: Dict[tuple, tuple] = {}
_scope_cache: Dict[tuple, Dict[str, str]] = {}

#: ``jax.named_scope`` names the program uses, by what they classify as
MODEL_SCOPES = ("embed", "attn", "mlp", "lm_head_loss")
SERVE_SCOPES = ("kv_write", "paged_attn", "sample")
#: parts of a layer told apart inside a program without gradients, as declared
_layer_scopes: List[str] = []


def layer_scopes(*names: str) -> None:
    """Declare ``names`` as ``jax.named_scope``s that :func:`classify` gives
    back as a layer's own part: called once, at import, by the module that
    opens them. Of two on one name's stack the one declared first decides."""
    for name in names:
        if name not in _layer_scopes:
            _layer_scopes.append(name)


def _abstract(x):
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        # only a committed array's placement is part of the signature
        sharding = x.sharding if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
    return x


def note_program(name: str, fn, args: tuple, kwargs: Optional[dict] = None,
                 key=None) -> None:
    """Remember that the jitted ``fn`` ran with ``args`` while recording, by
    shapes only (donated buffers are not kept). ``key`` tells a program's
    compiled variants apart; the first call of each is kept."""
    if not enabled() or (name, key) in _programs:
        return
    _programs[(name, key)] = (fn, jax.tree.map(_abstract, args),
                              jax.tree.map(_abstract, kwargs or {}))


def classify(op_name: str) -> str:
    """The scope of one HLO ``op_name`` (JAX's name stack, ``jit(step)/
    transpose(jvp(attn))/dot_general``): ``optimizer``; ``remat`` (the
    forward recomputed under the backward); ``bwd``; ``fwd`` (the
    differentiated forward); the serving scopes ``kv_write``, ``paged_attn``,
    ``sample``; a layer's own part, by the name its module declared
    (:func:`layer_scopes`), and else ``model`` (a model scope in a program
    without gradients);
    ``kv_carry`` (the paged program's layer scan itself: the ops that belong
    to the loop and to no layer scope, which is what it does to the arrays it
    carries, the stacked pool first, and to the stacked leaves it slices a
    layer out of); else ``unscoped``. The name's last component is the
    primitive itself and marks nothing: a ``transpose`` of an array, in a
    serving program or in a differentiated forward, is not a backward pass,
    and a name of one component has no scope."""
    parts = re.split(r"[/()]", op_name.rpartition("/")[0])
    if "optimizer" in parts:
        return "optimizer"
    for s in SERVE_SCOPES:
        if s in parts:
            return s
    if "rematted_computation" in parts:
        return "remat"
    if "transpose" in parts:
        return "bwd"
    if "jvp" in parts:
        return "fwd"
    for s in _layer_scopes:
        if s in parts:
            return s
    if any(s in parts for s in MODEL_SCOPES):
        return "model"
    if "kv_carry" in parts:
        return "kv_carry"
    return "unscoped"


_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_key(line: str) -> str:
    """``<instruction> <result shape>`` of an HLO line, or of a device trace
    event's name (a TPU trace names an event by its HLO line without
    metadata). The shape tells apart the same instruction name in two
    programs."""
    m = _INSTR.match(line)
    if not m:
        return line.strip().lstrip("%")
    return f"{m.group(1)} {m.group(2) or ''}".strip()


def scopes_of_hlo(text: str) -> Dict[str, str]:
    """{op_key: scope} of every instruction in optimized HLO text, and
    {instruction: scope} for a trace that names events by the instruction
    alone (the CPU client's)."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        if " = " not in line:
            continue
        found = _OP_NAME.search(line)
        scope = classify(found.group(1)) if found else "unscoped"
        key = op_key(line)
        for k in (key, key.split(" ")[0]):
            out[k] = scope if out.get(k, scope) == scope else "mixed"
    return out


def _scope_tables() -> Dict[tuple, Dict[str, str]]:
    """{(name, key): {op_key: scope}} of every program noted while recording,
    from its optimized HLO (``lower().compile().as_text()`` on the noted
    shapes: one compile-cache hit a variant, kept)."""
    for ident, (fn, args, kwargs) in list(_programs.items()):
        if ident not in _scope_cache:
            text = fn.lower(*args, **kwargs).compile().as_text()
            _scope_cache[ident] = scopes_of_hlo(text)
    return _scope_cache


def device_scopes() -> Dict[str, str]:
    """{op_key: scope} for the programs noted while recording, from their
    optimized HLO. Call it after the traced window. An instruction two
    programs put under different scopes reads ``mixed``."""
    merged: Dict[str, str] = {}
    for table in _scope_tables().values():
        for k, scope in table.items():
            merged[k] = scope if merged.get(k, scope) == scope else "mixed"
    return merged


def device_programs() -> Dict[str, Set[tuple]]:
    """{op_key: {(name, key), ...}}: the noted programs whose optimized HLO
    holds that instruction and shape, from the same tables as
    :func:`device_scopes`. A key in one program's set alone is device time
    that program spent (a decode round's rows against a mixed step's); one
    that two variants hold (a weight-shaped slice, the head's copies) is in
    both sets, and its time is *shared*, never guessed."""
    held: Dict[str, Set[tuple]] = {}
    for ident, table in _scope_tables().items():
        for k in table:
            held.setdefault(k, set()).add(ident)
    return held

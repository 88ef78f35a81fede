"""A trace whose device events stop before the window does (the profiler lost
three of eight seconds in the check of PR 58, and a roofline share read
119.7%): the readers that pair host-side work with device seconds take the
work of the covered interval alone. Two recorded fixtures stand for the trace.
``tiny_tpu``: three steps are three dispatches, its kernel carries each
family's name in turn, and the cut drops the device events of the last step
while the host's spans stay whole. ``pipelined_tpu`` (recorded with the tracer
that writes the anchor, ``fixtures/record_pipelined.py``): 240 steps of a host
loop one step ahead of the device, every twelfth a long one, with the
recorder's own spans beside it; it is cut at instants that respect no step."""

import json
import os

import pytest
from jax.profiler import ProfileData

from benchmark.harness import trace
from benchmark import run
from benchmark.harness.cell import ROOT, BenchmarkError, Cell, load_spec
from benchmark.readers import (covered, scope_ms, trace_idle, trace_kernel_ms,
                               trace_op_ms, variant_ms)
from deepspeed_tpu.utils import tracing
from tests.benchmark import rules
from tests.benchmark.test_trace import FIXTURE

R = tracing.Record
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
OFFSET = 10 ** 12            # profiler clock minus the recorder's, ns
US = 1000
WHOLE = trace._device_op_lines
#: every share of a roofline that moves ``itl_p50_ms``, from the spec and the
#: metric files: kernel (the reader's ``KERNEL``), reader, its arguments, the
#: entry's first cell. An entry that arrives is one more case of both tests
#: below; ``kernel.flash_roofline_share`` counts by ``steps`` and is held by
#: ``test_program_readers.py`` and ``covered.per``'s tests here
SHARES = [pytest.param(kernel, reader, args, cell, id=case)
          for case, kernel, reader, args, cell
          in rules.roofline_cases(load_spec(staged=True), ROOT)]


def bursts(events):
    """The fixture's device events by step: a gap of over 0.1 ms parts them."""
    out = []
    for e in sorted(events, key=lambda e: e[1]):
        if not out or e[1] - (out[-1][-1][1] + out[-1][-1][2]) > 100 * US:
            out.append([])
        out[-1].append(e)
    return out


def summary(monkeypatch, kernel, cut):
    """The fixture reduced with its kernel named ``kernel`` and, with ``cut``,
    without the device events of its last step; the recorder filled with one
    dispatch and one launch a step, on a clock ``OFFSET`` behind."""
    def lines(profile):
        out = {}
        for plane, events in WHOLE(profile).items():
            steps = bursts(events)
            kept = [e for step in (steps[:-1] if cut else steps) for e in step]
            out[plane] = [(n.replace("%step.1 =", f"%{kernel}.3 ="), s, d)
                          for n, s, d in kept]
        return out

    steps = bursts(next(iter(WHOLE(ProfileData.from_file(FIXTURE)).values())))
    assert len(steps) == 3
    spans, edges = [], []
    for k, step in enumerate(steps):
        lo = step[0][1] - 50 * US - OFFSET
        hi = step[-1][1] + step[-1][2] + 50 * US - OFFSET
        edges += [lo, hi]
        attrs = {"program": "ragged", "rows": 13, "padded_rows": 32,
                 "decode_rows": 13, "ctx_tokens": 50_000,
                 "ctx_tokens_by_row": 50_000, "sel_blocks": 10_000}
        spans.append(R(10 * k + 1, "engine.dispatch", int(lo), int(hi), 0, attrs))
        spans.append(R(10 * k + 2, "engine.enqueue", int(lo), int(lo + 60 * US),
                       10 * k + 1, {}))
    monkeypatch.setattr(tracing, "_buf", spans)
    monkeypatch.setattr(trace, "_device_op_lines", lines)
    opened, closed = int(min(edges) - 100 * US), int(max(edges) + 100 * US)
    out = trace.reduce_xplane(FIXTURE, (), window_s=(closed - opened) / 1e9)
    assert out["clock_offset_ns"] is None       # the fixture predates the anchor
    out["clock_offset_ns"], out["window_ns"] = OFFSET, (opened, closed)
    return out, spans


def ctx(trace_summary, cell=None, counters=None):
    return {"trace": trace_summary, "counters": counters or {"dispatches": 3},
            "spans": {}, "peak": PEAK, "cell": cell}


def test_reduce_xplane_returns_the_covered_interval(monkeypatch):
    whole, _ = summary(monkeypatch, "mla_decode", cut=False)
    cut, spans = summary(monkeypatch, "mla_decode", cut=True)
    assert whole["covered_ns"][0] == cut["covered_ns"][0]
    def length(s):
        return (s["covered_ns"][1] - s["covered_ns"][0]) / 1e9

    assert length(whole) == pytest.approx(1.6914e-3, rel=1e-3)
    assert length(cut) == pytest.approx(0.98e-3, rel=1e-2)
    assert cut["window_s"] == whole["window_s"] > length(whole)
    # every dispatch touches a whole trace; the cut one holds two of three
    c = ctx(cut)
    assert len(covered.inside(ctx(whole), spans)) == 6
    assert [s.id for s in covered.inside(c, spans)] == [1, 2, 11, 12]
    assert covered.per(c, "dispatches") == 2.0
    assert covered.per(ctx(whole), "dispatches") == 3.0
    assert "2 of 3 engine.dispatch spans inside" in covered.line(c)
    # without an anchor nothing is known of the clocks: the window whole
    cut["clock_offset_ns"] = None
    assert covered.inside(c, spans) == spans and covered.lost_s(c) == 0.0
    assert "no clock anchor" in covered.line(c)


def test_the_cases_are_the_specs_serving_shares():
    """Read from the spec, not listed here; the five of PR 59 are among them,
    each with its reader, so a filter that finds nothing cannot pass."""
    assert {"mla_decode-mla_roofline-gigachat3.1-702b-a36b.serve-longdoc",
            "mla_decode-mla_roofline_layers-longcat-flash-chat.serve-longout",
            "paged_decode-paged_roofline-gpt2-medium.serve-chat",
            "paged_decode-sparse_paged_roofline-minicpm-sala.serve-doc16k",
            "linear_decode-linear_roofline-minicpm-sala.serve-doc16k"} \
        <= {p.id for p in SHARES}
    layers = next(p for p in SHARES if "mla_roofline_layers" in p.id)
    assert layers.values[2] == {"layers": 8}      # the metric file's ``args``


def same_share_of_a_cut_trace(monkeypatch, kernel, reader, args, cell):
    """``cell`` a ``Cell``. The tiny fixture whole and without the device
    events of its last step read the same share, to 2%."""
    whole = reader.read(ctx(summary(monkeypatch, kernel, cut=False)[0], cell),
                        **args)
    cut_summary, _ = summary(monkeypatch, kernel, cut=True)
    cut = reader.read(ctx(cut_summary, cell), **args)
    assert whole > 0 and cut == pytest.approx(whole, rel=0.02)
    # the parent's reading: three dispatches' work over two dispatches' seconds
    cut_summary["clock_offset_ns"] = None
    assert reader.read(ctx(cut_summary, cell), **args) == pytest.approx(
        1.5 * whole, rel=0.02)


@pytest.mark.parametrize("kernel,reader,args,cell", SHARES)
def test_a_cut_trace_reads_the_same_roofline_share(monkeypatch, kernel, reader,
                                                   args, cell):
    same_share_of_a_cut_trace(monkeypatch, kernel, reader, args, Cell(cell))


def test_a_cut_trace_reads_the_same_time_a_dispatch(monkeypatch):
    scopes = {"fusion bf16[]": "model"}
    monkeypatch.setattr(tracing, "device_scopes", lambda: scopes)
    monkeypatch.setattr(tracing, "op_key", lambda name: "fusion bf16[]"
                        if name.startswith("%fusion") else name, raising=False)
    whole, _ = summary(monkeypatch, "mla_decode", cut=False)
    cut, _ = summary(monkeypatch, "mla_decode", cut=True)
    for read, args in (
            (trace_kernel_ms.read, {"prefix": "mla_decode", "per": "dispatches"}),
            (trace_op_ms.read, {"kind": "kernel", "per": "dispatches"}),
            (scope_ms.read, {"scope": "model", "per": "dispatches"})):
        a, b = read(ctx(whole), **args), read(ctx(cut), **args)
        assert a > 0 and b == pytest.approx(a, rel=0.02), (read.__module__, a, b)
    assert trace_kernel_ms.read(ctx(whole), prefix="mla_decode",
                                per="dispatches") == pytest.approx(
        1e3 * 5.496e-06 / 3, rel=1e-3)


def test_a_cut_trace_counts_the_variants_runs_inside_it(monkeypatch):
    cell = Cell("gigachat3.1-702b-a36b.serve-longdoc")
    progs = {}
    monkeypatch.setattr(tracing, "device_programs", lambda: progs, raising=False)
    monkeypatch.setattr(tracing, "device_scopes", lambda: {})
    for cut, runs in ((False, 3), (True, 2)):
        s, _ = summary(monkeypatch, "mla_decode", cut=cut)
        progs.clear()
        progs.update({tracing.op_key(n): {(variant_ms.RAGGED, (32,))}
                      for n in s["ops"]})
        monkeypatch.setattr(variant_ms, "_last", (None, None))
        assert variant_ms.account(ctx(s, cell))["n"] == {"round": runs,
                                                         "mixed": 0}


def test_idle_share_does_not_count_the_missing_third(monkeypatch):
    whole, _ = summary(monkeypatch, "mla_decode", cut=False)
    cut, spans = summary(monkeypatch, "mla_decode", cut=True)
    assert covered.lost_s(ctx(whole)) == 0.0
    assert covered.accounted_s(ctx(whole)) == whole["window_s"]
    # the third step's launch began after the last device event that is left
    third = spans[-1]
    lost = (third.end - (cut["covered_ns"][1] - OFFSET)) / 1e9
    assert lost > 0.7e-3
    assert covered.lost_s(ctx(cut)) == pytest.approx(lost)
    accounted = covered.accounted_s(ctx(cut))
    assert accounted == pytest.approx(cut["window_s"] - lost)
    idle = trace_idle.read(ctx(cut))
    assert idle == pytest.approx(100 * (1 - cut["busy_first_s"] / accounted))
    # two thirds of the busy time over the whole window is the parent's reading
    assert 100 - idle > 1.5 * 100 * cut["busy_first_s"] / cut["window_s"]
    # a launch-free edge is idle time, not a loss: no launch after the cut
    monkeypatch.setattr(tracing, "_buf", spans[:4])
    assert covered.lost_s(ctx(cut)) == 0.0


def test_clock_offset_needs_the_anchor_and_its_instant():
    found = [("sched.step", 5, 9), (trace.ANCHOR, 1_000, 1_001)]
    assert trace.clock_offset(found, 400) == 600
    assert trace.clock_offset(found[:1], 400) is None
    assert trace.clock_offset(found, None) is None
    # the tracer reads time.monotonic_ns beside its anchor: the recorder's clock
    assert tracing.clock_ns is trace.time.monotonic_ns


def test_a_counter_without_a_span_cannot_divide_device_time(monkeypatch):
    """``per`` scales a counter by its spans inside the covered interval; a
    counter it knows no span for would come back whole, and a cut trace would
    again read a fraction of the time a step takes."""
    whole, _ = summary(monkeypatch, "mla_decode", cut=False)
    c = ctx(whole, counters={"dispatches": 3, "decode_steps": 2})
    with pytest.raises(BenchmarkError, match="decode_steps"):
        covered.per(c, "decode_steps")
    with pytest.raises(BenchmarkError, match="decode_steps"):
        trace_kernel_ms.read(c, prefix="mla_decode", per="decode_steps")
    # every metric file names a counter that has one
    rules.counters_have_spans(ROOT)


def test_the_result_line_says_what_the_trace_covers(monkeypatch):
    cut, _ = summary(monkeypatch, "mla_decode", cut=True)
    cut["accounted_s"] = covered.accounted_s(ctx(cut))
    device = run.device_account(cut)
    assert device["window_s"] == cut["accounted_s"] < device["traced_s"]
    assert device["traced_s"] == cut["window_s"]
    assert device["covered_s"] == pytest.approx(0.98e-3, rel=1e-2)
    assert device["anchor"] is True and device["busy_s"] == cut["busy_s"]
    cut["clock_offset_ns"] = None
    assert run.device_account(cut)["anchor"] is False
    json.dumps(device)


# -- the pipelined fixture: spans that lead their device work by one step ----

PIPELINED = os.path.join(ROOT, "fixtures", "pipelined_tpu.xplane.pb")
with open(os.path.join(ROOT, "fixtures", "pipelined_tpu.spans.json")) as _f:
    META = json.load(_f)
RECORDED = [R(*row[:5], row[5]) for row in META["spans"]]
DISPATCHES = [r for r in RECORDED if r.name == "engine.dispatch"]
STEPS = len(META["kinds"])


#: what the device plane's clock leads the host's by in the recording, ns (the
#: host saw a step done 2.0 ms after its last operation's recorded end)
LEAD = 2_000_000


def pipelined(monkeypatch, kernel="mla_decode", lo=0.0, hi=1.0, lead=LEAD):
    """The fixture reduced as ``Tracer.summary`` reduces it, the anchor read
    out of the file; its kernel named ``kernel``; of its device events those
    that lie wholly inside the part [lo, hi] of the covered interval. Returns
    the summary and the steps whose kernel is among them: the truth a reader
    is held to. ``lead`` 0 places the device's events ``LEAD`` later, where
    they ran: a step after their spans."""
    whole = WHOLE(ProfileData.from_file(PIPELINED))
    (plane, events), = whole.items()
    first = min(e[1] for e in events)
    last = max(e[1] + e[2] for e in events)
    a, b = first + lo * (last - first), first + hi * (last - first)
    kept = [(n.replace("%round_kernel.1 =", f"%{kernel}.3 ="), s, d)
            for n, s, d in events if s >= a and s + d <= b]
    kernels = sorted(s for n, s, _ in events if n.startswith("%round_kernel"))
    assert len(kernels) == STEPS
    steps = [k for k, s in enumerate(kernels) if a <= s <= b]
    monkeypatch.setattr(trace, "_device_op_lines", lambda profile: {plane: kept})
    monkeypatch.setattr(tracing, "_buf", list(RECORDED))
    opened, closed = META["window_ns"]
    out = trace.reduce_xplane(PIPELINED, ("sched.step",),
                              window_s=(closed - opened) / 1e9, anchor_ns=opened)
    out["window_ns"] = (opened, closed)
    out["clock_offset_ns"] -= LEAD - lead
    return out, steps


def truth(monkeypatch, cut, steps, cell=None):
    """The context a reader would have if the host's side were cut exactly as
    the device's: the dispatches of ``steps`` and no others, nothing to place."""
    monkeypatch.setattr(tracing, "_buf", [DISPATCHES[k] for k in steps])
    return ctx({**cut, "clock_offset_ns": None}, cell,
               {"dispatches": len(steps)})


#: cuts that respect no step: the end lost in the middle of a round and of a
#: long step (step 131 runs from 0.5293 to 0.5499 of the interval, step 167
#: from 0.6793 to 0.6999), the start lost, both
CUTS = {"end": (0.0, 0.62), "end-in-a-long-step": (0.0, 0.54),
        "start": (0.27, 1.0), "both": (0.21, 0.69)}


def test_the_anchor_is_read_out_of_the_recorded_trace(monkeypatch):
    whole, steps = pipelined(monkeypatch)
    assert META["device"] == "TPU v5 lite" and steps == list(range(STEPS))
    assert whole["clock_offset_ns"] is not None
    c = ctx(whole, counters={"dispatches": STEPS})
    assert len(covered.inside(c, DISPATCHES)) == STEPS
    assert covered.per(c, "dispatches") == STEPS
    assert f"{STEPS} of {STEPS} engine.dispatch spans inside" in covered.line(c)
    # the device plane's clock runs ahead of the host's in this recording: the
    # first operation starts 0.84 ms before the launch that caused it, and the
    # last ends 2 ms before the host saw it done. Less than a step, and so
    # inside the edge error below; a whole window loses 2 ms to it
    lo, hi = covered.interval(whole)
    first = min(r.start for r in RECORDED if r.name == "engine.enqueue")
    assert 0.5e6 < first - lo < 1.5e6
    assert 1.5e6 < META["window_ns"][1] - hi < 2.5e6
    assert covered.lost_s(c) == 0.0
    assert trace_idle.read(c) == pytest.approx(0.336, abs=0.01)


def test_the_recorded_spans_lead_their_device_work_by_a_step(monkeypatch):
    """The loop launches step k + 1 before it waits for step k, so a step's
    device work lies a step after its span: on the host's own clock, every
    wait for step k ends after the dispatch of step k + 1 has. On the
    recorded clocks the device plane leads by about ``LEAD``, more than a
    1.43 ms round, which hides the lag; ``lead=0`` puts it back."""
    fetches = [r for r in RECORDED if r.name == "engine.fetch"]
    assert len(fetches) == STEPS
    assert all(fetches[k].end > DISPATCHES[k + 1].end for k in range(STEPS - 1))
    whole, _ = pipelined(monkeypatch)
    off = whole["clock_offset_ns"]
    (events,) = trace._device_op_lines(None).values()
    done = sorted(s + d - off for n, s, d in events if n.startswith("%mla_decode"))
    # the host saw step k done LEAD after its kernel's recorded end
    seen = sorted(fetches[k].end - done[k] for k in range(STEPS))
    assert seen[0] > 1.8e6 and seen[STEPS // 2] == pytest.approx(LEAD, rel=0.1)
    # placed where it ran, a kernel follows its own span and the next one
    behind = [sum(d.end < done[k] + LEAD for d in DISPATCHES) - 1 - k
              for k in range(STEPS - 1)]
    assert set(behind) == {1}


def share_of_what_a_cut_leaves(monkeypatch, kernel, reader, args, cell, cut,
                               lead):
    """``cell`` a ``Cell``. The rule is *a span that touches the covered
    interval counts*. Under a loop one step ahead it admits a dispatch too
    many or too few at each cut edge (the span of the step after the last
    whose device work is left; the first step's, which ended before its work
    began): at most 2 of the 116-173 dispatches left here, and the reading is
    held to 2% of the truth, the clocks as recorded or the device's work a
    step behind its span. (Three and a half steps behind, the count is off by
    up to 4.) Taking the window whole, as the parent did, reads the window
    over the part: 1.6 times the share at the first cut."""
    summary_, steps = pipelined(monkeypatch, kernel, *CUTS[cut], lead=lead)
    assert 90 < len(steps) < 0.8 * STEPS
    got = reader.read(ctx(summary_, cell, {"dispatches": STEPS}), **args)
    inside = len(covered.inside(ctx(summary_), DISPATCHES))
    assert abs(inside - len(steps)) <= 2
    parent = reader.read(ctx({**summary_, "clock_offset_ns": None}, cell), **args)
    want = reader.read(truth(monkeypatch, summary_, steps, cell), **args)
    assert want > 0 and got == pytest.approx(want, rel=0.02)
    assert parent > 1.25 * want


@pytest.mark.parametrize("lead", [LEAD, 0], ids=["as-recorded", "a-step-behind"])
@pytest.mark.parametrize("cut", CUTS)
@pytest.mark.parametrize("kernel,reader,args,cell", SHARES)
def test_a_cut_at_any_instant_reads_the_share_of_what_is_left(
        monkeypatch, kernel, reader, args, cell, cut, lead):
    share_of_what_a_cut_leaves(monkeypatch, kernel, reader, args, Cell(cell),
                               cut, lead)


@pytest.mark.parametrize("lead", [LEAD, 0], ids=["as-recorded", "a-step-behind"])
@pytest.mark.parametrize("cut", CUTS)
def test_a_cut_at_any_instant_reads_the_time_a_dispatch(monkeypatch, cut, lead):
    """Device milliseconds a dispatch: the count is right to the two
    dispatches of the edges, and a cut inside a long step leaves part of that
    step's seconds under a whole count. Both stay under 2% here (read: 1.5%
    at the worst, the cut inside the 11.4 ms step); what the rule admits is
    the longest step's share of what is left, a mixed step of 27-76 ms in
    five seconds of a served cell."""
    summary_, steps = pipelined(monkeypatch, "mla_decode", *CUTS[cut], lead=lead)
    edge = 0.02
    for read, args in (
            (trace_kernel_ms.read, {"prefix": "mla_decode", "per": "dispatches"}),
            (trace_op_ms.read, {"kind": "fusion", "per": "dispatches"})):
        got = read(ctx(summary_, counters={"dispatches": STEPS}), **args)
        want = read(truth(monkeypatch, summary_, steps), **args)
        monkeypatch.setattr(tracing, "_buf", list(RECORDED))
        assert want > 0 and got == pytest.approx(want, rel=edge)
        whole_count = read(ctx({**summary_, "clock_offset_ns": None},
                               counters={"dispatches": STEPS}), **args)
        assert whole_count < 0.8 * want


@pytest.mark.parametrize("cut", CUTS)
def test_a_cut_at_any_instant_counts_each_variants_runs(monkeypatch, cut):
    """``variant_ms`` divides a variant's seconds by its own runs: the edge
    costs a long step one run in the dozen left (8%; one in the ~90 mixed
    steps of five served seconds)."""
    cell = Cell("gigachat3.1-702b-a36b.serve-longdoc")
    summary_, steps = pipelined(monkeypatch, "mla_decode", *CUTS[cut])
    monkeypatch.setattr(tracing, "device_programs", lambda: {
        tracing.op_key(n): {(variant_ms.RAGGED, (32,)), (variant_ms.RAGGED, (512,))}
        for n in summary_["ops"]}, raising=False)
    monkeypatch.setattr(tracing, "device_scopes", lambda: {})
    monkeypatch.setattr(variant_ms, "_last", (None, None))
    n = variant_ms.account(ctx(summary_, cell))["n"]
    mixed = sum(META["kinds"][k] == "mixed" for k in steps)
    assert abs(n["mixed"] - mixed) <= 1 and mixed >= 8
    assert abs(n["round"] - (len(steps) - mixed)) <= 2


@pytest.mark.parametrize("cut", CUTS)
def test_a_cut_at_any_instant_leaves_the_idle_share(monkeypatch, cut):
    """The device is busy 99.7% of this loop. With a third and more of its
    events gone the share of the window reads 28-52% idle; of what the trace
    accounts for, under 5%. What is left over the truth is the edge: the lost
    part is counted to the end of the last *launch*, and the device worked a
    step longer (here the loop ends on a long step, 11.4 ms of 0.3 s; in a
    served cell launches run up to the window's close, and the edge is a
    round of eight seconds)."""
    summary_, _ = pipelined(monkeypatch, "mla_decode", *CUTS[cut])
    c = ctx(summary_, counters={"dispatches": STEPS})
    assert 100 * (1 - summary_["busy_first_s"] / summary_["window_s"]) > 25
    assert covered.lost_s(c) > 0.25 * summary_["window_s"]
    assert 0 <= trace_idle.read(c) < 5
    assert "of launches without device events" in covered.line(c)

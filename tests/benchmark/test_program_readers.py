"""The readers of the program's own spans and scopes, each against a
hand-built ``ctx`` and a recorder filled by hand: no profiler, no device."""

import sys

import pytest

from benchmark.harness.cell import Cell, load_spec
from benchmark.kernels import paged_attention
from benchmark.readers import (flash_roofline, paged_roofline, program_span,
                               program_spans, scope_ms, span_attr_ratio,
                               span_count)
from deepspeed_tpu.utils import tracing

R = tracing.Record
MS = 1_000_000
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
KERNEL = ('%paged_decode.3 = bf16[64,16,1,64]{3,2,1,0} custom-call(%a), '
          'custom_call_target="tpu_custom_call"')


@pytest.fixture
def recorded(monkeypatch):
    """Fill the recorder with two scheduler steps and their dispatches."""
    spans = [
        R(1, "sched.step", 0, 100 * MS, 0, {}),
        R(2, "sched.dispatch", 10 * MS, 95 * MS, 1, {"kind": "decode"}),
        R(3, "engine.dispatch", 11 * MS, 94 * MS, 2,
          {"rows": 8, "padded_rows": 64, "ctx_tokens": 2000,
           "ctx_tokens_by_row": 2000}),
        R(4, "engine.build", 11 * MS, 13 * MS, 3, {}),
        R(5, "engine.fetch", 14 * MS, 94 * MS, 3, {}),
        R(6, "sched.step", 100 * MS, 300 * MS, 0, {}),
        R(7, "engine.dispatch", 110 * MS, 290 * MS, 6,
          {"rows": 136, "padded_rows": 256, "ctx_tokens": 3000,
           "ctx_tokens_by_row": 40000}),
        R(8, "engine.build", 110 * MS, 116 * MS, 7, {}),
        R(9, "engine.fetch", 120 * MS, 290 * MS, 7, {}),
        R(10, "req.queue", 5 * MS, 95 * MS, 1, {"uid": 1, "prompt_tokens": 40}),
    ]
    monkeypatch.setattr(tracing, "_buf", spans)
    return spans


def ctx(ops=None, counters=None, peak=PEAK, cell=None):
    trace = None if ops is None else {"ops": ops, "busy_first_s": 1.0,
                                      "window_s": 2.0}
    return {"trace": trace, "counters": counters or {}, "spans": {},
            "peak": peak, "cell": cell}


def test_no_recorder_or_nothing_recorded_reads_nothing(monkeypatch):
    monkeypatch.setattr(tracing, "_buf", [])
    assert program_spans.spans() is None
    assert program_span.read(ctx(), span="sched.step") is None
    assert span_attr_ratio.read(ctx(), span="engine.dispatch", num="rows",
                                den="padded_rows") is None
    assert span_count.read(ctx(), span="compile") is None
    assert paged_roofline.read(ctx(ops={KERNEL: (0.1, 2)})) is None
    # the parent of the PR that added the recorder has no such module
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.utils.tracing", None)
    monkeypatch.delattr("deepspeed_tpu.utils.tracing", raising=False)
    assert program_spans.spans("sched.step") is None
    assert scope_ms.read(ctx(ops={KERNEL: (0.1, 2)}, counters={"steps": 2}),
                         scope="fwd", per="steps") is None


def test_program_span_median_and_minus(recorded):
    assert program_span.read(ctx(), span="engine.build") == 4.0
    assert program_span.read(ctx(), span="sched.step") == 150.0
    # 100 - 80 and 200 - 170: the host's share of each step
    assert program_span.read(ctx(), span="sched.step",
                             minus="engine.fetch") == 25.0
    assert program_span.read(ctx(), span="req.queue") == 90.0
    assert program_span.read(ctx(), span="no.such.span") is None


def test_span_attr_ratio_sums_before_dividing(recorded):
    assert span_attr_ratio.read(ctx(), span="engine.dispatch", num="rows",
                                den="padded_rows") == 100.0 * 144 / 320
    assert span_attr_ratio.read(ctx(), span="engine.build", num="rows",
                                den="padded_rows") is None


def test_span_count_is_zero_when_other_spans_were_recorded(recorded):
    assert span_count.read(ctx(), span="compile") == 0.0
    recorded.append(R(11, "compile", 0, MS, 6, {"program": "jit(f)",
                                                "cached": True}))
    assert span_count.read(ctx(), span="compile") == 1.0
    assert isinstance(span_count.read(ctx(), span="sched.step"), float)


def test_scope_ms_sums_device_time_by_scope(monkeypatch, capsys):
    scopes = {"fusion.1 f32[8]": "fwd", "fusion.2 f32[8]": "bwd",
              "flash_bwd.4 bf16[8,16,1024,64]": "bwd",
              "fusion.3 f32[8]": "optimizer", "while.5": "fwd"}
    monkeypatch.setattr(tracing, "device_scopes", lambda: scopes)
    ops = {"%fusion.1 = f32[8]{0} fusion(%p), kind=kLoop": (0.2, 10),
           "%fusion.2 = f32[8]{0} fusion(%p), kind=kLoop": (0.3, 10),
           '%flash_bwd.4 = (bf16[8,16,1024,64]{3,2,1,0}, bf16[8]{0}) custom-call(%q), '
           'custom_call_target="tpu_custom_call"': (0.1, 10),
           "%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop": (0.05, 10),
           "%while.5 = (f32[8]{0}) while(%t), body=%b": (0.9, 10),   # a container
           "%copy.9 = f32[8]{0} copy(%x)": (0.05, 10)}
    c = ctx(ops=ops, counters={"steps": 10})
    assert scope_ms.read(c, scope="fwd", per="steps") == pytest.approx(20.0)
    assert scope_ms.read(c, scope="bwd", per="steps") == pytest.approx(40.0)
    assert scope_ms.read(c, scope="optimizer", per="steps") == pytest.approx(5.0)
    assert scope_ms.read(c, scope="remat", per="steps") == 0.0
    out = capsys.readouterr().out
    assert out.count("share of device time by scope") == 1    # one table a trace
    assert "unscoped 7.1%" in out and "copy.9" in out
    assert scope_ms.read(c, scope="fwd", per="dispatches") is None
    assert scope_ms.read(ctx(counters={"steps": 3}), scope="fwd",
                         per="steps") is None


def test_scope_ms_without_noted_programs_reads_nothing(monkeypatch):
    monkeypatch.setattr(tracing, "device_scopes", lambda: {})
    c = ctx(ops={"%fusion.1 = f32[8]{0} fusion(%p)": (0.2, 10)},
            counters={"steps": 10})
    assert scope_ms.read(c, scope="fwd", per="steps") is None


def test_paged_work_counts_required_bytes_and_flops():
    flops, nbytes = paged_attention.dispatches(
        ctx_tokens=1000, ctx_tokens_by_row=5000, rows=10, layers=24, heads=16,
        kv_heads=16, head_dim=64)
    assert nbytes == 24 * (2 * 1000 * 16 * 64 * 2 + 2 * 10 * 16 * 64 * 2)
    assert flops == 24 * 4 * 5000 * 16 * 64
    # grouped-query attention reads fewer key/value heads, computes the same
    assert paged_attention.dispatches(1000, 5000, 10, 24, 16, 4, 64)[1] < nbytes


def test_paged_roofline_share(recorded, capsys):
    cell = Cell("gpt2-medium.serve-chat", load_spec())
    ops = {KERNEL: (0.050, 48), "%fusion.7 = bf16[64,1024]{1,0} fusion(%p)": (1.0, 48),
           '%other.1 = bf16[8]{0} custom-call(%a), custom_call_target="tpu_custom_call"':
               (0.5, 2)}
    share = paged_roofline.read(ctx(ops=ops, cell=cell))
    flops, nbytes = paged_attention.dispatches(5000, 42000, 144, 24, 16, 16, 64)
    assert share == pytest.approx(100.0 * (nbytes / 819e9) / 0.050)
    assert 0 < share < 100 and "bound by bytes" in capsys.readouterr().out
    # no chip's peak (the CPU rehearsal), no kernel of that name: nothing
    assert paged_roofline.read(ctx(ops=ops, cell=cell, peak=None)) is None
    assert paged_roofline.read(ctx(ops={"%fusion.7 = f32[8]{0} fusion(%p)": (1.0, 1)},
                                   cell=cell)) is None


FUSED_CE = ('%fused_ce_fwd.1 = (f32[8192]{0}, bf16[8192,50304]{1,0}) custom-call(%h), '
            'custom_call_target="tpu_custom_call"')


def flash(name, shape="bf16[8,1024,1024]"):
    return (f'%{name} = {shape}{{2,1,0}} custom-call(%q), '
            'custom_call_target="tpu_custom_call"')


def test_flash_roofline_ignores_a_fused_ce_custom_call(capsys):
    """The train step holds the head's kernels beside the attention's (since
    PR 56); the share is of the kernels named ``flash_*`` alone."""
    counters = {"steps": 10, "global_batch": 8, "chips": 1, "seq_len": 1024,
                "n_heads": 16, "head_dim": 64, "n_layers": 24}
    ops = {flash("flash_fwd.3"): (0.10, 240), flash("flash_bwd.5"): (0.16, 240)}
    alone = flash_roofline.read(ctx(ops=ops, counters=counters))
    ops[FUSED_CE] = (0.134, 10)
    ops['%fused_ce_bwd.1 = bf16[8192,1024]{1,0} custom-call(%g), '
        'custom_call_target="tpu_custom_call"'] = (0.09, 10)
    assert flash_roofline.read(ctx(ops=ops, counters=counters)) == alone
    assert 0 < alone < 100 and "kernels 26.000 ms a step" in capsys.readouterr().out
    # a step with no flash kernel (the CPU's XLA attention) reads nothing
    assert flash_roofline.read(ctx(ops={FUSED_CE: (0.1, 10)},
                                   counters=counters)) is None


def test_paged_decode_ms_is_the_paged_decode_kernels_alone():
    """``kernel.paged_decode_ms`` counted every custom call, ``kv_write``
    among them since PR 35; it reads the family ``paged_decode*`` by name."""
    read, args = Cell("gpt2-medium.serve-chat").reader("kernel.paged_decode_ms")
    ops = {KERNEL: (0.050, 48),
           '%kv_write.13 = bf16[8]{0} custom-call(%a), '
           'custom_call_target="tpu_custom_call"': (0.5, 48),
           "%fusion.7 = bf16[64,1024]{1,0} fusion(%p)": (1.0, 48)}
    assert read(ctx(ops=ops, counters={"dispatches": 2}), **args) == \
        pytest.approx(25.0)


NAMED = ("sched.host_ms", "engine.row_fill", "engine.kv_write_ms",
         "engine.kv_carry_ms", "kernel.paged_roofline_share.chat",
         "model.fwd_ms", "model.bwd_ms", "model.remat_ms", "engine.optimizer_ms",
         "engine.compiles.train", "engine.compiles.serve")


@pytest.mark.parametrize(
    "m,cell", [(m, cell) for m in load_spec()["per_layer"]
               if m["name"] in NAMED for cell in m["workloads"]],
    ids=lambda v: v["name"] if isinstance(v, dict) else v)
def test_new_metrics_name_a_reader_whose_arguments_fit(m, cell):
    import inspect

    read, args = Cell(cell, load_spec()).reader(m["name"])
    inspect.signature(read).bind({}, **args)
    assert m["layer"] in ("Serving scheduler", "Serving engine", "Kernels",
                          "Training engine", "Model")

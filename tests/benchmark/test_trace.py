"""The reduction from a profiler trace to metrics, against a small trace
recorded on one TPU v5e chip (``benchmark/fixtures/tiny_tpu.xplane.pb``: three
steps of a jitted flash-attention kernel plus a matmul, under the benchmark's
``train_batch`` and ``sync`` spans; recorded with ``BENCH_KEEP_TRACE``). The
numbers asserted are what that recording holds, not a claim about speed."""

import os

import pytest

from benchmark.harness import trace
from benchmark.harness.cell import ROOT

FIXTURE = os.path.join(ROOT, "fixtures", "tiny_tpu.xplane.pb")
HLO = ('%checkpoint.24 = (f32[8,16,1024,64]{3,2,1,0:T(8,128)}, bf16[8,16,1024,64]'
       '{3,2,1,0:T(8,128)(2,1)S(1)}) custom-call(bf16[8,16,1024,64]{3,2,1,0} '
       '%bitcast.3758), custom_call_target="tpu_custom_call", operand_layout={}')


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_xplane(FIXTURE, ("train_batch", "sync"))


def test_busy_time_is_the_union_of_device_operations(summary):
    assert summary["devices"] == 1
    assert summary["busy_s"] == pytest.approx(9.94e-06, rel=1e-3)
    assert summary["busy_first_s"] == summary["busy_s"]
    assert summary["busy_s"] < summary["window_s"] < 0.01
    assert trace.reduce_xplane(FIXTURE, (), window_s=2.0)["window_s"] == 2.0


def test_operation_table_finds_the_kernel(summary):
    kinds = {}
    for name, (sec, count) in summary["ops"].items():
        k = trace.op_kind(name)
        kinds[k] = (kinds.get(k, (0, 0))[0] + sec, kinds.get(k, (0, 0))[1] + count)
    assert kinds["kernel"] == (pytest.approx(5.496e-06, rel=1e-3), 3)
    assert set(kinds) == {"kernel", "copy", "fusion", "copy-start", "copy-done"}
    top = trace.breakdown(summary)
    assert top["device_ops"][0][0] == "step.1 kernel bf16[1,2,256,64]"
    assert 1 <= len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10
    assert all(isinstance(s, float) for _, s in top["device_ops"])


def test_idle_gaps_are_attributed_to_the_host_span_open_at_the_time(summary):
    idle = summary["idle_by_span"]
    assert set(idle) == {"none", "train_batch", "sync"}
    assert idle["train_batch"] == pytest.approx(7.08e-04, rel=1e-2)
    total = summary["window_s"] - summary["busy_s"]
    assert sum(idle.values()) == pytest.approx(total, rel=1e-6)


@pytest.mark.parametrize("name,instr,opcode", [
    (HLO, "checkpoint.24", "kernel"),
    ('%custom-call.11 = bf16[1024,1024]{1,0} custom-call(bf16[256,1024]{1,0} '
     '%slice-done), custom_call_target="ConcatBitcast"', "custom-call.11",
     "custom-call"),
    ("%fusion.18 = bf16[50304,1024]{1,0:T(8,128)(2,1)} fusion(f32[8,1024]{1,0} "
     "%get), kind=kLoop", "fusion.18", "fusion"),
    ("%all-gather-start.3 = (bf16[8]{0}, bf16[32]{0}) all-gather-start(bf16[8]{0}"
     " %p), dimensions={0}", "all-gather-start.3", "all-gather-start"),
    ("%while.7 = (s32[]{:T(128)}, bf16[2,4]{1,0}) while((s32[], bf16[2,4]) %t), "
     "condition=%c, body=%b", "while.7", "while"),
    ("dot_general.1", "dot_general.1", "dot_general"),      # the CPU client
    ("all-gather.8", "all-gather.8", "all-gather"),
])
def test_parse_op(name, instr, opcode):
    assert trace.parse_op(name) == (instr, opcode)
    assert trace.is_collective(name) == opcode.startswith("all-gather")


def test_labels_and_containers():
    assert trace.label(HLO) == "checkpoint.24 kernel f32[8,16,1024,64]"
    assert trace.label("dot_general.1") == "dot_general.1 dot_general"
    table = {"ops": {"%while.7 = (s32[]) while((s32[]) %t), body=%b": (5.0, 1),
                     "%fusion.1 = f32[2]{0} fusion(f32[2]{0} %x)": (1.0, 2)},
             "idle_by_span": {}}
    assert trace.breakdown(table)["device_ops"] == [["fusion.1 fusion f32[2]", 1.0]]


def test_union_merges_overlaps():
    assert trace._union([(5, 7), (0, 2), (1, 3), (6, 6)]) == [[0, 3], [5, 7]]


def test_tracer_off_is_free():
    t = trace.Tracer(False)
    t.start()
    with t.span("x"):
        pass
    t.stop()
    assert t.summary() is None and t.spans == {}

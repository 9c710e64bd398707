"""The reduction from trace to numbers: on synthetic events, and on a small
trace recorded on the TPU v5e (benchmark/tools/record_tiny_trace.py: five
executions of one tiny jitted program, each inside a ``bench.step`` span)."""
import os

import pytest

from benchmark import trace as T

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny.xplane.pb")


def test_union_busy_and_self_times_on_synthetic_events():
    evs = [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("c", 3.0, 1.0)]
    assert T.union_s(evs) == pytest.approx(2.5)
    assert T.union_s(evs, lo=0.25, hi=3.5) == pytest.approx(1.75)
    tr = T.Trace({0: evs, 1: [("a", 0.0, 0.5)]}, {}, [], 0.0, 4.0)
    assert T.busy_s(tr) == pytest.approx((2.5 + 0.5) / 2)
    nested = [("%while.1 = (s32[]) while(x)", 0.0, 10.0),
              ("%fusion.1 = bf16[4]{0} fusion(y)", 1.0, 3.0),
              ("%fusion.2 = bf16[4]{0} fusion(y)", 5.0, 3.0),
              ("%copy.1 = bf16[8]{0} copy(z)", 11.0, 1.0)]
    assert dict(T.self_times(nested))["%while.1 = (s32[]) while(x)"] == \
        pytest.approx(4.0)
    tr = T.Trace({0: nested}, {}, [], 0.0, 12.0)
    top = T.top_ops(tr, 10)
    assert top[0] == ["%fusion fusion bf16[4]", pytest.approx(6.0)]   # two of a kind
    assert top[1][0].startswith("%while while") and top[1][1] == pytest.approx(4.0)
    assert T.short_name("%fusion.7 = bf16[16,4096]{1,0:T(8,128)} fusion(a, b)") \
        == "%fusion fusion bf16[16,4096]"


def test_idle_gaps_are_charged_to_the_covering_host_span():
    ops = [("x", 0.0, 1.0), ("x", 3.0, 1.0), ("x", 4.5, 0.5)]
    spans = [("bench.step", 0.0, 1.2), ("bench.sleep", 1.2, 1.7),
             ("bench.step", 2.9, 2.1)]
    tr = T.Trace({0: ops}, {}, spans, 0.0, 5.0)
    gaps = dict(T.idle_gaps(tr))
    assert gaps["bench.sleep"] == pytest.approx(2.0)
    assert gaps["bench.step"] == pytest.approx(0.5)


def test_module_time_by_name_pattern():
    mods = [("jit_step(123)", 0.0, 2.0), ("jit_chunk(9)", 2.0, 1.0),
            ("jit_step(123)", 3.0, 2.0), ("jit_stepper(1)", 5.0, 7.0)]
    tr = T.Trace({}, {0: mods}, [], 0.0, 12.0)
    assert T.module_time_s(tr, r"^jit_step(\(|$)") == (pytest.approx(4.0), 2)
    assert T.module_time_s(tr, r"^jit_chunk(\(|$)") == (pytest.approx(1.0), 1)
    assert T.module_time_s(tr, r"^jit_step(\(|$)", lo=1.0) == \
        (pytest.approx(2.0), 1)


def test_op_time_inside_a_program():
    mods = [("jit_step(1)", 0.0, 2.0), ("jit_chunk(2)", 2.0, 2.0),
            ("jit_step(1)", 4.0, 2.0)]
    gather = "%all-gather.3 = bf16[210,8,128,128]{3,2,1,0} all-gather(x)"
    ops = [(gather, 0.5, 0.25), ("%fusion.1 = bf16[4]{0} fusion(y)", 1.0, 0.5),
           (gather, 2.5, 0.25), (gather, 4.5, 0.5)]
    tr = T.Trace({0: ops}, {0: mods}, [], 0.0, 6.0)
    from benchmark.layer_metrics.sp_gather_ms import GATHER, DECODE
    assert T.op_time_within(tr, GATHER, DECODE) == (pytest.approx(0.75), 2)


def test_the_recorded_tpu_trace_reduces():
    tr = T.load(TINY)
    assert sorted(tr.ops) == [0] and sorted(tr.modules) == [0]
    secs, n = T.module_time_s(tr, r"^jit_step(\(|$)")
    assert n == 5 and 1e-6 < secs < 1e-3
    assert [s[0] for s in tr.spans] == ["bench.step", "bench.sleep"] * 5
    busy, window = T.busy_s(tr), tr.t1_s - tr.t0_s
    assert 0 < busy < window < 0.1
    assert T.top_ops(tr, 3)[0][0].startswith("%fusion")
    assert sum(v for _, v in T.idle_gaps(tr)) == pytest.approx(window - busy,
                                                              rel=1e-3)

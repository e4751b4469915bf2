"""The trace reduction: on hand-made lines, and on a small trace recorded on
a TPU v5e (``data/small.xplane.pb``: two programs, one of them a loop, run
three times under ``TraceAnnotation`` ranges with sleeps between)."""
import os

import numpy as np
import pytest

from benchmark.trace import xplane

RECORDED = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def _line(*events):
    return xplane.Line([e[0] for e in events],
                       np.asarray([e[1] for e in events], np.int64),
                       np.asarray([e[2] for e in events], np.int64))


def test_union_merges_overlap_and_nesting():
    # [0,10) and [5,20) overlap; [30,40) holds [32,35); [50,60) alone
    line = _line(("a", 0, 10), ("b", 5, 15), ("w", 30, 10), ("c", 32, 3),
                 ("d", 50, 10))
    assert xplane.union_s(line) == pytest.approx(40e-9)
    assert xplane.union_s(line, lo_ns=8, hi_ns=34) == pytest.approx(16e-9)
    assert xplane.union_s(xplane.EMPTY) == 0.0


def test_self_times_take_nested_events_out():
    # a loop of 100 ns holding two bodies of 30 ns, one holding 10 ns more
    line = _line(("while", 0, 100), ("body", 10, 30), ("body", 50, 30),
                 ("inner", 55, 10), ("alone", 200, 7))
    got = xplane.self_times(line)
    assert got == pytest.approx({"while": 40e-9, "body": 50e-9,
                                 "inner": 10e-9, "alone": 7e-9})
    assert sum(got.values()) == pytest.approx(xplane.union_s(line))
    assert xplane.top(got, 2) == [["body", pytest.approx(50e-9)],
                                  ["while", pytest.approx(40e-9)]]


def test_matching_and_totals():
    line = _line(("jit__tree_impl(1)", 0, 5), ("jit__root_impl(2)", 9, 2),
                 ("jit_other(3)", 20, 1))
    found = line.matching(r"^jit_(_root_impl|_tree_impl)\b")
    assert found.names == ["jit__tree_impl(1)", "jit__root_impl(2)"]
    assert found.total_s() == pytest.approx(7e-9)


def test_idle_gaps_are_named_by_the_innermost_open_span():
    ops = _line(("op", 0, 10), ("op", 110, 10), ("op", 150, 10),
                ("op", 1160, 10))
    host = {"thread": _line(("outer::all", 0, 2000), ("inner::wait", 5, 110),
                            ("not a span", 0, 3000))}
    trace = xplane.Trace({0: {xplane.OPS_LINE: ops}}, host)
    gaps = dict(xplane.idle_gaps(trace, r"::"))
    # gaps: [10,110) inside inner::wait, [120,150) and [160,1160) outside it
    assert gaps == pytest.approx({"inner::wait": 100e-9,
                                  "outer::all": 1030e-9})
    assert xplane.busy_s(trace) == pytest.approx(40e-9)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the test")
def test_recorded_tpu_trace():
    trace = xplane.load(RECORDED)
    assert 0 in trace.devices
    modules, ops = trace.modules(), trace.ops()
    loops = modules.matching(r"^jit_bench_small_loop\b")
    adds = modules.matching(r"^jit_bench_small_add\b")
    assert len(loops) == 3 and len(adds) == 3
    busy = xplane.busy_s(trace)
    assert 0 < busy <= xplane.union_s(modules) * 1.001
    # every op lies inside some program, so self times add up to the union
    assert sum(xplane.self_times(ops).values()) == pytest.approx(
        xplane.union_s(ops), rel=1e-6)
    # the sleeps between the programs are the longest gaps, each inside the
    # range that was open around it
    gaps = xplane.idle_gaps(trace, r"^bench::")
    assert gaps and gaps[0][0] == "bench::sleep" and gaps[0][1] > 0.05

"""Trace reduction (bench/xtrace.py) on a small trace recorded on the CPU.

The trace holds four ``bench.step`` spans, each around one jitted call
whose XLA operations ran on the CPU, and ``bench.idle`` spans between
them, all inside one ``bench.window``.  On the CPU the XLA operations
are host events with an ``hlo_op`` stat; the tests count those as the
device's operations.
"""

from pathlib import Path

import pytest

from bench import xtrace

TRACE = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"


def cpu_ops(plane, line, event):
    return plane == "/host:CPU" and any(k == "hlo_op" for k, _ in event.stats)


@pytest.fixture(scope="module")
def trace():
    return xtrace.load(str(TRACE), cpu_ops)


def test_spans_and_ops_are_found(trace):
    assert len(xtrace.spans(trace, "bench.step")) == 4
    assert len(xtrace.spans(trace, "bench.window")) == 1
    assert {o.name for o in trace.device} == {
        "dot_general.1", "wrapped_tanh", "wrapped_reduce-window",
        "wrapped_reduce"}
    # the default selection finds no TPU plane in a CPU trace
    assert xtrace.load(str(TRACE)).device == []


def test_busy_time_lies_inside_each_step(trace):
    steps = xtrace.spans(trace, "bench.step")
    busy = xtrace.busy_in(trace.device, steps)
    for (s, e), b in zip(steps, busy):
        assert 0 < b <= e - s
    # every operation ran inside a step: the window's busy time is theirs
    lo, hi = xtrace.spans(trace, "bench.window")[0]
    whole = xtrace.union_within([(o.start, o.end) for o in trace.device],
                                lo, hi)
    assert whole == pytest.approx(sum(busy))


def test_kernel_time_counts_matching_ops_in_windows(trace):
    steps = xtrace.spans(trace, "bench.step")
    dots = [o for o in trace.device if o.name == "dot_general.1"]
    assert xtrace.time_in(trace.device, steps,
                          lambda n: n == "dot_general.1") == pytest.approx(
        sum(o.end - o.start for o in dots))
    assert xtrace.time_in(trace.device, steps[:1],
                          lambda n: n == "dot_general.1") == pytest.approx(
        dots[0].end - dots[0].start)


def test_breakdown_names_ops_and_idle_host_work(trace):
    lo, hi = xtrace.spans(trace, "bench.window")[0]
    top = xtrace.top_ops(trace.device, lo, hi)
    assert top[0][0] == "dot_general.1"
    assert [n for n, _ in top] == sorted(
        {o.name for o in trace.device},
        key=lambda n: -sum(o.end - o.start for o in trace.device
                           if o.name == n))
    gaps = dict(xtrace.idle_gaps(trace, lo, hi))
    busy = xtrace.union_within([(o.start, o.end) for o in trace.device],
                               lo, hi)
    # idle time in gaps of a microsecond or more, by host activity
    assert sum(gaps.values()) == pytest.approx((hi - lo - busy) * 1e-9,
                                               rel=0.05)
    assert max(gaps, key=gaps.get) == "bench.idle"


def test_interval_arithmetic():
    assert xtrace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xtrace.union_within([(0, 10), (5, 20), (30, 40)], 2, 35) == 23
    ops = [xtrace.Op("a", 0, 10), xtrace.Op("b", 5, 20),
           xtrace.Op("c", 30, 40)]
    assert xtrace.busy_in(ops, [(0, 4), (8, 32), (41, 50)]) == [4, 14, 0]


@pytest.mark.parametrize("name,short,op", [
    ('%closed_call.13 = bf16[512,3,3,64]{3,2,1,0:T(4,128)(2,1)S(1)} '
     'custom-call(s32[512]{0:T(512)S(1)} %get-tuple-element.639), '
     'custom_call_target="tpu_custom_call"',
     "closed_call.13 custom-call bf16[512,3,3,64]", "custom-call"),
    ('%while.5 = (s32[]{:T(128)}, bf16[1,32,5,16,128]{4,3,2,1,0:T(8,128)'
     '(2,1)S(1)}) while(%tuple.1)', "while.5 while tuple", "while"),
    ("dot_general.1", "dot_general.1", ""),
])
def test_tpu_op_names(name, short, op):
    assert xtrace.short_name(name) == short
    assert xtrace.opcode(name) == op

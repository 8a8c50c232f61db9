"""Busy time, idle gaps and per-op sums of bench.xplane on hand-made ops."""
from bench import xplane
from bench.xplane import DeviceOp


def ops(*spans):
    return [DeviceOp(s, e, n) for s, e, n in spans]


def test_busy_union_and_gaps():
    o = ops((10, 20, "a"), (15, 30, "b"), (40, 50, "a"), (45, 46, "c"))
    assert xplane.busy_intervals(o) == [(10, 30), (40, 50)]
    assert xplane.busy_ns(o) == 30
    # Window [0, 60): idle 0-10, 30-40, 50-60, longest first (ties by start).
    gaps = xplane.idle_gaps(o, (0, 60))
    assert sorted(gaps) == [(0, 10), (30, 40), (50, 60)]
    assert all(e - s == 10 for s, e in gaps)


def test_op_seconds_sums_by_name():
    o = ops((0, 1_000_000_000, "a"), (2_000_000_000, 2_500_000_000, "a"), (0, 10, "b"))
    assert xplane.op_seconds(o) == {"a": 1.5, "b": 1e-08}


def test_clock_offset():
    t = xplane.Trace(window=(100, 200), marker_ns=90)
    # A host reading 5 ns after the marker's host reading lands at 95.
    assert t.to_trace_ns(1_005, 1_000) == 95
    assert t.window_s == 1e-07

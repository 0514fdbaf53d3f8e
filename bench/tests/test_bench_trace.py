"""Trace reduction, the DP work function and the peak table."""
import pytest

from bench import dpwork, peaks
from bench.tracefile import Busy, Span, TraceView


def test_merge_and_overlap():
    b = Busy([(20, 30), (0, 10), (5, 15), (40, 40)])
    assert b.iv == [(0, 15), (20, 30)]
    assert b.within(12, 25) == 8
    assert b.within(-5, 100) == 25
    assert b.within(15, 20) == 0
    assert b.within(31, 39) == 0
    assert b.within(2, 4) == 2
    assert b.gaps(0, 40) == [(15, 20), (30, 40)]


def _view(chips, spans, window=(0, 100)):
    return TraceView(busy=[Busy(c) for c in chips], spans=spans,
                     op_time={"fusion.1": 60.0, "copy": 20.0}, window=window)


def test_idle_share_and_busy_single_chip():
    v = _view([[(10, 30), (25, 50)]], [])
    assert v.busy_ns() == 40
    assert v.idle_share() == pytest.approx(0.6)


def test_device_time_inside_span():
    spans = [Span("bench.admit", 0, 20), Span("bench.admit", 20, 60)]
    v = _view([[(5, 10), (15, 25), (40, 45)]], spans)
    assert [v.device_in(s) for s in spans] == [10, 10]


def test_four_chip_rule():
    """Device time in a span is the busiest chip's; the idle share is the
    mean over the chips."""
    chips = [[(0, 10)], [(0, 40)], [(10, 30)], []]
    span = Span("bench.run_fleet", 0, 50)
    v = _view(chips, [span])
    assert v.device_in(span) == 40
    assert v.idle_share() == pytest.approx(
        ((1 - .1) + (1 - .4) + (1 - .2) + 1.0) / 4)
    assert v.busy_ns() == pytest.approx((10 + 40 + 20 + 0) / 4)
    assert v.top_ops(1) == [["fusion.1", 60.0 / 4 / 1e9]]


def test_idle_gaps_are_labelled_by_span():
    spans = [Span("bench.window", 0, 100), Span("bench.scenario", 40, 70),
             Span("bench.run_fleet", 70, 100)]
    v = _view([[(0, 40), (90, 100)], [(0, 30)]], spans)
    assert v.idle_gaps() == [["bench.scenario", 50 / 1e9]]


def test_dp_work_at_a_small_shape():
    assert dpwork.dp_ops(2, 10, 3) == 2 * 10 * 27
    assert dpwork.dp_bytes(2, 10, 3) == 2 * (10 * 8 + 4)
    t, bound = dpwork.least_time(16384, 32768, 3, peaks.for_kind(
        "TPU v5 lite"))
    assert bound == "bytes"
    assert t == pytest.approx(16384 * (32768 * 8 + 4) / 819e9)


def test_unknown_chip_has_no_peaks():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.for_kind("TPU v9 imaginary")

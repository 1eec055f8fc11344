"""The reduction from a profiler trace to busy time, idle share and the
attribution of idle gaps to host spans, on synthetic intervals."""
from __future__ import annotations

import pytest

import _bench_cells  # noqa: F401  (puts the repo root on the path)
from bench import trace as T


def ev(name, s, t):
    return T.Event(name, s, t)


def test_union_merges_overlaps_and_clips_to_window():
    ops = [ev("a", 0.0, 2.0), ev("b", 1.0, 3.0), ev("c", 5.0, 6.0),
           ev("d", 9.0, 12.0)]
    assert T.merged(ops, 1.5, 10.0) == [(1.5, 3.0), (5.0, 6.0), (9.0, 10.0)]
    assert T.busy_seconds(ops, 1.5, 10.0) == pytest.approx(3.5)


def test_idle_gaps_cover_the_window_outside_ops():
    ops = [ev("a", 1.0, 2.0), ev("b", 4.0, 5.0)]
    assert T.idle_gaps(ops, 0.0, 6.0) == [(0.0, 1.0), (2.0, 4.0), (5.0, 6.0)]
    assert T.idle_gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_gap_goes_to_innermost_span_holding_its_midpoint():
    spans = [ev("bench.window", 0.0, 10.0), ev("bench.period", 0.0, 8.0),
             ev("bench.batch", 2.0, 3.5)]
    got = T.attribute([(2.0, 3.0), (5.0, 6.0), (8.5, 9.5), (11.0, 12.0)],
                      spans)
    assert got == pytest.approx({"bench.batch": 1.0, "bench.period": 1.0,
                                 "bench.window": 1.0,
                                 "outside any span": 1.0})


def test_reduce_averages_devices_and_ranks_breakdown():
    trace = T.Trace(
        ops={"/device:TPU:0": [ev("dot", 1.0, 4.0), ev("add", 5.0, 6.0)],
             "/device:TPU:1": [ev("dot", 1.0, 2.0)]},
        spans=[ev("bench.window", 0.0, 10.0), ev("bench.batch", 4.0, 5.0)])
    red = T.reduce(trace)
    assert red.window_s == 10.0
    assert red.busy_s == pytest.approx((4.0 + 1.0) / 2)
    assert red.idle_share == pytest.approx(0.75)
    assert red.device_ops == [["dot", 2.0], ["add", 0.5]]
    # device 0: gaps 0-1, 4-5 (batch), 6-10; device 1: 0-1, 2-10
    assert dict(red.idle_gaps) == pytest.approx(
        {"bench.window": (1 + 4 + 1 + 8) / 2, "bench.batch": 0.5})


def test_reduce_needs_one_window_span_and_a_device():
    with pytest.raises(ValueError):
        T.reduce(T.Trace({"/device:TPU:0": []}, []))
    with pytest.raises(ValueError):
        T.reduce(T.Trace({}, [ev("bench.window", 0.0, 1.0)]))


def test_device_plane_names():
    assert T.DEVICE_PLANE.match("/device:TPU:0")
    assert T.DEVICE_PLANE.match("/device:TPU:3")
    assert not T.DEVICE_PLANE.match("/host:CPU")


def test_containers_are_dropped_and_ops_named_from_hlo():
    ops = [ev("while", 0.0, 10.0), ev("a", 0.0, 3.0), ev("b", 4.0, 9.0),
           ev("c", 11.0, 12.0)]
    assert [e.name for e in T.leaves(ops)] == ["a", "b", "c"]
    assert T._op_name("%fusion.1359 = (f32[4,2,6,1500]{3,2,1,0:T(8,128)}, "
                      "f32[4]{0}) fusion(f32[4] %p), kind=kOutput") \
        == "fusion f32[4,2,6,1500]"
    assert T._op_name("%while.498 = (s32[]{:T(128)}, bf16[4]{0}) while(%t)") \
        == "while s32[]"

"""Trace reduction: busy, idle, per-op and collective-exposed time."""

import pytest

import trace_reduce as tr

MS = 1_000_000


def test_union_merges_overlaps_and_nesting():
    assert tr.union([(5, 7), (0, 2), (1, 3), (6, 6), (10, 12), (11, 11)]) == \
        [(0, 3), (5, 7), (10, 12)]


def test_subtract_and_gaps():
    busy = [(2, 4), (6, 9)]
    assert tr.subtract([(0, 10)], busy) == [(0, 2), (4, 6), (9, 10)]
    assert tr.gaps(busy, 3, 8) == [(4, 6)]
    assert tr.subtract([(0, 5), (7, 9)], [(1, 2), (4, 8)]) == [(0, 1), (2, 4), (8, 9)]


def _synthetic():
    # device 0: compute 0-4 ms and 6-10 ms, an all-gather 3-6 ms (2 ms exposed)
    # device 1: compute 0-9 ms, a reduce-scatter 8-10 ms (1 ms exposed)
    ops = {
        0: [("fusion.1", 0, 4 * MS), ("all-gather.3", 3 * MS, 6 * MS),
            ("fusion.2", 6 * MS, 10 * MS)],
        1: [("convolution.7", 0, 9 * MS), ("reduce-scatter.1", 8 * MS, 10 * MS)],
    }
    spans = [("data.read", 3 * MS, 7 * MS), ("dispatch", 10 * MS, 12 * MS)]
    return tr.Trace(ops, spans)


def test_reduce_synthetic():
    red = tr.reduce(_synthetic(), 0, 12 * MS, [0, 1])
    assert red.window_s == pytest.approx(0.012)
    assert red.busy_s == pytest.approx([0.010, 0.010])
    assert red.idle_share == pytest.approx(2 / 12)
    assert red.exposed_collective_s == pytest.approx([0.002, 0.001])
    assert red.top_ops[0] == ("convolution", pytest.approx(0.0045))
    # device 0 is idle only 10-12 ms, while the host dispatches
    assert red.idle_by_span == [("dispatch", pytest.approx(0.002))]


def test_reduce_clips_to_the_window():
    red = tr.reduce(_synthetic(), 2 * MS, 8 * MS, [0])
    assert red.busy_s == pytest.approx([0.006])
    assert red.exposed_collective_s == pytest.approx([0.002])


def test_reduce_refuses_missing_devices():
    with pytest.raises(ValueError):
        tr.reduce(_synthetic(), 0, MS, [0, 3])


def test_json_round_trip():
    t = _synthetic()
    assert tr.Trace.from_json(t.to_json()) == t

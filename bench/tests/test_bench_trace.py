"""The trace reduction against totals counted by hand."""
import json
from pathlib import Path

import pytest

import bench_tiny
from benchlib import trace

TESTDATA = bench_tiny.BENCH / "testdata"


def test_union_gaps_and_host_attribution_by_hand():
    planes = {
        "devices": {0: [("a", 10, 30), ("b", 20, 40), ("a", 60, 70),
                        ("c", 85, 90)],
                    1: [("a", 10, 100)]},
        "host": [("bench.dispatch_step", 0, 12), ("float", 40, 58),
                 ("bench.dispatch_step", 72, 84), ("outer", 0, 100)],
    }
    r = trace.reduce(planes)
    # window: first to last device op, 10..100; device 0 is busy on
    # [10,40] + [60,70] + [85,90] = 45 ns, device 1 on 90 ns
    assert r["window_s"] == pytest.approx(90e-9)
    assert r["devices"][0]["busy_s"] == pytest.approx(45e-9)
    assert r["busy_s"] == pytest.approx(67.5e-9)
    # self time: each stretch goes to the running op that started last, so
    # b takes [20,40] from a and the totals add up to the busy time
    assert r["ops_s"] == {"a": pytest.approx(20e-9), "b": pytest.approx(20e-9),
                          "c": pytest.approx(5e-9)}
    # device 0's gaps, longest first, each named by the innermost host event
    # that covers at least half of it
    assert r["breakdown"]["idle_gaps"] == [
        ["float", pytest.approx(20e-9)],
        ["bench.dispatch_step", pytest.approx(15e-9)],
        ["outer", pytest.approx(10e-9)]]
    assert r["breakdown"]["device_ops"][0] == ["a", pytest.approx(20e-9)]


def test_marked_window_counts_idle_at_its_ends():
    """With the bench's window span, ops are cut to it and the device's idle
    time before its first op and after its last counts."""
    planes = {
        "devices": {0: [("a", 0, 30), ("b", 50, 60), ("c", 90, 120)]},
        "host": [("float", 30, 50), ("np.asarray", 60, 100)],
        "window": (20, 100),
    }
    r = trace.reduce(planes)
    assert r["window_s"] == pytest.approx(80e-9)
    # busy: [20,30] + [50,60] + [90,100]
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["ops_s"] == {"a": pytest.approx(10e-9), "b": pytest.approx(10e-9),
                          "c": pytest.approx(10e-9)}
    assert r["breakdown"]["idle_gaps"] == [
        ["np.asarray", pytest.approx(30e-9)], ["float", pytest.approx(20e-9)]]
    with pytest.raises(ValueError):
        trace.reduce(dict(planes, window=(200, 300)))


def test_enclosed_ops_count_their_own_time_only():
    ops = [("loop", 0, 100), ("f", 10, 30), ("g", 40, 90), ("h", 50, 60)]
    assert trace.self_times(ops) == {"loop": 30, "f": 20, "g": 40, "h": 10}


def test_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": [("x", 0, 1)]})


def _sweep_busy(intervals):
    """Covered length by a sweep over +1/-1 boundaries (a second way to
    count what trace._union counts)."""
    edges = sorted([(s, 1) for s, _ in intervals]
                   + [(e, -1) for _, e in intervals])
    depth, covered, last = 0, 0, None
    for t, step in edges:
        if depth > 0:
            covered += t - last
        depth += step
        last = t
    return covered


def test_recorded_chip_trace_against_a_hand_count():
    """Two steps of qwen3-1.7b.train traced on one TPU v5e chip (device 0's
    ops and the dispatching thread's host events, as trace.load reads
    them). The totals below were counted from the file by a sweep over the
    ops' boundaries, apart from this module."""
    import gzip

    with gzip.open(TESTDATA / "trace-qwen3-1.7b.train.json.gz", "rt") as f:
        recorded = json.load(f)
    ops = [tuple(e) for e in recorded["devices"]["0"]]
    host = [tuple(e) for e in recorded["host"]]
    r = trace.reduce({"devices": {0: ops}, "host": host})

    assert len(ops) == 3815
    assert r["window_s"] == pytest.approx(593_045_700e-9, abs=1e-12)
    assert r["busy_s"] == pytest.approx(583_193_571e-9, abs=1e-12)
    assert _sweep_busy([(s, e) for _, s, e in ops]) == 583_193_571
    # idle: 9,852,129 ns, of which two gaps of 4,959,982 and 4,875,717 ns,
    # one between each pair of steps, while the host read the step's loss
    idle = [v for _, v in r["breakdown"]["idle_gaps"]]
    assert idle[:2] == [pytest.approx(4_959_982e-9, abs=1e-12),
                        pytest.approx(4_875_717e-9, abs=1e-12)]
    assert sum(idle) <= (593_045_700 - 583_193_571) * 1e-9 + 1e-12
    assert sum(r["ops_s"].values()) == pytest.approx(r["busy_s"])
    # the longest op: the head's fused matmul, three events, none enclosed
    assert r["breakdown"]["device_ops"][0] == [
        "%fusion.365", pytest.approx(80_908_769e-9, abs=1e-12)]

"""The trace reduction, on intervals worked out by hand and on a small
trace recorded on a TPU v5 lite (``record_trace.py``)."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import xplane  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "small_tpu.xplane.pb"


def test_reduce_by_hand():
    ms = 1_000_000
    devices = {"/device:TPU:0": [("a", 10 * ms, 20 * ms), ("b", 16 * ms, 30 * ms),
                                 ("c", 50 * ms, 60 * ms),
                                 ("a", 95 * ms, 110 * ms)]}
    spans = {xplane.WINDOW: [(0, 100 * ms)], xplane.ROUND: [(9 * ms, 70 * ms)],
             xplane.SCORE: [(9 * ms, 35 * ms)]}
    r = xplane.reduce(devices, spans)
    assert r["window_s"] == pytest.approx(0.100)
    # busy: (10, 30) + (50, 60) + (95, 100) ms
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["busy_in_score_s"] == pytest.approx(0.020)
    assert r["device_ops"] == [["a", pytest.approx(0.015)],
                               ["b", pytest.approx(0.014)],
                               ["c", pytest.approx(0.010)]]
    # idle (-1, 10), (30, 50), (60, 95), counted from 1 ms before the
    # window, split by host span: the score span, from 8 ms, holds (8, 10)
    # and (30, 35); the rest of the round (8, 70) holds (35, 50) and
    # (60, 70); the engine holds (-1, 8) and (70, 95)
    assert r["idle_gaps"] == [["engine: all 2 gaps", pytest.approx(0.034)],
                              ["engine: longest gap", pytest.approx(0.025)],
                              ["commit: all 2 gaps", pytest.approx(0.025)],
                              ["commit: longest gap", pytest.approx(0.015)],
                              ["score_queue: all 2 gaps",
                               pytest.approx(0.007)],
                              ["score_queue: longest gap",
                               pytest.approx(0.005)]]


def test_interval_arithmetic():
    assert xplane.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert xplane.intersect([(1, 4), (5, 8)], [(3, 6)]) == [(3, 4), (5, 6)]
    assert xplane.subtract([(0, 10), (12, 14)], [(2, 3), (5, 13)]) \
        == [(0, 2), (3, 5), (13, 14)]
    assert xplane.clip([(0, 5), (6, 9)], 2, 7) == [(2, 5), (6, 7)]


def test_recorded_tpu_trace():
    """Three rounds of one matmul program, each in a score span, with host
    sleeps of 2 ms (commit) and 5 ms (engine) after it: the device is busy
    only inside the score spans, and the idle gaps are named by the span
    the host was in."""
    devices, spans = xplane.read_events(str(RECORDED))
    assert list(devices) == ["/device:TPU:0"]
    assert len(spans[xplane.SCORE]) == 3 and len(spans[xplane.ROUND]) == 3
    r = xplane.reduce(devices, spans)
    assert r["busy_s"] > 0
    assert r["busy_in_score_s"] == pytest.approx(r["busy_s"])
    assert r["busy_s"] < r["window_s"]
    gaps = dict(r["idle_gaps"])
    assert gaps["commit: longest gap"] >= 0.002
    assert gaps["engine: longest gap"] >= 0.005
    assert r["device_ops"][0][1] > 0

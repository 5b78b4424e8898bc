"""The program's spans on the trace's clock (``spans.py``): the idle
attribution on intervals worked out by hand, on a small trace recorded on
a TPU v5 lite (``record_spans_trace.py``), and a traced run of the cell
at a small size on the CPU with the registry installed."""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import spans   # noqa: E402
import xplane  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "small_tpu_spans.xplane.pb"
MS = 1_000_000
LAYERS = [m for m, _ in spans.ROUND_LAYERS] + ["engine_commit_ms_per_pod",
                                               "readback_gb_per_s"]


def test_innermost_names_each_piece_by_the_latest_started_span():
    got = spans.innermost({"outer": [(0, 10)], "a": [(2, 4), (6, 12)],
                           "b": [(3, 5)]})
    assert got == [(0, 2, "outer"), (2, 3, "a"), (3, 5, "b"),
                   (5, 6, "outer"), (6, 12, "a")]
    assert spans.innermost({}) == []


def test_idle_by_span_by_hand():
    devices = {"/device:TPU:0": [("x", 10 * MS, 20 * MS),
                                 ("y", 50 * MS, 60 * MS)]}
    host = {xplane.WINDOW: [(0, 100 * MS)]}
    program = {"engine_round": [(5 * MS, 90 * MS)],
               "scheduler_batch": [(8 * MS, 70 * MS)],
               "scheduler_readback": [(9 * MS, 30 * MS)],
               "scheduler_argsort": [(32 * MS, 45 * MS)]}
    # counted from 1 ms early, the spans cover (4, 90) ms; the window's
    # idle (-1, 10), (20, 50), (60, 100) falls to the innermost span:
    # engine_round (4, 7) and (70, 90); the batch (7, 8), (30, 31),
    # (45, 50) and (60, 70); the readback (8, 10) and (20, 30); the
    # argsort (31, 45); (-1, 4) and (90, 100) to no span
    got = spans.idle_by_span(devices, host, program)
    assert got == [
        ["span engine_round: all 2 gaps", pytest.approx(0.023)],
        ["span engine_round: longest gap", pytest.approx(0.020)],
        ["span scheduler_batch: all 4 gaps", pytest.approx(0.017)],
        ["span scheduler_batch: longest gap", pytest.approx(0.010)],
        ["span scheduler_argsort: all 1 gaps", pytest.approx(0.014)],
        ["span scheduler_argsort: longest gap", pytest.approx(0.014)],
        ["span scheduler_readback: all 2 gaps", pytest.approx(0.012)],
        ["span scheduler_readback: longest gap", pytest.approx(0.010)]]
    assert spans.idle_s(devices, host) == pytest.approx(0.081)
    assert spans.idle_by_span(devices, host, {}) == []


def test_busy_by_program_by_hand():
    host = {xplane.WINDOW: [(0, 100 * MS)]}
    modules = [[("jit_f", -5 * MS, 3 * MS), ("jit_g", 10 * MS, 30 * MS),
                ("jit_f", 40 * MS, 45 * MS), ("jit_f", 99 * MS, 120 * MS)]]
    # from 1 ms before the window: jit_f 4 + 5 + 1 ms, jit_g 20 ms
    assert spans.busy_by_program(modules, host) == [
        ["program jit_g", pytest.approx(0.020)],
        ["program jit_f", pytest.approx(0.010)]]


def test_layer_metrics_by_hand():
    totals = {"scheduler_batch": {"count": 4, "total_s": 0.4, "self_s": 0.0},
              "scheduler_sync": {"count": 4, "total_s": 0.004, "self_s": 0.0},
              "scheduler_argsort": {"count": 4, "total_s": 0.2,
                                    "self_s": 0.2},
              "scheduler_readback": {"count": 4, "total_s": 0.02,
                                     "self_s": 0.02},
              "engine_commit": {"count": 4, "total_s": 0.01, "self_s": 0.01}}
    counters = {"engine_commits": 1000.0,
                "scheduler_readback_bytes": 4e7}
    assert spans.layer_metrics(totals, counters) == {
        "sync_ms_per_round": pytest.approx(1.0),
        "argsort_ms_per_round": pytest.approx(50.0),
        "readback_ms_per_round": pytest.approx(5.0),
        "engine_commit_ms_per_pod": pytest.approx(0.01),
        "readback_gb_per_s": pytest.approx(2.0)}
    assert spans.layer_metrics({}, {}) == {}


def test_recorded_tpu_trace_with_program_spans():
    """Three bursts of a small fleet scheduled on the chip under the
    registry: the program's spans are on the host plane, the device's
    programs are named, and the idle time put down to spans is part of
    the window's idle time."""
    devices, host = xplane.read_events(str(RECORDED))
    names = {"engine_round", "engine_commit", "scheduler_batch",
             "scheduler_sync", "scheduler_mask", "scheduler_upload",
             "scheduler_dispatch", "scheduler_readback",
             "scheduler_argsort", "scheduler_walk"}
    found, modules = spans.read_events(str(RECORDED), names)
    assert set(found) == names
    assert len(found["scheduler_batch"]) == 3
    programs = dict(spans.busy_by_program(modules, host))
    assert "program jit__closeness_from_kinds" in programs
    assert all(v > 0 for v in programs.values())
    named = spans.idle_by_span(devices, host, found)
    total = sum(v for k, v in named if k.endswith(" gaps"))
    assert 0 < total <= spans.idle_s(devices, host) + 1e-9
    assert any(k.startswith("span scheduler_argsort") for k, _ in named)


def test_cpu_traced_run_reports_every_layer():
    from test_bench_cells import SEED, small_cell
    cell = small_cell("k8s-5000.steady")
    out = spans.traced_run(cell, SEED, 2.0, True, time.perf_counter(),
                           n_nodes=200)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    for name in LAYERS:
        assert out["metrics"][name]["value"] > 0, name
    assert out["spans"]["scheduler_batch"]["count"] == out["attempted"]
    # the window's rounds only: the warm-up ran without the registry
    assert out["counters"]["engine_commits"] > 0
    # the CPU has no device plane: nothing to put down to the spans
    assert "idle_named_share" not in out
    without = spans.traced_run(cell, SEED, 2.0, False, time.perf_counter(),
                               n_nodes=200)
    assert without["correct"]
    assert not set(LAYERS) & set(without["metrics"])

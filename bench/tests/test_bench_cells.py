"""The benchmark's control flow at a small size on the CPU, and its
correctness check against the control and every planted fault.

Runs each cell through ``run.run_cell`` (which skips ``main``'s look for
a chip) with a 200-node fleet and short bursts:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import faults  # noqa: E402
import run     # noqa: E402

SMALL = {"k8s-5000.steady": {"n_bursts": 6, "burst_size": 32}}
SEED = 2**31 + 12345


def small_cell(name):
    cell = run.load_cell(name)
    cell.traffic = dict(cell.traffic, **SMALL[name])
    return cell


def run_small(name, seconds=2.0, trace=False, patches=()):
    cell = small_cell(name)
    return cell, run.run_cell(cell, SEED, seconds, trace, time.perf_counter(),
                              n_nodes=200, patches=patches)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_cell_runs_correct_with_its_metrics(name):
    cell, out = run_small(name)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def test_traced_run_reports_host_layers_and_breakdown():
    cell, out = run_small("k8s-5000.steady", trace=True)
    assert out["correct"], out["checks"]
    for name in ("engine_ms_per_pod", "score_ms_per_round",
                 "commit_ms_per_round"):
        assert out["metrics"][name]["value"] > 0
    # the CPU has no device plane: the device readers find nothing
    assert "device_idle_share" not in out["metrics"]
    assert "score_hbm_roofline" not in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_window_stops_at_a_round_after_the_limit():
    # the steady traffic's first replay always finishes; at this size it
    # takes well under the window, so several replays run and the last is
    # stopped at a round
    cell, out = run_small("k8s-5000.steady", seconds=1.5)
    assert out["correct"]
    assert out["attempted"] > 2 * SMALL["k8s-5000.steady"]["n_bursts"]


@pytest.mark.parametrize("name", sorted(SMALL))
def test_control_fails(name):
    cell, out = run_small(name, patches=[faults.control(small_cell(name)
                                                        .config)])
    assert not out["correct"]
    assert out["checks"]["closeness_err"]["value"] \
        > out["checks"]["closeness_err"]["limit"]


@pytest.mark.parametrize("fault, number", [
    ("stale_state", "closeness_err"), ("half_batch", "inf_mismatch"),
    ("altered_answer", "commit_illegal"), ("dropped_commit", "unplaced"),
    ("energy_skew", "energy_gap")])
@pytest.mark.parametrize("name", sorted(SMALL))
def test_fault_fails(name, fault, number):
    cell, out = run_small(name, patches=[faults.FAULTS[fault]()])
    assert not out["correct"]
    check = out["checks"][number]
    assert check["value"] is None or check["value"] > check["limit"]

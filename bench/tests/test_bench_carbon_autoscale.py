"""The ``k8s-5000-carbon-autoscale.steady`` cell on the CPU: its own
configuration and traffic, with the fleet cut to 200 nodes and the traffic
to 4 bursts of 32, through ``run.run_cell``. The program passes every
number on three seeds, the replay defers, wakes and drains, and the
control fails on precision.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import faults   # noqa: E402
import harness  # noqa: E402
import run      # noqa: E402

CELL = "k8s-5000-carbon-autoscale.steady"
SMALL = {"n_bursts": 4, "burst_size": 32}
NODES = 200
SEEDS = (2**31 + 12345, 3_000_000_019, 4_100_000_003)


def small_cell():
    cell = run.load_cell(CELL)
    cell.traffic = dict(cell.traffic, **SMALL)
    return cell


def run_small(seed, seconds=1.0, trace=False, patches=()):
    """One run of the cut cell; returns the cell, the result line and the
    harness's recorder."""
    cell = small_cell()
    seen = {}
    check = harness.check

    def keep(cfg, traffic, rec, win):
        seen["rec"] = rec
        return check(cfg, traffic, rec, win)

    harness.check = keep
    try:
        out = run.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                           n_nodes=NODES, patches=patches)
    finally:
        harness.check = check
    return cell, out, seen["rec"]


def test_cell_declares_both_policies_and_a_deferrable_share():
    cell = run.load_cell(CELL)
    assert set(cell.config["policies"]) == {"carbon", "autoscale"}
    assert cell.config["scheme"] == "carbon_energy_balanced"
    assert len(cell.config["weights"]) == len(cell.config["criteria"]) == 6
    assert cell.traffic["deferrable"]["share"] == 0.5
    assert set(cell.limits) == {
        "closeness_err", "inf_mismatch", "commit_illegal", "unplaced",
        "energy_gap", "defer_illegal", "preempt_illegal", "drain_illegal",
        "wake_illegal"}
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "pods_per_s"}


@pytest.mark.parametrize("seed", SEEDS)
def test_program_is_correct_and_defers_wakes_and_drains(seed):
    cell, out, rec = run_small(seed)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert out["metrics"]["pods_per_s"]["value"] > 0
    log = rec.logs[0]
    bursts = rec.bursts[0]
    last_arrival = bursts[-1][0]
    deferrable = {p.uid for _, pods in bursts for p in pods if p.deferrable}
    released = [e for e in log if e[0] == "commit" and e[2] in deferrable]
    # every deferrable pod is held past the last arrival and released
    assert {e[2] for e in released} == deferrable
    assert min(e[1] for e in released) > last_arrival
    assert any(e[0] == "wake" for e in log)
    assert any(e[0] == "evict" and e[4] == "drain" for e in log)


def test_traced_run_counts_select_calls_per_pod():
    cell, out, rec = run_small(SEEDS[0], trace=True)
    assert out["correct"], out["checks"]
    calls = out["metrics"]["select_calls_per_pod"]["value"]
    assert calls == len(rec.rounds) / len(rec.placed) > 0
    assert out["metrics"]["engine_ms_per_pod"]["value"] > 0
    assert {m["name"] for m in cell.per_layer} == {
        "engine_ms_per_pod", "device_idle_share", "select_calls_per_pod"}


def test_control_fails_closeness_err():
    cell = small_cell()
    _, out, _ = run_small(SEEDS[1], seconds=0.0,
                          patches=[faults.control(cell.config)])
    assert not out["correct"]
    closeness = out["checks"]["closeness_err"]
    assert closeness["value"] > closeness["limit"]

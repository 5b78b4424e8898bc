"""The carbon and autoscale policies through the benchmark at 200 nodes on
the CPU: a configuration and traffic of the tests' own
(``data/policy-200*.json``: the k8s-5000 configuration at 200 nodes with
the policies at the Cluster Autoscaler FAQ's and Radovanovic et al.'s
values, every time divided by 48), the program passing every number, each
planted policy fault failing its own, and the reference's power-state
ledger on a hand-built timeline.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import faults              # noqa: E402
import harness             # noqa: E402
import reference           # noqa: E402
import reference_policies  # noqa: E402
import run                 # noqa: E402

SEEDS = (2**31 + 12345, 3_000_000_019, 4_100_000_003)


def policy_cell():
    steady = run.load_cell("k8s-5000.steady")
    return SimpleNamespace(
        name="policy-200", chips=1,
        config=json.loads((DATA / "policy-200.json").read_text()),
        traffic=json.loads((DATA / "policy-200.traffic.json").read_text()),
        limits=json.loads((DATA / "policy-200.limits.json").read_text()),
        end_to_end=steady.end_to_end, per_layer=steady.per_layer)


def run_policy(seed, patches=(), seconds=0.0):
    cell = policy_cell()
    return cell, run.run_cell(cell, seed, seconds, False, time.perf_counter(),
                              n_nodes=200, patches=patches)


def failed(out, number):
    check = out["checks"][number]
    return check["value"] is None or check["value"] > check["limit"]


@pytest.mark.parametrize("seed", SEEDS)
def test_program_passes_every_number_with_the_policies_on(seed):
    cell, out = run_policy(seed)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == set(cell.limits)
    assert out["metrics"]["energy_j_per_pod"]["value"] > 0


def test_window_of_several_replays_with_the_policies_on():
    _, out = run_policy(SEEDS[0], seconds=2.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0


def test_window_cut_in_a_wake_pass_is_not_judged_half_done(monkeypatch):
    """The window's time runs out in the middle of a replay's first wake
    pass: the harness ends the window at the next round's start, so no
    round is judged with only some of its wakes made."""
    from repro.core.elastic import ElasticFleet
    clock = {"skip": 0.0, "replays": 0}
    real = time.perf_counter
    monkeypatch.setattr(harness, "time", SimpleNamespace(
        perf_counter=lambda: real() + clock["skip"]))
    replay = harness.replay_scenario

    def counted(*a, **kw):
        clock["replays"] += 1
        return replay(*a, **kw)

    wake = ElasticFleet.request_wake

    def request_wake(fleet, *a, **kw):
        out = wake(fleet, *a, **kw)
        if clock["replays"] >= 2:
            clock["skip"] = 1e6
        return out

    monkeypatch.setattr(harness, "replay_scenario", counted)
    monkeypatch.setattr(ElasticFleet, "request_wake", request_wake)
    _, out = run_policy(SEEDS[0], seconds=1e5)
    assert clock["skip"] > 0
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault, numbers", [
    ("never_defer", ["defer_illegal"]),
    ("never_sleep", ["inf_mismatch", "energy_gap"]),
    ("never_preempt", ["preempt_illegal"]),
    ("carbon_blind", ["closeness_err"]),
    ("wake_storm", ["wake_illegal"]),
    ("drain_busy", ["drain_illegal"])])
@pytest.mark.parametrize("seed", SEEDS)
def test_policy_fault_fails_its_number(fault, numbers, seed):
    _, out = run_policy(seed, patches=[faults.POLICY_FAULTS[fault]()])
    assert not out["correct"]
    for number in numbers:
        assert failed(out, number), (number, out["checks"])


def test_control_fails_on_precision_alone():
    cell = policy_cell()
    _, out = run_policy(SEEDS[1], patches=[faults.control(cell.config)])
    assert [k for k in out["checks"] if failed(out, k)] == ["closeness_err"]


def test_policy_free_fleet_energy_is_task_energy():
    cell = run.load_cell("k8s-5000.steady")
    cell.traffic = dict(cell.traffic, n_bursts=6, burst_size=32)
    seen = {}
    check = harness.check

    def keep(cfg, traffic, rec, win):
        seen.update(rec=rec, win=win)
        return check(cfg, traffic, rec, win)

    harness.check = keep
    try:
        out = run.run_cell(cell, SEEDS[2], 1.0, False, time.perf_counter(),
                           n_nodes=200)
    finally:
        harness.check = check
    assert out["correct"], out["checks"]
    rec, win = seen["rec"], seen["win"]
    assert len(win.results) > 1
    for replay, fleet, res in win.results:
        ledger = reference_policies.Ledger(cell.config, fleet,
                                           rec.bursts[replay])
        got = ledger.run(harness.placements(fleet, res)).fleet_energy_j()
        want = reference.task_energy_j(res.records, fleet)
        assert abs(got - want) <= 1e-12 * want
        if replay == 0:
            placed = len({r.pod.uid for r in res.records})
            assert out["metrics"]["energy_j_per_pod"]["value"] \
                == pytest.approx(want / placed, rel=1e-12)
    for k, c in out["checks"].items():
        if k.endswith("_illegal"):
            assert c["value"] == 0


# --- the ledger on a hand-built timeline --------------------------------

AUTO = {"idle_timeout_s": 10.0, "consolidate_interval_s": 5.0,
        "consolidate_util_below": 0.5, "wake_on_pressure": True,
        "min_awake": 1,
        "wake_profiles": {"X": {"wake_latency_s": 8.0, "sleep_power_w": 0.5,
                                "wake_energy_j": 40.0}}}
CFG = {"regions": ["r0"], "policies": {"autoscale": AUTO}}


class Fleet3:
    node_class = ["X", "X", "X"]
    vcpus = np.array([4.0, 4.0, 4.0])
    mem_gb = np.array([8.0, 8.0, 8.0])
    speed = np.array([1.0, 1.0, 1.0])
    dyn_power = np.array([3.0, 5.0, 7.0])
    idle_power = np.array([10.0, 20.0, 30.0])

    def __len__(self):
        return 3


def pod(uid, base, deferrable=False, deadline=600.0):
    return SimpleNamespace(uid=uid, cpu=1.0, mem=1.0, deferrable=deferrable,
                           deadline_s=deadline,
                           workload=SimpleNamespace(base_time_s=base))


# uid 0 runs 20 s, uid 1 5 s, uid 2 is deferrable with 5 s to its deadline
BURSTS = [(0.0, [pod(0, 20.0), pod(1, 5.0)]),
          (30.0, [pod(2, 5.0, deferrable=True, deadline=5.0)])]
LOG = [("round", 0.0), ("commit", 0.0, 0, 2), ("commit", 0.0, 1, 1),
       ("round", 5.0),                        # the first consolidation pass
       ("evict", 5.0, 0, 2, "drain"),         # node 2 sleeps at once
       ("commit", 5.0, 0, 0),                 # node 0 is below min_awake
       ("round", 30.0),                       # uid 2 finds no node ...
       ("wake", 30.0, 2),                     # ... and wakes node 2 until 38
       ("round", 31.0), ("commit", 31.0, 2, 0)]


def test_ledger_idle_timeout_min_awake_drain_and_late_wake():
    ledger = reference_policies.Ledger(CFG, Fleet3(), BURSTS)
    ledger.run(LOG, samples=[(4, 5.0), (9, 31.0)])
    assert ledger.numbers == {"defer_illegal": 0, "preempt_illegal": 0,
                              "drain_illegal": 0, "wake_illegal": 0,
                              "asleep_commits": 0}
    # t = 5, before the drain: nothing sleeps, nothing wakes
    asleep, ready, _, _ = ledger.snapshots[4]
    assert not asleep.any() and np.isnan(ready).all()
    # t = 31: node 0 never sleeps (min_awake), node 1 slept at 5 + 10,
    # node 2 wakes until 38, past uid 2's deadline of 35
    asleep, ready, _, _ = ledger.snapshots[9]
    assert asleep.tolist() == [False, True, False]
    assert ready[2] == 38.0 and ledger.deadline(2) == 35.0
    sample = SimpleNamespace(seq=9, probe=False, pods=[BURSTS[1][1][0]])
    awake, exclude = harness._masks(CFG, sample, ledger)
    assert awake.tolist() == [True, False, True]
    assert exclude.tolist() == [[False, True, True]]
    # horizon 36 (uid 2 ends): node 1 asleep 15..36, node 2 asleep 5..30
    # and WAKING from 30 to the horizon
    asleep_s = np.array([0.0, 21.0, 25.0])
    dyn = 7.0 * 5 + 5.0 * 5 + 3.0 * 20 + 3.0 * 5
    want = dyn + float(np.sum(Fleet3.idle_power * (36.0 - asleep_s)
                              + 0.5 * asleep_s)) + 40.0
    assert ledger.fleet_energy_j() == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("log, number", [
    ([("round", 0.0), ("commit", 0.0, 0, 2), ("round", 4.0),
      ("evict", 4.0, 0, 2, "drain")], "drain_illegal"),       # off cadence
    ([("round", 0.0), ("commit", 0.0, 0, 0), ("round", 5.0),
      ("evict", 5.0, 0, 0, "drain")], "drain_illegal"),       # min_awake
    ([("round", 0.0), ("round", 1.0), ("wake", 1.0, 1)],
     "wake_illegal"),                                         # node awake
    ([("round", 0.0), ("commit", 0.0, 1, 1), ("round", 30.0),
      ("wake", 30.0, 2), ("wake", 30.0, 1)],
     "wake_illegal"),                # node 2 holds both pods: one too many
    ([("round", 0.0), ("commit", 0.0, 1, 1), ("round", 20.0),
      ("commit", 20.0, 0, 1)], "asleep_commits"),             # slept at 15
])
def test_ledger_counts_broken_rules(log, number):
    ledger = reference_policies.Ledger(CFG, Fleet3(), BURSTS).run(log)
    assert ledger.numbers[number] == 1
    assert sum(ledger.numbers.values()) == 1

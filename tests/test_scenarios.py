"""Event-driven scenario engine: golden paper-mode reproduction, arrival
processes, time-resolved energy accounting.

The golden fixture (tests/golden_table6.json) was recorded by running
``table6()`` and ``run_experiment`` on the pre-refactor legacy simulator
(post-hoc ``_union_length`` accounting, hand-rolled all-at-t0 loop). The
event-driven engine must reproduce it through ``PaperArrivals`` — same
placements, same energies — and the power-timeline accounting must match
the legacy idle+dynamic decomposition exactly.
"""
import json
import os

import numpy as np
import pytest

from repro.core.energy import (NODE_ENERGY_PROFILES, PowerTimeline,
                               merge_intervals, union_length)
from repro.cluster.node import (Node, SCENARIO_PROFILES,
                                make_scenario_cluster)
from repro.cluster.simulator import run_experiment, run_scenario, table6
from repro.cluster.workload import (PaperArrivals, PoissonArrivals,
                                    TraceArrivals, make_pods)

GOLDEN = json.load(open(os.path.join(os.path.dirname(__file__),
                                     "golden_table6.json")))


# --- golden paper-mode reproduction ------------------------------------------
def test_table6_matches_prerefactor_golden():
    """table6() through the event-driven engine == the recorded output of
    the pre-refactor legacy simulator, to float-roundoff."""
    t6 = table6()
    for level, d in GOLDEN["table6"].items():
        for scheme, vals in d.items():
            for key, want in vals.items():
                got = t6[level][scheme][key]
                assert abs(got - want) < 1e-9, (level, scheme, key, got, want)


@pytest.mark.parametrize("level", ["low", "medium", "high"])
def test_paper_mode_placements_match_golden(level):
    res = run_experiment(level, "energy_centric")
    g = GOLDEN["placements"][level]
    assert [r.node for r in res.records] == g["nodes"]
    assert abs(res.energy_kj("topsis") - g["energy_topsis_kj"]) < 1e-9
    assert abs(res.energy_kj("default") - g["energy_default_kj"]) < 1e-9


def test_run_experiment_is_paper_arrivals_scenario():
    """run_experiment is exactly PaperArrivals through run_scenario."""
    a = run_experiment("medium", "general")
    b = run_scenario(PaperArrivals("medium"), "general")
    assert [r.node for r in a.records] == [r.node for r in b.records]
    assert a.energy_kj("topsis") == b.energy_kj("topsis")


# --- energy conservation: timeline vs legacy decomposition -------------------
def _legacy_energy_kj(records, scheduler):
    """The pre-refactor SimResult.energy_kj: per-pod dynamic energy + idle
    power x union busy time per node (verbatim legacy arithmetic)."""
    dyn = sum(r.energy_j for r in records if r.pod.scheduler == scheduler)
    idle, by_node, classes = 0.0, {}, {}
    for r in records:
        if r.pod.scheduler == scheduler:
            by_node.setdefault(r.node, []).append(
                (r.start_s, r.start_s + r.runtime_s))
            classes[r.node] = r.node_class
    for node, ivs in by_node.items():
        idle += (NODE_ENERGY_PROFILES[classes[node]]["idle_power"]
                 * union_length(ivs))
    return (dyn + idle) / 1000.0


@pytest.mark.parametrize("level", ["low", "medium", "high"])
def test_timeline_reproduces_legacy_decomposition(level):
    """Timeline idle+dynamic accounting == legacy union-of-intervals
    decomposition, exactly (1e-9), in paper mode."""
    res = run_experiment(level, "energy_centric")
    for scheduler in ("topsis", "default"):
        legacy = _legacy_energy_kj(res.records, scheduler)
        assert abs(res.energy_kj(scheduler) - legacy) < 1e-9
        # the decomposition itself also matches term by term
        dyn = sum(r.energy_j for r in res.records
                  if r.pod.scheduler == scheduler)
        assert abs(res.timeline.dynamic_energy_j(scheduler) - dyn) < 1e-9


def test_energy_series_integrates_to_scalar_total():
    res = run_experiment("medium", "energy_centric")
    for scheduler in ("topsis", "default", None):
        edges, joules = res.energy_series(scheduler)
        want = (res.timeline.dynamic_energy_j(scheduler)
                + res.timeline.idle_energy_j(scheduler))
        assert abs(joules[-1] - want) < 1e-6 * max(want, 1.0)
        assert np.all(np.diff(joules) >= -1e-9)          # cumulative
        assert np.all(np.diff(edges) > 0)
        _, watts = res.power_series(scheduler)
        assert len(watts) == len(edges) - 1
        assert np.all(watts >= -1e-9)


def test_dynamic_energy_invariant_across_arrival_processes():
    """Identical placements => identical dynamic energy, regardless of the
    arrival process that produced them: replaying the paper stream as a
    t=0 trace gives the same placements and the same dynamic energy sum."""
    for level in ("low", "medium"):
        trace = TraceArrivals([
            {"t": 0.0, "kind": p.workload.kind, "scheduler": p.scheduler}
            for p in make_pods(level)])
        a = run_experiment(level, "energy_centric")
        b = run_scenario(trace, "energy_centric")
        assert [r.node for r in a.records] == [r.node for r in b.records]
        for scheduler in ("topsis", "default"):
            assert (a.timeline.dynamic_energy_j(scheduler)
                    == b.timeline.dynamic_energy_j(scheduler))


# --- interval helpers --------------------------------------------------------
def test_merge_intervals_and_union_length():
    ivs = [(5.0, 7.0), (0.0, 2.0), (1.0, 3.0), (6.5, 6.6)]
    assert merge_intervals(ivs) == [(0.0, 3.0), (5.0, 7.0)]
    assert union_length(ivs) == 5.0
    assert union_length([]) == 0.0
    assert merge_intervals([]) == []


def test_power_timeline_direct():
    tl = PowerTimeline()
    tl.add("n0", "A", "topsis", 0.0, 10.0, 3.0)
    tl.add("n0", "A", "topsis", 5.0, 10.0, 2.0)   # overlaps -> one idle span
    idle = NODE_ENERGY_PROFILES["A"]["idle_power"]
    assert tl.dynamic_energy_j("topsis") == 3.0 * 10 + 2.0 * 10
    assert abs(tl.idle_energy_j("topsis") - idle * 15.0) < 1e-12
    edges, watts = tl.power_series("topsis")
    np.testing.assert_allclose(edges, [0.0, 5.0, 10.0, 15.0])
    np.testing.assert_allclose(watts, [3.0 + idle, 5.0 + idle, 2.0 + idle])
    assert tl.energy_kj("default") == 0.0


def test_ledger_totals_are_the_commit_order_fold():
    """Totals add the segments left to right in commit order: 1e16 + 1.0
    rounds back to 1e16, so the fold gives 0.0. A compensated sum (the
    built-in ``sum()`` of floats since Python 3.12) would give 1.0."""
    tl = PowerTimeline()
    for power in (1e16, 1.0, -1e16):
        tl.add("n0", "A", "topsis", 0.0, 1.0, power)
    assert [s.energy_j for s in tl.segments] == [1e16, 1.0, -1e16]
    assert tl.dynamic_energy_j() == 0.0


# --- Poisson scenarios -------------------------------------------------------
def test_poisson_scenario_end_to_end():
    """Poisson bursts on a mixed fleet: every pod accounted for, placements
    deterministic under the seed, no overcommit, energy invariants hold."""
    make_run = lambda: run_scenario(
        PoissonArrivals(rate_per_s=0.5, n_bursts=5, burst_size=4, seed=7),
        "energy_centric",
        cluster_factory=lambda: make_scenario_cluster("mixed", 16, seed=2),
        batch=True, batch_backend="numpy")
    res, res2 = make_run(), make_run()
    arrivals = PoissonArrivals(rate_per_s=0.5, n_bursts=5, burst_size=4,
                               seed=7)
    assert len(res.records) + res.unschedulable == arrivals.total_pods()
    assert res.unschedulable == 0
    # deterministic replay
    assert [r.node for r in res.records] == [r.node for r in res2.records]
    assert res.energy_kj("topsis") == res2.energy_kj("topsis")
    # starts at/after the pod's burst arrival
    arrival_t = {p.uid: t for t, pods in arrivals.events() for p in pods}
    for r in res.records:
        assert r.start_s >= arrival_t[r.pod.uid] - 1e-12
    # dynamic energy conserves: equals per-record sum, independent of timing
    for scheduler in ("topsis", "default"):
        dyn = sum(r.energy_j for r in res.records
                  if r.pod.scheduler == scheduler)
        assert abs(res.timeline.dynamic_energy_j(scheduler) - dyn) < 1e-9
    edges, joules = res.energy_series()
    assert np.all(np.diff(joules) >= -1e-9) and joules[-1] > 0


def test_poisson_events_sorted_and_seeded():
    a = PoissonArrivals(rate_per_s=1.0, n_bursts=8, burst_size=3, seed=1)
    evs = a.events()
    ts = [t for t, _ in evs]
    assert ts == sorted(ts) and len(evs) == 8
    assert all(len(pods) == 3 for _, pods in evs)
    uids = [p.uid for _, pods in evs for p in pods]
    assert len(set(uids)) == len(uids)                # unique across bursts
    assert [t for t, _ in a.events()] == ts           # regeneration is stable
    b = PoissonArrivals(rate_per_s=1.0, n_bursts=8, burst_size=3, seed=2)
    assert [t for t, _ in b.events()] != ts


def test_scenario_unschedulable_counted():
    """Pods that can never fit are counted once the cluster drains."""
    res = run_scenario(
        TraceArrivals([{"t": 0.0, "kind": "complex", "scheduler": "topsis",
                        "count": 1}]),
        "energy_centric",
        cluster_factory=lambda: [Node("tiny", "A", 0.1, 0.1)])
    assert res.unschedulable == 1 and not res.records
    assert res.unschedulable_rate() == 1.0


# --- trace scenarios ---------------------------------------------------------
def test_trace_from_file_replays(tmp_path):
    entries = [
        {"t": 0.0, "kind": "complex", "scheduler": "topsis", "count": 2},
        {"t": 40.0, "kind": "light", "scheduler": "default", "count": 3},
        {"t": 40.0, "kind": "medium", "scheduler": "topsis"},
    ]
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(entries))
    run = lambda arr: run_scenario(arr, "energy_centric")
    a = run(TraceArrivals.from_file(str(path)))
    b = run(TraceArrivals(entries))
    assert [r.node for r in a.records] == [r.node for r in b.records]
    assert a.energy_kj("topsis") == b.energy_kj("topsis")
    assert len(a.records) == 6 and a.unschedulable == 0
    # the second burst starts at its trace time, not at t=0
    late = [r for r in a.records if r.pod.workload.kind != "complex"]
    assert all(r.start_s >= 40.0 for r in late)
    # time-resolved series spans both bursts
    edges, _ = a.energy_series()
    assert edges[0] == 0.0 and edges[-1] > 40.0


def test_trace_validates_entries():
    for bad in (
        [{"t": 0.0, "kind": "nope"}],                          # unknown kind
        [{"t": 0.0}],                                          # missing kind
        [{"t": 0.0, "kind": "light", "scheduler": "huh"}],
        [{"kind": "light"}],                                   # missing t
        [{"t": -1.0, "kind": "light"}],
        [{"t": float("nan"), "kind": "light"}],
        [{"t": float("inf"), "kind": "light"}],
        [{"t": "soon", "kind": "light"}],                      # non-numeric t
        [{"t": 0.0, "kind": "light", "count": 0}],             # non-positive
        [{"t": 0.0, "kind": "light", "count": -3}],
        [{"t": 0.0, "kind": "light", "count": 1.5}],           # non-integer
        [{"t": 0.0, "kind": "light", "count": "two"}],
        [{"t": 0.0, "kind": "light", "deadline_s": 0.0}],
        [{"t": 0.0, "kind": "light", "deadline_s": float("inf")}],
        ["not-a-dict"],
    ):
        with pytest.raises(ValueError):
            TraceArrivals(bad)
    # the error message names the offending entry and field
    with pytest.raises(ValueError, match="count.*positive integer"):
        TraceArrivals([{"t": 0.0, "kind": "light", "count": 0}])
    with pytest.raises(ValueError, match="unknown workload kind"):
        TraceArrivals([{"t": 0.0, "kind": "nope"}])
    # valid deferral fields round-trip into pods
    arr = TraceArrivals([{"t": 0.0, "kind": "light", "count": 2,
                          "deferrable": True, "deadline_s": 120.0}])
    (_, pods), = arr.events()
    assert len(pods) == 2
    assert all(p.deferrable and p.deadline_s == 120.0 for p in pods)


def test_arrival_exactly_at_completion_sees_freed_capacity():
    """A burst arriving at exactly a completion's end time schedules against
    the freed resources ([start, end) semantics): on a one-pod node the
    second pod starts at the tie instant instead of deferring."""
    one_node = lambda: [Node("solo", "B", vcpus=1.2, mem_gb=2.5)]
    first = run_scenario(
        TraceArrivals([{"t": 0.0, "kind": "complex", "scheduler": "topsis"}]),
        "energy_centric", cluster_factory=one_node)
    end_t = first.records[0].start_s + first.records[0].runtime_s
    res = run_scenario(
        TraceArrivals([
            {"t": 0.0, "kind": "complex", "scheduler": "topsis"},
            {"t": end_t, "kind": "complex", "scheduler": "topsis"}]),
        "energy_centric", cluster_factory=one_node)
    assert res.unschedulable == 0 and len(res.records) == 2
    assert res.records[1].start_s == end_t


# --- scenario fleets ---------------------------------------------------------
def test_make_scenario_cluster_profiles():
    for profile, mix in SCENARIO_PROFILES.items():
        nodes = make_scenario_cluster(profile, 512, seed=0)
        assert len(nodes) == 512
        # first four nodes: one per class (heterogeneity floor)
        assert [n.node_class for n in nodes[:4]] == list(mix)
        counts = {}
        for n in nodes:
            counts[n.node_class] = counts.get(n.node_class, 0) + 1
        if profile != "mixed":      # uniform mix has no dominant class
            dominant = max(mix, key=mix.get)
            assert counts[dominant] == max(counts.values())
    # deterministic in seed
    a = make_scenario_cluster("edge_heavy", 64, seed=3)
    b = make_scenario_cluster("edge_heavy", 64, seed=3)
    assert [(n.name, n.node_class, n.vcpus) for n in a] == \
           [(n.name, n.node_class, n.vcpus) for n in b]
    with pytest.raises(ValueError):
        make_scenario_cluster("nope", 8)
    with pytest.raises(ValueError):
        make_scenario_cluster("mixed", 2)


def test_scenario_batch_backends_agree():
    """numpy and jax batched backends place Poisson scenarios identically
    (the engine's burst path is backend-invariant)."""
    runs = {}
    for backend in ("numpy", "jax"):
        runs[backend] = run_scenario(
            PoissonArrivals(rate_per_s=0.3, n_bursts=4, burst_size=6, seed=5),
            "energy_centric",
            cluster_factory=lambda: make_scenario_cluster("cloud_heavy", 32,
                                                          seed=4),
            batch=True, batch_backend=backend)
    assert ([r.node for r in runs["numpy"].records]
            == [r.node for r in runs["jax"].records])
    assert abs(runs["numpy"].energy_kj("topsis")
               - runs["jax"].energy_kj("topsis")) < 1e-9

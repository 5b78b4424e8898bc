"""The registry's layer spans inside a scheduling round: the modes a
registry is built with, the parent and round links of its span log, its
per-name totals, the profiler annotations it writes, and what the
disabled default does not do."""
import glob
import os

import jax
import pytest

from engine_golden_spec import SCENARIOS, arrivals, fleet, run_cell
from repro.cluster.simulator import run_scenario
from repro.core import telemetry
from repro.core.telemetry import Telemetry

ROUND_LAYERS = ("scheduler_sync", "scheduler_mask", "scheduler_upload",
                "scheduler_dispatch", "scheduler_readback",
                "scheduler_argsort", "scheduler_walk")
NEW_SPANS = ROUND_LAYERS + ("engine_round", "engine_commit")


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.disable()
    yield
    telemetry.disable()


def _outcome(res) -> tuple:
    return ([(r.node, r.pod.uid, r.start_s, r.runtime_s, r.energy_j,
              r.arrival_s) for r in res.records],
            res.events, res.unschedulable, res.preemptions, res.migrations,
            res.wakes, res.sleeps, res.energy_kj("topsis"),
            res.energy_kj("default"), res.fleet_energy_kj())


def _policy_free_jax_run():
    return run_scenario(arrivals(False), "energy_centric",
                        cluster_factory=fleet(), batch=True,
                        batch_backend="jax")


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_registry_without_timelines_reproduces_bitwise(name, backend):
    """Every cell of the golden matrix runs to the same outcome, bit for
    bit, with a timeline-free registry on as with none, and the registry
    holds spans and counters but no series and no energy rollups."""
    plain = run_cell(name, backend)
    with telemetry.enabled(Telemetry(timelines=False)) as tel:
        res = run_cell(name, backend)
    assert _outcome(res) == _outcome(plain)
    assert tel.timeseries == {}
    assert {g.name for g in tel.gauges.values()} == {"engine_unschedulable"}
    assert tel.counter_value("engine_events", kind="arrival") > 0
    names = {s["name"] for s in tel.spans}
    assert {"engine_round", "engine_commit", "scheduler_batch",
            "scheduler_sync", "scheduler_mask", "scheduler_argsort",
            "scheduler_walk"} <= names
    if SCENARIOS[name]["carbon"] or SCENARIOS[name]["autoscale"]:
        assert "engine_policies" in names


def test_round_spans_link_parents_and_rounds():
    with telemetry.enabled(Telemetry(timelines=False)) as tel:
        res = _policy_free_jax_run()
    log = tel.spans
    names = {s["name"] for s in log}
    assert set(NEW_SPANS) <= names
    assert "engine_policies" not in names          # no policies, no hooks
    parent_name = lambda s: (log[s["parent"]]["name"]
                             if s["parent"] is not None else None)
    batches = [i for i, s in enumerate(log) if s["name"] == "scheduler_batch"]
    assert [log[i]["round"] for i in batches] == list(
        range(1, len(batches) + 1))
    for i, s in enumerate(log):
        if s["name"] in ROUND_LAYERS:
            # every layer of a round sits inside that round's batch
            batch = log[s["parent"]]
            assert batch["name"] == "scheduler_batch"
            assert s["round"] == batch["round"]
            assert s["depth"] == batch["depth"] + 1
        elif s["name"] == "scheduler_batch":
            assert parent_name(s) == "engine_round"
        elif s["name"] == "engine_commit":
            assert parent_name(s) == "engine_round"
            assert s["round"] == log[s["parent"]]["round"]
        elif s["name"] == "engine_round":
            assert s["parent"] is None
        if s["parent"] is not None:
            assert s["parent"] > i                  # parents end later
    # a child's time lies inside its parent's
    for s in log:
        if s["parent"] is not None:
            p = log[s["parent"]]
            assert p["start_s"] <= s["start_s"]
            assert (s["start_s"] + s["duration_s"]
                    <= p["start_s"] + p["duration_s"] + 1e-9)
    totals = tel.span_totals()
    for child, parent in (("scheduler_sync", "scheduler_batch"),
                          ("scheduler_argsort", "scheduler_batch"),
                          ("scheduler_batch", "engine_round"),
                          ("engine_commit", "engine_round")):
        assert totals[child]["total_s"] <= totals[parent]["total_s"]
    assert (sum(totals[n]["total_s"] for n in ROUND_LAYERS)
            <= totals["scheduler_batch"]["total_s"])
    for t in totals.values():
        assert 0.0 <= t["self_s"] <= t["total_s"] + 1e-12
    placed = sum(1 for r in res.records if r.pod.scheduler == "topsis")
    assert tel.counter_value("engine_commits", scheduler="topsis") == placed
    assert tel.counter_value("scheduler_pods_scored") >= placed
    assert tel.counter_value("scheduler_upload_bytes") > 0
    assert tel.counter_value("scheduler_readback_bytes") > 0


def test_policy_free_run_opens_rounds_only_to_score():
    """Without policies an iteration that only releases a completion,
    with nothing pending, opens no ``engine_round``: each one scores."""
    with telemetry.enabled(Telemetry(timelines=False)) as tel:
        res = _policy_free_jax_run()
    rounds = [i for i, s in enumerate(tel.spans) if s["name"] == "engine_round"]
    scored = {s["parent"] for s in tel.spans if s["name"] == "scheduler_batch"}
    assert set(rounds) == scored
    completions = sum(1 for e in res.events if e[1] == "completion")
    assert len(rounds) < completions


def test_span_totals_self_time_on_a_hand_built_nest():
    tel = Telemetry()
    # outer(10) > [a(3) > b(1)], c(2); then a lone c(4)
    tel.spans = [
        {"name": "b", "duration_s": 1.0, "parent": 1},
        {"name": "a", "duration_s": 3.0, "parent": 3},
        {"name": "c", "duration_s": 2.0, "parent": 3},
        {"name": "outer", "duration_s": 10.0, "parent": None},
        {"name": "c", "duration_s": 4.0, "parent": None},
    ]
    assert tel.span_totals() == {
        "b": {"count": 1, "total_s": 1.0, "self_s": 1.0},
        "a": {"count": 1, "total_s": 3.0, "self_s": 2.0},
        "c": {"count": 2, "total_s": 6.0, "self_s": 6.0},
        "outer": {"count": 1, "total_s": 10.0, "self_s": 5.0},
    }


def test_live_nest_links_and_round_counter():
    tel = Telemetry()
    with tel.span("scheduler_batch"):
        with tel.stage("scheduler_sync"):
            pass
        with tel.stage("scheduler_walk"):
            pass
    with tel.stage("engine_commit"):
        pass
    with tel.span("scheduler_grid"):
        pass
    assert [(s["name"], s["parent"], s["round"]) for s in tel.spans] == [
        ("scheduler_sync", 2, 1), ("scheduler_walk", 2, 1),
        ("scheduler_batch", None, 1), ("engine_commit", None, 1),
        ("scheduler_grid", None, 2)]
    t = tel.span_totals()["scheduler_batch"]
    assert t["self_s"] == pytest.approx(
        tel.spans[2]["duration_s"] - tel.spans[0]["duration_s"]
        - tel.spans[1]["duration_s"])


def test_device_trace_spans_reach_the_profilers_host_plane(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.enabled(Telemetry(timelines=False,
                                         device_trace=True)):
            _policy_free_jax_run()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    data = jax.profiler.ProfileData.from_file(paths[0])
    seen = {ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}
    assert set(NEW_SPANS) | {"scheduler_batch"} <= seen


def test_disabled_default_records_nothing_and_annotates_nothing(monkeypatch):
    opened = []

    class Counting:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    null = telemetry.active()
    assert null is telemetry.NULL and not null.timelines
    assert null.stage("scheduler_sync", backend="jax") is null.stage("x")
    res = _policy_free_jax_run()
    assert res.records
    assert opened == []
    assert not hasattr(null, "spans") and not hasattr(null, "counters")
    # a live registry without device_trace opens none either; one with it
    # opens one per recorded span
    with telemetry.enabled(Telemetry(timelines=False)):
        _policy_free_jax_run()
    assert opened == []
    with telemetry.enabled(Telemetry(timelines=False,
                                     device_trace=True)) as tel:
        _policy_free_jax_run()
    assert len(opened) == len(tel.spans) > 0


def _pressure_run():
    """A 20-node fleet whose idle nodes fall asleep between two bursts, so
    that the second burst's unplaced pods wake nodes: one probe for a node
    per pod that no node woken earlier in the pass covers."""
    from repro.cluster.node import make_scenario_cluster
    from repro.cluster.workload import WORKLOADS, ArrivalProcess, Pod
    from repro.core.elastic import AutoscalePolicy

    class TwoBursts(ArrivalProcess):
        def events(self):
            kinds = list(WORKLOADS.values())
            return [(1.0, [Pod(i, kinds[i % 3], "topsis")
                           for i in range(6)]),
                    (400.0, [Pod(100 + i, WORKLOADS["complex"], "topsis")
                             for i in range(24)])]

    return run_scenario(TwoBursts(), "energy_centric",
                        cluster_factory=lambda: make_scenario_cluster(
                            "mixed", 20, seed=3),
                        batch=True, batch_backend="jax",
                        autoscale=AutoscalePolicy(idle_timeout_s=30.0,
                                                  min_awake=2,
                                                  wake_on_pressure=True))


def test_wake_probes_are_spans_inside_their_pass(monkeypatch):
    """Each probe of the pressure pass for a node to wake is one
    ``policy_wake_probe`` span and one ``policy_wake_probes`` count, inside
    the pass's ``policy_wake_pass`` span; without a registry nothing is
    recorded and the run places the same."""
    from repro.core import elastic
    calls = []
    best = elastic._best_node

    def counted(*a, **kw):
        calls.append(1)
        return best(*a, **kw)

    monkeypatch.setattr(elastic, "_best_node", counted)
    with telemetry.enabled(Telemetry(timelines=False)) as tel:
        res = _pressure_run()
    log = tel.spans
    probes = [s for s in log if s["name"] == "policy_wake_probe"]
    passes = [i for i, s in enumerate(log) if s["name"] == "policy_wake_pass"]
    assert res.wakes > 0 and len(probes) >= res.wakes
    assert tel.counter_value("policy_wake_probes",
                             policy="AutoscaleScheduling") \
        == len(probes) == len(calls)
    for s in probes:
        assert s["parent"] in passes
    for i in passes:
        parent = log[i]["parent"]
        assert log[parent]["name"] == "engine_policies"
        assert log[parent]["labels"]["hook"] == "round_end"

    n_calls = len(calls)
    assert telemetry.active() is telemetry.NULL
    plain = _pressure_run()
    assert _outcome(plain) == _outcome(res)
    assert len(calls) == 2 * n_calls
    assert not hasattr(telemetry.NULL, "spans")

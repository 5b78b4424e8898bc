"""Elastic fleet subsystem: power-state lifecycle, autoscale policies,
TOPSIS-driven consolidation, and state-ledger energy/carbon accounting.

The backbone invariant mirrors the carbon subsystem's: with the policy
disabled (``autoscale=None``) the engine's output is *bitwise* identical to
the policy-free engine — same placements, same energy totals, empty state
ledger, and ``table6()`` still reproduces the recorded golden exactly —
pinned by a hypothesis property test across all three backends. Elasticity
only changes behaviour when a policy is attached.
"""
import json
import math
import os

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    def settings(*args, **kwargs):
        def wrap(f):
            return f
        return wrap

    def given(*args, **kwargs):
        def wrap(f):
            def skipped():
                pytest.skip("hypothesis not installed "
                            "(pip install -r requirements-dev.txt)")
            skipped.__name__ = f.__name__
            skipped.__doc__ = f.__doc__
            return skipped
        return wrap

    class _AnyStrategy:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _AnyStrategy()

from repro.core import telemetry
from repro.core.carbon import CarbonPolicy, ConstantCarbon, TraceCarbon
from repro.core.elastic import (ACTIVE, ASLEEP, IDLE, WAKING,
                                AutoscalePolicy, ElasticFleet,
                                NODE_WAKE_PROFILES)
from repro.core.energy import NODE_ENERGY_PROFILES, PowerTimeline
from repro.core.scheduler import (BatchScheduler, DefaultK8sScheduler,
                                  GreenPodScheduler)
from repro.core.telemetry import Telemetry
from repro.cluster.engine import RunningTask
from repro.cluster.node import (FleetState, Node, NodeTable,
                                make_scenario_cluster)
from repro.cluster.simulator import run_scenario, table6
from repro.cluster.workload import (WORKLOADS, Pod, PoissonArrivals,
                                    TraceArrivals)

GOLDEN = json.load(open(os.path.join(os.path.dirname(__file__),
                                     "golden_table6.json")))


# --- policy & profiles -------------------------------------------------------
def test_autoscale_policy_validation():
    AutoscalePolicy()                                      # defaults valid
    AutoscalePolicy(idle_timeout_s=math.inf)               # always-on fleet
    for bad in (dict(idle_timeout_s=0.0), dict(idle_timeout_s=-5.0),
                dict(idle_timeout_s=math.nan),
                dict(consolidate_interval_s=0.0),
                dict(consolidate_interval_s=-1.0),
                dict(consolidate_util_below=1.5),
                dict(consolidate_util_below=-0.1),
                dict(min_awake=-1)):
        with pytest.raises(ValueError):
            AutoscalePolicy(**bad)


def test_wake_profiles_sane():
    """Every node class has a positive wake latency, a sleep draw well
    below idle, and a positive wake surge."""
    for cls, prof in NODE_WAKE_PROFILES.items():
        idle = NODE_ENERGY_PROFILES[cls]["idle_power"]
        assert prof["wake_latency_s"] > 0.0
        assert 0.0 < prof["sleep_power_w"] < idle
        assert prof["wake_energy_j"] > 0.0


def test_power_state_column_feeds_awake():
    """A real power-state column overrides the static used_cpu derivation:
    IDLE/WAKING/ACTIVE nodes are awake (zero marginal idle cost), ASLEEP
    nodes are not; None entries keep the legacy rule."""
    nodes = [Node("n0", "A", 2, 4), Node("n1", "B", 2, 8),
             Node("n2", "C", 4, 16), Node("n3", "B", 2, 8)]
    nodes[3].bind(0.5, 1.0)
    table = NodeTable.from_nodes(nodes)
    np.testing.assert_array_equal(table.awake, [False, False, False, True])
    nodes[0].power_state = IDLE
    nodes[1].power_state = ASLEEP
    nodes[2].power_state = WAKING
    table = NodeTable.from_nodes(nodes)      # n3 stays on the legacy rule
    np.testing.assert_array_equal(table.awake, [True, False, True, True])
    nodes[3].power_state = ACTIVE
    np.testing.assert_array_equal(NodeTable.from_nodes(nodes).awake,
                                  [True, False, True, True])


# --- scheduler exclude masks -------------------------------------------------
def test_select_exclude_masks_nodes():
    nodes = [Node("a-0", "A", 4, 16), Node("b-0", "B", 4, 16),
             Node("c-0", "C", 8, 32)]
    table = NodeTable.from_nodes(nodes)
    pod = Pod(0, WORKLOADS["medium"], "topsis")
    for sched in (GreenPodScheduler("energy_centric"), DefaultK8sScheduler()):
        base, _ = sched.select(pod, table)
        ex = np.zeros(3, dtype=bool)
        ex[base] = True
        alt, _ = sched.select(pod, table, exclude=ex)
        assert alt is not None and alt != base
        none, diag = sched.select(pod, table, exclude=np.ones(3, bool))
        assert none is None and diag["reason"] == "unschedulable"


def test_select_many_exclude_row_and_matrix():
    nodes = [Node("a-0", "A", 4, 16), Node("b-0", "B", 4, 16),
             Node("c-0", "C", 8, 32)]
    table = NodeTable.from_nodes(nodes)
    pods = [Pod(0, WORKLOADS["light"], "topsis"),
            Pod(1, WORKLOADS["light"], "topsis")]
    sched = BatchScheduler("energy_centric", backend="numpy")
    base, _ = sched.select_many(pods, table)
    # (N,) mask applies to every pod
    ex = np.zeros(3, dtype=bool)
    ex[base[0]] = True
    asn, _ = sched.select_many(pods, table, exclude=ex)
    assert all(a is not None and a != base[0] for a in asn)
    # (P, N) mask applies per pod
    ex2 = np.zeros((2, 3), dtype=bool)
    ex2[1, :] = True
    asn2, _ = sched.select_many(pods, table, exclude=ex2)
    assert asn2[0] == base[0] and asn2[1] is None


# --- disabled policy: bitwise identity (satellite property test) -------------
@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 2 ** 31 - 1),
       profile=st.sampled_from(("mixed", "edge_heavy")))
def test_property_disabled_policy_is_bitwise_inert(seed, profile):
    """autoscale=None ⇒ run_scenario output bitwise identical to the
    policy-free engine on every backend: same placements and start times,
    bitwise-equal energy totals, empty state ledger, zero elastic
    counters."""
    arr = lambda: PoissonArrivals(rate_per_s=0.3, n_bursts=3, burst_size=4,
                                  seed=seed)
    fac = lambda: make_scenario_cluster(profile, 8, seed=seed)
    ref = run_scenario(arr(), "energy_centric", cluster_factory=fac,
                       batch=True, batch_backend="numpy")
    for backend in ("numpy", "jax", "pallas"):
        res = run_scenario(arr(), "energy_centric", cluster_factory=fac,
                           batch=True, batch_backend=backend,
                           autoscale=None)
        assert [r.node for r in res.records] == [r.node for r in ref.records]
        assert ([r.start_s for r in res.records]
                == [r.start_s for r in ref.records])
        for s in ("topsis", "default"):
            assert res.energy_kj(s) == ref.energy_kj(s)
        assert res.unschedulable == ref.unschedulable
        assert not res.timeline.state_intervals
        assert not res.timeline.wake_transitions
        assert res.wakes == res.sleeps == res.migrations == 0
        # with the ledger empty the fleet totals reduce to the legacy ones
        # (compared in kJ, the unit the total is computed in: J / 1000 *
        # 1000 need not give the same double back)
        assert res.fleet_idle_energy_kj() \
            == res.timeline.idle_energy_j(None) / 1000.0


def test_table6_still_matches_golden_bitwise():
    """The elastic stack leaves paper mode untouched: table6() equals the
    recorded pre-refactor golden exactly."""
    t6 = table6()
    for level, d in GOLDEN["table6"].items():
        for scheme, vals in d.items():
            for key, want in vals.items():
                assert t6[level][scheme][key] == want, (level, scheme, key)


# --- always-on accounting ----------------------------------------------------
def test_always_on_policy_accounts_full_fleet_idle():
    """idle_timeout=inf: every node is awake the whole run, so fleet idle
    energy is exactly sum(idle_power) x horizon — the baseline an
    idle-timeout policy is measured against."""
    fac = lambda: make_scenario_cluster("mixed", 8, seed=2)
    res = run_scenario(
        PoissonArrivals(rate_per_s=0.3, n_bursts=3, burst_size=4, seed=5),
        "energy_centric", cluster_factory=fac, batch=True,
        batch_backend="numpy",
        autoscale=AutoscalePolicy(idle_timeout_s=math.inf))
    horizon = max(r.start_s + r.runtime_s for r in res.records)
    want = sum(NODE_ENERGY_PROFILES[n.node_class]["idle_power"]
               for n in fac()) * horizon / 1000.0
    assert abs(res.fleet_idle_energy_kj() - want) < 1e-9 * want
    assert res.sleeps == 0 and res.wakes == 0
    # the state ledger only holds IDLE stretches
    assert res.state_energy_kj(ASLEEP) == 0.0
    assert res.state_energy_kj(WAKING) == 0.0
    assert res.state_energy_kj(IDLE) > 0.0


def test_idle_timeout_cuts_fleet_idle_energy():
    """The acceptance invariant at test scale: an idle-timeout policy
    sleeps empty nodes and measurably cuts fleet idle energy vs the
    always-on baseline; the min_awake floor node never sleeps."""
    arr = lambda: PoissonArrivals(rate_per_s=0.3, n_bursts=3, burst_size=4,
                                  seed=5)
    fac = lambda: make_scenario_cluster("mixed", 8, seed=2)
    run = lambda pol: run_scenario(arr(), "energy_centric",
                                   cluster_factory=fac, batch=True,
                                   batch_backend="numpy", autoscale=pol)
    base = run(AutoscalePolicy(idle_timeout_s=math.inf))
    elastic = run(AutoscalePolicy(idle_timeout_s=20.0, min_awake=1))
    assert elastic.sleeps > 0
    assert elastic.fleet_idle_energy_kj() < base.fleet_idle_energy_kj()
    # the awake floor: node 0 never appears as an ASLEEP interval
    floor = fac()[0].name
    assert all(iv.node != floor
               for iv in elastic.timeline.state_intervals
               if iv.state == ASLEEP)
    # every pod still placed and accounted
    assert elastic.unschedulable == 0
    assert len({r.pod.uid for r in elastic.records}) \
        == len({r.pod.uid for r in base.records})


# --- wake events -------------------------------------------------------------
def _sleepy_cluster():
    return [Node("a-0", "A", 4, 16), Node("b-0", "B", 4, 16)]


def test_pod_arriving_on_sleeping_fleet_starts_after_wake_latency():
    """All nodes asleep: the arrival wakes the TOPSIS-best node and the pod
    starts exactly one wake latency after its arrival."""
    res = run_scenario(
        TraceArrivals([{"t": 100.0, "kind": "light", "scheduler": "topsis"}]),
        "energy_centric", cluster_factory=_sleepy_cluster,
        autoscale=AutoscalePolicy(idle_timeout_s=30.0, min_awake=0))
    assert res.wakes == 1 and res.unschedulable == 0
    r, = res.records
    lat = NODE_WAKE_PROFILES[r.node_class]["wake_latency_s"]
    assert r.start_s == 100.0 + lat
    # the woken node is the TOPSIS-best among the sleeping fleet (not just
    # first-fit): recompute the ranking the wake decision saw
    nodes = _sleepy_cluster()
    for n in nodes:
        n.power_state = ASLEEP
    want, _ = GreenPodScheduler("energy_centric").select(
        Pod(0, WORKLOADS["light"], "topsis"), nodes, now=100.0)
    assert r.node == nodes[want].name
    # the ledger saw the whole lifecycle: idle -> asleep -> waking
    states = {iv.state for iv in res.timeline.state_intervals}
    assert {IDLE, ASLEEP, WAKING} <= states
    assert len(res.timeline.wake_transitions) == 1


def test_pod_arriving_while_chosen_node_is_waking_starts_at_ready():
    """A second pod lands mid-wake on the already-WAKING node: no second
    wake, and both pods start exactly at the wake-completion instant."""
    # which node does the first arrival wake, and how long does it take?
    probe = run_scenario(
        TraceArrivals([{"t": 100.0, "kind": "light", "scheduler": "topsis"}]),
        "energy_centric", cluster_factory=_sleepy_cluster,
        autoscale=AutoscalePolicy(idle_timeout_s=30.0, min_awake=0))
    lat = NODE_WAKE_PROFILES[probe.records[0].node_class]["wake_latency_s"]
    res = run_scenario(
        TraceArrivals([
            {"t": 100.0, "kind": "light", "scheduler": "topsis"},
            {"t": 100.0 + lat / 2.0, "kind": "light", "scheduler": "topsis"},
        ]),
        "energy_centric", cluster_factory=_sleepy_cluster,
        autoscale=AutoscalePolicy(idle_timeout_s=30.0, min_awake=0))
    assert res.unschedulable == 0 and len(res.records) == 2
    first, second = sorted(res.records, key=lambda r: r.arrival_s)
    assert first.node == second.node == probe.records[0].node
    assert first.start_s == second.start_s == 100.0 + lat
    assert res.wakes == 1                      # mid-wake arrival rides along


def test_unschedulable_when_pressure_wake_disabled():
    """wake_on_pressure=False with the whole fleet asleep: the pod can
    never be placed and is counted unschedulable (the engine terminates
    instead of spinning)."""
    res = run_scenario(
        TraceArrivals([{"t": 100.0, "kind": "light", "scheduler": "topsis"}]),
        "energy_centric", cluster_factory=_sleepy_cluster,
        autoscale=AutoscalePolicy(idle_timeout_s=30.0, min_awake=0,
                                  wake_on_pressure=False))
    assert res.unschedulable == 1 and not res.records
    assert res.wakes == 0


# --- consolidation drains ----------------------------------------------------
def test_consolidation_drains_low_util_node_and_preserves_pod_metrics():
    """A low-utilization node is drained at the consolidation tick: its
    task migrates through the preemption machinery (truncated segment +
    requeued full rerun), the node sleeps, and per-pod (not per-attempt)
    metric semantics hold."""
    fac = lambda: [Node("a-0", "A", 4, 16), Node("b-0", "B", 4, 16)]
    res = run_scenario(
        TraceArrivals([{"t": 0.0, "kind": "medium", "scheduler": "topsis"}]),
        "energy_centric", cluster_factory=fac,
        autoscale=AutoscalePolicy(idle_timeout_s=math.inf, min_awake=0,
                                  consolidate_interval_s=10.0,
                                  consolidate_util_below=0.25))
    assert res.migrations == 1
    assert len(res.records) == 2
    first, second = res.records
    assert first.pod.uid == second.pod.uid
    assert second.node != first.node                      # migrated off
    assert first.runtime_s == 10.0                        # truncated at tick
    assert second.start_s == 10.0                         # restarted at once
    # the drained node sleeps immediately (no idle-timeout wait)
    asleep = [iv for iv in res.timeline.state_intervals
              if iv.state == ASLEEP and iv.node == first.node]
    assert asleep and asleep[0].start_s == 10.0
    # per-pod metrics: one pod, both attempts summed, energy counted once
    assert res.mean_exec_time_s("topsis") \
        == first.runtime_s + second.runtime_s
    n_pods = len({r.pod.uid for r in res.records})
    assert n_pods == 1
    assert res.mean_energy_kj("topsis") == res.energy_kj("topsis")
    # timeline dynamic energy equals the split segments' sum
    segs = res.timeline.segments
    assert len(segs) == 2 and segs[0].runtime_s == 10.0
    assert abs(res.timeline.dynamic_energy_j("topsis")
               - (segs[0].energy_j + segs[1].energy_j)) < 1e-12


def test_drain_skipped_when_victims_fit_nowhere_awake():
    """A drain candidate whose tasks only fit on sleeping capacity is left
    alone — consolidation never strands a task (or forces it through a
    wake latency)."""
    fac = lambda: [Node("a-0", "A", 4, 16),
                   Node("b-tiny", "B", 0.4, 0.8)]     # cannot host medium
    res = run_scenario(
        TraceArrivals([{"t": 0.0, "kind": "medium", "scheduler": "topsis"}]),
        "energy_centric", cluster_factory=fac,
        autoscale=AutoscalePolicy(idle_timeout_s=math.inf, min_awake=0,
                                  consolidate_interval_s=10.0,
                                  consolidate_util_below=0.25))
    assert res.migrations == 0
    assert len(res.records) == 1              # ran to completion in place
    assert res.records[0].runtime_s > 70.0


def test_multi_victim_drain_requires_order_independent_fit_for_deferrable():
    """The TOPSIS round re-places drain victims by score, not by the
    eligibility ledger's first-fit order — so a deferrable victim is only
    drained when it fits on some awake node even if every other victim of
    the pass landed there first. First-fit alone passing is not enough."""
    med = Pod(0, WORKLOADS["medium"], "topsis", deferrable=True,
              deadline_s=100.0)
    comp = Pod(1, WORKLOADS["complex"], "topsis")

    def drain_pass(y_caps):
        nodes = [Node("x", "B", 1.0, 2.0), Node("y", "B", *y_caps),
                 Node("z", "B", 4.0, 8.0)]
        fleet = ElasticFleet(
            nodes, AutoscalePolicy(idle_timeout_s=math.inf, min_awake=0,
                                   consolidate_interval_s=10.0,
                                   consolidate_util_below=0.9),
            PowerTimeline())
        for pod in (med, comp):
            fleet.on_commit(2, 0.0)
            nodes[2].bind(pod.cpu, pod.mem)
        running = [RunningTask(50.0, med.uid, med, 2, 0, 0),
                   RunningTask(60.0, comp.uid, comp, 2, 1, 1)]
        return fleet.consolidation_victims(5.0, running,
                                           lambda p: p.deadline_s)
    # roomy y: the deferrable victim fits y even after the complex victim
    # is charged there too -> whole node drained
    drained, victims = drain_pass((1.6, 3.2))
    assert drained == [2] and len(victims) == 2
    # tight y: first-fit packs (medium -> x, complex -> y) so the naive
    # proof passes, but a score-ordered round could take y first and
    # strand the deferrable victim -> the node must not be drained
    drained, victims = drain_pass((1.2, 2.4))
    assert drained == [] and victims == []


def test_drain_colliding_with_deferral_deadline_never_starts_pod_late():
    """Drains interact correctly with carbon deferral deadlines: a drained
    deferrable pod that re-defers (the signal spiked) is started exactly at
    its deadline, never past it; and once the deadline has passed the task
    is not drained at all."""
    sig = TraceCarbon([{"t": 0.0, "intensity": 100.0},
                       {"t": 15.0, "intensity": 500.0}])
    fac = lambda: [Node("a-0", "A", 4, 16), Node("b-0", "B", 4, 16)]
    pol = lambda interval: AutoscalePolicy(idle_timeout_s=math.inf,
                                           min_awake=0,
                                           consolidate_interval_s=interval,
                                           consolidate_util_below=0.25)
    trace = lambda ddl: TraceArrivals([
        {"t": 0.0, "kind": "medium", "scheduler": "topsis",
         "deferrable": True, "deadline_s": ddl}])
    carbon = CarbonPolicy(sig, defer_threshold=300.0, check_interval_s=7.0)
    # signal is low at t=0 (pod starts immediately), spikes at 15; the
    # drain at t=20 requeues the pod, deferral holds it, and it starts
    # exactly at its deadline (t=60) on the other node
    res = run_scenario(trace(60.0), "energy_centric", cluster_factory=fac,
                       carbon=carbon, autoscale=pol(20.0))
    assert res.migrations == 1 and res.unschedulable == 0
    first, second = res.records
    assert first.start_s == 0.0 and first.runtime_s == 20.0
    assert second.start_s == 60.0             # == deadline, never past
    assert second.node != first.node
    # deadline already passed at the drain tick: the task is left running
    res2 = run_scenario(trace(15.0), "energy_centric", cluster_factory=fac,
                        carbon=carbon, autoscale=pol(20.0))
    assert res2.migrations == 0 and len(res2.records) == 1
    assert res2.records[0].start_s == 0.0


def test_preempting_pod_on_waking_node_clamps_to_zero_runtime():
    """Carbon preemption can hit a pod committed to a still-WAKING node
    (its start lies in the future): the partial attempt clamps to zero
    runtime/energy instead of going negative, and the pod reruns in
    full."""
    sig = TraceCarbon([{"t": 0.0, "intensity": 100.0},
                       {"t": 106.5, "intensity": 900.0}])
    fac = lambda: [Node("c-0", "C", 4, 16)]
    res = run_scenario(
        TraceArrivals([
            {"t": 100.0, "kind": "medium", "scheduler": "topsis",
             "deferrable": True, "deadline_s": 600.0},
            # the 107.0 round commits the deferrable pod onto the WAKING
            # node (ready at 108); the 107.5 round preempts it before the
            # wake completes — its start still lies in the future
            {"t": 107.0, "kind": "light", "scheduler": "default"},
            {"t": 107.5, "kind": "light", "scheduler": "default"},
        ]),
        "energy_centric", cluster_factory=fac,
        carbon=CarbonPolicy(sig, defer_threshold=1000.0,
                            preempt_threshold=400.0, check_interval_s=50.0),
        autoscale=AutoscalePolicy(idle_timeout_s=30.0, min_awake=0))
    assert res.preemptions == 1 and res.unschedulable == 0
    lat = NODE_WAKE_PROFILES["C"]["wake_latency_s"]
    attempts = [r for r in res.records if r.pod.deferrable]
    assert len(attempts) == 2
    first, rerun = attempts
    # evicted at t=107, before its wake-delayed start at 108: zero, not -1
    assert first.start_s == 100.0 + lat
    assert first.runtime_s == 0.0 and first.energy_j == 0.0
    assert rerun.start_s == 100.0 + lat and rerun.runtime_s > 0.0
    assert all(s.runtime_s >= 0.0 for s in res.timeline.segments)
    assert all(r.runtime_s >= 0.0 and r.energy_j >= 0.0
               for r in res.records)


def test_waking_node_excluded_for_deadline_late_deferrable_pod():
    """The commit guard: a WAKING node whose ready time lies past a
    deferrable pod's deadline is masked out of its scoring validity."""
    nodes = [Node("a-0", "A", 4, 16), Node("b-0", "B", 4, 16)]
    policy = AutoscalePolicy(idle_timeout_s=30.0, min_awake=0)
    fleet = ElasticFleet(nodes, policy, PowerTimeline())
    fleet.request_wake(0, 100.0)               # ready at 102
    base = fleet.exclude_mask(100.0)
    late = fleet.exclude_for_deadline(base, deadline=101.0)
    ok = fleet.exclude_for_deadline(base, deadline=102.0)   # ready == ddl
    assert late[0] and not ok[0]


# --- the state machine against a per-node reference -------------------------
class _PerNodeFleet:
    """The power-state rules node by node, on plain lists: the reference
    ``ElasticFleet``'s vectorised state pass is held to."""

    def __init__(self, nodes, policy, timeline):
        self.nodes, self.policy, self.timeline = nodes, policy, timeline
        n = len(nodes)
        self.running = [0] * n
        self.idle_since = [0.0] * n
        self.sleep_at = [None] * n
        self.wake_started = [None] * n
        self.wake_ready = [None] * n

    def sleep_due(self, i):
        if self.idle_since[i] is None:
            return math.inf
        if self.sleep_at[i] is not None:
            return self.sleep_at[i]
        if i < self.policy.min_awake:
            return math.inf
        return self.idle_since[i] + self.policy.idle_timeout_s

    def state(self, i, t):
        if self.wake_ready[i] is not None:
            return WAKING
        if self.running[i] > 0:
            return ACTIVE
        return ASLEEP if t >= self.sleep_due(i) else IDLE

    def states(self, t):
        return [self.state(i, t) for i in range(len(self.nodes))]

    def next_transition(self, t):
        later = [r for r in self.wake_ready if r is not None and r > t]
        return min(later) if later else None

    def exclude_for_deadline(self, base, deadline):
        return [b or (r is not None and r > deadline)
                for b, r in zip(base, self.wake_ready)]

    def flush_idle(self, i, upto):
        since = self.idle_since[i]
        if since is None:
            return
        node, due = self.nodes[i], self.sleep_due(i)
        self.timeline.add_state(
            node.name, node.node_class, IDLE, since, min(upto, due),
            NODE_ENERGY_PROFILES[node.node_class]["idle_power"])
        if upto > due:
            self.timeline.add_state(
                node.name, node.node_class, ASLEEP, max(due, since), upto,
                NODE_WAKE_PROFILES[node.node_class]["sleep_power_w"])
        self.idle_since[i] = self.sleep_at[i] = None

    def flush_wake(self, i, end):
        node = self.nodes[i]
        self.timeline.add_state(
            node.name, node.node_class, WAKING, self.wake_started[i], end,
            NODE_ENERGY_PROFILES[node.node_class]["idle_power"])
        self.wake_started[i] = self.wake_ready[i] = None

    def advance_to(self, t):
        for i, ready in enumerate(self.wake_ready):
            if ready is not None and ready <= t:
                self.flush_wake(i, ready)
                self.idle_since[i] = ready if self.running[i] == 0 else None

    def on_commit(self, i, t):
        start = self.wake_ready[i]
        if start is None:
            start = t
            self.flush_idle(i, t)
        self.running[i] += 1
        self.idle_since[i] = None
        return start

    def on_complete(self, i, t):
        self.running[i] -= 1
        if self.running[i] == 0 and self.wake_ready[i] is None:
            self.idle_since[i] = t

    def request_wake(self, i, t):
        node = self.nodes[i]
        self.flush_idle(i, t)
        prof = NODE_WAKE_PROFILES[node.node_class]
        self.wake_started[i] = t
        self.wake_ready[i] = t + prof["wake_latency_s"]
        self.timeline.add_wake(node.name, node.node_class, t,
                               prof["wake_energy_j"])
        return self.wake_ready[i]

    def force_sleep(self, i, t):
        self.idle_since[i] = self.sleep_at[i] = t

    def close(self, horizon):
        for i in range(len(self.nodes)):
            ready = self.wake_ready[i]
            if ready is not None:
                self.flush_wake(i, min(ready, horizon))
                if ready < horizon and self.running[i] == 0:
                    self.idle_since[i], self.sleep_at[i] = ready, None
                    self.flush_idle(i, horizon)
                continue
            self.flush_idle(i, horizon)


def _next_clock(rng, t, ref):
    """A later clock instant: a random step, or exactly a wake's ready
    instant or an idle node's sleep-due instant (the boundaries)."""
    due = [d for d in (ref.sleep_due(i) for i in range(len(ref.nodes)))
           if t < d < math.inf]
    ready = ref.next_transition(t)
    pick = rng.integers(4)
    if pick == 0 and due:
        return float(due[int(rng.integers(len(due)))])
    if pick == 1 and ready is not None:
        return ready
    return t + float(rng.exponential(12.0))


@pytest.mark.parametrize("idle_timeout_s", [30.0, math.inf])
@pytest.mark.parametrize("min_awake", [0, 3])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_state_machine_matches_per_node_reference(seed, min_awake,
                                                  idle_timeout_s):
    """A seeded random run of commits, completions, evictions, wakes,
    drains and clock advances on 64 nodes: after every step the states,
    the exclude mask, the next transition and the deadline masks equal a
    per-node reference of the rules, each sync dirties exactly the nodes
    whose state changed, and the state ledgers agree to the bit."""
    rng = np.random.default_rng(seed)
    nodes = make_scenario_cluster("mixed", 64, seed=seed)
    policy = AutoscalePolicy(idle_timeout_s=idle_timeout_s,
                             min_awake=min_awake)
    table = FleetState.from_nodes(nodes)
    fleet = ElasticFleet(nodes, policy, PowerTimeline(), table=table)
    ref = _PerNodeFleet(nodes, policy, PowerTimeline())
    t = 0.0
    synced = ref.states(t)
    assert fleet.states(t) == synced == list(table.power_state)
    for _ in range(400):
        op, i = int(rng.integers(6)), int(rng.integers(len(nodes)))
        sts = ref.states(t)
        if op == 0 and sts[i] != ASLEEP:
            assert fleet.on_commit(i, t) == ref.on_commit(i, t)
        elif op == 1 and ref.running[i] > 0:
            fleet.on_complete(i, t)
            ref.on_complete(i, t)
        elif op == 2 and ref.running[i] > 0:
            fleet.on_evict(i, t)
            ref.on_complete(i, t)
        elif op == 3 and sts[i] == ASLEEP:
            assert fleet.request_wake(i, t) == ref.request_wake(i, t)
        elif op == 4 and sts[i] == ACTIVE:
            while ref.running[i]:              # drain, then sleep at once
                fleet.on_evict(i, t)
                ref.on_complete(i, t)
            fleet.force_sleep(i, t)
            ref.force_sleep(i, t)
        else:
            t = _next_clock(rng, t, ref)
            fleet.advance_to(t)
            ref.advance_to(t)
        version = table.version
        fleet.write_states(t)
        want = ref.states(t)
        assert table.modified_since(version).tolist() == [
            j for j, (a, b) in enumerate(zip(synced, want)) if a != b]
        synced = want
        assert fleet.states(t) == want == list(table.power_state)
        assert [n.power_state for n in nodes] == want
        base = fleet.exclude_mask(t)
        assert base.tolist() == [s == ASLEEP for s in want]
        assert fleet.next_transition(t) == ref.next_transition(t)
        for deadline in (t, t + 1.0, t + 4.0, t + 8.0):
            assert fleet.exclude_for_deadline(base, deadline).tolist() == \
                ref.exclude_for_deadline(base.tolist(), deadline)
    fleet.close(t + 100.0)
    ref.close(t + 100.0)
    assert fleet.timeline.state_intervals == ref.timeline.state_intervals
    assert fleet.timeline.wake_transitions == ref.timeline.wake_transitions


def test_sync_pushes_only_the_nodes_that_changed():
    """A sync with no transition dirties nothing; with a live registry
    ``policy_state_transitions`` counts exactly the nodes a sync pushed,
    and without one nothing is counted."""
    nodes = [Node(f"a-{i}", "A", 4, 16) for i in range(8)]
    table = FleetState.from_nodes(nodes)
    fleet = ElasticFleet(nodes, AutoscalePolicy(idle_timeout_s=30.0,
                                                min_awake=0),
                         PowerTimeline(), table=table)
    fleet.on_commit(1, 0.0)
    fleet.on_commit(2, 0.0)
    with telemetry.enabled(Telemetry(timelines=False)) as tel:
        version = table.version
        fleet.write_states(5.0)                  # nodes 1 and 2 go ACTIVE
        assert table.modified_since(version).tolist() == [1, 2]
        version = table.version
        fleet.write_states(6.0)                  # nothing changes
        assert table.version == version
        fleet.write_states(30.0)                 # six idle nodes sleep
        assert table.modified_since(version).tolist() == [0, 3, 4, 5, 6, 7]
        assert tel.counter_value("policy_state_transitions",
                                 policy="AutoscaleScheduling") == 8.0
    fleet.on_complete(1, 31.0)
    fleet.write_states(31.0)
    assert telemetry.active() is telemetry.NULL
    assert nodes[1].power_state == IDLE == table.power_state[1]


def test_values_handed_to_the_state_ledger_are_python_floats(monkeypatch):
    """Every time and power ``ElasticFleet`` posts to ``PowerTimeline`` is a
    Python ``float``, never a numpy scalar read off its arrays."""
    posted = []
    add_state, add_wake = PowerTimeline.add_state, PowerTimeline.add_wake

    def state(tl, node, cls, st, start_s, end_s, power_w):
        posted.append((st, start_s, end_s, power_w))
        return add_state(tl, node, cls, st, start_s, end_s, power_w)

    def wake(tl, node, cls, t_s, energy_j):
        posted.append((WAKING, t_s, energy_j))
        return add_wake(tl, node, cls, t_s, energy_j)

    monkeypatch.setattr(PowerTimeline, "add_state", state)
    monkeypatch.setattr(PowerTimeline, "add_wake", wake)
    res = run_scenario(
        PoissonArrivals(rate_per_s=0.05, n_bursts=6, burst_size=6, seed=4,
                        deferrable_share=0.5, deadline_s=400.0),
        "carbon_energy_balanced",
        cluster_factory=lambda: make_scenario_cluster("mixed", 12, seed=4),
        batch=True, batch_backend="numpy",
        carbon=CarbonPolicy(TraceCarbon([{"t": 0.0, "intensity": 100.0},
                                         {"t": 40.0, "intensity": 500.0},
                                         {"t": 90.0, "intensity": 100.0}]),
                            defer_threshold=300.0, preempt_threshold=400.0,
                            check_interval_s=15.0),
        autoscale=AutoscalePolicy(idle_timeout_s=20.0, min_awake=1,
                                  consolidate_interval_s=30.0,
                                  consolidate_util_below=0.3))
    assert res.wakes > 0 and res.sleeps > 0
    assert {p[0] for p in posted} >= {IDLE, ASLEEP, WAKING}
    for entry in posted:
        assert all(type(v) is float for v in entry[1:]), entry


# --- state-ledger accounting -------------------------------------------------
def test_state_ledger_energy_and_carbon_accounting():
    """Manual ledger: state intervals and wake lumps sum exactly, and under
    a flat signal carbon is energy x intensity / 3.6e6."""
    tl = PowerTimeline(carbon_signal=ConstantCarbon(400.0),
                       node_region={"n0": "default"})
    tl.add("n0", "A", "topsis", 0.0, 10.0, 3.0)
    tl.add_state("n0", "A", IDLE, 10.0, 40.0, 6.0)
    tl.add_state("n0", "A", ASLEEP, 40.0, 100.0, 0.3)
    tl.add_state("n0", "A", WAKING, 100.0, 102.0, 6.0)
    tl.add_wake("n0", "A", 100.0, 25.0)
    tl.add_state("n0", "A", IDLE, 0.0, 0.0, 6.0)      # empty: dropped
    assert len(tl.state_intervals) == 3
    assert tl.state_energy_j(IDLE) == 180.0
    assert tl.state_energy_j(ASLEEP) == 18.0
    assert tl.state_energy_j(WAKING) == 12.0
    assert tl.state_energy_j() == 210.0
    assert tl.wake_transition_energy_j() == 25.0
    idle_busy = NODE_ENERGY_PROFILES["A"]["idle_power"] * 10.0
    want_idle = (idle_busy + 210.0 + 25.0) / 1000.0
    assert abs(tl.fleet_idle_energy_kj() - want_idle) < 1e-12
    assert abs(tl.fleet_energy_kj() - (30.0 / 1000.0 + want_idle)) < 1e-12
    # carbon: every joule at 400 g/kWh
    want_c = (210.0 + 25.0) * 400.0 / 3.6e6
    assert abs(tl.state_carbon_g() - want_c) < 1e-12
    assert abs(tl.fleet_carbon_g()
               - (tl.total_carbon_g(None) + want_c)) < 1e-12


def test_elastic_scenario_carbon_and_backend_agreement():
    """An elastic + carbon scenario: numpy and jax backends place
    identically, fleet carbon exceeds the task-attributed total (sleep
    residuals and idle stretches emit too), and every deferrable pod
    starts by its deadline."""
    arr = lambda: PoissonArrivals(rate_per_s=0.3, n_bursts=3, burst_size=4,
                                  seed=7, deferrable_share=0.5,
                                  deadline_s=300.0)
    fac = lambda: make_scenario_cluster("mixed", 8, seed=3)
    pol = AutoscalePolicy(idle_timeout_s=20.0, min_awake=1,
                          consolidate_interval_s=60.0)
    carbon = CarbonPolicy(ConstantCarbon(400.0))
    runs = {}
    for backend in ("numpy", "jax"):
        runs[backend] = run_scenario(arr(), "energy_centric",
                                     cluster_factory=fac, batch=True,
                                     batch_backend=backend, carbon=carbon,
                                     autoscale=pol)
    a, b = runs["numpy"], runs["jax"]
    assert [r.node for r in a.records] == [r.node for r in b.records]
    assert a.fleet_idle_energy_kj() == b.fleet_idle_energy_kj()
    assert a.fleet_carbon_g() > a.total_carbon_g(None)
    arrival = {r.pod.uid: r.arrival_s for r in a.records}
    for r in a.records:
        if r.pod.deferrable:
            assert r.start_s <= arrival[r.pod.uid] + r.pod.deadline_s + 1e-9

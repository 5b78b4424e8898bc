"""FleetState delta maintenance: the dirty-column contract against the
full-rebuild oracle, incremental scoring equivalence on every backend, and
burst routing by scheduler group.

The tentpole invariant: after ANY interleaving of commit/release/evict/
sleep/wake mutations, the delta-maintained columns are bitwise-equal to a
fresh ``NodeTable.from_nodes`` rebuild of the same Node objects, and the
incremental criteria cache scores bitwise (numpy) / within 1e-5 (float32
backends) of the full-rebuild scoring path kept verbatim in
``BatchScheduler.score_queue``.
"""
import itertools

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
            def skipped(*a, **k):
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

from repro.cluster.engine import EventEngine, SimState
from repro.cluster.node import FleetState, NodeTable, make_fleet_nodes
from repro.cluster.workload import WORKLOADS, ArrivalProcess, Pod
from repro.core import telemetry
from repro.core.carbon import diurnal_fleet_signal
from repro.core.elastic import ASLEEP, IDLE
from repro.core.energy import PowerTimeline
from repro.core.scheduler import (BatchScheduler, DefaultK8sScheduler,
                                  GreenPodScheduler)

BACKENDS = ("numpy", "jax", "pallas")

# the op alphabet of the property tests: every mutator the event engine
# drives through FleetState (commit=bind, completion=release, evict, and
# the elastic sleep/wake power-state transitions)
OPS = st.lists(
    st.tuples(st.sampled_from(["bind", "release", "evict", "sleep", "wake"]),
              st.integers(0, 2**16), st.integers(0, 2**16)),
    max_size=60)


def _apply(fs: FleetState, ops, bound=None) -> None:
    """Replay an op word against the fleet, keeping it physically valid:
    binds honor capacity, releases/evicts pop an outstanding bind (release
    the newest, evict the oldest — both are column releases; the engine
    only differs in requeue bookkeeping)."""
    n = len(fs)
    bound = bound if bound is not None else []
    for kind, a, b in ops:
        i = a % n
        if kind == "bind":
            cpu = 0.25 * (1 + b % 8)
            mem = 0.5 * (1 + b % 4)
            if fs.free_cpu[i] >= cpu and fs.free_mem[i] >= mem:
                fs.bind(i, cpu, mem)
                bound.append((i, cpu, mem))
        elif kind == "release" and bound:
            fs.release(*bound.pop())
        elif kind == "evict" and bound:
            fs.release(*bound.pop(0))
        elif kind == "sleep":
            fs.set_power_states([i], [ASLEEP])
        elif kind == "wake":
            fs.set_power_states([i], [IDLE])


def _queue(n_pods: int = 6) -> list[Pod]:
    uid = itertools.count()
    kinds = itertools.cycle(["light", "medium", "complex"])
    return [Pod(next(uid), WORKLOADS[next(kinds)], "topsis")
            for _ in range(n_pods)]


# --- the dirty-column contract ----------------------------------------------
@settings(deadline=None, max_examples=60)
@given(ops=OPS, seed=st.integers(0, 3))
def test_columns_bitwise_equal_fresh_rebuild(ops, seed):
    """Any commit/release/evict/sleep/wake interleaving leaves every
    delta-maintained column bitwise-equal to NodeTable.from_nodes over the
    same Node objects — the rebuild the engine used to pay per round."""
    fs = FleetState.from_nodes(make_fleet_nodes(12, seed=seed,
                                                utilization=0.25))
    _apply(fs, ops)
    ref = NodeTable.from_nodes(fs.nodes)
    np.testing.assert_array_equal(fs.used_cpu, ref.used_cpu)
    np.testing.assert_array_equal(fs.used_mem, ref.used_mem)
    np.testing.assert_array_equal(fs.awake, ref.awake)
    assert list(fs.power_state) == list(ref.power_state)
    np.testing.assert_array_equal(fs.free_cpu, ref.free_cpu)
    np.testing.assert_array_equal(fs.free_mem, ref.free_mem)


def test_columns_equal_rebuild_seeded():
    """Deterministic twin of the column property (runs without
    hypothesis)."""
    rng = np.random.default_rng(11)
    kinds = ["bind", "release", "evict", "sleep", "wake"]
    for seed in range(4):
        ops = [(kinds[int(rng.integers(len(kinds)))],
                int(rng.integers(2**16)), int(rng.integers(2**16)))
               for _ in range(50)]
        fs = FleetState.from_nodes(make_fleet_nodes(12, seed=seed,
                                                    utilization=0.25))
        _apply(fs, ops)
        ref = NodeTable.from_nodes(fs.nodes)
        np.testing.assert_array_equal(fs.used_cpu, ref.used_cpu)
        np.testing.assert_array_equal(fs.used_mem, ref.used_mem)
        np.testing.assert_array_equal(fs.awake, ref.awake)
        assert list(fs.power_state) == list(ref.power_state)


def test_modified_since_is_a_multi_consumer_cursor():
    """Each consumer holds its own version cursor; older cursors keep
    seeing the union of everything touched since."""
    fs = FleetState.from_nodes(make_fleet_nodes(8, seed=0))
    v0 = fs.version
    fs.bind(3, 1.0, 2.0)
    fs.bind(5, 1.0, 2.0)
    assert set(fs.modified_since(v0)) == {3, 5}
    v1 = fs.version
    fs.release(3, 1.0, 2.0)
    assert set(fs.modified_since(v1)) == {3}
    assert set(fs.modified_since(v0)) == {3, 5}
    assert fs.modified_since(fs.version).size == 0
    # a no-op power-state write must not dirty anything
    v2 = fs.version
    fs.set_power_states(range(len(fs)), list(fs.power_state))
    assert fs.version == v2


# --- incremental scoring vs the full-rebuild oracle --------------------------
def _check_incremental_vs_oracle(fs, ops, backend):
    """Interleave mutation bursts with scoring rounds: the attached
    (incremental) scheduler must agree with a detached scheduler scoring a
    fresh NodeTable rebuild — bitwise on numpy (same float64 arithmetic),
    1e-5 on the float32 jax/pallas backends — with identical -inf
    feasibility patterns."""
    inc = BatchScheduler("energy_centric", backend=backend)
    inc.attach(fs)
    oracle = BatchScheduler("energy_centric", backend=backend)
    pods = _queue()
    bound = []
    step = max(1, len(ops) // 3)
    for lo in range(0, len(ops) + 1, step):
        _apply(fs, ops[lo:lo + step], bound)
        cc_inc = inc.score_queue(pods, fs, now=0.0)
        cc_ref = oracle.score_queue(pods, NodeTable.from_nodes(fs.nodes),
                                    now=0.0)
        np.testing.assert_array_equal(np.isneginf(cc_inc),
                                      np.isneginf(cc_ref))
        finite = np.isfinite(cc_ref)
        if backend == "numpy":
            np.testing.assert_array_equal(cc_inc, cc_ref)
        else:
            np.testing.assert_allclose(cc_inc[finite], cc_ref[finite],
                                       atol=1e-5, rtol=0)


@settings(deadline=None, max_examples=8)
@given(ops=OPS, backend=st.sampled_from(BACKENDS))
def test_incremental_scores_match_rebuild_oracle(ops, backend):
    fs = FleetState.from_nodes(make_fleet_nodes(24, seed=1, utilization=0.3))
    _check_incremental_vs_oracle(fs, ops, backend)


@pytest.mark.parametrize("backend", BACKENDS)
def test_incremental_scores_match_rebuild_oracle_seeded(backend):
    """Deterministic twin of the property test (runs even without
    hypothesis): seeded random op words through the same oracle check."""
    rng = np.random.default_rng(7)
    kinds = ["bind", "release", "evict", "sleep", "wake"]
    for seed in range(3):
        ops = [(kinds[int(rng.integers(len(kinds)))],
                int(rng.integers(2**16)), int(rng.integers(2**16)))
               for _ in range(40)]
        fs = FleetState.from_nodes(make_fleet_nodes(24, seed=seed,
                                                    utilization=0.3))
        _check_incremental_vs_oracle(fs, ops, backend)


@pytest.mark.parametrize("backend", BACKENDS)
def test_incremental_carbon_column_tracks_decision_time(backend):
    """With a carbon signal the cached carbon column must follow ``now``:
    scoring at a later instant refreshes intensity x power for ALL nodes,
    not just dirty ones, and still matches the rebuild oracle."""
    sig = diurnal_fleet_signal(base=300.0, amplitude=200.0, period_s=3600.0)
    fs = FleetState.from_nodes(make_fleet_nodes(16, seed=2, utilization=0.2))
    inc = BatchScheduler("energy_centric", backend=backend,
                         carbon_signal=sig)
    inc.attach(fs)
    oracle = BatchScheduler("energy_centric", backend=backend,
                            carbon_signal=sig)
    pods = _queue(4)
    for now in (0.0, 0.0, 617.3, 1805.0):   # repeat: carbon_moved=False leg
        fs.bind(3, 0.5, 1.0)
        cc_inc = inc.score_queue(pods, fs, now=now)
        cc_ref = oracle.score_queue(pods, NodeTable.from_nodes(fs.nodes),
                                    now=now)
        finite = np.isfinite(cc_ref)
        np.testing.assert_array_equal(np.isneginf(cc_inc),
                                      np.isneginf(cc_ref))
        if backend == "numpy":
            np.testing.assert_array_equal(cc_inc, cc_ref)
        else:
            np.testing.assert_allclose(cc_inc[finite], cc_ref[finite],
                                       atol=1e-5, rtol=0)
        fs.release(3, 0.5, 1.0)


def test_detached_jax_calls_leave_the_attached_mirror_alone():
    """A jax call on a table that is not the attached fleet runs the same
    kind-gather round on a cache of its own. Interleaved with attached
    rounds, both kinds of call stay within 1e-5 of numpy, and the attached
    cache re-uploads its device mirror exactly as often as it does without
    them: each detached call uploads once, for its own cache."""
    def run(detached: bool):
        fs = FleetState.from_nodes(make_fleet_nodes(24, seed=4,
                                                    utilization=0.3))
        other = NodeTable.from_nodes(make_fleet_nodes(24, seed=5,
                                                      utilization=0.5))
        sched = BatchScheduler("energy_centric", backend="jax")
        sched.attach(fs)
        ref = BatchScheduler("energy_centric", backend="numpy")
        pods = _queue()
        calls = 0
        with telemetry.enabled() as tel:
            for step in range(4):
                fs.bind(step, 0.25, 0.5)
                tables = [other, fs, other] if detached else [fs]
                for table in tables:
                    snapshot = (NodeTable.from_nodes(fs.nodes)
                                if table is fs else other)
                    want = ref.score_queue(pods, snapshot)
                    got = sched.score_queue(pods, table)
                    calls += table is other
                    finite = np.isfinite(want)
                    np.testing.assert_array_equal(finite, np.isfinite(got))
                    np.testing.assert_allclose(got[finite], want[finite],
                                               atol=1e-5, rtol=0)
            uploads = (tel.counter_value("cache_device_reuploads",
                                         reason="first")
                       + tel.counter_value("cache_device_reuploads",
                                           reason="growth"))
        cache = sched._cache
        np.testing.assert_array_equal(np.asarray(cache.dev),
                                      cache.mats.astype(np.float32))
        return uploads - calls

    assert run(detached=True) == run(detached=False) == 1


def test_attached_per_pod_scheduler_matches_detached():
    """GreenPodScheduler's cached select agrees with the detached
    rebuild-per-call form after fleet mutations (same index, same scores)."""
    fs = FleetState.from_nodes(make_fleet_nodes(20, seed=3, utilization=0.4))
    inc = GreenPodScheduler("energy_centric")
    inc.attach(fs)
    det = GreenPodScheduler("energy_centric")
    pod = Pod(0, WORKLOADS["medium"], "topsis")
    for i in (7, 11, 13):
        if fs.free_cpu[i] >= 0.5 and fs.free_mem[i] >= 1.0:
            fs.bind(i, 0.5, 1.0)
        idx_i, diag_i = inc.select(pod, fs)
        idx_d, diag_d = det.select(pod, NodeTable.from_nodes(fs.nodes))
        assert idx_i == idx_d
        np.testing.assert_array_equal(diag_i["closeness"],
                                      diag_d["closeness"])


# --- burst routing by scheduler group (engine regression) --------------------
class _SpyBatch(BatchScheduler):
    """BatchScheduler that records which pods it was asked to place."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.seen: list[int] = []

    def select_many(self, pods, nodes, now=0.0, blocked=None, exclude=None):
        self.seen.extend(p.uid for p in pods)
        return super().select_many(pods, nodes, now=now, blocked=blocked,
                                   exclude=exclude)


class _OneBurst(ArrivalProcess):
    def __init__(self, pods):
        self._pods = list(pods)

    def events(self):
        return [(0.0, self._pods)]


def test_burst_routing_by_scheduler_group():
    """Regression: the burst path used to hardcode schedulers["topsis"],
    so in a mixed queue every batch-capable group's pods were scored (and
    logged) by the wrong engine. Bursts must group by ``pod.scheduler``
    and each group must flow through its own ``select_many``."""
    fleet = FleetState.from_nodes(make_fleet_nodes(8, seed=0))
    a = _SpyBatch("energy_centric", backend="numpy")
    b = _SpyBatch("energy_centric", backend="numpy")
    schedulers = {"topsis": a, "alt": b, "default": DefaultK8sScheduler()}
    for sched in (a, b):
        sched.attach(fleet)
    uid = itertools.count()
    pods = [Pod(next(uid), WORKLOADS["light"], s)
            for s in ("topsis", "alt", "topsis", "alt", "topsis")]
    state = SimState(fleet=fleet, schedulers=schedulers,
                     timeline=PowerTimeline())
    res = EventEngine(state, (), _OneBurst(pods), batch=True).run()
    assert a.seen == [0, 2, 4]
    assert b.seen == [1, 3]
    assert len(res.records) == len(pods)
    # and each engine's decision log only carries its own group's pods
    assert {d["pod"] for d in a.decision_log} == {0, 2, 4}
    assert {d["pod"] for d in b.decision_log} == {1, 3}

"""Fleet-scale batched scheduling path: equivalence vs the numpy reference.

Every batched backend (vectorized decision matrix, BatchScheduler backends,
the valid-masked Pallas wrapper) must match ``topsis.closeness_np`` within
1e-5 — including valid-masked rows, padded criteria (C < C_PAD), and the
degenerate all-equal matrix.

The property-based block (randomized fleets and pod queues via
``hypothesis``) needs ``hypothesis`` (requirements-dev.txt); when it is
absent those tests skip with a clear reason and the unit tests still run.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    # Degrade gracefully: stand-in decorators collect each property test as
    # a no-arg test that skips at runtime (mirrors @given consuming the
    # function's parameters, so pytest never looks for fixtures).
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

from repro.core import topsis
from repro.core.criteria import benefit_mask
from repro.core.scheduler import (BatchScheduler, GreenPodScheduler,
                                  decision_matrix, decision_matrix_batch)
from repro.core.weighting import SCHEME_NAMES
from repro.cluster.node import Node, NodeTable, make_fleet, make_paper_cluster
from repro.cluster.workload import WORKLOADS, Pod
from repro.kernels import ops

BENEFIT = benefit_mask()


def make_queue(p, seed=0):
    rng = np.random.default_rng(seed)
    kinds = list(WORKLOADS)
    return [Pod(i, WORKLOADS[kinds[int(rng.integers(len(kinds)))]], "topsis")
            for i in range(p)]


# --- vectorized decision matrix ----------------------------------------------
def test_node_table_matches_node_list():
    nodes = make_paper_cluster()
    nodes[1].bind(0.5, 1.0)
    table = NodeTable.from_nodes(nodes)
    np.testing.assert_array_equal(table.fits(0.5, 1.0),
                                  [n.fits(0.5, 1.0) for n in nodes])
    np.testing.assert_allclose(table.free_cpu,
                               [n.free_cpu for n in nodes])
    np.testing.assert_allclose(table.cpu_util,
                               [n.cpu_util for n in nodes])


def test_decision_matrix_batch_rows_match_single():
    """(P, N, 5) batch tensor row p == the single-pod (N, 5) matrix."""
    table = make_fleet(33, seed=1, utilization=0.4)
    pods = make_queue(5)
    batch = decision_matrix_batch(pods, table)
    assert batch.shape == (5, 33, 5)
    for i, p in enumerate(pods):
        np.testing.assert_allclose(batch[i], decision_matrix(p, table),
                                   rtol=0, atol=0)


# --- pallas wrapper with valid mask -----------------------------------------
@pytest.mark.parametrize("n,c", [(4, 5), (100, 3), (700, 5), (1000, 8)])
def test_pallas_valid_mask_matches_closeness_np(n, c):
    rng = np.random.default_rng(n * 7 + c)
    M = rng.uniform(0.1, 10.0, (n, c))
    w = rng.uniform(0.1, 1.0, c)
    benefit = rng.uniform(size=c) < 0.5
    valid = rng.uniform(size=n) < 0.6
    valid[rng.integers(n)] = True
    want = topsis.closeness_np(M, w, benefit, valid).closeness
    got = np.asarray(ops.topsis_closeness(M, w, benefit, valid=valid))
    np.testing.assert_allclose(got[valid], want[valid], atol=1e-5)
    assert np.all(np.isneginf(got[~valid]))


def test_pallas_batched_matches_closeness_np():
    rng = np.random.default_rng(3)
    p, n, c = 6, 300, 5
    mats = rng.uniform(0.1, 10.0, (p, n, c))
    ws = rng.uniform(0.1, 1.0, (p, c))
    valid = rng.uniform(size=(p, n)) < 0.7
    valid[:, 0] = True
    want = topsis.batched_closeness_np(mats, ws, BENEFIT, valid)
    got = np.asarray(ops.topsis_closeness_batched(mats, ws, BENEFIT,
                                                  valid=valid))
    np.testing.assert_allclose(got[valid], want[valid], atol=1e-5)
    assert np.all(np.isneginf(got[~valid]))


def test_pallas_degenerate_all_equal():
    M = np.ones((16, 5))
    got = np.asarray(ops.topsis_closeness(M, np.ones(5), BENEFIT))
    np.testing.assert_allclose(got, 0.5, atol=1e-6)
    batched = np.asarray(ops.topsis_closeness_batched(
        np.ones((3, 16, 5)), np.ones(5), BENEFIT))
    np.testing.assert_allclose(batched, 0.5, atol=1e-6)


# --- scheduler backends ------------------------------------------------------
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_scheduler_backend_matches_numpy(backend):
    """GreenPodScheduler closeness identical across backends (within 1e-5),
    same selected node."""
    table = make_fleet(200, seed=2, utilization=0.5)
    for pod in make_queue(4, seed=5):
        ref = GreenPodScheduler("energy_centric", backend="numpy")
        alt = GreenPodScheduler("energy_centric", backend=backend)
        i_ref, d_ref = ref.select(pod, table)
        i_alt, d_alt = alt.select(pod, table)
        finite = np.isfinite(d_ref["closeness"])
        np.testing.assert_allclose(d_alt["closeness"][finite],
                                   d_ref["closeness"][finite], atol=1e-5)
        assert i_ref == i_alt


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_batch_scheduler_scores_match_numpy(backend):
    pods = make_queue(8, seed=7)
    table = make_fleet(257, seed=4, utilization=0.4)   # non-pow2 N (padding)
    want = BatchScheduler("energy_centric",
                          backend="numpy").score_queue(pods, table)
    got = BatchScheduler("energy_centric",
                         backend=backend).score_queue(pods, table)
    finite = np.isfinite(want)
    np.testing.assert_array_equal(finite, np.isfinite(got))
    np.testing.assert_allclose(got[finite], want[finite], atol=1e-5)


@pytest.mark.parametrize("attached", [False, True],
                         ids=["rebuild", "incremental"])
def test_pallas_shrinking_queues_share_one_kernel(attached):
    """The Pallas rounds pad the pod axis to a power of two, as the jax
    rounds do: queues of 8, 7, 6 and 5 pods reuse one compiled kernel, and
    every round still matches the numpy reference."""
    from repro.cluster.node import FleetState, make_fleet_nodes
    from repro.kernels import topsis_pallas as tp
    kernel = (tp.topsis_closeness_kinds_blocks if attached
              else tp.topsis_closeness_batched_blocks)
    fleet = FleetState.from_nodes(make_fleet_nodes(100, seed=3,
                                                   utilization=0.4))
    sched = BatchScheduler("energy_centric", backend="pallas")
    if attached:
        sched.attach(fleet)
    ref = BatchScheduler("energy_centric", backend="numpy")
    pods = make_queue(8, seed=9)
    before = kernel._cache_size()
    for p in (8, 7, 6, 5):
        got = sched.score_queue(pods[:p], fleet)
        want = ref.score_queue(pods[:p], NodeTable.from_nodes(fleet.nodes))
        assert got.shape == want.shape == (p, 100)
        finite = np.isfinite(want)
        np.testing.assert_array_equal(finite, np.isfinite(got))
        np.testing.assert_allclose(got[finite], want[finite], atol=1e-5)
    assert kernel._cache_size() - before <= 1


@pytest.mark.parametrize("backend", ["jax"])
def test_batch_scheduler_assignments_match_numpy(backend):
    pods = make_queue(16, seed=11)
    table = make_fleet(64, seed=6, utilization=0.6)
    a_ref, _ = BatchScheduler("energy_centric",
                              backend="numpy").select_many(pods, table)
    a_alt, _ = BatchScheduler("energy_centric",
                              backend=backend).select_many(pods, table)
    assert a_ref == a_alt


def test_batch_scheduler_respects_capacity_ledger():
    """Greedy commit never overcommits a node within one burst, and the
    input table is not mutated."""
    nodes = make_paper_cluster()
    table = NodeTable.from_nodes(nodes)
    used0 = table.used_cpu.copy()
    pods = [Pod(i, WORKLOADS["complex"], "topsis") for i in range(12)]
    sched = BatchScheduler("energy_centric", backend="numpy")
    assignments, _ = sched.select_many(pods, table)
    np.testing.assert_array_equal(table.used_cpu, used0)
    cpu = np.zeros(len(table))
    mem = np.zeros(len(table))
    for pod, idx in zip(pods, assignments):
        if idx is None:
            continue
        cpu[idx] += pod.cpu
        mem[idx] += pod.mem
    assert np.all(cpu <= table.free_cpu + 1e-9)
    assert np.all(mem <= table.free_mem + 1e-9)
    # the queue exceeds the 4-node cluster: some pods must spill
    assert any(a is None for a in assignments)
    assert any(a is not None for a in assignments)


def test_batch_scheduler_infeasible_pod_unplaced():
    table = NodeTable.from_nodes(make_paper_cluster())
    big = Pod(0, WORKLOADS["complex"], "topsis")
    tiny = Pod(1, WORKLOADS["light"], "topsis")
    # saturate everything so 'big' can't fit anywhere
    table.used_cpu[:] = table.vcpus - table.reserved_cpu - 0.25
    table.used_mem[:] = table.mem_gb - table.reserved_mem - 0.6
    assignments, diag = BatchScheduler(
        "energy_centric", backend="numpy").select_many([big, tiny], table)
    assert assignments[0] is None
    assert assignments[1] is not None
    assert np.all(np.isneginf(diag["closeness"][0]))


# --- simulator batch mode ----------------------------------------------------
def test_simulator_batch_mode_schedules_all():
    from repro.cluster.simulator import run_experiment
    for level in ("low", "medium"):
        res = run_experiment(level, "energy_centric", batch=True,
                             batch_backend="numpy")
        assert res.unschedulable == 0
        n_expected = {"low": 8, "medium": 14}[level]
        assert len(res.records) == n_expected
        # both schedulers' pods all completed
        assert sum(1 for r in res.records
                   if r.pod.scheduler == "topsis") == n_expected // 2


def test_simulator_batch_jax_backend_runs():
    from repro.cluster.simulator import run_experiment
    res = run_experiment("low", "energy_centric", batch=True,
                         batch_backend="jax")
    assert res.unschedulable == 0 and len(res.records) == 8


# --- greedy capacity-ledger regressions --------------------------------------
def test_ledger_falls_through_to_next_ranked_node():
    """A pod whose top-ranked node was exhausted by an earlier queue entry
    must take its next-ranked *feasible* node, not drop out."""
    # b-small is the snapshot's top-ranked node for a complex pod under
    # energy_centric weights and fits exactly one (1.2 vcpu / 2.5 GB vs the
    # pod's 1.0 / 2.0 request); two identical pods contend for it.
    nodes = [Node("a-0", "A", vcpus=4, mem_gb=16),
             Node("b-small", "B", vcpus=1.2, mem_gb=2.5),
             Node("c-0", "C", vcpus=8, mem_gb=32)]
    table = NodeTable.from_nodes(nodes)
    pods = [Pod(0, WORKLOADS["complex"], "topsis"),
            Pod(1, WORKLOADS["complex"], "topsis")]
    sched = BatchScheduler("energy_centric", backend="numpy")
    assignments, diag = sched.select_many(pods, table)
    cc = diag["closeness"]
    top = int(np.argmax(cc[0]))
    # preconditions: both pods rank the one-pod node first on the snapshot
    assert top == 1 and int(np.argmax(cc[1])) == top
    assert assignments[0] == top
    # pod 1's top choice is ledger-exhausted: it takes its next-ranked node
    order = np.argsort(-cc[1], kind="stable")
    assert assignments[1] == int(order[1]) != top
    assert assignments[1] is not None


def test_ledger_neginf_break_does_not_skip_feasible_nodes():
    """-inf closeness marks snapshot-infeasible nodes; they sort after every
    finite entry (stable descending argsort), so the early break must never
    hide a finite-scored node that still has ledger capacity."""
    nodes = [Node("a-small", "A", vcpus=1.2, mem_gb=2.5),    # fits one
             Node("b-tiny", "B", vcpus=0.5, mem_gb=1.0),     # never fits
             Node("c-0", "C", vcpus=8, mem_gb=32)]
    table = NodeTable.from_nodes(nodes)
    pods = [Pod(i, WORKLOADS["complex"], "topsis") for i in range(3)]
    sched = BatchScheduler("energy_centric", backend="numpy")
    assignments, diag = sched.select_many(pods, table)
    cc = diag["closeness"]
    assert np.all(np.isneginf(cc[:, 1]))     # b-tiny snapshot-infeasible
    # every pod with any ledger-feasible finite-scored node got placed
    assert assignments == [0, 2, 2]
    # and an exhausted queue leaves later pods unplaced, not misplaced:
    many = [Pod(i, WORKLOADS["complex"], "topsis") for i in range(12)]
    assignments, diag = sched.select_many(many, table)
    cc = diag["closeness"]
    free_cpu, free_mem = table.free_cpu.copy(), table.free_mem.copy()
    for pod, a in zip(many, assignments):
        if a is not None:
            free_cpu[a] -= pod.cpu
            free_mem[a] -= pod.mem
            continue
        # None => no finite-scored node had residual ledger capacity
        for j in np.flatnonzero(np.isfinite(cc[0])):
            assert (free_cpu[j] < pod.cpu - 1e-9
                    or free_mem[j] < pod.mem - 1e-9)


# --- property-based equivalence (hypothesis) ---------------------------------
def _rand_pod(rng, uid=0):
    kinds = list(WORKLOADS)
    return Pod(uid, WORKLOADS[kinds[int(rng.integers(len(kinds)))]], "topsis")


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2 ** 31 - 1), n=st.integers(4, 200),
       util=st.floats(0.0, 0.8), scheme=st.sampled_from(SCHEME_NAMES))
def test_property_singleton_queue_matches_per_pod_select(seed, n, util,
                                                         scheme):
    """On a singleton queue the batched path must agree with the per-pod
    scheduler for every scheme, over randomized fleets: same node (or both
    unschedulable)."""
    rng = np.random.default_rng(seed)
    table = make_fleet(n, seed=seed, utilization=util)
    pod = _rand_pod(rng)
    idx, _ = GreenPodScheduler(scheme, backend="numpy").select(pod, table)
    assignments, _ = BatchScheduler(scheme,
                                    backend="numpy").select_many([pod], table)
    assert assignments == [idx]


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 31 - 1),
       n=st.sampled_from((4, 64, 257)), p=st.integers(1, 8),
       util=st.floats(0.0, 0.8))
def test_property_backends_equivalent(seed, n, p, util):
    """All three backends score randomized (fleet, queue) pairs within 1e-5
    of the numpy reference, with identical feasibility masks."""
    table = make_fleet(n, seed=seed, utilization=util)
    pods = make_queue(p, seed=seed)
    want = BatchScheduler("energy_centric",
                          backend="numpy").score_queue(pods, table)
    for backend in ("jax", "pallas"):
        got = BatchScheduler("energy_centric",
                             backend=backend).score_queue(pods, table)
        finite = np.isfinite(want)
        np.testing.assert_array_equal(finite, np.isfinite(got))
        np.testing.assert_allclose(got[finite], want[finite], atol=1e-5)

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

from repro.core import telemetry, topsis
from repro.core.criteria import benefit_mask
from repro.core.scheduler import (BatchScheduler, GreenPodScheduler,
                                  _greedy_assign, decision_matrix,
                                  decision_matrix_batch)
from repro.core.weighting import SCHEME_NAMES
from repro.cluster.node import Node, NodeTable, make_fleet, make_paper_cluster
from repro.cluster.workload import WORKLOADS, Pod, WorkloadSpec
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
    got = np.asarray(ops.topsis_closeness_kinds(mats, np.arange(p), ws,
                                                BENEFIT, valid=valid))
    np.testing.assert_allclose(got[valid], want[valid], atol=1e-5)
    assert np.all(np.isneginf(got[~valid]))


def test_pallas_degenerate_all_equal():
    M = np.ones((16, 5))
    got = np.asarray(ops.topsis_closeness(M, np.ones(5), BENEFIT))
    np.testing.assert_allclose(got, 0.5, atol=1e-6)
    batched = np.asarray(ops.topsis_closeness_kinds(
        np.ones((3, 16, 5)), np.arange(3), np.ones(5), BENEFIT))
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
    rounds do: queues of 8, 7, 6 and 5 pods reuse one compiled kinds
    kernel, on the attached fleet and on a fleet that is not attached
    alike, and every round still matches the numpy reference."""
    from repro.cluster.node import FleetState, make_fleet_nodes
    from repro.kernels import topsis_pallas as tp
    kernel = tp.topsis_closeness_kinds_blocks
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


# --- ranking groups in the greedy commit --------------------------------------
def _oracle_greedy_assign(cc, pods, table, blocked=None):
    """The commit before ranking groups, verbatim but for its spans and a
    count of the ranking entries it examines: every row sorted and walked
    from rank 0. Returns ``(assignments, examined)``."""
    examined = 0
    order = np.argsort(-cc, kind="stable", axis=-1)
    free_cpu = table.free_cpu.copy()
    free_mem = table.free_mem.copy()
    assignments: list[int | None] = []
    for i, pod in enumerate(pods):
        forbid = blocked[i] if blocked is not None else None
        chosen = None
        for j in order[i]:
            examined += 1
            if np.isneginf(cc[i, j]):
                break           # rest of the ranking is infeasible
            if forbid is not None and int(j) == forbid:
                continue
            if free_cpu[j] >= pod.cpu - 1e-9 \
                    and free_mem[j] >= pod.mem - 1e-9:
                chosen = int(j)
                free_cpu[j] -= pod.cpu
                free_mem[j] -= pod.mem
                break
        assignments.append(chosen)
    return assignments, examined


_ODD = WorkloadSpec("odd", -0.8, 0.5, 10.0, "negative cpu request")


def _commit_case(seed):
    """A random commit input built to stress ranking groups: a few base
    rows reused across pods (duplicates, and one row under several
    requests, and rows that are permutations of each other), scores from
    a few levels (exact ties at different indices, 0.0 beside -0.0), -inf
    tails and wholly -inf rows, now and then a NaN, a negative request,
    nodes small enough to fill mid-queue, ``blocked`` entries that sit on
    the node the unblocked walk would take, in C or Fortran order."""
    rng = np.random.default_rng(seed)
    n, p = int(rng.integers(1, 24)), int(rng.integers(1, 40))
    nodes = [Node(f"n{j}", "ABC"[j % 3], float(rng.choice([0.5, 1.0, 1.2, 2.0])),
                  float(rng.choice([1.0, 2.0, 2.5, 4.0]))) for j in range(n)]
    table = NodeTable.from_nodes(nodes)
    levels = np.array([0.0, -0.0, 0.25, 0.5, 0.5, 0.9, -np.inf])
    if rng.random() < 0.2:
        levels = np.append(levels, np.nan)
    base = rng.choice(levels, size=(int(rng.integers(1, 5)), n))
    base[rng.random(len(base)) < 0.2] = -np.inf
    if rng.random() < 0.3:
        base = np.vstack([base, rng.permutation(base[0])])
    dtype = np.float32 if rng.random() < 0.5 else np.float64
    cc = base[rng.integers(len(base), size=p)].astype(dtype)
    if rng.random() < 0.5:
        cc = np.asfortranarray(cc)         # the device's readback layout
    specs = list(WORKLOADS.values()) + ([_ODD] if rng.random() < 0.2 else [])
    pods = [Pod(i, specs[int(rng.integers(len(specs)))], "topsis")
            for i in range(p)]
    blocked = None
    if rng.random() < 0.6:
        free, _ = _oracle_greedy_assign(cc, pods, table)
        blocked = [a if a is not None and rng.random() < 0.4
                   else (int(rng.integers(n)) if rng.random() < 0.2 else None)
                   for a in free]
    return cc, pods, table, blocked


def _assert_commit_matches_oracle(seed):
    cc, pods, table, blocked = _commit_case(seed)
    want, _ = _oracle_greedy_assign(cc, pods, table, blocked=blocked)
    assert _greedy_assign(cc, pods, table, blocked=blocked) == want


@settings(max_examples=200, deadline=None)
@given(seed=st.integers(0, 2 ** 63 - 1))
def test_property_grouped_commit_matches_full_argsort_walk(seed):
    """Sorting each distinct (row, request) once and walking it with a
    shared cursor places every pod where sorting and walking every row
    from rank 0 does."""
    _assert_commit_matches_oracle(seed)


@pytest.mark.parametrize("seed", range(16))
def test_grouped_commit_matches_full_argsort_walk_seeded(seed):
    """The property above on fixed seeds, so it runs without hypothesis."""
    _assert_commit_matches_oracle(2 ** 40 + 7919 * seed)


def test_blocked_node_at_the_cursor_stays_for_the_group():
    """A node blocked for one pod that still fits is the next pod's pick:
    the group's cursor must not pass it."""
    table = NodeTable.from_nodes([Node("a", "A", 2.0, 4.0),
                                  Node("b", "B", 2.0, 4.0)])
    pods = [Pod(i, WORKLOADS["complex"], "topsis") for i in range(3)]
    cc = np.array([[0.9, 0.5]] * 3)
    got = _greedy_assign(cc, pods, table, blocked=[0, None, None])
    assert got == [1, 0, 0]
    assert got == _oracle_greedy_assign(cc, pods, table, [0, None, None])[0]


def test_rows_that_fold_alike_stay_apart():
    """Two rows holding the same scores in another order (equal XOR folds
    of their bits) are two rankings."""
    table = NodeTable.from_nodes([Node(f"n{j}", "C", 4.0, 16.0)
                                  for j in range(3)])
    pods = [Pod(i, WORKLOADS["light"], "topsis") for i in range(2)]
    cc = np.array([[0.9, 0.5, 0.25], [0.25, 0.5, 0.9]], dtype=np.float32)
    assert _greedy_assign(cc, pods, table) == [0, 2]


def test_negative_request_gives_capacity_back():
    """A negative request frees capacity within the call, so a node that
    failed a group's fit test can fit it again: no cursor then skips it."""
    table = NodeTable.from_nodes([Node("a", "C", 1.2, 8.0),
                                  Node("b", "B", 1.0, 2.0)])
    pods = [Pod(0, WORKLOADS["complex"], "topsis"),
            Pod(1, WORKLOADS["complex"], "topsis"),
            Pod(2, _ODD, "topsis"),
            Pod(3, WORKLOADS["complex"], "topsis")]
    cc = np.array([[0.9, 0.5]] * 4)
    want, _ = _oracle_greedy_assign(cc, pods, table)
    assert want == [0, 1, 0, 0]
    assert _greedy_assign(cc, pods, table) == want


def test_rank_group_and_walk_step_counters():
    """``scheduler_rank_groups`` counts distinct (row, request) rankings a
    call, ``scheduler_walk_steps`` the ranking entries it examined, which
    the shared cursor keeps at most the full walk's; a registry records
    nothing once it is switched off."""
    table = make_fleet(64, seed=6, utilization=0.3)
    kinds = list(WORKLOADS.values())
    pods = [Pod(i, kinds[i % 3], "topsis") for i in range(48)]
    sched = BatchScheduler("energy_centric", backend="numpy")
    with telemetry.enabled(telemetry.Telemetry(timelines=False)) as tel:
        _, diag = sched.select_many(pods, table)
    assert tel.counter_value("scheduler_rank_groups") == 3
    _, examined = _oracle_greedy_assign(diag["closeness"], pods, table)
    steps = tel.counter_value("scheduler_walk_steps")
    assert 0 < steps <= examined

    rng = np.random.default_rng(0)
    cc = rng.random((20, 64))                   # every row distinct
    with telemetry.enabled(telemetry.Telemetry(timelines=False)) as tel:
        _greedy_assign(cc, pods[:20], table)
        # 0.0 and -0.0 differ in their bytes: two groups, one placement
        pair = [Pod(i, WORKLOADS["light"], "topsis") for i in range(2)]
        signed = np.array([[0.0, 1.0], [-0.0, 1.0]])
        assert _greedy_assign(signed, pair, table) == [1, 1]
    assert tel.counter_value("scheduler_rank_groups") == 20 + 2

    assert telemetry.active() is telemetry.NULL
    before = {k: v[2] for k, v in tel.counters.items()}
    _greedy_assign(cc, pods[:20], table)
    assert {k: v[2] for k, v in tel.counters.items()} == before


# --- queue lengths that compile nothing new ----------------------------------
@pytest.fixture(scope="module")
def backend_compiles():
    """A running count of XLA backend compilations (persistent-cache loads
    included), as ``bench/compile_counter.py`` counts them. ``jax.monitoring``
    keeps a listener for the life of the process, so one per module."""
    import jax
    seen = [0]

    def count(event, duration_secs, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            seen[0] += 1

    jax.monitoring.register_event_duration_secs_listener(count)
    return seen


@pytest.mark.parametrize("attached", [False, True],
                         ids=["rebuild", "incremental"])
@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_unpadded_queue_lengths_compile_nothing_new(backend, attached,
                                                    backend_compiles,
                                                    monkeypatch):
    """Once each padded queue length has been scored at each count of
    distinct pod kinds, queues of any real length up to it compile no new
    program: the padding rows are dropped
    after the readback, on the host. The (p, N) scores are the first p rows
    of the padded device result bit for bit, in a contiguous array of their
    own, and match the numpy reference."""
    from repro.cluster.node import FleetState, make_fleet_nodes
    from repro.core import scheduler as scheduler_mod
    n = 100
    fleet = FleetState.from_nodes(make_fleet_nodes(n, seed=3,
                                                   utilization=0.4))
    sched = BatchScheduler("energy_centric", backend=backend)
    if attached:
        sched.attach(fleet)
    table = fleet if attached else NodeTable.from_nodes(fleet.nodes)
    ref = BatchScheduler("energy_centric", backend="numpy")
    kinds = list(WORKLOADS.values())
    queue = lambda p: [Pod(i, kinds[i % len(kinds)], "topsis")
                       for i in range(p)]
    lengths = (1, 3, 37, 255, 257)
    # every pod kind first, so that the kind cache holds them all while
    # each padded length compiles; a table that is not attached is scored
    # through a cache of its own per call, whose kind count is part of the
    # program's shape, so each padded length compiles at every count
    sched.score_queue(queue(len(kinds)), table)
    for pad in sorted({scheduler_mod._pow2_pad_len(p) for p in lengths}):
        for k in range(1, len(kinds) + 1):
            sched.score_queue([Pod(i, kinds[i % k], "topsis")
                               for i in range(pad)], table)
    padded = []
    readback = scheduler_mod._readback

    def keep(tel, cc, labels, p=None):
        # pod-minor (Fortran order), as the TPU hands the scores back
        full = np.asfortranarray(np.asarray(cc))
        padded.append(full)
        return readback(tel, full, labels, p)

    monkeypatch.setattr(scheduler_mod, "_readback", keep)
    before = backend_compiles[0]
    for p in lengths:
        got = sched.score_queue(queue(p), table)
        full = padded[-1]
        assert full.shape == (scheduler_mod._pow2_pad_len(p), n)
        assert got.shape == (p, n)
        # rows of their own: the commit gathers and reduces them without
        # striding across the padding
        assert got.flags.c_contiguous or got.flags.f_contiguous
        np.testing.assert_array_equal(got, full[:p])
        assert got.tobytes() == full[:p].tobytes()
        want = ref.score_queue(queue(p), NodeTable.from_nodes(fleet.nodes))
        finite = np.isfinite(want)
        np.testing.assert_array_equal(finite, np.isfinite(got))
        np.testing.assert_allclose(got[finite], want[finite], atol=1e-5)
    assert backend_compiles[0] == before
    assert len(padded) == len(lengths)

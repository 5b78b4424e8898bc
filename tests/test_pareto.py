"""Pareto frontier engine: weight grids, fused grid scoring, dominance.

Pins the tentpole equivalences: grid row ``s`` is bitwise
(numpy) / 1e-5 (jax, pallas) equal to scoring the queue under ``ws[s]``
alone; the paper's named schemes come back as a grid special case with
placements identical to per-scheme ``select_many``; and the dominance
filter is exact on hand-built metric sets. The property-based block needs
``hypothesis`` (requirements-dev.txt); when absent it skips cleanly.
"""
import xml.etree.ElementTree as ET

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

from repro.core import pareto, topsis
from repro.core.scheduler import BatchScheduler
from repro.core.weighting import (SCHEME_NAMES, scheme_grid,
                                  validate_weights, weights_for)
from repro.cluster.node import (FleetState, make_fleet_nodes,
                                make_paper_cluster)
from repro.cluster.workload import WORKLOADS, Pod

BENEFIT5 = np.array([False, False, True, True, True])


def make_queue(n):
    kinds = ("light", "medium", "complex")
    return [Pod(i, WORKLOADS[kinds[i % 3]], "topsis") for i in range(n)]


def rand_grid_inputs(p, n, s, seed):
    rng = np.random.default_rng(seed)
    mats = rng.uniform(0.1, 10.0, (p, n, 5))
    ws = rng.uniform(0.01, 1.0, (s, 5))
    ws /= ws.sum(axis=1, keepdims=True)
    valids = rng.random((p, n)) > 0.3
    valids[:, 0] = True          # at least one feasible node per pod
    return mats, ws, valids


# --- simplex-lattice weight grids -------------------------------------------
def test_weight_grid_unit_vectors_at_n1():
    g = pareto.weight_grid(1, 5)
    assert g.shape == (5, 5)
    assert np.array_equal(g, np.eye(5))
    g6 = pareto.weight_grid(1, 6)
    assert np.array_equal(g6, np.eye(6))


@pytest.mark.parametrize("n,criteria", [(1, 5), (2, 5), (4, 5), (3, 6)])
def test_weight_grid_counts_and_validity(n, criteria):
    g = pareto.weight_grid(n, criteria)
    assert g.shape == (pareto.grid_size(n, criteria), criteria)
    assert (g >= 0.0).all()
    assert np.allclose(g.sum(axis=1), 1.0, atol=1e-12)
    # normalized at generation: every grid scheme passes the same check
    # user-supplied vectors get
    validate_weights(g)
    # all rows distinct
    assert len({tuple(row) for row in g}) == len(g)


def test_weight_grid_upto_is_deterministic_prefix():
    ws = pareto.weight_grid_upto(512)
    assert ws.shape == (512, 5)
    full = pareto.weight_grid(pareto.lattice_n_for(512), 5)
    assert np.array_equal(ws, full[:512])
    assert pareto.lattice_n_for(5) == 1


def test_weight_grid_rejects_bad_args():
    with pytest.raises(ValueError):
        pareto.weight_grid(0, 5)
    with pytest.raises(ValueError):
        pareto.weight_grid(2, 4)


# --- weight validation (satellite bugfix) ------------------------------------
def test_validate_weights_accepts_scheme_registry():
    for name in SCHEME_NAMES:
        validate_weights(weights_for(name))
        validate_weights(weights_for(name, carbon=True))
    validate_weights(weights_for("carbon_centric"))
    validate_weights(scheme_grid())


@pytest.mark.parametrize("bad,msg", [
    (np.array([0.5, 0.5, 0.5, 0.2, 0.1]), "sums to"),
    (np.array([0.5, 0.6, -0.1, 0.0, 0.0]), "negative"),
    (np.array([0.5, 0.5, np.nan, 0.0, 0.0]), "non-finite"),
    (np.array([0.5, 0.5]), "5 weights"),
    (np.ones((2, 2, 5)) / 5.0, "vector or"),
])
def test_validate_weights_rejects(bad, msg):
    with pytest.raises(ValueError, match=msg):
        validate_weights(bad)


def test_validate_weights_names_offending_row():
    grid = np.vstack([np.full(5, 0.2), np.full(5, 0.5)])
    with pytest.raises(ValueError, match=r"weights\[1\]"):
        validate_weights(grid)


def test_scheduler_rejects_unnormalized_grid():
    sched = BatchScheduler(backend="numpy")
    pods = make_queue(3)
    nodes = make_paper_cluster()
    with pytest.raises(ValueError, match="sums to"):
        sched.score_queue_grid(pods, nodes, np.full((2, 5), 0.3))
    with pytest.raises(ValueError, match="6 weights"):
        # 6-weight rows need a carbon signal on the scheduler
        sched.score_queue_grid(pods, nodes, np.full((2, 6), 1.0 / 6.0))


# --- closeness_grid equivalence ----------------------------------------------
def device_grid(mats, ws, valids):
    """The jax grid round's program on a plain (P, N, C) tensor, each pod
    its own kind."""
    from repro.core import scheduler
    scheduler._jit_helpers()
    return np.asarray(scheduler._closeness_grid_from_kinds(
        mats, np.arange(len(mats)), ws, BENEFIT5, valids))


def test_closeness_grid_np_rows_bitwise():
    mats, ws, valids = rand_grid_inputs(4, 23, 6, seed=0)
    grid = topsis.closeness_grid_np(mats, ws, BENEFIT5, valids)
    assert grid.shape == (6, 4, 23)
    for s in range(6):
        per_scheme = topsis.batched_closeness_np(
            mats, np.broadcast_to(ws[s], (4, 5)), BENEFIT5, valids)
        assert np.array_equal(grid[s], per_scheme)
        for i in range(4):
            row = topsis.closeness_np(mats[i], ws[s], BENEFIT5,
                                      valids[i]).closeness
            assert np.array_equal(grid[s, i], row)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_closeness_grid_matches_reference(backend):
    mats, ws, valids = rand_grid_inputs(3, 37, 5, seed=1)
    want = topsis.closeness_grid_np(mats, ws, BENEFIT5, valids)
    if backend == "jax":
        got = device_grid(mats, ws, valids)
    else:
        from repro.kernels import ops
        got = np.asarray(ops.topsis_closeness_grid(mats, ws, BENEFIT5,
                                                   valid=valids))
    finite = np.isfinite(want)
    assert np.array_equal(finite, np.isfinite(got))
    assert np.max(np.abs(got[finite] - want[finite])) < 1e-5


def test_closeness_grid_no_mask_matches_masked_alltrue():
    from repro.kernels import ops
    mats, ws, _ = rand_grid_inputs(2, 9, 3, seed=2)
    alltrue = np.ones((2, 9), bool)
    a = np.asarray(ops.topsis_closeness_grid(mats, ws, BENEFIT5))
    b = np.asarray(ops.topsis_closeness_grid(mats, ws, BENEFIT5,
                                             valid=alltrue))
    assert np.array_equal(a, b)
    ref = topsis.closeness_grid_np(mats, ws, BENEFIT5)
    assert np.max(np.abs(a - ref)) < 1e-5
    assert np.max(np.abs(device_grid(mats, ws, alltrue) - ref)) < 1e-5


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=1, max_value=8),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_grid_row_property(s, seed):
    """Property: every grid row equals its per-scheme score — bitwise on
    numpy, 1e-5 on the float32 jax and pallas engines."""
    from repro.kernels import ops
    mats, ws, valids = rand_grid_inputs(3, 17, s, seed)
    want = topsis.closeness_grid_np(mats, ws, BENEFIT5, valids)
    for si in range(s):
        assert np.array_equal(
            want[si],
            topsis.batched_closeness_np(
                mats, np.broadcast_to(ws[si], (3, 5)), BENEFIT5, valids))
    for got in (device_grid(mats, ws, valids),
                np.asarray(ops.topsis_closeness_grid(mats, ws, BENEFIT5,
                                                     valid=valids))):
        finite = np.isfinite(want)
        assert np.array_equal(finite, np.isfinite(got))
        assert np.max(np.abs(got[finite] - want[finite])) < 1e-5


# --- dominance filtering ------------------------------------------------------
def test_pareto_mask_hand_built():
    m = np.array([[1.0, 1.0],     # optimal
                  [2.0, 2.0],     # dominated by 0
                  [1.0, 2.0],     # dominated by 0
                  [0.5, 3.0],     # optimal (best metric 0)
                  [1.0, 1.0]])    # exact tie with 0: both kept
    assert pareto.pareto_mask(m).tolist() == [True, False, False, True,
                                              True]


def test_pareto_mask_single_point_and_all_dominated():
    assert pareto.pareto_mask(np.array([[3.0, 7.0]])).tolist() == [True]
    # one point dominates everything else -> front is exactly that point
    m = np.array([[5.0, 5.0], [1.0, 1.0], [9.0, 2.0], [2.0, 9.0]])
    assert pareto.pareto_mask(m).tolist() == [False, True, False, False]


def test_pareto_mask_rejects_bad_input():
    with pytest.raises(ValueError):
        pareto.pareto_mask(np.ones(4))
    with pytest.raises(ValueError):
        pareto.pareto_mask(np.array([[1.0, np.inf]]))


def test_frontier_dominant_deterministic_tie_break():
    pts = [pareto.SchemePoint(i, np.eye(3)[i % 3],
                              {"a": a, "b": b})
           for i, (a, b) in enumerate([(1.0, 2.0), (2.0, 1.0),
                                       (1.0, 2.0)])]
    f = pareto.ParetoFrontier(pts, ("a", "b"))
    assert f.mask.tolist() == [True, True, True]
    # symmetric costs: normalized means tie at 0.5 -> lowest index wins
    assert f.dominant().index == 0


def test_frontier_atlas_lookup():
    pts = [pareto.SchemePoint(0, np.eye(5)[0], {"a": 1.0, "b": 1.0})]
    atlas = pareto.FrontierAtlas()
    atlas.add("low", pareto.ParetoFrontier(pts, ("a", "b")))
    assert atlas.dominant_scheme("low").index == 0
    with pytest.raises(KeyError, match="low"):
        atlas.dominant_scheme("nope")
    rep = atlas.to_report()
    assert rep["low"]["n_front"] == 1
    assert rep["low"]["dominant"]["index"] == 0


# --- paper schemes as a grid special case ------------------------------------
@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_paper_schemes_recovered_from_grid(backend):
    """Stacking the paper's named schemes as a weight grid and placing via
    ``select_many_grid`` reproduces per-scheme ``select_many`` placements
    exactly (the table6 decision path) — bitwise scores on numpy."""
    pods = make_queue(8)
    nodes = make_paper_cluster()
    grid_sched = BatchScheduler(scheme="general", backend=backend)
    assigns, diag = grid_sched.select_many_grid(pods, nodes,
                                                list(SCHEME_NAMES))
    assert len(assigns) == len(SCHEME_NAMES)
    for s, name in enumerate(SCHEME_NAMES):
        solo = BatchScheduler(scheme=name, backend=backend)
        want_assign, want_diag = solo.select_many(pods, nodes)
        assert assigns[s] == want_assign
        if backend == "numpy":
            assert np.array_equal(diag["closeness"][s],
                                  want_diag["closeness"])
        else:
            got, want = diag["closeness"][s], want_diag["closeness"]
            finite = np.isfinite(want)
            assert np.array_equal(finite, np.isfinite(got))
            assert np.max(np.abs(got[finite] - want[finite])) < 1e-5


@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_grid_incremental_matches_rebuild(backend):
    """The attached-fleet grid path (dirty-column sync + device-resident
    tensor) agrees with the full-rebuild numpy reference after churn."""
    pods = make_queue(6)
    ws = pareto.weight_grid(2, 5)          # 15 schemes
    fleet = FleetState.from_nodes(make_fleet_nodes(40, seed=3,
                                                   utilization=0.3))
    sched = BatchScheduler(scheme="general", backend=backend)
    sched.attach(fleet)
    sched.score_queue_grid(pods, fleet, ws)      # warm sync + upload
    fleet.bind(2, 1.0, 2.0)
    fleet.bind(11, 0.5, 0.5)
    fleet.release(2, 1.0, 2.0)
    got = sched.score_queue_grid(pods, fleet, ws)
    want = BatchScheduler(scheme="general",
                          backend="numpy").score_queue_grid(pods, fleet,
                                                            ws)
    finite = np.isfinite(want)
    assert np.array_equal(finite, np.isfinite(got))
    err = np.max(np.abs(got[finite] - want[finite]))
    assert err == 0.0 if backend == "numpy" else err < 1e-5


# --- metric collection + report ----------------------------------------------
def test_placement_metrics_and_frontier():
    pods = make_queue(10)
    nodes = make_fleet_nodes(32, seed=4, utilization=0.3)
    ws = pareto.weight_grid_upto(24)
    points = pareto.placement_metrics(pods, nodes, ws, backend="numpy")
    assert len(points) == 24
    for p in points:
        assert set(p.metrics) == {"energy_kj", "mean_latency_s",
                                  "unschedulable_rate"}
        assert all(np.isfinite(v) for v in p.metrics.values())
    f = pareto.frontier_for(points)
    assert 1 <= len(f.front) <= 24
    assert f.dominant() in f.front
    # the dominant pick is never dominated by any swept point
    dom = np.array([f.dominant().metrics[k] for k in f.metric_names])
    for p in points:
        row = np.array([p.metrics[k] for k in f.metric_names])
        assert not ((row <= dom).all() and (row < dom).any())


def test_placement_metrics_reads_decision_tensor():
    """One pod, one feasible node: metrics are exactly the decision
    tensor's predicted energy / runtime for that placement."""
    from repro.core.scheduler import decision_matrix_batch
    pods = make_queue(1)
    nodes = make_paper_cluster()
    ws = np.full((1, 5), 0.2)
    points = pareto.placement_metrics(pods, nodes, ws, backend="numpy")
    [pt] = points
    mats = decision_matrix_batch(pods, nodes)
    sched = BatchScheduler(scheme="general", backend="numpy")
    [assign], _ = sched.select_many_grid(pods, nodes, ws)
    a = assign[0]
    assert pt.metrics["energy_kj"] == pytest.approx(mats[0, a, 1] / 1e3)
    assert pt.metrics["mean_latency_s"] == pytest.approx(mats[0, a, 0])
    assert pt.metrics["unschedulable_rate"] == 0.0


def test_report_frontier_section_well_formed():
    from repro.telemetry.report import html_report
    pods = make_queue(8)
    nodes = make_fleet_nodes(16, seed=5, utilization=0.2)
    points = pareto.placement_metrics(pods, nodes,
                                      pareto.weight_grid_upto(12),
                                      backend="numpy")
    atlas = pareto.FrontierAtlas()
    atlas.add("baseline", pareto.frontier_for(points))
    doc = html_report(frontier=atlas.to_report())
    ET.fromstring(doc)                 # well-formed XML, as the spec pins
    assert "Pareto frontier" in doc
    assert "baseline" in doc

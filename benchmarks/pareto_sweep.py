"""Pareto weight-scheme sweep: fused grid dispatch vs serial per-scheme loop.

The frontier workload (repro.core.pareto) scores one pod queue under S
weighting schemes on one fleet snapshot — the offline what-if analysis an
operator runs to pick a scheme. This benchmark sweeps S x fleet size x
backend and times the scoring round two ways through the SAME attached
incremental machinery (FleetCriteriaCache; jax keeps the criteria tensor
device-resident, no re-upload per scheme):

  fused   — ONE ``BatchScheduler.score_queue_grid`` call over the whole
            (S, C) grid: one engine dispatch for the (S, P, N) tensor
            (jax: one gather + vmapped closeness; pallas: the
            weight-grid kernel with schemes innermost so each criteria
            node-block is fetched once; numpy: the scheme x pod reference
            loop)
  serial  — S single-scheme ``score_queue_grid`` calls, one per grid row:
            the pre-grid status quo of one scoring round per scheme. On
            numpy both modes are the same Python loop (speedup ~1x, there
            is no dispatch to amortize); the jax speedup is the headline.

Before timing, every backend's fused (S, P, N) tensor is verified against
the ``topsis.closeness_grid_np`` float64 reference at 1e-5. The reference
scores also drive the frontier lane: per-scheme greedy placements
(``_greedy_assign``), decision-tensor metrics
(``pareto.points_from_placements``), and the exact dominance filter —
``frontier_size`` and ``frontier_checksum`` are backend-independent and
gated EXACTLY by the regression check (timings are one-sided). The pallas
backend is opt-in off-TPU (interpret mode, flagged ``interpret_mode``) and
capped by ``--pallas-max-schemes``; numpy timing is capped by
``--numpy-max-schemes`` (the frontier/reference lane still runs at full S).

Run: PYTHONPATH=src python benchmarks/pareto_sweep.py \
        [--backend all|numpy|jax|pallas] [--nodes 64,1024] \
        [--schemes 5,64,512,4096] [--pods 8] [--smoke] \
        [--out BENCH_pareto.json]
"""
from __future__ import annotations

import argparse
import itertools
import time

import numpy as np

try:
    from benchmarks import common
except ImportError:          # run as a script: benchmarks/ is sys.path[0]
    import common
from repro.core import pareto, topsis
from repro.core.criteria import benefit_mask
from repro.core.scheduler import (BACKENDS, BatchScheduler, _greedy_assign,
                                  decision_matrix_batch)
from repro.cluster.node import FleetState, NodeTable, make_fleet_nodes
from repro.cluster.workload import WORKLOADS, Pod
from repro.kernels.ops import resolve_interpret
from repro.device import enable_compile_cache

DEFAULT_NODES = (64, 1024)
DEFAULT_SCHEME_COUNTS = (5, 64, 512, 4096)
DEFAULT_PODS = 8             # keeps the S=4096 (S, P, N, C) tensor in RAM
BIG_S = 512                  # fewer reps at and past this scheme count
MAX_NUMPY_SCHEMES = 64       # numpy timing cap (reference lane uncapped)
MAX_PALLAS_SCHEMES = 512     # pallas interpret-mode timing cap off-TPU


def _time(f, reps=5, warmup=2):
    for _ in range(warmup):
        f()
    t0 = time.perf_counter()
    for _ in range(reps):
        f()
    return (time.perf_counter() - t0) / reps


def make_queue(n_pods: int) -> list[Pod]:
    kinds = itertools.cycle(["light", "medium", "complex"])
    return [Pod(i, WORKLOADS[next(kinds)], "topsis") for i in range(n_pods)]


def _frontier_fingerprint(points) -> tuple[int, float]:
    """(size, checksum) of the Pareto set — an exact, order-sensitive
    membership fingerprint (sum of squared 1-based member indices, folded
    to 31 bits) so the regression gate catches any membership change."""
    front = pareto.frontier_for(points)
    members = np.flatnonzero(front.mask).astype(np.int64)
    checksum = int(((members + 1) ** 2).sum() % (2 ** 31))
    return int(front.mask.sum()), float(checksum)


def run(backends=common.DEFAULT_BACKENDS, node_counts=DEFAULT_NODES,
        scheme_counts=DEFAULT_SCHEME_COUNTS, n_pods: int = DEFAULT_PODS,
        reps: int = 5, out: str | None = "BENCH_pareto.json", seed: int = 0,
        numpy_max_schemes: int = MAX_NUMPY_SCHEMES,
        pallas_max_schemes: int = MAX_PALLAS_SCHEMES) -> dict:
    interpret_mode = resolve_interpret()
    pods = make_queue(n_pods)
    benefit = benefit_mask()
    results = []
    print("backend,n_nodes,n_schemes,pods,ms_fused,ms_serial,speedup,"
          "frontier_size")

    def emit(rec):
        results.append(rec)
        print(f"{rec['backend']},{rec['n_nodes']},{rec['n_schemes']},"
              f"{rec['pods']},{rec['ms_fused']:.3f},{rec['ms_serial']:.3f},"
              f"{rec['speedup_fused_vs_serial']:.2f},"
              f"{rec['frontier_size']}")

    for n in node_counts:
        nodes = make_fleet_nodes(n, seed=seed, utilization=0.3)
        table = NodeTable.from_nodes(nodes)
        mats = decision_matrix_batch(pods, table)
        valid = table.fits(np.asarray([p.cpu for p in pods])[:, None],
                           np.asarray([p.mem for p in pods])[:, None])
        for n_s in scheme_counts:
            ws = pareto.weight_grid_upto(n_s)
            # float64 reference: verification oracle AND the frontier lane
            want = topsis.closeness_grid_np(mats, ws, benefit, valid)
            assignments = [_greedy_assign(want[s], pods, table)
                           for s in range(n_s)]
            points = pareto.points_from_placements(ws, assignments, mats)
            frontier_size, frontier_checksum = _frontier_fingerprint(points)
            n_reps = reps if n_s < BIG_S else max(2, reps // 3)
            for backend in backends:
                if backend == "numpy" and n_s > numpy_max_schemes:
                    print(f"# skip numpy timing at S={n_s}: the serial "
                          f"reference loop is O(S*P) closeness_np calls "
                          f"(--numpy-max-schemes {numpy_max_schemes})")
                    continue
                if backend == "pallas" and interpret_mode \
                        and n_s > pallas_max_schemes:
                    print(f"# skip pallas at S={n_s}: interpret mode "
                          f"(--pallas-max-schemes {pallas_max_schemes})")
                    continue
                fleet = FleetState.from_nodes(
                    make_fleet_nodes(n, seed=seed, utilization=0.3))
                sched = BatchScheduler("general", backend=backend)
                sched.attach(fleet)
                got = sched.score_queue_grid(pods, fleet, ws)
                finite = np.isfinite(want)
                assert np.array_equal(finite, np.isfinite(got)), \
                    f"{backend}/N={n}/S={n_s}: feasibility masks differ"
                err = float(np.max(np.abs(got[finite] - want[finite])))
                assert err < 1e-5, \
                    f"{backend}/N={n}/S={n_s}: closeness err {err:.2e}"
                t_fused = _time(
                    lambda: sched.score_queue_grid(pods, fleet, ws),
                    reps=n_reps)
                # the pre-grid status quo: one scoring round per scheme
                # through the same attached incremental path (single-row
                # grids share one jit trace; S dispatches per rep)
                t_serial = _time(
                    lambda: [sched.score_queue_grid(pods, fleet,
                                                    ws[s:s + 1])
                             for s in range(n_s)],
                    reps=max(1, n_reps // 2), warmup=1)
                rec = {"backend": backend, "n_nodes": n, "n_schemes": n_s,
                       "pods": n_pods, "ms_fused": t_fused * 1e3,
                       "ms_serial": t_serial * 1e3,
                       "us_per_scheme_fused": t_fused / n_s * 1e6,
                       "speedup_fused_vs_serial": t_serial / t_fused,
                       "max_closeness_err_vs_numpy": err,
                       "frontier_size": frontier_size,
                       "frontier_checksum": frontier_checksum}
                if backend == "pallas":
                    rec["interpret_mode"] = interpret_mode
                emit(rec)
    report = {"bench": "pareto_sweep",
              "config": {"pods": n_pods, "reps": reps, "seed": seed,
                         "node_counts": list(node_counts),
                         "scheme_counts": list(scheme_counts),
                         "backends": list(backends),
                         "numpy_max_schemes": numpy_max_schemes,
                         "pallas_max_schemes": pallas_max_schemes,
                         "interpret_mode": interpret_mode},
              "results": results}
    return common.write_report(report, out)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="all",
                    help="all (= numpy,jax; pallas is opt-in, interpret "
                         "mode is slow on CPU) | comma-list from "
                         + ",".join(BACKENDS))
    ap.add_argument("--nodes", default=",".join(map(str, DEFAULT_NODES)),
                    help="comma-separated fleet sizes to sweep")
    ap.add_argument("--schemes",
                    default=",".join(map(str, DEFAULT_SCHEME_COUNTS)),
                    help="comma-separated scheme-grid sizes S to sweep")
    ap.add_argument("--pods", type=int, default=DEFAULT_PODS)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--numpy-max-schemes", type=int,
                    default=MAX_NUMPY_SCHEMES,
                    help="largest S the numpy backend is TIMED at (its "
                         "reference/frontier lane always runs at full S)")
    ap.add_argument("--pallas-max-schemes", type=int,
                    default=MAX_PALLAS_SCHEMES,
                    help="largest S the pallas backend runs at in "
                         "interpret mode (no cap on a real TPU)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI lane: N=8, S=4, 4 pods, 2 reps")
    ap.add_argument("--out", default="BENCH_pareto.json")
    args = ap.parse_args()
    backends = common.resolve_backends(args.backend)
    node_counts = common.split_csv_int(args.nodes)
    scheme_counts = common.split_csv_int(args.schemes)
    n_pods, reps = args.pods, args.reps
    if args.smoke:
        node_counts = list(common.SMOKE_NODE_COUNTS)
        scheme_counts, n_pods, reps = [4], 4, 2
    run(backends=backends, node_counts=node_counts,
        scheme_counts=scheme_counts, n_pods=n_pods, reps=reps,
        out=args.out, numpy_max_schemes=args.numpy_max_schemes,
        pallas_max_schemes=args.pallas_max_schemes)


if __name__ == "__main__":
    main()

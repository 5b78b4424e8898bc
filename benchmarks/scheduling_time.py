"""Paper §IV.C 'Scheduling Time (ms)' at fleet scale.

The paper's cluster has 4 nodes; a production fleet has tens of thousands.
This benchmark sweeps N candidate nodes and times a scheduling round three
ways:

  per-pod      — GreenPodScheduler.select in a Python loop over the queue
                 (numpy backend: the latency path, one rescore per bind;
                 only timed through N=8192 — it is off the pareto front
                 long before that)
  rebuild      — numpy only, the pre-FleetState round: flatten the Node
                 list into a fresh NodeTable snapshot, build the (P, N, C)
                 decision tensor from scratch, score (BatchScheduler's
                 full-rebuild path, kept as the reference oracle)
  incremental  — every backend, the delta-maintained round: an attached
                 FleetState with dirty-column sync (FleetCriteriaCache),
                 scoring through the per-kind (K, N, C) cache — numpy reads
                 zero-copy row views, jax gathers from the device-resident
                 donated mirror in one dispatch, pallas streams kind blocks
                 through the scalar-prefetch kernel

Each timed rep first touches ~32 random node columns (bind+release pairs:
net-zero capacity, but they dirty the columns) so the incremental path
pays its per-round delta sync honestly; ``speedup_vs_rebuild`` divides
the numpy rebuild's time by it. Every backend/mode closeness matrix is
asserted against the numpy rebuild within 1e-5 before timing. The
pallas backend runs the kernel in interpret mode off-TPU (recorded as
``interpret_mode``) and is capped at ``--pallas-max-nodes``
(default 8192) there — interpret-mode wall time is not a kernel
measurement, the cap just keeps the sweep finishable on CPU. Results are
printed as CSV and written to BENCH_scheduling.json.

Run: PYTHONPATH=src python benchmarks/scheduling_time.py \
        [--backend all|numpy|jax|pallas] \
        [--nodes 4,256,2048,8192,32768,65536] [--pods 64] \
        [--pallas-max-nodes 8192] [--smoke] [--out BENCH_scheduling.json]
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
from repro.core.scheduler import BACKENDS, BatchScheduler, GreenPodScheduler
from repro.cluster.node import FleetState, NodeTable, make_fleet_nodes
from repro.cluster.workload import WORKLOADS, Pod
from repro.kernels.ops import resolve_interpret
from repro.device import enable_compile_cache

DEFAULT_NODES = (4, 256, 2048, 8192, 32768, 65536)
MAX_PER_POD_NODES = 8192     # the per-pod baseline stops scaling here
BIG_N = 32768                # fewer reps at and past this fleet size
DIRTY_PER_ROUND = 32         # node columns touched per timed rep


def _time(f, reps=10, warmup=2):
    for _ in range(warmup):
        f()
    t0 = time.perf_counter()
    for _ in range(reps):
        f()
    return (time.perf_counter() - t0) / reps


def make_queue(n_pods: int) -> list[Pod]:
    kinds = itertools.cycle(["light", "medium", "complex"])
    return [Pod(i, WORKLOADS[next(kinds)], "topsis") for i in range(n_pods)]


def _dirty(fleet: FleetState, rng: np.random.Generator,
           k: int = DIRTY_PER_ROUND) -> None:
    """Touch ~k node columns the way an engine round does (commit +
    completion): net-zero on capacity so every timed rep scores the same
    snapshot, but each touched column goes through the dirty tracker."""
    for i in rng.integers(0, len(fleet), size=k):
        if fleet.free_cpu[i] >= 0.25 and fleet.free_mem[i] >= 0.5:
            fleet.bind(i, 0.25, 0.5)
            fleet.release(i, 0.25, 0.5)


def verify_scores(label: str, got, want, atol=1e-5) -> float:
    """Max |closeness - want| over the queue's feasible entries, where
    ``want`` is the numpy-reference score matrix for the same snapshot."""
    got = np.asarray(got)
    finite = np.isfinite(want)
    assert np.array_equal(finite, np.isfinite(got)), \
        f"{label}: feasibility masks differ"
    err = float(np.max(np.abs(got[finite] - want[finite]))) \
        if finite.any() else 0.0
    assert err < atol, f"{label}: max closeness err {err:.2e} >= {atol}"
    return err


def run(backends=BACKENDS, node_counts=DEFAULT_NODES, n_pods: int = 64,
        reps: int = 10, out: str | None = "BENCH_scheduling.json",
        seed: int = 0, pallas_max_nodes: int = MAX_PER_POD_NODES) -> dict:
    interpret_mode = resolve_interpret()
    pods = make_queue(n_pods)
    results = []
    print("mode,backend,n_nodes,pods,ms_total,us_per_pod")

    def emit(rec):
        results.append(rec)
        print(f"{rec['mode']},{rec['backend']},{rec['n_nodes']},"
              f"{rec['pods']},{rec['ms_total']:.3f},"
              f"{rec['us_per_pod']:.1f}")

    for n in node_counts:
        n_reps = reps if n < BIG_N else max(2, reps // 3)
        fleet = FleetState.from_nodes(
            make_fleet_nodes(n, seed=seed, utilization=0.3))
        rng = np.random.default_rng(seed + 1)
        if n <= MAX_PER_POD_NODES:
            # the per-pod latency baseline: P independent select() calls
            g = GreenPodScheduler("energy_centric", backend="numpy")
            table = NodeTable.from_nodes(fleet.nodes)
            t = _time(lambda: [g.select(p, table) for p in pods],
                      reps=n_reps)
            emit({"mode": "per-pod", "backend": "numpy", "n_nodes": n,
                  "pods": n_pods, "ms_total": t * 1e3,
                  "us_per_pod": t / n_pods * 1e6})
        # rebuild: flatten + full (P, N, C) build + score, per round —
        # the numpy reference every backend is verified against
        s_reb = BatchScheduler("energy_centric", backend="numpy")
        want = s_reb.score_queue(pods, NodeTable.from_nodes(fleet.nodes))
        t_reb = _time(
            lambda: (_dirty(fleet, rng),
                     s_reb.select_many(pods,
                                       NodeTable.from_nodes(fleet.nodes))),
            reps=n_reps)
        emit({"mode": "rebuild", "backend": "numpy", "n_nodes": n,
              "pods": n_pods, "ms_total": t_reb * 1e3,
              "us_per_pod": t_reb / n_pods * 1e6})
        for backend in backends:
            if backend == "pallas" and interpret_mode \
                    and n > pallas_max_nodes:
                print(f"# skip pallas at N={n}: interpret mode "
                      f"(--pallas-max-nodes {pallas_max_nodes})")
                continue
            # incremental: attached FleetState, dirty-column sync only
            s_inc = BatchScheduler("energy_centric", backend=backend)
            s_inc.attach(fleet)
            err = verify_scores(f"incremental/{backend}/N={n}",
                                s_inc.score_queue(pods, fleet), want)
            t_inc = _time(
                lambda: (_dirty(fleet, rng),
                         s_inc.select_many(pods, fleet)),
                reps=n_reps)
            rec = {"mode": "incremental", "backend": backend, "n_nodes": n,
                   "pods": n_pods, "ms_total": t_inc * 1e3,
                   "us_per_pod": t_inc / n_pods * 1e6,
                   "max_closeness_err_vs_numpy": err,
                   "speedup_vs_rebuild": t_reb / t_inc}
            if backend == "pallas":
                rec["interpret_mode"] = interpret_mode
            emit(rec)
    report = {"bench": "scheduling_time",
              "config": {"pods": n_pods, "reps": reps, "seed": seed,
                         "node_counts": list(node_counts),
                         "backends": list(backends),
                         "dirty_per_round": DIRTY_PER_ROUND,
                         "pallas_max_nodes": pallas_max_nodes,
                         "interpret_mode": interpret_mode},
              "results": results}
    return common.write_report(report, out)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="all",
                    help="all | " + " | ".join(BACKENDS))
    ap.add_argument("--nodes", default=",".join(map(str, DEFAULT_NODES)),
                    help="comma-separated fleet sizes to sweep")
    ap.add_argument("--pods", type=int, default=64)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--pallas-max-nodes", type=int,
                    default=MAX_PER_POD_NODES,
                    help="largest N the pallas backend runs at in "
                         "interpret mode (no cap on a real TPU)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI lane: N=8, 8 pods, 2 reps")
    ap.add_argument("--out", default="BENCH_scheduling.json")
    args = ap.parse_args()
    backends = common.resolve_backends(args.backend, default=BACKENDS)
    node_counts = common.split_csv_int(args.nodes)
    n_pods, reps = args.pods, args.reps
    if args.smoke:
        node_counts, n_pods, reps = list(common.SMOKE_NODE_COUNTS), 8, 2
    run(backends=backends, node_counts=node_counts, n_pods=n_pods,
        reps=reps, out=args.out, pallas_max_nodes=args.pallas_max_nodes)


if __name__ == "__main__":
    main()

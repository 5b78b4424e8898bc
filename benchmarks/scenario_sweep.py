"""Scenario sweep: energy, scheduling time, and unschedulable rate per
(scenario x scheme x backend) through the event-driven engine.

Each scenario is an (arrival process, fleet) pair well beyond the paper's
single all-at-t0 burst on 4 nodes: Poisson bursts streamed onto edge-heavy /
cloud-heavy / mixed fleets (``make_scenario_cluster``), with every TOPSIS
burst routed through ``BatchScheduler.select_many`` on the chosen backend.
Per cell we record scalar energy totals (dynamic + idle decomposition off
the power timeline), the per-pod scheduling time, the unschedulable rate,
and the length of the energy-vs-time series. Results go to
BENCH_scenarios.json.

Run: PYTHONPATH=src python benchmarks/scenario_sweep.py \
        [--smoke] [--backend all|numpy|jax|pallas] \
        [--profiles mixed,edge_heavy,cloud_heavy] [--nodes 16,256] \
        [--bursts 8] [--burst-size 16] [--schemes energy_centric,...] \
        [--out BENCH_scenarios.json]

``--smoke`` shrinks everything (8 nodes, 3 bursts of 4) so CI can exercise
the whole scenario path in seconds.
"""
from __future__ import annotations

try:
    from benchmarks import common
except ImportError:          # run as a script: benchmarks/ is sys.path[0]
    import common
from repro.cluster.node import SCENARIO_PROFILES, make_scenario_cluster
from repro.cluster.simulator import run_scenario
from repro.cluster.workload import PoissonArrivals
from repro.device import enable_compile_cache

DEFAULT_PROFILES = tuple(SCENARIO_PROFILES)
DEFAULT_NODES = (16, 256)
DEFAULT_SCHEMES = ("energy_centric", "performance_centric")
DEFAULT_BACKENDS = common.DEFAULT_BACKENDS


def run_cell(profile: str, n_nodes: int, scheme: str, backend: str,
             n_bursts: int, burst_size: int, seed: int = 0) -> dict:
    arrivals = PoissonArrivals(rate_per_s=0.2, n_bursts=n_bursts,
                               burst_size=burst_size, seed=seed)
    res = run_scenario(
        arrivals, scheme,
        cluster_factory=lambda: make_scenario_cluster(profile, n_nodes,
                                                      seed=seed),
        batch=True, batch_backend=backend)
    tl = res.timeline
    edges, _ = res.energy_series()
    return {
        "profile": profile, "n_nodes": n_nodes, "scheme": scheme,
        "backend": backend, "n_bursts": n_bursts, "burst_size": burst_size,
        "pods": len(res.records) + res.unschedulable,
        "unschedulable_rate": res.unschedulable_rate(),
        "energy_topsis_kj": res.energy_kj("topsis"),
        "energy_default_kj": res.energy_kj("default"),
        "dyn_energy_topsis_j": tl.dynamic_energy_j("topsis"),
        "idle_energy_topsis_j": tl.idle_energy_j("topsis"),
        "mean_sched_time_topsis_ms": res.mean_sched_time_ms("topsis"),
        "mean_sched_time_default_ms": res.mean_sched_time_ms("default"),
        "energy_series_points": int(len(edges)),
    }


def run(profiles=DEFAULT_PROFILES, node_counts=DEFAULT_NODES,
        schemes=DEFAULT_SCHEMES, backends=DEFAULT_BACKENDS,
        n_bursts: int = 8, burst_size: int = 16, seed: int = 0,
        out: str | None = "BENCH_scenarios.json") -> dict:
    results = []
    print("profile,n_nodes,scheme,backend,pods,unsched_rate,"
          "E_topsis_kJ,E_default_kJ,sched_ms_topsis")
    for profile, n, scheme, backend in common.iter_cells(
            profiles, node_counts, schemes, backends):
        rec = run_cell(profile, n, scheme, backend,
                       n_bursts, burst_size, seed=seed)
        results.append(rec)
        print(f"{profile},{n},{scheme},{backend},"
              f"{rec['pods']},{rec['unschedulable_rate']:.3f},"
              f"{rec['energy_topsis_kj']:.4f},"
              f"{rec['energy_default_kj']:.4f},"
              f"{rec['mean_sched_time_topsis_ms']:.3f}")
    report = {"bench": "scenario_sweep",
              "config": {"profiles": list(profiles),
                         "node_counts": list(node_counts),
                         "schemes": list(schemes),
                         "backends": list(backends),
                         "n_bursts": n_bursts, "burst_size": burst_size,
                         "seed": seed},
              "results": results}
    return common.write_report(report, out)


def main():
    enable_compile_cache()
    ap = common.sweep_parser("BENCH_scenarios.json", DEFAULT_PROFILES,
                             DEFAULT_NODES, schemes=DEFAULT_SCHEMES)
    args = ap.parse_args()
    run(profiles=common.split_csv(args.profiles),
        schemes=common.split_csv(args.schemes),
        backends=common.resolve_backends(args.backend),
        seed=args.seed, out=args.out, **common.sweep_sizes(args))


if __name__ == "__main__":
    main()

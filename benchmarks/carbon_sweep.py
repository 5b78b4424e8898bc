"""Carbon sweep: energy, operational carbon, deferral latency, and
preemption count per (scenario x scheme x backend) through the carbon-aware
event-driven engine.

Every cell streams Poisson bursts (half the pods deferrable) onto a
scenario fleet whose nodes are spread across regions with a staggered
sinusoidal grid-intensity signal — all regions start near their peak and
dip within the run, so both levers are exercised: *spatial* shifting (the
carbon-rate criterion steers placements toward currently-clean regions)
and *temporal* shifting (deferrable pods wait for the dip, bounded by
their deadline; running deferrable tasks are preempted off spiking
regions). Per cell we record scalar energy and carbon totals per
scheduler, the mean deferral latency, and the preemption count. A
verification cell re-runs ``energy_centric`` with the signal attached but
zero carbon weight and asserts placements and energy totals are bitwise
identical to the carbon-free engine (the PR-2 path).

Run: PYTHONPATH=src python benchmarks/carbon_sweep.py \
        [--smoke] [--backend all|numpy|jax|pallas] \
        [--profiles mixed,edge_heavy] [--nodes 16,64] [--bursts 8] \
        [--burst-size 16] [--schemes energy_centric,carbon_centric,...] \
        [--seed 0] [--out BENCH_carbon.json]

``--smoke`` shrinks everything (one profile, 8 nodes, 3 bursts of 4) so CI
can exercise the whole carbon path in seconds.
"""
from __future__ import annotations

import itertools

try:
    from benchmarks import common
except ImportError:          # run as a script: benchmarks/ is sys.path[0]
    import common
from repro.core.carbon import CarbonPolicy, diurnal_fleet_signal
from repro.cluster.node import DEFAULT_REGIONS, make_scenario_cluster
from repro.cluster.simulator import run_scenario
from repro.cluster.workload import PoissonArrivals
from repro.device import enable_compile_cache

DEFAULT_PROFILES = ("mixed", "edge_heavy")
DEFAULT_NODES = (16, 64)
DEFAULT_SCHEMES = ("energy_centric", "carbon_energy_balanced",
                   "carbon_centric")
DEFAULT_BACKENDS = common.DEFAULT_BACKENDS

# Signal: one sinusoidal "day" compressed to 30 min so a few-minute
# scenario sees real intensity movement. The global phase puts every
# region near its peak at t=0 (deferrable pods defer, then catch the dip);
# the stagger spreads regional peaks so a clean region usually exists
# (spatial shifting). Thresholds sit at the midline (defer) and upper
# quartile (preempt).
PERIOD_S = 1800.0
BASE, AMPLITUDE = 300.0, 200.0


def make_policy(preempt: bool = True) -> CarbonPolicy:
    sig = diurnal_fleet_signal(DEFAULT_REGIONS, base=BASE,
                               amplitude=AMPLITUDE, period_s=PERIOD_S,
                               phase_s=PERIOD_S / 4.0,
                               stagger_s=PERIOD_S / 16.0)
    return CarbonPolicy(sig, defer_threshold=BASE,
                        preempt_threshold=(BASE + 0.75 * AMPLITUDE
                                           if preempt else None),
                        check_interval_s=30.0)


def make_arrivals(n_bursts: int, burst_size: int, seed: int,
                  deferrable_share: float = 0.5) -> PoissonArrivals:
    return PoissonArrivals(rate_per_s=0.2, n_bursts=n_bursts,
                           burst_size=burst_size, seed=seed,
                           deferrable_share=deferrable_share,
                           deadline_s=PERIOD_S / 2.0)


def run_cell(profile: str, n_nodes: int, scheme: str, backend: str,
             n_bursts: int, burst_size: int, seed: int = 0) -> dict:
    res = run_scenario(
        make_arrivals(n_bursts, burst_size, seed), scheme,
        cluster_factory=lambda: make_scenario_cluster(profile, n_nodes,
                                                      seed=seed),
        batch=True, batch_backend=backend, carbon=make_policy())
    return {
        "profile": profile, "n_nodes": n_nodes, "scheme": scheme,
        "backend": backend, "n_bursts": n_bursts, "burst_size": burst_size,
        # a preempted pod has one record per run attempt: count unique pods
        "pods": len({r.pod.uid for r in res.records}) + res.unschedulable,
        "unschedulable_rate": res.unschedulable_rate(),
        "energy_topsis_kj": res.energy_kj("topsis"),
        "energy_default_kj": res.energy_kj("default"),
        "carbon_topsis_g": res.total_carbon_g("topsis"),
        "carbon_default_g": res.total_carbon_g("default"),
        "mean_deferral_latency_s": res.mean_deferral_latency_s("topsis"),
        "preemptions": res.preemptions,
        "carbon_series_points": int(len(res.carbon_series()[0])),
    }


def run_zero_weight_check(profile: str, n_nodes: int, backend: str,
                          n_bursts: int, burst_size: int,
                          seed: int = 0) -> dict:
    """energy_centric with the signal attached (zero carbon weight, no
    deferral/preemption thresholds) must reproduce the carbon-free engine
    bitwise — placements and energy totals."""
    arrivals = lambda: make_arrivals(n_bursts, burst_size, seed,
                                     deferrable_share=0.0)
    factory = lambda: make_scenario_cluster(profile, n_nodes, seed=seed)
    plain = run_scenario(arrivals(), "energy_centric",
                         cluster_factory=factory, batch=True,
                         batch_backend=backend)
    carbon = run_scenario(arrivals(), "energy_centric",
                          cluster_factory=factory, batch=True,
                          batch_backend=backend,
                          carbon=CarbonPolicy(make_policy().signal))
    same_nodes = ([r.node for r in plain.records]
                  == [r.node for r in carbon.records])
    same_energy = all(plain.energy_kj(s) == carbon.energy_kj(s)
                      for s in ("topsis", "default"))
    if not (same_nodes and same_energy):
        raise AssertionError(
            f"zero-carbon-weight run diverged from the carbon-free engine "
            f"({profile}, {n_nodes} nodes, {backend}): "
            f"placements equal={same_nodes}, energy equal={same_energy}")
    return {"profile": profile, "n_nodes": n_nodes, "backend": backend,
            "zero_weight_bitwise_match": True,
            "energy_topsis_kj": plain.energy_kj("topsis")}


def run(profiles=DEFAULT_PROFILES, node_counts=DEFAULT_NODES,
        schemes=DEFAULT_SCHEMES, backends=DEFAULT_BACKENDS,
        n_bursts: int = 8, burst_size: int = 16, seed: int = 0,
        out: str | None = "BENCH_carbon.json") -> dict:
    results, checks = [], []
    print("profile,n_nodes,scheme,backend,pods,E_topsis_kJ,C_topsis_g,"
          "defer_s,preempt")
    for profile, n in itertools.product(profiles, node_counts):
        for scheme, backend in itertools.product(schemes, backends):
            rec = run_cell(profile, n, scheme, backend,
                           n_bursts, burst_size, seed=seed)
            results.append(rec)
            print(f"{profile},{n},{scheme},{backend},"
                  f"{rec['pods']},{rec['energy_topsis_kj']:.4f},"
                  f"{rec['carbon_topsis_g']:.4f},"
                  f"{rec['mean_deferral_latency_s']:.1f},"
                  f"{rec['preemptions']}")
        checks.append(run_zero_weight_check(profile, n, backends[0],
                                            n_bursts, burst_size,
                                            seed=seed))
        print(f"{profile},{n}: zero-carbon-weight run matches the "
              f"carbon-free engine bitwise")
    # headline: carbon_centric vs energy_centric carbon reduction per cell
    summary = []
    by_key = {(r["profile"], r["n_nodes"], r["backend"], r["scheme"]): r
              for r in results}
    for (profile, n, backend, scheme), r in by_key.items():
        if scheme != "carbon_centric":
            continue
        base = by_key.get((profile, n, backend, "energy_centric"))
        if base and base["carbon_topsis_g"] > 0:
            summary.append({
                "profile": profile, "n_nodes": n, "backend": backend,
                "carbon_reduction_pct": 100.0
                * (1.0 - r["carbon_topsis_g"] / base["carbon_topsis_g"])})
    for s in summary:
        print(f"carbon_centric vs energy_centric "
              f"({s['profile']}, {s['n_nodes']}, {s['backend']}): "
              f"{s['carbon_reduction_pct']:.1f}% less carbon")
    report = {"bench": "carbon_sweep",
              "config": {"profiles": list(profiles),
                         "node_counts": list(node_counts),
                         "schemes": list(schemes),
                         "backends": list(backends),
                         "n_bursts": n_bursts, "burst_size": burst_size,
                         "seed": seed, "period_s": PERIOD_S,
                         "base": BASE, "amplitude": AMPLITUDE},
              "results": results,
              "zero_weight_checks": checks,
              "carbon_reduction_summary": summary}
    return common.write_report(report, out)


def main():
    enable_compile_cache()
    ap = common.sweep_parser("BENCH_carbon.json", DEFAULT_PROFILES,
                             DEFAULT_NODES, schemes=DEFAULT_SCHEMES)
    args = ap.parse_args()
    profiles = common.split_csv(args.profiles)
    run(profiles=profiles[:1] if args.smoke else profiles,
        schemes=common.split_csv(args.schemes),
        backends=common.resolve_backends(args.backend),
        seed=args.seed, out=args.out, **common.sweep_sizes(args))


if __name__ == "__main__":
    main()

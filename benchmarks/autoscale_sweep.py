"""Autoscale sweep: energy, carbon, and latency per (scenario x policy x
backend) through the elastic event-driven engine.

Every cell streams Poisson bursts (half the pods deferrable, with real
deadlines) onto a scenario fleet with a flat carbon signal attached (for
carbon accounting — zero carbon weight, so placements stay comparable) and
one of four elasticity policies:

  * ``none``         — today's engine: no lifecycle, no state ledger. Its
                       fleet idle energy is the *always-on analytic
                       baseline* sum(idle_power) x horizon — what a fleet
                       without a lifecycle actually pays.
  * ``always_on``    — AutoscalePolicy(idle_timeout_s=inf): full state
                       accounting, nodes never sleep. Sanity row: its fleet
                       idle energy must equal the analytic baseline of its
                       own horizon.
  * ``idle_timeout`` — nodes empty for 60 s fall asleep; queue pressure
                       wakes the TOPSIS-best sleeping node.
  * ``consolidate``  — idle-timeout plus a periodic drain pass that
                       migrates low-utilization nodes' tasks and puts the
                       nodes straight to sleep.

Per cell we record fleet idle energy / total fleet energy / fleet carbon
(state ledger included), per-scheduler task energy, mean start delay and
exec time (wake latencies and migration reruns show up here), and the
wake/sleep/migration counters. The headline is the fleet idle-energy
reduction of ``idle_timeout`` (and ``consolidate``) vs the ``none``
baseline, asserted positive on at least one swept fleet — the acceptance
invariant (tight fleets that never idle long enough legitimately sit at
~0%) — along with a per-record check that no deferrable pod ever started
past its deadline.

Run: PYTHONPATH=src python benchmarks/autoscale_sweep.py \
        [--smoke] [--backend all|numpy|jax|pallas] \
        [--profiles mixed,edge_heavy] [--nodes 16,64] [--bursts 8] \
        [--burst-size 16] [--seed 0] [--out BENCH_autoscale.json]

``--smoke`` shrinks everything (one profile, 8 nodes, 3 bursts of 4) so CI
can exercise the whole elastic path in seconds.
"""
from __future__ import annotations

import math

try:
    from benchmarks import common
except ImportError:          # run as a script: benchmarks/ is sys.path[0]
    import common
from repro.core.carbon import CarbonPolicy, ConstantCarbon
from repro.core.elastic import AutoscalePolicy, always_on_fleet_idle_kj
from repro.cluster.node import make_scenario_cluster
from repro.cluster.simulator import run_scenario
from repro.cluster.workload import PoissonArrivals
from repro.device import enable_compile_cache

DEFAULT_PROFILES = ("mixed", "edge_heavy")
DEFAULT_NODES = (16, 64)
DEFAULT_BACKENDS = common.DEFAULT_BACKENDS
CARBON_INTENSITY = 400.0          # flat gCO2/kWh: accounting only
DEADLINE_S = 900.0

POLICIES: dict[str, AutoscalePolicy | None] = {
    "none": None,
    "always_on": AutoscalePolicy(idle_timeout_s=math.inf),
    "idle_timeout": AutoscalePolicy(idle_timeout_s=60.0, min_awake=1),
    "consolidate": AutoscalePolicy(idle_timeout_s=60.0, min_awake=1,
                                   consolidate_interval_s=30.0,
                                   consolidate_util_below=0.3),
}


def _mean_start_delay_s(res) -> float:
    """Mean wait between arrival and first start per pod (wake latencies
    and capacity queueing both land here)."""
    first: dict[int, float] = {}
    arrival: dict[int, float] = {}
    for r in res.records:
        arrival[r.pod.uid] = r.arrival_s
        cur = first.get(r.pod.uid)
        if cur is None or r.start_s < cur:
            first[r.pod.uid] = r.start_s
    if not first:
        return 0.0
    return sum(first[u] - arrival[u] for u in first) / len(first)


def _check_deadlines(res) -> None:
    """No deferrable pod's attempt may start past its deadline (drains and
    wake latencies included)."""
    for r in res.records:
        if r.pod.deferrable:
            assert r.start_s <= r.arrival_s + r.pod.deadline_s + 1e-9, (
                f"deferrable pod {r.pod.uid} started at {r.start_s} past "
                f"deadline {r.arrival_s + r.pod.deadline_s}")


def run_cell(profile: str, n_nodes: int, policy_name: str, backend: str,
             n_bursts: int, burst_size: int, seed: int = 0) -> dict:
    nodes = make_scenario_cluster(profile, n_nodes, seed=seed)
    res = run_scenario(
        PoissonArrivals(rate_per_s=0.2, n_bursts=n_bursts,
                        burst_size=burst_size, seed=seed,
                        deferrable_share=0.5, deadline_s=DEADLINE_S),
        "energy_centric",
        cluster_factory=lambda: make_scenario_cluster(profile, n_nodes,
                                                      seed=seed),
        batch=True, batch_backend=backend,
        carbon=CarbonPolicy(ConstantCarbon(CARBON_INTENSITY)),
        autoscale=POLICIES[policy_name])
    _check_deadlines(res)
    horizon = max((r.start_s + r.runtime_s for r in res.records),
                  default=0.0)
    if policy_name == "none":
        # the lifecycle-free engine pays every node's idle power for the
        # whole run: the always-on analytic baseline
        fleet_idle_kj = always_on_fleet_idle_kj(nodes, horizon)
    else:
        fleet_idle_kj = res.fleet_idle_energy_kj()
    dyn_kj = res.timeline.dynamic_energy_j(None) / 1000.0
    return {
        "profile": profile, "n_nodes": n_nodes, "policy": policy_name,
        "backend": backend, "n_bursts": n_bursts, "burst_size": burst_size,
        "pods": len({r.pod.uid for r in res.records}) + res.unschedulable,
        "unschedulable_rate": res.unschedulable_rate(),
        "horizon_s": horizon,
        "fleet_idle_energy_kj": fleet_idle_kj,
        "fleet_energy_kj": dyn_kj + fleet_idle_kj,
        "fleet_carbon_g": (res.fleet_carbon_g() if policy_name != "none"
                           else (dyn_kj + fleet_idle_kj) * 1000.0
                           * CARBON_INTENSITY / 3.6e6),
        "energy_topsis_kj": res.energy_kj("topsis"),
        "energy_default_kj": res.energy_kj("default"),
        "mean_start_delay_s": _mean_start_delay_s(res),
        "mean_exec_time_topsis_s": res.mean_exec_time_s("topsis"),
        "wakes": res.wakes, "sleeps": res.sleeps,
        "migrations": res.migrations,
    }


def run(profiles=DEFAULT_PROFILES, node_counts=DEFAULT_NODES,
        policies=tuple(POLICIES), backends=DEFAULT_BACKENDS,
        n_bursts: int = 8, burst_size: int = 16, seed: int = 0,
        out: str | None = "BENCH_autoscale.json") -> dict:
    results = []
    print("profile,n_nodes,policy,backend,pods,fleet_idle_kJ,fleet_kJ,"
          "delay_s,wakes,sleeps,migr")
    for profile, n, policy_name, backend in common.iter_cells(
            profiles, node_counts, policies, backends):
        rec = run_cell(profile, n, policy_name, backend,
                       n_bursts, burst_size, seed=seed)
        results.append(rec)
        print(f"{profile},{n},{policy_name},{backend},"
              f"{rec['pods']},"
              f"{rec['fleet_idle_energy_kj']:.4f},"
              f"{rec['fleet_energy_kj']:.4f},"
              f"{rec['mean_start_delay_s']:.2f},"
              f"{rec['wakes']},{rec['sleeps']},"
              f"{rec['migrations']}")
    # headline: fleet idle-energy reduction vs the no-policy baseline
    summary = []
    by_key = {(r["profile"], r["n_nodes"], r["backend"], r["policy"]): r
              for r in results}
    for (profile, n, backend, policy_name), r in by_key.items():
        if policy_name in ("none", "always_on"):
            continue
        base = by_key.get((profile, n, backend, "none"))
        if base and base["fleet_idle_energy_kj"] > 0:
            summary.append({
                "profile": profile, "n_nodes": n, "backend": backend,
                "policy": policy_name,
                "idle_reduction_pct": 100.0
                * (1.0 - r["fleet_idle_energy_kj"]
                   / base["fleet_idle_energy_kj"])})
    for s in summary:
        print(f"{s['policy']} vs none ({s['profile']}, {s['n_nodes']}, "
              f"{s['backend']}): {s['idle_reduction_pct']:.1f}% less fleet "
              f"idle energy")
    # acceptance: idle_timeout cuts fleet idle energy on every fleet swept
    assert any(s["policy"] == "idle_timeout" and s["idle_reduction_pct"] > 0
               for s in summary), \
        "idle_timeout policy failed to reduce fleet idle energy anywhere"
    report = {"bench": "autoscale_sweep",
              "config": {"profiles": list(profiles),
                         "node_counts": list(node_counts),
                         "policies": list(policies),
                         "backends": list(backends),
                         "n_bursts": n_bursts, "burst_size": burst_size,
                         "seed": seed, "deadline_s": DEADLINE_S,
                         "carbon_intensity": CARBON_INTENSITY},
              "results": results,
              "idle_reduction_summary": summary}
    return common.write_report(report, out)


def main():
    enable_compile_cache()
    ap = common.sweep_parser("BENCH_autoscale.json", DEFAULT_PROFILES,
                             DEFAULT_NODES, policies=tuple(POLICIES))
    args = ap.parse_args()
    profiles = common.split_csv(args.profiles)
    run(profiles=profiles[:1] if args.smoke else profiles,
        policies=common.split_csv(args.policies),
        backends=common.resolve_backends(args.backend),
        seed=args.seed, out=args.out, **common.sweep_sizes(args))


if __name__ == "__main__":
    main()

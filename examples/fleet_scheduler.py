"""Beyond-paper: GreenPod TOPSIS as the placement engine for a TPU fleet.

Loads the compiled dry-run roofline records (launch/dryrun.py output) as
schedulable JOBS and places them on a heterogeneous fleet of slices with the
paper's weighting schemes. Shows the energy-centric vs performance-centric
allocation difference — the TPU analogue of paper §V.D — and straggler
re-placement.

Run: PYTHONPATH=src python examples/fleet_scheduler.py [dryrun_dir]
"""
import sys

from repro.launch import fleet

dryrun_dir = sys.argv[1] if len(sys.argv) > 1 else "experiments/dryrun"
jobs = fleet.load_jobs(dryrun_dir)
if not jobs:
    # standalone demo jobs if no dry-run artifacts exist yet
    jobs = [fleet.Job("llama3-8b", "train_4k", 256, 1.5, 12.0, 8.0, 8e9),
            fleet.Job("gemma-7b", "prefill_32k", 256, 0.6, 3.5, 1.7, 8e9),
            fleet.Job("rwkv6-1.6b", "decode_32k", 256, 1e-5, 1e-3, 4e-4,
                      1e9)]
print(f"{len(jobs)} jobs loaded from {dryrun_dir}")


def new_fleet():
    return [fleet.Slice("v5e-0", 256, 256, "v5e"),
            fleet.Slice("v5e-1", 256, 256, "v5e"),
            fleet.Slice("v4-0", 256, 256, "v4"),
            fleet.Slice("v5p-0", 256, 256, "v5p"),
            fleet.Slice("v5p-1", 512, 512, "v5p")]


for scheme in ("energy_centric", "performance_centric"):
    slices = new_fleet()
    placed = fleet.schedule_queue(jobs[:5], slices, scheme)
    print(f"\n--- scheme: {scheme}")
    for job, idx in placed:
        where = slices[idx].name if idx is not None else "UNSCHEDULABLE"
        step, energy = (fleet.job_on_slice(job, slices[idx])
                        if idx is not None else (float('nan'), float('nan')))
        print(f"  {job.arch:22s} {job.shape:12s} -> {where:8s} "
              f"step={step:9.3e}s energy={energy / 1e3:9.2f} kJ")

# --- straggler mitigation -------------------------------------------------------
slices = new_fleet()
job = jobs[0]
cur, _ = fleet.place(job, slices, "energy_centric")
print(f"\njob {job.arch}/{job.shape} initially on {slices[cur].name}")
new = fleet.replace_slice(job, slices, cur, "energy_centric")
print(f"straggler alert -> degraded {slices[cur].name} (health "
      f"{slices[cur].health:.1f}x), re-placed on {slices[new].name}")

# --- fleet-scale batched scheduling ---------------------------------------------
# The paper's cluster has 4 nodes; the batched engine scores a whole queue
# of pods against thousands of candidate nodes in one TOPSIS pass
# (BatchScheduler.select_many). numpy rebuilds the decision tensor from a
# snapshot as the reference; jax scores the fleet it is attached to
# through the per-kind criteria cache, gathered on the device (see
# benchmarks/scheduling_time.py for the full sweep).
import time

import numpy as np

from repro.core.scheduler import BatchScheduler
from repro.cluster.node import FleetState, NodeTable, make_fleet_nodes
from repro.cluster.workload import WORKLOADS, Pod

N_NODES, N_PODS = 2048, 64
fleet = FleetState.from_nodes(make_fleet_nodes(N_NODES, seed=0,
                                               utilization=0.3))
queue = [Pod(i, WORKLOADS[("light", "medium", "complex")[i % 3]], "topsis")
         for i in range(N_PODS)]
print(f"\n--- batched fleet scheduling: {N_PODS} pods x {N_NODES} nodes")
reference = None
for backend in ("numpy", "jax"):
    sched = BatchScheduler("energy_centric", backend=backend)
    if backend == "numpy":
        table = NodeTable.from_nodes(fleet.nodes)   # rebuilt snapshot
    else:
        sched.attach(fleet)
        table = fleet
    sched.select_many(queue, table)            # warm up (jit compile)
    t0 = time.perf_counter()
    assignments, diag = sched.select_many(queue, table)
    dt = time.perf_counter() - t0
    placed = sum(a is not None for a in assignments)
    cc = diag["closeness"]
    if reference is None:
        reference = cc
    finite = np.isfinite(reference)
    assert np.array_equal(finite, np.isfinite(cc))
    err = float(np.max(np.abs(cc[finite] - reference[finite])))
    assert err < 1e-5, f"{backend}: closeness err {err:.2e} vs numpy"
    print(f"  {backend:6s}: {placed}/{N_PODS} placed in {dt * 1e3:7.2f} ms "
          f"({diag['per_pod_time_s'] * 1e6:.0f} us/pod, "
          f"max closeness err vs numpy {err:.1e})")

# --- event-driven scenario: Poisson bursts, time-resolved energy ----------------
# Beyond the one-shot queue above: stream Poisson arrival bursts onto an
# edge-heavy fleet through the event-driven engine (run_scenario), each
# burst scored in one select_many pass, energy read off the per-node power
# timeline as a cumulative series instead of a single post-hoc total.
from repro.cluster.node import make_scenario_cluster
from repro.cluster.simulator import run_scenario
from repro.cluster.workload import PoissonArrivals

arrivals = PoissonArrivals(rate_per_s=0.2, n_bursts=6, burst_size=12, seed=0)
res = run_scenario(arrivals, "energy_centric",
                   cluster_factory=lambda: make_scenario_cluster(
                       "edge_heavy", 64, seed=0),
                   batch=True, batch_backend="jax")
print(f"\n--- event-driven scenario: {arrivals.total_pods()} pods in "
      f"{arrivals.n_bursts} Poisson bursts on 64 edge-heavy nodes")
# SimResult.summary() rolls up the per-scheduler metrics the sweeps record
summary = res.summary()
sched_stats = summary["schedulers"]
print(f"  unschedulable rate: {summary['unschedulable_rate']:.3f}   "
      f"TOPSIS {sched_stats['topsis']['energy_kj']:.2f} kJ vs "
      f"default {sched_stats['default']['energy_kj']:.2f} kJ")
print(f"  TOPSIS per-pod mean: {sched_stats['topsis']['mean_energy_kj']:.3f} kJ, "
      f"{sched_stats['topsis']['mean_sched_time_ms']:.2f} ms/decision, "
      f"allocation {sched_stats['topsis']['allocation']}")
edges, joules = res.energy_series("topsis")
for k in range(0, len(edges), max(1, len(edges) // 6)):
    print(f"  t={edges[k]:8.1f}s  cumulative TOPSIS energy "
          f"{joules[k] / 1e3:7.3f} kJ")

# --- carbon-aware scheduling: grid signals, deferral, preemption ----------------
# The fleet's nodes sit in regions with a staggered sinusoidal grid-carbon
# signal (all near peak at t=0, dipping within the run). carbon_centric
# weights the sixth TOPSIS criterion (node power x regional intensity at
# decision time) to chase clean regions; the CarbonPolicy additionally
# defers deferrable pods until the fleet-wide dip (bounded by their
# deadline) and preempts running deferrable tasks off spiking regions.
# Carbon is integrated exactly over the power timeline (power x intensity).
from repro.core.carbon import CarbonPolicy, diurnal_fleet_signal

period = 1800.0
signal = diurnal_fleet_signal(base=300.0, amplitude=200.0, period_s=period,
                              phase_s=period / 4.0, stagger_s=period / 16.0)
policy = CarbonPolicy(signal, defer_threshold=300.0,
                      preempt_threshold=450.0, check_interval_s=30.0)
carbon_arrivals = lambda: PoissonArrivals(
    rate_per_s=0.2, n_bursts=6, burst_size=12, seed=0,
    deferrable_share=0.5, deadline_s=period / 2.0)
print("\n--- carbon-aware scenario: staggered diurnal signal on 64 mixed "
      "nodes")
for scheme in ("energy_centric", "carbon_centric"):
    res = run_scenario(carbon_arrivals(), scheme,
                       cluster_factory=lambda: make_scenario_cluster(
                           "mixed", 64, seed=0),
                       batch=True, batch_backend="jax", carbon=policy)
    print(f"  {scheme:22s}: {res.energy_kj('topsis'):6.2f} kJ  "
          f"{res.total_carbon_g('topsis'):6.3f} gCO2  "
          f"defer {res.mean_deferral_latency_s('topsis'):5.1f}s  "
          f"preemptions {res.preemptions}")
edges, grams = res.carbon_series("topsis")
for k in range(0, len(edges), max(1, len(edges) // 4)):
    print(f"  t={edges[k]:8.1f}s  cumulative TOPSIS carbon "
          f"{grams[k]:7.4f} g")

# --- elastic fleet: idle-timeout sleep + TOPSIS-driven consolidation ------------
# Without a node lifecycle the fleet pays every node's idle power for the
# whole run. AutoscalePolicy sleeps nodes empty past the idle timeout
# (queue pressure wakes the TOPSIS-best sleeping node back up; pods landing
# on a WAKING node start after its wake latency), and the consolidation
# pass drains low-utilization nodes through the preemption machinery, then
# puts them straight to sleep. Fleet idle energy — busy-union idle + the
# IDLE/ASLEEP/WAKING state ledger + wake surges — drops accordingly.
from repro.core.elastic import AutoscalePolicy, always_on_fleet_idle_kj

elastic_arrivals = lambda: PoissonArrivals(rate_per_s=0.2, n_bursts=6,
                                           burst_size=12, seed=0)
mixed_fleet = lambda: make_scenario_cluster("mixed", 64, seed=0)
print("\n--- elastic fleet: idle-timeout + consolidation on 64 mixed nodes")
runs = {}
for name, pol in (
        ("no policy (always-on)", None),
        ("idle-timeout 60s", AutoscalePolicy(idle_timeout_s=60.0)),
        ("+ consolidation", AutoscalePolicy(idle_timeout_s=60.0,
                                            consolidate_interval_s=30.0,
                                            consolidate_util_below=0.3))):
    res = run_scenario(elastic_arrivals(), "energy_centric",
                       cluster_factory=mixed_fleet, batch=True,
                       batch_backend="jax", autoscale=pol)
    horizon = max(r.start_s + r.runtime_s for r in res.records)
    if pol is None:
        # lifecycle-free engine: every node draws idle power all run long
        idle_kj = always_on_fleet_idle_kj(mixed_fleet(), horizon)
    else:
        idle_kj = res.fleet_idle_energy_kj()
    runs[name] = idle_kj
    print(f"  {name:22s}: fleet idle {idle_kj:7.2f} kJ  "
          f"wakes {res.wakes:2d}  sleeps {res.sleeps:2d}  "
          f"migrations {res.migrations:2d}")
base = runs["no policy (always-on)"]
for name, kj in runs.items():
    if name != "no policy (always-on)":
        print(f"  {name:22s}: {100.0 * (1.0 - kj / base):.1f}% less fleet "
              f"idle energy than the always-on baseline")

# --- flight recorder: telemetry, decision latency, Perfetto trace ---------------
# Re-run the carbon+autoscale scenario with the flight recorder on. The
# recorder is a pure observer (placements and energy totals are bitwise
# identical with it enabled — tests/test_telemetry.py pins this); what it
# adds is the operator view: engine/cache counters, per-decision latency
# histograms, and a Chrome trace-event file for ui.perfetto.dev with one
# track group per node (task lanes + power states) and one per policy.
from repro.core import telemetry
from repro.telemetry.export import write_perfetto

with telemetry.enabled() as tel:
    res = run_scenario(carbon_arrivals(), "carbon_centric",
                       cluster_factory=mixed_fleet, batch=True,
                       batch_backend="jax", carbon=policy,
                       autoscale=AutoscalePolicy(idle_timeout_s=60.0))
print("\n--- flight recorder: carbon+autoscale scenario, telemetry on")
print(f"  events: "
      + "  ".join(f"{k}={int(tel.counter_value('engine_events', kind=k))}"
                  for k in ("arrival", "completion", "carbon_check",
                            "wake_done")))
print(f"  rounds {len([s for s in tel.spans if s['name'] == 'engine_round'])}"
      f"  deferral holds "
      f"{int(tel.counter_value('policy_deferred_pods', policy='CarbonScheduling'))}"
      f"  wakes "
      f"{int(tel.counter_value('policy_node_wakes', policy='AutoscaleScheduling'))}")
hist = tel.histogram("scheduler_batch_seconds", scheduler="topsis-batch",
                     backend="jax")
if hist is not None:
    print(f"  batch decision latency ({hist.count} rounds, "
          f"min {hist.min * 1e3:.2f} ms, max {hist.max * 1e3:.2f} ms):")
    for edge, c in zip(hist.edges, hist.counts):
        if c:
            print(f"    le {edge * 1e3:9.3f} ms : {'#' * c} {c}")
trace_path = write_perfetto(res, "fleet_scheduler.trace.json",
                            trace_name="carbon+autoscale demo")
print(f"  wrote {trace_path} — open at https://ui.perfetto.dev")

# --- why TOPSIS picked that node: per-criterion attribution ---------------------
# explain=True (numpy scoring) records, per decision, how each criterion
# moved the winner-vs-runner-up closeness gap — the deltas sum to the gap
# exactly, so "why this node" reads off as six signed numbers.
res = run_scenario(elastic_arrivals(), "energy_centric",
                   cluster_factory=mixed_fleet, batch=True,
                   batch_backend="numpy", explain=True)
exp = max((e for e in res.explanations if e["runner_up"] is not None),
          key=lambda e: abs(e["gap"]))
print(f"\n--- decision explainability: pod {exp['pod']} -> {exp['node']} "
      f"(runner-up {exp['runner_up_node']}, "
      f"gap {exp['gap']:+.4f} closeness)")
for c in sorted(exp["contributions"], key=lambda c: -abs(c["delta_cc"])):
    print(f"  {c['criterion']:16s} delta_cc {c['delta_cc']:+.4f}   "
          f"winner {c['winner_value']:10.4f}  vs  "
          f"runner-up {c['runner_up_value']:10.4f}")

# --- operator HTML report + benchmark regression gate ---------------------------
# With the recorder on, the registry also carries sim-time timelines
# (queue depth, fleet power, cumulative energy/carbon at every clock
# advance); html_report renders them — plus the run summary and the
# TOPSIS explanation table — as a single dependency-free HTML file with
# inline-SVG charts, the same artifact CI uploads for every PR.
from repro.telemetry.report import write_html_report

with telemetry.enabled() as tel:
    res = run_scenario(elastic_arrivals(), "energy_centric",
                       cluster_factory=mixed_fleet, batch=True,
                       batch_backend="numpy", explain=True)
report_path = write_html_report("fleet_scheduler_report.html", tel=tel,
                                result=res, title="fleet scheduler demo")
print(f"\n--- operator report: wrote {report_path} "
      f"({len(tel.timeseries)} series charted) — open in a browser")

# Cross-run regression gating: compare_reports diffs two recorded
# BENCH_*.json cell-by-cell (exact physics at 1e-6 relative, wall-clock
# timings one-sided at +75%). `python -m benchmarks.run --check` runs
# this against the committed baselines and exits nonzero on regression.
from repro.telemetry.baseline import compare_reports, format_verdict

cells = [{"profile": "mixed", "n_nodes": 8, "backend": "numpy",
          "energy_topsis_kj": 10.0, "mean_sched_time_topsis_ms": 5.0}]
baseline = {"bench": "demo_sweep", "results": cells}
drifted = {"bench": "demo_sweep",
           "results": [dict(cells[0], energy_topsis_kj=10.4,
                            mean_sched_time_topsis_ms=6.0)]}
print("\n--- regression gate: 4% energy drift trips, 20% timing "
      "noise does not")
print(format_verdict(compare_reports(drifted, baseline)))

# --- Pareto frontier: 512 weighting schemes in one fused dispatch ---------------
# The paper ships five hand-named schemes; the grid engine scores an
# entire simplex lattice of them at once. select_many_grid places the
# same queue under all 512 schemes in one (S x P x N) dispatch per
# round, placement_metrics reads predicted energy / latency / carbon off
# the decision tensor, pareto_mask keeps only the non-dominated schemes,
# and the atlas answers the operator question: which weighting dominates
# under which carbon regime?
from repro.core import pareto
from repro.core.carbon import ConstantCarbon

frontier_pods = [Pod(i, WORKLOADS[("light", "medium", "complex")[i % 3]],
                     "topsis") for i in range(24)]
frontier_nodes = make_scenario_cluster("mixed", 128, seed=0)
ws = pareto.weight_grid_upto(512, criteria=6)   # 6th column = carbon weight
# two regional regimes (flat intensities would make carbon ∝ energy and
# collapse the trade-off): a mild split vs a hard one where eu-west runs
# on a nearly clean grid while ap-south burns coal
regimes = {"mild split (300±100)": ConstantCarbon(300.0, per_region={
               "eu-west": 200.0, "ap-south": 400.0}),
           "hard split (50 vs 700)": ConstantCarbon(400.0, per_region={
               "eu-west": 50.0, "ap-south": 700.0})}
atlas = pareto.FrontierAtlas()
print(f"\n--- Pareto frontier: {len(ws)} weighting schemes x "
      f"{len(frontier_pods)} pods x {len(frontier_nodes)} nodes per regime")
for regime, signal in regimes.items():
    points = pareto.placement_metrics(frontier_pods, frontier_nodes, ws,
                                      backend="jax", carbon_signal=signal)
    front = pareto.frontier_for(points)
    atlas.add(regime, front)
    dom = atlas.dominant_scheme(regime)
    w = ", ".join(f"{v:.2f}" for v in dom.weights)
    print(f"  {regime:24s}: {len(front.front):3d}/{len(points)} "
          f"Pareto-optimal; dominant scheme #{dom.index} w=[{w}]")
    print(f"    {'  '.join(f'{k}={v:.4g}' for k, v in dom.metrics.items())}")

# the same atlas feeds the HTML report's frontier section: one scatter +
# table per regime, dominant pick starred
report_path = write_html_report("fleet_frontier_report.html",
                                frontier=atlas.to_report(),
                                title="weighting-scheme frontier")
print(f"  wrote {report_path} — frontier scatter + table per regime")

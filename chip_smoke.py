"""Chip smoke test: the GreenPod scheduling round on one TPU, end to end.

Drives ``run_scenario(..., batch=True)`` — the event engine's batched
TOPSIS round: criteria-cache sync, one scoring dispatch on the device,
readback, greedy commit — on a 5,000-node fleet (Kubernetes' documented
per-cluster limit, kubernetes.io "Considerations for large clusters") under
16,384 Poisson-burst pods, all routed to the batched TOPSIS scheduler.
Three phases, each run twice in this one process, cold and warm:

  jax             energy_centric, no policies: the device-resident kind
                  mirror and one fused gather+closeness dispatch per round
  pallas          the same scenario through the compiled scalar-prefetch
                  kind kernel (Mosaic, no interpret mode)
  jax+carbon+autoscale
                  the carbon and autoscale sweeps' knobs: the 6-criterion
                  mirror, carbon-column rewrites and pressure wakes

The warm run checks every device scoring round against the float64
numpy reference on the same pods, fleet snapshot, weights and masks:
closeness within 1e-5 on feasible entries and the same -inf feasibility
pattern. The warm run must compile nothing. The jax phase's placements are
also compared with the numpy backend's: the placed count must match.

Usage, from the repository root on a machine with one TPU:

    python3 chip_smoke.py

One JSON line per phase, then, as the last line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or on any failed check, it exits non-zero without that line.
Nothing is written but JAX's compile cache (see ``repro.device``).
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_NODES = 5000
TRAFFIC = dict(rate_per_s=0.5, n_bursts=64, burst_size=256,
               topsis_share=1.0, seed=0)
ATOL = 1e-5

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(Exception):
    pass


class CompileCounter:
    """Backend compilations (persistent-cache loads included) and
    persistent-cache hits since construction, from ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kwargs):
        if event == _BACKEND_COMPILE:
            self.compiles += 1

    def _event(self, event, **kwargs):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.cache_hits


class RoundChecker:
    """Wraps ``BatchScheduler.score_queue`` for one run and checks each
    round's (P, N) closeness against ``topsis.batched_closeness_np``
    rebuilt from scratch off the same fleet snapshot."""

    def __init__(self):
        self.rounds = 0
        self.max_err = 0.0
        self.seconds = 0.0

    @contextlib.contextmanager
    def installed(self):
        from repro.core.scheduler import BatchScheduler
        score_queue = BatchScheduler.score_queue

        def checked(sched, pods, nodes, now=0.0, exclude=None):
            cc = score_queue(sched, pods, nodes, now=now, exclude=exclude)
            t0 = time.perf_counter()
            self.check(sched, pods, nodes, now, exclude, cc)
            self.seconds += time.perf_counter() - t0
            return cc

        BatchScheduler.score_queue = checked
        try:
            yield self
        finally:
            BatchScheduler.score_queue = score_queue

    def check(self, sched, pods, nodes, now, exclude, cc) -> None:
        import numpy as np
        from repro.cluster.node import NodeTable
        from repro.core import topsis
        from repro.core.criteria import benefit_mask
        from repro.core.scheduler import decision_matrix_batch
        table = nodes if isinstance(nodes, NodeTable) \
            else NodeTable.from_nodes(nodes)
        sig = sched.carbon_signal
        inten = sig.intensities(table.region, now) if sig is not None \
            else None
        mats = decision_matrix_batch(pods, table, carbon_intensity=inten)
        col = lambda xs: np.asarray(xs, dtype=np.float64)[:, None]
        valid = table.fits(col([p.cpu for p in pods]),
                           col([p.mem for p in pods]))
        if exclude is not None:
            valid = valid & ~np.asarray(exclude, dtype=bool)
        w = sched.weights(table)
        ref = topsis.batched_closeness_np(
            mats, np.broadcast_to(w, (len(pods), w.size)),
            benefit_mask(sched.criteria), valid)
        self.rounds += 1
        where = f"round {self.rounds} (P={len(pods)}, t={now})"
        if cc.shape != ref.shape:
            raise SmokeFailure(f"{where}: closeness shape {cc.shape}, "
                               f"reference {ref.shape}")
        if not np.array_equal(np.isneginf(cc), np.isneginf(ref)):
            raise SmokeFailure(f"{where}: -inf feasibility pattern differs "
                               f"from the reference")
        feasible = ~np.isneginf(ref)
        if not feasible.any():
            return
        err = float(np.max(np.abs(cc[feasible] - ref[feasible])))
        if not err <= ATOL:        # also catches NaN
            raise SmokeFailure(f"{where}: max |closeness - reference| = "
                               f"{err!r} > {ATOL}")
        self.max_err = max(self.max_err, err)


def _policies(phase: str) -> dict:
    if phase != "jax+carbon+autoscale":
        return {}
    from benchmarks.autoscale_sweep import POLICIES
    from benchmarks.carbon_sweep import make_policy
    return {"carbon": make_policy(), "autoscale": POLICIES["consolidate"]}


PHASES = {   # name -> (batch backend, scheme)
    "jax": ("jax", "energy_centric"),
    "pallas": ("pallas", "energy_centric"),
    "jax+carbon+autoscale": ("jax", "carbon_energy_balanced"),
}


def run(phase: str, n_nodes: int, traffic: dict, backend: str | None = None,
        checker: RoundChecker | None = None):
    """One scenario of ``phase`` through ``run_scenario``; returns the
    ``SimResult`` and its wall time in seconds (fleet build included; each
    round reads its scores back, so the device work is done when it
    returns)."""
    from repro.cluster.node import make_scenario_cluster
    from repro.cluster.simulator import run_scenario
    from repro.cluster.workload import PoissonArrivals
    phase_backend, scheme = PHASES[phase]
    ctx = checker.installed() if checker else contextlib.nullcontext()
    t0 = time.perf_counter()
    with ctx:
        res = run_scenario(
            PoissonArrivals(**traffic), scheme,
            cluster_factory=lambda: make_scenario_cluster("mixed", n_nodes,
                                                          seed=0),
            batch=True, batch_backend=backend or phase_backend,
            **_policies(phase))
    return res, time.perf_counter() - t0


def _placements(res) -> dict:
    # a preempted or drained pod has one record per attempt: keep the last
    return {r.pod.uid: r.node for r in res.records}


def run_phase(phase: str, n_nodes: int, traffic: dict, device_kind: str,
              counter: CompileCounter):
    """A cold run, then a warm run with every round checked; returns the
    phase's record and the warm run's ``SimResult``. ``warm_s`` leaves out
    the reference checks' own time (``check_s``)."""
    c0, h0 = counter.snapshot()
    cold, cold_s = run(phase, n_nodes, traffic)
    c1, h1 = counter.snapshot()
    checker = RoundChecker()
    warm, warm_s = run(phase, n_nodes, traffic, checker=checker)
    c2, _ = counter.snapshot()
    rec = {
        "phase": phase, "device_kind": device_kind, "nodes": n_nodes,
        "pods": traffic["n_bursts"] * traffic["burst_size"],
        "cold_s": cold_s, "warm_s": warm_s - checker.seconds,
        "check_s": checker.seconds,
        "cold_compiles": c1 - c0, "cold_cache_hits": h1 - h0,
        "warm_compiles": c2 - c1, "rounds": checker.rounds,
        "placed": len(_placements(warm)),
        "unschedulable_rate": warm.unschedulable_rate(),
        "max_abs_err": checker.max_err,
    }
    if rec["warm_compiles"]:
        raise SmokeFailure(f"{phase}: the warm run compiled "
                           f"{rec['warm_compiles']} programs")
    if _placements(cold) != _placements(warm):
        raise SmokeFailure(f"{phase}: the cold and warm runs placed pods "
                           f"differently")
    return rec, warm


def compare_with_numpy(jax_res, n_nodes: int, traffic: dict) -> dict:
    """The jax phase's scenario on the numpy backend: the placed count must
    match; the rest is reported (float32 near-ties may cascade)."""
    ref, ref_s = run("jax", n_nodes, traffic, backend="numpy")
    got, want = _placements(jax_res), _placements(ref)
    rec = {
        "phase": "numpy-vs-jax", "numpy_wall_s": ref_s,
        "placed_jax": len(got), "placed_numpy": len(want),
        "unschedulable_rate_numpy": ref.unschedulable_rate(),
        "same_node_share": (sum(got.get(u) == n for u, n in want.items())
                            / max(len(want), 1)),
        "fleet_energy_rel_diff": ((jax_res.fleet_energy_kj()
                                   - ref.fleet_energy_kj())
                                  / ref.fleet_energy_kj()),
    }
    if len(got) != len(want):
        raise SmokeFailure(f"jax placed {len(got)} pods, numpy "
                           f"{len(want)}")
    return rec


def smoke(n_nodes: int, traffic: dict, device_kind: str) -> None:
    """Every phase plus the numpy comparison, one JSON line each; raises
    :class:`SmokeFailure` on the first failed check."""
    counter = CompileCounter()
    for phase in PHASES:
        rec, res = run_phase(phase, n_nodes, traffic, device_kind, counter)
        print(json.dumps(rec), flush=True)
        if phase == "jax":
            print(json.dumps(compare_with_numpy(res, n_nodes, traffic)),
                  flush=True)


def check_pallas_compiled(n_nodes: int, p: int, k: int = 3) -> None:
    """The pallas phase's scoring program, lowered as the scheduler calls it
    (default ``interpret``), must hold the Mosaic kernel."""
    import jax
    import jax.numpy as jnp
    from repro.core.criteria import benefit_mask
    from repro.kernels import ops
    if ops.resolve_interpret():
        raise SmokeFailure("Pallas kernels would run in interpret mode")
    c = len(benefit_mask())
    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((k, n_nodes, c), f32),
            jax.ShapeDtypeStruct((p,), jnp.int32),
            jax.ShapeDtypeStruct((p, c), f32),
            jax.ShapeDtypeStruct((c,), jnp.bool_))
    valid = jax.ShapeDtypeStruct((p, n_nodes), jnp.bool_)
    text = jax.jit(ops.topsis_closeness_kinds).lower(
        *args, valid=valid).as_text()
    if "tpu_custom_call" not in text:
        raise SmokeFailure("the Pallas scoring program holds no "
                           "tpu_custom_call")


def main() -> int:
    from repro.device import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's default device is on "
              f"platform {dev.platform!r}", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(devices)}, compile cache: "
          f"{cache_dir}", flush=True)
    try:
        check_pallas_compiled(N_NODES, TRAFFIC["burst_size"])
        smoke(N_NODES, TRAFFIC, dev.device_kind)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

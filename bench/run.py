"""Chip benchmark of GreenPod's scheduling round.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout on a machine with the chips the cell asks
for. Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``
(with an optional ``policies`` key: the carbon signal and thresholds, the
autoscaler's settings and the per-class wake profiles), its traffic in
``bench/traffic/<traffic>.json`` (with an optional ``deferrable`` share
and deadline), the limits of its correctness check in
``bench/limits/<cell>.json`` and each metric's reader in
``bench/metrics/<metric>.py``.

With ``--trace 0`` the last line of standard output is one JSON object
with the cell's end-to-end metrics; with ``--trace 1`` the window runs
under the profiler and the line holds the per-layer metrics, the device's
busy time and a breakdown of device ops and idle gaps. Every number
compared for ``correct`` is printed beside its limit, as the last lines
on standard error and under the line's last key, ``checks``. Without an
accelerator, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse                          # noqa: E402
import contextlib                        # noqa: E402
import ctypes                            # noqa: E402
import gc                                # noqa: E402
import importlib.util                    # noqa: E402
import json                              # noqa: E402
import math                              # noqa: E402
import os                                # noqa: E402
import shutil                            # noqa: E402
import sys                               # noqa: E402
import tempfile                          # noqa: E402
from pathlib import Path                 # noqa: E402
from types import SimpleNamespace        # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def use_cache_dir() -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout,
    without a size limit: a limit turns on eviction, which scans the whole
    directory at every write. Call before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def steady_heap() -> None:
    """Fix glibc's heap thresholds for the process: blocks under 32 MiB
    come from the heap, which grows 64 MiB at a time and is not given
    back below 1 GiB. At glibc's adaptive defaults a run that loads its
    programs from the cache starts its window on a small heap, which is
    trimmed and faulted in again while it grows: on a TPU v5e host the
    rounds of the window's first 10 to 30 seconds then read a 95th
    percentile of 14 to 19 ms against 12 once the heap has settled, or
    where set-up compiled and grew the heap first. Call before the heap
    grows."""
    mallopt = ctypes.CDLL("libc.so.6").mallopt
    for param, value in ((-3, 32 << 20),     # M_MMAP_THRESHOLD
                         (-1, 1 << 30),      # M_TRIM_THRESHOLD
                         (-2, 64 << 20)):    # M_TOP_PAD
        if not mallopt(param, value):
            raise OSError(f"mallopt({param}, {value}) failed")


def load_cell(name: str) -> SimpleNamespace:
    """The cell's entry in ``BENCHMARK.json`` with its configuration,
    traffic, limits and the metrics it reports in each mode."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; choose from "
                         f"{sorted(cells)}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    listed = lambda m: name in m.get("workloads", [name])
    return SimpleNamespace(
        name=name, chips=int(cell["chips"]),
        config=json.loads((ROOT / config["file"]).read_text()),
        traffic=json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                           .read_text()),
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if listed(m)],
        per_layer=[m for m in bench["per_layer"] if listed(m)])


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}",
        BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(cell, seed: int, seconds: float, trace: bool, t0: float,
             n_nodes: int | None = None, patches=()) -> dict:
    """One run of ``cell``: set-up, window, checks; returns the result
    line. ``n_nodes`` shrinks the fleet and ``patches`` (context managers)
    break the program underneath, for the benchmark's own tests only."""
    import jax

    import generate
    import harness
    from compile_counter import CompileCounter
    from repro.device import enable_compile_cache

    t_enter = time.perf_counter()
    enable_compile_cache()
    counter = CompileCounter()
    cfg, traffic = cell.config, cell.traffic
    dev = jax.devices()[0]
    rec = harness.Recorder(seed, trace, policies="policies" in cfg)
    with contextlib.ExitStack() as stack:
        for p in patches:
            stack.enter_context(p)
        stack.enter_context(rec.installed())
        harness.warm_up(cfg, traffic, seed, n_nodes, rec)
        t_warm = time.perf_counter()
        warm_compiles, warm_hits = counter.snapshot()
        fleet0 = generate.Fleet(cfg, seed, n_nodes)
        gc.collect()
        trace_dir = None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        setup_s = time.perf_counter() - t0
        win = harness.run_window(cfg, traffic, seed, seconds, fleet0, rec,
                                 counter)
        if trace:
            jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    reduced = None
    if trace:
        import xplane
        devices, spans = xplane.read_events(xplane.find_trace(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = xplane.reduce(devices, spans)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    del fleet0
    numbers = harness.check(cfg, traffic, rec, win)
    energy = None
    if numbers["placed_first"]:
        energy = numbers["fleet_energy_j"] / numbers["placed_first"]
    rounds = rec.rounds
    ctx = SimpleNamespace(
        setup_s=setup_s, window_s=win.t_end - win.t_start,
        placed=len(rec.placed),
        rounds=rounds, n_rounds=len(rounds),
        select_s=sum(r.select_s for r in rounds),
        score_s=sum(r.score_s for r in rounds),
        n_nodes=len(rec.fleets[0]), n_criteria=len(cfg["criteria"]),
        device_kind=dev.device_kind, trace=reduced, energy_j_per_pod=energy)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = {k: {"value": numbers[k], "limit": cell.limits[k]}
              for k in cell.limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for c in checks.values():
        if not math.isfinite(c["value"]):
            c["value"] = None
    print(f"setup: {t_enter - t0:.3f} s to the cell (imports, TPU start), "
          f"warm-up {t_warm - t_enter:.3f} s ({warm_compiles} compiles, "
          f"{warm_hits} of them cache hits), fleet and trace start "
          f"{t0 + setup_s - t_warm:.3f} s", file=sys.stderr)
    print(f"window: {ctx.window_s:.3f} s, {len(rounds)} rounds, "
          f"{ctx.placed} pods placed, {len(win.results)} replays finished, "
          f"compiles in window: {win.compiles}, rounds checked: "
          f"{numbers['rounds_checked']}", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    out = {"correct": correct, "attempted": len(rounds),
           "failed": 0 if correct else 1, "metrics": metrics,
           "device": device}
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    steady_heap()
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    use_cache_dir()
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        print(f"bench: needs {cell.chips} accelerator chip(s); JAX finds "
              f"{len(devices)} device(s) on platform {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The program's own spans on the device trace's clock, and the round's
layers they measure.

A registry built as ``Telemetry(timelines=False, device_trace=True)``
(``repro.core.telemetry``) records every span in its log and, through
``jax.profiler.TraceAnnotation``, on the host plane of the profiler trace
that ``xplane.read_events`` reads. From the two this module takes:

- ``layer_metrics``: per scheduling round, the wall time of each layer of
  the round (cache sync, fit mask, upload, readback, argsort, walk), the
  engine's commit time per pod placed, and the readback's rate, from the
  registry's ``span_totals()`` and counters;
- ``idle_by_span``: each piece of the window's device idle time put down
  to the innermost program span the host was in, counted from
  ``xplane.SKEW_NS`` before the span's start as ``xplane.reduce`` counts
  its own spans;
- ``busy_by_program``: device busy time per XLA module (the device
  plane's ``XLA Modules`` line), named by the jitted function, which
  stays the same when XLA fuses its operations differently.

``bench/run.py`` does not call it yet (see ``PERF.md``, section 7); run a
traced window of a cell with the registry installed from here:

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \
        [--registry 0|1]

The last line of standard output is ``run.py``'s traced result line with
the layer metrics added to ``metrics`` and the span and program entries
appended to the breakdown. ``--registry 0`` runs the same traced window
without the registry, for the registry's cost.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import heapq                             # noqa: E402
import re                                # noqa: E402

import xplane                            # noqa: E402

MODULES_LINE = "XLA Modules"
_FINGERPRINT = re.compile(r"\(\d+\)$")

# (metric, span whose total it reads) for the per-round layer metrics
ROUND_LAYERS = (
    ("sync_ms_per_round", "scheduler_sync"),
    ("mask_ms_per_round", "scheduler_mask"),
    ("upload_ms_per_round", "scheduler_upload"),
    ("readback_ms_per_round", "scheduler_readback"),
    ("argsort_ms_per_round", "scheduler_argsort"),
    ("walk_ms_per_round", "scheduler_walk"),
)
ROUND_SPAN = "scheduler_batch"


def layer_metrics(totals: dict, counters: dict) -> dict:
    """The round's layer metrics from a registry's ``span_totals()`` and
    its counters summed over labels (``{name: value}``). A metric whose
    span or counter the registry never recorded is left out."""
    out = {}
    rounds = totals.get(ROUND_SPAN, {}).get("count", 0)
    for metric, span in ROUND_LAYERS:
        if rounds and span in totals:
            out[metric] = 1000.0 * totals[span]["total_s"] / rounds
    commits = counters.get("engine_commits", 0.0)
    if commits and "engine_commit" in totals:
        out["engine_commit_ms_per_pod"] = (
            1000.0 * totals["engine_commit"]["total_s"] / commits)
    readback = totals.get("scheduler_readback", {}).get("total_s", 0.0)
    if readback > 0 and counters.get("scheduler_readback_bytes"):
        out["readback_gb_per_s"] = (
            counters["scheduler_readback_bytes"] / readback / 1e9)
    return out


def read_events(path, names) -> tuple[dict, list]:
    """``(spans, modules)`` from one ``.xplane.pb``: the host-plane events
    whose name is in ``names`` as ``{name: [(start_ns, end_ns)]}``, and
    per device plane a list of ``(module, start_ns, end_ns)`` from its
    ``XLA Modules`` line, the module named without its fingerprint."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    names = set(names)
    spans: dict = {}
    modules: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        (_FINGERPRINT.sub("", ev.name), ev.start_ns,
                         ev.end_ns) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        spans.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.end_ns))
    return spans, [m for m in modules.values() if m]


def innermost(spans: dict) -> list:
    """Disjoint ``(start, end, name)`` pieces covering the union of the
    spans (``{name: [(start, end)]}``), each piece named by the span that
    covers it and started last: for spans that nest, the innermost."""
    ivs = sorted((s, e, name) for name, pieces in spans.items()
                 for s, e in pieces if e > s)
    points = sorted({p for s, e, _ in ivs for p in (s, e)})
    out, live, i = [], [], 0
    for a, b in zip(points, points[1:]):
        while i < len(ivs) and ivs[i][0] <= a:
            s, e, name = ivs[i]
            heapq.heappush(live, (-s, e, name))
            i += 1
        # the latest-started live span is on top; ended spans below it
        # surface, and go, only once it has ended too
        while live and live[0][1] <= a:
            heapq.heappop(live)
        if live:
            name = live[0][2]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def _window(host_spans) -> tuple[int, int]:
    wins = host_spans[xplane.WINDOW]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {xplane.WINDOW} span, found "
                           f"{len(wins)}")
    lo, hi = wins[0]
    return lo - xplane.SKEW_NS, hi


def idle_by_span(devices: dict, host_spans: dict, spans: dict) -> list:
    """Breakdown entries ``[label, seconds]``: for each program span, the
    device idle time inside the window while it was the host's innermost
    span, as ``span <name>: all <n> gaps`` and ``span <name>: longest
    gap``, largest first; times averaged over the device planes.
    ``devices`` and ``host_spans`` are what ``xplane.read_events`` gives,
    ``spans`` what :func:`read_events` gives."""
    lo, hi = _window(host_spans)
    early = {name: xplane.clip([(s - xplane.SKEW_NS, e) for s, e in ivs],
                               lo, hi)
             for name, ivs in spans.items()}
    by_name: dict = {}
    for s, e, name in innermost(early):
        by_name.setdefault(name, []).append((s, e))
    totals: dict = {}
    for ops in devices.values():
        busy = xplane.union(xplane.clip([(s, e) for _, s, e in ops], lo, hi))
        idle = xplane.subtract([(lo, hi)], busy)
        for name, ivs in by_name.items():
            gaps = xplane.intersect(idle, ivs)
            t = totals.setdefault(name, [0.0, 0.0, 0])
            t[0] += xplane.length(gaps) * 1e-9
            t[1] = max([t[1]] + [(e - s) * 1e-9 for s, e in gaps])
            t[2] += len(gaps)
    n = max(len(devices), 1)
    out = []
    for name, (total, longest, count) in sorted(totals.items(),
                                                key=lambda kv: -kv[1][0]):
        if count:
            out.append([f"span {name}: all {count} gaps", total / n])
            out.append([f"span {name}: longest gap", longest])
    return out


def busy_by_program(modules: list, host_spans: dict) -> list:
    """Breakdown entries ``[program <module>, seconds]``: device time per
    XLA module inside the window, largest first, averaged over the device
    planes."""
    lo, hi = _window(host_spans)
    busy: dict = {}
    for plane in modules:
        for name, s, e in plane:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                busy[name] = busy.get(name, 0.0) + d * 1e-9
    n = max(len(modules), 1)
    return [[f"program {name}", t / n]
            for name, t in sorted(busy.items(), key=lambda kv: -kv[1])]


def idle_s(devices: dict, host_spans: dict) -> float:
    """The window's device idle time, averaged over the device planes,
    on the same clock as :func:`idle_by_span`."""
    lo, hi = _window(host_spans)
    idle = [xplane.length(xplane.subtract(
        [(lo, hi)], xplane.union(xplane.clip([(s, e) for _, s, e in ops],
                                             lo, hi)))) * 1e-9
            for ops in devices.values()]
    return sum(idle) / max(len(idle), 1)


def main(argv=None) -> int:
    import argparse
    import json
    import sys

    import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--registry", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    run.use_cache_dir()
    sys.path.insert(0, str(run.ROOT / "src"))
    import jax
    if jax.devices()[0].platform == "cpu":
        print("spans: needs an accelerator chip", file=sys.stderr)
        return 2
    out = traced_run(cell, args.seed, args.seconds, bool(args.registry),
                     T_PROCESS)
    print(json.dumps(out), flush=True)
    return 0


def traced_run(cell, seed: int, seconds: float, registry: bool, t0: float,
               **kw) -> dict:
    """``run.run_cell`` with ``trace`` on and, with ``registry``, the
    program's registry installed for the window; returns its result line
    with the layer metrics and the span and program entries added."""
    import harness
    import run
    from repro.core import telemetry
    from repro.core.telemetry import Telemetry

    tel = Telemetry(timelines=False, device_trace=True) if registry else None
    found = {}
    run_window, find_trace = harness.run_window, xplane.find_trace

    def window(*a, **k):
        if tel is not None:
            telemetry.enable(tel)
        try:
            return run_window(*a, **k)
        finally:
            telemetry.disable()

    def trace_file(log_dir):
        path = find_trace(log_dir)
        devices, host_spans = xplane.read_events(path)
        names = tel.span_totals() if tel is not None else ()
        spans, modules = read_events(path, names)
        found.update(devices=devices, host_spans=host_spans, spans=spans,
                     modules=modules)
        return path

    harness.run_window, xplane.find_trace = window, trace_file
    try:
        out = run.run_cell(cell, seed, seconds, True, t0, **kw)
    finally:
        harness.run_window, xplane.find_trace = run_window, find_trace
    if tel is not None:
        counters: dict = {}
        for name, _, value in tel.counters.values():
            counters[name] = counters.get(name, 0.0) + value
        for name, value in layer_metrics(tel.span_totals(),
                                         counters).items():
            unit = ("GB/s" if name.endswith("_gb_per_s") else
                    "ms/pod" if name.endswith("_per_pod") else "ms")
            out["metrics"][name] = {"value": value, "unit": unit}
        out["spans"] = tel.span_totals()
        out["counters"] = counters
    if found.get("devices"):
        named = idle_by_span(found["devices"], found["host_spans"],
                             found["spans"])
        out["breakdown"]["idle_gaps"] += named
        out["breakdown"]["device_ops"] += busy_by_program(
            found["modules"], found["host_spans"])
        idle = idle_s(found["devices"], found["host_spans"])
        out["idle_named_share"] = (
            sum(v for k, v in named if k.endswith(" gaps")) / idle
            if idle > 0 else None)
    out["checks"] = out.pop("checks")
    return out


if __name__ == "__main__":
    import sys
    sys.exit(main())

"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Device busy time is the union of the intervals in which an operation ran
on a device: the events of each device plane's ``XLA Ops`` line. Host
spans are the harness's own ``jax.profiler.TraceAnnotation`` events
(``bench.window``, ``bench.round`` around ``select_many``, ``bench.score``
around ``score_queue``), which the profiler records on the same
timeline. Everything is clipped to the ``bench.window`` span.

The device's clock is put on the host's with some error: on a TPU v5 lite
a device op was seen to start up to about 1 ms before the host span that
launched it (``tests/data/small_tpu.xplane.pb``). So device time counts
for a host span, or for the window, from ``SKEW_NS`` before its start.
"""
from __future__ import annotations

import glob
import os

WINDOW, ROUND, SCORE = "bench.window", "bench.round", "bench.score"
DEVICE_OPS_LINE = "XLA Ops"
SKEW_NS = 1_000_000


def union(intervals):
    """Sorted disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b):
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b):
    """``a`` less ``b``, both sorted disjoint interval lists."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if e > s:
            out.append((s, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def read_events(path):
    """``(device_ops, host_spans)`` from one ``.xplane.pb``: per device
    plane a list of ``(name, start_ns, end_ns)`` op events, and the
    harness's host spans by name as ``(start_ns, end_ns)`` lists."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = {}, {WINDOW: [], ROUND: [], SCORE: []}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    ops.extend((ev.name, ev.start_ns, ev.end_ns)
                               for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in spans:
                        spans[ev.name].append((ev.start_ns, ev.end_ns))
    return {k: v for k, v in devices.items() if v}, spans


def find_trace(log_dir) -> str:
    paths = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def reduce(devices, spans, top: int = 10) -> dict:
    """Busy and idle time, the top device ops, and the idle time split by
    the host span the harness was in (``score_queue``, the rest of the
    round, which is the commit, or the engine outside the rounds), as its
    total and its longest piece. Times in seconds, averaged over the
    device planes."""
    if len(spans[WINDOW]) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found "
                           f"{len(spans[WINDOW])}")
    lo, hi = spans[WINDOW][0]
    window_s = (hi - lo) * 1e-9
    early = lambda ivs: union(clip([(s - SKEW_NS, e) for s, e in ivs],
                                   lo - SKEW_NS, hi))
    rounds, scores = early(spans[ROUND]), early(spans[SCORE])
    lo -= SKEW_NS
    busy_s, busy_score_s, op_time = [], [], {}
    gaps_by_span: dict = {}
    for ops in devices.values():
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_s.append(length(busy) * 1e-9)
        busy_score_s.append(length(intersect(busy, scores)) * 1e-9)
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[name] = op_time.get(name, 0.0) + d * 1e-9
        idle = subtract([(lo, hi)], busy)
        outside = subtract(idle, scores)
        for where, pieces in (("score_queue", intersect(idle, scores)),
                              ("commit", intersect(outside, rounds)),
                              ("engine", subtract(outside, rounds))):
            g = gaps_by_span.setdefault(where, [0.0, 0.0, 0])
            g[0] += length(pieces) * 1e-9
            g[1] = max([g[1]] + [(e - s) * 1e-9 for s, e in pieces])
            g[2] += len(pieces)
    n = max(len(devices), 1)
    gaps = []
    for where, (total, longest, count) in sorted(
            gaps_by_span.items(), key=lambda kv: -kv[1][0]):
        if not count:
            continue
        gaps.append([f"{where}: all {count} gaps", total / n])
        gaps.append([f"{where}: longest gap", longest])
    ops_sorted = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": sum(busy_s) / n,
        "busy_in_score_s": sum(busy_score_s) / n,
        "n_devices": len(devices),
        "device_ops": [[k, v / n] for k, v in ops_sorted],
        "idle_gaps": gaps[:top],
    }

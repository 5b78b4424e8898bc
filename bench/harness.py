"""One run of one cell: set-up, the measured window, the checks.

The window replays the cell's scenario back to back through the
program's entry, ``repro.cluster.simulator.run_scenario(..., batch=True)``
with the backend left at the program's default, on seeds ``seed``,
``seed + 1``, ... The harness times every ``BatchScheduler.select_many``
call (a scheduling round) and every ``score_queue`` call from wrappers of
its own, and counts the pods placed at ``EventEngine._commit``, each pod
once per replay. The first replay always runs to its end, so the energy
it reports does not depend on how many replays fit into the window.
After that the window ends at the first round that starts after
``seconds``; the harness stops the replay there by raising from its
wrapper.
"""
from __future__ import annotations

import contextlib
import inspect
import math
import time
from dataclasses import dataclass, field

import numpy as np

import generate
import reference

SAMPLED_ROUNDS = 16       # rounds checked against the reference per run


class StopWindow(Exception):
    """Raised from the harness's round wrapper to end the window."""


@dataclass
class Round:
    p: int                 # real queue length
    k: int                 # pod kinds the scheduler holds
    select_s: float
    score_s: float
    scored: bool


@dataclass
class Sample:
    """A round kept for the reference check: its inputs as the program
    saw them at the start of the round, and its answers."""
    replay: int
    pods: list
    used_cpu: np.ndarray
    used_mem: np.ndarray
    cc: object = None
    assignments: list = field(default_factory=list)


class Recorder:
    """Wraps the program's round, scoring and bind calls for one run."""

    def __init__(self, seed: int, trace: bool):
        self.trace = trace
        self.rng = np.random.default_rng([seed, 7])
        self.in_window = False
        self.stop_at: float | None = None
        self.stopped_at: float | None = None
        self.rounds: list[Round] = []
        self.placed: set = set()      # (replay, pod uid) placed in the window
        self.replay = 0
        self.fleets: dict = {}        # replay -> generate.Fleet
        self.kinds: set = set()
        self.captured = None          # (scheduler, fleet) of the last round
        self.samples: list[Sample] = []
        self.largest: Sample | None = None
        self._seen = 0
        self._score_s = 0.0
        self._scored = False

    def begin_replay(self, replay: int, fleet) -> None:
        self.replay = replay
        self.fleets[replay] = fleet
        self.kinds = set()

    def _annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _snapshot(self, args) -> Sample:
        nodes = args["nodes"]
        return Sample(self.replay, list(args["pods"]),
                      np.array(nodes.used_cpu, dtype=np.float64),
                      np.array(nodes.used_mem, dtype=np.float64))

    def _keep(self, p: int):
        """Reservoir sampling of the window's rounds from the seed, plus
        the largest round so far."""
        self._seen += 1
        slot = None
        if len(self.samples) < SAMPLED_ROUNDS:
            slot = len(self.samples)
        else:
            j = int(self.rng.integers(self._seen))
            if j < SAMPLED_ROUNDS:
                slot = j
        largest = self.largest is None or p > len(self.largest.pods)
        return slot, largest

    @contextlib.contextmanager
    def installed(self):
        from repro.cluster.engine import EventEngine
        from repro.core.scheduler import BatchScheduler
        select_many = BatchScheduler.select_many
        score_queue = BatchScheduler.score_queue
        commit = EventEngine._commit
        sig_select = inspect.signature(select_many)
        rec = self

        def timed_select(sched, *a, **kw):
            args = sig_select.bind(sched, *a, **kw)
            args.apply_defaults()
            args = args.arguments
            if rec.in_window and rec.stop_at is not None \
                    and time.perf_counter() >= rec.stop_at:
                rec.stopped_at = time.perf_counter()
                raise StopWindow
            slot = largest = snap = None
            if rec.in_window:
                slot, largest = rec._keep(len(args["pods"]))
                snap = rec._snapshot(args) if (slot is not None
                                              or largest) else None
            rec._score_s, rec._scored = 0.0, False
            t0 = time.perf_counter()
            with rec._annotate("bench.round"):
                out = select_many(sched, *a, **kw)
            dt = time.perf_counter() - t0
            if rec.in_window:
                rec.rounds.append(Round(len(args["pods"]), len(rec.kinds), dt,
                                        rec._score_s, rec._scored))
                if snap is not None:
                    snap.assignments = list(out[0])
                    snap.cc = out[1]["closeness"]
                    if slot is not None:
                        if slot == len(rec.samples):
                            rec.samples.append(snap)
                        else:
                            rec.samples[slot] = snap
                    if largest:
                        rec.largest = snap
            return out

        def timed_score(sched, pods, nodes, *a, **kw):
            rec.kinds.update((p.cpu, p.mem, p.workload.base_time_s)
                             for p in pods)
            rec.captured = (sched, nodes)
            t0 = time.perf_counter()
            with rec._annotate("bench.score"):
                out = score_queue(sched, pods, nodes, *a, **kw)
            rec._score_s += time.perf_counter() - t0
            rec._scored = True
            return out

        def counted_commit(engine, pod, *a, **kw):
            out = commit(engine, pod, *a, **kw)
            if rec.in_window:
                rec.placed.add((rec.replay, pod.uid))
            return out

        BatchScheduler.select_many = timed_select
        BatchScheduler.score_queue = timed_score
        EventEngine._commit = counted_commit
        try:
            yield self
        finally:
            BatchScheduler.select_many = select_many
            BatchScheduler.score_queue = score_queue
            EventEngine._commit = commit


def _pad(p: int) -> int:
    """Next power of two at or above ``p``, and at least 2."""
    return 1 << max(p - 1, 1).bit_length()


def _pow2_upto(n: int) -> list:
    out, x = [], 1
    while x < n:
        out.append(x)
        x *= 2
    return out + [n]


def replay_scenario(cfg, traffic, seed: int, fleet):
    from repro.cluster.simulator import run_scenario
    return run_scenario(generate.arrivals(cfg, traffic, seed), cfg["scheme"],
                        cluster_factory=lambda: fleet.nodes, batch=True)


def warm_up(cfg, traffic, seed: int, n_nodes, rec: Recorder) -> None:
    """Compile every program the window can reach, on the cell's own
    shapes: the padded queue lengths up to the burst size and the padded
    counts of changed node columns up to the fleet."""
    from repro.cluster.workload import ArrivalProcess, Pod

    fleet = generate.Fleet(cfg, seed, n_nodes)
    specs = list(generate.workload_specs(cfg).values())

    class One(ArrivalProcess):
        def events(self):
            return [(1.0, [Pod(i, s, traffic["scheduler"])
                           for i, s in enumerate(specs)])]

    from repro.cluster.simulator import run_scenario
    run_scenario(One(), cfg["scheme"], cluster_factory=lambda: fleet.nodes,
                 batch=True)
    sched, table = rec.captured
    queue = lambda p: [Pod(10_000 + i, specs[i % len(specs)],
                           traffic["scheduler"]) for i in range(p)]
    size = int(traffic["burst_size"])
    now = 2.0
    for p in sorted(set(_pow2_upto(_pad(size))) | {size}):
        now += 1.0
        sched.score_queue(queue(p), table, now=now)
    n = len(table)
    for d in _pow2_upto(n):
        for i in range(d):
            table.bind(i, 0.0, 0.0)
        now += 1.0
        sched.score_queue(queue(1), table, now=now)
    rec.captured = None


@dataclass
class Window:
    t_start: float
    t_end: float
    results: list          # (replay, Fleet, SimResult) of finished replays
    compiles: int


def run_window(cfg, traffic, seed: int, seconds: float, fleet0, rec: Recorder,
               counter) -> Window:
    """Replays back to back until ``seconds`` have passed; the window then
    ends at the next round. The first replay always runs to its end."""
    results = []
    c0 = counter.snapshot()[0]
    t_start = time.perf_counter()
    rec.in_window = True
    rec.stop_at = None
    replay = 0
    t_end = None
    with rec._annotate("bench.window"):
        try:
            while True:
                fleet = fleet0 if replay == 0 else generate.Fleet(
                    cfg, seed + replay, len(fleet0))
                rec.begin_replay(replay, fleet)
                res = replay_scenario(cfg, traffic, seed + replay, fleet)
                results.append((replay, fleet, res))
                replay += 1
                if time.perf_counter() - t_start >= seconds:
                    t_end = time.perf_counter()
                    break
                rec.stop_at = t_start + seconds
        except StopWindow:
            t_end = rec.stopped_at
    rec.in_window = False
    return Window(t_start, t_end, results, counter.snapshot()[0] - c0)


def check(cfg, traffic, rec: Recorder, win: Window) -> dict:
    """The numbers compared with their limits (see ``bench/limits``)."""
    samples = list(rec.samples)
    if rec.largest is not None and all(s is not rec.largest for s in samples):
        samples.append(rec.largest)
    err, inf_mismatch, illegal = 0.0, 0, 0
    for s in samples:
        fleet = rec.fleets[s.replay]
        ref = reference.score_round(cfg, fleet, s.used_cpu, s.used_mem,
                                    s.pods)
        cc = np.asarray(s.cc, dtype=np.float64)
        if cc.shape != ref.shape:
            inf_mismatch += ref.size
            continue
        inf_mismatch += int((np.isneginf(cc) != np.isneginf(ref)).sum())
        both = np.isfinite(ref) & ~np.isneginf(cc)
        if both.any():
            e = float(np.max(np.abs(cc[both] - ref[both])))
            if not math.isnan(err) and not e <= err:     # NaN sticks
                err = e
        illegal += reference.illegal_commits(cc, s.pods, fleet, s.used_cpu,
                                             s.used_mem, s.assignments)
    unplaced = 0
    expected = set(range(int(traffic["n_bursts"]) * int(traffic["burst_size"])))
    for _, _, res in win.results:
        placed = {r.pod.uid for r in res.records}
        unplaced += len(expected - placed) + int(res.unschedulable)
    energy_gap = math.nan
    first = [x for x in win.results if x[0] == 0]
    if first:
        _, fleet, res = first[0]
        want = reference.task_energy_j(res.records, fleet)
        got = (res.timeline.dynamic_energy_j(None)
               + res.timeline.idle_energy_j(None))
        energy_gap = float(abs(got - want) / want)
    return {"closeness_err": err, "inf_mismatch": inf_mismatch,
            "commit_illegal": illegal, "unplaced": unplaced,
            "energy_gap": energy_gap, "rounds_checked": len(samples)}

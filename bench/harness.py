"""One run of one cell: set-up, the measured window, the checks.

The window replays the cell's scenario back to back through the
program's entry, ``repro.cluster.simulator.run_scenario(..., batch=True)``
with the backend left at the program's default, on seeds ``seed``,
``seed + 1``, ... The configuration's optional ``policies`` key becomes
the program's public ``CarbonPolicy`` and ``AutoscalePolicy``
(``program_policies``); without it the call carries neither. The harness
times every ``BatchScheduler.select_many`` call (a scheduling round, or
the autoscaler's probe for a node to wake) and every ``score_queue`` call
from wrappers of its own, and logs, per replay and in the order they
happen, the engine's rounds, its placements (``EventEngine._commit``, each
pod counted once per replay), its evictions with the policy that made
them, and the autoscaler's wake requests. The plain reference rebuilds
the fleet's power states from that log (``reference_policies``). The
first replay always runs to its end, so the energy it reports does not
depend on how many replays fit into the window. After that the window
ends at the first round that starts after ``seconds``; the harness stops
the replay there by raising from its wrapper.
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
import reference_policies

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
    saw them at the start of the round, and its answers. ``seq`` is the
    length of the replay's log when the round began, ``probe`` marks the
    autoscaler's probe for a node to wake."""
    replay: int
    seq: int
    now: float
    probe: bool
    pods: list
    used_cpu: np.ndarray
    used_mem: np.ndarray
    cc: object = None
    assignments: list = field(default_factory=list)


class Recorder:
    """Wraps the program's round, scoring and bind calls for one run, and
    with ``policies`` its eviction and wake calls too.

    With ``policies``, ``logs[replay]`` lists, in order, ``("round", t)``,
    ``("commit", t, uid, node)``, ``("evict", t, uid, node, cause)`` with
    ``cause`` ``"carbon"`` or ``"drain"``, and ``("wake", t, node)``.
    Without, the window runs no wrapper beyond the round, scoring and bind
    counters, and the reference takes the placements from the replays'
    records (``placements``)."""

    def __init__(self, seed: int, trace: bool, policies: bool = False):
        self.trace = trace
        self.policies = policies
        self.rng = np.random.default_rng([seed, 7])
        self.in_window = False
        self.stop_at: float | None = None
        self.stopped_at: float | None = None
        self.rounds: list[Round] = []
        self.placed: set = set()      # (replay, pod uid) placed in the window
        self.replay = 0
        self.fleets: dict = {}        # replay -> generate.Fleet
        self.bursts: dict = {}        # replay -> generate.bursts
        self.logs: dict = {}          # replay -> [event, ...]
        self.log: list = []
        self.cause: str | None = None
        self.probing = False
        self.kinds: set = set()
        self.captured = None          # (scheduler, fleet) of the last round
        self.samples: list[Sample] = []
        self.largest: Sample | None = None
        self._seen = 0
        self._score_s = 0.0
        self._scored = False

    def begin_replay(self, replay: int, fleet, bursts) -> None:
        self.replay = replay
        self.fleets[replay] = fleet
        self.bursts[replay] = bursts
        self.log = self.logs[replay] = []
        self.kinds = set()

    def _stop_due(self) -> bool:
        """The window's time is up (never in the first replay); notes
        when."""
        if self.in_window and self.stop_at is not None \
                and time.perf_counter() >= self.stop_at:
            self.stopped_at = time.perf_counter()
            return True
        return False

    def _annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _snapshot(self, args) -> Sample:
        nodes = args["nodes"]
        return Sample(self.replay, len(self.log), float(args["now"]),
                      self.probing, list(args["pods"]),
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
        from repro.core.carbon import CarbonScheduling
        from repro.core.elastic import AutoscaleScheduling, ElasticFleet
        from repro.core.scheduler import BatchScheduler
        select_many = BatchScheduler.select_many
        score_queue = BatchScheduler.score_queue
        commit = EventEngine._commit
        engine_round = EventEngine._round
        sig_select = inspect.signature(select_many)
        rec = self

        def timed_select(sched, *a, **kw):
            args = sig_select.bind(sched, *a, **kw)
            args.apply_defaults()
            args = args.arguments
            if not rec.policies and rec._stop_due():
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

        def logged(orig, entry):
            def call(obj, *a, **kw):
                if rec.in_window:
                    entry(obj, *a, **kw)
                return orig(obj, *a, **kw)
            return call

        def during(orig, name, value):
            """``orig`` with ``rec.<name>`` set to ``value`` while it runs."""
            def call(*a, **kw):
                off = getattr(rec, name)
                setattr(rec, name, value)
                try:
                    return orig(*a, **kw)
                finally:
                    setattr(rec, name, off)
            return call

        def logged_round(engine, t, tel):
            # with policies the window ends only between rounds: a round
            # cut in its wake pass would be judged with half its wakes
            if rec.in_window:
                if rec._stop_due():
                    raise StopWindow
                rec.log.append(("round", t))
            return engine_round(engine, t, tel)

        def on_commit(engine, pod, idx, t, sched_time_s):
            rec.log.append(("commit", t, pod.uid, idx))

        if self.policies:
            commit = logged(commit, on_commit)

        def counted_commit(engine, pod, *a, **kw):
            out = commit(engine, pod, *a, **kw)
            if rec.in_window:
                rec.placed.add((rec.replay, pod.uid))
            return out

        def on_evict(engine, victims, t):
            rec.log.extend(("evict", t, v.uid, v.node_index, rec.cause)
                           for v in victims)

        def on_wake(fleet, i, t):
            rec.log.append(("wake", t, i))

        patches = [
            (BatchScheduler, "select_many", timed_select),
            (BatchScheduler, "score_queue", timed_score),
            (EventEngine, "_commit", counted_commit),
        ]
        if self.policies:
            patches += [
                (EventEngine, "_round", logged_round),
                (EventEngine, "evict", logged(EventEngine.evict, on_evict)),
                (ElasticFleet, "request_wake",
                 logged(ElasticFleet.request_wake, on_wake)),
                (ElasticFleet, "wake_for_pressure",
                 during(ElasticFleet.wake_for_pressure, "probing", True)),
                (CarbonScheduling, "on_round_start",
                 during(CarbonScheduling.on_round_start, "cause", "carbon")),
                (AutoscaleScheduling, "on_round_start",
                 during(AutoscaleScheduling.on_round_start, "cause",
                        "drain")),
            ]
        saved = [(owner, name, getattr(owner, name))
                 for owner, name, _ in patches]
        for owner, name, fn in patches:
            setattr(owner, name, fn)
        try:
            yield self
        finally:
            for owner, name, fn in saved:
                setattr(owner, name, fn)


def _pad(p: int) -> int:
    """Next power of two at or above ``p``, and at least 2."""
    return 1 << max(p - 1, 1).bit_length()


def _pow2_upto(n: int) -> list:
    out, x = [], 1
    while x < n:
        out.append(x)
        x *= 2
    return out + [n]


def program_policies(cfg: dict) -> dict:
    """``run_scenario``'s ``carbon`` and ``autoscale`` arguments, built
    from the configuration's ``policies`` key with the program's public
    types; empty without the key. The wake profiles stay with the
    reference: the program keeps its own tables."""
    pol = cfg.get("policies", {})
    out = {}
    if "carbon" in pol:
        from repro.core.carbon import CarbonPolicy, diurnal_fleet_signal
        c = pol["carbon"]
        out["carbon"] = CarbonPolicy(
            diurnal_fleet_signal(cfg["regions"], **c["signal"]),
            defer_threshold=c["defer_threshold"],
            preempt_threshold=c["preempt_threshold"],
            check_interval_s=c["check_interval_s"])
    if "autoscale" in pol:
        from repro.core.elastic import AutoscalePolicy
        a = {k: v for k, v in pol["autoscale"].items()
             if k != "wake_profiles"}
        out["autoscale"] = AutoscalePolicy(**a)
    return out


def replay_scenario(cfg, traffic, seed: int, fleet, events=None):
    """One replay through the program's entry; ``events`` are the
    replay's bursts (``generate.bursts``), made from ``seed`` if not
    given."""
    from repro.cluster.simulator import run_scenario
    if events is None:
        events = generate.bursts(cfg, traffic, seed)
    return run_scenario(generate.arrivals(events), cfg["scheme"],
                        cluster_factory=lambda: fleet.nodes, batch=True,
                        **program_policies(cfg))


def largest_queue(traffic: dict) -> int:
    """The most pods one round can be handed: a burst, plus every
    deferrable pod of the replay held back to one release."""
    size = int(traffic["burst_size"])
    held = sum(d for _, d in generate.burst_pool(traffic))
    return size + held * int(traffic["n_bursts"])


def warm_up(cfg, traffic, seed: int, n_nodes, rec: Recorder) -> None:
    """Compile every program the window can reach, on the cell's own
    shapes and with its policies: the padded queue lengths up to the
    largest queue a round can be handed and the padded counts of changed
    node columns up to the fleet."""
    from repro.cluster.workload import ArrivalProcess, Pod

    fleet = generate.Fleet(cfg, seed, n_nodes)
    specs = list(generate.workload_specs(cfg).values())

    class One(ArrivalProcess):
        def events(self):
            return [(1.0, [Pod(i, s, traffic["scheduler"])
                           for i, s in enumerate(specs)])]

    from repro.cluster.simulator import run_scenario
    run_scenario(One(), cfg["scheme"], cluster_factory=lambda: fleet.nodes,
                 batch=True, **program_policies(cfg))
    sched, table = rec.captured
    queue = lambda p: [Pod(10_000 + i, specs[i % len(specs)],
                           traffic["scheduler"]) for i in range(p)]
    size = int(traffic["burst_size"])
    now = 2.0
    for p in sorted(set(_pow2_upto(_pad(largest_queue(traffic)))) | {size}):
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
                events = generate.bursts(cfg, traffic, seed + replay)
                rec.begin_replay(replay, fleet, events)
                res = replay_scenario(cfg, traffic, seed + replay, fleet,
                                      events)
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


def _masks(cfg, sample: Sample, ledger):
    """The reference's ``awake`` and ``exclude`` for a sampled round, from
    the power-state ledger's nodes at the point the round began: sleeping
    nodes are masked and cost their idle power; a deferrable pod is also
    kept off WAKING nodes that complete after its deadline. The
    autoscaler's probe may only pick a sleeping node, and sees the states
    of its round's start."""
    if "autoscale" not in cfg.get("policies", {}):
        return None, None
    asleep, ready, woken, _ = ledger.snapshots[sample.seq]
    if sample.probe:
        return ~(asleep | woken), ~asleep
    deadlines = np.asarray([ledger.deadline(p.uid) if p.deferrable
                            else np.inf for p in sample.pods])
    if not np.isfinite(deadlines).any():
        return ~asleep, asleep
    with np.errstate(invalid="ignore"):
        late = ready[None, :] > deadlines[:, None]
    if not late.any():
        return ~asleep, asleep
    return ~asleep, asleep[None, :] | late


def placements(fleet, res) -> list:
    """A finished replay's placements as the log's commit entries, from
    its records: without policies every task starts where and when it was
    placed."""
    index = {name: i for i, name in enumerate(fleet.names)}
    return [("commit", r.start_s, r.pod.uid, index[r.node])
            for r in res.records]


def check(cfg, traffic, rec: Recorder, win: Window) -> dict:
    """The numbers compared with their limits (see ``bench/limits``), and
    the first replay's fleet energy and pods placed, which
    ``energy_j_per_pod`` reads."""
    samples = list(rec.samples)
    if rec.largest is not None and all(s is not rec.largest for s in samples):
        samples.append(rec.largest)
    out = {"closeness_err": 0.0, "inf_mismatch": 0, "commit_illegal": 0,
           "unplaced": 0, "energy_gap": math.nan, "defer_illegal": 0,
           "preempt_illegal": 0, "drain_illegal": 0, "wake_illegal": 0,
           "rounds_checked": len(samples), "fleet_energy_j": None,
           "placed_first": 0}
    logs = rec.logs if rec.policies else {
        replay: placements(fleet, res)
        for replay, fleet, res in win.results if replay == 0}
    ledgers = {}
    for replay, log in logs.items():
        ledger = reference_policies.Ledger(cfg, rec.fleets[replay],
                                           rec.bursts[replay])
        ledgers[replay] = ledger.run(log, [(s.seq, s.now) for s in samples
                                           if s.replay == replay
                                           and rec.policies])
        for k in ("defer_illegal", "preempt_illegal", "drain_illegal",
                  "wake_illegal"):
            out[k] += ledger.numbers[k]
        out["commit_illegal"] += ledger.numbers["asleep_commits"]
    err = 0.0
    for s in samples:
        fleet = rec.fleets[s.replay]
        ledger = ledgers[s.replay] if rec.policies else None
        awake, exclude = _masks(cfg, s, ledger)
        ref = reference.score_round(cfg, fleet, s.used_cpu, s.used_mem,
                                    s.pods, now=s.now, awake=awake,
                                    exclude=exclude)
        cc = np.asarray(s.cc, dtype=np.float64)
        if cc.shape != ref.shape:
            out["inf_mismatch"] += ref.size
            continue
        out["inf_mismatch"] += int((np.isneginf(cc)
                                    != np.isneginf(ref)).sum())
        both = np.isfinite(ref) & ~np.isneginf(cc)
        if both.any():
            e = float(np.max(np.abs(cc[both] - ref[both])))
            if not math.isnan(err) and not e <= err:     # NaN sticks
                err = e
        out["commit_illegal"] += reference.illegal_commits(
            cc, s.pods, fleet, s.used_cpu, s.used_mem, s.assignments,
            blocked=ledger.snapshots[s.seq][3] if ledger else None)
    out["closeness_err"] = err
    expected = set(range(int(traffic["n_bursts"]) * int(traffic["burst_size"])))
    for _, _, res in win.results:
        placed = {r.pod.uid for r in res.records}
        out["unplaced"] += len(expected - placed) + int(res.unschedulable)
    first = [x for x in win.results if x[0] == 0]
    if first:
        res = first[0][2]
        want = ledgers[0].fleet_energy_j()
        got = res.fleet_energy_kj() * 1000.0
        out["energy_gap"] = float(abs(got - want) / want)
        out["fleet_energy_j"] = want
        out["placed_first"] = len({e[2] for e in logs[0]
                                   if e[0] == "commit"})
    return out

"""The plain reference of the carbon and autoscale policies: what the
configuration's ``policies`` allow and demand, rebuilt from the program's
decisions alone, in float64 numpy.

It imports nothing of the program. Its inputs are the configuration, the
benchmark's own fleet and bursts, and the log the harness keeps of one
replay (``harness.Recorder``): the engine's rounds, the program's
placements, its evictions with the policy that made them, and its wake
requests, in the order they happened. From these it rebuilds one
power-state ledger per node by the declared rules:

* a node is IDLE from the start of the replay, and again from the end of
  its last task or from the end of a wake that found it empty;
* an IDLE node falls ASLEEP ``idle_timeout_s`` later, unless its index is
  below ``min_awake``, and at once when a consolidation drains it;
* a wake request takes an ASLEEP node through WAKING for its class's
  ``wake_latency_s``; a pod placed on a WAKING node starts when the wake
  completes.

From the ledger it derives, at any point of the log, which nodes sleep
(masked out of scoring, and costing no idle power in the energy
criterion) and which are WAKING with their completion instant (masked for
a deferrable pod whose deadline comes first), which node each pod just
preempted may not restart on at the same instant, and the replay's fleet
energy. It never reads the program's power states, masks or fleet state
machine. Alongside it counts the decisions that break the policies'
rules (``Ledger.numbers``).
"""
from __future__ import annotations

import heapq
import itertools
import math

import numpy as np

import reference

SLACK = 1e-9      # relative slack of a threshold test on the signal
DEADLINE_SLACK = 1e-12
_READY, _DONE = 0, 1  # a wake completing at t comes before a task ending at t


class _Task:
    __slots__ = ("uid", "node", "start", "run", "cpu", "active")

    def __init__(self, uid, node, start, run, cpu):
        self.uid, self.node = uid, node
        self.start, self.run, self.cpu = start, run, cpu
        self.active = True

    @property
    def end(self) -> float:
        return self.start + self.run


def _above(x: float, threshold: float) -> bool:
    return x > threshold + SLACK * max(1.0, abs(threshold))


def _at_or_below(x: float, threshold: float) -> bool:
    return x <= threshold - SLACK * max(1.0, abs(threshold))


class Ledger:
    """One replay's power-state ledger and policy checks. Feed the log
    through :meth:`run`; read :attr:`numbers`, :attr:`snapshots` and
    :meth:`fleet_energy_j`."""

    def __init__(self, cfg: dict, fleet, bursts):
        pol = cfg.get("policies", {})
        self.cfg, self.fleet = cfg, fleet
        self.carbon = pol.get("carbon")
        self.auto = pol.get("autoscale")
        self.any_policy = bool(self.carbon or self.auto)
        n = len(fleet)
        self.region = np.arange(n) % len(cfg["regions"])
        self.pods = {p.uid: (t, p) for t, pods in bursts for p in pods}
        self.arrivals = sorted(((t, p.uid) for t, pods in bursts
                                for p in pods))
        self._arrived = 0
        self.running = np.zeros(n, dtype=np.int64)
        self.used = np.zeros(n)
        self.idle_since = np.zeros(n)
        self.sleep_at = np.full(n, np.nan)
        self.wake_ready = np.full(n, np.nan)
        self.asleep_s = np.zeros(n)
        self.wakes = np.zeros(n, dtype=np.int64)
        if self.auto:
            prof = self.auto["wake_profiles"]
            col = lambda k: np.asarray([prof[c][k] for c in fleet.node_class],
                                       dtype=np.float64)
            self.latency = col("wake_latency_s")
            self.sleep_power = col("sleep_power_w")
            self.wake_energy = col("wake_energy_j")
            self.timeout = np.where(np.arange(n) >= self.auto["min_awake"],
                                    float(self.auto["idle_timeout_s"]),
                                    math.inf)
            self.next_pass = self.auto.get("consolidate_interval_s")
        self.tasks: dict = {}          # uid -> its latest attempt
        self.attempts: list = []       # every attempt, in commit order
        self._timed: list = []         # (t, _READY | _DONE, n, key)
        self._n = itertools.count()
        self.blocked: dict = {}        # uid -> node it left at this instant
        self.pending: dict = {}        # uid -> its place in the queue
        self._order = itertools.count()
        self._rounds = 0
        self.preempted: set = set()
        self._cands = [set() for _ in cfg["regions"]]
        self.horizon = 0.0
        self.numbers = {"defer_illegal": 0, "preempt_illegal": 0,
                        "drain_illegal": 0, "wake_illegal": 0,
                        "asleep_commits": 0}
        self.snapshots: dict = {}
        self._round = None

    # --- the signal -----------------------------------------------------
    def _intensity(self, t: float) -> np.ndarray:
        return reference.intensity(self.cfg, t)

    def deadline(self, uid: int) -> float:
        t, pod = self.pods[uid]
        return t + pod.deadline_s

    def _held(self, uid: int, t: float, fleet_min: float) -> bool:
        """A policy holds this pending pod out of the round at ``t``: the
        carbon policy's deferral, or the block on a restart on the node
        it was just preempted off."""
        pod = self.pods[uid][1]
        return uid in self.blocked or (
            self.carbon is not None and pod.deferrable
            and _above(fleet_min, self.carbon["defer_threshold"])
            and t < self.deadline(uid) - DEADLINE_SLACK)

    # --- node states ----------------------------------------------------
    # A node is IDLE or ASLEEP exactly while ``idle_since`` is set; it falls
    # asleep at its ``sleep_at`` (a drain) or ``idle_timeout_s`` later.
    def _due(self) -> np.ndarray:
        """(N,) instant each node falls (or fell) asleep; NaN while it is
        not IDLE."""
        return np.where(np.isnan(self.sleep_at),
                        self.idle_since + self.timeout, self.sleep_at)

    def _due_one(self, i: int) -> float:
        if not math.isnan(self.sleep_at[i]):
            return self.sleep_at[i]
        return self.idle_since[i] + self.timeout[i]

    def asleep(self, t: float) -> np.ndarray:
        if not self.auto:
            return np.zeros(len(self.fleet), dtype=bool)
        with np.errstate(invalid="ignore"):
            return t >= self._due()

    def _asleep_one(self, i: int, t: float) -> bool:
        return bool(self.auto) and t >= self._due_one(i)

    def _end_idle(self, i: int, t: float) -> None:
        """Node i's idle stretch ends at ``t`` (a placement or a wake):
        book the asleep part of it."""
        if self.auto and t > self._due_one(i):
            self.asleep_s[i] += t - self._due_one(i)
        self.idle_since[i] = np.nan
        self.sleep_at[i] = np.nan

    def _emptied(self, i: int, t: float) -> None:
        if self.running[i] == 0 and math.isnan(self.wake_ready[i]):
            self.idle_since[i] = t

    def advance(self, t: float, inclusive: bool = False) -> None:
        """Apply the wakes that completed by ``t`` and the tasks that ended
        before it (at or before it, with ``inclusive``)."""
        timed = self._timed
        while timed and (timed[0][0] < t or (timed[0][0] == t and (
                inclusive or timed[0][1] == _READY))):
            when, kind, _, key = heapq.heappop(timed)
            if kind == _READY:
                if self.wake_ready[key] == when:
                    self.wake_ready[key] = np.nan
                    self._emptied(key, when)
                continue
            task = key
            if task.active:
                task.active = False
                self._release(task)
                self._emptied(task.node, when)

    def _release(self, task: _Task) -> None:
        self.running[task.node] -= 1
        self.used[task.node] -= task.cpu
        self._cands[self.region[task.node]].discard(task.uid)

    # --- the log --------------------------------------------------------
    def run(self, log: list, samples=()) -> "Ledger":
        """Feed one replay's log. ``samples`` are ``(seq, now)`` of the
        rounds whose node states :attr:`snapshots` keeps, taken when the
        log held ``seq`` entries."""
        want: dict = {}
        for seq, now in samples:
            want.setdefault(seq, []).append(now)
        for seq, ev in enumerate(log):
            for now in want.pop(seq, ()):
                self._snapshot(seq, now)
            getattr(self, "_on_" + ev[0])(*ev[1:])
        for seq, nows in want.items():
            for now in nows:
                self._snapshot(seq, now)
        self._close_round()
        return self

    def _snapshot(self, seq: int, now: float) -> None:
        woken = np.zeros(len(self.fleet), dtype=bool)
        if self._round is not None:
            woken[list(self._round["woken"])] = True
        self.snapshots[seq] = (self.asleep(now), self.wake_ready.copy(),
                               woken, dict(self.blocked))

    def _enqueue(self, uid: int, front: bool = False) -> None:
        """The engine's queue order: arrivals and preempted pods join at
        the back, each consolidation pass puts its drained pods, in
        order, in front of everything."""
        n = next(self._order)
        self.pending[uid] = (0, -self._rounds, n) if front else (1, n)

    def _on_round(self, t: float) -> None:
        self._close_round()
        self.advance(t)
        self.horizon = max(self.horizon, t)
        self._rounds += 1
        while (self._arrived < len(self.arrivals)
               and self.arrivals[self._arrived][0] <= t):
            self._enqueue(self.arrivals[self._arrived][1])
            self._arrived += 1
        is_pass = False
        if self.auto and self.next_pass is not None and t >= self.next_pass:
            is_pass = True
            self.next_pass = t + self.auto["consolidate_interval_s"]
        demanded = set()
        thr = self._preempt_threshold()
        if thr is not None:
            inten = self._intensity(t)
            for r, cands in enumerate(self._cands):
                if not _above(inten[r], thr):
                    continue
                demanded.update(
                    uid for uid in cands
                    if self.tasks[uid].end > t
                    and t < self.deadline(uid))
        if self._round is None or self._round["t"] != t:
            self.blocked = {}
        self._round = {"t": t, "pass": is_pass, "demanded": demanded,
                       "preempted": set(), "drained": set(), "woken": [],
                       "asleep": None}

    def _close_round(self) -> None:
        """Count the round's preemptions that the rule demanded and the
        program did not make, and its wakes that the queue-pressure rule
        did not ask for."""
        r = self._round
        if r is None:
            return
        self.numbers["preempt_illegal"] += len(r["demanded"]
                                               - r["preempted"])
        if r["woken"]:
            self.numbers["wake_illegal"] += self._unasked_wakes(r)
        r["demanded"], r["woken"] = set(), []

    def _unasked_wakes(self, r) -> int:
        """The autoscaler's rule, walked over the pods that the round left
        unplaced and no policy holds, in queue order: a pod that fits the
        spare capacity of a node woken earlier in the pass takes it;
        any other pod that fits a sleeping node asks for one wake. The
        program's wakes, in order, answer those asks; a wake that answers
        none, or takes a node that was not asleep or does not fit, breaks
        the rule, and so does an ask left unanswered."""
        woken = r["woken"]
        if not self.auto["wake_on_pressure"]:
            return len(woken)
        t, fleet = r["t"], self.fleet
        fleet_min = (float(self._intensity(t).min()) if self.carbon
                     else -math.inf)
        queue = sorted((key, uid) for uid, key in self.pending.items()
                       if not self._held(uid, t, fleet_min))
        avail = r["asleep"].copy()
        spare = np.zeros((len(woken), 2))
        used, bad = 0, 0
        slack = reference.FIT_SLACK
        unanswered: dict = {}     # (cpu, mem) -> a sleeping node fits it
        for _, uid in queue:
            pod = self.pods[uid][1]
            cpu, mem = pod.cpu, pod.mem
            fit = ((spare[:used, 0] >= cpu - slack)
                   & (spare[:used, 1] >= mem - slack))
            if fit.any():
                spare[int(np.argmax(fit))] -= (cpu, mem)
                continue
            if used == len(woken):
                if (cpu, mem) not in unanswered:
                    unanswered[cpu, mem] = bool(
                        (avail & (fleet.vcpus >= cpu - slack)
                         & (fleet.mem_gb >= mem - slack)).any())
                bad += unanswered[cpu, mem]
                continue
            node = woken[used]
            bad += int(not (avail[node] and fleet.vcpus[node] >= cpu - slack
                            and fleet.mem_gb[node] >= mem - slack))
            avail[node] = False
            spare[used] = (fleet.vcpus[node] - cpu, fleet.mem_gb[node] - mem)
            used += 1
        return bad + len(woken) - used

    def _on_commit(self, t: float, uid: int, node: int) -> None:
        arrival, pod = self.pods[uid]
        if self._asleep_one(node, t):
            self.numbers["asleep_commits"] += 1
        waking = not math.isnan(self.wake_ready[node])
        start = float(self.wake_ready[node]) if waking else t
        if not waking:
            self._end_idle(node, t)
        task = _Task(uid, node, start,
                     pod.workload.base_time_s / self.fleet.speed[node],
                     pod.cpu)
        self.running[node] += 1
        self.used[node] += pod.cpu
        self.tasks[uid] = task
        self.attempts.append(task)
        heapq.heappush(self._timed, (task.end, _DONE, next(self._n), task))
        self.pending.pop(uid, None)
        if (pod.deferrable and uid not in self.preempted
                and self._preempt_threshold() is not None):
            self._cands[self.region[node]].add(uid)
        if pod.deferrable and self.any_policy:
            deadline = arrival + pod.deadline_s
            bad = start > deadline + 1e-9
            if self.carbon and t < deadline - DEADLINE_SLACK:
                fleet_min = float(self._intensity(t).min())
                bad |= _above(fleet_min, self.carbon["defer_threshold"])
            self.numbers["defer_illegal"] += int(bad)

    def _preempt_threshold(self):
        return (self.carbon or {}).get("preempt_threshold")

    def _on_evict(self, t: float, uid: int, node: int, cause) -> None:
        """A carbon preemption or a consolidation drain (``cause``); the
        log holds these only inside rounds."""
        task = self.tasks.get(uid)
        live = task is not None and task.active and task.node == node
        r = self._round
        if cause == "carbon":
            thr = self._preempt_threshold()
            ok = (live and thr is not None and self.pods[uid][1].deferrable
                  and uid not in self.preempted and task.end > t
                  and t < self.deadline(uid)
                  and not _at_or_below(
                      self._intensity(t)[self.region[node]], thr))
            self.numbers["preempt_illegal"] += int(not ok)
            self.preempted.add(uid)
            self.blocked[uid] = node
            r["preempted"].add(uid)
        elif node not in r["drained"]:
            a = self.auto
            ok = (r["pass"] and node >= a["min_awake"]
                  and self.running[node] > 0
                  and math.isnan(self.wake_ready[node])
                  and self.used[node] / self.fleet.vcpus[node]
                  < a["consolidate_util_below"] + reference.FIT_SLACK)
            self.numbers["drain_illegal"] += int(not ok)
            r["drained"].add(node)
        if not live:
            return
        task.run = max(t - task.start, 0.0)
        task.active = False
        self._release(task)
        self._enqueue(uid, front=cause == "drain")
        self._emptied(node, t)
        if cause == "drain" and self.running[node] == 0:
            self.idle_since[node] = t
            self.sleep_at[node] = t

    def _on_wake(self, t: float, node: int) -> None:
        r = self._round
        if r["asleep"] is None:
            r["asleep"] = self.asleep(t)
        self._end_idle(node, t)
        self.wake_ready[node] = t + self.latency[node]
        self.wakes[node] += 1
        heapq.heappush(self._timed, (float(self.wake_ready[node]), _READY,
                                     next(self._n), node))
        r["woken"].append(node)

    # --- energy ---------------------------------------------------------
    def fleet_energy_j(self) -> float:
        """The replay's fleet energy: each task's dynamic power for its run
        (cut at its eviction), plus, without an autoscaler, each node's
        idle power over the union of its tasks' runs; with one, each
        node's idle power while awake (ACTIVE, IDLE or WAKING), its sleep
        power while ASLEEP and one wake energy per wake, from the start of
        the replay to its horizon (the last round or task end)."""
        fleet = self.fleet
        horizon = max([self.horizon] + [t.end for t in self.attempts])
        self.advance(horizon, inclusive=True)
        dyn = 0.0
        busy: dict = {}
        for task in self.attempts:
            j = task.node
            dyn += fleet.dyn_power[j] * task.cpu * task.run
            busy.setdefault(j, []).append((task.start, task.end))
        if not self.auto:
            return dyn + sum(fleet.idle_power[j]
                             * reference.union_length(ivs)
                             for j, ivs in busy.items())
        asleep_s = self.asleep_s.copy()
        due = self._due()
        tail = self.asleep(horizon) & (due < horizon)
        asleep_s[tail] += horizon - due[tail]
        return dyn + float(np.sum(fleet.idle_power * (horizon - asleep_s)
                                  + self.sleep_power * asleep_s
                                  + self.wake_energy * self.wakes))

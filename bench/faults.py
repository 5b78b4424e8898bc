"""The control and the planted faults that the correctness check has to
catch. Each is a context manager that breaks the program underneath a
run; none is used by a benchmark run itself (see ``calibrate.py`` and
``tests/test_bench_cells.py``).

* ``control``: the reference, computed in bfloat16 (the precision below
  the configuration's float32), put in the place of ``score_queue``.
* ``stale_state``: the fleet reports no changed nodes, so the scheduler's
  criteria cache and device mirror keep their state unchanged.
* ``half_batch``: the second half of every queue is left out of scoring.
* ``altered_answer``: the first pod of every round is committed to its
  second-best node instead of its best.
* ``dropped_commit``: every 97th placement is dropped where the engine
  commits it, so its pod is never placed.
* ``energy_skew``: the program's power ledger books 1% more dynamic power.

Faults of the carbon and autoscale policies (a cell whose configuration
declares ``policies``):

* ``never_defer``: the carbon policy holds no pod back.
* ``never_sleep``: the autoscaler's idle timeout is infinite.
* ``never_preempt``: the carbon policy preempts nothing at a round's start.
* ``carbon_blind``: the schedulers' carbon-rate column reads zero.
* ``wake_storm``: every queue-pressure pass wakes every sleeping node.
* ``drain_busy``: consolidation drains nodes at any utilisation.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math

import numpy as np


@contextlib.contextmanager
def _patched(owner, name, make):
    orig = getattr(owner, name)
    setattr(owner, name, functools.wraps(orig)(make(orig)))
    try:
        yield
    finally:
        setattr(owner, name, orig)


class _FleetView:
    """The static columns of the program's fleet, under the names the
    reference reads."""

    def __init__(self, nodes):
        self.vcpus, self.mem_gb, self.speed = (nodes.vcpus, nodes.mem_gb,
                                               nodes.speed)
        self.dyn_power = nodes.dyn_power_per_vcpu
        self.idle_power = nodes.idle_power
        self.names = nodes.names

    def __len__(self):
        return len(self.names)


def control(cfg):
    """The reference in bfloat16 on ``score_queue``'s own inputs: the
    fleet's load and awake column, the round's time and the engine's
    exclusion masks."""
    import jax.numpy as jnp

    import reference
    from repro.core.scheduler import BatchScheduler

    def make(orig):
        def score_queue(sched, pods, nodes, now=0.0, exclude=None):
            cc = reference.score_round(
                cfg, _FleetView(nodes), nodes.used_cpu, nodes.used_mem, pods,
                now=now, awake=nodes.awake, exclude=exclude, xp=jnp,
                dtype=jnp.bfloat16)
            return cc.astype(np.float32)
        return score_queue
    return _patched(BatchScheduler, "score_queue", make)


def stale_state():
    from repro.cluster.node import FleetState

    def make(orig):
        def modified_since(fleet, version):
            return np.zeros(0, dtype=np.int64)
        return modified_since
    return _patched(FleetState, "modified_since", make)


def half_batch():
    from repro.core.scheduler import BatchScheduler

    def make(orig):
        def score_queue(sched, pods, nodes, *a, **kw):
            cc = np.array(orig(sched, pods, nodes, *a, **kw))
            cc[(len(pods) + 1) // 2:] = -np.inf
            return cc
        return score_queue
    return _patched(BatchScheduler, "score_queue", make)


def altered_answer():
    from repro.core.scheduler import BatchScheduler

    def make(orig):
        def select_many(sched, pods, nodes, *a, **kw):
            assignments, diag = orig(sched, pods, nodes, *a, **kw)
            cc = diag["closeness"]
            best = assignments[0] if len(pods) else None
            if best is not None:
                taken = set(assignments)
                worse = [j for j in np.argsort(-cc[0], kind="stable")
                         if np.isfinite(cc[0, j]) and cc[0, j] < cc[0, best]
                         and int(j) not in taken]
                if worse:
                    assignments = [int(worse[0])] + list(assignments[1:])
            return assignments, diag
        return select_many
    return _patched(BatchScheduler, "select_many", make)


def dropped_commit():
    from repro.cluster.engine import EventEngine
    count = [0]

    def make(orig):
        def commit(engine, *a, **kw):
            count[0] += 1
            if count[0] % 97 == 0:
                return None
            return orig(engine, *a, **kw)
        return commit
    return _patched(EventEngine, "_commit", make)


def energy_skew():
    from repro.core.energy import PowerTimeline

    def make(orig):
        def add(timeline, node, node_class, scheduler, start_s, runtime_s,
                dyn_power_w):
            return orig(timeline, node, node_class, scheduler, start_s,
                        runtime_s, dyn_power_w * 1.01)
        return add
    return _patched(PowerTimeline, "add", make)


def never_defer():
    from repro.core.carbon import CarbonScheduling
    return _patched(CarbonScheduling, "filter_pending",
                    lambda orig: lambda policy, sim, pods, t: [])


def _autoscale_knobs(**changes):
    """The autoscaler runs with ``changes`` to the cell's settings."""
    from repro.core.elastic import AutoscaleScheduling

    def make(orig):
        def init(policy, knobs):
            orig(policy, dataclasses.replace(knobs, **changes))
        return init
    return _patched(AutoscaleScheduling, "__init__", make)


def never_sleep():
    return _autoscale_knobs(idle_timeout_s=math.inf)


def never_preempt():
    from repro.core.carbon import CarbonScheduling
    return _patched(CarbonScheduling, "on_round_start",
                    lambda orig: lambda policy, sim, t: None)


def carbon_blind():
    from repro.core.carbon import CarbonSignal

    def make(orig):
        def intensities(signal, regions, t):
            return np.zeros(len(regions))
        return intensities
    return _patched(CarbonSignal, "intensities", make)


def wake_storm():
    from repro.core.elastic import ASLEEP, ElasticFleet

    def make(orig):
        def wake_for_pressure(fleet, sched, pods, t):
            woken = orig(fleet, sched, pods, t)
            if pods and fleet.policy.wake_on_pressure:
                for i, state in enumerate(fleet.states(t)):
                    if state == ASLEEP:
                        fleet.request_wake(i, t)
                        woken.append(i)
            return woken
        return wake_for_pressure
    return _patched(ElasticFleet, "wake_for_pressure", make)


def drain_busy():
    return _autoscale_knobs(consolidate_util_below=1.0)


FAULTS = {"stale_state": stale_state, "half_batch": half_batch,
          "altered_answer": altered_answer, "dropped_commit": dropped_commit,
          "energy_skew": energy_skew}

POLICY_FAULTS = {"never_defer": never_defer, "never_sleep": never_sleep,
                 "never_preempt": never_preempt,
                 "carbon_blind": carbon_blind, "wake_storm": wake_storm,
                 "drain_busy": drain_busy}

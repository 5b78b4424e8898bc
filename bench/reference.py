"""The plain reference: GreenPod's scoring, commit and energy arithmetic in
straightforward float64 numpy, from the configuration's data alone.

It imports nothing of the program and takes nothing the program has made
except the inputs of each answer: the fleet's load (``used_cpu``,
``used_mem``) at the start of a round and the pods of that round, and the
program's placements when it checks the commit and the energy ledger.
Node sizes, classes, power profiles and weights come from the
configuration and the benchmark's own fleet generator.

Criteria, in column order (GreenPod, arXiv 2506.04902, section III):
execution time and energy (cost), and free cores, free memory and balance
after placement (benefit); where the configuration declares a carbon
signal, a sixth, the carbon rate (cost): the placement's marginal power
times its region's grid intensity at the round's time, from the
configuration's own signal formula. TOPSIS normalises each column over
every node, scales by the normalised weights, takes the ideal and
anti-ideal points over feasible nodes only, and scores ``d- / (d+ + d-)``
(0.5 where both vanish, -inf on infeasible nodes).

Which nodes are awake and which are masked comes from the caller: without
an autoscaler a node that holds load is awake; with one, the power-state
ledger of ``reference_policies`` says which nodes sleep.
"""
from __future__ import annotations

import numpy as np

EPS = 1e-12
FIT_SLACK = 1e-9          # the Kubernetes fit test's float slack


def intensity(cfg: dict, t: float) -> np.ndarray:
    """(R,) grid intensity (gCO2/kWh) of the configuration's regions at
    ``t``: one diurnal sinusoid, ``base + amplitude * sin(2 pi (t + phase_s
    + r * stagger_s) / period_s)`` for the r-th region."""
    sig = cfg["policies"]["carbon"]["signal"]
    r = np.arange(len(cfg["regions"]), dtype=np.float64)
    phase = sig["phase_s"] + r * sig["stagger_s"]
    return sig["base"] + sig["amplitude"] * np.sin(
        2.0 * np.pi / sig["period_s"] * (t + phase))


def has_carbon(cfg: dict) -> bool:
    return "carbon" in cfg.get("policies", {})


def criteria(fleet, used_cpu, used_mem, cpu: float, mem: float,
             base_time_s: float, awake=None,
             node_intensity=None) -> np.ndarray:
    """(N, C) decision matrix of one pod kind against the round's fleet. An
    awake node's idle power is already paid; a pod placed on a node that
    is not awake pays that node's idle power too. ``awake`` defaults to
    the nodes that hold load; ``node_intensity`` (N,) adds the carbon-rate
    column."""
    up = used_cpu > 1e-9 if awake is None else awake
    exec_t = base_time_s / fleet.speed
    power = fleet.dyn_power * cpu + np.where(up, 0.0, fleet.idle_power)
    energy = power * exec_t
    cpu_after = (used_cpu + cpu) / fleet.vcpus
    mem_after = (used_mem + mem) / fleet.mem_gb
    cols = [exec_t, energy, np.maximum(1.0 - cpu_after, 0.0),
            np.maximum(1.0 - mem_after, 0.0),
            1.0 - np.abs(cpu_after - mem_after)]
    if node_intensity is not None:
        cols.append(power * node_intensity)
    return np.stack(cols, axis=-1)


def topsis(mat, weights, benefit, valid, xp=np, dtype=np.float64):
    """(N,) closeness of one (N, C) decision matrix, every step in
    ``dtype`` on the array module ``xp`` (numpy float64 is the reference;
    the control runs the same steps in a lower precision)."""
    mat = xp.asarray(mat, dtype=dtype)
    w = xp.asarray(weights, dtype=dtype)
    w = w / xp.maximum(w.sum(), EPS)
    benefit = xp.asarray(benefit, dtype=bool)
    valid = xp.asarray(valid, dtype=bool)
    norms = xp.sqrt((mat * mat).sum(axis=0, keepdims=True))
    v = mat / xp.maximum(norms, EPS) * w
    inf = xp.asarray(np.inf, dtype=dtype)
    worst = xp.where(benefit, -inf, inf)
    best = xp.where(benefit, inf, -inf)
    vw = xp.where(valid[:, None], v, worst)
    vb = xp.where(valid[:, None], v, best)
    a_pos = xp.where(benefit, vw.max(axis=0), vw.min(axis=0))
    a_neg = xp.where(benefit, vb.min(axis=0), vb.max(axis=0))
    d_pos = xp.sqrt(((v - a_pos) ** 2).sum(axis=1))
    d_neg = xp.sqrt(((v - a_neg) ** 2).sum(axis=1))
    total = d_pos + d_neg
    cc = d_neg / xp.maximum(total, EPS)
    cc = xp.where(total <= EPS, xp.asarray(0.5, dtype=dtype), cc)
    return xp.where(valid, cc, -inf)


def fits(fleet, used_cpu, used_mem, cpu: float, mem: float) -> np.ndarray:
    return ((fleet.vcpus - used_cpu >= cpu - FIT_SLACK)
            & (fleet.mem_gb - used_mem >= mem - FIT_SLACK))


def score_round(cfg: dict, fleet, used_cpu, used_mem, pods, now=0.0,
                awake=None, exclude=None, xp=np,
                dtype=np.float64) -> np.ndarray:
    """(P, N) closeness of a round's queue on one fleet snapshot at time
    ``now``. ``awake`` (N,) as in ``criteria``; ``exclude`` (N,) or (P, N)
    masks nodes out of every pod's, or each pod's, feasible set. Pods of
    one kind and one mask row share their scores."""
    benefit = [c["benefit"] for c in cfg["criteria"]]
    weights = cfg["weights"]
    node_intensity = None
    if has_carbon(cfg):
        region = np.arange(len(fleet)) % len(cfg["regions"])
        node_intensity = intensity(cfg, now)[region]
    ex = None if exclude is None else np.asarray(exclude, dtype=bool)
    rows: dict = {}
    out = np.empty((len(pods), len(fleet)), dtype=np.float64)
    for i, pod in enumerate(pods):
        row_ex = ex if ex is None or ex.ndim == 1 else ex[i]
        kind = (pod.cpu, pod.mem, pod.workload.base_time_s)
        key = kind if ex is None or ex.ndim == 1 else (kind,
                                                        row_ex.tobytes())
        if key not in rows:
            mat = criteria(fleet, used_cpu, used_mem, *kind, awake=awake,
                           node_intensity=node_intensity)
            valid = fits(fleet, used_cpu, used_mem, pod.cpu, pod.mem)
            if row_ex is not None:
                valid &= ~row_ex
            with np.errstate(invalid="ignore"):    # rows with no fit
                rows[key] = np.asarray(topsis(mat, weights, benefit, valid,
                                              xp=xp, dtype=dtype),
                                       dtype=np.float64)
        out[i] = rows[key]
    return out


def illegal_commits(cc: np.ndarray, pods, fleet, used_cpu, used_mem,
                    assignments, blocked=None) -> int:
    """Pods whose placement is not a legal greedy ledger walk of the
    round's scores: in queue order each pod must take a node of highest
    finite score among those that still fit the ledger, or stay unplaced
    when none does. Ties may go to any of
    the tied nodes; the ledger follows the program's own choices.
    ``blocked`` maps a pod's uid to the node it may not take this round
    (the one it was just preempted off)."""
    free_cpu = fleet.vcpus - used_cpu
    free_mem = fleet.mem_gb - used_mem
    bad = 0
    for i, pod in enumerate(pods):
        ok = (free_cpu >= pod.cpu - FIT_SLACK) & (free_mem >= pod.mem - FIT_SLACK)
        ok &= np.isfinite(cc[i])
        if blocked and pod.uid in blocked:
            ok[blocked[pod.uid]] = False
        chosen = assignments[i]
        if chosen is None:
            bad += int(ok.any())
            continue
        if not ok[chosen] or cc[i, chosen] < cc[i][ok].max():
            bad += 1
        free_cpu[chosen] -= pod.cpu
        free_mem[chosen] -= pod.mem
    return bad


def union_length(intervals) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def task_energy_j(records, fleet) -> float:
    """Energy of the replay's task placements: each task's dynamic power
    for its run, plus each node's idle power over the union of the time
    its tasks keep it busy. Each task runs ``base_time_s / speed`` of its
    node."""
    index = {name: i for i, name in enumerate(fleet.names)}
    dyn = 0.0
    busy: dict = {}
    for r in records:
        j = index[r.node]
        run = r.pod.workload.base_time_s / fleet.speed[j]
        dyn += fleet.dyn_power[j] * r.pod.cpu * run
        busy.setdefault(j, []).append((r.start_s, r.start_s + run))
    idle = sum(fleet.idle_power[j] * union_length(ivs)
               for j, ivs in busy.items())
    return dyn + idle

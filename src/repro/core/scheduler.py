"""Schedulers: GreenPod (TOPSIS), its fleet-scale batched variant, and the
default-K8s baseline.

Per-pod schedulers expose ``select(pod, nodes) -> (node_index | None,
diagnostics)`` over a list of ``repro.cluster.node.Node`` (or a prebuilt
``NodeTable``). ``BatchScheduler.select_many(pods, nodes)`` scores a whole
queue of pods against one fleet snapshot in a single call — the 1000+-node
path. The baseline reimplements the upstream kube-scheduler scoring
pipeline the paper compares against: filter (PodFitsResources) → score
(LeastRequestedPriority + BalancedResourceAllocation) → bind to max score.

Backends (scoring engines, identical semantics — tests assert equivalence):

  numpy   — ``topsis.closeness_np``; lowest latency for single decisions
            (no device dispatch) and the semantic reference. A
            ``BatchScheduler`` call on a table that is not its attached
            fleet rebuilds the whole (P, N, C) decision tensor: the oracle.
  jax     — jitted jnp engine; ``BatchScheduler`` scores a queue as one
            gather from a device-resident per-kind criteria tensor plus
            the vmapped closeness (``topsis.batched_closeness``).
  pallas  — the tiled TPU kernels via ``repro.kernels.ops`` (interpret
            mode on CPU, Mosaic on TPU); ``BatchScheduler`` streams the
            per-kind tensor through the scalar-prefetch kinds kernel.

The device backends of ``BatchScheduler`` have one scoring round, through
a :class:`FleetCriteriaCache`: the attached fleet's, kept across rounds,
or one built for a single call on any other table.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from repro.core import telemetry, topsis
from repro.core.carbon import CarbonSignal
from repro.core.criteria import (benefit_mask, criteria_matrix,
                                 greenpod_criteria, placement_power)
from repro.core.energy import predicted_task_energy_joules
from repro.core.weighting import (CARBON_SCHEMES, adaptive_weights,
                                  scheme_grid, validate_weights, weights_for)
from repro.cluster.node import FleetState, Node, NodeTable
from repro.cluster.workload import Pod

_BENEFIT = benefit_mask()

BACKENDS = ("numpy", "jax", "pallas")


def predict_exec_time(pod: Pod, node: Node) -> float:
    """Energy-profiling module prediction: runtime scales inversely with the
    node class's per-core speed (requests are guaranteed, no oversubscription
    past the filter)."""
    return pod.workload.base_time_s / node.speed


def predict_energy(pod: Pod, node: Node) -> float:
    awake = node.used_cpu > 1e-9
    return predicted_task_energy_joules(
        node.node_class, predict_exec_time(pod, node), pod.cpu, awake)


def _as_table(nodes) -> NodeTable:
    return nodes if isinstance(nodes, NodeTable) else NodeTable.from_nodes(nodes)


def decision_matrix_table(cpu, mem, base_time_s, table: NodeTable,
                          carbon_intensity=None) -> np.ndarray:
    """(..., N, C) GreenPod decision matrix by broadcasting over the fleet's
    column arrays (criteria.CRITERIA_NAMES order) — no per-node Python loop.

    ``cpu`` / ``mem`` / ``base_time_s`` are scalars for one pod (→ (N, C))
    or ``(P, 1)`` arrays for a queue (→ (P, N, C)). C is 5, or 6 when
    ``carbon_intensity`` (the (N,) gCO2/kWh column for the fleet's regions
    at decision time) is given — the sixth column is the placement's
    emission rate: power draw (dynamic for the request, plus the idle power
    a sleeping node would newly wake) x regional intensity. The arithmetic
    lives in :func:`repro.core.criteria.criteria_matrix` — the same code
    the incremental :class:`FleetCriteriaCache` uses to refresh dirty node
    columns, so the two paths agree bitwise by construction."""
    return criteria_matrix(cpu, mem, base_time_s, table,
                           carbon_intensity=carbon_intensity)


def decision_matrix(pod: Pod, nodes, carbon_intensity=None) -> np.ndarray:
    """(N, C) decision matrix for one pod; ``nodes`` is a Node list or a
    NodeTable."""
    table = _as_table(nodes)
    return decision_matrix_table(pod.cpu, pod.mem, pod.workload.base_time_s,
                                 table, carbon_intensity=carbon_intensity)


def decision_matrix_batch(pods: Sequence[Pod], nodes,
                          carbon_intensity=None) -> np.ndarray:
    """(P, N, C) decision tensor for a queue of pods against one fleet
    snapshot (every pod scored on identical cluster state)."""
    table = _as_table(nodes)
    col = lambda xs: np.asarray(xs, dtype=np.float64)[:, None]
    return decision_matrix_table(col([p.cpu for p in pods]),
                                 col([p.mem for p in pods]),
                                 col([p.workload.base_time_s for p in pods]),
                                 table, carbon_intensity=carbon_intensity)


def _score(mat: np.ndarray, weights: np.ndarray, valid: np.ndarray,
           backend: str, benefit: np.ndarray = _BENEFIT) -> np.ndarray:
    """(N,) closeness for one decision matrix on the given backend
    (invalid rows are -inf)."""
    if backend == "numpy":
        return np.asarray(topsis.closeness_np(mat, weights, benefit,
                                              valid).closeness)
    if backend == "jax":
        return np.asarray(topsis.closeness(mat, weights, benefit,
                                           valid).closeness)
    if backend == "pallas":
        from repro.kernels import ops
        return np.asarray(ops.topsis_closeness(mat, weights, benefit,
                                               valid=valid))
    raise ValueError(f"unknown backend {backend!r}; choose from {BACKENDS}")


def _take_rows(a: np.ndarray, idx) -> np.ndarray:
    """``a[idx]`` of a 2-D array, gathered in the array's own memory order:
    the device hands the scores back pod-minor (Fortran order), where a
    row-wise gather strides across the whole matrix."""
    if a.flags.f_contiguous and not a.flags.c_contiguous:
        return np.take(a.T, idx, axis=1).T
    return np.take(a, idx, axis=0)


def _rank_groups(cc: np.ndarray, requests: list) -> "tuple[list, list]":
    """Group the queue's pods that share one ranking: rows of ``cc`` equal
    byte for byte and equal ``(cpu, mem)`` requests. Returns ``(group,
    reps)``: pod i's group index and each group's first pod. Each pod is
    compared with the first pod of its request whose row has the same XOR
    fold of its bits; a pod whose row still differs is grouped on its
    row's bytes."""
    bits = cc[:len(requests)].view(np.dtype(f"u{cc.itemsize}"))
    folds = np.bitwise_xor.reduce(bits, axis=1).tolist()
    first: dict = {}
    rep_of = [first.setdefault((folds[i], req), i)
              for i, req in enumerate(requests)]
    same = (bits == _take_rows(bits, rep_of)).all(axis=1).tolist()
    group: list[int] = []
    reps: list[int] = []
    ids: dict = {}
    for i, req in enumerate(requests):
        key = rep_of[i] if same[i] else (req, bits[i].tobytes())
        g = ids.get(key)
        if g is None:
            g = ids[key] = len(reps)
            reps.append(i)
        group.append(g)
    return group, reps


def _greedy_assign(cc: np.ndarray, pods: Sequence[Pod], table: NodeTable,
                   blocked=None) -> "list[int | None]":
    """Commit one (P, N) closeness matrix greedily in queue order against a
    fresh capacity ledger: each pod takes its best-ranked node that still
    fits (``blocked[i]`` optionally forbids one node index for ``pods[i]``).
    Extracted from :meth:`BatchScheduler.select_many` so the grid path
    commits every scheme through identical code — the per-scheme ledgers
    are independent what-if placements off the same snapshot.

    Pods with the same row and request (:func:`_rank_groups`) share one
    stable descending argsort and one cursor into it: when no request is
    negative, free capacity only shrinks within a call, so a node that
    fails a group's fit test fails it for the rest of the call and the
    cursor moves past it for good. A ``-inf`` score ends a scan; a node
    blocked for one pod that still fits holds the cursor for the rest of
    the group. Placements are those of sorting and walking every row from
    rank 0."""
    tel = telemetry.active()
    requests = [(pod.cpu, pod.mem) for pod in pods]
    with tel.stage("scheduler_argsort"):
        group, reps = _rank_groups(cc, requests)
        order = np.argsort(-_take_rows(cc, reps), kind="stable",
                           axis=-1)
    with tel.stage("scheduler_walk"):
        free_cpu = table.free_cpu.copy()
        free_mem = table.free_mem.copy()
        shrinks = all(c >= 0 and m >= 0 for c, m in requests)
        ranks = list(order)
        scores = [cc[i] for i in reps]
        cursor = [0] * len(reps)
        n = cc.shape[-1]
        neg_inf = -np.inf
        steps = 0
        assignments: list[int | None] = []
        for i, (cpu, mem) in enumerate(requests):
            g = group[i]
            rank, score = ranks[g], scores[g]
            forbid = blocked[i] if blocked is not None else None
            need_cpu, need_mem = cpu - 1e-9, mem - 1e-9
            pos = start = cursor[g]
            advance = shrinks
            chosen = None
            while pos < n:
                j = rank[pos]
                if score[j] == neg_inf:
                    break           # rest of the ranking is infeasible
                if free_cpu[j] >= need_cpu and free_mem[j] >= need_mem:
                    if forbid is None or j != forbid:
                        chosen = int(j)
                        free_cpu[j] -= cpu
                        free_mem[j] -= mem
                        break
                    advance = False     # blocked for this pod only
                elif advance:
                    cursor[g] = pos + 1
                pos += 1
            steps += pos - start + (pos < n)
            assignments.append(chosen)
    tel.inc("scheduler_rank_groups", value=float(len(reps)))
    tel.inc("scheduler_walk_steps", value=float(steps))
    return assignments


def _upload(tel, arrays):
    """Put one round's host inputs on the device, counting the bytes."""
    import jax
    out = jax.device_put(arrays)
    tel.inc("scheduler_upload_bytes", value=float(sum(a.nbytes for a in out)))
    return out


def _readback(tel, cc, labels: dict, p: int | None = None) -> np.ndarray:
    """Wait for the device's scores and copy them to the host, then keep
    the first ``p`` pod rows (the pod axis is the second to last). The
    device array keeps its padded pod axis: slicing it there would
    compile one program per distinct queue length, so the padding rows
    are dropped on the host. ``scheduler_readback_bytes`` counts the
    padded bytes copied.

    The rows kept are copied into an array of their own, in the
    readback's memory order: a view of the first ``p`` rows of the
    pod-minor (Fortran-order) padded matrix is contiguous in neither
    order, and the commit's row gathers and reductions on such a view
    (``_rank_groups``, ``_take_rows``) stride across the whole matrix."""
    with tel.stage("scheduler_readback", **labels):
        out = np.asarray(cc)
        nbytes = out.nbytes
        if p is not None and p != out.shape[-2]:
            out = out[..., :p, :].copy(order="K")
    tel.inc("scheduler_readback_bytes", value=float(nbytes))
    return out


def _check_carbon_scheme(scheme: str, carbon_signal) -> None:
    if scheme in CARBON_SCHEMES and carbon_signal is None:
        raise ValueError(
            f"scheme {scheme!r} weights the carbon-rate criterion; "
            f"construct the scheduler with a carbon_signal "
            f"(repro.core.carbon.CarbonSignal) to use it")


class FleetCriteriaCache:
    """Incrementally maintained decision-matrix cache over one fleet
    snapshot: the scheduler's attached :class:`~repro.cluster.node.
    FleetState`, or a plain :class:`~repro.cluster.node.NodeTable` that a
    device-backend call scores once (a throwaway cache, synced whole on
    first use: every kind row is built in full, and the table is never
    dirty).

    The insight that makes the cache cheap: pods come in a handful of
    workload *kinds* (identical ``(cpu, mem, base_time_s)`` request
    triples), and the criteria arithmetic is elementwise per node — so one
    ``(K, N, C)`` float64 tensor (K = kinds seen so far) covers every pod,
    and a pod's ``(N, C)`` matrix is a zero-copy row view. Per round
    :meth:`sync` consumes the fleet's dirty-column contract
    (``modified_since``): only columns of nodes touched since the last
    sync are recomputed (through ``repro.core.criteria.criteria_matrix``,
    the same code the full-rebuild oracle uses — bitwise agreement by
    construction), and the carbon_rate column is refreshed from the cached
    time-invariant power factor whenever decision time moves.

    ``dev`` is the cache's own device-resident float32 mirror of ``mats``
    (:meth:`sync_device`), which the jax round gathers from; a throwaway
    cache uploads a mirror of its own and leaves the attached one alone.

    Returned matrices/rows are views into the cache: read-only until the
    next :meth:`sync`.
    """

    def __init__(self, fleet: NodeTable,
                 carbon_signal: CarbonSignal | None):
        self.fleet = fleet
        self.signal = carbon_signal
        self.n_criteria = 6 if carbon_signal is not None else 5
        self._kinds: dict[tuple, int] = {}    # request triple -> row index
        self._reqs: list[tuple] = []
        n = len(fleet)
        self.mats = np.zeros((0, n, self.n_criteria))
        self._power_w = np.zeros((0, n))      # carbon power factor per kind
        self._tracked = isinstance(fleet, FleetState)
        self._synced = fleet.version if self._tracked else 0
        self._carbon_now: float | None = None
        self.intensities: np.ndarray | None = None   # (N,) at _carbon_now
        self.dev = None           # device-resident (K, N, C) float32 mirror

    def _kind_of(self, pod: Pod) -> tuple:
        return (pod.cpu, pod.mem, pod.workload.base_time_s)

    def _full_row(self, req: tuple) -> tuple[np.ndarray, np.ndarray]:
        cpu, mem, bts = req
        mat = np.zeros((len(self.fleet), self.n_criteria))
        mat[:, :5] = criteria_matrix(cpu, mem, bts, self.fleet)
        power = np.zeros(0)
        if self.signal is not None:
            power = placement_power(cpu, self.fleet)
            mat[:, 5] = power * self.intensities
        return mat, power

    def sync(self, pods: Sequence[Pod], now: float):
        """Bring the cache up to date with the fleet and decision time;
        returns ``(kind_idx, dirty, carbon_moved, grew)`` — the per-pod row
        indices, the node indices whose columns were recomputed, whether
        the whole carbon column was refreshed (``now`` moved), and whether
        new kind rows were appended (device mirrors re-upload on growth)."""
        tel = telemetry.active()
        fleet = self.fleet
        if self._tracked:
            dirty = fleet.modified_since(self._synced)
            self._synced = fleet.version
        else:
            dirty = np.zeros(0, dtype=np.int64)
        carbon_moved = False
        if self.signal is not None and now != self._carbon_now:
            self.intensities = np.asarray(
                self.signal.intensities(fleet.region, now), dtype=np.float64)
            self._carbon_now = now
            carbon_moved = True
        if dirty.size and self._reqs:
            col = lambda xs: np.asarray(xs, dtype=np.float64)[:, None]
            cpus, mems, bts = (col([r[j] for r in self._reqs])
                               for j in range(3))
            self.mats[:, dirty, :5] = criteria_matrix(cpus, mems, bts,
                                                      fleet, cols=dirty)
            if self.signal is not None:
                self._power_w[:, dirty] = placement_power(cpus, fleet,
                                                          cols=dirty)
        if self.signal is not None and self._reqs:
            # the carbon column is (time-invariant power) x (intensity at
            # now): refresh all nodes when now moved, else just the dirty
            # subset — elementwise either way, so bitwise-equal to a full
            # rebuild at the same instant
            if carbon_moved:
                self.mats[:, :, 5] = self._power_w * self.intensities
            elif dirty.size:
                self.mats[:, dirty, 5] = (self._power_w[:, dirty]
                                          * self.intensities[dirty])
        grew = False
        new_kinds = 0
        kind_idx = np.empty(len(pods), dtype=np.int64)
        for i, pod in enumerate(pods):
            req = self._kind_of(pod)
            k = self._kinds.get(req)
            if k is None:
                mat, power = self._full_row(req)
                k = len(self._reqs)
                self._kinds[req] = k
                self._reqs.append(req)
                self.mats = np.concatenate([self.mats, mat[None]])
                if self.signal is not None:
                    self._power_w = np.concatenate(
                        [self._power_w, power[None]])
                grew = True
                new_kinds += 1
            kind_idx[i] = k
        if dirty.size:
            tel.inc("cache_dirty_columns", value=float(dirty.size))
        if carbon_moved:
            tel.inc("cache_carbon_refreshes")
        if new_kinds:
            tel.inc("cache_kind_rows_added", value=float(new_kinds))
        return kind_idx, dirty, carbon_moved, grew

    def sync_device(self, dirty: np.ndarray, carbon_moved: bool,
                    grew: bool):
        """Mirror the delta :meth:`sync` just returned onto ``dev`` and
        return it. Growth (a kind first seen — at most once per workload
        kind per run) re-uploads the whole (K, N, C) tensor; otherwise the
        dirty node columns are scattered into the donated buffer (idx
        padded to a power of two with repeats so the scatter trace is
        shape-stable), and the carbon column is rewritten only when
        decision time moved. Builds the jitted helpers on first use."""
        import jax.numpy as jnp
        _jit_helpers()
        tel = telemetry.active()
        if self.dev is None or grew:
            tel.inc("cache_device_reuploads",
                    reason="growth" if self.dev is not None else "first")
            self.dev = jnp.asarray(self.mats.astype(np.float32))
            tel.inc("scheduler_upload_bytes", value=float(self.dev.nbytes))
            return self.dev
        if dirty.size:
            tel.inc("cache_device_scatters")
            d_pad = _pow2_pad_len(dirty.size)
            idx = np.concatenate(
                [dirty, np.full(d_pad - dirty.size, dirty[0],
                                dtype=dirty.dtype)])
            block = self.mats[:, idx, :].astype(np.float32)
            self.dev = _scatter_node_cols(self.dev,
                                          *_upload(tel, (idx, block)))
        if carbon_moved and self.signal is not None:
            tel.inc("cache_device_carbon_updates")
            col = self.mats[:, :, -1].astype(np.float32)
            self.dev = _set_carbon_col(self.dev, *_upload(tel, (col,)))
        return self.dev


def _jit_helpers():
    """The jax round's jitted helpers, built lazily so importing
    the scheduler never pays jax tracing up front."""
    global _scatter_node_cols, _set_carbon_col, _closeness_from_kinds
    global _closeness_grid_from_kinds
    if _scatter_node_cols is not None:
        return
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _scatter_node_cols(dev, idx, block):
        # donated: the old snapshot's buffer is reused in place, so a round
        # never holds two (K, N, C) copies on device
        return dev.at[:, idx, :].set(block)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _set_carbon_col(dev, col):
        return dev.at[:, :, -1].set(col)

    @jax.jit
    def _closeness_from_kinds(dev, kind_idx, ws, benefit, valids):
        # gather the per-kind rows and score in ONE dispatch; the closeness
        # body is topsis.batched_closeness
        return topsis.batched_closeness(dev[kind_idx], ws, benefit,
                                        valids).closeness

    @jax.jit
    def _closeness_grid_from_kinds(dev, kind_idx, ws, benefit, valids):
        # the grid round: ONE fused gather + (S, P, N) closeness dispatch
        # off the device-resident kind tensor — no re-upload per scheme.
        # The gather happens once; XLA shares the weight-independent
        # normalization across the vmapped scheme axis.
        mats = dev[kind_idx]

        def one_scheme(w):
            wp = jnp.broadcast_to(w, (mats.shape[0], w.shape[-1]))
            return topsis.batched_closeness(mats, wp, benefit,
                                            valids).closeness

        return jax.vmap(one_scheme)(ws)


_scatter_node_cols = None
_set_carbon_col = None
_closeness_from_kinds = None
_closeness_grid_from_kinds = None


def _pow2_pad_len(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length()


def _pad_pod_axis(kind_idx: np.ndarray, valid: np.ndarray,
                  ws: np.ndarray | None = None):
    """Pad one round's device scoring inputs along the pod axis to the next
    power of two: jit and Mosaic both cache compiled programs by shape, so
    shrinking retry queues (P, P-1, ...) reuse a compiled program instead
    of compiling one per queue length. The (P,) kind index pads with kind
    0, ``ws`` with ones, and ``valid`` with all-False rows: padding pods
    are infeasible everywhere, score -inf, and :func:`_readback` drops
    them on the host."""
    pad = _pow2_pad_len(len(valid)) - len(valid)
    if pad:
        kind_idx = np.concatenate([kind_idx, np.zeros(pad, kind_idx.dtype)])
        valid = np.concatenate(
            [valid, np.zeros((pad, valid.shape[-1]), bool)])
        if ws is not None:
            ws = np.concatenate([ws, np.ones((pad, ws.shape[-1]))])
    return kind_idx, valid, ws


def _valid_mask(pods: Sequence[Pod], table: NodeTable,
                exclude) -> np.ndarray:
    """(P, N) scoring validity: capacity fit, minus the engine's
    ``exclude`` mask ((N,) or (P, N))."""
    valid = table.fits(np.asarray([p.cpu for p in pods])[:, None],
                       np.asarray([p.mem for p in pods])[:, None])
    if exclude is not None:
        valid = valid & ~np.asarray(exclude, dtype=bool)
    return valid


class GreenPodScheduler:
    """TOPSIS-based multi-criteria scheduler (paper §III).

    With a ``carbon_signal`` attached the decision matrix gains the sixth
    carbon-rate column (node power x regional grid intensity at ``now``) and
    weight vectors are the 6-criteria form — paper schemes carry a zero
    carbon weight, so their rankings are bitwise unchanged."""

    name = "topsis"

    def __init__(self, scheme: str = "energy_centric", adaptive: bool = False,
                 backend: str = "numpy",
                 carbon_signal: CarbonSignal | None = None,
                 explain: bool = False):
        _check_carbon_scheme(scheme, carbon_signal)
        self.scheme = scheme
        self.adaptive = adaptive
        self.backend = backend
        self.carbon_signal = carbon_signal
        self.criteria = greenpod_criteria(carbon=carbon_signal is not None)
        self._benefit = benefit_mask(self.criteria)
        self.decision_log: list[dict] = []
        self.explain = explain
        self.explanations: list[dict] = []
        self._cache: FleetCriteriaCache | None = None

    def attach(self, fleet: FleetState) -> None:
        """Adopt ``fleet`` as a live, delta-maintained snapshot: subsequent
        ``select`` calls that receive this exact object reuse the
        incrementally synced decision-matrix cache instead of rebuilding
        the pod's (N, C) matrix from scratch."""
        self._cache = FleetCriteriaCache(fleet, self.carbon_signal)

    def weights(self, nodes) -> np.ndarray:
        carbon = self.carbon_signal is not None
        if not self.adaptive:
            return weights_for(self.scheme, carbon=carbon)
        util = float(np.mean(_as_table(nodes).cpu_util))
        return adaptive_weights(self.scheme, util, carbon=carbon)

    def select(self, pod: Pod, nodes, now: float = 0.0, exclude=None,
               explain: bool = False):
        """Best node for one pod; ``exclude`` optionally masks nodes the
        engine forbids this round (ASLEEP nodes, or WAKING nodes whose
        ready time would start a deferrable pod past its deadline) — they
        are treated exactly like capacity-infeasible nodes. With
        ``explain=True`` (or the scheduler constructed with it) the
        decision's per-criterion attribution (``topsis.explain_np``) is
        appended to ``self.explanations`` and returned in the diagnostics
        — numpy backend only (the jax/pallas engines do not expose the
        weighted intermediates)."""
        explain = explain or self.explain
        if explain and self.backend != "numpy":
            raise ValueError(
                f"explain=True needs backend='numpy', not "
                f"{self.backend!r}: only the numpy path exposes the "
                f"weighted separation terms the attribution decomposes")
        w = None
        with telemetry.active().span("scheduler_decision",
                                     scheduler=self.name,
                                     backend=self.backend) as sp:
            table = _as_table(nodes)
            valid = table.fits(pod.cpu, pod.mem)
            if exclude is not None:
                valid = valid & ~np.asarray(exclude, dtype=bool)
            if not valid.any():
                return None, {"reason": "unschedulable"}
            if self._cache is not None and table is self._cache.fleet:
                kind_idx, _, _, _ = self._cache.sync([pod], now)
                mat = self._cache.mats[kind_idx[0]]
            else:
                inten = (self.carbon_signal.intensities(table.region, now)
                         if self.carbon_signal is not None else None)
                mat = decision_matrix_table(pod.cpu, pod.mem,
                                            pod.workload.base_time_s, table,
                                            carbon_intensity=inten)
            w = self.weights(table)
            cc = _score(mat, w, valid, self.backend, benefit=self._benefit)
            idx = int(np.argmax(cc))   # first max — same tie-break as a
            #                            stable sort
        dt = sp.duration_s
        diag = {"closeness": cc, "scheduling_time_s": dt, "matrix": mat}
        if explain:
            exp = topsis.explain_np(mat, w, self._benefit, valid,
                                    criteria_names=[c.name
                                                    for c in self.criteria])
            exp.update(pod=pod.uid, t=now, node=table.names[idx],
                       runner_up_node=(table.names[exp["runner_up"]]
                                       if exp["runner_up"] is not None
                                       else None))
            self.explanations.append(exp)
            diag["explanation"] = exp
        self.decision_log.append({"pod": pod.uid, "node": table.names[idx],
                                  "time_s": dt})
        return idx, diag


class BatchScheduler:
    """Fleet-scale batched TOPSIS: one scoring pass per arrival burst.

    ``select_many`` builds the (P, N, 5) decision tensor by broadcasting,
    scores every pod against the same fleet snapshot on the configured
    backend, then commits placements greedily in queue order against a
    capacity ledger (each pod takes its best-ranked node that still fits).
    Snapshot scoring is the throughput trade-off vs. the per-pod scheduler's
    rescore-after-every-bind: one engine call amortizes dispatch over the
    whole queue, which is what wins at 1000+ nodes (see
    benchmarks/scheduling_time.py). Input nodes are never mutated — the
    caller binds from the returned assignments.
    """

    name = "topsis-batch"

    def __init__(self, scheme: str = "energy_centric", adaptive: bool = False,
                 backend: str = "jax",
                 carbon_signal: CarbonSignal | None = None,
                 explain: bool = False):
        _check_carbon_scheme(scheme, carbon_signal)
        self.scheme = scheme
        self.adaptive = adaptive
        self.backend = backend
        self.carbon_signal = carbon_signal
        self.criteria = greenpod_criteria(carbon=carbon_signal is not None)
        self._benefit = benefit_mask(self.criteria)
        self.decision_log: list[dict] = []
        self.explain = explain
        self.explanations: list[dict] = []
        self._cache: FleetCriteriaCache | None = None

    def attach(self, fleet: FleetState) -> None:
        """Adopt ``fleet`` as a live, delta-maintained snapshot. Scoring
        calls that receive this exact object take the incremental path:
        only dirty node columns are recomputed, and (jax backend) the
        per-kind criteria tensor stays device-resident across rounds —
        dirty columns are scattered into the donated buffer and a round is
        one fused gather+closeness dispatch."""
        self._cache = FleetCriteriaCache(fleet, self.carbon_signal)

    def weights(self, table: NodeTable) -> np.ndarray:
        carbon = self.carbon_signal is not None
        if not self.adaptive:
            return weights_for(self.scheme, carbon=carbon)
        return adaptive_weights(self.scheme, float(np.mean(table.cpu_util)),
                                carbon=carbon)

    def _cache_for(self, table: NodeTable) -> FleetCriteriaCache | None:
        """The criteria cache a scoring call on ``table`` runs through: the
        attached one when ``table`` is the attached fleet; otherwise None
        on numpy, whose full rebuild is the reference oracle
        (tests/test_fleet_state.py asserts the two agree bitwise), and a
        throwaway cache over the snapshot on jax and pallas, so the device
        backends have one scoring round only."""
        if self._cache is not None and table is self._cache.fleet:
            return self._cache
        if self.backend == "numpy":
            return None
        return FleetCriteriaCache(table, self.carbon_signal)

    def score_queue(self, pods: Sequence[Pod], nodes,
                    now: float = 0.0, exclude=None) -> np.ndarray:
        """(P, N) closeness matrix for the whole queue on one snapshot
        (infeasible nodes are -inf per pod). ``now`` is the decision time
        the carbon column is evaluated at (ignored without a signal).
        ``exclude`` — (N,) or (P, N) bool — masks nodes the engine forbids
        (sleeping nodes; per-pod deadline-late WAKING nodes), folded into
        the validity mask every backend already honors.

        Scores go through the per-kind criteria cache of
        :meth:`_cache_for`, except numpy on a table that is not the
        attached fleet, which rebuilds the (P, N, C) tensor below."""
        table = _as_table(nodes)
        tel = telemetry.active()
        tel.inc("scheduler_pods_scored", value=float(len(pods)))
        cache = self._cache_for(table)
        if cache is not None:
            return self._score_queue_kinds(pods, cache, now, exclude)
        labels = {"backend": self.backend, "path": "rebuild"}
        with tel.stage("scheduler_sync", **labels):
            inten = (self.carbon_signal.intensities(table.region, now)
                     if self.carbon_signal is not None else None)
            mats = decision_matrix_batch(pods, table, carbon_intensity=inten)
        with tel.stage("scheduler_mask", **labels):
            valid = _valid_mask(pods, table, exclude)
            w = self.weights(table)
            ws = np.broadcast_to(w, (len(pods), w.shape[0]))
        return topsis.batched_closeness_np(mats, ws, self._benefit, valid)

    def _score_queue_kinds(self, pods: Sequence[Pod],
                           cache: FleetCriteriaCache, now: float,
                           exclude) -> np.ndarray:
        """The one-dispatch round: sync the per-kind criteria cache (dirty
        columns only), then score every pod as a row gather — numpy reads
        zero-copy views, jax gathers from the cache's device-resident
        mirror, pallas streams kind blocks through the scalar-prefetch
        kernel."""
        tel = telemetry.active()
        labels = {"backend": self.backend,
                  "path": ("incremental" if cache is self._cache
                           else "rebuild")}
        fleet = cache.fleet
        with tel.stage("scheduler_sync", **labels):
            kind_idx, dirty, carbon_moved, grew = cache.sync(pods, now)
        with tel.stage("scheduler_mask", **labels):
            valid = _valid_mask(pods, fleet, exclude)
            w = self.weights(fleet)
            ws = np.broadcast_to(w, (len(pods), w.shape[0]))
            p = len(pods)
            if self.backend != "numpy":
                # padding pods gather kind 0 but are all-invalid (see
                # _pad_pod_axis)
                kind_idx, valid, ws = _pad_pod_axis(kind_idx, valid, ws)
        if self.backend == "numpy":
            return np.stack([
                np.asarray(topsis.closeness_np(cache.mats[k], ws[i],
                                               self._benefit,
                                               valid[i]).closeness)
                for i, k in enumerate(kind_idx)])
        if self.backend == "jax":
            with tel.stage("scheduler_upload", **labels):
                dev = cache.sync_device(dirty, carbon_moved, grew)
                args = _upload(tel, (kind_idx, ws, self._benefit, valid))
            with tel.stage("scheduler_dispatch", **labels):
                cc = _closeness_from_kinds(dev, *args)
            return _readback(tel, cc, labels, p)
        if self.backend == "pallas":
            from repro.kernels import ops
            with tel.stage("scheduler_dispatch", **labels):
                cc = ops.topsis_closeness_kinds(
                    cache.mats, kind_idx, ws, self._benefit,
                    valid=valid)
            return _readback(tel, cc, labels, p)
        raise ValueError(f"unknown backend {self.backend!r}; "
                         f"choose from {BACKENDS}")

    def _weight_grid(self, schemes) -> np.ndarray:
        """Resolve ``schemes`` — a sequence of scheme names or an (S, C)
        array of weight vectors — into a validated (S, C) float64 grid
        matching this scheduler's criteria count. Name rows go through
        :func:`weights_for` (so the paper schemes stay bitwise identical to
        the scalar path); raw vectors must pass
        :func:`repro.core.weighting.validate_weights`, and 5-weight rows
        are padded with a zero carbon weight when a signal is attached —
        the same inert extension the named schemes get."""
        carbon = self.carbon_signal is not None
        seq = list(schemes) if not isinstance(schemes, np.ndarray) else None
        if seq is not None and seq and all(isinstance(s, str) for s in seq):
            for s in seq:
                _check_carbon_scheme(s, self.carbon_signal)
            return scheme_grid(tuple(seq), carbon=carbon)
        ws = validate_weights(np.atleast_2d(np.asarray(schemes,
                                                      dtype=np.float64)),
                              name="schemes")
        c = len(self._benefit)
        if ws.shape[-1] == 5 and c == 6:
            ws = np.concatenate([ws, np.zeros((ws.shape[0], 1))], axis=-1)
        if ws.shape[-1] != c:
            raise ValueError(
                f"scheme grid has {ws.shape[-1]} weights but this "
                f"scheduler scores {c} criteria "
                f"({'with' if carbon else 'without'} a carbon signal)")
        return ws

    def score_queue_grid(self, pods: Sequence[Pod], nodes, schemes,
                         now: float = 0.0, exclude=None) -> np.ndarray:
        """(S, P, N) closeness tensor: the whole queue scored under every
        weighting scheme in ONE engine dispatch (the Pareto-sweep path —
        see ``repro.core.pareto``). ``schemes`` is a list of scheme names
        or an (S, C) weight grid (:meth:`_weight_grid`); row ``s`` equals
        what :meth:`score_queue` returns with ``ws[s]`` as the scheme.
        ``now`` / ``exclude`` behave exactly as in :meth:`score_queue`;
        the feasibility mask is scheme-independent and shared.

        Like :meth:`score_queue`, every call but numpy on a table that is
        not the attached fleet goes through the per-kind criteria cache —
        on jax one fused gather+grid-closeness dispatch against the
        cache's device-resident kind tensor, with no re-upload per
        scheme."""
        table = _as_table(nodes)
        ws = self._weight_grid(schemes)
        tel = telemetry.active()
        tel.inc("scheduler_pods_scored", value=float(len(pods)))
        cache = self._cache_for(table)
        if cache is not None:
            return self._score_grid_kinds(pods, cache, ws, now, exclude)
        labels = {"backend": self.backend, "path": "rebuild"}
        with tel.stage("scheduler_sync", **labels):
            inten = (self.carbon_signal.intensities(table.region, now)
                     if self.carbon_signal is not None else None)
            mats = decision_matrix_batch(pods, table, carbon_intensity=inten)
        with tel.stage("scheduler_mask", **labels):
            valid = _valid_mask(pods, table, exclude)
        return topsis.closeness_grid_np(mats, ws, self._benefit, valid)

    def _score_grid_kinds(self, pods: Sequence[Pod],
                          cache: FleetCriteriaCache, ws: np.ndarray,
                          now: float, exclude) -> np.ndarray:
        """Grid round through the per-kind cache: one dirty-column sync,
        then the per-backend (S, P, N) scoring — numpy loops scheme x pod
        over the zero-copy cache views (the reference), jax fuses gather +
        grid closeness into one dispatch on the device mirror, pallas
        streams the (P, N, C) gather through the weight-grid kernel."""
        tel = telemetry.active()
        labels = {"backend": self.backend,
                  "path": ("incremental" if cache is self._cache
                           else "rebuild")}
        fleet = cache.fleet
        with tel.stage("scheduler_sync", **labels):
            kind_idx, dirty, carbon_moved, grew = cache.sync(pods, now)
        with tel.stage("scheduler_mask", **labels):
            valid = _valid_mask(pods, fleet, exclude)
            p = len(pods)
            if self.backend == "jax":
                kind_idx, valid, _ = _pad_pod_axis(kind_idx, valid)
        if self.backend == "numpy":
            return np.stack([
                np.stack([
                    np.asarray(topsis.closeness_np(cache.mats[k], w,
                                                   self._benefit,
                                                   valid[i]).closeness)
                    for i, k in enumerate(kind_idx)])
                for w in ws])
        if self.backend == "jax":
            with tel.stage("scheduler_upload", **labels):
                dev = cache.sync_device(dirty, carbon_moved, grew)
                args = _upload(tel, (kind_idx, ws, self._benefit, valid))
            with tel.stage("scheduler_dispatch", **labels):
                cc = _closeness_grid_from_kinds(dev, *args)
            return _readback(tel, cc, labels, p)
        if self.backend == "pallas":
            from repro.kernels import ops
            with tel.stage("scheduler_dispatch", **labels):
                cc = ops.topsis_closeness_grid(cache.mats[kind_idx], ws,
                                               self._benefit, valid=valid)
            return _readback(tel, cc, labels)
        raise ValueError(f"unknown backend {self.backend!r}; "
                         f"choose from {BACKENDS}")

    def select_many_grid(self, pods: Sequence[Pod], nodes, schemes,
                         now: float = 0.0, exclude=None):
        """What-if placement of one queue under every scheme: returns
        ``(assignments, diagnostics)`` where ``assignments[s][i]`` is the
        node index pods[i] would take under scheme ``s`` (or None). One
        fused :meth:`score_queue_grid` dispatch scores all schemes; each
        scheme's greedy capacity-ledger walk then starts from the SAME
        fresh snapshot (``_greedy_assign``) — the per-scheme placements are
        independent hypotheticals, identical to running
        :meth:`select_many` once per scheme, which is what the frontier
        layer compares. Input nodes are never mutated."""
        with telemetry.active().span("scheduler_grid",
                                     scheduler=self.name,
                                     backend=self.backend) as sp:
            table = _as_table(nodes)
            n_s = len(schemes)
            if not len(pods):
                return ([[] for _ in range(n_s)],
                        {"closeness": np.zeros((n_s, 0, len(table))),
                         "scheduling_time_s": 0.0, "per_scheme_time_s": 0.0})
            cc = self.score_queue_grid(pods, table, schemes, now=now,
                                       exclude=exclude)
            assignments = [_greedy_assign(cc[s], pods, table)
                           for s in range(cc.shape[0])]
        dt = sp.duration_s
        return assignments, {"closeness": cc, "scheduling_time_s": dt,
                             "per_scheme_time_s": dt / cc.shape[0]}

    def _explain_batch(self, pods, table, now, exclude, assignments) -> None:
        """Per-pod attribution for one batch round (numpy path): rebuild
        each pod's (N, C) matrix and validity exactly as ``score_queue``
        saw them and decompose winner vs runner-up. ``node`` records the
        greedy ledger's actual commit — it can differ from the scoring
        ``winner`` when an earlier pod took the capacity."""
        names = [c.name for c in self.criteria]
        if self._cache is not None and table is self._cache.fleet:
            # fleet untouched since the scoring sync -> dirty is empty and
            # these are the same cache rows score_queue just read
            kind_idx, _, _, _ = self._cache.sync(pods, now)
            mats = [self._cache.mats[k] for k in kind_idx]
        else:
            inten = (self.carbon_signal.intensities(table.region, now)
                     if self.carbon_signal is not None else None)
            mats = decision_matrix_batch(pods, table, carbon_intensity=inten)
        valid = _valid_mask(pods, table, exclude)
        w = self.weights(table)
        for i, (pod, idx) in enumerate(zip(pods, assignments)):
            exp = topsis.explain_np(mats[i], w, self._benefit, valid[i],
                                    criteria_names=names)
            exp.update(pod=pod.uid, t=now,
                       node=table.names[idx] if idx is not None else None,
                       runner_up_node=(table.names[exp["runner_up"]]
                                       if exp["runner_up"] is not None
                                       else None))
            self.explanations.append(exp)

    def select_many(self, pods: Sequence[Pod], nodes, now: float = 0.0,
                    blocked: "Sequence[int | None] | None" = None,
                    exclude=None, explain: bool = False):
        """Place a queue: returns (assignments, diagnostics) where
        ``assignments[i]`` is the node index for ``pods[i]`` or None.
        ``blocked[i]`` optionally names one node index ``pods[i]`` must not
        take this pass (a node it was just preempted off) — skipped inside
        the greedy ledger walk, so a blocked top choice falls through to
        the next-ranked node without phantom capacity charges. ``exclude``
        ((N,) or (P, N) bool) hard-masks nodes out of the scoring validity
        instead (sleeping / deadline-late nodes, see :meth:`score_queue`).
        ``explain=True`` (numpy backend only, like
        :meth:`GreenPodScheduler.select`) appends a per-criterion
        attribution per placed pod to ``self.explanations``."""
        explain = explain or self.explain
        if explain and self.backend != "numpy":
            raise ValueError(
                f"explain=True needs backend='numpy', not "
                f"{self.backend!r}: only the numpy path exposes the "
                f"weighted separation terms the attribution decomposes")
        with telemetry.active().span("scheduler_batch",
                                     scheduler=self.name,
                                     backend=self.backend) as sp:
            table = _as_table(nodes)
            if not len(pods):
                return [], {"closeness": np.zeros((0, len(table))),
                            "scheduling_time_s": 0.0, "per_pod_time_s": 0.0}
            cc = self.score_queue(pods, table, now=now, exclude=exclude)
            assignments = _greedy_assign(cc, pods, table, blocked=blocked)
        dt = sp.duration_s
        per_pod = dt / len(pods)
        if explain:
            self._explain_batch(pods, table, now, exclude, assignments)
        for pod, idx in zip(pods, assignments):
            self.decision_log.append(
                {"pod": pod.uid,
                 "node": table.names[idx] if idx is not None else None,
                 "time_s": per_pod})
        return assignments, {"closeness": cc, "scheduling_time_s": dt,
                             "per_pod_time_s": per_pod}


class DefaultK8sScheduler:
    """Upstream kube-scheduler default scoring (the paper's baseline).

    LeastRequestedPriority: ((capacity - requested) / capacity) * 100,
    averaged over cpu and memory.
    BalancedResourceAllocation: 100 - |cpu_fraction - mem_fraction| * 100.
    Total = mean of the two plugins (equal default plugin weights).
    """

    name = "default"

    def __init__(self):
        self.decision_log: list[dict] = []

    def select(self, pod: Pod, nodes, now: float = 0.0, exclude=None):
        """Vectorized over ``NodeTable`` columns (``nodes`` may be a Node
        list or a prebuilt table): one broadcast pass scores the whole
        fleet, infeasible nodes score -1. Identical plugin arithmetic to
        the upstream per-node loop; ties resolve to the lowest node index
        (the loop's running-max-with-epsilon tie-break, which only diverges
        for score gaps below 1e-12 — see tests/test_scheduler.py pinning).
        ``now`` is accepted for engine-call symmetry and ignored — the
        baseline is carbon-blind. ``exclude`` masks engine-forbidden nodes
        (sleeping capacity) exactly like capacity infeasibility."""
        with telemetry.active().span("scheduler_decision",
                                     scheduler=self.name,
                                     backend="numpy") as sp:
            table = _as_table(nodes)
            fits = table.fits(pod.cpu, pod.mem)
            if exclude is not None:
                fits = fits & ~np.asarray(exclude, dtype=bool)
            if not fits.any():
                return None, {"reason": "unschedulable"}
            cpu_frac = (table.reserved_cpu + table.used_cpu
                        + pod.cpu) / table.vcpus
            mem_frac = (table.reserved_mem + table.used_mem
                        + pod.mem) / table.mem_gb
            least = 100.0 * ((1.0 - cpu_frac) + (1.0 - mem_frac)) / 2.0
            balanced = 100.0 * (1.0 - np.abs(cpu_frac - mem_frac))
            scores = np.where(fits, (least + balanced) / 2.0, -1.0)
            best = int(np.argmax(scores))
        dt = sp.duration_s
        self.decision_log.append({"pod": pod.uid, "node": table.names[best],
                                  "time_s": dt})
        return best, {"scores": scores, "scheduling_time_s": dt}

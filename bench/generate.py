"""Fleet and arrival generators: the cell's inputs, made from ``--seed``.

Both build the program's own types (``Node``, ``Pod``, ``WorkloadSpec``)
from the configuration's and the traffic file's data, so a change to the
program's built-in tables cannot move the yardstick. Every seed gets the
same work in another order: the same multiset of node classes and sizes,
the same burst gaps (quantiles of the exponential gap distribution) and the
same kind counts in every burst, with the traffic's optional ``deferrable``
share split within each kind. What the seed changes is which node is
which, the order of the gaps and the order of pods within a burst.
"""
from __future__ import annotations

import math

import numpy as np


def _quota(shares: dict, total: int) -> dict:
    """Largest-remainder split of ``total`` by ``shares`` (ties by key
    order), so the counts sum to ``total`` exactly."""
    keys = list(shares)
    weight = sum(shares.values())
    raw = [shares[k] / weight * total for k in keys]
    counts = [math.floor(r) for r in raw]
    left = total - sum(counts)
    by_rest = sorted(range(len(keys)), key=lambda i: (-(raw[i] - counts[i]), i))
    for i in by_rest[:left]:
        counts[i] += 1
    return dict(zip(keys, counts))


class Fleet:
    """One replay's fleet: the ``Node`` list handed to the program and the
    static columns the reference reads (never the program's own)."""

    def __init__(self, cfg: dict, seed: int, n_nodes: int | None = None):
        from repro.cluster.node import Node
        n = int(n_nodes or cfg["nodes"])
        classes = cfg["node_classes"]
        regions = cfg["regions"]
        rng = np.random.default_rng(seed)
        head = list(classes) if cfg.get("paper_nodes_first") else []
        kinds = []
        for cls, count in _quota(cfg["class_mix"], n - len(head)).items():
            scales = _quota({s: 1.0 for s in cfg["capacity_scales"]}, count)
            kinds += [(cls, float(s)) for s, c in scales.items()
                      for _ in range(c)]
        kinds = [(c, 1.0) for c in head] + [kinds[i]
                                           for i in rng.permutation(len(kinds))]
        self.node_class = [c for c, _ in kinds]
        self.vcpus = np.asarray([classes[c]["vcpus"] * s for c, s in kinds],
                                dtype=np.float64)
        self.mem_gb = np.asarray([classes[c]["mem_gb"] * s for c, s in kinds],
                                 dtype=np.float64)
        self.region = [regions[i % len(regions)] for i in range(n)]
        col = lambda key: np.asarray([classes[c][key] for c in self.node_class],
                                     dtype=np.float64)
        self.speed = col("speed")
        self.dyn_power = col("dyn_power_per_vcpu")
        self.idle_power = col("idle_power")
        self.names = [f"{cfg['name']}-{i:05d}" for i in range(n)]
        self.nodes = [Node(self.names[i], self.node_class[i],
                           float(self.vcpus[i]), float(self.mem_gb[i]),
                           region=self.region[i]) for i in range(n)]

    def __len__(self) -> int:
        return len(self.names)


def workload_specs(cfg: dict) -> dict:
    from repro.cluster.workload import WorkloadSpec
    return {k: WorkloadSpec(k, float(v["cpu"]), float(v["mem_gb"]),
                            float(v["base_time_s"]), k)
            for k, v in cfg["pod_kinds"].items()}


def burst_pool(traffic: dict) -> list:
    """One burst's ``(kind, deferrable)`` multiset, the same in every burst
    on every seed: the kind counts split ``mix``, and the traffic's
    optional ``deferrable`` share is split within each kind."""
    size = int(traffic["burst_size"])
    share = float(traffic.get("deferrable", {}).get("share", 0.0))
    pool = []
    for kind, count in _quota(traffic["mix"], size).items():
        split = _quota({True: share, False: 1.0 - share}, count)
        pool += [(kind, d) for d in (True, False) for _ in range(split[d])]
    return pool


def bursts(cfg: dict, traffic: dict, seed: int) -> list:
    """``[(t_arrival_s, [Pod, ...]), ...]`` for one replay. A deferrable
    pod carries the traffic's ``deferrable.deadline_s``."""
    from repro.cluster.workload import Pod
    specs = workload_specs(cfg)
    rng = np.random.default_rng(seed)
    n_bursts, size = int(traffic["n_bursts"]), int(traffic["burst_size"])
    rate = float(traffic["rate_per_s"])
    gaps = [-math.log(1.0 - (k + 0.5) / n_bursts) / rate
            for k in range(n_bursts)]
    gaps = [gaps[i] for i in rng.permutation(n_bursts)]
    pool = burst_pool(traffic)
    extra = {}
    if "deferrable" in traffic:
        extra = {"deadline_s": float(traffic["deferrable"]["deadline_s"])}
    out, uid, t = [], 0, 0.0
    for gap in gaps:
        t += gap
        drawn = [pool[i] for i in rng.permutation(size)]
        pods = [Pod(uid + i, specs[kind], traffic["scheduler"],
                    deferrable=d, **extra)
                for i, (kind, d) in enumerate(drawn)]
        uid += size
        out.append((t, pods))
    return out


def arrivals(events: list):
    """A replay's bursts (``bursts``) as the program's
    ``ArrivalProcess``."""
    from repro.cluster.workload import ArrivalProcess

    class Replay(ArrivalProcess):
        def events(self):
            return events

    return Replay()

"""The generators give every seed the same work in another order."""
import json
import sys
from collections import Counter
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import generate  # noqa: E402

CFG = json.loads((BENCH / "configs" / "k8s-5000.json").read_text())
TRAFFIC = json.loads((BENCH / "traffic" / "steady.json").read_text())
SEEDS = (2**31 + 3, 4_000_000_007)


def test_fleet_same_sizes_other_order():
    a, b = (generate.Fleet(CFG, s) for s in SEEDS)
    assert len(a) == len(b) == CFG["nodes"]
    key = lambda f: Counter(zip(f.node_class, f.vcpus.tolist(),
                                f.mem_gb.tolist()))
    assert key(a) == key(b)
    assert a.node_class != b.node_class
    assert a.node_class[:4] == list(CFG["node_classes"])
    counts = Counter(a.node_class[4:])
    assert set(counts.values()) == {(CFG["nodes"] - 4) // 4}
    assert sum(a.vcpus) == sum(b.vcpus)
    again = generate.Fleet(CFG, SEEDS[0])
    assert again.node_class == a.node_class


def test_bursts_same_work_other_order():
    a, b = (generate.bursts(CFG, TRAFFIC, s) for s in SEEDS)
    assert len(a) == len(b) == TRAFFIC["n_bursts"]
    gaps = lambda ev: sorted(round(t1 - t0, 9) for (t0, _), (t1, _)
                             in zip([(0.0, None)] + ev[:-1], ev))
    assert gaps(a) == gaps(b)
    size = TRAFFIC["burst_size"]
    for _, pods in a + b:
        assert len(pods) == size
        kinds = Counter(p.workload.kind for p in pods)
        assert kinds == {"light": 128, "medium": 77, "complex": 51}
        assert not any(p.deferrable for p in pods)
    uids = [p.uid for _, pods in a for p in pods]
    assert uids == list(range(len(uids)))
    assert [p.workload.kind for p in a[0][1]] \
        != [p.workload.kind for p in b[0][1]]


def test_deferrable_share_same_in_every_burst_and_seed():
    traffic = json.loads((BENCH / "tests" / "data" / "policy-200.traffic.json")
                         .read_text())
    a, b = (generate.bursts(CFG, traffic, s) for s in SEEDS)
    deadline = traffic["deferrable"]["deadline_s"]
    for _, pods in a + b:
        held = Counter(p.workload.kind for p in pods if p.deferrable)
        assert held == {"light": 8, "medium": 5, "complex": 3}
        assert all(p.deadline_s == deadline for p in pods if p.deferrable)
    assert [p.deferrable for p in a[0][1]] != [p.deferrable for p in b[0][1]]
    # without the key the draw is the steady traffic's, pod for pod
    plain = dict(traffic)
    del plain["deferrable"]
    c = generate.bursts(CFG, plain, SEEDS[0])
    assert [(t, [p.workload.kind for p in pods]) for t, pods in c] \
        == [(t, [p.workload.kind for p in pods]) for t, pods in a]
    assert not any(p.deferrable for _, pods in c for p in pods)

"""The commit rounds beside pods that fit nowhere (ops/rounds.py).

Rounds 2+ judge the B = `compact_window(P)` lowest-rank active pods. A
pod that no acceptance of the cycle can give a node used to hold a row
of that window to the cycle's end: with about B of them ranked before
the feasible leftovers of round 1, a round accepted a handful or
`max_rounds` ended the cycle, and pods were refused that fit hundreds of
nodes (PERF.md section 6, PR 36). The engine now
parks such a pod the round it is judged. These cases hold it to the
plain sequential scheduler's verdict (`oracle.validate_rounds_assignment`,
which knows no excuse for a round cap any more; no
`allow_feasible_unplaced`): every binding valid, every unplaced pod
infeasible on every node in the final state. The
cases at s >= B - 12 fail on the engine as it was.
"""

from __future__ import annotations

import numpy as np
import pytest

from k8s_scheduler_tpu import oracle
from k8s_scheduler_tpu.core.cycle import build_cycle_fn
from k8s_scheduler_tpu.models import SnapshotEncoder
from k8s_scheduler_tpu.models.builders import MakeNode, MakePod
from k8s_scheduler_tpu.ops.rounds import compact_window

ZONES = [f"zone-{c}" for c in "abcdef"]
TYPES = ["general", "compute", "memory"]
ZONE_KEY = "topology.kubernetes.io/zone"
HOST_KEY = "kubernetes.io/hostname"
NODES, PODS, B = 664, 4000, 512  # the P pad 4,096 gives a window of 512


def cluster(n: int) -> list:
    """scheduler_perf's node-default (4 CPU, 32Gi, 110 pods), every node
    type in every zone, a tenth tainted."""
    rng = np.random.default_rng(0)
    nodes = []
    for i in range(n):
        b = MakeNode(f"node-{i}").capacity(
            {"cpu": "4", "memory": "32Gi", "pods": 110}
        ).labels({
            ZONE_KEY: ZONES[i % 6], HOST_KEY: f"node-{i}",
            "node-type": TYPES[(i // 6) % 3],
        })
        if rng.random() < 0.1:
            b.taint("dedicated", "special")
        nodes.append(b.obj())
    return nodes


def queue(stuck: int) -> list:
    """`stuck` pods of 9 CPU (pod-large-cpu: they fit no 4-CPU node),
    priority 0 and the oldest, so ranked BEFORE every feasible
    priority-0 pod; then the full constraint mix over 8 apps, up to
    PODS pods in all."""
    rng = np.random.default_rng(1)
    pods = [
        MakePod(f"stuck-{i}")
        .req({"cpu": "9", "memory": "500Mi"})
        .labels({"app": "stuck"})
        .priority(0)
        .created(-2.0 + i * 1e-6)
        .obj()
        for i in range(stuck)
    ]
    for i in range(PODS - stuck):
        app = f"app-{int(rng.integers(0, 8))}"
        b = (
            MakePod(f"pod-{i}")
            .req({"cpu": "100m", "memory": "500Mi"})
            .labels({"app": app})
            .priority(int(rng.choice((0, 0, 10, 100))))
            .created(float(i))
        )
        if rng.random() < 0.3:
            b.node_selector({"node-type": TYPES[i % 3]})
        if rng.random() < 0.1:
            b.toleration("dedicated", "special", "NoSchedule")
        if rng.random() < 0.3:
            b.pod_affinity(ZONE_KEY, {"app": app})
        if rng.random() < 0.2:
            b.pod_affinity(HOST_KEY, {"app": app}, anti=True)
        if rng.random() < 0.2:
            b.spread(2, ZONE_KEY, {"app": app})
        pods.append(b.obj())
    return pods


@pytest.fixture(scope="module")
def cycle_fn():
    return build_cycle_fn(commit_mode="rounds")  # one compile, six cases


@pytest.mark.parametrize(
    "stuck", [0, B // 2, B - 12, B, B * 7 // 4, 3 * B],
    ids=lambda s: f"stuck{s}")
def test_pods_that_fit_nowhere_cost_no_feasible_pod_its_node(
        cycle_fn, stuck):
    nodes, pods = cluster(NODES), queue(stuck)
    snap = SnapshotEncoder().encode(nodes, pods)
    assert compact_window(snap.P) == B < snap.P
    out = cycle_fn(snap)
    a = np.asarray(out.assignment)[: len(pods)]
    errors = oracle.validate_rounds_assignment(nodes, pods, a)
    assert errors == [], (len(errors), errors[:5])
    # the 9-CPU pods are parked in round 1, the only refusals here
    assert int((a < 0).sum()) == stuck
    assert (a[:stuck] < 0).all()
    assert int(out.rounds_parked) == stuck
    assert int(out.rounds_used) < 64
    # ... and each of them is told why on every node, past the B rows
    # of one attribution window too (a diagnosis that leaves nodes open
    # reads, to the served path's check, as a pod refused with room)
    rejects = np.asarray(out.reject_counts)[:stuck]
    assert (rejects.sum(axis=1) == NODES).all()


def _zone_node(name: str, zone: str, cpu: str):
    return (
        MakeNode(name).capacity({"cpu": cpu, "memory": "32Gi", "pods": 110})
        .labels({ZONE_KEY: zone, HOST_KEY: name}).obj()
    )


def test_a_pod_whose_peer_arrives_later_in_the_cycle_is_not_parked(cycle_fn):
    """`follower` needs a pod of app=leader in its zone and is none
    itself (no bootstrap): in round 1 required affinity closes every
    node to it, and the leader placed in that round opens a zone."""
    nodes = [_zone_node(f"n{i}", f"z{i % 2}", "4") for i in range(4)]
    pods = [
        MakePod("follower").req({"cpu": "1"}).labels({"app": "follower"})
        .pod_affinity(ZONE_KEY, {"app": "leader"}).created(0.0).obj(),
        MakePod("leader").req({"cpu": "1"}).labels({"app": "leader"})
        .created(1.0).obj(),
    ]
    snap = SnapshotEncoder().encode(nodes, pods)
    out = cycle_fn(snap)
    a = np.asarray(out.assignment)[:2]
    assert (a >= 0).all(), a
    assert a[0] % 2 == a[1] % 2  # the leader's zone
    assert int(out.rounds_parked) == 0
    assert int(out.rounds_used) >= 2
    assert oracle.validate_rounds_assignment(nodes, pods, a) == []


def test_a_pod_whose_domain_opens_when_the_minimum_rises_is_not_parked(
        cycle_fn):
    """Two app=web pods run in z0, none in z1; `spreader` (maxSkew 1)
    fits only z0's nodes (z1's are too small), where the skew would be
    3. Two web pods pinned to z1 are placed in round 1, the minimum
    rises to 2 and z0 opens."""
    nodes = [_zone_node("big0", "z0", "8"), _zone_node("big1", "z0", "8"),
             _zone_node("small0", "z1", "1"), _zone_node("small1", "z1", "1")]
    existing = [
        (MakePod(f"run{i}").req({"cpu": "1"}).labels({"app": "web"}).obj(),
         f"big{i}")
        for i in range(2)
    ]
    pods = [
        MakePod("spreader").req({"cpu": "2"}).labels({"app": "web"})
        .spread(1, ZONE_KEY, {"app": "web"}).created(0.0).obj(),
    ] + [
        MakePod(f"web{i}").req({"cpu": "500m"}).labels({"app": "web"})
        .node_selector({ZONE_KEY: "z1"}).created(1.0 + i).obj()
        for i in range(2)
    ]
    snap = SnapshotEncoder().encode(nodes, pods, existing)
    out = cycle_fn(snap)
    a = np.asarray(out.assignment)[:3]
    assert (a >= 0).all(), a
    assert a[0] in (0, 1) and set(a[1:].tolist()) <= {2, 3}
    assert int(out.rounds_parked) == 0
    assert oracle.validate_rounds_assignment(
        nodes, pods, a, existing) == []

"""The commit rounds on ONE spread group (ops/rounds.py, the level-fill).

scheduler_perf's TopologySpreading: every pending pod carries the same
DoNotSchedule constraint on the zone key over a selector all of them
match. The engine used to hold a (selector, domain) to `maxSkew - count
+ minimum` AT THE ROUND'S START, one acceptance a zone a round at
`maxSkew` 1, and a group of a few hundred ended at `max_rounds` with
most of it refused beside open nodes (ISSUE 46: 38 refusals against a
limit of 3 in the cell's rehearsal, 51-64 rounds a cycle). The guard now
follows the level the round's own arrivals lift the minimum to, and
claims go to every domain that level opens. These cases hold the engine
to the plain sequential scheduler: every pod with room is placed, as
many as the strict scan places, `oracle.validate_rounds_assignment`
passes, no domain a pod was bound into ends more than `maxSkew` above
the minimum, the loop does not end at its cap, and the rounds do not
grow with the pods per zone. A mix in which no spread rule binds is
placed bit for bit as the engine before placed it (digests taken on the
parent commit, PR 45).
"""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from k8s_scheduler_tpu import oracle
from k8s_scheduler_tpu.core.cycle import build_cycle_fn
from k8s_scheduler_tpu.models import SnapshotEncoder
from k8s_scheduler_tpu.models.builders import MakeNode, MakePod

ZONE_KEY = "topology.kubernetes.io/zone"
HOST_KEY = "kubernetes.io/hostname"
TYPES = ["general", "compute", "memory"]
UNEVEN = (0, 5, 5, 3, 9, 1)


@pytest.fixture(scope="module")
def rounds_fn():
    return build_cycle_fn(commit_mode="rounds")


@pytest.fixture(scope="module")
def scan_fn():
    return build_cycle_fn(commit_mode="scan")


def group(n_pods, zones, skew, start=(), anti=False, n_nodes=72,
          small_zone_cpu=None):
    """`n_pods` of app=blue, each spread over the zones at `skew` (and,
    with `anti`, one a node); `start[z]` blue pods already run in zone
    z; with `small_zone_cpu` zone 0's nodes have that much CPU."""
    nodes = [
        MakeNode(f"n{i}")
        .capacity({
            "cpu": small_zone_cpu if small_zone_cpu and i % zones == 0
            else "8",
            "memory": "64Gi", "pods": 110,
        })
        .labels({ZONE_KEY: f"z{i % zones}", HOST_KEY: f"n{i}"})
        .obj()
        for i in range(n_nodes)
    ]
    per_zone = n_nodes // zones
    existing = [
        (MakePod(f"run-{z}-{j}").req({"cpu": "10m"})
         .labels({"app": "blue"}).obj(),
         f"n{z + zones * (j % per_zone)}")
        for z, c in enumerate(start[:zones]) for j in range(c)
    ]
    pods = []
    for i in range(n_pods):
        b = (
            MakePod(f"p{i}").req({"cpu": "10m"}).labels({"app": "blue"})
            .spread(skew, ZONE_KEY, {"app": "blue"}).created(float(i))
        )
        if anti:
            b.labels({"app": "blue", "gen": "new"})
            b.pod_affinity(HOST_KEY, {"gen": "new"}, anti=True)
        pods.append(b.obj())
    return nodes, pods, existing


def zone_counts(a, zones, start):
    z = np.bincount(a[a >= 0] % zones, minlength=zones)
    z[: len(start[:zones])] += np.asarray(start[:zones], int)
    return z


def check_group(rounds_fn, scan_fn, nodes, pods, existing, zones, skew,
                start):
    snap = SnapshotEncoder().encode(nodes, pods, existing)
    out = rounds_fn(snap)
    a = np.asarray(out.assignment)[: len(pods)]
    errors = oracle.validate_rounds_assignment(nodes, pods, a, existing)
    assert errors == [], (len(errors), errors[:5])
    assert int(out.round_cap_hit) == 0
    # every carrier is constrained alike, so the bound at placement
    # time shows in the final state: no zone a pod went to stands more
    # than `skew` above the lowest
    z = zone_counts(a, zones, start)
    went = np.bincount(a[a >= 0] % zones, minlength=zones) > 0
    assert (z[went] - z.min() <= skew).all(), z
    strict = np.asarray(scan_fn(snap).assignment)[: len(pods)]
    assert int((a >= 0).sum()) == int((strict >= 0).sum())
    return out, a


@pytest.mark.parametrize("anti", [False, True], ids=["plain", "anti"])
@pytest.mark.parametrize("start", [(), UNEVEN], ids=["level", "uneven"])
@pytest.mark.parametrize("skew", [1, 2])
@pytest.mark.parametrize("zones", [3, 6])
def test_one_group_is_filled_level_by_level(
        rounds_fn, scan_fn, zones, skew, start, anti):
    # one a node under `anti`: 16 nodes a zone leave room for the 14
    # the lowest zone of the uneven start has to take
    nodes, pods, existing = group(60, zones, skew, start, anti, n_nodes=96)
    out, a = check_group(
        rounds_fn, scan_fn, nodes, pods, existing, zones, skew, start)
    assert (a >= 0).all(), int((a < 0).sum())
    assert int(out.rounds_used) <= 16


@pytest.mark.parametrize("start", [(), UNEVEN, (0, 300, 300, 300, 300, 300)],
                         ids=["level", "uneven", "one-empty-zone"])
def test_rounds_do_not_grow_with_the_pods_per_zone(
        rounds_fn, scan_fn, start):
    used = {}
    for n in (60, 600):
        nodes, pods, existing = group(n, 6, 1, start)
        out, a = check_group(
            rounds_fn, scan_fn, nodes, pods, existing, 6, 1, start)
        assert (a >= 0).all()
        used[n] = int(out.rounds_used)
    # the engine before needed a round a level: 10 and 100 (of 64)
    assert used[600] <= used[60] + 6, used


@pytest.mark.parametrize("skew", [1, 2])
def test_a_zone_short_of_room_holds_the_others_to_its_level(
        rounds_fn, scan_fn, skew):
    # zone 0: 24 nodes of 20m take two 10m pods each, 48 in all; the
    # other zones may stand `skew` above that and no higher
    nodes, pods, existing = group(
        200, 3, skew, small_zone_cpu="20m")
    out, a = check_group(
        rounds_fn, scan_fn, nodes, pods, existing, 3, skew, ())
    assert zone_counts(a, 3, ()).tolist() == [48, 48 + skew, 48 + skew]
    assert int(out.spread_revoked) > 0


def loose_mix(seed, skew):
    """The full constraint mix over 8 apps on 96 nodes; spread at a
    `skew` no count reaches, so the rule never closes a node and the
    guard never revokes a claim."""
    rng = np.random.default_rng(seed)
    nodes = [
        MakeNode(f"node-{i}")
        .capacity({"cpu": "4", "memory": "32Gi", "pods": 110})
        .labels({ZONE_KEY: f"zone-{i % 6}", HOST_KEY: f"node-{i}",
                 "node-type": TYPES[(i // 6) % 3]})
        .obj()
        for i in range(96)
    ]
    pods = []
    for i in range(700):
        app = f"app-{int(rng.integers(0, 8))}"
        b = (
            MakePod(f"pod-{i}")
            .req({"cpu": "100m", "memory": "500Mi"})
            .labels({"app": app})
            .priority(int(rng.choice((0, 0, 10))))
            .created(float(i))
        )
        if rng.random() < 0.3:
            b.node_selector({"node-type": TYPES[i % 3]})
        if rng.random() < 0.2:
            b.pod_affinity(ZONE_KEY, {"app": app})
        if rng.random() < 0.1:
            b.pod_affinity(HOST_KEY, {"app": app}, anti=True)
        if rng.random() < 0.5:
            b.spread(skew, ZONE_KEY, {"app": app})
        pods.append(b.obj())
    return nodes, pods


# sha256 of the i32 assignment, first 16 hex digits, computed with the
# engine of PR 45 (ENGINE_MARK ":parks") on these very inputs
PARENT_DIGESTS = {
    0: "2a84bb324748b328", 1: "a5f093c7e57f1e64", 2: "407910229afd315f",
}


@pytest.mark.parametrize("seed", sorted(PARENT_DIGESTS))
def test_where_no_spread_rule_binds_placements_are_the_parents(
        rounds_fn, seed):
    nodes, pods = loose_mix(seed, skew=500)
    out = rounds_fn(SnapshotEncoder().encode(nodes, pods))
    a = np.asarray(out.assignment)[: len(pods)].astype(np.int32)
    assert hashlib.sha256(a.tobytes()).hexdigest()[:16] == \
        PARENT_DIGESTS[seed]
    assert int(out.spread_revoked) == 0
    assert int(out.round_cap_hit) == 0
    assert oracle.validate_rounds_assignment(nodes, pods, a) == []


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_binding_mix_stays_valid(rounds_fn, seed):
    """The same mix at `maxSkew` 1: the rule binds in every group, pods
    with and without the constraint match the same selectors, and some
    carry an affinity or anti-affinity term beside it."""
    nodes, pods = loose_mix(seed, skew=1)
    out = rounds_fn(SnapshotEncoder().encode(nodes, pods))
    a = np.asarray(out.assignment)[: len(pods)]
    errors = oracle.validate_rounds_assignment(nodes, pods, a)
    assert errors == [], (len(errors), errors[:5])
    assert int(out.round_cap_hit) == 0


def test_the_scan_reports_neither_count(scan_fn):
    nodes, pods, existing = group(12, 3, 1)
    out = scan_fn(SnapshotEncoder().encode(nodes, pods, existing))
    assert int(out.round_cap_hit) == 0 and int(out.spread_revoked) == 0


def test_the_cap_is_counted_where_it_ends_the_loop():
    """`max_rounds` 2 on a group that needs more: the count says so."""
    nodes, pods, existing = group(60, 6, 1, UNEVEN)
    out = build_cycle_fn(commit_mode="rounds", max_rounds=2)(
        SnapshotEncoder().encode(nodes, pods, existing))
    assert int(out.rounds_used) == 2
    assert int(out.round_cap_hit) == 1
    assert int((np.asarray(out.assignment)[:60] < 0).sum()) > 0

"""percentageOfNodesToScore against the plain reference.

`oracle.sampled_candidates` walks the node indices from a start, wraps,
and stops at k feasible nodes; `ops/sampling.sample_feasible` gets the
same set from one prefix count. Seeded random clusters of 200-600 nodes
(so that k < N at every percentage tried), the scan, the full rounds
program and the carry program the served path runs."""

from __future__ import annotations

import functools

import numpy as np
import pytest

from k8s_scheduler_tpu import oracle
from k8s_scheduler_tpu.models import SnapshotEncoder
from k8s_scheduler_tpu.ops import sampling
from k8s_scheduler_tpu.utils.synth import ZONES, make_cluster, make_pods

from k8s_scheduler_tpu.models.builders import MakePod

from test_sampling import PROGRAMS, _pod, run


@functools.cache
def _seeded(seed):
    """A cluster on which k < N at every percentage tried, with pods
    that have more feasible nodes than k and pods that have fewer (one
    zone in six, untainted: under 100 nodes, the least k can be)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(200, 601))
    nodes = make_cluster(n, seed=seed, taint_fraction=0.3)
    pods = make_pods(
        80, seed=seed + 100, selector_fraction=0.4,
        toleration_fraction=0.3, priorities=(0, 10),
    )
    pods += [
        _pod(f"zone-{j}").created(200.0 + j).node_selector(
            {"topology.kubernetes.io/zone": ZONES[j % len(ZONES)]}).obj()
        for j in range(12)
    ]
    return nodes, pods


def _feasible_rows(nodes, pods, existing=()):
    state = oracle.OracleState.build(nodes, existing)
    return [
        [all(f(p, state, i) for f in oracle.DEFAULT_FILTERS)
         for i in range(len(nodes))]
        for p in pods
    ]


@functools.cache
def _seeded_rows(seed):
    """Every pod's feasible nodes on the empty cluster, by the oracle's
    filters."""
    return _feasible_rows(*_seeded(seed))


@pytest.mark.parametrize("pct", [0, 10, 50])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_candidate_set_equals_the_sequential_walk(seed, pct):
    """`sample_feasible` (one prefix count) picks exactly the nodes the
    walk of `oracle.sampled_candidates` stops with, from the start the
    reference states, for the k the reference states."""
    nodes, pods = _seeded(seed)
    snap = SnapshotEncoder().encode(nodes, pods)
    n = len(nodes)
    k = oracle.num_feasible_nodes_to_find(n, pct)
    assert k < n
    assert int(sampling.num_feasible_nodes_to_find(snap.num_nodes, pct)) == k
    rank = {pi: r for r, pi in enumerate(oracle.queue_order(pods))}
    starts = [oracle.sample_start(rank[i], int(snap.cycle_index), n)
              for i in range(len(pods))]
    off = np.asarray(sampling.start_offsets(snap))
    assert off[: len(pods)].tolist() == starts
    rows = _seeded_rows(seed)
    feasible = np.zeros((len(pods), snap.N), bool)
    feasible[:, :n] = rows
    got, narrowed = sampling.sample_feasible(
        feasible, off[: len(pods)], np.int32(k))
    got, narrowed = np.asarray(got), np.asarray(narrowed)
    for i, row in enumerate(rows):
        want = oracle.sampled_candidates(row, starts[i], k)
        assert sorted(want) == np.nonzero(got[i])[0].tolist(), i
        assert bool(narrowed[i]) == (sum(row) > k)
    assert narrowed.any() and not narrowed.all()


@pytest.mark.parametrize("n, k", [
    (99, 99),      # under minFeasibleNodesToFind: every node
    (100, 100),    # 50% of 100, floored at 100
    (500, 230),    # 50 - 500/125 = 46%: SchedulingBasic 500Nodes
    (5000, 500),   # 10%: the 5,000-node cells
    (6000, 300),   # 50 - 48 = 2, floored at 5%
])
def test_the_adaptive_k_on_both_branches(n, k):
    """`percentageOfNodesToScore` unset: the traced arithmetic gives the
    reference's k where the percentage follows the node count (500
    nodes) and where it sits on its floor (5,000 and beyond)."""
    assert oracle.num_feasible_nodes_to_find(n, 0) == k
    assert int(sampling.num_feasible_nodes_to_find(np.int32(n), 0)) == k


@functools.cache
def _plain(seed):
    """SchedulingBasic 500Nodes' shapes: 500 nodes of 4 CPU, 32Gi, 110
    pods (`node-default`), pods of 100m, 500Mi with no constraint
    (`pod-default`), seeded priorities and ages so that the queue order
    is not the list's."""
    rng = np.random.default_rng(seed)
    nodes = make_cluster(500, seed=seed, cpu_choices=(4,),
                         memory_choices=(32,))
    pods = [
        MakePod(f"basic-{i}").req({"cpu": "100m", "memory": "500Mi"})
        .labels({"app": f"app-{i % 50}"})
        .priority(int(rng.integers(0, 3)))
        .created(float(rng.integers(0, 1000))).obj()
        for i in range(120)
    ]
    return nodes, pods


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("seed", [0, 1])
def test_500_plain_nodes_under_the_default_sample(seed, program):
    """k = 230 of 500, every pod narrowed (all 500 nodes admit it). The
    scan binds what the reference's sequential assignment binds, pod for
    pod; the rounds programs bind every pod validly, each on a node of
    its walk's sample (no node fills up: 120 pods of 100m)."""
    nodes, pods = _plain(seed)
    snap, out = run(program, nodes, pods, 0)
    a = np.asarray(out.assignment)[: len(pods)].tolist()
    assert (int(out.sample_k), int(out.sample_narrowed_pods)) == (230, 120)
    if program == "scan":
        assert a == [d.node_index for d in oracle.schedule(
            nodes, pods, percentage_of_nodes_to_score=0,
            cycle_index=int(snap.cycle_index))]
        return
    assert min(a) >= 0
    assert oracle.validate_rounds_assignment(nodes, pods, a) == []
    rank = {pi: r for r, pi in enumerate(oracle.queue_order(pods))}
    for j, i in enumerate(a):
        start = oracle.sample_start(rank[j], int(snap.cycle_index), 500)
        assert i in oracle.sampled_candidates([True] * 500, start, 230), j


@pytest.mark.parametrize("pct", [0, 10, 50])
@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_assignment_against_the_reference(seed, program, pct):
    """The scan binds what the sequential reference binds, pod for pod.
    The rounds programs judge a round's claims together, so they are held
    to what defines them: every binding valid and every refusal
    infeasible in the final state, and, where no node filled up (the
    feasible sets never moved), every pod on a node of the walk's
    sample."""
    nodes, pods = _seeded(seed)
    snap, out = run(program, nodes, pods, pct)
    a = np.asarray(out.assignment)[: len(pods)].tolist()
    n = len(nodes)
    k = oracle.num_feasible_nodes_to_find(n, pct)
    assert int(out.sample_k) == k
    rows = _seeded_rows(seed)
    assert int(out.sample_narrowed_pods) == sum(sum(r) > k for r in rows)
    if program == "scan":
        want = [d.node_index for d in oracle.schedule(
            nodes, pods, percentage_of_nodes_to_score=pct,
            cycle_index=int(snap.cycle_index))]
        assert a == want
        return
    assert oracle.validate_rounds_assignment(nodes, pods, a) == []
    bound = [(p, nodes[i].name) for p, i in zip(pods, a) if i >= 0]
    still = _feasible_rows(nodes, pods, bound)
    for j, i in enumerate(a):
        if i >= 0:
            still[j][i] = True  # its own node, now holding it
    assert still == rows, "a node filled up: the fixture is too tight"
    rank = {pi: r for r, pi in enumerate(oracle.queue_order(pods))}
    for j, row in enumerate(rows):
        start = oracle.sample_start(rank[j], int(snap.cycle_index), n)
        if any(row):
            assert a[j] in oracle.sampled_candidates(row, start, k), j
        else:
            assert a[j] == -1



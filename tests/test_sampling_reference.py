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



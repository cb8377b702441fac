"""percentageOfNodesToScore: upstream's feasible-node sample.

A pod is scored on the first k nodes FEASIBLE for it, walking the nodes
from its start offset (ops/sampling.py). The knob narrows a choice; it
never refuses a pod while a node admits it. The differential against
the plain walk (oracle.sampled_candidates) is in
test_sampling_reference.py, a file of its own so that the driver's
workers share the compiles."""

from __future__ import annotations

import numpy as np
import pytest

from k8s_scheduler_tpu.core import (
    build_carry_fns,
    build_packed_cycle_carry_fn,
    build_stable_state_fn,
)
from k8s_scheduler_tpu.core.cycle import build_cycle_fn
from k8s_scheduler_tpu.models import SnapshotEncoder
from k8s_scheduler_tpu.models.builders import MakeNode, MakePod
from k8s_scheduler_tpu.ops import sampling

PROGRAMS = ("scan", "rounds", "carry")


def _cluster(n=200):
    return [MakeNode(f"n{i}").capacity({"cpu": "8", "memory": "32Gi"})
            .labels({"slot": str(i)}).obj() for i in range(n)]


def _pod(name):
    return MakePod(name).req({"cpu": "1", "memory": "1Gi"})


def run(program, nodes, pods, pct, encoder=None):
    """One cycle of `program` over a fresh encode: (snapshot, result)."""
    enc = encoder or SnapshotEncoder()
    if program != "carry":
        snap = enc.encode(nodes, pods)
        fn = build_cycle_fn(
            commit_mode=program, percentage_of_nodes_to_score=pct)
        return snap, fn(snap)
    w, b, spec, snap, _ = enc.encode_packed(nodes, pods)
    stable = build_stable_state_fn(spec)(w, b)
    carry = build_carry_fns(spec)[0](w, b, stable)
    fn = build_packed_cycle_carry_fn(spec, percentage_of_nodes_to_score=pct)
    return snap, fn(w, b, stable, carry)


def test_sampling_window_excludes_far_nodes():
    # rank 0 starts its walk at node 137 on this snapshot (cycle_index =
    # 1) and k is 100, so the first hundred INDICES it visits are
    # [137, 199] + [0, 36]. The one feasible node (slot=100) lies outside
    # them. A window of indices refused this pod; the walk counts
    # feasible nodes only, reaches node 100 and binds it there
    nodes = _cluster(200)
    pods = [_pod("p0").node_selector({"slot": "100"}).obj()]
    snap = SnapshotEncoder().encode(nodes, pods)
    assert int(sampling.start_offsets(snap)[0]) == 137
    full = build_cycle_fn(percentage_of_nodes_to_score=100)(snap)
    sampled = build_cycle_fn(percentage_of_nodes_to_score=50)(snap)
    assert int(np.asarray(full.assignment)[0]) == 100
    assert int(np.asarray(sampled.assignment)[0]) == 100
    assert (int(sampled.sample_k), int(sampled.sample_narrowed_pods)) == (
        100, 0)


def test_sampling_rotates_across_cycles_no_starvation():
    # the same pod re-encoded on later cycles starts its walk elsewhere
    # and is bound whichever way it turns
    nodes = _cluster(200)
    pods = [_pod("p0").node_selector({"slot": "100"}).obj()]
    enc = SnapshotEncoder()
    fn = build_cycle_fn(percentage_of_nodes_to_score=50)
    placed = []
    for _ in range(6):
        snap = enc.encode(nodes, pods)
        placed.append(int(np.asarray(fn(snap).assignment)[0]))
    assert placed == [100] * 6


def test_small_clusters_are_never_sampled():
    # <100-node floor: adaptive default must not drop candidates
    nodes = _cluster(50)
    pods = [_pod("p0").node_selector({"slot": "49"}).obj()]
    snap = SnapshotEncoder().encode(nodes, pods)
    out = build_cycle_fn(percentage_of_nodes_to_score=0)(snap)
    assert int(np.asarray(out.assignment)[0]) == 49


def test_sampling_rotates_with_rank():
    # many identical pods: rotation spreads their walks, so a large
    # cluster still fills evenly under aggressive sampling
    nodes = _cluster(200)
    pods = [_pod(f"p{i}").created(float(i)).obj() for i in range(100)]
    snap = SnapshotEncoder().encode(nodes, pods)
    out = build_cycle_fn(percentage_of_nodes_to_score=50)(snap)
    a = np.asarray(out.assignment)[:100]
    assert (a >= 0).all()
    # the walks start all over the cluster: placements are not all in
    # the first half
    assert (a >= 100).any()
    assert int(out.sample_narrowed_pods) == 100  # 200 feasible, k = 100


@pytest.mark.parametrize("program", PROGRAMS)
def test_never_refused_while_a_node_admits(program):
    """Nodes 0-99 hold one pod each, nodes 100-199 ten. Every pod's first
    sample (k = 100 of 200) is half small nodes, and the 40 pods a small
    node sees in the first round fill it while it sits in their samples.
    A pod whose sample died that way is sampled again over what is still
    feasible: all 1,000 pods bind, on 1,100 places."""
    nodes = [
        MakeNode(f"n{i}").capacity(
            {"cpu": "1" if i < 100 else "10", "memory": "64Gi"}
        ).obj()
        for i in range(200)
    ]
    pods = [_pod(f"p{i}").created(float(i)).obj() for i in range(1000)]
    _, out = run(program, nodes, pods, 50)
    a = np.asarray(out.assignment)[:1000]
    assert (a >= 0).all(), int((a < 0).sum())
    assert np.bincount(a, minlength=200).max() <= 10
    assert (int(out.sample_k), int(out.sample_narrowed_pods)) == (100, 1000)


@pytest.mark.parametrize("program", PROGRAMS)
def test_k_comes_from_the_real_node_count_not_the_pad(program):
    nodes = _cluster(200)
    pods = [_pod(f"p{i}").created(float(i)).obj() for i in range(8)]
    pods.append(_pod("lone").node_selector({"slot": "7"}).obj())
    snap, out = run(program, nodes, pods, 0,
                    SnapshotEncoder(pad_nodes=1024))
    assert (snap.N, int(snap.num_nodes)) == (1024, 200)
    # adaptive at 200 nodes: 49%, 98, floored at 100 (the pad would give
    # 42% of 1,024 = 430, and nothing would be narrowed)
    assert (int(out.sample_k), int(out.sample_narrowed_pods)) == (100, 8)
    assert int(np.asarray(out.assignment)[8]) == 7


@pytest.mark.parametrize("program", PROGRAMS)
@pytest.mark.parametrize("n_nodes,pct", [(60, 0), (200, 100), (200, 150)])
def test_nothing_of_the_sample_is_traced_when_every_node_counts(
        monkeypatch, program, n_nodes, pct):
    """Under 100 nodes, and at 100% or more, the programs are the ones
    they were before the key did anything: the sampling functions are
    never reached while they are traced, and the result carries no
    count."""
    def refuse(*a, **kw):
        raise AssertionError("the sample was traced")

    for name in ("sample_feasible", "start_offsets",
                 "num_feasible_nodes_to_find"):
        monkeypatch.setattr(sampling, name, refuse)
    nodes = _cluster(n_nodes)
    pods = [_pod("p0").node_selector({"slot": str(n_nodes - 1)}).obj()]
    _, out = run(program, nodes, pods, pct)
    assert int(np.asarray(out.assignment)[0]) == n_nodes - 1
    assert not hasattr(out, "sample_k")


def test_between_the_floor_and_the_pad_every_node_is_considered():
    # 90 real nodes under a pad of 128: traced, and k is all of them
    nodes = _cluster(90)
    pods = [_pod(f"p{i}").created(float(i)).obj() for i in range(4)]
    _, out = run("carry", nodes, pods, 0)
    assert (int(out.sample_k), int(out.sample_narrowed_pods)) == (0, 0)
    assert (np.asarray(out.assignment)[:4] >= 0).all()

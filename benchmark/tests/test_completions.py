"""Pods finish (PR 35), checked without a chip (run by hand with the rest
of `benchmark/tests`):

- the completion draw: the same seed completes the same pods, never one
  on a probe-pool node, only as many as the resident set stands over
  its target, and the pod stream of a seed does not move with it;
- the reference follows the deletions: a completed pod frees the only
  feasible node, so refusing the next pod is `wrongly_refused`; the
  same refusal with the pod still there is right; a completed uid that
  was not resident is `bad_completions`; a resident set over its target
  at a cycle's start is `resident_over_target`;
- a whole (rehearsal) run in which the agent stops completing, and one
  in which every delete is dropped before the server, both come out
  `correct: false`;
- the `flight_count` reader on a known series.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import agent, generate, reduce, reference  # noqa: E402
from k8s_scheduler_tpu.models.builders import MakeNode, MakePod  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def deployment(seed: int):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sp5000-mixed.json")) as f:
        cfg = json.load(f)
    cut = {k: v for k, v in cfg["rehearse"].items() if k != "server"}
    return generate.deployment(cfg, seed, cut)


def driver(dep, seed: int, bound: int):
    """A Driver that never calls its server: the init pods placed as
    `load` places them, then `bound` pods of the stream on plain nodes
    and one probe a pool, as confirmations place them."""
    drv = agent.Driver(1, dep, {"order": "uniform"}, seed)
    for pod, node in dep.init:
        drv._placed(pod.uid, node)
    for i, pod in enumerate(dep.pending(bound, "pod")):
        drv._placed(pod.uid, dep.nodes[i % 100].name)
    for pool in dep.pools:
        drv._placed(dep.probe(pool).uid, dep.nodes[pool.nodes[0]].name)
    return drv


@pytest.mark.parametrize("seed", [1, 3_000_000_019])
def test_the_completion_draw(seed):
    dep = deployment(seed)
    target = dep.cfg["resident_target"]
    a, b = driver(dep, seed, 400), driver(deployment(seed), seed, 400)
    over = a.resident - target
    assert over > 0
    first, again = a.completions_due(), b.completions_due()
    assert first == again and len(set(first)) == len(first) == over
    assert a.resident == target and a.completions_due() == []
    # never a probe, never a probe load: check (e) reads their nodes
    assert not any("probe" in uid for uid in first)
    # init pods and window pods alike
    assert {u.split("/")[-1].split("-")[0] for u in first} == {"init", "pod"}
    # another seed, another draw
    other = driver(deployment(seed), seed + 1, 400).completions_due()
    assert len(other) == over and other != first
    # the deployment's stream does not move with the draw
    assert [p.uid for p in dep.pending(50, "pod")] == [
        p.uid for p in deployment(seed).pending(450, "pod")[400:]]
    # no `completions` block: nothing finishes
    quiet = agent.Driver(1, dep, None, seed)
    quiet._placed("default/x", dep.nodes[0].name)
    assert quiet.completions_due() == [] and quiet.resident_target is None
    with pytest.raises(agent.BenchError):
        agent.Driver(1, dep, {"order": "oldest_first"}, seed)


def tiny():
    nodes = [MakeNode(f"n{i}").capacity(
        {"cpu": "1", "memory": "1Gi", "pods": 10}).obj() for i in range(2)]
    pods = {p.uid: p for p in (
        MakePod(name).req({"cpu": "1", "memory": "100Mi"}).obj()
        for name in ("a", "b", "c"))}
    a, b, c = pods.values()
    return nodes, [(a, "n0"), (b, "n1")], pods, a, c


def test_a_completed_pod_frees_the_only_feasible_node():
    nodes, init, pods, a, c = tiny()
    refusal = (c.uid, 2, 2, "0/2 nodes are available: 2 Insufficient cpu.")
    gone = reference.Cycle(offered={c.uid}, bindings=[], evictions=[],
                           refused=[refusal], completed=[a.uid])
    v = reference.check_run(nodes, init, pods, [gone], [], 0, 2)
    assert not v.ok and v.wrongly_refused == 1
    assert v.counts["wrongly_refused"] == [1, 0]
    assert v.counts["completed"] == 1
    assert (v.resident_at_start, v.resident_after) == ([1], [1])
    # the server that got the delete binds c where a was: sound
    bound = reference.Cycle(offered={c.uid}, bindings=[(c.uid, "n0")],
                            evictions=[], refused=[], completed=[a.uid])
    v = reference.check_run(nodes, init, pods, [bound], [], 0, 2)
    assert v.ok and (v.resident_at_start, v.resident_after) == ([1], [2])
    # with a still there the refusal is right, and binding c is not
    still = reference.Cycle(offered={c.uid}, bindings=[], evictions=[],
                            refused=[refusal])
    v = reference.check_run(nodes, init, pods, [still], [], 0, 2)
    assert v.ok and v.wrongly_refused == 0
    over = reference.Cycle(offered={c.uid}, bindings=[(c.uid, "n0")],
                           evictions=[], refused=[])
    v = reference.check_run(nodes, init, pods, [over], [], 0, 2)
    assert not v.ok and v.counts["nodes_over_allocatable"][0] == 1


def test_a_completion_of_a_pod_that_was_not_resident():
    nodes, init, pods, a, c = tiny()
    cyc = reference.Cycle(offered=set(), bindings=[], evictions=[],
                          refused=[], completed=[c.uid, a.uid, a.uid])
    v = reference.check_run(nodes, init, pods, [cyc], [], 0, 2)
    assert not v.ok and v.counts["bad_completions"] == [2, 0]


def test_completions_that_stopped_read_over_the_target():
    nodes, init, pods, a, c = tiny()
    cyc = reference.Cycle(offered=set(), bindings=[], evictions=[],
                          refused=[])
    v = reference.check_run(nodes, init, pods, [cyc], [], 0, 1)
    assert not v.ok and v.counts["resident_over_target"] == [1, 0]
    # no target (a cell without completions): nothing to hold
    v = reference.check_run(nodes, init, pods, [cyc], [], 0)
    assert v.ok and v.counts["resident_over_target"] == [0, 0]


def rehearse(cell: str, seed: int):
    from benchmark import run

    class Args:
        seconds, trace = 2.0, 0

    Args.seed = seed
    return run.run_cell(
        BENCHMARK, run.find(BENCHMARK["workloads"], cell, "workload"),
        Args, rehearse=True)


@pytest.mark.parametrize("cell", CELLS)
def test_an_agent_that_stops_completing_comes_out_not_correct(
        cell, monkeypatch):
    real = agent.Driver.completions_due

    def stops(self):
        return real(self) if len(self.cycles) < 4 else []

    monkeypatch.setattr(agent.Driver, "completions_due", stops)
    line = rehearse(cell, 31)
    assert line["compared"]["resident_over_target"][0] > 0
    assert line["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_deletes_dropped_before_the_server_come_out_not_correct(
        cell, monkeypatch):
    """The agent believes it completed the pods; the server never hears
    of it and keeps them: the server's own count of the pods it holds
    says it first; a cluster that fills up says it again through (d)."""
    monkeypatch.setattr(agent.StrictAgent, "delete_pod", lambda *_: None)
    line = rehearse(cell, 32)
    assert line["compared"]["server_resident_drift"][0] > 0
    assert line["correct"] is False


def test_flight_count_reader_on_a_known_series():
    with open(os.path.join(ROOT, "benchmark", "layers",
                           "full_encodes_per_cycle.sat.json")) as f:
        spec = json.load(f)
    bare = {"t_start_s": 0.0, "t_end_s": 1.0}

    def records(totals):
        return [dict(bare, counts={"full_encodes": t, "fold_hits": 0})
                for t in totals]

    def read(flight):
        return reduce.read_layer(spec, {"spans": [], "flight": flight})

    assert read(records([3, 4, 5, 6, 7])) == 1.0  # every cycle falls back
    assert read(records([1, 1, 1, 1])) == 0.0  # the delta path: a count
    assert read(records([2, 2, 3, 3, 4])) == 0.5
    # a program whose records keep no such count, a window of one cycle
    assert read([bare, dict(bare, counts={"pods": 9})] * 3) is None
    assert read(records([5])) is None and read([]) is None
    # records without the count (an empty pop's) are left out
    assert read(records([1, 2]) + [dict(bare, counts={})]) == 1.0

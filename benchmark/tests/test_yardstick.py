"""The yardstick checked against itself (run by hand, not part of tests/):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- percentile arithmetic on a known series;
- the trace reduction on a small recorded `.xplane.pb` gives a known
  busy share;
- the plain reference passes a sound run and fails a seeded over-commit,
  a wrongly refused pod, a hostname anti-affinity breach, probes that
  never bound, more refusals with nodes left open than a sound run makes,
  and a probe choice made in the precision below float32 (the control);
- a rehearsal run whose timed path is broken underneath (a binding
  altered where it is produced) comes out `correct: false`.
"""

import json
import os
import sys

import ml_dtypes
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import generate, reduce, reference, xplane  # noqa: E402


def config(name="sp5000-mixed"):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cut = {k: v for k, v in cfg["rehearse"].items() if k != "server"}
    return cfg, cut


def sound_run(seed: int, n_pods: int = 300):
    """A deployment and one cycle placed by the reference itself, one
    pod at a time: each pod goes to its first feasible node; probes go
    where `probe_choice` in float64 sends them."""
    cfg, cut = config()
    dep = generate.deployment(cfg, seed, cut)
    cl = reference.Cluster(dep.nodes)
    for pod, node in dep.init:
        cl.add(pod, cl.index[node])
    pods = dep.pending(n_pods, "pod")
    bindings, left = [], list(pods)
    while left:  # rounds: a pod refused early may fit once a peer is in
        still = []
        for pod in left:
            open_nodes = np.flatnonzero(cl.feasible(pod))
            if open_nodes.size:
                cl.add(pod, int(open_nodes[0]))
                bindings.append((pod.uid, dep.nodes[open_nodes[0]].name))
            else:
                still.append(pod)
        if len(still) == len(left):
            break
        left = still
    refused = [(p.uid, len(dep.nodes), len(dep.nodes), "") for p in left]
    for pool in dep.pools:
        probe = dep.probe(pool)
        pods.append(probe)
        idx = np.array(pool.nodes)
        j = reference.probe_choice(cl.alloc[idx], cl.used[idx])
        bindings.append((probe.uid, dep.nodes[pool.nodes[j]].name))
    by_uid = {p.uid: p for p in pods}
    cycle = reference.Cycle(
        offered=set(by_uid), bindings=bindings, evictions=[],
        refused=refused)
    return dep, by_uid, cycle


def verdict(dep, by_uid, cycle, probe_rounds=1):
    return reference.check_run(
        dep.nodes, dep.init, by_uid, [cycle], dep.pools, probe_rounds)


def test_percentile_known_series():
    series = list(range(1, 101))
    assert reduce.percentile(series, 50) == 50
    assert reduce.percentile(series, 95) == 95
    assert reduce.percentile(series, 100) == 100
    assert reduce.percentile([3.0], 95) == 3.0
    assert reduce.percentile([5, 1, 9, 7], 50) == 5
    # the contract's spread: interquartile distance over the median
    assert reduce.spread([10, 10, 10, 10, 10, 10]) == 0
    assert reduce.spread([95, 98, 100, 100, 102, 105]) == pytest.approx(0.055)


def test_client_latency_reader_on_a_known_series():
    """The steady cell's latencies, per layer since PR 29: the same
    arithmetic as when they stood end to end; nothing in a closed loop."""
    lat = [float(v) for v in range(1, 101)]
    src = {"spans": [], "flight": [], "latency_ms": lat}
    for q, want in (("p50", 50.5), ("p95", 95.0)):
        with open(os.path.join(ROOT, "benchmark", "layers",
                               f"bind_latency_{q}_ms.steady.json")) as f:
            spec = json.load(f)
        assert reduce.read_layer(spec, src) == want
        assert reduce.read_layer(spec, {**src, "latency_ms": None}) is None
        assert reduce.read_layer(spec, {**src, "latency_ms": []}) is None


def test_union_of_intervals():
    assert xplane.union_seconds([(0, 10), (5, 15), (20, 30)]) == 25
    assert xplane.union_seconds([]) == 0
    assert xplane.program_kind("jit_cycle_carry_1a2b3c4d(17)") == "cycle_carry"
    assert xplane.program_kind("jit_preempt_0123abcd") == "preempt"


def test_recorded_trace_gives_known_busy_share():
    path = os.path.join(HERE, "data", "recorded.xplane.pb")
    with open(os.path.join(HERE, "data", "recorded.json")) as f:
        known = json.load(f)
    got = xplane.reduce_trace(path)
    assert got["planes"] == known["planes"]
    assert got["busy_s"] == pytest.approx(known["busy_s"], rel=1e-5)
    assert got["busy_s"] / got["window_s"] == pytest.approx(
        known["busy_share"], rel=1e-5)
    assert got["launches"] == known["launches"]


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_019])
def test_reference_passes_a_sound_run(seed):
    v = verdict(*sound_run(seed))
    assert v.ok, v.problems
    assert v.counts["probes_bound"] == 2
    assert v.counts["probe_score_gap_max"][0] == 0.0


@pytest.mark.parametrize("size", ["cell", "rehearsal"])
def test_every_node_type_stands_in_every_zone(size):
    """A type that stands in two zones of six makes a pod with its
    selector and a zone spread infeasible for good whenever neither zone
    is its app's emptiest: a backlog that grows all through a run (PR
    29's refusal, PERF.md section 6)."""
    cfg, cut = config()
    dep = generate.deployment(cfg, 3, cut if size == "rehearsal" else None)
    zones_of = {}
    for n in dep.nodes:
        if generate.POOL_KEY in n.metadata.labels:
            continue
        zones_of.setdefault(n.metadata.labels["node-type"], set()).add(
            n.metadata.labels[generate.ZONE_KEY])
    assert set(zones_of) == set(generate.NODE_TYPES)
    assert all(z == set(generate.ZONES) for z in zones_of.values())


@pytest.mark.parametrize("name", ["sp5000-mixed", "sp5000-default"])
def test_what_a_cell_refuses_is_the_configurations_count(name):
    """The mix refuses nothing (the reference places every pod of it);
    the configuration's unschedulable pods fit no node, are named as
    warm-up pods, and sort first among the lowest priority."""
    cfg, cut = config(name)
    dep, _by_uid, cycle = sound_run(6, n_pods=600)
    assert cycle.refused == []
    dep = generate.deployment(cfg, 6, cut)
    stay = dep.unschedulable()
    assert len(stay) == cut["unschedulable"]["count"] > 0
    assert cfg["unschedulable"]["count"] * 5 < cfg["depth"] // 8
    cl = reference.Cluster(dep.nodes)
    for pod, node in dep.init:
        cl.add(pod, cl.index[node])
    first = dep.pending(1, "warm")[0]
    for pod in stay:
        assert not cl.feasible(pod).any()
        assert pod.uid.split("/")[-1].startswith("warm-")
        assert pod.spec.priority == 0
        assert (pod.metadata.creation_timestamp
                < first.metadata.creation_timestamp)


def test_reference_fails_an_over_commit():
    dep, by_uid, cycle = sound_run(4, n_pods=100)
    # 100m pods on a 4-CPU node: the 41st does not fit
    target = dep.nodes[0].name
    plain = [(u, n) for u, n in cycle.bindings if "probe" not in u]
    cycle.bindings = [(u, target) for u, _ in plain[:60]] + [
        b for b in cycle.bindings if "probe" in b[0]]
    v = verdict(dep, by_uid, cycle)
    assert not v.ok and v.counts["nodes_over_allocatable"][0] >= 1


def test_reference_fails_a_wrongly_refused_pod():
    dep, by_uid, cycle = sound_run(5)
    uid, _node = cycle.bindings.pop(0)
    n = len(dep.nodes)
    cycle.refused.append((uid, n, n, ""))  # "0/N nodes are available"
    v = verdict(dep, by_uid, cycle)
    assert not v.ok and v.wrongly_refused == 1
    # the engine's allowance: its own diagnosis leaves nodes open
    cycle.refused[-1] = (uid, n - 3, n, "")
    v = verdict(dep, by_uid, cycle)
    assert v.ok
    assert v.counts["refused_with_nodes_left_open_by_the_program"][0] == 1
    # ... and says where they were and how many nodes IT finds open
    assert v.open_refusals["by_cycle"] == {0: 1}
    assert v.open_refusals["distinct_pods"] == 1
    assert uid in v.open_refusals["samples"][0]
    # ... up to what sound runs make, and no further: the program's own
    # diagnosis may not decide which refusals are looked at
    for _ in range(reference.REFUSED_OPEN_LIMIT):
        uid, _node = cycle.bindings.pop(0)
        cycle.refused.append((uid, n - 3, n, ""))
    v = verdict(dep, by_uid, cycle)
    assert not v.ok
    assert v.counts["refused_with_nodes_left_open_by_the_program"][0] == (
        reference.REFUSED_OPEN_LIMIT + 1)


@pytest.mark.parametrize("fault", ["left_pending", "refused_with_nodes_open"])
def test_reference_fails_probes_that_never_bound(fault):
    """Check (e) reads only probes that bound: a run whose probes stay
    pending, or are refused under the program's own allowance, has not
    been checked for precision and may not pass."""
    dep, by_uid, cycle = sound_run(8)
    probes = [b for b in cycle.bindings if reference.is_probe(b[0])]
    cycle.bindings = [b for b in cycle.bindings if b not in probes]
    if fault == "refused_with_nodes_open":
        n = len(dep.nodes)
        cycle.refused += [(u, n - 4, n, "") for u, _n in probes[:1]]
    v = verdict(dep, by_uid, cycle)
    assert not v.ok
    assert v.counts["probes_bound"] == 0
    assert v.counts["probes_missing_share"][0] == 1.0
    # five rounds fell due, one round of probes bound: the rest pending
    v = verdict(*sound_run(8), probe_rounds=5)
    assert not v.ok and v.counts["probes_missing_share"][0] == 0.8


def test_reference_fails_a_hostname_anti_affinity_breach():
    dep, by_uid, cycle = sound_run(6, n_pods=1000)  # every app twice
    holders = [
        (i, u) for i, (u, _n) in enumerate(cycle.bindings)
        if by_uid[u].spec.affinity
        and by_uid[u].spec.affinity.pod_anti_affinity
    ]
    i, uid = holders[0]
    app = by_uid[uid].metadata.labels["app"]
    j = next(k for k, (u, _n) in enumerate(cycle.bindings)
             if u != uid and by_uid[u].metadata.labels["app"] == app)
    # a same-app pod on the holder's node: broken in both directions
    cycle.bindings[j] = (cycle.bindings[j][0], cycle.bindings[i][1])
    v = verdict(dep, by_uid, cycle)
    assert not v.ok and v.counts["constraint_breaches"][0] >= 1


def test_reference_fails_a_pod_on_a_tainted_node():
    dep, by_uid, cycle = sound_run(7)
    pool_node = dep.nodes[dep.pools[0].nodes[0]].name
    uid = next(u for u, _n in cycle.bindings if "probe" not in u)
    cycle.bindings = [(u, pool_node if u == uid else n)
                      for u, n in cycle.bindings]
    v = verdict(dep, by_uid, cycle)
    assert not v.ok and v.counts["constraint_breaches"][0] >= 1


@pytest.mark.parametrize("seed", [11, 12, 13, 14, 15, 16])
def test_control_bfloat16_probe_choice_fails(seed):
    """The control: the reference in the program's place, scoring in
    bfloat16 over the full-size configuration's pools. float32 (what the
    configuration states) finds every pool's best node; bfloat16 has to
    miss one by more than the limit."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sp5000-mixed.json")) as f:
        cfg = json.load(f)
    small = {"nodes": {**cfg["nodes"], "count": 200}, "init_pods": 0}
    dep = generate.deployment(cfg, seed, small)
    cl = reference.Cluster(dep.nodes)
    for pod, node in dep.init:
        cl.add(pod, cl.index[node])
    gaps = {}
    for name, dtype in (("f32", np.float32), ("bf16", ml_dtypes.bfloat16)):
        chosen = []
        for pool in dep.pools:
            idx = np.array(pool.nodes)
            chosen.append(pool.nodes[reference.probe_choice(
                cl.alloc[idx], cl.used[idx], dtype)])
        gaps[name] = reference.probe_gaps(cl, dep.pools, chosen)[0]
    assert gaps["f32"] == 0.0
    assert gaps["bf16"] > reference.PROBE_GAP_LIMIT


def test_broken_timed_path_comes_out_not_correct(monkeypatch):
    """Drive a whole (rehearsal) run with one binding altered where the
    client receives it: a constrained pod rebound to a probe pool's node.
    `correct` has to come out false."""
    from benchmark import run
    from benchmark.lib import agent

    real = agent.Driver.step
    state = {"done": False}

    def broken(self, due, probes=True):
        span = real(self, due, probes)
        cyc = self.cycles[-1]
        if not state["done"] and len(self.cycles) > 2 and cyc.bindings:
            uid, _node = cyc.bindings[0]
            pool_node = self.dep.nodes[self.dep.pools[0].nodes[0]].name
            cyc.bindings[0] = (uid, pool_node)
            state["done"] = True
        return span

    monkeypatch.setattr(agent.Driver, "step", broken)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = run.find(bench["workloads"], "sp5000-mixed.sat", "workload")

    class Args:
        seed, seconds, trace = 21, 2.0, 0

    line = run.run_cell(bench, cell, Args, rehearse=True)
    assert state["done"]
    assert line["correct"] is False


def test_the_traced_window_is_at_least_as_long_as_its_last_operation():
    """The child times its window from start_trace's return; an
    operation that ends past that length stretches the window instead
    of being counted as busy time of a window that does not hold it."""
    path = os.path.join(HERE, "data", "recorded.xplane.pb")
    whole = xplane.reduce_trace(path)
    short = xplane.reduce_trace(path, whole["window_s"] / 2)
    long = xplane.reduce_trace(path, 5.0)
    assert short["busy_s"] == long["busy_s"] == whole["busy_s"]
    assert short["window_s"] >= whole["window_s"] > whole["window_s"] / 2
    assert short["busy_s"] <= short["window_s"]
    assert long["window_s"] == 5.0

"""The guards of the benchmark's data path that were hand-run until
PR 44 (`benchmark/tests/test_templates.py`, PR 42), held by tier 1 with
no process started:

- the old path of `benchmark/lib/generate.py` did not move: each of the
  four configurations without `templates` is offered PR 41's nodes and
  pods, same order, same uids (a digest pinned on PR 41's file: the
  guard against `benchmark_moved`);
- a configuration with `templates` draws its `init` stream from one and
  its offered streams from the other, a seed repeats, and the order in
  which the streams are asked is part of what a seed offers;
- check (f) of `benchmark/lib/reference.py`: each of its five counts
  reads above 0 on its hand-made fault and all five read 0 on the sound
  hand-made run;
- the two reductions `sp5000-preempt.sat`'s metrics use,
  `mean_per_cycle` and `program_per_launch`, on hand-made sources, and
  the fifteen entries of that cell beside their layer files;
- and, beside the program's own preemption tests
  (`tests/test_scheduler_host.py`), the two counts a flight record keeps
  of the nominated pods' fate (PR 44): a pod nominated in one cycle that
  comes back and binds in a later one.

No rehearsal here: the registered cell's two are
`tests/test_benchmark_rehearsal.py`'s, which owns `.bench/`.
"""

import hashlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.lib import generate, reduce, reference  # noqa: E402
from benchmark.lib.reference import Cycle  # noqa: E402
from k8s_scheduler_tpu.models import MakeNode, MakePod  # noqa: E402
from test_scheduler_host import make_scheduler  # noqa: E402

CONFIG = "sp5000-preempt"
CELL = "sp5000-preempt.sat"
FIVE = ("bad_nominations", "bad_evictions", "victims_not_lower",
        "nominations_without_room", "needless_victims")


def load(*parts) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def config(name: str) -> dict:
    return load("benchmark", "configs", name + ".json")


# ---- the old path did not move ----------------------------------------

# computed on PR 41's benchmark/lib/generate.py (b52c2e0) by `digest`;
# the two that differ in one YAML key are one cluster
PARENTS = {
    ("sp5000-mixed", 7): "09be183776434f1a",
    ("sp5000-mixed", 3_000_000_019): "1b3b45daf529de7a",
    ("sp5000-default", 7): "09be183776434f1a",
    ("sp5000-default", 3_000_000_019): "1b3b45daf529de7a",
    ("sp5000-unschedulable", 7): "7798943409776973",
    ("sp5000-unschedulable", 3_000_000_019): "be7f9484ec299658",
    ("sp500-basic", 7): "3a5171bf74d71f50",
    ("sp500-basic", 3_000_000_019): "d25629a125423501",
}


def say(pod) -> str:
    s = pod.spec
    return repr((
        pod.uid, sorted(pod.resource_requests().items()), s.priority,
        sorted(pod.metadata.labels.items()),
        sorted(s.node_selector.items()), s.tolerations, s.affinity,
        s.topology_spread_constraints, pod.metadata.creation_timestamp))


def digest(cfg: dict, seed: int, n: int = 3000,
           streams=("pod", "warm")) -> str:
    """Nodes, then `init` (inside `deployment`), `pod`, `warm`, the
    stuck pods: the order in which `run.py` asks."""
    dep = generate.deployment(cfg, seed, {"init_pods": n})
    h = hashlib.sha256()
    for nd in dep.nodes:
        h.update(repr((
            nd.name, sorted(nd.metadata.labels.items()), nd.spec.taints,
            sorted(nd.status.allocatable.items()))).encode())
    for pod, node in dep.init:
        h.update((say(pod) + node).encode())
    drawn = {s: dep.pending(n, s) for s in streams}
    for stream in ("pod", "warm"):
        for pod in drawn[stream]:
            h.update(say(pod).encode())
    for pod in dep.unschedulable():
        h.update(say(pod).encode())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,seed", sorted(PARENTS))
def test_an_accepted_configuration_is_offered_the_parents_pods(name, seed):
    assert "templates" not in config(name)
    assert digest(config(name), seed) == PARENTS[name, seed]


# ---- two templates, and the order of the streams -----------------------


def test_streams_draw_from_their_templates_and_a_seed_repeats():
    cfg = config(CONFIG)
    cut = {k: v for k, v in cfg["rehearse"].items() if k != "server"}
    dep = generate.deployment(cfg, 11, cut)
    low, high = cfg["templates"]["low"], cfg["templates"]["high"]
    plain = [p for p, _ in dep.init if p.name.startswith("init-")]
    assert len(plain) == cut["init_pods"]
    assert {p.spec.priority for p in plain} == set(low["priorities"])
    assert {reference.requests(p)[0] for p in plain} == {900}
    offered = dep.pending(300, "pod") + dep.pending(300, "warm")
    assert {p.spec.priority for p in offered} == set(high["priorities"])
    assert {reference.requests(p)[0] for p in offered} == {3000}
    # four low pods a plain node: 3,600m of 4,000m, no fifth
    n_pool = cut["probe"]["pools"] * cut["probe"]["nodes_per_pool"]
    per_node = {}
    for p, node in dep.init:
        if p.name.startswith("init-"):
            per_node[node] = per_node.get(node, 0) + 1
    assert set(per_node.values()) == {4}
    assert len(per_node) == cut["nodes"]["count"] - n_pool
    assert digest(cfg, 11, 600) == digest(cfg, 11, 600)
    assert digest(cfg, 11, 600) != digest(cfg, 12, 600)


@pytest.mark.parametrize("name", ("sp500-basic", CONFIG))
def test_the_order_in_which_streams_are_asked_is_part_of_the_seed(name):
    """Every stream draws lazily from the deployment's one generator:
    `warm` asked before `pod` offers other pods under the same uids."""
    cfg = config(name)
    assert digest(cfg, 7, 600) != digest(cfg, 7, 600, ("warm", "pod"))
    dep = generate.deployment(cfg, 7, {"init_pods": 10})
    if "templates" in cfg:
        assert dep.template("init") is cfg["templates"]["low"]
        assert dep.template("warm") is dep.template("pod") \
            is cfg["templates"]["high"]
    else:
        assert dep.template("init") is dep.template("pod") is cfg["pods"]


# ---- check (f) on hand-made runs ----------------------------------------


def node(name: str):
    return MakeNode(name).capacity(
        {"cpu": "4", "memory": "32Gi", "pods": 110}).obj()


def low(name: str, priority: int = 0):
    return MakePod(name).req({"cpu": "900m", "memory": "500Mi"}).priority(
        priority).obj()


def high(name: str, priority: int = 10):
    return MakePod(name).req({"cpu": "3", "memory": "500Mi"}).priority(
        priority).obj()


def preemption(victims=("l0", "l1", "l2"), nominated=("h",),
               victim_priority: int = 0, free_node: bool = False,
               victims_on: str = "node-0"):
    """Two nodes, four low pods on each (node-1 empty if `free_node`),
    two pending pods of 3 CPU and priority 10; one cycle that refuses
    `h`, nominates `nominated` to node-0 and evicts `victims` from
    `victims_on`."""
    nodes = [node("node-0"), node("node-1")]
    init = [(low(f"l{i}", victim_priority), "node-0") for i in range(4)]
    if not free_node:
        init += [(low(f"m{i}"), "node-1") for i in range(4)]
    pods = {p.uid: p for p in (high("h"), high("g"))}
    uid = {p.name: p.uid for p in [*pods.values(), *(p for p, _ in init)]}
    cyc = Cycle(
        offered={uid["h"], uid["g"]}, bindings=[],
        evictions=[(uid[v], victims_on) for v in victims],
        refused=[(uid["h"], 2, 2, "0/2 nodes are available: "
                  "2 Insufficient cpu.")],
        nominations=[(uid[n], "node-0") for n in nominated],
    )
    return reference.check_run(nodes, init, pods, [cyc], [], 0)


def test_a_sound_preemption_reads_five_zeros():
    v = preemption()
    assert v.ok, v.problems
    assert {k: v.counts[k] for k in FIVE} == dict.fromkeys(FIVE, [0, 0])
    assert v.counts["wrongly_refused"] == [0, 0]
    assert (v.preemption["nominations"], v.preemption["victims"],
            v.preemption["victims_per_nomination"]) == (1, 3, 3.0)
    # the victims left the replay: one low pod stays on node-0
    assert v.resident_after == [5]


CONTROLS = {
    # an equal-priority victim: no pod is evicted for an equal one
    "victims_not_lower": dict(victim_priority=10),
    # a fourth victim where three make room
    "needless_victims": dict(victims=("l0", "l1", "l2", "l3")),
    # two victims where three are needed: 1,800m + 400m < 3,000m
    "nominations_without_room": dict(victims=("l0", "l1")),
    # victims on a node nobody was nominated to
    "bad_evictions": dict(victims=("m0", "m1", "m2"), victims_on="node-1"),
    # a nomination of a pod that a node admits: preemption in a cluster
    # with room
    "bad_nominations": dict(free_node=True),
}


@pytest.mark.parametrize("count", FIVE)
def test_each_count_of_check_f_fails_on_its_control(count):
    v = preemption(**CONTROLS[count])
    assert v.counts[count][0] > 0 and v.counts[count][1] == 0, v.counts
    assert not v.ok


# ---- the two reductions, and the cell's entries -------------------------


def test_mean_per_cycle_is_over_the_records_that_carry_the_count():
    spec = {"select": ["preemptors"], "reduce": "mean_per_cycle"}
    flight = [{"counts": {"preemptors": 64}}, {"counts": {"preemptors": 0}},
              {"counts": {}}, {"counts": {"preemptors": 32}}]
    assert reduce.read_flight_count(spec, {"flight": flight}) == 32.0
    # a program whose records keep no such count: nothing, never 0
    assert reduce.read_flight_count(spec, {"flight": [{"counts": {}}]}) \
        is None
    # a running total is still read as a rise
    rise = {"select": ["refusals"], "reduce": "rise_per_cycle"}
    assert reduce.read_flight_count(rise, {"flight": [
        {"counts": {"refusals": 10}}, {"counts": {"refusals": 30}}]}) == 20.0


def test_program_per_launch_is_the_programs_own_time():
    trace = {"busy_s": 1.2, "window_s": 12.0,
             "by_program": {"packed_preempt": 0.6, "carry_cycle": 0.4},
             "launches": {"packed_preempt": 3, "carry_cycle": 4}}
    own = load("benchmark", "layers", "preempt_device_ms.json")
    assert reduce.read_layer(own, {"trace": trace}) == pytest.approx(200.0)
    # ... where `busy_per_launch` divides ALL busy time by the launches
    busy = {**own, "reduce": "busy_per_launch"}
    assert reduce.read_layer(busy, {"trace": trace}) == pytest.approx(400.0)
    assert reduce.read_layer({**own, "per": "diagnose"},
                             {"trace": trace}) is None
    assert reduce.read_layer(own, {"trace": None}) is None


BENCHMARK = load("BENCHMARK.json")
# the entries of this cell alone (an entry that lists every cell, as
# PR 45's `snapshot_rows_encoded_per_cycle` does, is held elsewhere:
# tests/test_state_fragments.py)
OF_THE_CELL = [m for m in BENCHMARK["per_layer"] if m["workloads"] == [CELL]]
# the metric over a count of ITS cycle -> the count it selects
PER_CYCLE = {
    "nominations_per_cycle": "preemptors", "victims_per_cycle": "victims",
    "bound_per_cycle": "scheduled",
    "backoff_held_per_cycle": "queue_backoff",
    "nominated_dispatched_per_cycle": "nominated_dispatched",
    "nominated_bound_per_cycle": "nominated_bound",
}
SOURCE = {"flight_count": "program_counter", "trace_ops": "device_trace",
          "flight_phase": "program_span", "program_span": "program_span"}


@pytest.mark.parametrize("entry", OF_THE_CELL, ids=lambda m: m["name"])
def test_an_entry_of_the_cell_says_what_its_layer_file_says(entry):
    """Fifteen entries, each of this cell alone, each a file of its own;
    a `.preempt` one is its `.sat` or shared original with `name`,
    `workloads` and `what` changed and nothing else."""
    assert len(OF_THE_CELL) == 15
    name = entry["name"]
    spec = load("benchmark", "layers", name + ".json")
    assert entry["workloads"] == spec["workloads"] == [CELL]
    assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
        name, entry["unit"], entry["layer"], entry["moves"])
    assert entry["moves"] == "pods_bound_per_s"
    assert SOURCE[spec["source_kind"]] == entry["source"]
    if name in PER_CYCLE:
        assert (spec["select"], spec["reduce"]) == (
            [PER_CYCLE[name]], "mean_per_cycle")
    if name.endswith(".preempt"):
        stem = name[:-len(".preempt")]
        path = os.path.join(ROOT, "benchmark", "layers", stem + ".sat.json")
        orig = stem + ".sat" if os.path.exists(path) else stem
        own = ("name", "workloads", "what")
        was = load("benchmark", "layers", orig + ".json")
        assert {k: v for k, v in spec.items() if k not in own} == {
            k: v for k, v in was.items() if k not in own}
        (accepted,) = [m for m in BENCHMARK["per_layer"]
                       if m["name"] == orig]
        assert {k: v for k, v in entry.items() if k not in own} == {
            k: v for k, v in accepted.items() if k not in own}


def test_the_cell_is_registered_on_its_own_files():
    (cfg_entry,) = [c for c in BENCHMARK["configs"] if c["name"] == CONFIG]
    (cell,) = [w for w in BENCHMARK["workloads"] if w["name"] == CELL]
    cfg = config(CONFIG)
    assert cfg_entry["source"] == cfg["source"][:200]
    assert cfg_entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert cfg_entry["reduced"] == [] and not cfg["reduced"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "sat", 1)
    assert "pinned by the cluster's size" in cell["why"]
    assert len(cell["why"]) <= 200 and len(cfg_entry["why"]) <= 200
    traffic = load("benchmark", "workloads", CELL + ".json")
    assert traffic["config"] == CONFIG and "completions" not in traffic


# ---- the nominated pods' fate, in the flight record ---------------------

FATE = ("nominated_dispatched", "nominated_bound")


def fate(sched) -> list:
    return [tuple(r.counts[k] for k in FATE)
            for r in sched.flight.snapshot()]


@pytest.mark.parametrize("comes_back", ("binds", "is_refused_again"))
def test_a_record_counts_what_became_of_a_nominated_pod(comes_back):
    """One node full of one low-priority pod, one pending pod that fits
    once it is evicted. The cycle that nominates it reads 0 and 0; the
    cycle it comes back in reads `nominated_dispatched` 1, and
    `nominated_bound` 1 if it binds there, 0 if a pod of higher
    priority took the freed node first."""
    sched, cluster, clock = make_scheduler()
    sched.on_node_add(MakeNode("n0").capacity({"cpu": "2"}).obj())
    sched.on_pod_add(
        MakePod("victim").req({"cpu": "2"}).priority(1).obj(),
        node_name="n0")
    sched.on_pod_add(MakePod("urgent").req({"cpu": "2"}).priority(10).obj())
    stats = sched.schedule_cycle()
    assert (stats.preemptors, stats.victims) == (1, 1)
    assert fate(sched) == [(0, 0)]
    if comes_back == "is_refused_again":
        sched.on_pod_add(
            MakePod("first").req({"cpu": "2"}).priority(100).obj())
    clock.tick(2.0)  # past the nominated pod's backoff
    stats = sched.schedule_cycle()
    assert stats.scheduled == 1
    if comes_back == "binds":
        assert cluster.bound == {"urgent": "n0"}
        assert fate(sched) == [(0, 0), (1, 1)]
    else:
        assert cluster.bound == {"first": "n0"}
        assert fate(sched) == [(0, 0), (1, 0)]
    # the dump and /debug/flightrecorder carry them like every count
    assert all(k in sched.flight.to_dicts()[-1]["counts"] for k in FATE)

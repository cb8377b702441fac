"""The server's policy for the cyclic collector (core/collector.py).

One in-process service serves the whole file (its programs compile
once); every test deletes the pods it added. A test that installs the
policy does so through the `policy` fixture, which puts the
interpreter's collector back (thresholds, nothing frozen, no callback),
so no other test inherits the policy.
"""

from __future__ import annotations

import contextlib
import gc
import glob
import itertools
import json
import os
import re
import subprocess
import sys
from concurrent import futures

import grpc
import pytest

from k8s_scheduler_tpu.config import SchedulerConfiguration
from k8s_scheduler_tpu.core import collector
from k8s_scheduler_tpu.core import spans as _spans
from k8s_scheduler_tpu.core.spans import AGENT_SPAN_NAMES, SPAN_NAMES
from k8s_scheduler_tpu.models import MakeNode, MakePod
from k8s_scheduler_tpu.service import convert
from k8s_scheduler_tpu.service import scheduler_pb2 as pb
from k8s_scheduler_tpu.service.client import SchedulerClient
from k8s_scheduler_tpu.service.server import SchedulerService, add_to_server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "k8s_scheduler_tpu")

NODES = 4
ROUNDS, PER_ROUND = 8, 6
_ROUND = itertools.count()  # pod names are never used twice


def collector_state():
    return (gc.get_threshold(), gc.get_freeze_count(), list(gc.callbacks))


@pytest.fixture(scope="module")
def shared():
    svc = SchedulerService(config=SchedulerConfiguration(
        pod_initial_backoff_seconds=0.05, pod_max_backoff_seconds=0.2))
    req = pb.UpdateRequest()
    for i in range(NODES):
        req.node_adds.append(convert.node_to(
            MakeNode(f"n{i}").capacity({"cpu": "4000", "pods": "4000"}).obj()))
    svc.Update(req, None)
    return svc


@pytest.fixture()
def svc(shared):
    yield shared
    delete_pods(shared, [
        p.uid for p, _n in shared.scheduler.cache.existing_pods()])
    delete_pods(shared, [p.uid for p in shared.scheduler.queue.all_pending()])
    assert shared.scheduler.census()[0] == NODES


@contextlib.contextmanager
def installed(svc, census):
    """A CollectorPolicy over `census`, installed on the service; undone
    whatever the body did."""
    found = collector_state()
    pol = collector.CollectorPolicy(census, metrics=svc.scheduler.metrics)
    svc.collector = pol
    pol.install()
    try:
        yield pol
    finally:
        svc.collector = None
        pol.uninstall()
    # thresholds and callbacks as found and nothing left frozen (the
    # interpreter starts with a few hundred objects frozen of its own;
    # `gc.unfreeze()` knows no part)
    assert (gc.get_threshold(), list(gc.callbacks)) == (found[0], found[2])
    assert gc.get_freeze_count() == 0


@pytest.fixture()
def policy(svc, monkeypatch):
    """An installed CollectorPolicy on the service, with thresholds and
    a floor that fit a test's few dozen pods."""
    monkeypatch.setattr(collector, "THRESHOLDS", (50, 10, 10))
    monkeypatch.setattr(collector, "SWEEP_MIN_DEPARTURES", 10)
    with installed(svc, svc.scheduler.census) as pol:
        yield pol


@pytest.fixture()
def armed():
    rec = _spans.arm(rate=1.0)
    yield rec
    _spans.disarm()


def passes(rec) -> list:
    return [s for s in rec.snapshot() if s.name == "gc.pass"]


def add_pods(svc, n: int = PER_ROUND) -> None:
    r = next(_ROUND)
    req = pb.UpdateRequest()
    for i in range(n):
        req.pod_adds.append(pb.PodEvent(pod=convert.pod_to(
            MakePod(f"r{r}-{i}").req({"cpu": "1"}).obj())))
    svc.Update(req, None)


def whole(pod) -> None:
    """The pod is made part of a reference cycle, so that only a
    collector pass can free it once it is deleted."""
    pod.metadata.annotations["self"] = pod


def one_object(pod) -> None:
    """The pod leaves by reference count and leaves ONE object behind:
    a list that holds itself."""
    loop: list = []
    loop.append(loop)
    pod.metadata.annotations["loop"] = loop


def cycle_and_confirm(svc, leak=whole, every: int = 1) -> list:
    """One `Cycle`, every binding confirmed by reference; then `leak`
    (None: nothing) is applied to every `every`-th pod it bound."""
    resp = svc.Cycle(pb.CycleRequest(), None)
    req = pb.UpdateRequest()
    for b in resp.bindings:
        req.bind_confirms.append(
            pb.BindConfirm(pod_uid=b.pod_uid, node_name=b.node_name))
    assert svc.Update(req, None).bind_confirms_applied == len(resp.bindings)
    new = {b.pod_uid for b in resp.bindings[::every]} if leak else ()
    for pod, _node in svc.scheduler.cache.existing_pods():
        if pod.uid in new:
            leak(pod)
    return [b.pod_uid for b in resp.bindings]


def delete_pods(svc, uids) -> None:
    req = pb.UpdateRequest()
    req.pod_deletes.extend(uids)
    svc.Update(req, None)


def churn(svc, rounds: int, deletes: bool, after_cycle=None,
          per_round: int = PER_ROUND, bound=None, **leak) -> list:
    """`rounds` of: add `per_round` pods, bind them (`leak`: see
    `cycle_and_confirm`) and, from the second round on (the first,
    where `bound` carries on an earlier call's), delete the oldest
    `per_round` bound."""
    carried = bound is not None
    bound = bound if carried else []
    for r in range(rounds):
        add_pods(svc, per_round)
        bound += cycle_and_confirm(svc, **leak)
        if after_cycle is not None:
            after_cycle(r)
        if deletes and (r or carried):
            delete_pods(svc, bound[:per_round])
            del bound[:per_round]
    return bound


def objects_alive() -> int:
    return len(gc.get_objects()) + gc.get_freeze_count()


# ---- (a) nothing but cmd/main installs it ---------------------------------

def test_import_and_construction_leave_the_collector_alone():
    """In a fresh interpreter: importing the package, the policy's
    module and the entry point, and constructing a service, change no
    threshold, freeze nothing and add no callback."""
    code = (
        "import gc, jax\n"  # jax hooks the collector itself: before the snapshot
        "found = (gc.get_threshold(), gc.get_freeze_count(), list(gc.callbacks))\n"
        "import k8s_scheduler_tpu, k8s_scheduler_tpu.core.collector\n"
        "import k8s_scheduler_tpu.cmd.main\n"
        "from k8s_scheduler_tpu.service.server import SchedulerService\n"
        "svc = SchedulerService()\n"
        "assert svc.collector is None\n"
        "now = (gc.get_threshold(), gc.get_freeze_count(), list(gc.callbacks))\n"
        "assert now == found, (found, now)\n"
        "print('as found')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("as found")


def test_a_service_without_the_policy_changes_nothing(svc):
    found = collector_state()
    churn(svc, 2, deletes=True)
    assert svc.collector is None and collector_state() == found


def _sources(pattern: str) -> dict:
    hits: dict = {}
    for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            n = len(re.findall(pattern, f.read()))
        if n:
            hits[os.path.relpath(path, PACKAGE)] = n
    return hits


def test_main_is_the_only_installer_and_the_module_the_only_gc_user():
    main = os.path.join("cmd", "main.py")
    assert _sources(r"CollectorPolicy\(") == {main: 1}
    assert _sources(r"\bcollector\.install\(\)") == {main: 1}
    assert list(_sources(
        r"\bgc\.(freeze|unfreeze|set_threshold|disable|collect)\("
    )) == [os.path.join("core", "collector.py")]


# ---- what the rule counts: departures, not movements ----------------------

def test_the_cache_counts_what_it_drops_and_nothing_else():
    from k8s_scheduler_tpu.internal.cache import SchedulerCache

    clock = [0.0]
    cache = SchedulerCache(assumed_pod_ttl_seconds=1.0, now=lambda: clock[0])
    a, b, c, d = (MakePod(n).obj() for n in "abcd")
    cache.add_node(MakeNode("n0").obj())
    cache.update_node(MakeNode("n0").obj())
    cache.add_pod(a, "n0")
    for pod in (b, c, d):
        cache.assume(pod, "n0")
    assert cache.confirm(b.uid, "n0") is b  # assumed -> bound: a move
    cache.finish_binding(c.uid)
    assert cache.departed == 0
    cache.remove_pod("default/nobody")
    cache.forget(b.uid)  # bound by now: nothing to forget
    assert cache.departed == 0
    cache.remove_pod(a.uid)
    cache.forget(d.uid)
    clock[0] = 5.0
    assert [p.uid for p, _n in cache.cleanup_expired()] == [c.uid]
    cache.remove_node("n0")
    cache.remove_node("n0")
    assert cache.departed == 4


def test_the_queue_counts_a_deleted_pending_pod_not_a_confirmed_one(svc):
    q = svc.scheduler.queue
    at = q.departed
    add_pods(svc, 3)
    uids = cycle_and_confirm(svc)  # each confirmation deletes an in-flight uid
    assert len(uids) == 3 and q.departed == at
    add_pods(svc, 2)
    pending = [p.uid for p in q.all_pending()]
    delete_pods(svc, pending + ["default/nobody"])
    assert q.departed == at + 2
    resident, departed = svc.scheduler.census()
    assert resident == NODES + 3
    assert departed == svc.scheduler.cache.departed + q.departed


# ---- (b) churn: the sweep bounds what freezing leaks ----------------------

class Schedule:
    """An `after_cycle` hook for `churn`: where the rule puts the
    sweeps, from the scheduler's census after every cycle and the rate
    the policy had in force, beside the sweeps the policy placed."""

    def __init__(self, svc, pol) -> None:
        self.census, self.pol = svc.scheduler.census, pol
        self.swept_at, self.swept, self.deferred = self.census()[1], [], 0
        self.placed, self.rates = [], [pol.q]

    def __call__(self, _r) -> None:
        resident, departed = self.census()
        left = departed - self.swept_at
        due = collector.SWEEP_SHARE * resident
        asked = left >= collector.SWEEP_MIN_DEPARTURES and left > due
        if asked and self.rates[-1] * left > due:
            self.swept.append(len(self.placed))
            self.swept_at = departed
        else:
            self.deferred += asked
        self.placed.append(self.pol.sweeps)
        self.rates.append(self.pol.q)

    def held(self) -> None:
        n = range(1, len(self.placed) + 1)
        want = [sum(r < i for r in self.swept) for i in n]
        assert self.placed == want, (
            str(self.placed), self.swept, str(self.rates))
        assert self.pol.deferred == self.deferred
        assert all(0.0 < q <= 1.0 for q in self.rates), self.rates
        falls = zip(self.rates, self.rates[1:])
        assert all(b >= a * collector.SWEEP_SHARE for a, b in falls)


def under_both(svc, request, run, settle=None):
    """`run(after_cycle)` under the interpreter's collector and then
    under the policy (`settle()` between the two): the policy's
    `Schedule`, held, and what each run left alive, tracked or frozen,
    the first after a full pass and the second as the policy left it."""
    gc.collect()
    base = objects_alive()
    delete_pods(svc, run(None))
    gc.collect()
    default_alive = objects_alive() - base
    if settle is not None:
        settle()
    pol = request.getfixturevalue("policy")
    base = objects_alive()
    sched = Schedule(svc, pol)
    delete_pods(svc, run(sched))
    sched.held()
    return sched, objects_alive() - base, default_alive


def test_churn_ends_within_a_margin_of_the_default_collector(
        svc, request, armed):
    """Pods that are reference cycles, added, bound and deleted: with
    the policy on, what is alive at the end (tracked or frozen) is what
    the default collector leaves, plus at most the departures one sweep
    may wait for; and the sweeps ran where the rate in force put them,
    not before. A pod that has just left is still held by the cycle's
    records, so the first sweep may find nothing of it; every later one
    finds more objects than pods have left, which reads `q` 1 and is
    the schedule of a policy that presumes every departure leaked."""
    rounds = ROUNDS + 4
    churn(svc, 2, deletes=True)  # whatever the first cycles build once
    sched, policy_alive, default_alive = under_both(
        svc, request,
        lambda hook: churn(svc, rounds, deletes=True, after_cycle=hook))
    pol = sched.pol
    assert 2 <= pol.sweeps < rounds - 1  # it ran, and not every round
    assert [s.attrs["kind"] for s in passes(armed)].count("sweep") == pol.sweeps
    # from the second sweep on the rate reads 1, and a sweep follows
    # every two rounds' 12 departures, as under the presumption
    assert sched.rates[sched.swept[1] + 2:] == [1.0] * (
        rounds - sched.swept[1] - 1)
    assert {b - a for a, b in zip(sched.swept[1:], sched.swept[2:])} == {2}
    # what may still wait for a sweep: the pods deleted since the last
    # one (under three rounds' worth here), ~40 objects each, and the
    # spans the armed ring holds now
    margin = 3 * PER_ROUND * 40 + 12 * len(armed.snapshot())
    assert policy_alive <= default_alive + margin, (
        policy_alive, default_alive, margin)
    assert gc.get_freeze_count() > 0  # and the rest is out of sight


def test_a_leak_that_begins_late_puts_the_schedule_back(svc, request, armed):
    """Pods that leave by reference count until two fruitless sweeps
    have let `q` fall to a sixteenth, then pods that are reference
    cycles: the next sweep, which the rate in force when the leak began
    put 66 departures after the last, finds them and reads `q` 1; from
    it on a sweep follows every two rounds, as under the presumption,
    and what is alive at the end is within the margin of the test
    above."""
    quiet, leaking = 7, 15

    def run(hook):
        bound = churn(svc, quiet, deletes=True, after_cycle=hook, leak=None)
        return churn(svc, leaking, deletes=True, after_cycle=hook,
                     bound=bound)

    def settle():
        # the records of the last cycles still hold the pods just
        # deleted: cycles that the policy's first sweep must not find
        delete_pods(svc, churn(svc, 5, deletes=True, leak=None))
        gc.collect()

    delete_pods(svc, churn(svc, 2, deletes=True))
    sched, policy_alive, default_alive = under_both(
        svc, request, run, settle)
    assert sched.swept == [3, 6, 17, 19, 21]
    assert sched.rates[quiet] == collector.SWEEP_SHARE ** 2  # two fruitless
    assert sched.rates[18:] == [1.0] * 5  # found at once, and kept
    margin = 3 * PER_ROUND * 40 + 12 * len(armed.snapshot())
    assert policy_alive <= default_alive + margin, (
        policy_alive, default_alive, margin)


def test_one_object_in_ten_departures_settles_the_rate_at_a_tenth(
        svc, request):
    """Ten pods a round, and every tenth leaves one object behind (a
    list that holds itself): the rate settles at the 0.1 objects a
    departure that sweeps find, a sweep follows where that rate puts it
    (seven rounds' departures, not one), and what is alive stays under
    what the default collector leaves plus a quarter of the resident
    set's objects."""
    per_round, rounds = 10, 30
    delete_pods(svc, churn(svc, 2, deletes=True, per_round=per_round))
    sched, policy_alive, default_alive = under_both(
        svc, request,
        lambda hook: churn(svc, rounds, deletes=True, after_cycle=hook,
                           per_round=per_round, leak=one_object, every=10))
    assert sched.pol.sweeps >= 4
    assert sched.pol.q == pytest.approx(0.1, rel=0.25)
    assert sched.swept[-1] - sched.swept[-2] == 7
    resident_objects = 40 * (NODES + 2 * per_round)
    assert policy_alive <= default_alive + (
        collector.SWEEP_SHARE * resident_objects), (
            policy_alive, default_alive)


# ---- (c) no departures, no sweep ------------------------------------------

def test_no_departures_no_sweep_however_many_cycles(svc, policy, armed):
    churn(svc, 12, deletes=False, per_round=2)
    assert policy.sweeps == 0
    kinds = [s.attrs["kind"] for s in passes(armed)]
    assert "sweep" not in kinds and kinds.count("freeze") == 12


def test_a_cycle_that_left_nothing_standing_is_not_frozen(svc, policy, armed):
    gc.set_threshold(5000, 10, 10)  # an empty Cycle's spans are no young pass
    svc.Cycle(pb.CycleRequest(), None)
    before = len(passes(armed))
    for _ in range(5):
        svc.Cycle(pb.CycleRequest(), None)
    assert len(passes(armed)) == before


# ---- (d) what the ring and the counters see -------------------------------

def test_gc_pass_is_in_the_inventory():
    assert "gc.pass" in SPAN_NAMES and "gc.pass" in AGENT_SPAN_NAMES


@pytest.mark.parametrize("cell, suffix", [
    ("sp5000-mixed.sat", "sat"), ("sp5000-default.sat", "default"),
    ("sp5000-mixed.steady", "steady")])
def test_gc_pass_ms_names_one_accepted_cell_and_a_stamped_span(cell, suffix):
    """The three metrics over the span are data: a layer file and a
    `per_layer` entry each, which say the same."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = f"gc_pass_ms.{suffix}"
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    with open(os.path.join(REPO, "benchmark", "layers", name + ".json")) as f:
        spec = json.load(f)
    assert entry["workloads"] == spec["workloads"] == [cell]
    assert cell in {w["name"] for w in bench["workloads"]}
    assert spec["source_kind"] == entry["source"] == "program_span"
    assert spec["select"] == ["gc.pass"] and spec["select"][0] in SPAN_NAMES
    assert spec["reduce"] == "mean" and spec["unit"] == entry["unit"] == "ms"
    assert entry["moves"] == spec["moves"] == "pods_bound_per_s"
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    snapshot = next(m for m in bench["per_layer"]
                    if m["name"] == f"cycle_snapshot_ms.{suffix}")
    assert entry["layer"] == spec["layer"] == snapshot["layer"]


def test_gc_sweeps_deferred_per_cycle_names_every_cell_and_a_kept_count():
    """The metric over the policy's second count is data too: one layer
    file and one `per_layer` entry for the five cells that have
    completions, beside
    `gc_sweeps_per_cycle`'s and of its shape, over a count the flight
    records keep."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = "gc_sweeps_deferred_per_cycle"
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    with open(os.path.join(REPO, "benchmark", "layers", name + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(
            REPO, "benchmark", "layers", "gc_sweeps_per_cycle.json")) as f:
        placed = json.load(f)
    # the cells in which pods finish: the policy counts departures, and
    # a cell that completes nothing (its traffic file has no
    # `completions` block) has no sweep to put off. Of the cells the
    # benchmark had when the metric was registered (its first six, PR
    # 45's): a cell a later PR adds takes the entries its own PR names
    # (an accepted layer file is no later PR's to edit)
    cells = []
    for w in bench["workloads"][:6]:
        with open(os.path.join(REPO, "benchmark", "workloads",
                               w["name"] + ".json")) as f:
            if "completions" in json.load(f):
                cells.append(w["name"])
    assert len(cells) == 5
    assert sorted(entry["workloads"]) == sorted(cells)
    assert entry["workloads"] == spec["workloads"] == placed["workloads"]
    assert spec["select"] == ["gc_sweeps_deferred"]
    own = ("name", "select", "what")  # all else is its neighbour's
    assert {k: v for k, v in spec.items() if k not in own} == {
        k: v for k, v in placed.items() if k not in own}
    assert (spec["source_kind"], entry["source"]) == (
        "flight_count", "program_counter")
    assert spec["unit"] == entry["unit"] == "1"
    assert entry["moves"] == spec["moves"] == "pods_bound_per_s"
    sweeps = next(m for m in bench["per_layer"]
                  if m["name"] == "gc_sweeps_per_cycle")
    assert entry["layer"] == spec["layer"] == sweeps["layer"]
    with open(os.path.join(PACKAGE, "core", "scheduler.py")) as f:
        assert 'rec.counts["%s"]' % spec["select"][0] in f.read()


@pytest.fixture()
def measured_shape_run(svc):
    """Throw-away cycles of the shape `[freeze]` below measures, run
    before the policy is installed (its departures are not the
    policy's): a process's first such cycle compiles its pad regime
    and its second, the first to encode by delta, the carry's update
    program. Either allocates enough for the interpreter to start full
    passes of its own, which a measured cycle would stamp `auto_full`
    beside the one pass it expects."""
    add_pods(svc, 2 * PER_ROUND)
    cycle_and_confirm(svc, leak=None)
    for _ in range(2):
        add_pods(svc)
        delete_pods(svc, [b.pod_uid for b in svc.Cycle(
            pb.CycleRequest(), None).bindings])
    delete_pods(svc, [
        p.uid for p, _n in svc.scheduler.cache.existing_pods()])


@pytest.mark.parametrize("kind", ["freeze", "sweep", "auto_full"])
def test_each_operation_stamps_one_pass_when_armed(
        svc, measured_shape_run, policy, armed, kind):
    add_pods(svc, 2 * PER_ROUND)
    uids = cycle_and_confirm(svc)
    before = len(passes(armed))
    if kind == "freeze":
        add_pods(svc)
        svc.Cycle(pb.CycleRequest(), None)
    elif kind == "sweep":
        delete_pods(svc, uids)
        svc.Cycle(pb.CycleRequest(), None)
        assert policy.sweeps == 1
        # the departures it covered, the rate it was placed by, and what
        # it found, which is what the next one is placed by
        (sweep,) = passes(armed)[before:]
        assert (sweep.attrs["left"], sweep.attrs["q"]) == (len(uids), 1.0)
        assert policy.q == min(1.0, max(
            sweep.attrs["collected"] / len(uids), collector.SWEEP_SHARE))
    else:
        gc.collect()
    (span,) = passes(armed)[before:]
    assert span.parent == "" and span.t1 >= span.t0
    placed_by = {"left", "q"} if kind == "sweep" else set()
    assert set(span.attrs) == {
        "kind", "generation", "collected", "frozen"} | placed_by
    assert span.attrs["kind"] == kind
    assert span.attrs["generation"] == (1 if kind == "freeze" else 2)
    assert span.attrs["collected"] >= 0
    assert span.attrs["frozen"] > 0
    if kind != "freeze":
        # counted after it, not carried from before (reference counting
        # has freed a few frozen objects since)
        assert span.attrs["frozen"] == pytest.approx(
            gc.get_freeze_count(), abs=200)


def test_unarmed_nothing_is_stamped_and_the_policy_still_runs(svc, policy):
    rec = _spans.arm(rate=1.0)
    _spans.disarm()
    before = rec.count
    frozen = gc.get_freeze_count()
    delete_pods(svc, churn(svc, 3, deletes=False))
    svc.Cycle(pb.CycleRequest(), None)
    gc.collect()
    assert rec.count == before
    assert policy.sweeps == 1 and gc.get_freeze_count() > frozen


def test_young_passes_reach_the_two_counters(svc, policy):
    m = svc.scheduler.metrics
    at = m.gc_young_passes._value.get()
    keep = [[i] for i in range(5 * collector.THRESHOLDS[0])]
    assert policy.young_passes >= 5 and policy.young_seconds > 0.0
    assert m.gc_young_passes._value.get() == at  # carried at a cycle's end
    svc.Cycle(pb.CycleRequest(), None)
    assert m.gc_young_passes._value.get() == at + policy.young_passes
    text = m.expose().decode()
    assert "scheduler_gc_young_passes_total" in text
    assert "scheduler_gc_young_pass_seconds_total" in text
    del keep


# ---- a small cluster: sweeps that find nothing grow rare -------------------

@pytest.mark.parametrize("per_cycle, swept_after, put_off_after", [
    (1000, [1, 5, 18], 16),  # the floor is met by every cycle
    (996, [2, 6, 19], 13),   # four short of it: by every other
])
def test_a_small_cluster_is_swept_after_most_cycles(
        svc, per_cycle, swept_after, put_off_after):
    """scheduler_perf's SchedulingBasic 500Nodes under the policy as
    shipped (a floor of 1,000 departures, a quarter of what is
    resident): 500 nodes and 2,500 pods stand at a cycle's end and
    `per_cycle` pods have finished since the one before, none of them
    part of a reference cycle. 40% of the set is over the share after
    every cycle, so under the presumption that every departure leaks
    the floor alone decided and a sweep followed most cycles. The first
    sweep still falls there; it finds nothing, so the second waits for
    over four times the 750 departures that a quarter of the set is,
    and the third for over sixteen times. The flight records carry the
    policy's running totals as `gc_sweeps` and `gc_sweeps_deferred` (a
    cycle's record is committed before the pass that follows it),
    `scheduler_gc_sweeps_total` and `scheduler_gc_sweeps_deferred_total`
    keep step, and the second counts every cycle after which the
    presumption would have swept."""
    assert (collector.SWEEP_MIN_DEPARTURES, collector.SWEEP_SHARE) == (
        1_000, 0.25)
    departed = [0]
    metrics = svc.scheduler.metrics
    at = metrics.gc_sweeps._value.get()
    put_off_at = metrics.gc_sweeps_deferred._value.get()
    gc.collect()  # what earlier tests left is not this policy's to find
    with installed(svc, lambda: (500 + 2_500, departed[0])) as pol:
        in_record, after, put_off, since = [], [], 0, 0
        for cycle in range(1, swept_after[-1] + 2):
            departed[0] += per_cycle
            add_pods(svc, 2)
            svc.Cycle(pb.CycleRequest(), None)  # no context: swept at once
            counts = svc.scheduler.flight.last_record().counts
            in_record.append(
                (counts["gc_sweeps"], counts["gc_sweeps_deferred"]))
            since = 0 if cycle in swept_after else since + per_cycle
            put_off += since >= 1_000
            after.append((pol.sweeps, pol.deferred))
        assert [n for n, _d in after] == [
            sum(c <= cycle for c in swept_after)
            for cycle in range(1, len(after) + 1)]
        assert pol.deferred == put_off == put_off_after
        assert pol.q == 0.25 ** len(swept_after)
        assert in_record == [(0, 0)] + after[:-1]
        assert metrics.gc_sweeps._value.get() == at + len(swept_after)
        assert metrics.gc_sweeps_deferred._value.get() == put_off_at + put_off
        text = metrics.expose()
        assert (b"scheduler_gc_sweeps_total %.1f" % (
            at + len(swept_after))) in text
        assert (b"scheduler_gc_sweeps_deferred_total %.1f" % (
            put_off_at + put_off)) in text
    # and with no policy the next record keeps no such counts
    add_pods(svc, 1)
    svc.Cycle(pb.CycleRequest(), None)
    counts = svc.scheduler.flight.last_record().counts
    assert "gc_sweeps" not in counts and "gc_sweeps_deferred" not in counts


# ---- the rate: what any history of sweeps may do to it --------------------

class Scripted(collector.CollectorPolicy):
    """The policy over a census the test moves, whose sweeps find what
    the test says and walk nothing."""

    def __init__(self, svc, finds) -> None:
        self.at = [3_000, 0]
        super().__init__(lambda: tuple(self.at), svc.scheduler.metrics)
        self.finds, self.seen = iter(finds), []

    def _place(self, kind, resident, count, **attrs):
        if kind != "sweep":
            return 0
        self.seen.append(attrs)
        return next(self.finds)


@pytest.mark.parametrize("finds", [
    [0] * 6,                             # nothing, ever: a quarter a sweep
    [0, 0, 0, 10 ** 9, 0, 0],            # a leak of everything, once
    [300, 290, 310, 0, 5, 10 ** 6, 1],   # a small one that comes and goes
], ids=["fruitless", "whole", "partial"])
def test_the_rate_falls_by_a_quarter_at_most_and_never_passes_one(svc, finds):
    """Whatever sweeps find: `q` is what the last one found a departure,
    never under a quarter of the rate before it and never over 1; a
    sweep is placed only where the presumption would place one, so on
    any history there are no more of them; and one that finds as many
    objects as pods have left puts the next where the presumption
    would."""
    pol = Scripted(svc, finds)
    pol.install()
    try:
        presumed = since = cycles = 0
        rates = [pol.q]
        while len(pol.seen) < len(finds):
            pol.at[1] += 1_000  # over the floor, and over the share
            since += 1_000
            cycles += 1
            if since > 0.25 * pol.at[0]:
                presumed, since = presumed + 1, 0
            swept = pol.sweeps
            pol.cycle_done()
            if pol.sweeps > swept:
                left, q = pol.seen[-1]["left"], pol.seen[-1]["q"]
                assert q == rates[-1] and q * left > 0.25 * pol.at[0]
                assert q * (left - 1_000) <= 0.25 * pol.at[0]  # not before
                found = finds[len(pol.seen) - 1]
                assert pol.q == min(1.0, max(found / left, q * 0.25))
                rates.append(pol.q)
            assert pol.sweeps <= presumed
            assert pol.sweeps + pol.deferred == cycles
    finally:
        pol.uninstall()
    assert all(0.0 < b <= 1.0 and b >= a * 0.25
               for a, b in zip(rates, rates[1:])), rates
    for found, this, then in zip(finds, pol.seen, pol.seen[1:]):
        if found >= this["left"]:  # everything: back to the presumption
            assert (then["q"], then["left"]) == (1.0, 1_000)


# ---- placement: after the response, not before ----------------------------

class Context:
    """The parts of a grpc.ServicerContext `Cycle` uses."""

    def __init__(self, open_: bool = True) -> None:
        self.open, self.callbacks = open_, []

    def invocation_metadata(self):
        return ()

    def set_trailing_metadata(self, metadata) -> None:
        self.trailing = metadata

    def add_callback(self, fn) -> bool:
        if self.open:
            self.callbacks.append(fn)
        return self.open


def test_cycle_hands_the_pass_to_the_call_s_end(svc, policy, armed):
    add_pods(svc)
    before = len(passes(armed))
    ctx = Context()
    svc.Cycle(pb.CycleRequest(), ctx)
    assert ctx.callbacks == [policy.cycle_done]
    assert len(passes(armed)) == before  # not inside the handler
    ctx.callbacks[0]()
    assert len(passes(armed)) == before + 1
    # a call that has already ended runs no callback: at once, then
    add_pods(svc)
    svc.Cycle(pb.CycleRequest(), Context(open_=False))
    assert len(passes(armed)) == before + 2


def test_over_grpc_the_pass_begins_after_the_rpc_has_ended(
        svc, policy, armed):
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
    add_to_server(svc, server)
    port = server.add_insecure_port("127.0.0.1:0")
    server.start()
    try:
        client = SchedulerClient(f"127.0.0.1:{port}")
        for _ in range(3):
            add_pods(svc)
            assert len(client.cycle().bindings) == PER_ROUND
        client.close()
    finally:
        server.stop(grace=None).wait(timeout=10)
    spans = armed.snapshot()
    cycles = [s for s in spans if s.name == "rpc.cycle"]
    placed = passes(armed)  # install's came before the ring was armed
    assert len(cycles) == 3 and len(placed) == 3
    assert policy.sweeps == 0
    for c, p in zip(cycles, placed):
        assert p.attrs["kind"] == "freeze" and p.t0 >= c.t1

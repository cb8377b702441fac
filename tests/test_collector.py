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


def cycle_and_confirm(svc) -> list:
    """One `Cycle`, every binding confirmed by reference; each bound pod
    is then made part of a reference cycle, so that only a collector
    pass can free it once it is deleted."""
    resp = svc.Cycle(pb.CycleRequest(), None)
    req = pb.UpdateRequest()
    for b in resp.bindings:
        req.bind_confirms.append(
            pb.BindConfirm(pod_uid=b.pod_uid, node_name=b.node_name))
    assert svc.Update(req, None).bind_confirms_applied == len(resp.bindings)
    for pod, _node in svc.scheduler.cache.existing_pods():
        pod.metadata.annotations["self"] = pod
    return [b.pod_uid for b in resp.bindings]


def delete_pods(svc, uids) -> None:
    req = pb.UpdateRequest()
    req.pod_deletes.extend(uids)
    svc.Update(req, None)


def churn(svc, rounds: int, deletes: bool, after_cycle=None,
          per_round: int = PER_ROUND) -> list:
    """`rounds` of: add `per_round` pods, bind them and, from the second
    round on, delete the oldest `per_round` bound."""
    bound: list = []
    for r in range(rounds):
        add_pods(svc, per_round)
        bound += cycle_and_confirm(svc)
        if after_cycle is not None:
            after_cycle(r)
        if deletes and r:
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

def test_churn_ends_within_a_margin_of_the_default_collector(
        svc, request, armed):
    """Pods that are reference cycles, added, bound and deleted: with
    the policy on, what is alive at the end (tracked or frozen) is what
    the default collector leaves, plus at most the departures one sweep
    may wait for; and the sweeps ran when the rule says, not before."""
    churn(svc, 2, deletes=True)  # whatever the first cycles build once
    gc.collect()
    base = objects_alive()
    delete_pods(svc, churn(svc, ROUNDS, deletes=True))
    gc.collect()
    default_alive = objects_alive() - base

    pol = request.getfixturevalue("policy")
    base = objects_alive()
    expected, swept_at, log = 0, 0, []

    def after_cycle(r):
        # the rule, from the counts the test itself makes: resident =
        # nodes + bound (a cycle binds all that is pending), departures
        # = every pod deleted so far
        nonlocal expected, swept_at
        resident = NODES + PER_ROUND * (1 if r == 0 else 2)
        left = PER_ROUND * max(r - 1, 0) - swept_at
        if left >= collector.SWEEP_MIN_DEPARTURES and left > (
                collector.SWEEP_SHARE * resident):
            expected += 1
            swept_at += left
        log.append((r, pol.sweeps, expected))

    delete_pods(svc, churn(svc, ROUNDS, deletes=True, after_cycle=after_cycle))
    assert all(got == want for _r, got, want in log), log
    assert 2 <= pol.sweeps < ROUNDS - 1  # it ran, and not every round
    assert [s.attrs["kind"] for s in passes(armed)].count("sweep") == pol.sweeps
    # what may still wait for a sweep: the pods deleted since the last
    # one (under three rounds' worth here), ~40 objects each, and the
    # spans the armed ring holds now
    policy_alive = objects_alive() - base
    margin = 3 * PER_ROUND * 40 + 12 * len(armed.snapshot())
    assert policy_alive <= default_alive + margin, (
        policy_alive, default_alive, margin)
    assert gc.get_freeze_count() > 0  # and the rest is out of sight


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


@pytest.mark.parametrize("kind", ["freeze", "sweep", "auto_full"])
def test_each_operation_stamps_one_pass_when_armed(svc, policy, armed, kind):
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
    else:
        gc.collect()
    (span,) = passes(armed)[before:]
    assert span.parent == "" and span.t1 >= span.t0
    assert set(span.attrs) == {"kind", "generation", "collected", "frozen"}
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


# ---- a small cluster: a sweep after most cycles ---------------------------

@pytest.mark.parametrize("per_cycle, swept_after", [
    (1000, [1, 2, 3, 4, 5, 6]),  # the floor is met by every cycle
    (996, [0, 1, 1, 2, 2, 3]),   # four short of it: every other cycle
])
def test_a_small_cluster_is_swept_after_most_cycles(
        svc, per_cycle, swept_after):
    """scheduler_perf's SchedulingBasic 500Nodes under the policy as
    shipped (a floor of 1,000 departures, a quarter of what is
    resident): 500 nodes and 2,500 pods stand at a cycle's end and
    `per_cycle` pods have finished since the one before. 40% of the set
    is over the share after every cycle, so the floor alone decides. The
    flight records carry the policy's running total as `gc_sweeps` (a
    cycle's record is committed before the sweep that follows it), and
    `scheduler_gc_sweeps_total` keeps step."""
    assert (collector.SWEEP_MIN_DEPARTURES, collector.SWEEP_SHARE) == (
        1_000, 0.25)
    departed = [0]
    metrics = svc.scheduler.metrics
    at = metrics.gc_sweeps._value.get()
    with installed(svc, lambda: (500 + 2_500, departed[0])) as pol:
        in_record, after = [], []
        for _ in swept_after:
            departed[0] += per_cycle
            add_pods(svc, 2)
            svc.Cycle(pb.CycleRequest(), None)  # no context: swept at once
            in_record.append(
                svc.scheduler.flight.last_record().counts["gc_sweeps"])
            after.append(pol.sweeps)
        assert after == swept_after
        assert in_record == [0] + swept_after[:-1]
        assert metrics.gc_sweeps._value.get() == at + swept_after[-1]
        assert (b"scheduler_gc_sweeps_total %.1f" % (at + swept_after[-1])
                ) in metrics.expose()
    # and with no policy the next record keeps no such count
    add_pods(svc, 1)
    svc.Cycle(pb.CycleRequest(), None)
    assert "gc_sweeps" not in svc.scheduler.flight.last_record().counts


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

"""gRPC shim tests (SURVEY.md §7 step 7): end-to-end over a real grpc
channel on localhost, plus the fault-tolerance contract from §5.3 —
shim restart recovers via agent re-list, bind failures forget+backoff,
and no pod is ever double-bound."""

import copy
from concurrent import futures

import grpc
import pytest

from k8s_scheduler_tpu.config import SchedulerConfiguration
from k8s_scheduler_tpu.core.scheduler import Scheduler
from k8s_scheduler_tpu.internal.cache import SchedulerCache
from k8s_scheduler_tpu.internal.queue import SchedulingQueue
from k8s_scheduler_tpu.models import MakeNode, MakePod
from k8s_scheduler_tpu.models.api import PodGroup
from k8s_scheduler_tpu.service import (
    SchedulerAgent,
    SchedulerClient,
    serve,
)
from k8s_scheduler_tpu.service import convert
from k8s_scheduler_tpu.service import scheduler_pb2 as pb
from k8s_scheduler_tpu.service.server import SchedulerService, add_to_server
from k8s_scheduler_tpu.state import DurableState
from k8s_scheduler_tpu.state.journal import BATCH_OP, iter_batch, replay_dir


# ---- conversion round-trips ------------------------------------------------


def test_pod_proto_roundtrip_preserves_scheduling_fields():
    pod = (
        MakePod("web-1", namespace="prod")
        .req({"cpu": "500m", "memory": "1Gi"})
        .labels({"app": "web"})
        .priority(7)
        .node_selector({"disk": "ssd"})
        .toleration("dedicated", "gpu", "NoSchedule")
        .pod_affinity("topology.kubernetes.io/zone", {"app": "cache"})
        .pod_affinity("kubernetes.io/hostname", {"app": "web"}, anti=True)
        .spread(2, "topology.kubernetes.io/zone", {"app": "web"})
        .host_port(8080)
        .group("gang-a")
        .obj()
    )
    back = convert.pod_from(convert.pod_to(pod))
    assert back.uid == pod.uid
    assert back.resource_requests() == pod.resource_requests()
    assert back.spec.priority == 7
    assert back.spec.node_selector == {"disk": "ssd"}
    assert back.spec.tolerations == pod.spec.tolerations
    assert back.spec.affinity == pod.spec.affinity
    assert (
        back.spec.topology_spread_constraints
        == pod.spec.topology_spread_constraints
    )
    assert back.host_ports() == pod.host_ports()
    assert back.spec.pod_group == "gang-a"


def test_node_proto_roundtrip():
    node = (
        MakeNode("n1")
        .capacity({"cpu": "16", "memory": "32Gi"})
        .labels({"topology.kubernetes.io/zone": "zone-a"})
        .taint("dedicated", "gpu")
        .obj()
    )
    back = convert.node_from(convert.node_to(node))
    assert back.name == "n1"
    assert back.status.allocatable == node.status.allocatable
    assert back.spec.taints == node.spec.taints
    assert back.metadata.labels == node.metadata.labels


# ---- end-to-end over localhost ---------------------------------------------


class Applier:
    """Fake cluster-side bind applier."""

    def __init__(self):
        self.bound = {}
        self.fail_uids = set()
        self.evicted = []

    def bind(self, uid, name, namespace, node_name):
        if uid in self.fail_uids:
            raise RuntimeError("binding POST failed")
        assert uid not in self.bound, f"double bind of {uid}"
        self.bound[uid] = node_name

    def evict(self, uid, node_name):
        self.evicted.append(uid)


@pytest.fixture()
def shim():
    server, service, port = serve("127.0.0.1:0")
    client = SchedulerClient(f"127.0.0.1:{port}")
    yield server, service, client
    client.close()
    server.stop(grace=None)


def test_service_schedules_over_the_wire(shim):
    _, _, client = shim
    applier = Applier()
    agent = SchedulerAgent(client, applier.bind, applier.evict)
    for i in range(3):
        agent.upsert_node(MakeNode(f"n{i}").capacity({"cpu": "8"}).obj())
    for i in range(6):
        agent.upsert_pod(MakePod(f"p{i}").req({"cpu": "1"}).obj())
    resp = agent.run_cycle()
    assert resp.stats.scheduled == 6
    assert len(applier.bound) == 6
    assert set(applier.bound.values()) <= {"n0", "n1", "n2"}
    # Scheduled events ride the response, drained per cycle
    assert sum(1 for ev in resp.events if ev.reason == "Scheduled") == 6
    # second cycle: nothing pending
    resp2 = agent.run_cycle()
    assert resp2.stats.attempted == 0
    assert len(resp2.events) == 0
    assert client.health().ok
    assert b"scheduler_schedule_attempts_total" in client.metrics_text()


def test_volume_binding_over_the_wire(shim):
    from k8s_scheduler_tpu.models.api import (
        VOLUME_BINDING_WAIT,
        NodeSelectorRequirement,
        NodeSelectorTerm,
        PersistentVolume,
        PersistentVolumeClaim,
        StorageClass,
    )

    _, _, client = shim
    applier = Applier()
    agent = SchedulerAgent(client, applier.bind, applier.evict)
    zone = "topology.kubernetes.io/zone"
    for i in range(4):
        agent.upsert_node(
            MakeNode(f"n{i}")
            .capacity({"cpu": "8"})
            .labels({zone: f"z{i % 2}"})
            .obj()
        )
    agent.upsert_storage_class(
        StorageClass("local", VOLUME_BINDING_WAIT, provisioner=False)
    )
    agent.upsert_pv(
        PersistentVolume(
            "pv-z1", capacity=10.0, storage_class="local",
            node_affinity=(
                NodeSelectorTerm(
                    (NodeSelectorRequirement(zone, "In", ("z1",)),)
                ),
            ),
        )
    )
    agent.upsert_pvc(
        PersistentVolumeClaim("data", storage_class="local", request=1.0)
    )
    agent.upsert_pod(MakePod("db").req({"cpu": "1"}).volume("data").obj())
    resp = agent.run_cycle()
    assert resp.stats.scheduled == 1
    # the only candidate PV is zone-restricted to z1 (nodes n1, n3)
    assert list(applier.bound.values())[0] in ("n1", "n3")


def test_serve_raises_on_unbindable_address():
    server, _, port = serve("127.0.0.1:0")
    try:
        # grpc raises RuntimeError itself when SO_REUSEPORT is off; the
        # serve() OSError is the belt-and-braces path for versions that
        # signal failure by returning port 0 instead
        with pytest.raises((OSError, RuntimeError)):
            serve(f"127.0.0.1:{port}")  # already taken
    finally:
        server.stop(grace=None)


def test_bind_failure_forgets_and_retries(shim):
    _, _, client = shim
    applier = Applier()
    agent = SchedulerAgent(client, applier.bind, applier.evict)
    agent.upsert_node(MakeNode("n0").capacity({"cpu": "8"}).obj())
    pod = MakePod("p").req({"cpu": "1"}).obj()
    agent.upsert_pod(pod)
    applier.fail_uids.add(pod.uid)
    resp = agent.run_cycle()
    assert len(resp.bindings) == 1 and not applier.bound
    # the failure report goes out with the next cycle; backoff applies, so
    # drive cycles until the pod comes back (initial backoff 1s is too long
    # for a test -> flush by event instead: a node update unsticks nothing
    # in backoff; wait out via repeated cycles is flaky. Use the queue
    # directly through the service's scheduler for determinism.)
    applier.fail_uids.clear()
    service = shim[1]
    agent.run_cycle()  # reports the failure; pod now in backoff
    assert not service.scheduler.cache.is_assumed(pod.uid)
    # force the backoff to expire deterministically
    for e in service.scheduler.queue._backoff.values():
        e.backoff_expiry = 0.0
    resp = agent.run_cycle()
    assert resp.stats.scheduled == 1
    assert applier.bound[pod.uid] == "n0"


def test_gang_scheduling_over_the_wire(shim):
    _, _, client = shim
    applier = Applier()
    agent = SchedulerAgent(client, applier.bind, applier.evict)
    agent.upsert_node(MakeNode("n0").capacity({"cpu": "4", "pods": "110"}).obj())
    agent.add_pod_group(PodGroup("gang", 3))
    for i in range(3):
        agent.upsert_pod(
            MakePod(f"g{i}").req({"cpu": "2"}).group("gang").obj()
        )
    resp = agent.run_cycle()
    # only 2 of 3 fit -> all-or-nothing unwind, nothing binds
    assert resp.stats.scheduled == 0
    assert resp.stats.gang_dropped == 2
    assert not applier.bound


def test_batched_updates_coalesce_into_one_rpc(shim):
    _, service, client = shim
    applier = Applier()
    agent = SchedulerAgent(client, applier.bind, applier.evict)
    calls = {"n": 0}
    orig = client.update

    def counting_update(req, timeout=10.0):
        calls["n"] += 1
        return orig(req, timeout=timeout)

    client.update = counting_update
    with agent.batched():
        for i in range(4):
            agent.upsert_node(MakeNode(f"n{i}").capacity({"cpu": "8"}).obj())
        for i in range(20):
            agent.upsert_pod(MakePod(f"p{i}").req({"cpu": "1"}).obj())
    assert calls["n"] == 1  # 24 objects, one RPC
    resp = agent.run_cycle()
    assert resp.stats.scheduled == 20


def test_shim_restart_recovers_without_double_bind(shim):
    server, _, client = shim
    applier = Applier()
    agent = SchedulerAgent(client, applier.bind, applier.evict)
    agent.upsert_node(MakeNode("n0").capacity({"cpu": "8"}).obj())
    agent.upsert_pod(MakePod("a").req({"cpu": "1"}).obj())
    resp = agent.run_cycle()
    assert resp.stats.scheduled == 1 and len(applier.bound) == 1

    # kill the shim mid-flight and bring up a fresh one (new state)
    server.stop(grace=None)
    new_server, new_service, new_port = serve("127.0.0.1:0")
    try:
        agent.client = SchedulerClient(f"127.0.0.1:{new_port}")
        # agent notices the restart on the next call and re-lists; the
        # bound pod is replayed WITH its binding, the new pod without
        agent.upsert_pod(MakePod("b").req({"cpu": "1"}).obj())
        resp = agent.run_cycle()
        # restart must not re-schedule pod a (it is bound state, not
        # pending) — only b binds, and the applier asserts no double-bind
        assert resp.stats.scheduled == 1
        assert set(applier.bound) == {"default/a", "default/b"}
        assert new_service.scheduler.cache.counts()["bound"] >= 1
    finally:
        agent.client.close()
        new_server.stop(grace=None)


def test_preemption_over_the_wire(shim):
    _, _, client = shim
    applier = Applier()
    agent = SchedulerAgent(client, applier.bind, applier.evict)
    agent.upsert_node(MakeNode("n0").capacity({"cpu": "2", "pods": "110"}).obj())
    victim = MakePod("victim").req({"cpu": "2"}).priority(1).obj()
    agent.upsert_pod(victim, bound_node="n0")
    urgent = MakePod("urgent").req({"cpu": "2"}).priority(10).obj()
    agent.upsert_pod(urgent)
    resp = agent.run_cycle()
    assert resp.stats.scheduled == 0
    assert [n.pod_uid for n in resp.nominations] == [urgent.uid]
    assert [e.pod_uid for e in resp.evictions] == [victim.uid]
    assert applier.evicted == [victim.uid]


# ---- bind confirmation by reference ----------------------------------------


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class Shim:
    """A served scheduler on an injected clock, journaling to `path`."""

    def __init__(self, path):
        self.clock = Clock()
        self.path = str(path)
        self.state = DurableState(
            self.path, snapshot_interval_seconds=0, now=self.clock
        )
        self.service = SchedulerService(scheduler=Scheduler(
            config=SchedulerConfiguration(), now=self.clock,
            state=self.state,
        ))
        self.server = grpc.server(futures.ThreadPoolExecutor(max_workers=2))
        add_to_server(self.service, self.server)
        port = self.server.add_insecure_port("127.0.0.1:0")
        self.server.start()
        self.client = SchedulerClient(f"127.0.0.1:{port}")
        self.applier = Applier()
        self.agent = SchedulerAgent(
            self.client, self.applier.bind, self.applier.evict
        )
        # every Update the agent makes, as (request, response)
        self.updates = []
        inner = self.client.update

        def recording(request, timeout=10.0):
            resp = inner(request, timeout=timeout)
            self.updates.append((request, resp))
            return resp

        self.client.update = recording

    @property
    def cache(self):
        return self.service.scheduler.cache

    @property
    def queue(self):
        return self.service.scheduler.queue

    def journal_ops(self):
        """Every logical op journaled so far, batches expanded."""
        self.state.journal.flush()
        out = []
        for op, t, data in replay_dir(self.path):
            out.extend(iter_batch(data) if op == BATCH_OP else [(op, t, data)])
        return out

    def restored_bound(self):
        """uid -> node of the bound set a restore of the journal gives."""
        self.state.journal.flush()
        q, c = SchedulingQueue(now=self.clock), SchedulerCache(now=self.clock)
        st = DurableState(
            self.path, snapshot_interval_seconds=0, now=self.clock
        )
        st.restore_into(q, c)
        st.journal.close()
        return {d["pod"]["m"]["u"]: d["node"] for d in c.dump_state()["bound"]}

    def close(self):
        self.client.close()
        self.server.stop(grace=None)
        self.state.journal.close()


@pytest.fixture()
def shims(tmp_path):
    made = []

    def make(name="a"):
        made.append(Shim(tmp_path / name))
        return made[-1]

    yield make
    for s in made:
        s.close()


def confirm_loop(shim, pods, copies: bool):
    """The agent loop as the benchmark drives it: a raw `Cycle`, then
    every binding confirmed through `upsert_pod` and every eviction
    deleted, in one batch. `copies` hands `upsert_pod` an equal copy of
    each pod, which is what forces the full `pod_updates` path."""
    resp = shim.client.cycle()
    with shim.agent.batched():
        for b in resp.bindings:
            pod = pods[b.pod_uid]
            shim.agent.upsert_pod(
                copy.deepcopy(pod) if copies else pod, bound_node=b.node_name
            )
        for ev in resp.evictions:
            shim.agent.delete_pod(ev.pod_uid)
    return resp


def waves():
    """Three cycles' arrivals on three small nodes: plain pods, one pod
    more with anti-affinity than there are nodes (an update event moves
    it out of the unschedulable set), a pod too large to ever fit, and a
    high-priority pod that preempts."""
    host = "kubernetes.io/hostname"
    return [
        [MakePod(f"a{i}").req({"cpu": "1"}).labels({"app": "a"}).obj()
         for i in range(5)]
        + [MakePod("huge").req({"cpu": "64"}).obj()]
        + [MakePod(f"anti{i}").req({"cpu": "1"}).labels({"app": "x"})
           .pod_affinity(host, {"app": "x"}, anti=True).obj()
           for i in range(4)],
        [MakePod(f"b{i}").req({"cpu": "2"}).priority(1).obj()
         for i in range(6)],
        [MakePod("urgent").req({"cpu": "6"}).priority(100).obj()],
    ]


@pytest.mark.parametrize("driver", ["upsert_pod", "run_cycle"])
def test_confirm_by_reference_equals_full_pod_updates(shims, driver):
    """The same pods and nodes through two servers, one agent confirming
    its binds by reference and one forced onto full `pod_updates`: the
    same bindings cycle for cycle, the same cache and queue at the end,
    the same bound set after a restore; only the journal differs."""
    ref, full = shims("ref"), shims("full")
    pods, streams = {}, {ref: [], full: []}
    # run_cycle hands evictions to the applier: it deletes, as the loop does
    ref.agent.evict_applier = lambda uid, node: ref.agent.delete_pod(uid)
    for s in (ref, full):
        with s.agent.batched():
            for i in range(3):
                s.agent.upsert_node(
                    MakeNode(f"n{i}").capacity({"cpu": "8"}).obj()
                )
    n_setup = {s: len(s.journal_ops()) for s in (ref, full)}
    for wave in waves() + [[], []]:
        pods.update((p.uid, p) for p in wave)
        for s in (ref, full):
            with s.agent.batched():
                for p in wave:
                    s.agent.upsert_pod(p)
            if s is ref and driver == "run_cycle":
                resp = s.agent.run_cycle()
            else:
                resp = confirm_loop(s, pods, copies=s is full)
            streams[s].append((
                [(b.pod_uid, b.node_name) for b in resp.bindings],
                [(e.pod_uid, e.node_name) for e in resp.evictions],
            ))
            s.clock.t += 5.0  # past every backoff: the moved pods retry
    assert streams[ref] == streams[full]
    bound = {u: n for binds, _ in streams[ref] for u, n in binds}
    evicted = {u for _, evs in streams[ref] for u, _ in evs}
    assert len(bound) >= 15 and evicted and "default/huge" not in bound
    assert "q.move" in [op for op, _, _ in ref.journal_ops()]
    # the pod the server assumed carries the nomination the server put
    # on it when it preempted for it; the copy an agent sends back does
    # not, and nothing reads the field of a bound pod
    dumps = {s: s.cache.dump_state() for s in (ref, full)}
    noms = {s: {d["pod"]["m"]["u"]: d["pod"].pop("nom")
                for d in dumps[s]["bound"] if "nom" in d["pod"]}
            for s in (ref, full)}
    assert noms == {ref: {"default/urgent": bound["default/urgent"]},
                    full: {}}
    assert dumps[ref] == dumps[full]
    assert ref.cache.counts()["assumed"] == 0
    assert ref.queue.dump_state() == full.queue.dump_state()

    # one side sent references, the other whole pods, and both were told
    sent = {
        s: (sum(len(r.bind_confirms) for r, _ in s.updates),
            sum(1 for r, _ in s.updates for e in r.pod_updates
                if e.bound_node),
            sum(a.bind_confirms_applied for _, a in s.updates),
            sum(len(a.unconfirmed) for _, a in s.updates))
        for s in (ref, full)
    }
    assert sent[ref] == (len(bound), 0, len(bound), 0)
    assert sent[full] == (0, len(bound), 0, 0)

    # the journal records every bind of the window on both sides: as
    # c.confirm after the c.assume that holds the pod, or as c.add_pod
    ops = {s: [op for op, _, _ in s.journal_ops()[n_setup[s]:]]
           for s in (ref, full)}
    assert ops[ref].count("c.confirm") == len(bound)
    assert ops[ref].count("c.add_pod") == 0
    assert ops[full].count("c.add_pod") == len(bound)
    assert ops[full].count("c.confirm") == 0
    for s in (ref, full):
        assert ops[s].count("c.assume") == len(bound)
        assert ops[s].count("q.delete") == ops[ref].count("q.delete")
    live = {u: n for u, n in bound.items() if u not in evicted}
    assert ref.restored_bound() == full.restored_bound() == live


def spoil_unknown(shim, pod, node):
    # the server forgets the pod behind the agent's back
    shim.service.Update(pb.UpdateRequest(pod_deletes=[pod.uid]), None)
    return node


def spoil_expired(shim, pod, node):
    # the assumption outlives its TTL and the next cycle's sweep
    # requeues the pod, with backoff
    shim.clock.t += 31.0
    assert not shim.client.cycle().bindings
    assert not shim.cache.has_pod(pod.uid)
    assert shim.queue.pending_counts()["backoff"] == 1
    return node


def spoil_wrong_node(shim, pod, node):
    return "n1" if node == "n0" else "n0"  # where the agent bound it


def spoil_already_bound(shim, pod, node):
    # another informer stream delivered the bound pod first
    shim.service.Update(pb.UpdateRequest(pod_updates=[
        pb.PodEvent(pod=convert.pod_to(pod), bound_node=node)
    ]), None)
    return node


def spoil_old_server(shim, pod, node):
    # a server from before field 17 drops it and answers 0 and nothing
    inner = shim.client.update

    def old(request, timeout=10.0):
        known = pb.UpdateRequest()
        known.CopyFrom(request)
        known.ClearField("bind_confirms")
        resp = inner(known, timeout=timeout)
        assert not resp.bind_confirms_applied and not resp.unconfirmed
        return resp

    shim.client.update = old
    return node


@pytest.mark.parametrize("spoil", [
    spoil_unknown, spoil_expired, spoil_wrong_node, spoil_already_bound,
    spoil_old_server,
], ids=lambda f: f.__name__[6:])
def test_a_confirmation_the_server_cannot_apply_falls_back(shims, spoil):
    """No assumption on that node (or no server that knows the field):
    the uid comes back, or the counts do not add up, and the agent
    sends the whole pod before `batched()` returns: bound exactly
    once, nothing pending, and a re-list still carries whole pods."""
    shim = shims()
    for i in range(2):
        shim.agent.upsert_node(MakeNode(f"n{i}").capacity({"cpu": "8"}).obj())
    pod = MakePod("p").req({"cpu": "1"}).obj()
    shim.agent.upsert_pod(pod)
    (b,) = shim.client.cycle().bindings
    assert shim.cache.is_assumed(pod.uid)
    node = spoil(shim, pod, b.node_name)
    del shim.updates[:]
    with shim.agent.batched():
        shim.agent.upsert_pod(pod, bound_node=node)
    first, second = shim.updates
    assert [c.pod_uid for c in first[0].bind_confirms] in ([pod.uid], [])
    assert not first[0].pod_updates and not first[1].bind_confirms_applied
    if spoil is not spoil_old_server:
        assert list(first[1].unconfirmed) == [pod.uid]
    (ev,) = second[0].pod_updates
    assert (ev.pod.metadata.uid, ev.bound_node) == (pod.uid, node)
    assert not second[0].bind_confirms

    assert shim.cache.counts() == {"nodes": 2, "bound": 1, "assumed": 0}
    assert {d["pod"]["m"]["u"]: d["node"]
            for d in shim.cache.dump_state()["bound"]} == {pod.uid: node}
    assert sum(shim.queue.pending_counts().values()) == 0
    shim.clock.t += 60.0
    assert not shim.client.cycle().bindings  # and never bound again
    assert shim.restored_bound() == {pod.uid: node}

    del shim.updates[:]
    shim.agent.relist()
    ((req, _),) = shim.updates
    assert [(e.pod.metadata.uid, e.bound_node) for e in req.pod_adds] == [
        (pod.uid, node)]
    assert not req.bind_confirms and not req.pod_updates


def test_a_bound_pod_with_node_affinity_costs_no_full_encode():
    """The agent path with the rehearsal's pads: after the warm-up, every
    cycle binds a pod with a preferred node-affinity term (what the
    benchmark's score-fidelity probes carry, and what the native row
    writer does not cover) beside plain pods. The next cycle's fold
    builds that pod's row in Python: `full_encodes` stays flat in the
    flight records, `fold_fallback_pods` counts the pod, and the
    observer raises no `fold_miss`."""
    from k8s_scheduler_tpu import native

    if native.pod_rows_into is None:
        pytest.skip("native extension not built")
    server, service, port = serve(
        "127.0.0.1:0",
        config=SchedulerConfiguration(
            pad_existing=256, pad_pods_per_node=32, pad_hysteresis_pct=100
        ),
    )
    client = SchedulerClient(f"127.0.0.1:{port}")
    try:
        applier = Applier()
        agent = SchedulerAgent(client, applier.bind, applier.evict)
        for i in range(8):
            agent.upsert_node(
                MakeNode(f"n{i}").capacity({"cpu": "16"})
                .labels({"pool": f"pool-{i % 2}"}).obj()
            )

        def wave(c):
            with agent.batched():
                for j in range(3):
                    agent.upsert_pod(
                        MakePod(f"plain-{c}-{j}").req({"cpu": "100m"})
                        .labels({"app": "a"}).obj()
                    )
                agent.upsert_pod(
                    MakePod(f"probe-{c}").req({"cpu": "100m"})
                    .labels({"app": "a"})
                    .node_affinity_preferred(10, "pool", ["pool-1"]).obj()
                )
            return agent.run_cycle()

        warm, cycles = 3, 5
        for c in range(warm + cycles):
            assert wave(c).stats.scheduled == 4
        assert applier.bound[f"default/probe-{warm}"] in {
            "n1", "n3", "n5", "n7"}

        sched = service.scheduler
        recs = [r for r in sched.flight.snapshot() if r.counts.get("pods")]
        assert len(recs) == warm + cycles
        after = recs[warm:]
        # each of these cycles folds in the previous one's four binds,
        # one of them on the dict path
        assert {r.counts["full_encodes"] for r in after} == {
            recs[warm - 1].counts["full_encodes"]}
        assert [r.counts["fold_fallback_pods"] for r in after] == [1] * cycles
        assert (after[-1].counts["fold_hits"]
                - recs[warm - 1].counts["fold_hits"]) == cycles
        assert not [
            a for a in sched.observer.anomalies()
            if a["class"] == "fold_miss" and a["seq"] >= after[0].seq
        ]
        assert (b"scheduler_encode_fold_fallback_pods_total %.1f"
                % sum(r.counts["fold_fallback_pods"] for r in recs)
                ) in client.metrics_text()
    finally:
        client.close()
        server.stop(grace=None)


@pytest.mark.parametrize("depth, old_out, new_out, node_event", [
    (10, 6, 4, False),
    (3, 2, 1, False),
    (3, 2, 1, True),
], ids=["over_half", "under_half", "under_half_and_a_node_changes"])
def test_turnover_of_the_resident_set_is_told_from_a_fold_miss(
        depth, old_out, new_out, node_event):
    """scheduler_perf's SchedulingBasic 500Nodes in small, on the agent
    path: 15 pods resident at a cycle's start, `depth` binding in it,
    and before the next one `old_out` of the pods the encoder has seen
    finish (from anywhere in the list) with `new_out` of those just
    bound. With 10 of 25 turning over, more of the encoder's list
    changes than stays: the fold stands aside by its own rule, the
    records' `fold_declined` rises by one a cycle with `full_encodes`,
    and the observer raises NO `fold_miss`. With 3 of 18 the fold runs
    and both stay flat; and a full encode forced another way (a node
    replaced in every cycle) is a `fold_miss` as before."""
    from k8s_scheduler_tpu import native

    if native.pod_rows_into is None:
        pytest.skip("native extension not built")
    server, service, port = serve(
        "127.0.0.1:0",
        config=SchedulerConfiguration(
            pad_existing=256, pad_pods_per_node=32, pad_hysteresis_pct=100
        ),
    )
    client = SchedulerClient(f"127.0.0.1:{port}")
    try:
        applier = Applier()
        agent = SchedulerAgent(client, applier.bind, applier.evict)
        for i in range(8):
            agent.upsert_node(MakeNode(f"n{i}").capacity({"cpu": "16"}).obj())
        seen: list[str] = []  # uids the encoder has had in its list
        fresh: list[str] = []  # bound by the last cycle

        def wave(c, n, finish=(), touch_node=False):
            nonlocal seen, fresh
            pods = [
                MakePod(f"t-{c}-{j}").req({"cpu": "100m"})
                .labels({"app": "a"}).obj()
                for j in range(n)
            ]
            with agent.batched():
                for uid in finish:
                    agent.delete_pod(uid)
                if touch_node:
                    agent.upsert_node(
                        MakeNode(f"n{c % 8}").capacity({"cpu": "16"})
                        .labels({"touched": "yes"}).obj())
                for p in pods:
                    agent.upsert_pod(p)
            assert agent.run_cycle().stats.scheduled == n
            seen = [u for u in seen + fresh if u not in finish]
            fresh = [p.uid for p in pods]

        wave(0, 15)
        wave(1, depth)
        warm, cycles = 2, 5
        for c in range(warm, warm + cycles):
            wave(c, depth, seen[1:2 * old_out:2] + fresh[:new_out],
                 touch_node=node_event)

        sched = service.scheduler
        assert sched.cache.counts()["bound"] == 15 + depth
        recs = [r for r in sched.flight.snapshot() if r.counts.get("pods")]
        assert len(recs) == warm + cycles
        before, after = recs[warm - 1], recs[warm:]

        def rise(name):
            return [b.counts[name] - a.counts[name]
                    for a, b in zip([before] + after, after)]

        misses = [
            a["seq"] for a in sched.observer.anomalies()
            if a["class"] == "fold_miss"
        ]
        if node_event:
            assert rise("fold_declined") == [0] * cycles
            assert rise("full_encodes") == [1] * cycles
            assert misses == [r.seq for r in after]
        elif depth == 10:
            assert rise("fold_declined") == [1] * cycles
            assert rise("full_encodes") == [1] * cycles
            assert rise("fold_hits") == [0] * cycles
            assert misses == []
        else:
            assert rise("fold_declined") == [0] * cycles
            assert rise("full_encodes") == [0] * cycles
            assert rise("fold_removed_pods") == [old_out] * cycles
            assert misses == []
        # no collector policy in this process: the records keep no count
        # of its sweeps (None, never 0)
        assert "gc_sweeps" not in after[-1].counts
    finally:
        client.close()
        server.stop(grace=None)


def test_bound_pods_deleted_from_the_middle_cost_no_full_encode():
    """The agent path with the rehearsal's pads: after the warm-up, every
    `Update` deletes bound pods from the middle of the resident set
    (pods finish in no particular order) while the pods of the cycle
    before bind behind them. The fold compacts the rows over the holes:
    `full_encodes` stays flat in the flight records,
    `fold_removed_pods` rises by the pods deleted, and the observer
    raises no `fold_miss`."""
    from k8s_scheduler_tpu import native

    if native.pod_rows_into is None:
        pytest.skip("native extension not built")
    server, service, port = serve(
        "127.0.0.1:0",
        config=SchedulerConfiguration(
            pad_existing=256, pad_pods_per_node=32, pad_hysteresis_pct=100
        ),
    )
    client = SchedulerClient(f"127.0.0.1:{port}")
    try:
        applier = Applier()
        agent = SchedulerAgent(client, applier.bind, applier.evict)
        for i in range(8):
            agent.upsert_node(MakeNode(f"n{i}").capacity({"cpu": "16"}).obj())
        resident: list[str] = []  # uids, oldest first

        def wave(c, finish):
            pods = [
                MakePod(f"w-{c}-{j}").req({"cpu": "100m"})
                .labels({"app": "a"}).obj()
                for j in range(6)
            ]
            with agent.batched():
                for uid in finish:
                    agent.delete_pod(uid)
                    resident.remove(uid)
                for p in pods:
                    agent.upsert_pod(p)
            resp = agent.run_cycle()
            resident.extend(p.uid for p in pods)
            return resp

        warm, cycles = 3, 5
        for c in range(warm):
            assert wave(c, []).stats.scheduled == 6
        deleted = []
        for c in range(warm, warm + cycles):
            # two of the oldest third, one of the middle: never the tail
            finish = [resident[1], resident[3], resident[len(resident) // 2]]
            deleted.append(len(finish))
            assert wave(c, finish).stats.scheduled == 6

        sched = service.scheduler
        assert sched.cache.counts()["bound"] == len(resident)
        recs = [r for r in sched.flight.snapshot() if r.counts.get("pods")]
        assert len(recs) == warm + cycles
        before, after = recs[warm - 1], recs[warm:]
        assert {r.counts["full_encodes"] for r in after} == {
            before.counts["full_encodes"]}
        assert [
            b.counts["fold_removed_pods"] - a.counts["fold_removed_pods"]
            for a, b in zip([before] + after, after)
        ] == deleted
        assert (after[-1].counts["fold_hits"]
                - before.counts["fold_hits"]) == cycles
        assert not [
            a for a in sched.observer.anomalies()
            if a["class"] == "fold_miss" and a["seq"] >= after[0].seq
        ]
        assert (b"scheduler_encode_fold_removed_pods_total %.1f"
                % after[-1].counts["fold_removed_pods"]
                ) in client.metrics_text()
    finally:
        client.close()
        server.stop(grace=None)

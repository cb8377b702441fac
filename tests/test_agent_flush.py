"""`SchedulerAgent.batched()` flushes the open batch while the block
builds it (service/client.py): a chunk goes once the batch holds
FLUSH_FLOOR_BYTES and the chunk before it has been acknowledged, at
MAX_UPDATE_BYTES whatever is in flight, never two at once; the block
returns after the last acknowledgement and every chunk's response is
handled as a single Update's is. The servicer counts the requests
(`update_rpcs` in the flight records, `scheduler_update_rpcs_total`).

A real gRPC server on an ephemeral port with the thread pool `serve()`
gives it, durable state on, on an injected clock so that two servers'
dumps compare; the floor is cut so that a few hundred small pods are
many chunks."""

from __future__ import annotations

import threading
import time
from concurrent import futures

import grpc
import pytest

from k8s_scheduler_tpu.config import SchedulerConfiguration
from k8s_scheduler_tpu.core import spans as _spans
from k8s_scheduler_tpu.core.scheduler import Scheduler
from k8s_scheduler_tpu.internal.cache import SchedulerCache
from k8s_scheduler_tpu.internal.queue import SchedulingQueue
from k8s_scheduler_tpu.models import MakeNode, MakePod
from k8s_scheduler_tpu.service import client as client_mod
from k8s_scheduler_tpu.service import scheduler_pb2 as pb
from k8s_scheduler_tpu.service.client import SchedulerAgent, SchedulerClient
from k8s_scheduler_tpu.service.server import SchedulerService, add_to_server
from k8s_scheduler_tpu.state import DurableState

FLOOR = 2048  # ~15 of these pods; the shipped floor is ~500 whole pods
# what the test's loops wait per object, so that the server (a chunk of
# 15 pods in 1-3 ms) acknowledges well before the next floor is reached
# and the number of chunks does not hang on the machine's speed
PACE = 0.0005


class Served:
    """A served scheduler journaling to `path`, whose `Update` handler
    records what it was sent and how many of itself ran at once, and
    can be made slow or made to fail."""

    def __init__(self, path, agent_class=SchedulerAgent):
        self.path = str(path)
        self.clock = lambda: 100.0
        self.state = DurableState(
            self.path, snapshot_interval_seconds=0, now=self.clock
        )
        # no background compile of the adjacent pad regimes: its thread
        # would outlive a test, and the process if the test is the last
        self.service = SchedulerService(scheduler=Scheduler(
            config=SchedulerConfiguration(speculative_compile=False),
            now=self.clock, state=self.state,
        ))
        self.requests: list[pb.UpdateRequest] = []
        self.at_once = 0  # the most Update handlers running together
        self.delay = 0.0  # seconds each handler holds its request
        self.before = None  # called with (index, context) first
        self._running, self._lock = 0, threading.Lock()
        inner = self.service.Update

        def update(request, context):
            with self._lock:
                self._running += 1
                self.at_once = max(self.at_once, self._running)
                index = len(self.requests)
                self.requests.append(request)
            try:
                if self.before is not None:
                    self.before(index, context)
                time.sleep(self.delay)
                return inner(request, context)
            finally:
                with self._lock:
                    self._running -= 1

        self.service.Update = update
        # serve()'s pool: two Updates at once would run side by side
        self.server = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
        add_to_server(self.service, self.server)
        port = self.server.add_insecure_port("127.0.0.1:0")
        self.server.start()
        self.client = SchedulerClient(f"127.0.0.1:{port}")
        self.agent = agent_class(self.client, lambda *a: None)

    @property
    def scheduler(self):
        return self.service.scheduler

    def dumps(self):
        return (self.scheduler.cache.dump_state(),
                self.scheduler.queue.dump_state())

    def restored(self):
        """What a restore of the journal gives, as `dumps()`."""
        self.state.journal.flush()
        q, c = SchedulingQueue(now=self.clock), SchedulerCache(now=self.clock)
        st = DurableState(
            self.path, snapshot_interval_seconds=0, now=self.clock
        )
        st.restore_into(q, c)
        st.journal.close()
        return c.dump_state(), q.dump_state()

    def sizes(self):
        return [r.ByteSize() for r in self.requests]

    def close(self):
        self.client.close()
        self.server.stop(grace=None)
        self.state.journal.close()


@pytest.fixture()
def served(tmp_path):
    made = []

    def make(name="a", **kwargs):
        made.append(Served(tmp_path / name, **kwargs))
        return made[-1]

    yield make
    for s in made:
        s.close()


@pytest.fixture()
def small_floor(monkeypatch):
    monkeypatch.setattr(client_mod, "FLUSH_FLOOR_BYTES", FLOOR)


def nodes(n=8):
    return [MakeNode(f"n{i}").capacity({"cpu": "64", "pods": "110"}).obj()
            for i in range(n)]


def pods(n, tag="p"):
    return [MakePod(f"{tag}{i}").req({"cpu": "100m"}).obj() for i in range(n)]


def offer(s, wave, deletes=(), pace=PACE):
    """One iteration's first block as the benchmark builds it: the
    deletes of bound pods, then the new pods."""
    with s.agent.batched():
        for uid in deletes:
            s.agent.delete_pod(uid)
            time.sleep(pace)
        for p in wave:
            s.agent.upsert_pod(p)
            time.sleep(pace)


def confirm(s, resp, known):
    with s.agent.batched():
        for b in resp.bindings:
            s.agent.upsert_pod(known[b.pod_uid], bound_node=b.node_name)
            time.sleep(PACE)


def cycle_and_confirm(s, known):
    resp = s.client.cycle()
    confirm(s, resp, known)
    return resp


def load_nodes(s):
    with s.agent.batched():
        for n in nodes():
            s.agent.upsert_node(n)


# ---- (a) the same state, live and restored ---------------------------------


def test_chunks_and_one_request_leave_the_same_state(served, monkeypatch):
    """Two iterations of the benchmark's loop (offer, cycle, confirm by
    reference; the second deletes bound pods before it offers) through
    a block that goes in one request and through one that goes in many:
    cache and queue equal, and a restore of each journal equals both."""
    whole, chunked = served("whole"), served("chunked")
    first, second = pods(120, "a"), pods(120, "b")
    known = {p.uid: p for p in first + second}
    bound = {}
    for s in (whole, chunked):
        monkeypatch.setattr(
            client_mod, "FLUSH_FLOOR_BYTES",
            FLOOR if s is chunked else client_mod.MAX_UPDATE_BYTES)
        load_nodes(s)
        offer(s, first)
        resp = cycle_and_confirm(s, known)
        bound[s] = sorted((b.pod_uid, b.node_name) for b in resp.bindings)
        offer(s, second, deletes=[p.uid for p in first[:50]])
    assert len(bound[whole]) == 120 and bound[whole] == bound[chunked]
    # nodes, offer, confirm, offer: one request each, against many
    assert len(whole.requests) == 4 and len(chunked.requests) > 8
    assert whole.dumps() == chunked.dumps()
    cache, queue = whole.dumps()
    assert len(cache["bound"]) == 70 and cache["assumed"] == []
    assert [e["pod"]["m"]["u"] for e in queue["active"]] == [
        p.uid for p in second]
    assert whole.restored() == whole.dumps()
    assert chunked.restored() == chunked.dumps()


# ---- (b) one at a time, (d) all of it before the block returns -------------


def test_never_two_updates_at_once_and_all_acknowledged_at_exit(
        served, small_floor):
    """A server that takes its time over every chunk: the agent keeps
    building while one is in flight (chunks grow past the floor), never
    sends a second beside it, and the block returns only when the
    server has applied the last: a `Cycle` right after pops every pod."""
    s = served()
    load_nodes(s)
    s.delay = 0.1
    wave = pods(300)
    offer(s, wave, pace=0)
    handled = s.scheduler.update_rpcs
    assert s.at_once == 1
    assert handled == len(s.requests) >= 3  # the nodes' one, then chunks
    sent = [ev.pod.metadata.uid for r in s.requests for ev in r.pod_adds]
    assert sent == [p.uid for p in wave]  # each once, in the calls' order
    # the first chunk went at the floor; while the server held it the
    # agent built far more than a floor's worth into the next
    sizes = s.sizes()[1:]
    assert FLOOR <= sizes[0] < 2 * FLOOR and max(sizes) > 2 * FLOOR
    assert s.client.cycle().stats.attempted == 300


# ---- (c) under the floor one request; over the limit it still splits -------


def test_a_batch_under_the_floor_is_one_request(served):
    """The shipped floor: 24 objects are far under it, and go as the one
    synchronous request they always were. (f) Nested blocks are the
    outer one's batch."""
    s = served()
    with s.agent.batched():
        for n in nodes(4):
            s.agent.upsert_node(n)
        with s.agent.batched():
            for p in pods(10):
                s.agent.upsert_pod(p)
            with s.agent.batched():
                s.agent.delete_pod("default/p0")
            assert not s.requests  # an inner exit sends nothing
        for p in pods(10, "q"):
            s.agent.upsert_pod(p)
    (only,) = s.requests
    assert (len(only.node_adds), len(only.pod_adds), len(only.pod_deletes)
            ) == (4, 20, 1)
    assert only.ByteSize() < client_mod.FLUSH_FLOOR_BYTES
    assert s.scheduler.update_rpcs == 1
    assert s.client.cycle().stats.attempted == 19


def test_a_batch_over_the_limit_still_splits_there(
        served, small_floor, monkeypatch):
    """With a chunk held by the server the open batch grows to
    MAX_UPDATE_BYTES and is sent on there, after a wait for the one in
    flight: no request passes the limit by more than the object that
    reached it."""
    limit = 8 * FLOOR
    monkeypatch.setattr(client_mod, "MAX_UPDATE_BYTES", limit)
    s = served()
    s.delay = 0.2
    wave = pods(600)
    one = max(pb.UpdateRequest(pod_adds=[pb.PodEvent(
        pod=client_mod.convert.pod_to(p))]).ByteSize() for p in wave)
    offer(s, wave, pace=0)  # 600 pods in the time the server holds one chunk
    sizes = s.sizes()
    assert s.at_once == 1 and sum(len(r.pod_adds) for r in s.requests) == 600
    assert sizes[0] < 2 * FLOOR  # nothing was in flight: sent at the floor
    assert max(sizes) < limit + one, sizes
    assert sum(n >= limit for n in sizes) >= 2, sizes


# ---- (e) what a chunk's response triggers ----------------------------------


class Counting(SchedulerAgent):
    relists = 0

    def relist(self):
        self.relists += 1
        super().relist()


class Strict(SchedulerAgent):
    """The benchmark's StrictAgent: recovery is a failure."""

    def relist(self):
        raise RuntimeError("the agent fell into relist()")


def test_unconfirmed_uids_of_a_chunk_go_again_as_whole_pods(
        served, small_floor):
    """Confirmations by reference in several chunks; the server lost
    two assumptions (one in the first chunk, one in the last): each
    comes back `unconfirmed` in its chunk's response and is sent again
    as the whole pod before anything later."""
    s = served()
    load_nodes(s)
    wave = pods(400)
    known = {p.uid: p for p in wave}
    offer(s, wave, pace=0)
    resp = s.client.cycle()
    assert len(resp.bindings) == 400
    lost = [resp.bindings[0].pod_uid, resp.bindings[-1].pod_uid]
    for uid in lost:
        s.scheduler.cache.forget(uid)
    del s.requests[:]
    confirm(s, resp, known)
    by_ref = [r for r in s.requests if r.bind_confirms]
    again = [ev.pod.metadata.uid for r in s.requests for ev in r.pod_updates]
    assert len(by_ref) > 2 and again == lost
    assert sum(len(r.bind_confirms) for r in by_ref) == 400
    # the first one's resend follows its own chunk, not the block's end
    assert [bool(r.pod_updates) for r in s.requests[:2]] == [False, True]
    cache = s.scheduler.cache.dump_state()
    assert len(cache["bound"]) == 400 and cache["assumed"] == []
    assert s.at_once == 1


def test_a_boot_id_change_in_a_chunks_response_relists_once(
        served, small_floor):
    s = served(agent_class=Counting)
    load_nodes(s)

    def restart(index, context):
        if index == 3:  # a chunk in the middle of the block
            s.service.boot_id = "another incarnation"

    s.before = restart
    offer(s, pods(200))
    assert s.agent.relists == 1 and s.at_once == 1
    (relist,) = [r for r in s.requests if r.node_adds and r.pod_adds]
    assert len(relist.node_adds) == 8
    assert s.client.cycle().stats.attempted == 200


@pytest.mark.parametrize("agent_class", [Counting, Strict])
def test_unavailable_on_a_chunk_in_flight(served, small_floor, agent_class):
    """The server refuses one chunk as UNAVAILABLE: the stock agent
    relists and sends that chunk once more; an agent whose relist()
    raises (the benchmark's) raises out of the block."""
    s = served(agent_class=agent_class)
    load_nodes(s)
    refused = []

    def refuse(index, context):
        if index == 3:
            refused.append(s.requests[index])
            context.abort(grpc.StatusCode.UNAVAILABLE, "not now")

    s.before = refuse
    wave = pods(200)
    if agent_class is Strict:
        with pytest.raises(RuntimeError, match="relist"):
            offer(s, wave)
        assert s.agent._unacked is None and s.agent._batch is None
        return
    offer(s, wave)
    assert s.agent.relists == 1 and s.at_once == 1
    (chunk,) = refused
    same = [r for r in s.requests if r == chunk]
    assert len(same) == 2  # refused, then sent again after the relist
    assert s.client.cycle().stats.attempted == 200


def test_a_block_that_raises_leaves_nothing_in_flight(served, small_floor):
    s = served()
    s.delay = 0.05
    with pytest.raises(KeyError):
        with s.agent.batched():
            for p in pods(40):
                s.agent.upsert_pod(p)
            raise KeyError("the caller's own")
    assert s.agent._unacked is None and s.agent._batch is None
    sent = len(s.requests)
    assert sent >= 1 and s.scheduler.update_rpcs == sent
    s.agent.upsert_node(nodes(1)[0])  # synchronous, and alone
    assert s.at_once == 1


# ---- the counter and the spans ---------------------------------------------


def test_records_carry_update_rpcs_and_it_rises_by_the_requests_sent(
        served, small_floor):
    """`update_rpcs` in a cycle's flight record is the Update RPCs the
    servicer handled before it: it rises by the chunks the agent sent
    between two cycles, and /metrics keeps step. A scheduler no
    servicer feeds keeps no such count."""
    assert Scheduler(config=SchedulerConfiguration()).update_rpcs is None
    s = served()
    metrics = s.scheduler.metrics
    load_nodes(s)
    waves = [pods(100, "a"), pods(3, "b"), pods(100, "c")]
    known = {p.uid: p for w in waves for p in w}
    in_record, before_cycle, sent = [], [], []
    for wave in waves:
        at = len(s.requests)
        offer(s, wave)
        # a record is committed inside its Cycle: it counts the requests
        # up to there, this iteration's confirmations not yet
        before_cycle.append(len(s.requests))
        cycle_and_confirm(s, known)
        in_record.append(
            s.scheduler.flight.last_record().counts["update_rpcs"])
        sent.append(len(s.requests) - at)
    assert in_record == before_cycle
    # 3 pods and their confirmations: one request each, as ever
    assert sent[1] == 2 and sent[0] > 4 and sent[2] > 4
    assert in_record[2] - in_record[1] == sent[1] - 1 + (
        before_cycle[2] - before_cycle[1] - 1)
    assert s.scheduler.update_rpcs == len(s.requests) == 1 + sum(sent)
    assert metrics.update_rpcs._value.get() == len(s.requests)
    assert (b"scheduler_update_rpcs_total %.1f" % len(s.requests)
            ) in metrics.expose()


def test_a_chunk_costs_three_spans_and_the_agent_s_four(served, small_floor):
    """Armed, every Update RPC is `rpc.update` with `update.convert`
    and `update.apply` and nothing else on the server's side, so a
    block that goes in n chunks stamps 3 n spans there. The agent's own
    (service/client.py) come on top once it has heard that the ring is
    armed: per chunk a `client.build`, a `client.send` and a
    `client.update`, a `client.ack_wait` where it waited, and one
    `client.batch` a block; those the server could place are in the
    ring too: what PERF.md reckons the ring by."""
    rec = _spans.arm(rate=1.0)
    try:
        s = served()
        load_nodes(s)  # the first call: the agent hears of the ring
        assert s.client.tracing
        offer(s, pods(150))
        n = len(s.requests)
        assert n > 4
        spans = rec.snapshot()
    finally:
        _spans.disarm()
    served_side = [sp for sp in spans if sp.name not in
                   _spans.CLIENT_SPAN_NAMES]
    names = sorted(sp.name for sp in served_side)
    assert names == sorted(
        ["rpc.update", "update.convert", "update.apply"] * n)
    # the nodes' request is a trace of its own; the block's chunks
    # join the block's
    assert len({sp.trace_id for sp in served_side}) == 2
    agents = [sp.name for sp in spans if sp.name in _spans.CLIENT_SPAN_NAMES]
    assert len(agents) <= 4 * (n - 1)  # the last chunk's are not shipped
    assert "client.batch" not in agents and "client.cycle" not in agents

"""An `Update` request is applied as a group (service/server.py): each
of its pod lists goes to ONE handler call (`Scheduler.on_pods_add`,
`on_pods_update`, `confirm_pods`, `on_pods_delete`), and with a durable
state attached the whole pass runs inside `DurableState.batch()`, so
what the request journals is ONE `batch` record.

The reference is the path the servicer took before: the same request
applied pod by pod through the single-object handlers, outside any
scope. On the same stream of requests the two leave the cache, the
queue, `unconfirmed`, `_uid_index`, the store gauges and the state a
restore replays from the journal identical. The clock is injected and
steps between requests, never inside one: a list reads it once where
the loop reads it once a pod a store, so inside a request every record
carries the same value either way.

What differs, and is held here too: within a list a store's records
come together (the queue's stale-entry deletes, then the cache's adds;
the cache's removals, then the queue's), and the list's one queueing
hint pass comes after the run's records where the loop's first
effective pass came after its first pod's. The records themselves, their
count and their clock values are the loop's."""

from __future__ import annotations

import random
import threading

import pytest

from k8s_scheduler_tpu.config import SchedulerConfiguration
from k8s_scheduler_tpu.core import spans as _spans
from k8s_scheduler_tpu.core.flight_recorder import PodTimelines
from k8s_scheduler_tpu.core.scheduler import Scheduler
from k8s_scheduler_tpu.internal.cache import SchedulerCache
from k8s_scheduler_tpu.internal.queue import (
    EVENT_POD_DELETE,
    EVENT_POD_UPDATE,
    SchedulingQueue,
)
from k8s_scheduler_tpu.metrics import SchedulerMetrics
from k8s_scheduler_tpu.models import MakeNode, MakePod
from k8s_scheduler_tpu.service import convert
from k8s_scheduler_tpu.service import scheduler_pb2 as pb
from k8s_scheduler_tpu.service.server import SchedulerService
from k8s_scheduler_tpu.state import DurableState
from k8s_scheduler_tpu.state.codec import pod_to_state
from k8s_scheduler_tpu.state.journal import (
    BATCH_OP,
    Journal,
    iter_batch,
    replay_dir,
)

NODES = [f"node-{i}" for i in range(6)]
# reasons a cycle parks a pod under, and the events that cure each
# (internal/queue.QUEUEING_HINTS): PodDelete cures the first and the
# third, PodAdd / PodUpdate the third, nothing of an Update's the second
REASONS = [("NodeResourcesFit",), ("NodeAffinity",), ("PodTopologySpread",),
           ("NodeAffinity", "NodeResourcesFit"), ()]


class Clock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


class Side:
    """A scheduler behind a servicer, journaling to `path` (or with no
    durable state), on an injected clock."""

    def __init__(self, path, interval):
        self.path, self.interval = path, interval
        self.clock = Clock()
        self.metrics = SchedulerMetrics()
        self.state = None
        if path is not None:
            self.state = DurableState(
                str(path), snapshot_interval_seconds=interval,
                now=self.clock, metrics=self.metrics,
            )
        self.service = SchedulerService(
            scheduler=Scheduler(
                config=SchedulerConfiguration(speculative_compile=False),
                now=self.clock, state=self.state, metrics=self.metrics,
            ),
            metrics=self.metrics,
        )
        self.s = self.service.scheduler
        self.unconfirmed: list[list[str]] = []

    # -- the two ways a request is applied --------------------------------

    def grouped(self, request: pb.UpdateRequest) -> None:
        resp = self.service.Update(request, None)
        self.unconfirmed.append(list(resp.unconfirmed))

    def pod_by_pod(self, request: pb.UpdateRequest) -> None:
        """`SchedulerService.Update` as it stood before the list forms:
        the single-object handlers, a pod at a time, no journal scope."""
        s, index = self.s, self.service._uid_index
        for n in request.node_adds:
            s.on_node_add(convert.node_from(n))
        for ev in request.pod_adds:
            pod = convert.pod_from(ev.pod)
            index[pod.uid] = pod
            s.on_pod_add(pod, node_name=ev.bound_node)
        for ev in request.pod_updates:
            pod = convert.pod_from(ev.pod)
            index[pod.uid] = pod
            s.on_pod_update(pod, node_name=ev.bound_node)
        unconfirmed = []
        for c in request.bind_confirms:
            pod = s.cache.confirm(c.pod_uid, c.node_name)
            if pod is None:
                unconfirmed.append(c.pod_uid)
                continue
            s.queue.delete(c.pod_uid)
            s.flight.pod_event(
                c.pod_uid, pod.name, "BoundObserved", node=c.node_name
            )
        if len(unconfirmed) < len(request.bind_confirms):
            s.queue.move_all_to_active_or_backoff(EVENT_POD_UPDATE)
        for uid in request.pod_deletes:
            index.pop(uid, None)
            s.on_pod_delete(uid)
        for uid in request.bind_failures:
            s.cache.forget(uid)
            pod = index.get(uid)
            if pod is not None:
                s.queue.requeue_backoff(pod)
        s.stamp_store_gauges()
        self.unconfirmed.append(unconfirmed)

    # -- what a cycle does between two requests, the same on both ---------

    def cycle(self, plan: dict) -> None:
        s = self.s
        popped = {p.uid: p for p in s.queue.pop_ready()}
        for uid, what in plan.items():
            pod = popped.get(uid)
            if pod is None:
                continue
            if what[0] == "bind":
                s.cache.assume(pod, what[1])
                s.cache.finish_binding(uid)
            elif what[0] == "park":
                s.queue.requeue_unschedulable(pod, reasons=what[1])
            else:
                s.queue.requeue_backoff(pod)
        if self.state is not None:
            self.state.maybe_snapshot()

    # -- what is compared -------------------------------------------------

    def view(self) -> dict:
        m = self.metrics
        return {
            "cache": self.s.cache.dump_state(),
            "queue": self.s.queue.dump_state(),
            "unconfirmed": self.unconfirmed,
            "uid_index": {
                u: pod_to_state(p)
                for u, p in self.service._uid_index.items()
            },
            "gauges": [
                m.cache_size.labels(type=t)._value.get()
                for t in ("nodes", "pods", "assumed_pods")
            ] + [
                m.pending_pods.labels(queue=q)._value.get()
                for q in ("active", "backoff", "unschedulable")
            ],
            "departed": (self.s.cache.departed, self.s.queue.departed),
        }

    def restored(self) -> dict:
        """What a restore of the state directory gives."""
        self.state.journal.flush()
        q, c = SchedulingQueue(now=self.clock), SchedulerCache(now=self.clock)
        st = DurableState(
            str(self.path), snapshot_interval_seconds=self.interval,
            now=self.clock,
        )
        st.restore_into(q, c)
        st.journal.close()
        return {"cache": c.dump_state(), "queue": q.dump_state()}

    def records(self) -> list:
        """The journal's records as they lie in its segments."""
        self.state.journal.flush()
        return list(replay_dir(str(self.path)))

    def close(self) -> None:
        if self.state is not None:
            self.state.journal.close()


def expand(records) -> list:
    """Every logical op of `records`, a `batch` through `iter_batch`."""
    out = []
    for op, t, data in records:
        if op == BATCH_OP:
            out.extend(iter_batch(data))
        else:
            out.append((op, t, data))
    return out


def names(rows) -> list[str]:
    """The pod names of a dump's rows or entries."""
    return [r["pod"]["m"]["n"] for r in rows]


def versions(rows) -> list[tuple[str, str]]:
    return [(r["pod"]["m"]["n"], r["pod"]["m"]["l"]["v"]) for r in rows]


def of_store(ops, prefix: str) -> list:
    return [o for o in ops if o[0].startswith(prefix)]


def make_pod(uid: str, version: int = 0):
    """`version` changes the spec, so that of two pods of one uid it
    shows which one a store kept."""
    return (
        MakePod(uid)
        .uid(uid)
        .req({"cpu": f"{100 + version}m", "memory": "64Mi"})
        .labels({"app": f"app-{len(uid) % 3}", "v": str(version)})
        .priority(version % 3)
        .created(float(version))
        .obj()
    )


def nodes_request() -> pb.UpdateRequest:
    req = pb.UpdateRequest()
    for name in NODES:
        req.node_adds.append(convert.node_to(
            MakeNode(name).capacity({"cpu": "8", "memory": "32Gi"}).obj()
        ))
    return req


def add_event(events, pod, bound_node: str = "") -> None:
    ev = events.add()
    ev.pod.CopyFrom(convert.pod_to(pod))
    ev.bound_node = bound_node


class Stream:
    """A seeded stream of requests and cycle plans over a model of where
    every uid stands, so that each list meets every kind of uid: bound,
    assumed, queued, parked, in flight, unknown, and the same uid twice."""

    def __init__(self, seed: int) -> None:
        self.rng = random.Random(seed)
        self.n = 0
        self.version: dict[str, int] = {}
        self.pending: set[str] = set()  # sent pending, not yet bound
        self.assumed: dict[str, str] = {}
        self.bound: set[str] = set()

    def _new(self) -> str:
        self.n += 1
        uid = f"p{self.n}"
        self.version[uid] = 0
        return uid

    def _pod(self, uid: str):
        self.version[uid] = self.version.get(uid, 0) + 1
        return make_pod(uid, self.version[uid])

    def _some(self, pool, k: int) -> list[str]:
        pool = sorted(pool)
        return self.rng.sample(pool, min(k, len(pool)))

    def request(self) -> pb.UpdateRequest:
        rng, req = self.rng, pb.UpdateRequest()
        # pod_adds: runs of bound and pending pods, interleaved; some
        # uids again (a pending pod that comes bound, a bound pod that
        # comes pending, the same pending pod twice)
        for _ in range(rng.randrange(0, 12)):
            kind = rng.random()
            if kind < 0.45:
                uid = self._new()
                self.pending.add(uid)
                add_event(req.pod_adds, self._pod(uid))
                if rng.random() < 0.2:  # twice in the list: last wins
                    add_event(req.pod_adds, self._pod(uid))
            elif kind < 0.8:
                uid = self._new()
                self.bound.add(uid)
                add_event(req.pod_adds, self._pod(uid), rng.choice(NODES))
            elif self.pending:
                # a queued (or parked, or in-flight) pod observed bound:
                # the stale queue entry goes before the cache takes it
                uid = rng.choice(sorted(self.pending))
                self.pending.discard(uid)
                self.bound.add(uid)
                add_event(req.pod_adds, self._pod(uid), rng.choice(NODES))
            elif self.bound:
                uid = rng.choice(sorted(self.bound))
                add_event(req.pod_adds, self._pod(uid))
        for uid in self._some(self.pending | self.bound, rng.randrange(0, 6)):
            if uid in self.bound and rng.random() < 0.6:
                add_event(req.pod_updates, self._pod(uid), rng.choice(NODES))
            else:
                add_event(req.pod_updates, self._pod(uid))
                if rng.random() < 0.2:
                    add_event(req.pod_updates, self._pod(uid))
        # confirmations: of assumed pods on their node, on another node,
        # of unknown and of bound uids, and one twice
        for uid in self._some(self.assumed, rng.randrange(0, 8)):
            node = self.assumed[uid]
            c = req.bind_confirms.add()
            c.pod_uid = uid
            if rng.random() < 0.15:
                c.node_name = rng.choice([n for n in NODES if n != node])
                continue
            c.node_name = node
            del self.assumed[uid]
            self.pending.discard(uid)
            self.bound.add(uid)
            if rng.random() < 0.15:
                again = req.bind_confirms.add()
                again.pod_uid, again.node_name = uid, node
        for uid in ["nobody"] * (rng.random() < 0.3) + self._some(
                self.bound, rng.random() < 0.3):
            c = req.bind_confirms.add()
            c.pod_uid, c.node_name = uid, rng.choice(NODES)
        # deletes: of bound, assumed, queued / parked / in-flight and
        # unknown uids, and one twice
        gone = self._some(self.bound, rng.randrange(0, 5)) + self._some(
            self.pending, rng.randrange(0, 3)) + self._some(
            self.assumed, rng.random() < 0.3)
        rng.shuffle(gone)
        for uid in gone:
            req.pod_deletes.append(uid)
            if rng.random() < 0.1:
                req.pod_deletes.append(uid)
            self.bound.discard(uid)
            self.pending.discard(uid)
            self.assumed.pop(uid, None)
        if rng.random() < 0.2:
            req.pod_deletes.append("never-seen")
        for uid in self._some(self.assumed, rng.random() < 0.4):
            # the agent's bind failed: forgotten, and back off
            req.bind_failures.append(uid)
            del self.assumed[uid]
        return req

    def plan(self) -> dict:
        """What the next cycle does with each pod it may pop."""
        rng, plan = self.rng, {}
        for uid in sorted(self.pending):
            roll = rng.random()
            if roll < 0.45:
                node = rng.choice(NODES)
                plan[uid] = ("bind", node)
                self.assumed[uid] = node
            elif roll < 0.8:
                plan[uid] = ("park", rng.choice(REASONS))
            else:
                plan[uid] = ("backoff",)
        return plan


MODES = {
    "no-state": None,
    "journal-only": 0,  # snapshotInterval 0: no fragment at entry
    "compacting": 15,  # snapshotInterval 15: fragments, compactions
}


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("seed", range(6))
def test_a_stream_applied_as_groups_equals_it_applied_pod_by_pod(
        tmp_path, seed, mode):
    interval = MODES[mode]
    durable = interval is not None
    a = Side(tmp_path / "grouped" if durable else None, interval)
    b = Side(tmp_path / "pod-by-pod" if durable else None, interval)
    try:
        stream = Stream(seed)
        first = nodes_request()
        a.grouped(first)
        b.pod_by_pod(first)
        for step in range(14):
            request = stream.request()
            a.grouped(request)
            b.pod_by_pod(request)
            assert a.view() == b.view(), f"after request {step}"
            for side in (a, b):
                side.clock.t += 0.4  # between requests, never inside one
            plan = stream.plan()
            a.cycle(plan)
            b.cycle(plan)
            # backoffs of 1-10 s run out, an interval of 15 passes
            pause = stream.rng.choice((0.3, 2.5, 9.0))
            for side in (a, b):
                side.clock.t += pause
            assert a.view() == b.view(), f"after cycle {step}"
        if not durable:
            return
        live = {k: a.view()[k] for k in ("cache", "queue")}
        assert a.restored() == live
        assert b.restored() == live
        if interval:
            assert a.state.last_snapshot and b.state.last_snapshot
        # the same logical ops either way, every one with its clock
        # value; a list's come grouped by store, so as multisets. The
        # hint passes apart: the loop journals a pass that moved only
        # pods its own list then deletes or takes bound, the list form
        # has nothing left to move there and journals none
        ops_a, ops_b = expand(a.records()), expand(b.records())
        assert sorted(repr(o) for o in ops_a if o[0] != "q.move") \
            == sorted(repr(o) for o in ops_b if o[0] != "q.move")
        moves_a, moves_b = of_store(ops_a, "q.move"), of_store(ops_b, "q.move")
        assert len(moves_a) <= len(moves_b)
    finally:
        a.close()
        b.close()


def test_a_request_s_journal_is_one_batch_record_of_the_loop_s_records(
        tmp_path):
    """A multi-pod request appends ONE `batch` record whose expansion
    is the pod-by-pod path's records, op for op and clock for clock: in
    the loop's very order where one store journals a list (pending adds
    and updates), and store by store where two do (a pod's stale queue
    entry and its cache row, a confirmation's two halves, a delete's)."""
    a = Side(tmp_path / "grouped", 0)
    b = Side(tmp_path / "pod-by-pod", 0)
    try:
        first = nodes_request()
        a.grouped(first)
        b.pod_by_pod(first)
        # one store: pending adds (one uid twice) and their updates
        one = pb.UpdateRequest()
        for i in range(8):
            add_event(one.pod_adds, make_pod(f"q{i}", 1))
        add_event(one.pod_adds, make_pod("q3", 2))
        for i in (1, 5):
            add_event(one.pod_updates, make_pod(f"q{i}", 3))
        seen = [len(a.records()), len(b.records())]
        a.grouped(one)
        b.pod_by_pod(one)
        new_a, new_b = a.records()[seen[0]:], b.records()[seen[1]:]
        assert [op for op, _, _ in new_a] == [BATCH_OP]
        assert len(new_b) == 11 and BATCH_OP not in {r[0] for r in new_b}
        assert list(iter_batch(new_a[0][2])) == new_b
        for side in (a, b):
            side.clock.t += 1.0
        # a cycle assumes six of them and leaves two in flight
        plan = {f"q{i}": ("bind", NODES[i % 6]) for i in range(6)}
        a.cycle(plan)
        b.cycle(plan)
        for side in (a, b):
            side.clock.t += 1.0
        # two stores: bound adds (one over a queued pod), confirmations,
        # deletes of a bound and of an in-flight pod
        two = pb.UpdateRequest()
        add_event(two.pod_adds, make_pod("b0", 1), NODES[0])
        add_event(two.pod_adds, make_pod("q6", 4), NODES[1])
        add_event(two.pod_adds, make_pod("b1", 1), NODES[2])
        for i in range(6):
            c = two.bind_confirms.add()
            c.pod_uid, c.node_name = f"q{i}", NODES[i % 6]
        two.pod_deletes.extend(["b0", "q7", "q2"])
        seen = [len(a.records()), len(b.records())]
        a.grouped(two)
        b.pod_by_pod(two)
        new_a, new_b = a.records()[seen[0]:], b.records()[seen[1]:]
        assert [op for op, _, _ in new_a] == [BATCH_OP]
        ops = list(iter_batch(new_a[0][2]))
        assert len(ops) == len(new_b) == 20
        for store in ("q.", "c."):
            assert of_store(ops, store) == of_store(new_b, store)
        assert {t for _, t, _ in ops} == {a.clock.t}
        assert a.view() == b.view()
        assert a.restored() == b.restored()
    finally:
        a.close()
        b.close()


def test_a_one_op_request_appends_the_plain_record(tmp_path):
    """The scope around a request that journals one op degenerates to
    the record a scopeless emit writes, and one that journals nothing
    appends nothing."""
    a = Side(tmp_path / "grouped", 0)
    try:
        a.grouped(nodes_request())
        seen = len(a.records())
        req = pb.UpdateRequest()
        add_event(req.pod_adds, make_pod("only", 1))
        a.grouped(req)
        (record,) = a.records()[seen:]
        assert record[0] == "q.add" and names([record[2]]) == ["only"]
        nothing = pb.UpdateRequest()
        nothing.pod_deletes.append("nobody")
        at = a.state.journal.seq()
        a.grouped(nothing)
        assert a.state.journal.seq() == at
    finally:
        a.close()


# ---- the semantics a list holds, one by one --------------------------------


def plain(now=None) -> Scheduler:
    return Scheduler(
        config=SchedulerConfiguration(speculative_compile=False),
        now=now or Clock(),
    )


def test_a_uid_twice_in_a_list_resolves_as_the_loop_resolves_it():
    s = plain()
    s.on_pods_add([(make_pod("x", 1), ""), (make_pod("y", 1), ""),
                   (make_pod("x", 2), "")])
    active = s.queue.dump_state()["active"]
    # last wins, in the place the first took
    assert versions(active) == [("x", "2"), ("y", "1")]
    s.on_pods_add([(make_pod("z", 1), NODES[0]), (make_pod("z", 2), NODES[1])])
    (row,) = s.cache.dump_state()["bound"]
    assert (row["node"], versions([row])) == (NODES[1], [("z", "2")])
    s.on_pods_delete(["z", "z", "x"])
    assert s.cache.counts()["bound"] == 0 and len(s.queue) == 1
    assert s.cache.departed == 1 and s.queue.departed == 1


def test_a_bound_add_drops_the_stale_queue_entry_in_the_list_s_order():
    """`pod_adds` mixes bound and pending pods: runs are applied in the
    request's order, so a pod that comes pending and then bound is bound
    only, and one that comes bound and then pending is both (as the
    loop leaves it)."""
    s = plain()
    s.on_pods_add([
        (make_pod("p", 1), ""), (make_pod("q", 1), ""),
        (make_pod("p", 2), NODES[0]),
        (make_pod("r", 1), NODES[1]), (make_pod("r", 2), ""),
    ])
    assert sorted(names(s.queue.dump_state()["active"])) == ["q", "r"]
    assert sorted(names(s.cache.dump_state()["bound"])) == ["p", "r"]


def parked(s: Scheduler, reasons_by_uid: dict) -> None:
    s.on_pods_add([(make_pod(u, 1), "") for u in reasons_by_uid])
    for pod in s.queue.pop_ready():
        s.queue.requeue_unschedulable(pod, reasons=reasons_by_uid[pod.uid])


@pytest.mark.parametrize("handler", ["delete", "bound-add", "confirm"])
def test_one_hint_pass_moves_what_the_per_pod_passes_moved(handler):
    """Nothing enters the unschedulable set during an `Update`, so the
    one pass after a list moves exactly the pods the loop's passes moved
    together, into the same tiers and in the same order; a parked pod
    the event does not cure stays."""
    reasons = {"fit": ("NodeResourcesFit",), "aff": ("NodeAffinity",),
               "spread": ("PodTopologySpread",), "any": ()}
    pair = []
    for grouped in (True, False):
        clock = Clock()
        s = plain(clock)
        s.on_pods_add([(make_pod(f"b{i}", 1), NODES[i]) for i in range(4)])
        parked(s, reasons)
        s.on_pods_add([(make_pod("late", 1), "")])
        (late,) = s.queue.pop_ready()
        s.cache.assume(late, NODES[0])
        clock.t += 20.0  # every backoff has run out: cured pods go active
        if handler == "delete":
            uids = ["b0", "spread", "b1", "nobody"]
            if grouped:
                s.on_pods_delete(uids)
            else:
                for uid in uids:
                    s.on_pod_delete(uid)
            moved = {"fit", "any"}
        elif handler == "bound-add":
            pairs = [(make_pod("n0", 1), NODES[0]), (make_pod("n1", 1), ""),
                     (make_pod("fit", 2), NODES[1])]
            if grouped:
                s.on_pods_add(pairs)
            else:
                for pod, node in pairs:
                    s.on_pod_add(pod, node_name=node)
            moved = {"spread", "any"}
        else:
            if grouped:
                assert s.confirm_pods([("late", NODES[0])]) == []
            else:
                assert s.cache.confirm("late", NODES[0]) is late
                s.queue.delete("late")
                s.queue.move_all_to_active_or_backoff(EVENT_POD_UPDATE)
            moved = {"spread", "any"}
        dump = s.queue.dump_state()
        assert moved <= set(names(dump["active"]))
        assert "aff" in names(dump["unschedulable"])
        pair.append((dump, s.cache.dump_state()))
    assert pair[0] == pair[1]


def test_a_parked_pod_that_pod_delete_cures_is_cured_by_a_delete_list():
    s = plain()
    s.on_pods_add([(make_pod("b", 1), NODES[0])])
    parked(s, {"fit": ("NodeResourcesFit",)})
    assert s.queue.pending_counts()["unschedulable"] == 1
    s.on_pods_delete(["b"])
    counts = s.queue.pending_counts()
    assert counts["unschedulable"] == 0
    assert counts["active"] + counts["backoff"] == 1
    # and the queue was told once, of the one pod
    incoming = s.metrics.queue_incoming.labels(
        queue="backoff", event=EVENT_POD_DELETE)._value.get() + \
        s.metrics.queue_incoming.labels(
            queue="active", event=EVENT_POD_DELETE)._value.get()
    assert incoming == 1


def test_unconfirmed_uids_come_back_in_the_request_s_order(tmp_path):
    a = Side(tmp_path / "s", 0)
    try:
        a.grouped(nodes_request())
        req = pb.UpdateRequest()
        for i in range(5):
            add_event(req.pod_adds, make_pod(f"w{i}", 1))
        a.grouped(req)
        a.cycle({f"w{i}": ("bind", NODES[i]) for i in range(5)})
        req = pb.UpdateRequest()
        for uid, node in [("zz", NODES[0]), ("w1", NODES[1]),
                          ("w0", NODES[3]), ("w2", NODES[2]),
                          ("aa", NODES[0]), ("w1", NODES[1]),
                          ("w4", NODES[4])]:
            c = req.bind_confirms.add()
            c.pod_uid, c.node_name = uid, node
        resp = a.service.Update(req, None)
        # unknown, wrong node, unknown, a second time: in that order
        assert list(resp.unconfirmed) == ["zz", "w0", "aa", "w1"]
        assert resp.bind_confirms_applied == 3
        counts = a.s.cache.counts()
        assert (counts["bound"], counts["assumed"]) == (3, 2)
    finally:
        a.close()


def test_queue_incoming_steps_once_a_list_by_its_count():
    s = plain()
    calls = []
    inner = s.queue._on_enqueue
    s.queue._on_enqueue = lambda q, e, n=1: (calls.append((q, e, n)),
                                             inner(q, e, n))
    s.on_pods_add([(make_pod(f"p{i}", 1), "") for i in range(7)])
    assert calls == [("active", "PodAdd", 7)]
    assert s.metrics.queue_incoming.labels(
        queue="active", event="PodAdd")._value.get() == 7
    s.on_pod_add(make_pod("single", 1))
    assert calls[-1] == ("active", "PodAdd", 1)


def test_note_many_leaves_what_a_note_a_row_leaves():
    """The timelines after one `note_many` are those after a `note` a
    row: the entries, their order, their events, and who was evicted."""
    for uids in (9, 4):  # more than the ring holds; fewer, so events go
        rows = [(f"u{i % uids}", f"name-{i}" if i % 4 else "",
                 {"node": f"n{i}"} if i % 3 else None) for i in range(40)]
        one, many = PodTimelines(max_pods=5, max_events=3), \
            PodTimelines(max_pods=5, max_events=3)
        for uid, name, detail in rows:
            one.note(uid, name, "Seen", 1.5, 2.5, **(detail or {}))
        many.note_many("Seen", rows, 1.5, 2.5)
        assert list(one._pods.items()) == list(many._pods.items())
        assert len(many) == min(uids, 5)
    assert len(many.get("u3")["events"]) == 3


def test_a_request_makes_one_timeline_call_a_list(tmp_path):
    a = Side(None, None)
    calls = []
    inner = a.s.flight.pods.note_many
    a.s.flight.pods.note_many = lambda kind, rows, t, wall: (
        calls.append((kind, len(rows))), inner(kind, rows, t, wall))
    a.grouped(nodes_request())
    req = pb.UpdateRequest()
    for i in range(5):
        add_event(req.pod_adds, make_pod(f"w{i}", 1))
    for i in range(3):
        add_event(req.pod_adds, make_pod(f"b{i}", 1), NODES[i])
    req.pod_deletes.extend(["b0", "w0"])
    a.grouped(req)
    assert calls == [("Queued", 5), ("BoundObserved", 3), ("Deleted", 2)]
    assert [e["kind"] for e in a.s.flight.pods.get("b0")["events"]] == [
        "BoundObserved", "Deleted"]


# ---- the counter, and the bystanders ---------------------------------------


def test_journal_records_rises_by_one_for_a_multi_pod_request(tmp_path):
    """`Journal.seq()`, `scheduler_journal_records_total` and
    the `journal_records` of `rpc.update` count RECORDS, a batch as one;
    `scheduler_journal_appends_total{op}` keeps the logical ops."""
    a = Side(tmp_path / "s", 0)
    recorder = _spans.arm(rate=1.0)
    try:
        a.grouped(nodes_request())
        m = a.metrics

        def ops() -> dict:
            return {op: m.journal_appends.labels(op=op)._value.get()
                    for op in ("q.add", "q.delete", "c.add_pod",
                               "c.confirm", "c.remove_pod", BATCH_OP)}

        at, at_ops = a.state.journal.seq(), ops()
        total = m.journal_records._value.get()
        assert total == at == 1  # six node records, one group
        req = pb.UpdateRequest()
        for i in range(9):
            add_event(req.pod_adds, make_pod(f"w{i}", 1))
        for i in range(4):
            add_event(req.pod_adds, make_pod(f"b{i}", 1), NODES[i])
        a.grouped(req)
        assert a.state.journal.seq() == at + 1
        assert m.journal_records._value.get() == total + 1
        a.cycle({f"w{i}": ("bind", NODES[i % 6]) for i in range(9)})
        at = a.state.journal.seq()
        req = pb.UpdateRequest()
        for i in range(9):
            c = req.bind_confirms.add()
            c.pod_uid, c.node_name = f"w{i}", NODES[i % 6]
        req.pod_deletes.extend(["b0", "b1", "w0"])
        a.grouped(req)
        assert a.state.journal.seq() == at + 1
        rose = {op: n - at_ops[op] for op, n in ops().items()}
        assert rose == {"q.add": 9, "c.add_pod": 4, "c.confirm": 9,
                        "q.delete": 10, "c.remove_pod": 3, BATCH_OP: 2}
        assert b"scheduler_journal_records_total" in m.expose()
        spans = [sp for sp in recorder.snapshot() if sp.name == "rpc.update"]
        assert [sp.attrs["journal_records"] for sp in spans[-2:]] == [1, 1]
    finally:
        _spans.disarm()
        a.close()


def test_the_flight_record_carries_the_journal_s_records(tmp_path):
    """`journal_records` on a cycle's flight record is the journal's
    running total when the record is committed: between two cycles it
    rises by one a request and by the cycle's own pop and group. A
    scheduler with no durable state keeps no such count."""
    a = Side(tmp_path / "s", 0)
    try:
        a.grouped(nodes_request())
        seen = []
        for wave in range(2):
            req = pb.UpdateRequest()
            for i in range(6):
                add_event(req.pod_adds, make_pod(f"w{wave}-{i}", 1))
            a.grouped(req)
            before = a.state.journal.seq()
            resp = a.service.Cycle(pb.CycleRequest(), None)
            assert len(resp.bindings) == 6
            rec = a.s.flight.last_record()
            # its own q.pop and the group of six assumes, binds and
            # finishes: two records, whatever the pods
            assert rec.counts["journal_records"] == before + 2
            seen.append(rec.counts["journal_records"])
            req = pb.UpdateRequest()
            for b in resp.bindings:
                c = req.bind_confirms.add()
                c.pod_uid, c.node_name = b.pod_uid, b.node_name
            a.grouped(req)
        # a wave: its adds, its confirmations, the pop, the group
        assert seen[1] - seen[0] == 4
        b = Side(None, None)
        b.grouped(nodes_request())
        req = pb.UpdateRequest()
        add_event(req.pod_adds, make_pod("w", 1))
        b.grouped(req)
        b.service.Cycle(pb.CycleRequest(), None)
        assert "journal_records" not in b.s.flight.last_record().counts
    finally:
        a.close()


def test_another_thread_s_emission_lands_after_the_request_s_prefix(
        tmp_path):
    """While a request's scope is open, an emission from another thread
    (the front door, a compaction) first flushes what the request has
    buffered: the journal keeps the order the mutators ran in."""
    a = Side(tmp_path / "s", 0)
    try:
        a.grouped(nodes_request())
        seen = len(a.records())
        reached, go = threading.Event(), threading.Event()
        inner = a.s.on_pods_update

        def held(pairs):
            reached.set()
            assert go.wait(10)
            inner(pairs)

        a.s.on_pods_update = held
        req = pb.UpdateRequest()
        for i in range(4):
            add_event(req.pod_adds, make_pod(f"w{i}", 1))
        add_event(req.pod_updates, make_pod("w0", 2))
        req.pod_deletes.extend(["w1", "w2"])
        t = threading.Thread(target=a.grouped, args=(req,))
        t.start()
        assert reached.wait(10)
        a.s.on_pod_add(make_pod("front-door", 1))  # another thread's
        go.set()
        t.join(10)
        assert not t.is_alive()
        new = a.records()[seen:]
        assert [op for op, _, _ in new] == [BATCH_OP, "q.add", BATCH_OP]
        assert [op for op, _, _ in iter_batch(new[0][2])] == ["q.add"] * 4
        assert names([new[1][2]]) == ["front-door"]
        assert [op for op, _, _ in iter_batch(new[2][2])] == [
            "q.update", "q.delete", "q.delete"]
        live = {k: a.view()[k] for k in ("cache", "queue")}
        assert a.restored() == live
    finally:
        a.close()


def test_a_journal_of_single_records_still_restores(tmp_path):
    """What the parent wrote for a request, a record a pod a store and
    no group, replays to the state the grouped request leaves."""
    a = Side(tmp_path / "grouped", 0)
    try:
        first = nodes_request()
        a.grouped(first)
        req = pb.UpdateRequest()
        for i in range(5):
            add_event(req.pod_adds, make_pod(f"w{i}", 1))
        for i in range(3):
            add_event(req.pod_adds, make_pod(f"b{i}", 1), NODES[i])
        req.pod_deletes.extend(["b1", "w3"])
        a.grouped(req)
        singles = Journal(str(tmp_path / "singles"), fsync=False)
        for op, t, data in expand(a.records()):
            singles.append(op, t, data)
        singles.flush()
        singles.close()
        assert BATCH_OP not in {
            op for op, _, _ in replay_dir(str(tmp_path / "singles"))}
        q, c = SchedulingQueue(now=a.clock), SchedulerCache(now=a.clock)
        st = DurableState(str(tmp_path / "singles"), now=a.clock)
        st.restore_into(q, c)
        st.journal.close()
        assert {"cache": c.dump_state(), "queue": q.dump_state()} \
            == a.restored()
    finally:
        a.close()


def test_the_single_object_handlers_are_the_list_forms_at_length_one():
    """One path: a single-object handler hands its pod to the list form
    and does nothing else."""
    s = plain()
    calls = []
    for name in ("on_pods_add", "on_pods_update", "on_pods_delete"):
        inner = getattr(s, name)
        setattr(s, name, lambda arg, name=name, inner=inner: (
            calls.append((name, len(arg))), inner(arg)))
    pod = make_pod("p", 1)
    s.on_pod_add(pod)
    s.on_pod_update(make_pod("p", 2), node_name=NODES[0])
    s.on_pod_delete("p")
    assert calls == [("on_pods_add", 1), ("on_pods_update", 1),
                     ("on_pods_delete", 1)]
    assert s.cache.counts()["bound"] == 0 and len(s.queue) == 0

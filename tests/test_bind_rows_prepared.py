"""The bind loop serialises no pod it need not (core/scheduler.py):
between the dispatch and the decisions `_schedule_profile` prepares, for
every popped pod, the row `SchedulerCache.assume` would make of it: the
state dict the pod's in-flight queue entry keeps (what `q.add`,
`q.update` or its requeue journaled) and, under a journal that compacts,
the pod's half of its snapshot fragment. A winner's `assume` takes its
row and serialises nothing; a winner without one is serialised in the
loop, which is the path every caller that prepares nothing takes.

The reference is that path: the same requests and the same decisions
through the same `_apply_phase` with no rows. The two leave the journal's
files byte for byte equal, and with them the cache, the queue, their
snapshot bodies, their digest and what a restore gives. A row is handed
over only while the entry it was made from still keeps that very dict
for that very pod; each way it can fail to (an `Update` that refreshed
the entry while the device ran, an entry a compaction left bytes on, an
entry gone, no journal, a failed cycle) falls back, and journals what
the reference journals.

Most cycles here are the real `_apply_phase` over decisions the test
makes up (no device program, so a case takes milliseconds); the served
test runs real cycles behind a servicer."""

from __future__ import annotations

import os
import random
import re

import numpy as np
import pytest

from test_update_batch_apply import (  # noqa: E402
    MODES,
    NODES,
    Clock,
    Side,
    Stream,
    add_event,
    expand,
    make_pod,
    nodes_request,
)

from k8s_scheduler_tpu.core import spans as _spans
from k8s_scheduler_tpu.core.pipeline import CycleHandle
from k8s_scheduler_tpu.core.scheduler import CycleStats
from k8s_scheduler_tpu.internal import cache as cache_mod
from k8s_scheduler_tpu.internal.cache import SchedulerCache, _row_open
from k8s_scheduler_tpu.internal.queue import SchedulingQueue
from k8s_scheduler_tpu.models import MakePod
from k8s_scheduler_tpu.service import scheduler_pb2 as pb
from k8s_scheduler_tpu.state.codec import (
    json_bytes,
    pod_to_state,
    state_digest,
)

COUNTS = ("rows_prepared", "rows_prepared_used", "rows_prepared_fallback")


def prepare(s, pending):
    """What `_schedule_profile` prepares between the dispatch and the
    decisions."""
    return s.cache.prepare_rows(s.queue.in_flight_states(pending))


def bind_cycle(side, plan: dict, prepared: bool, nominate=(),
               before=None, between=None, seen=None) -> dict:
    """One cycle of `side` with the decisions `plan` gives: the pop,
    the preparing (where `prepared`), and the real `_apply_phase`. A
    pod the plan binds wins on that node, one it backs off loses as an
    extender error does, every other loses under the plan's reasons, and
    those in `nominate` are nominated in place on their way back.
    `before` runs between the pop and the preparing, `between` between
    the preparing and the bind loop: where the device would decide.
    Returns the cycle's three counts."""
    s = side.s
    profile = s._profile_order[0]
    framework = s.frameworks[profile]
    filters = framework.filter_names
    s.last_cycle_counts, s.last_nominations = {}, []
    pending = s.queue.pop_ready()
    nodes, existing = s.cache.nodes(), s.cache.existing_pods()
    if before is not None:
        before(pending)
    rows = prepare(s, pending) if prepared else None
    if rows is not None:
        for pod, row in zip(pending, rows):
            if row is not None:
                # the kept dict is the pod as it stands, whatever
                # happened to it since it was journaled
                state = pod_to_state(pod)
                assert row[0] == state
                assert row[1] in (None, json_bytes(state))
                if seen is not None:
                    seen.append(row[0])
    if between is not None:
        between(pending)
    index = {n.name: i for i, n in enumerate(nodes)}
    assignment = np.full(len(pending), -1, np.int32)
    nominated = np.full(len(pending), -1, np.int32)
    rejects = np.zeros((len(pending), len(filters)), np.int32)
    backed_off = {}
    for i, pod in enumerate(pending):
        what = plan.get(pod.uid, ("park", ()))
        if what[0] == "bind":
            assignment[i] = index[what[1]]
        elif what[0] == "backoff":
            backed_off[i] = "extender down"
        else:
            for reason in what[1]:
                rejects[i, filters.index(reason)] = 1
            if pod.uid in nominate:
                nominated[i] = index[NODES[len(pod.uid) % len(NODES)]]
    rec = s.flight.start(profile)
    s._apply_phase(
        profile, framework, pending, nodes, existing, assignment,
        np.zeros(len(pending), bool), backed_off, lambda: rejects,
        lambda: (nominated, np.zeros(len(existing), bool)),
        CycleStats(), side.clock(), rec, side.clock(), rows,
    )
    if side.state is not None:
        side.state.maybe_snapshot()
    return {k: rec.counts[k] for k in COUNTS}


def files(side) -> dict:
    """Every journal segment and snapshot of the side's state
    directory, by name: a segment's bytes as they lie, a snapshot's
    body (its head is the CRC and the length) with the one value that
    is the wall clock's taken out."""
    side.state.journal.flush()
    out = {}
    for name in sorted(os.listdir(side.path)):
        path = os.path.join(side.path, name)
        if os.path.isfile(path):
            with open(path, "rb") as fh:
                out[name] = fh.read()
            if name.endswith(".snap"):
                out[name] = re.sub(
                    rb'"taken_wall":[0-9.e+]+', b"", out[name][20:])
    return out


def assert_same_stores(a, b, where: str) -> None:
    assert a.view() == b.view(), where
    assert state_digest(a.s.queue, a.s.cache) \
        == state_digest(b.s.queue, b.s.cache), where


def assert_same_journal(a, b) -> None:
    """The two state directories hold the same files with the same
    bytes, the two sides serialise to the same snapshot bodies, and a
    restore of either gives what both hold."""
    fa, fb = files(a), files(b)
    assert list(fa) == list(fb)
    for name in fa:
        assert fa[name] == fb[name], name
    assert any(name.startswith("wal-") for name in fa)
    live = {k: a.view()[k] for k in ("cache", "queue")}
    assert a.restored() == live and b.restored() == live
    assert a.s.cache.dump_state_json() == b.s.cache.dump_state_json()
    assert a.s.queue.dump_state_json() == b.s.queue.dump_state_json()


def pair(tmp_path, interval):
    durable = interval is not None
    return (Side(tmp_path / "prepared" if durable else None, interval),
            Side(tmp_path / "in-the-loop" if durable else None, interval))


@pytest.mark.parametrize("mode", list(MODES))
@pytest.mark.parametrize("seed", range(8))
def test_a_stream_bound_from_prepared_rows_equals_it_bound_in_the_loop(
        tmp_path, seed, mode):
    interval = MODES[mode]
    a, b = pair(tmp_path, interval)
    try:
        stream, noms = Stream(seed), random.Random(seed)
        first = nodes_request()
        a.grouped(first)
        b.grouped(first)
        total = dict.fromkeys(COUNTS, 0)
        seen: list = []
        for step in range(14):
            request = stream.request()
            a.grouped(request)
            b.grouped(request)
            for side in (a, b):
                side.clock.t += 0.4
            plan = stream.plan()
            nominate = {uid for uid, what in sorted(plan.items())
                        if what[0] == "park" and noms.random() < 0.5}
            got = bind_cycle(a, plan, True, nominate, seen=seen)
            none = bind_cycle(b, plan, False, nominate)
            assert none == dict.fromkeys(COUNTS, 0)
            for k in COUNTS:
                total[k] += got[k]
            pause = stream.rng.choice((0.3, 2.5, 9.0))
            for side in (a, b):
                side.clock.t += pause
            assert_same_stores(a, b, f"after cycle {step}")
        if interval is None:
            assert total == dict.fromkeys(COUNTS, 0) and not seen
            return
        # rows were handed over (every one of them checked against
        # `pod_to_state` where it was prepared: `bind_cycle`)
        assert 0 < total["rows_prepared_used"] <= total["rows_prepared"]
        assert len(seen) == total["rows_prepared"]
        assert_same_journal(a, b)
        if interval:
            assert a.state.last_snapshot and b.state.last_snapshot
    finally:
        a.close()
        b.close()


def wave(sides, uids, version: int = 1) -> None:
    req = pb.UpdateRequest()
    for uid in uids:
        add_event(req.pod_adds, make_pod(uid, version))
    for side in sides:
        side.grouped(req)


def assumes(side) -> dict:
    """uid -> the pod of every `c.assume` record the journal holds."""
    return {data["pod"]["m"]["u"]: data["pod"]
            for op, _t, data in expand(side.records()) if op == "c.assume"}


def test_a_pod_nominated_in_place_binds_a_cycle_later_from_its_kept_dict(
        tmp_path):
    """The loser loop writes `nominated_node_name` into the pod it then
    requeues, so the dict that requeue keeps carries the nomination; the
    cycle that binds the pod hands that dict over, and the record says
    what a `pod_to_state` at bind time says."""
    a, b = pair(tmp_path, 15)
    try:
        for side in (a, b):
            side.grouped(nodes_request())
        uids = [f"w{i}" for i in range(5)]
        wave((a, b), uids)
        plan = {u: ("bind", NODES[0]) for u in uids[:3]}
        plan["w3"] = plan["w4"] = ("park", ("NodeResourcesFit",))
        for side, prepared in ((a, True), (b, False)):
            bind_cycle(side, plan, prepared, nominate={"w3"})
            parked = side.s.queue.dump_state()["unschedulable"]
            assert [e["pod"].get("nom") for e in parked] == [
                NODES[2], None]
            # a bound pod goes: the event cures NodeResourcesFit, and
            # the backoff of the first attempt runs out
            side.s.on_pods_delete(["w0"])
            side.clock.t += 5.0
        plan = {"w3": ("bind", NODES[4]), "w4": ("bind", NODES[5])}
        seen: list = []
        got = bind_cycle(a, plan, True, seen=seen)
        bind_cycle(b, plan, False)
        assert got == {"rows_prepared": 2, "rows_prepared_used": 2,
                       "rows_prepared_fallback": 0}
        assert [s.get("nom") for s in seen] == [NODES[2], None]
        assert assumes(a)["w3"]["nom"] == NODES[2]
        assert assumes(a) == assumes(b)
        assert_same_stores(a, b, "after the second cycle")
        assert_same_journal(a, b)
    finally:
        a.close()
        b.close()


def refreshed(uid):
    """An `Update` lands for `uid`: the entry takes another object."""
    def hook(side):
        side.s.on_pods_update([(make_pod(uid, 7), "")])
    return hook


def retired(uid):
    def hook(side):
        side.s.queue.retire_in_flight([uid])
    return hook


# how a row fails to be handed over -> (a hook before the pop, one
# between the pop and the preparing, one where the device would decide),
# and what the cycle of six winners then counts
FALLBACKS = {
    "refreshed-while-the-device-ran": (
        (None, None, refreshed("w2")), (6, 5, 1)),
    "refreshed-before-the-preparing": (
        (None, refreshed("w2"), None), (5, 5, 1)),
    "entry-gone-while-the-device-ran": (
        (None, None, retired("w4")), (6, 5, 1)),
    "entry-gone-before-the-preparing": (
        (None, retired("w4"), None), (5, 5, 1)),
    # what a compaction leaves on every entry it meets
    "entries-keep-bytes": (
        (lambda side: side.s.queue.dump_state_json(), None, None),
        (0, 0, 6)),
}


@pytest.mark.parametrize("interval", [0, 15], ids=["journal-only",
                                                   "compacting"])
@pytest.mark.parametrize("case", list(FALLBACKS))
def test_a_row_that_cannot_be_handed_over_falls_back(
        tmp_path, case, interval):
    (first, before, between), want = FALLBACKS[case]
    a, b = pair(tmp_path, interval)
    try:
        uids = [f"w{i}" for i in range(6)]
        for side in (a, b):
            side.grouped(nodes_request())
        wave((a, b), uids)
        plan = {u: ("bind", NODES[i]) for i, u in enumerate(uids)}
        for side, prepared in ((a, True), (b, False)):
            if first is not None:
                first(side)
            got = bind_cycle(
                side, plan, prepared,
                before=before and (lambda _p, s=side: before(s)),
                between=between and (lambda _p, s=side: between(s)),
            )
            if prepared:
                assert tuple(got.values()) == want
        # the cycle binds the object it popped, not the refreshing one
        assert {p["l"]["v"] for p in (
            d["m"] for d in assumes(a).values())} == {"1"}
        assert assumes(a) == assumes(b) and len(assumes(a)) == 6
        m = a.metrics.bind_rows_prepared
        assert [m.labels(outcome=o)._value.get()
                for o in ("used", "fallback", "unused")] == [
            want[1], want[2], want[0] - want[1]]
        assert_same_stores(a, b, case)
        assert_same_journal(a, b)
    finally:
        a.close()
        b.close()


def test_a_journal_that_never_compacts_gets_the_dict_and_no_fragment(
        tmp_path):
    a, b = pair(tmp_path, 0)
    try:
        for side in (a, b):
            side.grouped(nodes_request())
        wave((a, b), ["w0", "w1", "w2"])
        plan = {u: ("bind", NODES[1]) for u in ("w0", "w1", "w2")}
        halves: list = []
        got = bind_cycle(
            a, plan, True,
            between=lambda pending: halves.extend(
                row[1] for row in prepare(a.s, pending)))
        bind_cycle(b, plan, False)
        assert tuple(got.values()) == (3, 3, 0)
        assert halves == [None] * 3
        assert not a.s.cache._frags and not b.s.cache._frags
        assert_same_stores(a, b, "journal-only")
        assert_same_journal(a, b)
    finally:
        a.close()
        b.close()


def test_with_no_journal_nothing_is_prepared_and_nothing_counted():
    a, b = pair(None, None)
    wave((a, b), ["w0", "w1"])
    for side in (a, b):
        side.grouped(nodes_request())
    assert a.s.queue.in_flight_states(list(a.s.queue.all_pending())) is None
    plan = {"w0": ("bind", NODES[0]), "w1": ("park", ())}
    for side, prepared in ((a, True), (b, False)):
        got = bind_cycle(side, plan, prepared)
        assert got == dict.fromkeys(COUNTS, 0)
    assert_same_stores(a, b, "no state")
    assert a.s.cache.counts()["assumed"] == 1
    assert b"scheduler_bind_rows_prepared_total{" not in a.metrics.expose()


# ---- the two stores, alone --------------------------------------------------


class Recording:
    def __init__(self) -> None:
        self.records: list = []

    def __call__(self, op, t, data) -> None:
        self.records.append((op, t, data))


def counting_codec(monkeypatch) -> list:
    """Every pod `cache.py`'s binding of `pod_to_state` is called on."""
    cache_mod._codec()
    calls: list = []
    inner = cache_mod._pod_to_state
    monkeypatch.setattr(
        cache_mod, "_pod_to_state",
        lambda pod: (calls.append(pod.uid), inner(pod))[1],
    )
    return calls


@pytest.mark.parametrize("compacts", [True, False])
def test_assume_with_a_row_journals_what_assume_without_one_does(
        monkeypatch, compacts):
    calls = counting_codec(monkeypatch)
    pod = make_pod("p", 3)
    out = []
    for with_row in (False, True):
        journal, clock = Recording(), Clock()
        c = SchedulerCache(now=clock)
        c.set_journal(journal, compacts=compacts)
        row = None
        if with_row:
            (row,) = c.prepare_rows([pod_to_state(pod)])
            assert (row[1] is not None) == compacts
        del calls[:]
        c.assume(pod, "node-1", row)
        assert calls == ([] if with_row else ["p"])
        out.append((journal.records, dict(c._frags), c.dump_state()))
    assert out[0] == out[1]
    records, frags, _ = out[0]
    assert records == [
        ("c.assume", 1000.0, {"pod": pod_to_state(pod), "node": "node-1"})]
    assert frags == ({"p": _row_open(
        json_bytes(pod_to_state(pod)), b'"node-1"')} if compacts else {})


def test_assume_without_a_journal_takes_no_row_and_a_refusal_emits_nothing(
        monkeypatch):
    calls = counting_codec(monkeypatch)
    pod = make_pod("p", 1)
    c = SchedulerCache(now=Clock())
    assert c.prepare_rows([pod_to_state(pod)]) is None
    c.assume(pod, "node-0", (pod_to_state(pod), b"never read"))
    assert c.is_assumed("p") and not c._frags and not calls
    journal = Recording()
    c = SchedulerCache(now=Clock(), journal=journal)
    assert c.prepare_rows(None) is None
    c.add_pod(pod, "node-0")
    del journal.records[:], calls[:]
    (row,) = c.prepare_rows([pod_to_state(pod)])
    with pytest.raises(ValueError):
        c.assume(pod, "node-1", row)
    assert not journal.records and not calls
    assert c._frags["p"].endswith(b'"node":"node-0"')


def test_in_flight_states_gives_the_entry_s_dict_for_the_very_object():
    journal = Recording()
    q = SchedulingQueue(now=Clock(), journal=journal)
    pods = [make_pod(f"p{i}", 1) for i in range(4)]
    q.add_many(pods)
    assert q.in_flight_states(pods) == [None] * 4  # queued, not in flight
    popped = q.pop_ready()
    states = q.in_flight_states(popped)
    assert states == [pod_to_state(p) for p in pods]
    # the very dicts the `q.add` records carry: nothing was made anew
    added = [d["pod"] for op, _t, d in journal.records if op == "q.add"]
    assert all(s is d for s, d in zip(states, added))
    q.update(make_pod("p1", 2))  # the entry holds another object now
    q.dump_state_json()  # ... and every entry bytes
    assert q.in_flight_states(popped) == [None] * 4
    stranger = MakePod("x").uid("x").obj()
    assert q.in_flight_states([stranger]) == [None]
    q.set_journal(None)
    assert q.in_flight_states(popped) is None


# ---- served: real cycles, the three counts, a failed cycle ------------------


def confirm(sides, resp) -> None:
    req = pb.UpdateRequest()
    for bound in resp.bindings:
        c = req.bind_confirms.add()
        c.pod_uid, c.node_name = bound.pod_uid, bound.node_name
    for side in sides:
        side.grouped(req)


def test_served_cycles_count_their_rows_and_journal_what_the_loop_did(
        tmp_path, monkeypatch):
    """Real cycles behind two servicers, one of which prepares nothing:
    the counts on the flight record, the `rpc.cycle` span and /metrics;
    an `Update` that lands while the device decides; a cycle that fails
    at the fetch; and at the end the same journal."""
    a, b = pair(tmp_path, 15)
    b.s.cache.prepare_rows = lambda states: None
    recorder = _spans.arm(rate=1.0)
    try:
        for side in (a, b):
            side.grouped(nodes_request())

        def cycle() -> dict:
            ra = a.service.Cycle(pb.CycleRequest(), None)
            rb = b.service.Cycle(pb.CycleRequest(), None)
            assert sorted((x.pod_uid, x.node_name) for x in ra.bindings) \
                == sorted((x.pod_uid, x.node_name) for x in rb.bindings)
            assert_same_stores(a, b, "after a served cycle")
            counts = a.s.flight.last_record().counts
            assert {k: b.s.flight.last_record().counts[k]
                    for k in COUNTS} == dict.fromkeys(COUNTS, 0)
            span = [sp for sp in recorder.snapshot()
                    if sp.name == "rpc.cycle"][-2]
            assert {k: span.attrs[k] for k in COUNTS} \
                == {k: counts[k] for k in COUNTS}
            confirm((a, b), ra)
            return {k: counts[k] for k in COUNTS}, ra

        # every wave within the first pad regime of the existing set
        # (8 rows): one compile a side
        wave((a, b), [f"w{i}" for i in range(3)])
        got, resp = cycle()
        assert len(resp.bindings) == 3
        assert got == {"rows_prepared": 3, "rows_prepared_used": 3,
                       "rows_prepared_fallback": 0}
        m = a.metrics.bind_rows_prepared

        def outcomes() -> list:
            return [m.labels(outcome=o)._value.get()
                    for o in ("used", "fallback", "unused")]

        assert outcomes() == [3, 0, 0]
        assert b'scheduler_bind_rows_prepared_total{outcome="used"} 3.0' \
            in a.metrics.expose()

        # an `Update` refreshes one entry while the device decides
        wave((a, b), [f"x{i}" for i in range(3)])
        inner = a.s.cache.prepare_rows

        def and_an_update_lands(states, side=a, prepare=inner):
            rows = prepare(states)
            side.s.on_pods_update([(make_pod("x1", 5), "")])
            return rows

        a.s.cache.prepare_rows = and_an_update_lands
        b.s.cache.prepare_rows = (
            lambda states: and_an_update_lands(
                states, b, lambda _s: None))
        for side in (a, b):
            side.clock.t += 1.0
        got, resp = cycle()
        assert len(resp.bindings) == 3
        assert got == {"rows_prepared": 3, "rows_prepared_used": 2,
                       "rows_prepared_fallback": 1}
        assert outcomes() == [5, 1, 1]
        a.s.cache.prepare_rows = inner
        b.s.cache.prepare_rows = lambda states: None

        # the fetch fails: the rows go with the cycle
        wave((a, b), [f"y{i}" for i in range(2)])
        fetch = CycleHandle.decisions

        def down(self):
            self._pipe.note_fetch_failure(RuntimeError("tunnel down"))
            self.fetched = True
            self.release()
            raise RuntimeError("tunnel down")

        monkeypatch.setattr(CycleHandle, "decisions", down)
        at = outcomes()
        for side in (a, b):
            side.clock.t += 1.0
            resp = side.service.Cycle(pb.CycleRequest(), None)
            assert not resp.bindings
        assert a.s.flight.last_record().counts["aborted"] == 1
        assert {k: a.s.flight.last_record().counts[k] for k in COUNTS} == {
            "rows_prepared": 2, "rows_prepared_used": 0,
            "rows_prepared_fallback": 0}
        assert outcomes() == [at[0], at[1], at[2] + 2]
        assert_same_stores(a, b, "after the failed cycle")
        monkeypatch.setattr(CycleHandle, "decisions", fetch)
        for side in (a, b):
            side.clock.t += 20.0  # the backoff of the failed attempt
        got, resp = cycle()
        assert len(resp.bindings) == 2
        # the rows of the dicts the failed cycle's requeue kept
        assert got == {"rows_prepared": 2, "rows_prepared_used": 2,
                       "rows_prepared_fallback": 0}
        assert_same_journal(a, b)
    finally:
        _spans.disarm()
        a.close()
        b.close()

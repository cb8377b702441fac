"""Snapshot fragments (state/codec.pod_fragment, the cache's `_frags`,
`_QueuedPod.frag`, `dump_state_json`, `DurableState.snapshot`): a
compaction serialises no pod twice, and the file is the one a full
re-dump would have written.

One scripted run goes through every mutator of both classes. After any
prefix of it a snapshot's body parses to exactly the payload
`dump_state()` gives at that instant, a restore from it has the live
pair's digest, every kept fragment is the pod as the row holds it, and
there are as many fragments as rows."""

import dataclasses
import json
import os
import tempfile

import pytest

from k8s_scheduler_tpu.internal.cache import SchedulerCache
from k8s_scheduler_tpu.internal.queue import (
    EVENT_NODE_ADD,
    SchedulingQueue,
)
from k8s_scheduler_tpu.models import MakeNode, MakePod
from k8s_scheduler_tpu.state import DurableState, state_digest
from k8s_scheduler_tpu.state.codec import pod_to_state
from k8s_scheduler_tpu.state.journal import FORMAT_VERSION
from k8s_scheduler_tpu.state.snapshot import (
    _HEAD,
    read_snapshot,
    write_snapshot,
)

HEAD_FIELDS = ("format_version", "taken_mono", "taken_wall",
               "clean_shutdown", "journal_from")


class Clock:
    def __init__(self) -> None:
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t


class World:
    """A queue and a cache on one pinned clock, journaled into `path`
    (or not at all), with the pods and nodes the script names."""

    def __init__(self, path=None, interval=15.0) -> None:
        self.clock = Clock()
        self.q = SchedulingQueue(
            initial_backoff_seconds=0.5, max_backoff_seconds=4.0,
            unschedulable_timeout_seconds=30.0, now=self.clock,
        )
        self.c = SchedulerCache(assumed_pod_ttl_seconds=2.0, now=self.clock)
        self.st = None
        if path is not None:
            # no test lets the interval pass: each compaction is asked for
            self.st = DurableState(
                str(path), snapshot_interval_seconds=interval,
                now=self.clock)
            self.st.attach(self.q, self.c)
        # p*: through the queue; a*: assumed; b*: seen bound
        self.pods = {
            name: MakePod(name).req({"cpu": "1"}).labels({"v": "1"}).obj()
            for name in [f"p{i}" for i in range(9)]
            + [f"a{i}" for i in range(5)] + ["b0", "b1"]
        }
        self.nodes = {
            n: MakeNode(n).capacity({"cpu": "64"}).obj()
            for n in ("n0", "n1", "n2")
        }

    def changed(self, name: str, **labels) -> object:
        """The pod under the same uid with other labels: an update."""
        old = self.pods[name]
        new = MakePod(name).req({"cpu": "1"}).labels(labels).obj()
        new = dataclasses.replace(
            new, metadata=dataclasses.replace(new.metadata, uid=old.uid))
        self.pods[name] = new
        return new

    def tick(self, dt: float) -> None:
        self.clock.t += dt


def p(w, *names):
    return [w.pods[n] for n in names]


def nominate_then_requeue(w):
    # core/scheduler.py's loser loop: the one in-place mutation of a
    # Pod, and the requeue that journals the pod anew
    pod = w.pods["p3"]
    pod.nominated_node_name = "n1"
    w.q.requeue_unschedulable(pod, reasons=("NodeResourcesFit",))


def refused_assume(w):
    with pytest.raises(ValueError):
        w.c.assume(w.pods["b1"], "n1")


STEPS = [
    ("nodes: add three", lambda w: [
        w.c.add_node(n) for n in w.nodes.values()]),
    ("node: update", lambda w: w.c.update_node(
        MakeNode("n1").capacity({"cpu": "32"}).labels({"z": "a"}).obj())),
    ("node: remove", lambda w: w.c.remove_node("n2")),
    ("q: add six", lambda w: [
        w.q.add(x) for x in p(w, "p0", "p1", "p2", "p3", "p4", "p5")]),
    ("q: update an active pod", lambda w: w.q.update(
        w.changed("p0", v="2"))),
    ("q: pop", lambda w: w.q.pop_ready()),
    ("q: update an in-flight pod", lambda w: w.q.update(
        w.changed("p1", v="2", w="x"))),
    ("q: requeue two unschedulable", lambda w: [
        w.q.requeue_unschedulable(w.pods["p0"], ("NodeResourcesFit",)),
        w.q.requeue_unschedulable(w.pods["p1"], "NodeAffinity")]),
    ("q: update a parked pod", lambda w: w.q.update(
        w.changed("p0", v="3"))),
    ("q: requeue backoff", lambda w: w.q.requeue_backoff(w.pods["p2"])),
    ("q: nominate in place, then requeue", nominate_then_requeue),
    ("q: delete a pending pod", lambda w: w.q.delete(w.pods["p1"].uid)),
    ("q: add two, pop and hold", lambda w: [
        w.q.add(w.pods["p6"]), w.q.add(w.pods["p7"]),
        w.q.pop_ready(hold=True)]),
    ("q: delete an in-flight pod", lambda w: w.q.delete(w.pods["p4"].uid)),
    ("q: requeue the deleted one", lambda w: w.q.requeue_unschedulable(
        w.pods["p4"], ("NodeResourcesFit",))),
    ("q: retire", lambda w: w.q.retire_in_flight([w.pods["p5"].uid])),
    ("q: move on an event", lambda w: w.q.move_all_to_active_or_backoff(
        EVENT_NODE_ADD)),
    ("q: flush backoff", lambda w: [w.tick(5.0), w.q.flush_backoff()]),
    ("q: park one, flush the timeout", lambda w: [
        w.q.requeue_unschedulable(w.pods["p6"], ("NodeAffinity",)),
        w.tick(31.0), w.q.flush_unschedulable_timeout()]),
    ("q: update an unknown pod", lambda w: w.q.update(w.pods["p8"])),
    ("q: recover in flight", lambda w: w.q.recover_in_flight()),
    ("c: add two bound", lambda w: [
        w.c.add_pod(x, "n0") for x in p(w, "b0", "b1")]),
    ("c: assume five", lambda w: [
        w.c.assume(x, "n1") for x in p(w, "a0", "a1", "a2", "a3", "a4")]),
    ("c: assume a bound pod is refused", refused_assume),
    ("c: finish three", lambda w: [
        w.c.finish_binding(x.uid) for x in p(w, "a0", "a1", "a2")]),
    ("c: confirm", lambda w: w.c.confirm(w.pods["a0"].uid)),
    ("c: confirm on its node", lambda w: w.c.confirm(
        w.pods["a1"].uid, "n1")),
    ("c: confirm on another node", lambda w: w.c.confirm(
        w.pods["a2"].uid, "n0")),
    ("c: forget", lambda w: w.c.forget(w.pods["a3"].uid)),
    ("c: expire", lambda w: [w.tick(3.0), w.c.cleanup_expired()]),
    ("c: remove a bound and a confirmed pod", lambda w: [
        w.c.remove_pod(w.pods["b0"].uid),
        w.c.remove_pod(w.pods["a0"].uid)]),
    ("c: an assumed pod is seen bound", lambda w: w.c.add_pod(
        w.pods["a4"], "n1")),
    ("c: a bound pod is updated", lambda w: w.c.add_pod(
        w.changed("b1", v="9"), "n0")),
]
IDS = [name for name, _ in STEPS]


def queue_entries(q):
    return [e for tier in (q._active, q._backoff, q._unschedulable,
                           q._in_flight) for e in tier.values()]


def assert_fragments_are_the_rows(w, journaled=True):
    """(d): as many kept fragments as live rows, each the pod as its
    row holds it now (a dict until a compaction met it, bytes after)."""
    def as_state(frag):
        return json.loads(frag) if type(frag) is bytes else frag

    held = {**{u: pod for u, (pod, _n) in w.c._bound.items()},
            **{u: a.pod for u, a in w.c._assumed.items()}}
    if not journaled:
        assert w.c._frags == {}
        assert all(e.frag is None for e in queue_entries(w.q))
        return
    assert set(w.c._frags) == set(held)
    on = {**{u: n for u, (_p, n) in w.c._bound.items()},
          **{u: a.node_name for u, a in w.c._assumed.items()}}
    for uid, pod in held.items():
        # the cache's fragment is how the row opens, made at entry
        assert json.loads(w.c._frags[uid] + b"}") == {
            "pod": pod_to_state(pod), "node": on[uid]}, uid
    for e in queue_entries(w.q):
        assert as_state(e.frag) == pod_to_state(e.pod), e.pod.name


def body_of(path) -> bytes:
    with open(path, "rb") as f:
        blob = f.read()
    magic, version, _crc, length = _HEAD.unpack_from(blob, 0)
    assert version == FORMAT_VERSION == 1
    assert len(blob) == _HEAD.size + length
    return blob[_HEAD.size:]


def assert_snapshot_is_the_full_dump(w, tmp_path):
    """(a), (b) and the second half of (e): the file `snapshot()` wrote
    is, byte for byte, the one `write_snapshot` makes of the payload a
    full `dump_state()` gives under the same pinned clock; it passes
    `read_snapshot`; a restore from it has the live pair's digest."""
    live = {"queue": w.q.dump_state(), "cache": w.c.dump_state()}
    path = w.st.snapshot()
    got = read_snapshot(path)
    assert {k: got[k] for k in live} == live
    assert sorted(got) == sorted(HEAD_FIELDS + tuple(live))
    assert got["taken_mono"] == w.clock.t and got["format_version"] == 1
    # what the parent's writer makes of the same payload, key for key
    ref, nbytes = write_snapshot(
        tempfile.mkdtemp(dir=tmp_path),
        {**{k: got[k] for k in HEAD_FIELDS}, **live})
    assert body_of(ref) == body_of(path)
    assert nbytes == w.st.last_snapshot["bytes"] == os.path.getsize(path)
    # the dump changed nothing
    assert {"queue": w.q.dump_state(), "cache": w.c.dump_state()} == live
    q2, c2 = SchedulingQueue(now=w.clock), SchedulerCache(now=w.clock)
    stats = DurableState(
        w.st.dir, snapshot_interval_seconds=0).restore_into(q2, c2)
    assert stats["snapshot"] and stats["records_replayed"] == 0
    assert state_digest(q2, c2) == state_digest(w.q, w.c)
    return w.st.last_snapshot


@pytest.mark.parametrize("upto", range(len(STEPS)), ids=IDS)
def test_a_snapshot_after_any_prefix_is_the_full_dump(tmp_path, upto):
    """One compaction, after the first `upto + 1` steps: every row is
    serialised from the dict its last record carried."""
    w = World(tmp_path / "state")
    for _name, step in STEPS[: upto + 1]:
        step(w)
        assert_fragments_are_the_rows(w)
    last = assert_snapshot_is_the_full_dump(w, tmp_path)
    # the queue's entries wait for a compaction; the cache's rows came
    # serialised
    assert last["rows_encoded"] == len(queue_entries(w.q))
    assert last["rows"] == last["rows_encoded"] + len(w.c._bound) + len(
        w.c._assumed)
    assert_fragments_are_the_rows(w)
    w.st.seal()


def test_a_snapshot_after_every_step_splices_what_the_last_one_kept(
        tmp_path):
    """A compaction after EVERY step: entries the last one met go in as
    its bytes, whatever happened to their scalar fields and their tier
    since, and only entries a step journaled anew are serialised."""
    w = World(tmp_path / "state")
    total = 0
    for name, step in STEPS:
        before = {id(e): e.frag for e in queue_entries(w.q)}
        step(w)
        assert_fragments_are_the_rows(w)
        now = {id(e): e.frag for e in queue_entries(w.q)}
        fresh = sum(1 for f in now.values() if type(f) is not bytes)
        # a kept fragment is never serialised again: an entry's bytes
        # were its bytes before the step
        assert all(before.get(k) is f
                   for k, f in now.items() if type(f) is bytes), name
        now.update(w.c._frags)
        last = assert_snapshot_is_the_full_dump(w, tmp_path)
        assert last["rows_encoded"] == fresh, name
        assert last["rows"] == len(now), name
        total += fresh
        assert w.st.rows_encoded == total
        assert_fragments_are_the_rows(w)
    # the queue's pods were serialised by compactions, far fewer times
    # than the compactions they lived through
    assert 9 <= total < 3 * 9
    w.st.seal()


def test_a_second_compaction_encodes_nothing_and_k_new_pods_at_most_k(
        tmp_path):
    """(c)."""
    w = World(tmp_path / "state")
    for _name, step in STEPS:
        step(w)
    first = assert_snapshot_is_the_full_dump(w, tmp_path)
    assert first["rows"] > first["rows_encoded"] == len(
        queue_entries(w.q)) > 0
    second = assert_snapshot_is_the_full_dump(w, tmp_path)
    assert (second["rows"], second["rows_encoded"]) == (first["rows"], 0)
    # scalar fields move without a pod record: still nothing to encode
    w.q.pop_ready()
    w.tick(40.0)
    w.q.flush_unschedulable_timeout()
    w.c.finish_binding(next(iter(w.c._assumed), ""))
    third = assert_snapshot_is_the_full_dump(w, tmp_path)
    assert third["rows_encoded"] == 0
    k = 7
    for i in range(k):
        w.q.add(MakePod(f"late{i}").req({"cpu": "1"}).obj())
    fourth = assert_snapshot_is_the_full_dump(w, tmp_path)
    assert fourth["rows_encoded"] == k
    assert fourth["rows"] == third["rows"] + k
    assert w.st.rows_encoded == first["rows_encoded"] + k
    for part in ("dump_s", "write_s", "flush_s", "prune_s"):
        assert fourth[part] >= 0.0
    assert sum(fourth[x] for x in (
        "dump_s", "write_s", "flush_s", "prune_s")) <= fourth[
            "seconds"] + 1e-4
    assert w.st.status()["last_snapshot"] == fourth
    w.st.seal()


def test_a_restored_row_has_no_fragment_until_a_compaction_meets_it(
        tmp_path):
    """`load_state` restores rows with nothing kept: the first
    compaction of the new process serialises each once, from the pod,
    and the second none."""
    w = World(tmp_path / "state")
    for _name, step in STEPS:
        step(w)
    digest = state_digest(w.q, w.c)
    w.st.seal()
    w2 = World()
    w2.clock.t = w.clock.t
    w2.st = DurableState(str(tmp_path / "state"),
                         snapshot_interval_seconds=15, now=w2.clock)
    w2.st.attach(w2.q, w2.c)
    assert state_digest(w2.q, w2.c) == digest
    assert w2.c._frags == {}
    assert all(e.frag is None for e in queue_entries(w2.q))
    first = assert_snapshot_is_the_full_dump(w2, tmp_path)
    assert first["rows_encoded"] == first["rows"] > 0
    assert_fragments_are_the_rows(w2)
    assert assert_snapshot_is_the_full_dump(
        w2, tmp_path)["rows_encoded"] == 0
    w2.st.seal()


def test_a_file_the_parents_writer_wrote_restores(tmp_path):
    """(e), first half: `write_snapshot(dir, payload)` is the writer
    every snapshot before this change came from; its file restores to
    the state it was dumped from."""
    w = World(tmp_path / "live")
    for _name, step in STEPS:
        step(w)
    payload = {
        "format_version": 1, "taken_mono": w.clock.t, "taken_wall": 0.0,
        "clean_shutdown": True, "journal_from": 0,
        "queue": w.q.dump_state(), "cache": w.c.dump_state(),
    }
    old = tmp_path / "old"
    os.makedirs(old)
    path, _n = write_snapshot(str(old), payload)
    assert read_snapshot(path) == payload
    q2, c2 = SchedulingQueue(now=w.clock), SchedulerCache(now=w.clock)
    stats = DurableState(
        str(old), snapshot_interval_seconds=0).restore_into(q2, c2)
    assert stats["snapshot"] and stats["clean_shutdown"]
    assert state_digest(q2, c2) == state_digest(w.q, w.c)
    w.st.seal()


def test_with_no_journal_attached_no_fragment_is_made():
    """(f): a deployment with no `stateDir` builds no state dict in its
    mutators and keeps nothing."""
    w = World()
    for _name, step in STEPS:
        step(w)
        assert_fragments_are_the_rows(w, journaled=False)
    assert len(w.c._bound) + len(w.c._assumed) + len(
        queue_entries(w.q)) > 0


def test_a_journal_that_never_compacts_makes_no_fragment_at_entry(tmp_path):
    """`snapshotInterval: 0` is journal only: the one snapshot of such a
    process is the seal at exit, so the cache serialises nothing where
    it journals a pod, and whichever snapshot does come serialises every
    row from its pod and is the full dump all the same."""
    w = World(tmp_path / "state", interval=0)
    for _name, step in STEPS:
        step(w)
        assert w.c._frags == {}
    last = assert_snapshot_is_the_full_dump(w, tmp_path)
    assert last["rows_encoded"] == last["rows"] > len(queue_entries(w.q))
    # what that snapshot kept goes when its pod is journaled anew
    uid = w.pods["b1"].uid
    assert type(w.c._frags[uid]) is bytes
    w.c.add_pod(w.changed("b1", v="11"), "n0")
    assert uid not in w.c._frags
    assert assert_snapshot_is_the_full_dump(
        w, tmp_path)["rows_encoded"] == 1
    w.st.seal()


def test_a_detached_journal_leaves_no_stale_fragment(tmp_path):
    """The degradation ladder detaches the journal with plain stores; a
    row that changes afterwards must not keep the fragment of the pod
    it held before."""
    w = World(tmp_path / "state")
    for _name, step in STEPS:
        step(w)
    w.st.snapshot()  # fragments are bytes now
    w.st.detach()
    assert w.q._journal is None and w.c._journal is None
    uid = w.pods["b1"].uid
    assert type(w.c._frags[uid]) is bytes
    w.c.add_pod(w.changed("b1", v="10"), "n0")
    assert uid not in w.c._frags
    entry = next(iter(w.q._active.values()))
    w.q.update(dataclasses.replace(entry.pod))
    assert entry.frag is None
    body, rows, encoded = w.c.dump_state_json()
    assert json.loads(body) == w.c.dump_state() and encoded == 1
    body, rows, encoded = w.q.dump_state_json()
    assert json.loads(body) == w.q.dump_state() and encoded == 1


def test_snapshot_rows_are_counted_on_metrics(tmp_path):
    from k8s_scheduler_tpu.metrics import SchedulerMetrics

    m = SchedulerMetrics()
    w = World()
    w.st = DurableState(str(tmp_path), snapshot_interval_seconds=15,
                        metrics=m, now=w.clock)
    w.st.attach(w.q, w.c)
    for _name, step in STEPS:
        step(w)
    w.st.snapshot()
    rows, first = (w.st.last_snapshot[k] for k in ("rows", "rows_encoded"))
    assert 0 < first < rows
    w.q.add(MakePod("one-more").obj())
    w.st.snapshot()
    text = m.expose().decode()
    assert (f'scheduler_snapshot_rows_total{{source="encoded"}} '
            f'{first + 1}.0') in text
    assert (f'scheduler_snapshot_rows_total{{source="kept"}} '
            f'{rows - first + rows}.0') in text
    w.st.seal()


def test_snapshot_rows_encoded_per_cycle_names_every_cell_and_a_kept_count():
    """The metric over the count is data: ONE layer file and ONE
    `per_layer` entry that list the six cells of PR 45 (every YAML sets
    `snapshotInterval`, so every record carries the count), of the
    shape of `gc_sweeps_per_cycle`, over a count the records keep."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = "snapshot_rows_encoded_per_cycle"
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    layers = os.path.join(repo, "benchmark", "layers")
    with open(os.path.join(layers, name + ".json")) as f:
        spec = json.load(f)
    with open(os.path.join(layers, "gc_sweeps_per_cycle.json")) as f:
        model = json.load(f)
    # the six cells the benchmark had when PR 45 registered the metric:
    # a cell a later PR adds takes the entries its own PR names (an
    # accepted layer file is no later PR's to edit)
    cells = [w["name"] for w in bench["workloads"]][:6]
    assert entry["workloads"] == spec["workloads"] == cells
    assert cells[-1] == "sp5000-preempt.sat"
    own = ("name", "layer", "select", "what", "workloads")
    assert {k: v for k, v in spec.items() if k not in own} == {
        k: v for k, v in model.items() if k not in own}
    assert spec["select"] == ["snapshot_rows_encoded"]
    assert (spec["source_kind"], spec["reduce"], entry["source"]) == (
        "flight_count", "rise_per_cycle", "program_counter")
    assert (spec["unit"], entry["unit"], entry["better"]) == (
        "1", "1", "lower")
    assert entry["moves"] == spec["moves"] == "pods_bound_per_s"
    # the layer is the code that keeps the count and does the work
    assert entry["layer"] == spec["layer"] == (
        "durable state (state/manager.py, internal/cache.py, "
        "internal/queue.py)")
    # the reader finds the count where the records keep it, and nothing
    # where they do not (the parent's records)
    import sys
    sys.path.insert(0, repo)
    from benchmark.lib import reduce
    flight = [{"counts": {"snapshot_rows_encoded": n}}
              for n in (100, 100, 160, 160, 160, 220)]
    assert reduce.read_layer(spec, {"flight": flight}) == 24.0
    assert reduce.read_layer(
        spec, {"flight": [{"counts": {}}, {"counts": {}}]}) is None

"""Durable-state layer: journal framing, torn-tail crash consistency,
segment rotation/cut semantics, format versioning, codec round-trips,
and the no-fsync-on-the-append-path contract (state/ package)."""

import os
import struct
import threading
import zlib

import pytest

from k8s_scheduler_tpu.internal.cache import SchedulerCache
from k8s_scheduler_tpu.internal.queue import SchedulingQueue
from k8s_scheduler_tpu.models import MakeNode, MakePod
from k8s_scheduler_tpu.state import (
    DurableState,
    Journal,
    StateVersionError,
    replay_dir,
)
from k8s_scheduler_tpu.state.journal import (
    FORMAT_VERSION,
    encode_record,
    read_segment,
    segment_header,
    segment_indices,
    segment_path,
)


class FakeClock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


def _drain(journal):
    journal.flush()
    journal.close()


def test_journal_round_trip(tmp_path):
    d = str(tmp_path)
    j = Journal(d)
    recs = [("q.add", 1.5, {"pod": {"m": {"n": f"p{i}"}}}) for i in range(8)]
    for op, t, data in recs:
        j.append(op, t, data)
    _drain(j)
    assert list(replay_dir(d)) == recs


def test_torn_final_record_discarded_at_every_byte_offset(tmp_path):
    """The crash-consistency core claim: truncate the segment at EVERY
    byte offset inside the final record; replay must never raise and
    must yield exactly the records before it — a torn record is
    discarded whole, never partially applied."""
    d = str(tmp_path / "src")
    j = Journal(d)
    for i in range(5):
        j.append("q.add", float(i), {"pod": {"m": {"n": f"pod-{i}"}}})
    _drain(j)
    (idx,) = segment_indices(d)
    blob = open(segment_path(d, idx), "rb").read()
    final = encode_record("q.add", 4.0, {"pod": {"m": {"n": "pod-4"}}})
    body_end = len(blob)
    body_start = body_end - len(final)
    tdir = str(tmp_path / "torn")
    os.makedirs(tdir)
    tpath = segment_path(tdir, 0)
    for cut in range(body_start, body_end):
        with open(tpath, "wb") as f:
            f.write(blob[:cut])
        got = list(read_segment(tpath))
        assert len(got) == 4, f"cut at byte {cut}"
        assert [r[2]["pod"]["m"]["n"] for r in got] == [
            f"pod-{i}" for i in range(4)
        ]
    # untouched file yields all 5
    with open(tpath, "wb") as f:
        f.write(blob)
    assert len(list(read_segment(tpath))) == 5


def test_mid_segment_corruption_raises_not_truncates(tmp_path):
    """A bad record FOLLOWED BY MORE BYTES is not a crash tear (tears
    can only sit at EOF — every batch is fsynced before ack): replaying
    past a hole would silently diverge, so it must raise."""
    from k8s_scheduler_tpu.state import StateCorruption

    d = str(tmp_path)
    j = Journal(d)
    for i in range(5):
        j.append("q.add", float(i), {"pod": {"m": {"n": f"pod-{i}"}}})
    _drain(j)
    (idx,) = segment_indices(d)
    p = segment_path(d, idx)
    blob = bytearray(open(p, "rb").read())
    # flip one payload byte of the FIRST record (well before EOF)
    first = encode_record("q.add", 0.0, {"pod": {"m": {"n": "pod-0"}}})
    header_len = len(segment_header())
    blob[header_len + 8 + 2] ^= 0xFF
    with open(p, "wb") as f:
        f.write(blob)
    with pytest.raises(StateCorruption, match="mid-segment"):
        list(read_segment(p))
    assert len(first) > 8  # framing sanity for the offset above


def test_torn_segment_header_is_empty_not_error(tmp_path):
    p = segment_path(str(tmp_path), 0)
    header = segment_header()
    for cut in range(len(header)):
        with open(p, "wb") as f:
            f.write(header[:cut])
        assert list(read_segment(p)) == []


def test_future_format_version_refused(tmp_path):
    """A segment stamped by a NEWER build must fail loudly, not be
    misparsed into garbage state."""
    p = segment_path(str(tmp_path), 0)
    body = struct.pack("<8sI", b"TPUSWAL\x00", FORMAT_VERSION + 1)
    with open(p, "wb") as f:
        f.write(body + struct.pack("<I", zlib.crc32(body)))
        f.write(encode_record("q.pop", 0.0, {}))
    with pytest.raises(StateVersionError) as ei:
        list(read_segment(p))
    assert "newer than this build" in str(ei.value)
    # and the manager surfaces it on restore, not silently
    q, c = SchedulingQueue(), SchedulerCache()
    st = DurableState(str(tmp_path / "other"), snapshot_interval_seconds=0)
    st.restore_into(q, c)  # empty dir restores fine
    with pytest.raises(StateVersionError):
        list(replay_dir(str(tmp_path)))


def test_future_snapshot_version_refused(tmp_path):
    from k8s_scheduler_tpu.state.snapshot import (
        SNAPSHOT_MAGIC,
        read_snapshot,
        snapshot_path,
    )

    p = snapshot_path(str(tmp_path), 0)
    body = b"{}"
    with open(p, "wb") as f:
        f.write(
            struct.pack(
                "<8sIII", SNAPSHOT_MAGIC, FORMAT_VERSION + 1,
                zlib.crc32(body), len(body),
            )
        )
        f.write(body)
    with pytest.raises(StateVersionError):
        read_snapshot(p)


def test_segment_rotation_and_cut(tmp_path):
    d = str(tmp_path)
    j = Journal(d, max_segment_bytes=256)
    for i in range(20):
        j.append("q.add", float(i), {"pod": {"m": {"n": f"p{i:02d}"}}})
        if i % 5 == 4:
            # size rotation takes effect at group-commit granularity
            # (the writer checks real bytes after each drained batch)
            j.flush()
    assert len(segment_indices(d)) > 1  # size rotation happened
    # cut: everything after lands strictly in segments >= the cut index
    cut = j.cut()
    for i in range(20, 25):
        j.append("q.add", float(i), {"pod": {"m": {"n": f"p{i:02d}"}}})
    _drain(j)
    pre = [r[2]["pod"]["m"]["n"] for r in replay_dir(d) ]
    assert pre == [f"p{i:02d}" for i in range(25)]  # order preserved
    tail = [r[2]["pod"]["m"]["n"] for r in replay_dir(d, from_index=cut)]
    assert tail == [f"p{i:02d}" for i in range(20, 25)]
    # prune below the cut: only the tail remains
    j2 = Journal(d)
    j2.prune(cut)
    j2.close()
    assert [r[2]["pod"]["m"]["n"] for r in replay_dir(d)] == tail


def test_append_path_never_fsyncs_caller_thread(tmp_path, monkeypatch):
    """The ISSUE acceptance contract: group fsync lives on the writer
    thread only — mutations on the scheduling thread (the bind path)
    must never block on fsync."""
    fsync_threads = []
    real_fsync = os.fsync

    def spy(fd):
        fsync_threads.append(threading.current_thread().name)
        return real_fsync(fd)

    monkeypatch.setattr(os, "fsync", spy)
    q = SchedulingQueue()
    c = SchedulerCache()
    st = DurableState(str(tmp_path), snapshot_interval_seconds=0)
    st.attach(q, c)
    for i in range(50):
        q.add(MakePod(f"p{i}").req({"cpu": "1"}).obj())
        c.add_node(MakeNode(f"n{i}").capacity({"cpu": "8"}).obj())
    pods = q.pop_ready()
    for p in pods[:10]:
        c.assume(p, "n0")
        c.finish_binding(p.uid)
    st.journal.flush()
    assert fsync_threads, "writer thread never fsynced"
    assert set(fsync_threads) == {"journal-writer"}
    st.journal.close()


def test_codec_round_trips_rich_pod_and_node():
    from k8s_scheduler_tpu.models.api import pod_from_dict
    from k8s_scheduler_tpu.state.codec import (
        node_from_state,
        node_to_state,
        pod_from_state,
        pod_to_state,
    )

    pod = pod_from_dict(
        {
            "metadata": {
                "name": "rich",
                "namespace": "ns1",
                "uid": "u-1",
                "labels": {"app": "db", "tier": "backend"},
                "annotations": {"k": "v"},
                "creationTimestamp": 12.5,
            },
            "spec": {
                "containers": [
                    {
                        "name": "main",
                        "image": "img:1",
                        "resources": {
                            "requests": {"cpu": "1500m", "memory": "2Gi"}
                        },
                        "ports": [{"containerPort": 80, "hostPort": 8080}],
                    }
                ],
                "nodeSelector": {"disk": "ssd"},
                "affinity": {
                    "nodeAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": {
                            "nodeSelectorTerms": [
                                {
                                    "matchExpressions": [
                                        {
                                            "key": "zone",
                                            "operator": "In",
                                            "values": ["a", "b"],
                                        }
                                    ]
                                }
                            ]
                        }
                    },
                    "podAntiAffinity": {
                        "requiredDuringSchedulingIgnoredDuringExecution": [
                            {
                                "labelSelector": {
                                    "matchLabels": {"app": "db"}
                                },
                                "topologyKey": "kubernetes.io/hostname",
                            }
                        ]
                    },
                },
                "tolerations": [
                    {"key": "gpu", "operator": "Exists",
                     "effect": "NoSchedule"}
                ],
                "topologySpreadConstraints": [
                    {
                        "maxSkew": 1,
                        "topologyKey": "zone",
                        "whenUnsatisfiable": "DoNotSchedule",
                        "labelSelector": {"matchLabels": {"app": "db"}},
                    }
                ],
                "priority": 100,
                "priorityClassName": "high",
                "preemptionPolicy": "Never",
                "schedulerName": "tpu-scheduler",
                "podGroup": "g1",
            },
            "status": {"nominatedNodeName": "n7"},
        }
    )
    assert pod_from_state(pod_to_state(pod)) == pod

    node = (
        MakeNode("n1")
        .labels({"zone": "a"})
        .capacity({"cpu": "64", "memory": "128Gi"})
        .taint("dedicated", "db", "NoSchedule")
        .obj()
    )
    assert node_from_state(node_to_state(node)) == node


def test_restart_never_appends_into_old_segment(tmp_path):
    """A restarted process opens a fresh segment past everything on
    disk (old tails may be torn); replay glues them in order."""
    d = str(tmp_path)
    j1 = Journal(d)
    j1.append("q.add", 0.0, {"pod": {"m": {"n": "a"}}})
    _drain(j1)
    j2 = Journal(d)
    j2.append("q.add", 1.0, {"pod": {"m": {"n": "b"}}})
    _drain(j2)
    assert len(segment_indices(d)) == 2
    assert [r[2]["pod"]["m"]["n"] for r in replay_dir(d)] == ["a", "b"]


def test_writer_io_failure_fails_loudly_not_silently(tmp_path):
    """A dead disk must not leave append() buffering into a deque
    nobody drains: the writer marks the journal failed, flush() and
    append() raise, close() still joins."""
    import shutil

    from k8s_scheduler_tpu.state import StateError

    d = str(tmp_path / "j")
    j = Journal(d)
    shutil.rmtree(d)  # the writer's next segment open() will fail
    j.append("q.pop", 0.0, {})
    with pytest.raises(StateError, match="writer failed"):
        j.flush()
    assert j.failed is not None
    assert j.status()["failed"] is not None
    with pytest.raises(StateError, match="writer failed"):
        j.append("q.pop", 1.0, {})
    j.close()  # no hang, no raise


def test_manager_degrades_to_stateless_on_journal_failure(tmp_path):
    """DurableState must trade durability for availability: when the
    journal dies mid-run, emitters detach and the scheduler keeps
    mutating state untouched."""
    import shutil

    d = str(tmp_path / "state")
    q, c = SchedulingQueue(), SchedulerCache()
    st = DurableState(d, snapshot_interval_seconds=0)
    st.attach(q, c)
    q.add(MakePod("before").obj())
    st.journal.flush()
    shutil.rmtree(d)
    # POSIX keeps the already-open segment fd writable after the unlink;
    # force a segment switch so the writer must open() in the gone dir
    st.journal.cut()
    q.add(MakePod("buffered").obj())  # buffered; writer dies async
    deadline = __import__("time").monotonic() + 10
    while st.journal.failed is None:
        assert __import__("time").monotonic() < deadline
        __import__("time").sleep(0.01)
    # the NEXT emit hits the failure, detaches, and does not raise
    q.add(MakePod("after-failure").obj())
    assert q._journal is None and c._journal is None
    assert st.status()["sealed"]
    # serving continues: mutations still land in live state
    q.add(MakePod("still-serving").obj())
    assert q.pending_counts()["active"] == 4
    st.journal.close()


# ---- batch group-append (the vectorized apply/bind fold's record) ------


class _Clock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> None:
        self.t += dt


def _pair(clock):
    q = SchedulingQueue(
        initial_backoff_seconds=0.5, max_backoff_seconds=4.0,
        unschedulable_timeout_seconds=30.0, now=clock,
    )
    c = SchedulerCache(assumed_pod_ttl_seconds=2.0, now=clock)
    return q, c


def _drive_fold_trace(state_dir, *, batched, seed=11, n=60):
    """A randomized mutation trace shaped like the apply/bind fold:
    adds, pops, assumes/binds, backoff requeues — journaled either as
    singles or with each chunk grouped under DurableState.batch()."""
    import contextlib
    import random

    from k8s_scheduler_tpu.state import state_digest

    clock = _Clock()
    q, c = _pair(clock)
    st = DurableState(state_dir, snapshot_interval_seconds=0)
    st.attach(q, c)
    c.add_node(MakeNode("n0").capacity({"cpu": "64"}).obj())
    rng = random.Random(seed)
    uid = 0
    for _cycle in range(n):
        clock.tick(rng.random())
        scope = st.batch() if batched else contextlib.nullcontext()
        with scope:
            for _ in range(rng.randint(1, 5)):
                roll = rng.random()
                if roll < 0.5 or uid == 0:
                    pod = MakePod(f"p{uid}").req({"cpu": "1"}).obj()
                    uid += 1
                    q.add(pod)
                elif roll < 0.75:
                    e = q.pop_ready()
                    if e:
                        c.assume(e[0], "n0")
                        c.finish_binding(e[0].uid)
                else:
                    e = q.pop_ready()
                    if e:
                        q.requeue_backoff(e[0])
    st.journal.flush()
    digest = state_digest(q, c)
    st.journal.close()
    return digest


def test_batch_record_digest_identical_to_singles(tmp_path):
    """The group-append contract: the SAME randomized mutation trace
    journaled as one batch record per cycle vs N single records
    restores to a bit-identical state digest — each sub-op replays
    under its own clock value, so nothing (backoff expiries, assumed
    deadlines, tier order) can drift."""
    from k8s_scheduler_tpu.state import state_digest
    from k8s_scheduler_tpu.state.journal import BATCH_OP

    da, db = str(tmp_path / "singles"), str(tmp_path / "batched")
    live_a = _drive_fold_trace(da, batched=False)
    live_b = _drive_fold_trace(db, batched=True)
    assert live_a == live_b  # identical trace: journaling is a shadow

    ops_a = [op for op, _t, _d in replay_dir(da)]
    ops_b = [op for op, _t, _d in replay_dir(db)]
    assert BATCH_OP not in ops_a
    assert BATCH_OP in ops_b          # the variant actually folded
    assert len(ops_b) < len(ops_a)    # fewer records, same state

    for d in (da, db):
        q2 = SchedulingQueue(
            initial_backoff_seconds=0.5, max_backoff_seconds=4.0,
            unschedulable_timeout_seconds=30.0, now=_Clock(),
        )
        c2 = SchedulerCache(assumed_pod_ttl_seconds=2.0, now=_Clock())
        DurableState(d, snapshot_interval_seconds=0).restore_into(q2, c2)
        assert state_digest(q2, c2) == live_a, d


def test_torn_tail_batch_record_discarded_whole(tmp_path):
    """Crash atomicity at batch granularity: truncate the segment at
    EVERY byte offset inside a final BATCH record — replay must yield
    exactly the records before it, never a partially-applied prefix of
    the cycle's fold (the batch is one frame under one CRC)."""
    from k8s_scheduler_tpu.state.journal import (
        BATCH_OP,
        encode_batch_payload,
    )

    d = str(tmp_path / "src")
    j = Journal(d)
    for i in range(3):
        j.append("q.add", float(i), {"pod": {"m": {"n": f"pod-{i}"}}})
    sub_ops = [
        ("c.assume", 3.0 + k, {"uid": f"default/pod-{k}", "node": "n0"})
        for k in range(4)
    ]
    payload = encode_batch_payload(sub_ops)
    j.append(BATCH_OP, 6.0, payload)
    j.flush()
    j.close()
    (idx,) = segment_indices(d)
    blob = open(segment_path(d, idx), "rb").read()
    final = encode_record(BATCH_OP, 6.0, payload)
    body_start = len(blob) - len(final)
    assert blob[body_start:] == final  # framing sanity
    tdir = str(tmp_path / "torn")
    os.makedirs(tdir)
    tpath = segment_path(tdir, 0)
    for cut in range(body_start, len(blob)):
        with open(tpath, "wb") as f:
            f.write(blob[:cut])
        got = list(read_segment(tpath))
        assert [r[0] for r in got] == ["q.add"] * 3, f"cut at byte {cut}"
    with open(tpath, "wb") as f:
        f.write(blob)
    assert [r[0] for r in list(read_segment(tpath))][-1] == BATCH_OP


def test_open_batch_is_invisible_until_scope_exit(tmp_path):
    """kill -9 mid-flush: a batch scope that never exits contributes
    NOTHING durable — the segment bytes captured while the scope is
    open restore to the exact pre-batch state (the fold becomes
    durable atomically at scope exit, or not at all)."""
    import shutil

    from k8s_scheduler_tpu.state import state_digest

    d = str(tmp_path / "live")
    clock = _Clock()
    q, c = _pair(clock)
    st = DurableState(d, snapshot_interval_seconds=0)
    st.attach(q, c)
    q.add(MakePod("before").req({"cpu": "1"}).obj())
    st.journal.flush()
    pre = state_digest(q, c)
    (idx,) = segment_indices(d)

    mid = str(tmp_path / "mid")
    post = str(tmp_path / "post")
    with st.batch():
        q.add(MakePod("in-batch-1").req({"cpu": "1"}).obj())
        q.add(MakePod("in-batch-2").req({"cpu": "1"}).obj())
        # the crash point: nothing of the open batch may be on disk
        st.journal.flush()
        os.makedirs(mid)
        shutil.copy(segment_path(d, idx), segment_path(mid, idx))
    st.journal.flush()
    os.makedirs(post)
    shutil.copy(segment_path(d, idx), segment_path(post, idx))
    st.journal.close()

    q2, c2 = _pair(_Clock())
    DurableState(mid, snapshot_interval_seconds=0).restore_into(q2, c2)
    assert state_digest(q2, c2) == pre
    q3, c3 = _pair(_Clock())
    DurableState(post, snapshot_interval_seconds=0).restore_into(q3, c3)
    assert state_digest(q3, c3) == state_digest(q, c)


def test_debug_state_status_shape(tmp_path):
    q, c = SchedulingQueue(), SchedulerCache()
    st = DurableState(str(tmp_path), snapshot_interval_seconds=0)
    st.attach(q, c)
    q.add(MakePod("p").obj())
    st.journal.flush()
    s = st.status()
    assert s["journal"]["appended"] == 1
    assert s["journal"]["durable"] == 1
    assert s["journal"]["segments"] == 1
    assert s["last_restore"]["records_replayed"] == 0
    st.snapshot()
    s = st.status()
    assert s["last_snapshot"]["bytes"] > 0
    st.seal()
    assert st.status()["sealed"]


# ---- records of an older process: q.pop {"hold": true}, q.retire -----
# no serving code emits them now (ROADMAP D16); a journal may hold them


def test_hold_pop_keeps_buffered_groups_recoverable(tmp_path):
    """A crash while K groups are coalescing must recover EVERY
    buffered group, not just the last pop's: the journaled hold-pop
    accumulates the in-flight set instead of replacing it."""
    clock = FakeClock()
    q = SchedulingQueue(now=clock)
    c = SchedulerCache(now=clock)
    st = DurableState(str(tmp_path / "wal"), snapshot_interval_seconds=0)
    st.attach(q, c)
    q.add(MakePod("p0").req({"cpu": "1"}).obj())
    assert [p.uid for p in q.pop_ready()] == ["default/p0"]
    q.add(MakePod("p1").req({"cpu": "1"}).obj())
    # the second group's pop HOLDS the first group's in-flight entry
    assert [p.uid for p in q.pop_ready(hold=True)] == ["default/p1"]
    # a delete tombstone for a buffered pod must survive the hold-pop
    q.delete("default/p0")
    st.journal.flush()
    st.journal.close()

    q2 = SchedulingQueue(now=clock)
    c2 = SchedulerCache(now=clock)
    st2 = DurableState(
        str(tmp_path / "wal"), snapshot_interval_seconds=0
    )
    st2.attach(q2, c2)
    assert q2.recover_in_flight() == 1  # p1 requeued; p0's tombstone held
    assert [p.uid for p in q2.pop_ready()] == ["default/p1"]
    st2.journal.close()


def test_retire_in_flight_bounds_hold_accumulation(tmp_path):
    """Hold pops only ACCUMULATE the in-flight set; the batch flush
    must retire the pods whose outcomes it applied (journaled, so a
    replayed takeover recovers the same bounded set) — otherwise bound
    pods stay "recoverable" forever and a failover re-binds them."""
    clock = FakeClock()
    q = SchedulingQueue(now=clock)
    c = SchedulerCache(now=clock)
    st = DurableState(str(tmp_path / "wal"), snapshot_interval_seconds=0)
    st.attach(q, c)
    q.add(MakePod("p0").req({"cpu": "1"}).obj())
    q.pop_ready(hold=True)
    q.add(MakePod("p1").req({"cpu": "1"}).obj())
    q.pop_ready(hold=True)
    assert set(q._in_flight) == {"default/p0", "default/p1"}
    # flush applied p0's bind; p1 is still buffered — p0 retires, p1
    # stays recoverable
    q.retire_in_flight(["default/p0", "default/never-in-flight"])
    assert set(q._in_flight) == {"default/p1"}
    st.journal.flush()
    st.journal.close()

    q2 = SchedulingQueue(now=clock)
    c2 = SchedulerCache(now=clock)
    st2 = DurableState(str(tmp_path / "wal"), snapshot_interval_seconds=0)
    st2.attach(q2, c2)
    assert set(q2._in_flight) == {"default/p1"}  # replay reproduces it
    assert q2.recover_in_flight() == 1  # only p1 — p0 is NOT re-bound
    st2.journal.close()

"""Multi-cycle on-device serving: bit-identical equivalence of the
K-cycle device-resident loop (core/cycle.build_packed_multicycle_fn +
Scheduler._schedule_profile_multi) against K sequential single-cycle
dispatches with host bind-folding between them.

Three layers, matching the exactness contract the docstrings state:

- device level: the stacked loop's decisions vs the shared cycle body
  invoked K times with the carry folded on host (including the K=1
  degenerate program and the early-exit-on-drain path);
- scheduler level: randomized arrival traces through a multiCycleK=K
  scheduler vs a K=1 scheduler — identical bind streams, identical
  journal decision-record streams (modulo the q.pop markers, whose
  position is the ONLY thing batching moves), identical state digests,
  and identical per-cycle flight-record outcome counts;
- envelope: workloads that leave the exactness envelope (host ports,
  volumes, affinity, extenders) fall back to sequential dispatches and
  pin the profile out of batching.
"""

from __future__ import annotations

import dataclasses
import random

import numpy as np
import pytest

from k8s_scheduler_tpu.config import SchedulerConfiguration
from k8s_scheduler_tpu.core import Scheduler
from k8s_scheduler_tpu.core.cycle import (
    build_cycle_fn,
    build_packed_multicycle_fn,
    multicycle_unsupported_reason,
)
from k8s_scheduler_tpu.framework.runtime import Framework
from k8s_scheduler_tpu.models import MakeNode, MakePod, packing
from k8s_scheduler_tpu.models.encoding import SnapshotEncoder
from k8s_scheduler_tpu.state import DurableState, state_digest
from k8s_scheduler_tpu.state.journal import replay_dir


class FakeClock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> None:
        self.t += dt


# ---- device level -------------------------------------------------------


def _encode_groups(groups, nodes, existing=(), pod_groups=(),
                   pad_pods=8, pad_nodes=8):
    """Encode each arrival group against the same pre-batch state with
    one long-lived encoder (the scheduler's contract) and return
    (snaps, spec, wbufs, bbufs) stacked for the multi-cycle program."""
    enc = SnapshotEncoder()
    enc.pad_pods = pad_pods
    enc.pad_nodes = pad_nodes
    snaps = [
        enc.encode(nodes, g, existing, pod_groups=pod_groups)
        for g in groups
    ]
    spec = packing.make_spec(snaps[0])
    for s in snaps[1:]:
        assert packing.make_spec(s).key() == spec.key()
    packed = [packing.pack(s, spec) for s in snaps]
    wbufs = np.stack([w for w, _ in packed])
    bbufs = np.stack([b for _, b in packed])
    return snaps, spec, wbufs, bbufs


def _sequential_reference(snaps, fw, **cycle_kw):
    """K sequential single-cycle dispatches of the SAME cycle body with
    the node_requested + gang placed-count carry folded on host — the
    semantics the device loop must reproduce bit-identically."""
    cyc = build_cycle_fn(framework=fw, outputs="latency", **cycle_kw)
    out = []
    node_req = None
    gplaced = None
    for snap in snaps:
        if node_req is not None:
            snap = dataclasses.replace(
                snap,
                node_requested=node_req,
                group_existing_count=(
                    snap.group_existing_count + gplaced
                ),
            )
        dec = cyc(snap)
        a = np.asarray(dec.assignment)
        placed = np.asarray(snap.pod_valid) & (a >= 0)
        G = snap.group_min_member.shape[0]
        pg = np.asarray(snap.pod_group)
        add = np.zeros(G, np.int32)
        np.add.at(add, np.clip(pg, 0, G - 1),
                  np.where((pg >= 0) & placed, 1, 0))
        gplaced = add if gplaced is None else gplaced + add
        node_req = np.asarray(dec.node_requested)
        out.append(dec)
    return out


def _rand_groups(rng, n_groups, nodes):
    groups = []
    uid = 0
    for _ in range(n_groups):
        g = []
        for _ in range(rng.randint(1, 6)):
            cpu = rng.choice(["1", "2", "3"])
            g.append(
                MakePod(f"p{uid}")
                .req({"cpu": cpu, "memory": "1Gi"})
                .obj()
            )
            uid += 1
        groups.append(g)
    return groups


@pytest.mark.parametrize("seed", [0, 3, 11])
@pytest.mark.parametrize("commit_mode", ["rounds", "scan"])
def test_device_loop_matches_sequential_dispatches(seed, commit_mode):
    rng = random.Random(seed)
    nodes = [
        MakeNode(f"n{i}").capacity({"cpu": "4", "memory": "8Gi"}).obj()
        for i in range(5)
    ]
    groups = _rand_groups(rng, 4, nodes)
    snaps, spec, wbufs, bbufs = _encode_groups(groups, nodes)
    assert all(multicycle_unsupported_reason(s) is None for s in snaps)
    fw = Framework.from_config()
    kw = dict(commit_mode=commit_mode, gang_scheduling=True)
    mfn = build_packed_multicycle_fn(spec, framework=fw, k=4, **kw)
    res = mfn(wbufs, bbufs, None, np.int32(4))
    ref = _sequential_reference(snaps, fw, **kw)
    assert int(res.cycles_run) == 4
    for i, (snap, dec) in enumerate(zip(snaps, ref)):
        valid = np.asarray(snap.pod_valid)
        a_ref = np.where(valid, np.asarray(dec.assignment), -1)
        np.testing.assert_array_equal(
            np.asarray(res.assignment)[i], a_ref,
            err_msg=f"inner cycle {i} assignment diverged",
        )
        np.testing.assert_array_equal(
            np.asarray(res.unschedulable)[i],
            np.asarray(dec.unschedulable),
        )
        np.testing.assert_array_equal(
            np.asarray(res.gang_dropped)[i],
            np.asarray(dec.gang_dropped),
        )
        np.testing.assert_array_equal(
            np.asarray(res.attempted)[i], valid
        )
        np.testing.assert_array_equal(
            np.asarray(res.node_requested)[i],
            np.asarray(dec.node_requested),
            err_msg=f"inner cycle {i} capacity carry diverged",
        )


def test_device_loop_gang_carry_spans_inner_cycles():
    """A gang placed by inner cycle 0 counts toward minMember for a
    straggler member arriving in inner cycle 1 ONLY through the loop's
    placed-count carry (the stale snapshot says zero members exist) —
    sequential reference and the device loop must agree."""
    from k8s_scheduler_tpu.models.api import PodGroup

    nodes = [
        MakeNode(f"n{i}").capacity({"cpu": "8", "memory": "8Gi"}).obj()
        for i in range(4)
    ]
    pg = [PodGroup(name="gang", min_member=2)]
    groups = [
        [MakePod(f"a{i}").req({"cpu": "1"}).group("gang").obj()
         for i in range(2)],
        # a lone straggler: 1 < minMember unless cycle 0's placements
        # carry into its group_existing_count
        [MakePod("b0").req({"cpu": "1"}).group("gang").obj()],
    ]
    snaps, spec, wbufs, bbufs = _encode_groups(
        groups, nodes, pod_groups=pg
    )
    fw = Framework.from_config()
    kw = dict(commit_mode="rounds", gang_scheduling=True)
    mfn = build_packed_multicycle_fn(spec, framework=fw, k=2, **kw)
    res = mfn(wbufs, bbufs, None, np.int32(2))
    ref = _sequential_reference(snaps, fw, **kw)
    for i in range(2):
        np.testing.assert_array_equal(
            np.asarray(res.assignment)[i],
            np.where(
                np.asarray(snaps[i].pod_valid),
                np.asarray(ref[i].assignment), -1,
            ),
        )
        np.testing.assert_array_equal(
            np.asarray(res.gang_dropped)[i],
            np.asarray(ref[i].gang_dropped),
        )
    # cycle 0 reaches minMember on its own; the cycle-1 straggler
    # survives only because the carry counts cycle 0's placements
    assert (np.asarray(res.assignment)[0][:2] >= 0).all()
    assert int(np.asarray(res.assignment)[1][0]) >= 0
    assert not np.asarray(res.gang_dropped)[1][0]


def test_device_loop_k1_degenerate():
    nodes = [
        MakeNode(f"n{i}").capacity({"cpu": "4", "memory": "8Gi"}).obj()
        for i in range(3)
    ]
    groups = [[MakePod("p0").req({"cpu": "1"}).obj(),
               MakePod("p1").req({"cpu": "2"}).obj()]]
    snaps, spec, wbufs, bbufs = _encode_groups(groups, nodes)
    fw = Framework.from_config()
    kw = dict(commit_mode="rounds", gang_scheduling=True)
    mfn = build_packed_multicycle_fn(spec, framework=fw, k=1, **kw)
    res = mfn(wbufs, bbufs, None, np.int32(1))
    ref = _sequential_reference(snaps, fw, **kw)
    assert int(res.cycles_run) == 1
    np.testing.assert_array_equal(
        np.asarray(res.assignment)[0],
        np.where(np.asarray(snaps[0].pod_valid),
                 np.asarray(ref[0].assignment), -1),
    )


def test_device_loop_early_exit_on_drain():
    """Rows whose pod_valid is all-false end the loop: a short batch
    never pays the full K iterations, and the unran rows keep the init
    fill (-1 / False)."""
    nodes = [
        MakeNode(f"n{i}").capacity({"cpu": "4", "memory": "8Gi"}).obj()
        for i in range(3)
    ]
    groups = [[MakePod("p0").req({"cpu": "1"}).obj()],
              [MakePod("p1").req({"cpu": "1"}).obj()]]
    snaps, spec, wbufs, bbufs = _encode_groups(groups, nodes)
    k = 4
    wk = np.zeros((k,) + wbufs.shape[1:], wbufs.dtype)
    bk = np.zeros((k,) + bbufs.shape[1:], bbufs.dtype)
    wk[:2], bk[:2] = wbufs, bbufs
    fw = Framework.from_config()
    mfn = build_packed_multicycle_fn(
        spec, framework=fw, k=k, commit_mode="rounds",
        gang_scheduling=True,
    )
    res = mfn(wk, bk, None, np.int32(k))
    assert int(res.cycles_run) == 2
    a = np.asarray(res.assignment)
    assert (a[0][:1] >= 0).all() and (a[1][:1] >= 0).all()
    assert (a[2:] == -1).all()
    assert not np.asarray(res.attempted)[2:].any()


def test_envelope_gate_rejects_stateful_capabilities():
    nodes = [MakeNode("n0").capacity({"cpu": "4"}).obj()]
    enc = SnapshotEncoder()
    enc.pad_pods = enc.pad_nodes = 8
    ported = enc.encode(
        nodes, [MakePod("p").req({"cpu": "1"}).host_port(80).obj()]
    )
    assert multicycle_unsupported_reason(ported) == "host_ports"
    enc2 = SnapshotEncoder()
    enc2.pad_pods = enc2.pad_nodes = 8
    clean = enc2.encode(nodes, [MakePod("p").req({"cpu": "1"}).obj()])
    assert multicycle_unsupported_reason(clean) is None
    affine = enc2.encode(
        nodes,
        [MakePod("q").req({"cpu": "1"})
         .pod_affinity("zone", {"app": "x"}).obj()],
    )
    assert multicycle_unsupported_reason(affine) == "inter_pod_affinity"


def test_hold_pop_keeps_buffered_groups_recoverable(tmp_path):
    """A crash while K groups are coalescing must recover EVERY
    buffered group, not just the last pop's: the journaled hold-pop
    accumulates the in-flight set instead of replacing it."""
    from k8s_scheduler_tpu.internal.cache import SchedulerCache
    from k8s_scheduler_tpu.internal.queue import SchedulingQueue

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
    from k8s_scheduler_tpu.internal.cache import SchedulerCache
    from k8s_scheduler_tpu.internal.queue import SchedulingQueue

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


# ---- scheduler level ----------------------------------------------------


def _drive_trace(k, seed, state_dir, n_cycles=6):
    """Run one randomized arrival trace through a Scheduler with
    multiCycleK=k, journaling into state_dir. The clock is FROZEN so
    the only difference between a k=1 and a k=K run is the batching
    itself (backoffs never expire mid-trace, so each cycle's pop is
    exactly that cycle's arrivals in both runs)."""
    clock = FakeClock()
    binds = []
    cfg = SchedulerConfiguration(
        multi_cycle_k=k, multi_cycle_max_wait_ms=1e9
    )
    state = DurableState(state_dir, snapshot_interval_seconds=0)
    sched = Scheduler(
        config=cfg,
        binder=lambda pod, node: binds.append((pod.uid, node)),
        now=clock, pad_bucket=8, state=state,
    )
    for i in range(6):
        sched.on_node_add(
            MakeNode(f"n{i}")
            .capacity({"cpu": "4", "memory": "8Gi"}).obj()
        )
    rng = random.Random(seed)
    uid = 0
    for _c in range(n_cycles):
        for _ in range(rng.randint(1, 5)):
            sched.on_pod_add(
                MakePod(f"p{uid}")
                .req({"cpu": rng.choice(["1", "2", "3"]),
                      "memory": "1Gi"})
                .obj()
            )
            uid += 1
        sched.schedule_cycle()
    # idle pops flush any buffered groups (and are no-ops for k=1)
    for _ in range(2):
        sched.schedule_cycle()
    recs = [
        (r.counts.get("pods"), r.counts.get("scheduled"),
         r.counts.get("unschedulable"), r.counts.get("gang_dropped"))
        for r in sched.flight.snapshot()
    ]
    digest = state_digest(sched.queue, sched.cache)
    state.journal.flush()
    state.journal.close()
    return binds, recs, digest


def _journal_streams(state_dir):
    """Split the journal into the two streams batching may legitimately
    re-interleave but must each preserve exactly:

    - decisions: every scheduling-outcome record (assume, bind finish,
      requeues, forgets, evictions) — multi-cycle applies these per
      inner cycle in batch order, so the stream must be IDENTICAL to
      the sequential scheduler's (same ops, order, payloads, times);
    - arrivals: informer-driven records (adds/updates/deletes, node
      churn), journaled when they happen — batching moves the decision
      stream relative to them (K groups arrive before the batch
      flushes), but the arrival stream itself must be identical.

    The q.pop/q.move/q.flush/q.retire markers are the cycle-boundary
    bookkeeping whose position and hold-flag shape IS the batching, so
    they are the one thing excluded from the equivalence claim
    (q.retire exists ONLY under batching: it undoes what the hold pops
    accumulated; a K=1 journal never contains one)."""
    markers = {
        "q.pop", "q.move", "q.flush_backoff", "q.flush_timeout",
        "q.retire",
    }
    arrivals = {
        "q.add", "q.update", "q.delete", "c.add_node", "c.update_node",
        "c.remove_node", "c.add_pod", "c.remove_pod",
    }
    dec_stream, arr_stream = [], []
    for op, t, data in replay_dir(str(state_dir)):
        if op in markers:
            continue
        (arr_stream if op in arrivals else dec_stream).append(
            (op, t, data)
        )
    return dec_stream, arr_stream


@pytest.mark.parametrize("seed", [0, 5, 9])
def test_scheduler_multicycle_matches_sequential(tmp_path, seed):
    """The tentpole acceptance: a k=4 batched scheduler and a k=1
    sequential scheduler produce identical bind streams, identical
    journal decision records (same ops, same order, same payloads,
    same timestamps), identical state digests, and identical per-cycle
    flight outcome counts over a randomized trace."""
    b1, r1, d1 = _drive_trace(1, seed, str(tmp_path / "seq"))
    b4, r4, d4 = _drive_trace(4, seed, str(tmp_path / "mc"))
    assert b4 == b1
    assert r4 == r1
    assert d4 == d1
    dec1, arr1 = _journal_streams(tmp_path / "seq")
    dec4, arr4 = _journal_streams(tmp_path / "mc")
    assert dec4 == dec1
    assert arr4 == arr1


def _drive_selector_growth(incremental):
    """A K=4 batch where every later group interns a NEW node-selector
    expression WITHIN the padded table regime (Ex pads to 8, so the
    spec key never changes): the regression the table-growth re-encode
    trigger exists for. Returns (binds, encoder)."""
    clock = FakeClock()
    binds = []
    cfg = SchedulerConfiguration(
        multi_cycle_k=4, multi_cycle_max_wait_ms=1e9,
        incremental_encode=incremental,
    )
    sched = Scheduler(
        config=cfg,
        binder=lambda pod, node: binds.append((pod.uid, node)),
        now=clock, pad_bucket=8,
    )
    for i, tier in enumerate(("gold", "silver", "bronze", "iron")):
        sched.on_node_add(
            MakeNode(f"n{i}").capacity({"cpu": "8", "memory": "8Gi"})
            .labels({"tier": tier}).obj()
        )
    # group 0 interns nothing selector-shaped; groups 1..3 each bring a
    # selector value the tables have never seen
    sched.on_pod_add(MakePod("p0").req({"cpu": "1"}).obj())
    sched.schedule_cycle()
    for i, tier in enumerate(("silver", "bronze", "iron")):
        sched.on_pod_add(
            MakePod(f"p{i + 1}").req({"cpu": "1"})
            .node_selector({"tier": tier}).obj()
        )
        sched.schedule_cycle()  # 4th call flushes the batch
    return binds, sched._encoders["default-scheduler"]


def test_multicycle_table_growth_within_padding_rebinds(tmp_path):
    """A later group's pod may intern a new expression row WITHOUT
    changing the padded spec key — row 0's stable tables (the whole
    batch's stable side) would lack the entry its row references, and
    the pod was falsely rejected as NodeAffinity-unschedulable. The
    table-growth re-encode trigger must rebuild the batch so every
    selector pod binds to its labeled node."""
    binds, _enc = _drive_selector_growth(incremental=False)
    d = dict(binds)
    # p0 has no selector — its node is a scoring tiebreak; the
    # selector pods MUST land on their labeled nodes (without the
    # growth trigger they were falsely NodeAffinity-unschedulable)
    assert "default/p0" in d
    assert {k: d.get(k) for k in
            ("default/p1", "default/p2", "default/p3")} == {
        "default/p1": "n1", "default/p2": "n2", "default/p3": "n3",
    }


def test_multicycle_growth_reencode_reuses_interned_entries():
    """The dim-growth re-encode's second pass must REUSE the entries
    pass 1 interned (delta hits against the grown tables), not run a
    second round of full encodes — and under incrementalEncode the
    decisions are identical to the non-incremental engine."""
    binds_off, _ = _drive_selector_growth(incremental=False)
    binds_on, enc = _drive_selector_growth(incremental=True)
    assert binds_on == binds_off
    # pass 1: the growing groups full-encode; the retry pass re-rows
    # the earlier groups via the delta path (tables already grown, so
    # nothing forces a second full rebuild)
    assert enc.delta_hits > 0, (enc.delta_hits, enc.full_encodes)
    assert enc.full_encodes <= 4, (enc.delta_hits, enc.full_encodes)


def test_scheduler_flushes_on_latency_bound(tmp_path):
    """A buffered group is never held past multiCycleMaxWaitMs even if
    arrivals keep trickling in below the K threshold."""
    clock = FakeClock()
    binds = []
    cfg = SchedulerConfiguration(
        multi_cycle_k=8, multi_cycle_max_wait_ms=50.0
    )
    sched = Scheduler(
        config=cfg,
        binder=lambda pod, node: binds.append(pod.uid),
        now=clock, pad_bucket=8,
    )
    sched.on_node_add(MakeNode("n0").capacity({"cpu": "64"}).obj())
    sched.on_pod_add(MakePod("p0").req({"cpu": "1"}).obj())
    sched.schedule_cycle()
    assert binds == []  # buffered: below K, stream active, under bound
    clock.tick(0.2)  # past the 50 ms bound
    sched.on_pod_add(MakePod("p1").req({"cpu": "1"}).obj())
    sched.schedule_cycle()
    assert sorted(binds) == ["default/p0", "default/p1"]
    assert (
        sched.metrics.multicycle_batch._sum.get() == 2.0
    )  # one 2-cycle batch


def test_scheduler_envelope_fallback_pins_profile_off(tmp_path):
    """A STICKY capability (inter-pod affinity: the encoder's flag is
    grow-only) that leaves the envelope mid-run falls back to
    sequential dispatches (nothing lost) and pins batching off for the
    profile's lifetime."""
    clock = FakeClock()
    binds = []
    cfg = SchedulerConfiguration(
        multi_cycle_k=4, multi_cycle_max_wait_ms=1e9
    )
    sched = Scheduler(
        config=cfg,
        binder=lambda pod, node: binds.append(pod.uid),
        now=clock, pad_bucket=8,
    )
    sched.on_node_add(
        MakeNode("n0").capacity({"cpu": "64"})
        .labels({"zone": "z0"}).obj()
    )
    sched.on_pod_add(
        MakePod("p0").req({"cpu": "1"})
        .pod_affinity("zone", {"app": "x"}).obj()
    )
    sched.schedule_cycle()
    sched.on_pod_add(MakePod("p1").req({"cpu": "1"}).obj())
    sched.schedule_cycle()
    sched.schedule_cycle()  # idle pop -> flush -> envelope fallback
    assert "default/p1" in binds
    assert (
        sched._mc_off.get("default-scheduler") == "inter_pod_affinity"
    )
    # later arrivals go straight through the single-cycle path
    sched.on_pod_add(MakePod("p2").req({"cpu": "1"}).obj())
    sched.schedule_cycle()
    assert "default/p2" in binds


def test_scheduler_host_ports_fallback_is_per_batch(tmp_path):
    """host_ports is a per-SNAPSHOT envelope exit (only a PENDING pod
    requesting a port occupies one): the carrying batch falls back
    sequentially but the profile is NOT pinned — the next port-free
    batch dispatches through the device loop again."""
    clock = FakeClock()
    binds = []
    cfg = SchedulerConfiguration(
        multi_cycle_k=2, multi_cycle_max_wait_ms=1e9
    )
    sched = Scheduler(
        config=cfg,
        binder=lambda pod, node: binds.append(pod.uid),
        now=clock, pad_bucket=8,
    )
    sched.on_node_add(MakeNode("n0").capacity({"cpu": "64"}).obj())
    sched.on_pod_add(
        MakePod("p0").req({"cpu": "1"}).host_port(8080).obj()
    )
    sched.schedule_cycle()
    sched.on_pod_add(MakePod("p1").req({"cpu": "1"}).obj())
    sched.schedule_cycle()  # batch of 2 -> host_ports fallback
    assert sorted(binds) == ["default/p0", "default/p1"]
    assert "default-scheduler" not in sched._mc_off
    assert sched.metrics.multicycle_batch._sum.get() == 0.0
    # port-free traffic re-enters the batched path
    sched.on_pod_add(MakePod("p2").req({"cpu": "1"}).obj())
    sched.schedule_cycle()
    sched.on_pod_add(MakePod("p3").req({"cpu": "1"}).obj())
    sched.schedule_cycle()
    assert sorted(binds)[2:] == ["default/p2", "default/p3"]
    assert sched.metrics.multicycle_batch._sum.get() == 2.0


def test_multicycle_records_carry_batched_phases(tmp_path):
    """Inner-cycle flight records carry the batched decomposition the
    observer exports: batch_wait, device_share, and the multi_cycle_k
    marker that excuses their full encodes from fold_miss."""
    clock = FakeClock()
    # speculative depth-2 splits a flush into TWO dispatches, each with
    # its own record-0 pipeline window — this test pins the COMBINED
    # single-dispatch decomposition (the split shape is covered by
    # tests/test_speculative.py)
    cfg = SchedulerConfiguration(
        multi_cycle_k=2, multi_cycle_max_wait_ms=1e9,
        speculative_dispatch=False,
    )
    sched = Scheduler(config=cfg, now=clock, pad_bucket=8)
    sched.on_node_add(MakeNode("n0").capacity({"cpu": "64"}).obj())
    for i in range(2):
        sched.on_pod_add(MakePod(f"p{i}").req({"cpu": "1"}).obj())
        clock.tick(0.01)
        sched.schedule_cycle()
    recs = sched.flight.snapshot()
    assert len(recs) == 2
    waits = []
    for rec in recs:
        assert rec.counts["multi_cycle_k"] == 2
        assert "device_share_ms" in rec.phases
        waits.append(rec.phases["batch_wait_ms"])
        assert rec.counts["scheduled"] == 1
    # group 0 waited ~10 ms for group 1; group 1 flushed immediately
    assert waits[0] > waits[1]
    from k8s_scheduler_tpu.core.observe import phase_seconds

    ph = phase_seconds(recs[0])
    assert "batch_wait" in ph and "device_share" in ph
    assert sched.observer.anomaly_counts["fold_miss"] == 0
    # the batch-wide pipeline window lands ONLY on inner record 0 — K
    # copies would feed the phase histograms K observations of one
    # dispatch (and K duplicate stall anomalies); later records carry
    # the apportioned decomposition instead
    assert "device" in ph and "dispatch" in ph
    ph1 = phase_seconds(recs[1])
    assert "device" not in ph1 and "dispatch" not in ph1
    assert "device_share" in ph1 and "batch_wait" in ph1


def test_multicycle_records_carry_diag_lag(tmp_path):
    """An inner cycle whose pod found no node forces the deferred
    diagnosis through MultiCycleHandle.reject_counts — its flight
    record must carry the diag_lag phase and feed the
    scheduler_diag_lag_seconds summary, exactly as the single-cycle
    path does (stage_report is snapshotted before the apply loop, so
    the lag rides the handle instead)."""
    clock = FakeClock()
    cfg = SchedulerConfiguration(
        multi_cycle_k=2, multi_cycle_max_wait_ms=1e9
    )
    sched = Scheduler(config=cfg, now=clock, pad_bucket=8)
    sched.on_node_add(MakeNode("n0").capacity({"cpu": "4"}).obj())
    sched.on_pod_add(MakePod("fits").req({"cpu": "1"}).obj())
    clock.tick(0.01)
    sched.schedule_cycle()
    sched.on_pod_add(MakePod("huge").req({"cpu": "64"}).obj())
    clock.tick(0.01)
    sched.schedule_cycle()  # batch of 2 flushes; cycle 1 diagnoses
    recs = sched.flight.snapshot()
    assert [r.counts["multi_cycle_k"] for r in recs] == [2, 2]
    assert "diag_lag_ms" in recs[1].phases  # 'huge' was diagnosed
    assert "diag_lag_ms" not in recs[0].phases  # 'fits' bound clean
    assert sched.metrics.diag_lag._count.get() == 1


def test_mixed_burst_lull_traffic_no_false_fold_miss(tmp_path):
    """Bursts (batched) interleaved with lulls (single-cycle): every pod
    binds exactly once, and the first single-cycle dispatch after a
    batch — whose full re-encode is the batch's doing, because the
    stacked plain encodes leave the packed arena's _delta_state stale —
    is stamped post_batch=1 and raises NO fold_miss anomaly."""
    from collections import Counter

    clock = FakeClock()
    binds = []
    cfg = SchedulerConfiguration(
        multi_cycle_k=3, multi_cycle_max_wait_ms=1e9
    )
    sched = Scheduler(
        config=cfg,
        binder=lambda pod, node: binds.append(pod.uid),
        now=clock, pad_bucket=8,
    )
    for i in range(6):
        sched.on_node_add(
            MakeNode(f"n{i}").capacity({"cpu": "16"}).obj()
        )
    uid = 0
    attempted = []
    for _round in range(3):
        for _g in range(3):  # burst: 3 groups coalesce into one batch
            for _ in range(2):
                sched.on_pod_add(
                    MakePod(f"p{uid}").req({"cpu": "1"}).obj()
                )
                uid += 1
            clock.tick(0.01)
            attempted.append(sched.schedule_cycle().attempted)
        # lull: a lone group goes through the single-cycle path
        sched.on_pod_add(MakePod(f"p{uid}").req({"cpu": "1"}).obj())
        uid += 1
        clock.tick(0.01)
        attempted.append(sched.schedule_cycle().attempted)
        clock.tick(0.01)
        attempted.append(sched.schedule_cycle().attempted)  # idle flush
    assert sorted(Counter(binds).values()) == [1] * uid  # no dup binds
    assert len(binds) == uid
    # a pod is attempted in the cycle whose dispatch carried it —
    # exactly once across the trace (buffering cycles report 0, flush
    # cycles the batch size), so Σscheduled/Σattempted rates are honest
    assert sum(attempted) == uid
    assert attempted[:3] == [0, 0, 6]  # 2 buffering cycles, then flush
    # every flushed pod's outcome retired it from the in-flight set
    assert not sched.queue._in_flight
    assert sched.observer.anomaly_counts["fold_miss"] == 0
    recs = sched.flight.snapshot()
    # each round: 2 buffering cycles, then 3 batch inner records, then
    # the lone single-cycle records — the first single-cycle record
    # after each batch carries the post_batch excuse
    post = [
        r for r in recs
        if "multi_cycle_k" not in r.counts and "post_batch" in r.counts
    ]
    assert len(post) == 3  # one per round's first post-batch dispatch
    for r in post:
        assert r.counts["post_batch"] == 1

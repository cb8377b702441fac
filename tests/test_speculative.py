"""Depth-2 speculative dispatch pipelining + streamed decision fetch
(ISSUE 13): device-saturated multi-cycle serving that never trades
correctness for latency.

Four layers:

- device level: chaining batch B onto batch A's device-resident carry
  through the carry_in continuation program is bit-identical to the
  combined [A;B] batch;
- pipeline level: streamed per-row decisions equal the stacked fetch,
  the speculative ordering-guard relaxation ("binds fold before the
  next ADOPTED encode"), the speculation ledger, and the
  slot-accounting invariant (depth-2 never overwrites an unfetched
  slot — three slots required, refused loudly on two);
- scheduler level: a speculativeDispatch=on scheduler is bit-identical
  to the same trace with speculation off AND to the K=1 sequential
  scheduler (binds, journal decision records, state digests); the
  forced-mismatch path (a bind error in the predecessor's fold)
  abandons, re-dispatches against the true carry, still lands
  bit-identical binds, and counts one abandoned + one redispatched in
  the ledger; flight records carry first_bind_ms and the speculation
  tag;
- sentinel: a high abandon-rate EWMA raises speculation_thrash and
  auto-disables speculation for degradePromoteCycles opportunities.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from k8s_scheduler_tpu.config import SchedulerConfiguration, load_config
from k8s_scheduler_tpu.core import Scheduler
from k8s_scheduler_tpu.core.cycle import build_packed_multicycle_fn
from k8s_scheduler_tpu.core.pipeline import ServingPipeline
from k8s_scheduler_tpu.framework.runtime import Framework
from k8s_scheduler_tpu.models import MakeNode, MakePod, packing
from k8s_scheduler_tpu.models.encoding import SnapshotEncoder
from k8s_scheduler_tpu.state import DurableState, state_digest

from test_multicycle import FakeClock, _journal_streams


# ---- shared device-level fixtures ---------------------------------------


def _nodes(n=5, cpu="4"):
    return [
        MakeNode(f"n{i}").capacity({"cpu": cpu, "memory": "8Gi"}).obj()
        for i in range(n)
    ]


def _encode_stacked(groups, nodes, k):
    enc = SnapshotEncoder()
    enc.pad_pods = 8
    enc.pad_nodes = 8
    snaps = [enc.encode(nodes, g, ()) for g in groups]
    spec = packing.make_spec(snaps[0])
    for s in snaps[1:]:
        assert packing.make_spec(s).key() == spec.key()
    wb = np.zeros((k, spec.n_words), np.uint32)
    bb = np.zeros((k, spec.n_bytes), np.uint8)
    for i, s in enumerate(snaps):
        wb[i], bb[i] = packing.pack(s, spec)
    return spec, wb, bb


def _rand_groups(seed, n_groups, max_pods=5):
    rng = random.Random(seed)
    groups, uid = [], 0
    for _ in range(n_groups):
        g = []
        for _ in range(rng.randint(1, max_pods)):
            g.append(
                MakePod(f"p{uid}")
                .req({"cpu": rng.choice(["1", "2", "3"]),
                      "memory": "1Gi"})
                .obj()
            )
            uid += 1
        groups.append(g)
    return groups


# ---- device level: continuation chaining ---------------------------------


@pytest.mark.parametrize("seed", [0, 7])
def test_carry_chain_matches_combined_batch(seed):
    """Batch A (row 0) chained into batch B (rows 1..K-1) through the
    carry_in continuation program produces bit-identical decisions and
    final carry to the combined [A;B] dispatch — the property that
    makes adopting a speculative batch correctness-free."""
    nodes = _nodes()
    groups = _rand_groups(seed, 4)
    K = 4
    spec, wb, bb = _encode_stacked(groups, nodes, K)
    fw = Framework.from_config()
    mfn = build_packed_multicycle_fn(spec, framework=fw, k=K)
    mcont = build_packed_multicycle_fn(
        spec, framework=fw, k=K, carry_in=True
    )
    full = mfn(wb, bb, None, np.int32(4))
    wa = np.zeros_like(wb)
    ba = np.zeros_like(bb)
    wa[0], ba[0] = wb[0], bb[0]
    wB = np.zeros_like(wb)
    bB = np.zeros_like(bb)
    wB[:3], bB[:3] = wb[1:], bb[1:]
    ra = mfn(wa, ba, None, np.int32(1))
    rb = mcont(
        wB, bB, None, np.int32(3),
        ra.carry_node_requested, ra.carry_gplaced,
    )
    assert int(ra.cycles_run) == 1 and int(rb.cycles_run) == 3
    np.testing.assert_array_equal(
        np.asarray(full.assignment)[0], np.asarray(ra.assignment)[0]
    )
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(full.assignment)[i + 1],
            np.asarray(rb.assignment)[i],
            err_msg=f"chained inner cycle {i} diverged",
        )
        np.testing.assert_array_equal(
            np.asarray(full.unschedulable)[i + 1],
            np.asarray(rb.unschedulable)[i],
        )
        np.testing.assert_array_equal(
            np.asarray(full.gang_dropped)[i + 1],
            np.asarray(rb.gang_dropped)[i],
        )
    np.testing.assert_array_equal(
        np.asarray(full.carry_node_requested),
        np.asarray(rb.carry_node_requested),
    )
    # continuation batches report their own gplaced DELTA so chains add
    np.testing.assert_array_equal(
        np.asarray(full.carry_gplaced),
        np.asarray(ra.carry_gplaced) + np.asarray(rb.carry_gplaced),
    )


# ---- pipeline level ------------------------------------------------------


def _pipe_with_programs(spec, k, slots=3):
    fw = Framework.from_config()
    pipe = ServingPipeline(lambda *a: None, slots=slots)
    pipe.multi_fn = build_packed_multicycle_fn(spec, framework=fw, k=k)
    pipe.multi_cont_fn = build_packed_multicycle_fn(
        spec, framework=fw, k=k, carry_in=True
    )
    return pipe


def test_streamed_rows_equal_stacked_fetch():
    nodes = _nodes()
    groups = _rand_groups(3, 4)
    spec, wb, bb = _encode_stacked(groups, nodes, 4)
    pipe = _pipe_with_programs(spec, 4)
    h = pipe.dispatch_multi(wb, bb, None, 4)
    rows = [h.decisions_row(i) for i in range(4)]
    assert h.fetched  # every live row fetched -> guard released
    a, u, gd, att, ran = h.decisions()
    assert ran == 4 and h.cycles_run() == 4
    for i in range(4):
        np.testing.assert_array_equal(a[i], rows[i][0])
        np.testing.assert_array_equal(u[i], rows[i][1])
        np.testing.assert_array_equal(gd[i], rows[i][2])
        np.testing.assert_array_equal(att[i], rows[i][3])


def test_speculative_guard_and_ledger():
    """The ordering guard relaxes only for speculative dispatches: a
    normal dispatch with the predecessor unfetched is still refused,
    a speculative one proceeds, and a second dispatch is refused until
    the speculation resolves."""
    nodes = _nodes()
    groups = _rand_groups(5, 4)
    spec, wb, bb = _encode_stacked(groups, nodes, 4)
    pipe = _pipe_with_programs(spec, 4)
    wa = np.zeros_like(wb)
    ba = np.zeros_like(bb)
    wa[0], ba[0] = wb[0], bb[0]
    ha = pipe.dispatch_multi(wa, ba, None, 1)
    with pytest.raises(RuntimeError, match="before .* fetched"):
        pipe.dispatch_multi(wb, bb, None, 4)  # non-speculative: refused
    hb = pipe.dispatch_multi(
        wb, bb, None, 3,
        carry0=(ha.result.carry_node_requested, ha.result.carry_gplaced),
        speculative=True,
    )
    assert pipe.inflight() == 2  # depth 2: both batches in flight
    with pytest.raises(RuntimeError, match="unresolved speculative"):
        pipe.dispatch_multi(wb, bb, None, 4)
    ha.decisions_row(0)
    adopted = pipe.adopt_speculative()
    assert adopted is hb
    for i in range(3):
        hb.decisions_row(i)
    assert pipe.speculation == {
        "adopted": 1, "abandoned": 0, "redispatched": 0,
    }
    # resolved + fetched: the next dispatch proceeds normally
    pipe.dispatch_multi(wb, bb, None, 4)


def test_abandon_frees_the_slot_and_counts():
    nodes = _nodes()
    groups = _rand_groups(6, 4)
    spec, wb, bb = _encode_stacked(groups, nodes, 4)
    pipe = _pipe_with_programs(spec, 4)
    wa = np.zeros_like(wb)
    ba = np.zeros_like(bb)
    wa[0], ba[0] = wb[0], bb[0]
    ha = pipe.dispatch_multi(wa, ba, None, 1)
    hb = pipe.dispatch_multi(
        wb, bb, None, 3,
        carry0=(ha.result.carry_node_requested, ha.result.carry_gplaced),
        speculative=True,
    )
    pipe.abandon_speculative()
    assert hb.result is None  # released
    assert pipe.inflight() == 1  # only the predecessor remains
    assert hb not in pipe._slots  # the slot did not leak
    pipe.note_redispatch()
    assert pipe.speculation == {
        "adopted": 0, "abandoned": 1, "redispatched": 1,
    }
    # abandoning again is a no-op (failure paths call unconditionally)
    pipe.abandon_speculative()
    assert pipe.speculation["abandoned"] == 1


def test_depth2_never_overwrites_an_unfetched_slot():
    """The slot-accounting invariant: with only the two double-buffered
    slots, a dispatch sequence that would reuse the slot of a batch
    whose decisions were never fetched is refused loudly (dispatch A ->
    speculate B -> abandon -> re-speculate wraps to A's slot); the
    third slot makes the same sequence legal."""
    nodes = _nodes()
    groups = _rand_groups(8, 4)
    spec, wb, bb = _encode_stacked(groups, nodes, 4)

    def drive(slots):
        pipe = _pipe_with_programs(spec, 4, slots=slots)
        wa = np.zeros_like(wb)
        ba = np.zeros_like(bb)
        wa[0], ba[0] = wb[0], bb[0]
        ha = pipe.dispatch_multi(wa, ba, None, 1)
        carry = (
            ha.result.carry_node_requested, ha.result.carry_gplaced
        )
        pipe.dispatch_multi(
            wb, bb, None, 3, carry0=carry, speculative=True
        )
        pipe.abandon_speculative()
        # re-speculating claims the NEXT slot — with two slots that is
        # A's, still unfetched and still in flight
        return pipe.dispatch_multi(
            wb, bb, None, 3, carry0=carry, speculative=True
        )

    with pytest.raises(RuntimeError, match="unfetched in-flight"):
        drive(slots=2)
    drive(slots=3)  # the third arena slot makes depth 2 safe


# ---- scheduler level -----------------------------------------------------


def _drive(k, seed, state_dir, *, speculative, n_cycles=6,
           fail_uids=frozenset()):
    """One randomized arrival trace through a Scheduler (frozen clock,
    journaled); `fail_uids` makes the binder fail those pods — the
    deterministic fold divergence the mismatch path tests force."""
    clock = FakeClock()
    binds = []
    cfg = SchedulerConfiguration(
        multi_cycle_k=k, multi_cycle_max_wait_ms=1e9,
        speculative_dispatch=speculative,
    )
    state = DurableState(str(state_dir), snapshot_interval_seconds=0)

    def binder(pod, node):
        if pod.uid in fail_uids:
            raise RuntimeError(f"induced bind failure for {pod.uid}")
        binds.append((pod.uid, node))

    sched = Scheduler(
        config=cfg, binder=binder, now=clock, pad_bucket=8, state=state,
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
    for _ in range(2):
        sched.schedule_cycle()  # idle pops flush the buffer
    recs = [
        (r.counts.get("pods"), r.counts.get("scheduled"),
         r.counts.get("unschedulable"), r.counts.get("gang_dropped"))
        for r in sched.flight.snapshot()
    ]
    digest = state_digest(sched.queue, sched.cache)
    state.journal.flush()
    state.journal.close()
    return binds, recs, digest, sched


@pytest.mark.parametrize("seed", [0, 9])
def test_scheduler_speculative_matches_sequential(tmp_path, seed):
    """The tentpole acceptance: speculation on is bit-identical to
    speculation off AND to the K=1 sequential scheduler — same bind
    streams, same journal decision records, same state digests — while
    the ledger proves batches were actually adopted."""
    b1, r1, d1, _s1 = _drive(
        1, seed, tmp_path / "seq", speculative=False
    )
    b4, r4, d4, _s4 = _drive(
        4, seed, tmp_path / "mc", speculative=False
    )
    bs, rs, ds, sched = _drive(
        4, seed, tmp_path / "spec", speculative=True
    )
    assert bs == b4 == b1
    assert ds == d4 == d1
    assert rs == r4
    led = sched.speculation_ledger()
    assert led["adopted"] >= 1, led
    assert led["abandoned"] == led["redispatched"] == 0
    dec1, arr1 = _journal_streams(tmp_path / "seq")
    decs, arrs = _journal_streams(tmp_path / "spec")
    assert decs == dec1
    assert arrs == arr1
    assert sched.observer.anomaly_counts["speculation_thrash"] == 0


def test_mismatch_abandons_redispatches_bit_identical(tmp_path):
    """The forced-mismatch path: a bind error in the predecessor
    batch's fold diverges from the speculation's predicate digest —
    the in-flight batch must be abandoned, its groups re-dispatched
    against the true carry, the resulting binds bit-identical to the
    sequential scheduler under the same failing binder, and the ledger
    must count one abandoned + one redispatched."""
    # the first flushed batch's row-0 group contains p0: failing its
    # bind makes the first speculation's fold diverge deterministically
    fail = frozenset({"default/p0"})
    b1, _r1, d1, _s1 = _drive(
        1, 0, tmp_path / "seq", speculative=False, fail_uids=fail
    )
    bs, _rs, ds, sched = _drive(
        4, 0, tmp_path / "spec", speculative=True, fail_uids=fail
    )
    assert bs == b1
    assert ds == d1
    led = sched.speculation_ledger()
    assert led["abandoned"] >= 1, led
    assert led["redispatched"] == led["abandoned"]
    dec1, _arr1 = _journal_streams(tmp_path / "seq")
    decs, _arrs = _journal_streams(tmp_path / "spec")
    assert decs == dec1


def test_records_carry_first_bind_and_speculation_tag(tmp_path):
    """Observability satellites: the flush's first record carries the
    streamed-fetch first_bind phase and the speculation outcome; the
    adopted batch's records are its own dispatch's, not copies of the
    predecessor's window."""
    clock = FakeClock()
    cfg = SchedulerConfiguration(
        multi_cycle_k=3, multi_cycle_max_wait_ms=1e9,
        speculative_dispatch=True,
    )
    sched = Scheduler(config=cfg, now=clock, pad_bucket=8)
    sched.on_node_add(MakeNode("n0").capacity({"cpu": "64"}).obj())
    for i in range(3):
        sched.on_pod_add(MakePod(f"p{i}").req({"cpu": "1"}).obj())
        clock.tick(0.01)
        sched.schedule_cycle()
    recs = sched.flight.snapshot()
    assert len(recs) == 3
    from k8s_scheduler_tpu.core.observe import phase_seconds

    ph0 = phase_seconds(recs[0])
    assert "first_bind" in ph0
    assert recs[0].phases["first_bind_ms"] >= 0.0
    assert recs[0].speculation == "adopted"
    assert recs[0].to_dict()["speculation"] == "adopted"
    # exactly ONE record carries the outcome (one EWMA sample per
    # speculation); the adopted batch's own records are untagged
    assert [r.speculation for r in recs[1:]] == ["", ""]
    # record 1 is the adopted batch's record 0: its own dispatch marks
    assert "dispatch_start" in recs[1].marks
    assert recs[1].counts["multi_cycle_k"] == 3
    # the speculative dispatch itself is visible on the predecessor
    assert "spec_dispatch_ms" in recs[0].phases


def test_forced_sync_and_ladder_disable_speculation(tmp_path):
    """The escape hatches: forcedSync and a ladder rung at/below
    `sequential` force speculation off (batches still serve)."""
    clock = FakeClock()
    cfg = SchedulerConfiguration(
        multi_cycle_k=2, multi_cycle_max_wait_ms=1e9,
        speculative_dispatch=True, forced_sync=True,
    )
    binds = []
    sched = Scheduler(
        config=cfg, binder=lambda p, n: binds.append(p.uid),
        now=clock, pad_bucket=8,
    )
    sched.on_node_add(MakeNode("n0").capacity({"cpu": "64"}).obj())
    for i in range(2):
        sched.on_pod_add(MakePod(f"p{i}").req({"cpu": "1"}).obj())
        clock.tick(0.01)
        sched.schedule_cycle()
    sched.schedule_cycle()
    assert sorted(binds) == ["default/p0", "default/p1"]
    assert sched.speculation_ledger() == {
        "adopted": 0, "abandoned": 0, "redispatched": 0,
    }


def test_fold_free_driver_keeps_silent_slot_release():
    """require_decision_fetch=False (fold-free probes/throughput loops)
    opted out of the ordering guard — slot reuse must keep the old
    silent release, never the depth-2 unfetched-slot refusal."""
    nodes = _nodes()
    groups = _rand_groups(11, 4)
    spec, wb, bb = _encode_stacked(groups, nodes, 4)
    fw = Framework.from_config()
    pipe = ServingPipeline(
        lambda *a: None, require_decision_fetch=False, slots=2
    )
    pipe.multi_fn = build_packed_multicycle_fn(spec, framework=fw, k=4)
    for _ in range(3):  # third dispatch wraps onto an unfetched slot
        pipe.dispatch_multi(wb, bb, None, 4)


def test_apply_failure_releases_guard_and_speculation(tmp_path):
    """A NON-fetch failure inside the apply loop (here: a host plugin
    raising a plain exception) must release the ordering guard and
    free the in-flight speculation — the old stacked fetch had marked
    the handle consumed before any apply, and one apply-path error
    must not wedge the pipeline forever."""
    from k8s_scheduler_tpu.framework.host import HostPlugin

    class Boom(HostPlugin):
        name = "Boom"
        fired = False

        def reserve(self, pod, node_name):
            if not Boom.fired:
                Boom.fired = True
                raise RuntimeError("induced host-plugin failure")
            return None

    clock = FakeClock()
    binds = []
    cfg = SchedulerConfiguration(
        multi_cycle_k=2, multi_cycle_max_wait_ms=1e9,
        speculative_dispatch=True,
    )
    sched = Scheduler(
        config=cfg, binder=lambda p, n: binds.append(p.uid),
        now=clock, pad_bucket=8, host_plugins=[Boom()],
    )
    sched.on_node_add(MakeNode("n0").capacity({"cpu": "64"}).obj())
    sched.on_pod_add(MakePod("p0").req({"cpu": "1"}).obj())
    clock.tick(0.01)
    sched.schedule_cycle()  # buffers group 0
    sched.on_pod_add(MakePod("p1").req({"cpu": "1"}).obj())
    clock.tick(0.01)
    with pytest.raises(RuntimeError, match="induced host-plugin"):
        sched.schedule_cycle()  # the flush whose row-0 apply explodes
    # the pipeline is NOT wedged: later cycles schedule normally
    for i in range(2, 4):
        sched.on_pod_add(MakePod(f"p{i}").req({"cpu": "1"}).obj())
        clock.tick(0.01)
        sched.schedule_cycle()
    sched.schedule_cycle()
    assert "default/p2" in binds and "default/p3" in binds


# ---- sentinel: speculation_thrash ---------------------------------------


def test_sentinel_thrash_holds_and_reenables():
    from k8s_scheduler_tpu.core.observe import CycleObserver

    obs = CycleObserver(
        metrics=None, spec_hold_cycles=3, spec_warmup=4,
    )
    for i in range(4):
        obs.observe_phases(
            {"total": 0.01}, profile="p", seq=i,
            speculation="abandoned",
        )
    assert obs.anomaly_counts["speculation_thrash"] == 1
    ev = obs.anomalies(last=1)[0]
    assert ev["class"] == "speculation_thrash"
    assert ev["detail"]["hold_cycles"] == 3
    # the hold: three refused opportunities, then re-enabled
    assert [obs.speculation_ok("p") for _ in range(4)] == [
        False, False, False, True,
    ]
    # adopted outcomes keep the EWMA low: no re-fire
    for i in range(8):
        obs.observe_phases(
            {"total": 0.01}, profile="p", seq=10 + i,
            speculation="adopted",
        )
    assert obs.anomaly_counts["speculation_thrash"] == 1
    assert obs.speculation_ok("p")


def test_scheduler_consults_the_thrash_hold(tmp_path):
    """With the hold active the scheduler serves the batch without
    speculating (ledger stays flat while binds still land)."""
    clock = FakeClock()
    cfg = SchedulerConfiguration(
        multi_cycle_k=2, multi_cycle_max_wait_ms=1e9,
        speculative_dispatch=True,
    )
    binds = []
    sched = Scheduler(
        config=cfg, binder=lambda p, n: binds.append(p.uid),
        now=clock, pad_bucket=8,
    )
    sched.on_node_add(MakeNode("n0").capacity({"cpu": "64"}).obj())
    # arm the hold directly (the unit above covers how it arises)
    with sched.observer._lock:
        sched.observer._prof.setdefault(
            "default-scheduler", {"sig": None, "counts": {}, "cycles": 0}
        )["spec_hold"] = 100
    for i in range(2):
        sched.on_pod_add(MakePod(f"p{i}").req({"cpu": "1"}).obj())
        clock.tick(0.01)
        sched.schedule_cycle()
    sched.schedule_cycle()
    assert sorted(binds) == ["default/p0", "default/p1"]
    assert sched.speculation_ledger()["adopted"] == 0


# ---- config / CLI plumbing ----------------------------------------------


def test_config_and_cli_plumbing():
    assert SchedulerConfiguration().speculative_dispatch is True
    cfg = load_config({"speculativeDispatch": False})
    assert cfg.speculative_dispatch is False
    from k8s_scheduler_tpu.cmd.main import new_scheduler_command

    ap = new_scheduler_command()
    args = ap.parse_args(["--speculative-dispatch", "0"])
    assert args.speculative_dispatch == 0
    assert ap.parse_args([]).speculative_dispatch == -1

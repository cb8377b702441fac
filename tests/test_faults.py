"""Fault-injection framework, dispatch watchdog, and degradation ladder
(ISSUE 9): plan parsing/determinism, the watchdog's bound on a hung
decision fetch, ladder transitions + promotion + observability wiring,
fetch-failure attribution, journal-ENOSPC stateless degrade, and
compile-cache torn/ENOSPC store robustness. The kill -9
crash-during-degradation path rides tests/test_state_failover.py and
the slow-marked soak_chaos smoke below."""

from __future__ import annotations

import importlib.util
import os
import pathlib
import time

import pytest

from k8s_scheduler_tpu.core import faults
from k8s_scheduler_tpu.core.degrade import RUNGS, DegradationLadder
from k8s_scheduler_tpu.core.events import EventRecorder
from k8s_scheduler_tpu.core.observe import ANOMALY_CLASSES, CycleObserver
from k8s_scheduler_tpu.metrics import SchedulerMetrics


@pytest.fixture(autouse=True)
def _disarm():
    """No fault plan may leak across tests (arming is process-global)."""
    yield
    faults.disarm()


# ---- FaultPlan parsing / determinism --------------------------------------


def test_fault_plan_parse_full_grammar():
    p = faults.FaultPlan.parse(
        "seed=9; fetch_hang@cycle=40:ms=5000 ;"
        "device_error@cycle=5..9:kind=wedge:p=0.5:n=2,"
        "journal_enospc"
    )
    assert p.seed == 9
    hang, dev, jrn = p.rules
    assert (hang.point, hang.lo, hang.hi, hang.ms) == (
        "fetch_hang", 40, 40, 5000.0
    )
    assert (dev.point, dev.lo, dev.hi, dev.kind, dev.prob, dev.count) == (
        "device_error", 5, 9, "wedge", 0.5, 2
    )
    assert (jrn.point, jrn.lo, jrn.count) == ("journal_enospc", None, None)


@pytest.mark.parametrize("bad", [
    "nonsense@cycle=1",            # unknown point
    "fetch_hang@cycle",            # param without value
    "fetch_hang@wat=3",            # unknown param
    "device_error@kind=sideways",  # unknown error kind
    "",                            # no rules at all
    "seed=4",                      # seed only, still no rules
])
def test_fault_plan_parse_refuses_bad_specs(bad):
    with pytest.raises(faults.FaultPlanError):
        faults.FaultPlan.parse(bad)


def test_fault_plan_fires_deterministically():
    def run():
        p = faults.FaultPlan.parse(
            "seed=3;fetch_delay@cycle=1..20:p=0.4:ms=1"
        )
        return [
            cyc for cyc in range(1, 21)
            if p.fire("fetch_delay", cyc) is not None
        ]

    a, b = run(), run()
    assert a == b and 0 < len(a) < 20  # seeded, partial, reproducible


def test_fault_plan_window_count_and_log():
    p = faults.FaultPlan.parse("device_error@cycle=5:kind=corrupt:n=1")
    assert p.fire("device_error", 4) is None   # outside window
    assert p.fire("fetch_hang", 5) is None     # other point
    assert p.fire("device_error", 5) is not None
    assert p.fire("device_error", 5) is None   # count exhausted
    assert p.fired_points() == {"device_error"}
    assert p.log[0]["cycle"] == 5 and p.log[0]["kind"] == "corrupt"


def test_unarmed_hooks_are_dead_branches():
    assert faults.ARMED is False
    assert faults.fire("fetch_hang") is None
    assert faults.skew_s() == 0.0
    assert faults.torn_store() is False
    faults.raise_enospc("cache_enospc")  # no plan: must not raise


def test_injected_device_errors_match_real_classifiers():
    from k8s_scheduler_tpu.core.cycle import classify_failure

    for kind, expect in (
        ("transport", "transport"), ("corrupt", "corrupt"),
        ("wedge", "wedge"),
    ):
        faults.arm(faults.FaultPlan.parse(f"device_error@kind={kind}"))
        with pytest.raises(RuntimeError) as ei:
            faults.raise_device_error()
        assert classify_failure(ei.value) == expect
        faults.disarm()


# ---- degradation ladder (unit) --------------------------------------------


def test_ladder_degrade_promote_and_observability():
    m = SchedulerMetrics()
    ev = EventRecorder()
    obs = CycleObserver(metrics=m)
    lad = DegradationLadder(
        promote_after=2, metrics=m, events=ev, observer=obs
    )
    assert lad.rung == 0 and "degraded" in ANOMALY_CLASSES
    assert lad.degrade("tunnel hung", seq=7) == 1
    assert lad.degrade("still hung") == 2
    # bottom is sticky: further failures re-emit without moving past it
    for _ in range(5):
        lad.degrade("cascade")
    assert lad.rung == len(RUNGS) - 1
    # promotion: one rung per promote_after clean cycles
    for _ in range(2):
        lad.note_clean_cycle()
    assert lad.rung == len(RUNGS) - 2
    st = lad.status()
    assert st["name"] == RUNGS[lad.rung]
    assert st["degradations"] == 7
    # observability: events ring + anomaly ring + counters
    reasons = [e.reason for e in ev.events()]
    assert "Degraded" in reasons and "Promoted" in reasons
    degr = [a for a in obs.anomalies() if a["class"] == "degraded"]
    assert degr and degr[0]["seq"] == 7
    assert degr[0]["detail"]["from_rung"] == "normal"
    assert obs.anomaly_counts["degraded"] == len(lad.transitions)
    # fully recover, then one full episode is measurable
    for _ in range(20):
        lad.note_clean_cycle()
    assert lad.rung == 0
    lad.degrade("again")
    lad.note_clean_cycle()
    lad.note_clean_cycle()
    assert len(lad.recovery_episodes_ms()) == 2


def test_ladder_bottom_rung_failures_report_down_not_up():
    """A degrade() at the sticky bottom rung (old == new) must still
    read as a FAILURE — event reason Degraded, anomaly direction down —
    not as a promotion (the old/new comparison would say 'up')."""
    ev = EventRecorder()
    obs = CycleObserver()
    lad = DegradationLadder(promote_after=2, events=ev, observer=obs)
    for _ in range(len(RUNGS)):  # walk to the bottom...
        lad.degrade("cascade")
    ev.clear()
    lad.degrade("still failing")  # ...and fail AT the bottom
    (bottom_ev,) = ev.events()
    assert bottom_ev.reason == "Degraded"
    assert obs.anomalies()[-1]["detail"]["direction"] == "down"


def test_ladder_floor_pins_promotion():
    """With the floor pinned (the scheduler sets it at `stateless`
    after sealing durability away), clean cycles never promote past it
    — the ladder must not report 'normal' while mutations go
    unjournaled."""
    lad = DegradationLadder(promote_after=1)
    for _ in range(len(RUNGS)):
        lad.degrade("cascade")
    lad.floor = len(RUNGS) - 1
    for _ in range(10):
        lad.note_clean_cycle()
    assert lad.rung == len(RUNGS) - 1
    assert lad.status()["floor"] == len(RUNGS) - 1
    # clearing the floor (a fresh process) lets promotion resume
    lad.floor = 0
    lad.note_clean_cycle()
    assert lad.rung == len(RUNGS) - 2


def test_ladder_sticky_bottom_reapplies_rung_actions():
    """PR 8 ladder finding 1 (ISSUE 11 satellite): a degrade() at the
    sticky bottom rung kept old == new and skipped on_transition, so
    the retrace action was never re-applied under continued failure.
    The hook must fire on every DOWN call, sticky repeats included —
    and promotions must still fire only on a real rung change."""
    calls: list[tuple[int, int]] = []
    lad = DegradationLadder(
        promote_after=1, on_transition=lambda o, n, r: calls.append((o, n))
    )
    bottom = len(RUNGS) - 1
    for _ in range(bottom):
        lad.degrade("cascade")
    assert calls == [(i, i + 1) for i in range(bottom)]
    calls.clear()
    lad.degrade("still failing")  # sticky repeat AT the bottom
    assert calls == [(bottom, bottom)], (
        "sticky-bottom degrade must re-fire on_transition"
    )
    calls.clear()
    lad.note_clean_cycle()  # promotion: exactly one hook call, changed rung
    assert calls == [(bottom, bottom - 1)]


def test_scheduler_sticky_retrace_reclears_program_memos():
    """The scheduler-side half of finding 1: the retrace action (clear
    every program memo) runs again on a sticky-bottom repeat, so an
    executable installed after the last clear cannot survive into the
    next retry."""
    from k8s_scheduler_tpu.core.scheduler import Scheduler

    sched = Scheduler(binder=lambda p, n: None)
    bottom = len(RUNGS) - 1
    sched._packed[("stale-regime", "default-scheduler")] = {"fns": ()}
    sched._dev_stable[("stale", 0, 0)] = (None, None)
    sched._on_rung_transition(bottom, bottom, "still failing")
    assert not sched._packed
    assert not sched._dev_stable
    # ...and a promotion (new < old) must NOT clear a live regime
    sched._packed[("live-regime", "default-scheduler")] = {"fns": ()}
    sched._on_rung_transition(bottom, bottom - 1, "promoted")
    assert sched._packed


def test_ladder_transitions_are_a_bounded_ring():
    """PR 8 ladder finding 2 (ISSUE 11 satellite): `transitions` grew
    one dict per degrade forever in a long-lived process. It is now a
    bounded ring; the exact lifetime counts ride the counters."""
    from k8s_scheduler_tpu.core.degrade import TRANSITIONS_CAP

    lad = DegradationLadder(promote_after=1)
    n = TRANSITIONS_CAP + 100
    for _ in range(n):
        lad.degrade("storm")
        lad.note_clean_cycle()
    # every degrade and every promotion transitioned; the ring holds
    # only the recent window, the counters stay exact
    assert len(lad.transitions) == TRANSITIONS_CAP
    assert lad.transitions_total > TRANSITIONS_CAP
    assert lad.degradations == n
    st = lad.status()
    assert st["transitions"] == lad.transitions_total
    assert st["transitions_buffered"] == TRANSITIONS_CAP
    # MTTR episodes stay measurable over the buffered window
    assert lad.recovery_episodes_ms()


def test_observer_raise_anomaly_refuses_unknown_class():
    obs = CycleObserver()
    with pytest.raises(ValueError):
        obs.raise_anomaly("not_a_class")


# ---- dispatch watchdog (unit) ---------------------------------------------


def test_fetch_worker_bounds_a_hang_and_recovers():
    from k8s_scheduler_tpu.core.pipeline import (
        DispatchDeadlineExceeded,
        _FetchWorker,
    )

    w = _FetchWorker()
    assert w.run(lambda: 42, deadline_s=5.0) == 42
    t0 = time.perf_counter()
    with pytest.raises(DispatchDeadlineExceeded):
        w.run(lambda: time.sleep(3.0), deadline_s=0.1)
    assert time.perf_counter() - t0 < 1.0  # bounded, not the full hang
    # the wedged worker was abandoned; a fresh one serves the next call
    assert w.run(lambda: "after", deadline_s=5.0) == "after"
    # exceptions inside the bounded call are delivered whole
    def boom():
        raise ValueError("inner")

    with pytest.raises(ValueError, match="inner"):
        w.run(boom, deadline_s=5.0)


# ---- the acceptance scenario: fetch_hang vs dispatchDeadlineMs ------------


def _make_sched(fault_spec: str, deadline_ms: float = 250.0,
                promote: int = 2, binds=None):
    from k8s_scheduler_tpu.config import SchedulerConfiguration
    from k8s_scheduler_tpu.core.scheduler import Scheduler

    cfg = SchedulerConfiguration(
        dispatch_deadline_ms=deadline_ms,
        degrade_promote_cycles=promote,
        fault_spec=fault_spec,
        pod_initial_backoff_seconds=0.01,
        pod_max_backoff_seconds=0.05,
        pad_existing=256, pad_pods_per_node=128,
        speculative_compile=False,
    )
    sink = binds if binds is not None else []
    return Scheduler(config=cfg, binder=lambda p, n: sink.append(p.uid))


def test_fetch_hang_never_blocks_past_deadline_and_ladder_recovers():
    """The ISSUE acceptance criterion: an injected fetch_hang longer
    than dispatchDeadlineMs never blocks the serve loop past the
    deadline — the watchdog fires, the ladder steps down with event +
    anomaly + gauge + degraded /healthz, every pod requeues, and the
    scheduler promotes back to the top rung within N clean cycles."""
    from k8s_scheduler_tpu.cmd.httpserver import staleness_healthz
    from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

    binds: list[str] = []
    sched = _make_sched(
        "fetch_hang@cycle=3:ms=5000:n=1", deadline_ms=250.0, promote=2,
        binds=binds,
    )
    for nd in make_cluster(4):
        sched.on_node_add(nd)
    healthz = staleness_healthz(None, None, 0.0, ladder=sched.ladder)
    added: set[str] = set()
    walls: dict[int, float] = {}
    rung_after: dict[int, int] = {}
    for i in range(1, 8):
        for p in make_pods(3, seed=300 + i, name_prefix=f"a{i}-"):
            sched.on_pod_add(p)
            added.add(p.uid)
        t0 = time.perf_counter()
        sched.schedule_cycle()
        walls[i] = time.perf_counter() - t0
        rung_after[i] = sched.ladder.rung
        if i == 3:
            # degraded right now: /healthz carries the rung (still 200
            # — the ladder is actively recovering)
            ok, detail = healthz()
            assert ok and detail["degraded"] is True
            assert detail["degradation"]["name"] == "retrace"
        time.sleep(0.02)  # let the short backoffs expire
    # cycles 1-2 warm the programs; cycle 3's wall is watchdog-bounded
    # (the 5 s hang never reaches the serve loop; generous margin for a
    # loaded CI box, still far below the hang)
    assert walls[3] < 2.5, walls
    assert rung_after[3] == 1  # stepped down exactly one rung
    # the hang cycle's pods were requeued, retried, and eventually
    # bound: nothing lost, nothing double-bound
    assert set(binds) == added
    assert len(binds) == len(added)
    # promoted back to the top rung within N clean cycles
    assert sched.ladder.rung == 0
    assert sched.ladder.degradations == 1
    assert sched.ladder.recovery_episodes_ms()
    # attribution: metric + events-ring entry + degraded anomaly + gauge
    vals = {}
    for f in sched.metrics.registry.collect():
        for s in f.samples:
            vals[(s.name, tuple(sorted(s.labels.items())))] = s.value
    assert vals[(
        "scheduler_fetch_failures_total",
        (("class", "deadline"),),
    )] == 1.0
    assert vals[("scheduler_degradation_rung", ())] == 0.0
    assert vals[(
        "scheduler_degradation_transitions_total",
        (("from", "normal"), ("to", "retrace")),
    )] == 1.0
    assert any(
        e.reason == "FetchFailed" for e in sched.events.events()
    )
    assert any(
        e.reason in ("Degraded", "Promoted")
        for e in sched.events.events()
    )
    degr = [
        a for a in sched.observer.anomalies() if a["class"] == "degraded"
    ]
    assert len(degr) == 2  # down + up
    # the aborted cycle left a flight record stamped aborted + rung,
    # and the pods' timelines carry the DispatchFailed attempt
    recs = sched.flight.snapshot()
    ab = [r for r in recs if r.counts.get("aborted")]
    assert len(ab) == 1 and ab[0].counts["rung"] == 1
    some_uid = next(iter(added))
    # at least one pod has a DispatchFailed attempt in its timeline
    failed_attempts = [
        a
        for uid in added
        for a in (sched.pod_timeline(uid) or {}).get("attempts", [])
        if a["result"] == "DispatchFailed"
    ]
    assert failed_attempts and some_uid  # attribution reached timelines


def test_wedge_degrades_but_transport_and_corrupt_are_absorbed():
    """device_error routing: transport and corrupt classes are absorbed
    in-cycle by _Resilient (strikes, no rung change); a wedge fails
    fast and steps the ladder."""
    from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

    binds: list[str] = []
    sched = _make_sched(
        "device_error@cycle=3:kind=transport:n=1;"
        "device_error@cycle=4:kind=corrupt:n=1;"
        "device_error@cycle=6:kind=wedge:n=1",
        deadline_ms=0.0,  # no watchdog: this test is about _Resilient
        promote=2,
        binds=binds,
    )
    for nd in make_cluster(4):
        sched.on_node_add(nd)
    added: set[str] = set()
    for i in range(1, 10):
        for p in make_pods(2, seed=600 + i, name_prefix=f"d{i}-"):
            sched.on_pod_add(p)
            added.add(p.uid)
        rung_before = sched.ladder.rung
        sched.schedule_cycle()
        if i in (3, 4):
            # absorbed: the retry recovered inside the cycle
            assert sched.ladder.rung == rung_before == 0, i
        if i == 6:
            assert sched.ladder.rung == 1  # wedge fails fast
        time.sleep(0.02)
    assert set(binds) == added
    assert sched.ladder.degradations == 1
    # wedge_precursor anomalies recorded the absorbed strikes
    assert sched.observer.anomaly_counts["wedge_precursor"] >= 1


# ---- journal ENOSPC -> stateless degrade ----------------------------------


def test_journal_enospc_degrades_to_stateless(tmp_path):
    from k8s_scheduler_tpu.internal.cache import SchedulerCache
    from k8s_scheduler_tpu.internal.queue import SchedulingQueue
    from k8s_scheduler_tpu.models import MakePod
    from k8s_scheduler_tpu.state import DurableState, StateError

    faults.arm(faults.FaultPlan.parse("journal_enospc@n=1"))
    st = DurableState(str(tmp_path), snapshot_interval_seconds=0)
    q = SchedulingQueue()
    c = SchedulerCache()
    st.attach(q, c)
    q.add(MakePod("p1").req({"cpu": "1"}).obj())
    with pytest.raises(StateError):
        st.journal.flush(timeout=5.0)  # writer died on the injected fault
    assert st.journal.failed is not None
    # the NEXT mutation detaches the emitters (stateless degrade) and
    # the queue keeps serving
    q.add(MakePod("p2").req({"cpu": "1"}).obj())
    assert q._journal is None and c._journal is None
    assert len(q) == 2


# ---- compile-cache store faults -------------------------------------------


def test_cache_enospc_refuses_store_without_crash(tmp_path):
    from k8s_scheduler_tpu.core.compile_cache import CacheKey, CompileCache

    cc = CompileCache(str(tmp_path))
    key = CacheKey("k|v", "cycle")
    faults.arm(faults.FaultPlan.parse("cache_enospc@n=1"))
    assert cc.store(key, b"payload" * 100) is False  # refused, no raise
    assert cc.load(key) is None  # nothing landed
    # the cache is still writable after the fault clears
    assert cc.store(key, b"payload" * 100) is True
    assert cc.load(key) == b"payload" * 100


def test_cache_torn_store_is_refused_at_load(tmp_path):
    from k8s_scheduler_tpu.core.compile_cache import CacheKey, CompileCache

    cc = CompileCache(str(tmp_path))
    key = CacheKey("k|v", "cycle")
    faults.arm(faults.FaultPlan.parse("cache_torn@n=1"))
    assert cc.store(key, b"\x01\x02" * 512) is False
    # a truncated entry IS on disk at the final path...
    assert os.path.exists(os.path.join(str(tmp_path), key.name))
    # ...and load refuses it loudly instead of crashing or returning
    # garbage; a clean re-store then overwrites it whole
    assert cc.load(key) is None
    faults.disarm()
    assert cc.store(key, b"\x01\x02" * 512) is True
    assert cc.load(key) == b"\x01\x02" * 512


# ---- /debug/state + ladder surfacing --------------------------------------


def test_debug_state_and_healthz_carry_the_rung(tmp_path):
    from k8s_scheduler_tpu.cmd.httpserver import staleness_healthz
    from k8s_scheduler_tpu.state import DurableState

    lad = DegradationLadder(promote_after=4)
    st = DurableState(str(tmp_path), snapshot_interval_seconds=0)
    st.degradation = lad
    assert st.status()["degradation"]["rung"] == 0
    lad.degrade("testing")
    assert st.status()["degradation"]["name"] == "retrace"
    healthz = staleness_healthz(None, None, 0.0, ladder=lad)
    ok, detail = healthz()
    assert ok  # degraded is a paging signal, not a liveness failure
    assert detail["degraded"] is True
    assert "retrace" in detail["degraded_reason"]
    st.journal.close()


# ---- chaos soak smoke (slow tier) -----------------------------------------


def _load_soak_chaos():
    path = (
        pathlib.Path(__file__).parent.parent / "scripts" / "soak_chaos.py"
    )
    spec = importlib.util.spec_from_file_location("soak_chaos", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow
def test_soak_chaos_smoke(tmp_path):
    """Smoke subset of scripts/soak_chaos.py: a short plan in which
    every fault class fires once (serve + enospc phases in-process,
    the kill -9 crash phase as a subprocess), all invariants asserted
    by the phases themselves."""
    soak = _load_soak_chaos()
    serve = soak.run_serve_phase(
        cycles=30, cache_dir=str(tmp_path / "cc"), verbose=False
    )
    assert serve["bound"] == serve["added"]
    assert serve["mttr_ms"] > 0
    assert serve["degraded_cycles"] > 0
    enospc = soak.run_enospc_phase(str(tmp_path / "en"), verbose=False)
    assert enospc["journal_failed"]
    crash = soak.run_crash_phase(str(tmp_path / "cr"), verbose=False)
    assert crash["digest_matched"] and crash["restored_rung"] == 0

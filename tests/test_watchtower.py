"""Watchtower tests (ISSUE 20): the in-process metrics TSDB, the
declarative alert-rule engine, and the crash black box.

Layout mirrors the subsystem:

- TSDB storage: ring wrap, tier downsampling vs a numpy reference,
  seqlock snapshot consistency under a live concurrent writer, the
  series-cardinality ceiling, and the unarmed-hook overhead contract.
- Rules: `for`-duration gating, clear-threshold + symmetric-hold
  hysteresis (no flap), recording rules, file loading, validation —
  and the FaultPlan-shaped acceptance scenario: a stall burst fires
  `tunnel_stall_burst` only after its hold, then resolves cleanly,
  with both wall timestamps queryable.
- Black box: bundle round-trip through `scripts/blackbox_read.py`,
  retention rotation, throttling, and the unarmed trigger no-op.
- Endpoints: /debug/metrics/history, /debug/alerts, /debug/dashboard,
  the /debug/anomalies tenant filter, and the /debug/state ladder
  transition ring.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from k8s_scheduler_tpu.core import blackbox as _blackbox
from k8s_scheduler_tpu.core.degrade import DegradationLadder
from k8s_scheduler_tpu.core.observe import CycleObserver
from k8s_scheduler_tpu.metrics import tsdb as _tsdb
from k8s_scheduler_tpu.metrics.metrics import SchedulerMetrics
from k8s_scheduler_tpu.metrics.rules import (
    Rule,
    RuleEngine,
    builtin_rules,
    load_rules_file,
    scale_rules,
)
from k8s_scheduler_tpu.metrics.tsdb import MetricsTSDB

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarmed():
    """Every test starts and ends with both modules disarmed."""
    yield
    _tsdb.disarm()
    _blackbox.disarm()


# ---- TSDB storage ---------------------------------------------------------


def test_raw_ring_wraps_and_keeps_newest():
    db = MetricsTSDB(raw_cap=16, sec_cap=16, min_cap=16)
    for i in range(40):
        db.append("f", (), float(i), t=1000.0 + i)
    q = db.query("f", window_s=1e9, now=1040.0)
    (s,) = q["series"]
    assert s["total_samples"] == 40
    assert len(s["points"]) == 16  # capped at the ring size
    assert [p[1] for p in s["points"]] == [float(i) for i in range(24, 40)]
    ts = [p[0] for p in s["points"]]
    assert ts == sorted(ts)


def test_query_tier_selection_and_window_clip():
    db = MetricsTSDB()
    for i in range(120):
        db.append("f", {"k": "a"}, float(i), t=1000.0 + i)
    raw = db.query("f", window_s=10.0, now=1120.0)
    assert raw["tier"] == "raw"
    assert all(len(p) == 2 and p[0] >= 1110.0 for p in raw["series"][0]["points"])
    sec = db.query("f", window_s=30.0, step_s=1.0, now=1120.0)
    assert sec["tier"] == "1s"
    assert all(len(p) == 6 for p in sec["series"][0]["points"])
    mn = db.query("f", window_s=1e9, step_s=60.0, now=1120.0)
    assert mn["tier"] == "1m"
    # 120 one-second samples spanning 1000..1119 cover exactly 2 full
    # minute buckets + the open one
    assert len(mn["series"][0]["points"]) == 3


def test_label_selector_is_subset_match():
    db = MetricsTSDB()
    db.append("f", {"cls": "a", "x": "1"}, 1.0, t=10.0)
    db.append("f", {"cls": "b", "x": "1"}, 2.0, t=10.0)
    q = db.query("f", labels={"cls": "a"}, window_s=1e9, now=11.0)
    assert len(q["series"]) == 1
    assert q["series"][0]["labels"] == {"cls": "a", "x": "1"}
    q = db.query("f", labels={"x": "1"}, window_s=1e9, now=11.0)
    assert len(q["series"]) == 2


def test_downsample_matches_numpy_reference():
    """1 s and 1 m buckets (flushed + open) agree with a numpy groupby
    over the same randomized series."""
    rng = np.random.default_rng(7)
    t0 = 5000.0
    ts = np.sort(t0 + rng.uniform(0, 180.0, size=400))
    vs = rng.normal(10.0, 4.0, size=400)
    db = MetricsTSDB(raw_cap=1024, sec_cap=1024, min_cap=64)
    for t, v in zip(ts, vs):
        db.append("f", (), float(v), t=float(t))
    for step, width in ((1.0, 1.0), (60.0, 60.0)):
        q = db.query("f", window_s=1e9, step_s=step, now=float(ts[-1]) + 1)
        (s,) = q["series"]
        for bt, mn, mx, sm, cnt, last in s["points"]:
            mask = (ts >= bt) & (ts < bt + width)
            ref = vs[mask]
            assert cnt == int(mask.sum())
            assert mn == pytest.approx(ref.min())
            assert mx == pytest.approx(ref.max())
            assert sm == pytest.approx(ref.sum())
            assert last == pytest.approx(ref[-1])
        # the buckets cover every sample exactly once
        assert sum(p[4] for p in s["points"]) == len(ts)


def test_seqlock_snapshot_consistent_under_live_writer():
    """A reader snapshotting while a writer appends never sees a torn
    point: every raw point keeps the v == t invariant the writer
    maintains, and timestamps stay strictly increasing."""
    db = MetricsTSDB(raw_cap=64, sec_cap=64, min_cap=64)
    stop = threading.Event()
    wrote = [0]

    def writer():
        i = 0
        while not stop.is_set():
            db.append("f", (), float(i), t=float(i))
            i += 1
        wrote[0] = i

    th = threading.Thread(target=writer)
    th.start()
    try:
        deadline = time.monotonic() + 0.5
        reads = 0
        while time.monotonic() < deadline:
            q = db.query("f", window_s=1e9, now=1e12)
            for pt in q["series"][0]["points"] if q["series"] else []:
                assert pt[0] == pt[1]  # never a half-written pair
            snap = db.snapshot_all()
            for s in snap["series"]:
                ts = [p[0] for p in s["raw"]]
                assert ts == sorted(ts)
                for t, v in s["raw"]:
                    assert t == v
            reads += 1
    finally:
        stop.set()
        th.join()
    assert reads > 10 and wrote[0] > 100


def test_series_cardinality_ceiling_drops_not_grows():
    db = MetricsTSDB(max_series=4)
    for i in range(10):
        db.append("f", {"i": str(i)}, 1.0, t=10.0)
    st = db.status()
    assert st["series"] == 4
    assert st["dropped_series"] == 6


def test_unarmed_observe_record_is_a_noop():
    """The unarmed hook must not sample (one flag check and out)."""

    class Rec:
        wall_start = 1.0
        phases = {"total": 5.0}
        counts = {"pods": 3}

    db = MetricsTSDB()
    assert not _tsdb.ARMED
    db.observe_record(Rec())
    assert db.status()["series"] == 0
    _tsdb.arm(db)
    db.observe_record(Rec())
    assert db.status()["series"] == 2  # cycle_phase_ms + cycle_count
    fams = {f["family"] for f in db.families()}
    assert fams == {"cycle_phase_ms", "cycle_count"}


def test_arm_disarm_keeps_store_readable():
    db = _tsdb.arm(MetricsTSDB())
    db.append("f", (), 1.0, t=5.0)
    _tsdb.disarm()
    assert not _tsdb.ARMED and _tsdb.STORE is None
    # post-mortem reads still work (the black box relies on this)
    assert db.query("f", window_s=1e9, now=6.0)["series"]


def test_ticker_samples_registry_gauges(tmp_path):
    gm = SchedulerMetrics()
    db = _tsdb.arm(MetricsTSDB())
    db.start_ticker(gm.registry, interval_s=0.05)
    try:
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            q = db.query("scheduler_uptime_seconds", window_s=1e9)
            if q["series"] and q["series"][0]["points"]:
                break
            time.sleep(0.05)
        else:
            pytest.fail("ticker never sampled scheduler_uptime_seconds")
        # scrape-time gauge evaluated through collect() (whole seconds,
        # so a sub-second-old process legitimately reads 0)
        assert q["series"][0]["points"][-1][1] >= 0.0
        # histogram bucket fan-out is excluded from storage
        assert not [f for f in db.families()
                    if f["family"].endswith("_bucket")]
    finally:
        _tsdb.disarm()
    assert db._ticker is None  # disarm joined the ticker thread


# ---- rules ----------------------------------------------------------------


def _mk_engine(rule: Rule, **kw):
    db = MetricsTSDB()
    return db, RuleEngine([rule], db, **kw)


def test_for_duration_gates_firing():
    rule = Rule(name="r", family="f", agg="last", window_s=10.0,
                threshold=1.0, for_s=5.0)
    db, eng = _mk_engine(rule)
    st = eng._states["r"]
    db.append("f", (), 2.0, t=100.0)
    eng.evaluate(now=100.0)
    assert st.stage == "pending" and eng.fired_total == 0
    db.append("f", (), 2.0, t=103.0)
    eng.evaluate(now=103.0)  # held 3 s < for_s
    assert st.stage == "pending" and eng.fired_total == 0
    db.append("f", (), 2.0, t=105.5)
    eng.evaluate(now=105.5)  # held 5.5 s >= for_s
    assert st.stage == "firing" and eng.fired_total == 1
    (active,) = eng.status()["active"]
    assert active["fired_wall"] == 105.5
    assert active["resolved_wall"] is None


def test_pending_resets_when_condition_breaks_before_hold():
    rule = Rule(name="r", family="f", agg="last", window_s=10.0,
                threshold=1.0, for_s=5.0)
    db, eng = _mk_engine(rule)
    db.append("f", (), 2.0, t=100.0)
    eng.evaluate(now=100.0)
    db.append("f", (), 0.0, t=102.0)  # breaks before the hold
    eng.evaluate(now=102.0)
    assert eng._states["r"].stage == "ok"
    db.append("f", (), 2.0, t=104.0)
    eng.evaluate(now=104.0)
    eng.evaluate(now=108.0)  # held only 4 s since the RESTART
    assert eng.fired_total == 0


def test_hysteresis_no_flap_and_resolve_timestamps():
    """Once firing, values oscillating between `clear` and `threshold`
    keep the alert firing; resolution needs the value below `clear`
    held for the symmetric duration — then both wall timestamps land
    in the resolved tail."""
    rule = Rule(name="r", family="f", agg="last", window_s=30.0,
                threshold=1.0, for_s=4.0, clear=0.3)
    db, eng = _mk_engine(rule)
    st = eng._states["r"]
    for t in (100.0, 105.0):
        db.append("f", (), 2.0, t=t)
        eng.evaluate(now=t)
    assert st.stage == "firing" and eng.fired_total == 1
    # oscillate in the hysteresis band: below threshold, above clear
    for t in (107.0, 109.0, 111.0, 113.0):
        db.append("f", (), 0.6 if int(t) % 4 else 1.4, t=t)
        eng.evaluate(now=t)
        assert st.stage == "firing", t
    # drop below clear, but pop back up once before the hold elapses:
    # the clear clock must restart, not resolve
    db.append("f", (), 0.1, t=115.0)
    eng.evaluate(now=115.0)
    db.append("f", (), 0.6, t=117.0)
    eng.evaluate(now=117.0)
    assert st.stage == "firing"
    # now hold below clear for >= for_s
    db.append("f", (), 0.1, t=119.0)
    eng.evaluate(now=119.0)
    db.append("f", (), 0.1, t=124.0)
    eng.evaluate(now=124.0)
    assert st.stage == "ok"
    assert eng.fired_total == 1  # one firing, despite all oscillation
    status = eng.status()
    assert status["active"] == []
    (resolved,) = status["resolved"]
    assert resolved["fired_wall"] == 105.0
    assert resolved["resolved_wall"] == 124.0
    assert resolved["resolved_wall"] > resolved["fired_wall"]


def test_rate_agg_sums_series_and_clamps_counter_reset():
    rule = Rule(name="r", family="f", agg="rate", window_s=100.0,
                threshold=0.5, for_s=0.0)
    db, eng = _mk_engine(rule)
    # two labelsets, each rising 1/s -> combined rate 2/s
    for t in range(100, 111):
        db.append("f", {"k": "a"}, float(t - 100), t=float(t))
        db.append("f", {"k": "b"}, float(t - 100), t=float(t))
    assert eng._value(rule, now=110.0) == pytest.approx(2.0)
    # a counter reset reads as quiet, not a huge negative rate
    db2, eng2 = _mk_engine(rule)
    db2.append("f", (), 1000.0, t=100.0)
    db2.append("f", (), 1.0, t=110.0)
    assert eng2._value(rule, now=110.0) == 0.0


def test_recording_rule_appends_derived_series():
    rule = Rule(name="rec", family="f", agg="rate", window_s=60.0,
                kind="record", record_as="f_rate_1m")
    db, eng = _mk_engine(rule)
    for t in range(100, 120):
        db.append("f", (), float(t - 100), t=float(t))
    eng.evaluate(now=119.0)
    q = db.query("f_rate_1m", window_s=1e9, now=120.0)
    assert q["series"][0]["points"][-1][1] == pytest.approx(1.0)


def test_rule_validation_and_file_loading(tmp_path):
    with pytest.raises(ValueError):
        Rule.from_dict({"name": "x", "family": "f", "agg": "wat",
                        "window_s": 1.0})
    with pytest.raises(ValueError):
        Rule.from_dict({"name": "x", "family": "f", "agg": "avg",
                        "window_s": 1.0, "severity": "page-me"})
    with pytest.raises(ValueError):
        Rule.from_dict({"name": "x", "family": "f", "agg": "avg",
                        "window_s": 1.0, "kind": "record"})  # no record_as
    rules_json = tmp_path / "rules.json"
    rules_json.write_text(json.dumps([
        {"name": "x", "family": "f", "agg": "avg", "window_s": 5.0,
         "threshold": 2.0, "labels": {"k": "v"}},
    ]))
    (r,) = load_rules_file(str(rules_json))
    assert r.labels == (("k", "v"),)
    rules_yaml = tmp_path / "rules.yaml"
    rules_yaml.write_text(
        "- name: y\n  family: g\n  agg: max\n  window_s: 9\n"
        "  threshold: 3\n")
    (r,) = load_rules_file(str(rules_yaml))
    assert r.name == "y" and r.window_s == 9.0


def test_scale_rules_shrinks_windows_only():
    scaled = scale_rules(builtin_rules(), 0.1)
    orig = {r.name: r for r in builtin_rules()}
    for r in scaled:
        assert r.window_s == pytest.approx(orig[r.name].window_s * 0.1)
        assert r.for_s == pytest.approx(orig[r.name].for_s * 0.1)
        assert r.threshold == orig[r.name].threshold


def test_builtin_pack_parses_and_is_quiet_on_empty_store():
    db = MetricsTSDB()
    eng = RuleEngine(builtin_rules(), db)
    eng.evaluate(now=100.0)
    assert eng.fired_total == 0
    assert {r["state"] for r in eng.status()["rules"]} <= {"ok"}


# ---- the FaultPlan-shaped stall acceptance scenario -----------------------


def test_faultplan_stall_burst_fires_after_hold_and_resolves():
    """The acceptance scenario: a FaultPlan drives which cycles stall
    (the `fetch_hang` grammar), the PRODUCTION anomaly classifier turns
    the stalls into `tunnel_stall` anomalies, and the unmodified
    built-in `tunnel_stall_burst` rule fires only after its 10 s hold,
    stays up through the burst, and resolves with hysteresis once the
    plan goes quiet — with both timestamps queryable."""
    from k8s_scheduler_tpu.core import faults

    plan = faults.FaultPlan.parse("fetch_hang@cycle=40..75:ms=28000")
    metrics = SchedulerMetrics()
    obs = CycleObserver(metrics=metrics)
    db = MetricsTSDB()
    eng = RuleEngine(
        [r for r in builtin_rules() if r.name == "tunnel_stall_burst"],
        db, observer=obs, metrics=metrics)
    st = eng._states["tunnel_stall_burst"]
    fired_at = resolved_at = None
    first_stall = None
    for c in range(140):
        hang = plan.fire("fetch_hang", c)
        t = 28.0 if hang is not None else 0.5
        obs.observe_phases(
            {"total": t, "device": t, "decision_fetch": t},
            profile="fault", seq=c)
        now = float(c + 1)  # virtual clock: 1 s per cycle
        n = obs.anomaly_counts.get("tunnel_stall", 0)
        if n and first_stall is None:
            first_stall = now
        db.append("scheduler_anomalies_total",
                  {"class": "tunnel_stall"}, float(n), t=now)
        eng.evaluate(now=now)
        if st.stage == "firing" and fired_at is None:
            fired_at = now
        if fired_at is not None and resolved_at is None \
                and st.stage == "ok":
            resolved_at = now
    assert first_stall is not None  # the classifier saw the fault
    assert fired_at is not None and resolved_at is not None
    # the for-duration gated the page: never before hold elapsed
    assert fired_at >= first_stall + 10.0
    assert resolved_at > 75  # only after the plan went quiet
    assert eng.fired_total == 1  # burst + recovery, zero flap
    (resolved,) = eng.status()["resolved"]
    assert resolved["rule"] == "tunnel_stall_burst"
    assert resolved["severity"] == "critical"
    assert resolved["fired_wall"] == fired_at
    assert resolved["resolved_wall"] == resolved_at
    # the firing raised the `alert` anomaly with rule attribution
    alerts = [e for e in obs.anomalies() if e["class"] == "alert"]
    assert len(alerts) == 1
    assert alerts[0]["detail"]["rule"] == "tunnel_stall_burst"
    # ...and the counter metric
    vals = {}
    for f in metrics.registry.collect():
        for s in f.samples:
            vals[(s.name, tuple(sorted(s.labels.items())))] = s.value
    assert vals[("scheduler_alerts_total_total" if (
        "scheduler_alerts_total_total",
        (("rule", "tunnel_stall_burst"), ("severity", "critical")),
    ) in vals else "scheduler_alerts_total",
        (("rule", "tunnel_stall_burst"), ("severity", "critical")))] == 1.0


# ---- black box ------------------------------------------------------------


def _loaded_box(tmp_path, retention=8):
    metrics = SchedulerMetrics()
    obs = CycleObserver(metrics=metrics)
    obs.raise_anomaly("tunnel_stall", seq=7, profile="t", value_s=28.0)
    db = MetricsTSDB()
    db.append("f", (), 1.0, t=100.0)
    eng = RuleEngine(builtin_rules(), db, observer=obs, metrics=metrics)
    lad = DegradationLadder(promote_after=2)
    lad.degrade("blackbox-test")
    return _blackbox.BlackBox(
        str(tmp_path / "bb"), retention=retention,
        config={"statePath": "x"}, observer=obs, tsdb=db, engine=eng,
        ladder=lad)


def test_blackbox_bundle_round_trip(tmp_path):
    box = _loaded_box(tmp_path)
    path = box.dump("watchdog", "seq=7 deadline")
    assert path is not None and os.path.exists(path)
    assert not os.path.exists(path + ".tmp")
    b = _blackbox.load_bundle(path)
    assert b["trigger"] == "watchdog"
    assert b["detail"] == "seq=7 deadline"
    assert b["config"] == {"statePath": "x"}
    # the anomaly tail matches the injected fault
    evs = b["anomalies"]["events"]
    assert evs[-1]["class"] == "tunnel_stall" and evs[-1]["seq"] == 7
    assert b["alerts"]["fired_total"] == 0
    assert b["metrics_history"]["series"][0]["family"] == "f"
    (tr,) = b["ladder"]["transitions"]
    assert tr["reason"] == "blackbox-test" and "wall" in tr


def test_blackbox_throttle_and_sigterm_exemption(tmp_path):
    box = _loaded_box(tmp_path)
    assert box.dump("watchdog") is not None
    assert box.dump("watchdog") is None  # throttled per trigger
    assert box.dump("stateless") is not None  # other trigger unaffected
    assert box.dump("sigterm") is not None  # exempt
    assert box.dump("sigterm") is not None
    assert box.dumps == 4


def test_blackbox_retention_keeps_newest(tmp_path):
    box = _loaded_box(tmp_path, retention=2)
    box._last_dump = {}  # bypass throttle; rotation is what's under test
    paths = []
    for i in range(4):
        paths.append(box.dump("sigterm", f"n{i}"))
    names = box.status()["bundles"]
    assert len(names) == 2
    assert os.path.basename(paths[-1]) in names
    assert os.path.basename(paths[-2]) in names
    # sequence numbers keep rising past rotated-away bundles
    assert names[-1].startswith("blackbox-000003-")


def test_blackbox_trigger_unarmed_is_noop_and_armed_dumps(tmp_path):
    assert _blackbox.trigger("watchdog", "x") is None  # unarmed: no-op
    box = _blackbox.arm(_loaded_box(tmp_path))
    p = _blackbox.trigger("watchdog", "armed now")
    assert p is not None
    _blackbox.disarm()
    assert _blackbox.trigger("watchdog") is None
    assert box.dumps == 1


def test_blackbox_read_script_round_trip(tmp_path):
    """scripts/blackbox_read.py: summary on a directory (newest bundle),
    --json dump, and --perfetto trace extraction."""
    from k8s_scheduler_tpu.core import Scheduler
    from k8s_scheduler_tpu.models import MakeNode, MakePod

    sched = Scheduler(binder=lambda pod, node: None)
    sched.on_node_add(MakeNode("n0").capacity({"cpu": "8"}).obj())
    sched.on_pod_add(MakePod("p0").req({"cpu": "1"}).obj())
    sched.schedule_cycle()
    box = _blackbox.BlackBox(
        str(tmp_path / "bb"), recorder=sched.flight,
        observer=sched.observer, ladder=sched.ladder,
        events=sched.events)
    box.dump("serve_loop", "boom")
    script = os.path.join(REPO, "scripts", "blackbox_read.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, script, str(tmp_path / "bb")],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "trigger:  serve_loop  (boom)" in r.stdout
    r = subprocess.run(
        [sys.executable, script, box.last_path, "--json"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout)["trigger"] == "serve_loop"
    out = str(tmp_path / "trace.json")
    r = subprocess.run(
        [sys.executable, script, box.last_path, "--perfetto", out],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    trace = json.load(open(out))
    assert trace.get("traceEvents")


# ---- endpoints ------------------------------------------------------------


def _get(url):
    with urllib.request.urlopen(url) as r:
        return r.status, dict(r.headers), r.read()


def _watch_server(tmp_path):
    from k8s_scheduler_tpu.cmd.httpserver import start_http_server
    from k8s_scheduler_tpu.state import DurableState

    metrics = SchedulerMetrics()
    obs = CycleObserver(metrics=metrics)
    obs.raise_anomaly("tenant_starved", seq=3, profile="arena",
                      tenant="team-a", pending=4, streak=9)
    obs.raise_anomaly("tenant_starved", seq=4, profile="arena",
                      tenant="team-b", pending=1, streak=5)
    obs.raise_anomaly("tunnel_stall", seq=5, profile="p", value_s=2.0)
    db = MetricsTSDB()
    now = time.time()
    for i in range(30):
        db.append("scheduler_slo_burn_rate", {"window": "fast"},
                  0.4, t=now - 30.0 + i)
    eng = RuleEngine(builtin_rules(), db, observer=obs)
    eng.evaluate(now=now)
    state = DurableState(str(tmp_path / "st"), snapshot_interval_seconds=0)
    lad = DegradationLadder(promote_after=2)
    lad.degrade("endpoint-test")
    lad.note_clean_cycle()
    lad.note_clean_cycle()  # promote_after=2 clean cycles -> back up
    state.degradation = lad
    server = start_http_server(
        metrics, port=0, observer=obs, state=state, tsdb=db, alerts=eng)
    return server, state


def test_history_alerts_dashboard_and_state_endpoints(tmp_path):
    server, state = _watch_server(tmp_path)
    base = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        # inventory form (no family)
        st, _, body = _get(f"{base}/debug/metrics/history")
        assert st == 200
        inv = json.loads(body)
        assert any(f["family"] == "scheduler_slo_burn_rate"
                   for f in inv["families"])
        # series form, with labels + window + step
        st, _, body = _get(
            f"{base}/debug/metrics/history?family=scheduler_slo_burn_rate"
            "&labels=window=fast&window=1000000&step=1")
        assert st == 200
        hist = json.loads(body)
        assert hist["tier"] == "1s"
        assert hist["series"][0]["points"]
        assert hist["series"][0]["labels"] == {"window": "fast"}
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"{base}/debug/metrics/history?family=f&window=nope")
        assert ei.value.code == 400
        # alerts: quiet store, full rule inventory visible
        st, _, body = _get(f"{base}/debug/alerts")
        assert st == 200
        al = json.loads(body)
        assert al["active"] == [] and al["fired_total"] == 0
        assert {r["name"] for r in al["rules"]} == {
            r.name for r in builtin_rules()}
        # dashboard: self-contained HTML
        st, headers, body = _get(f"{base}/debug/dashboard")
        assert st == 200
        assert headers["Content-Type"].startswith("text/html")
        assert b"<svg" in body or b"sparkline" in body.lower()
        # anomaly tenant filter + counts
        st, _, body = _get(f"{base}/debug/anomalies?tenant=team-a")
        assert st == 200
        an = json.loads(body)
        assert an["tenant"] == "team-a"
        assert an["tenant_counts"] == {"team-a": 1, "team-b": 1}
        assert [e["detail"]["tenant"]
                for e in an["anomalies"]] == ["team-a"]
        st, _, body = _get(f"{base}/debug/anomalies")
        assert json.loads(body)["tenant"] is None
        assert len(json.loads(body)["anomalies"]) == 3
        # /debug/state carries the timestamped ladder transition ring
        st, _, body = _get(f"{base}/debug/state")
        assert st == 200
        moves = json.loads(body)["degradation"]["transition_log"]
        assert len(moves) == 2
        assert moves[0]["reason"] == "endpoint-test"
        assert all("wall" in m and "t" in m for m in moves)
        assert moves[0]["to"] > moves[1]["to"]  # down then back up
    finally:
        server.shutdown()
        state.journal.close()


def test_dashboard_disabled_404s(tmp_path):
    from k8s_scheduler_tpu.cmd.httpserver import start_http_server

    db = MetricsTSDB()
    server = start_http_server(
        SchedulerMetrics(), port=0, tsdb=db, dashboard=False)
    base = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(f"{base}/debug/dashboard")
        assert ei.value.code == 404
    finally:
        server.shutdown()


# ---- config / CLI surface -------------------------------------------------


def test_config_knobs_round_trip(tmp_path):
    from k8s_scheduler_tpu.config import load_config

    cfg_file = tmp_path / "cfg.yaml"
    cfg_file.write_text(
        "metricsHistorySamples: 128\n"
        "metricsTickerSeconds: 0.5\n"
        "alertRulesFile: /tmp/rules.yaml\n"
        "blackboxRetention: 3\n"
        "debugDashboard: false\n")
    cfg = load_config(str(cfg_file))
    assert cfg.metrics_history_samples == 128
    assert cfg.metrics_ticker_seconds == 0.5
    assert cfg.alert_rules_file == "/tmp/rules.yaml"
    assert cfg.blackbox_retention == 3
    assert cfg.debug_dashboard is False

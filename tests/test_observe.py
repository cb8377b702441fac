"""Cycle observer (core/observe.py): phase attribution, the anomaly
sentinel under synthetic injection, SLO burn rate, and the
/debug/anomalies + pod-filtered /debug/trace endpoints.

The injection tests are the ISSUE 5 live demonstration: a stalled
tunnel phase, a shape-signature flip, and a fold miss are each
fabricated as flight records, and the assertions pin the exact anomaly
class, the attributed dimension, the metric increments, and the seq
link back to the flight record."""

import json
import urllib.error
import urllib.request

import pytest

from k8s_scheduler_tpu.cmd.httpserver import (
    staleness_healthz,
    start_http_server,
)
from k8s_scheduler_tpu.core.flight_recorder import (
    TRACE_LANE_FOR_PHASE,
    FlightRecorder,
)
from k8s_scheduler_tpu.core.observe import (
    ANOMALY_CLASSES,
    PHASE_BUCKETS_S,
    PHASES,
    CycleObserver,
    SloEngine,
    StreamHist,
    phase_seconds,
)
from k8s_scheduler_tpu.metrics import SchedulerMetrics


def _commit_cycle(
    fr, t0, *, profile="default-scheduler", encode_ms=2.0, fold_ms=0.0,
    device_ms=5.0, fetch_ms=None, bind_ms=1.0, diag_ms=0.0,
    compile_ms=0.0, sig=None, **counts,
):
    """Synthesize one committed record with a realistic mark layout at
    recorder-clock second t0; fetch_ms defaults to the device window."""
    rec = fr.start(profile)
    rec.t_start = t0
    e, d, b = encode_ms / 1e3, device_ms / 1e3, bind_ms / 1e3
    rec.mark("encode_start", t0)
    rec.mark("dispatch_start", t0 + e)
    rec.mark("dispatch_end", t0 + e + 0.0005)
    rec.mark("decision_start", t0 + e + 0.0005)
    rec.mark("decision_end", t0 + e + 0.0005 + d)
    rec.mark("apply_start", t0 + e + 0.0005 + d)
    rec.mark("winners_end", t0 + e + 0.0005 + d + b)
    rec.mark("postfilter_end", t0 + e + 0.0005 + d + b + 0.0002)
    rec.phases.update(
        encode_ms=encode_ms,
        dispatch_ms=0.5,
        decision_wait_ms=device_ms if fetch_ms is None else fetch_ms,
    )
    if fold_ms:
        rec.phases["fold_ms"] = fold_ms
    if diag_ms:
        rec.phases["diag_lag_ms"] = diag_ms
    if compile_ms:
        rec.phases["compile_ms"] = compile_ms
    rec.sig = sig
    rec.counts.update(counts)
    rec.t_end = t0 + e + 0.0005 + d + b + 0.001
    fr.commit(rec)
    return rec


def _observed(metrics=None, **kw):
    """Recorder + attached observer, warmup shrunk for short tests."""
    fr = FlightRecorder(capacity=64)
    obs = CycleObserver(metrics=metrics, warmup_cycles=4, **kw)
    obs.epoch = fr.epoch
    fr.observers.append(obs.observe)
    return fr, obs


# ---- phase attribution ---------------------------------------------------


def test_phase_seconds_decomposition():
    fr = FlightRecorder(capacity=4)
    rec = _commit_cycle(
        fr, 10.0, encode_ms=4.0, fold_ms=1.5, device_ms=6.0,
        diag_ms=2.0, compile_ms=120.0,
    )
    ph = phase_seconds(rec)
    # every emitted phase is a member of the canonical inventory
    assert set(ph) <= set(PHASES)
    # fold is attributed separately; encode keeps the non-fold remainder
    assert abs(ph["encode"] - 0.0025) < 1e-9
    assert abs(ph["fold"] - 0.0015) < 1e-9
    assert abs(ph["device"] - 0.006) < 1e-9
    assert abs(ph["decision_fetch"] - 0.006) < 1e-9
    assert abs(ph["compile"] - 0.120) < 1e-9
    assert abs(ph["diag_lag"] - 0.002) < 1e-9
    assert ph["total"] == rec.t_end - rec.t_start
    # absent work is absent, not zero: a minimal record emits no
    # bind/postfilter/diag/compile phases
    bare = fr.start()
    bare.t_start, bare.t_end = 20.0, 20.001
    assert set(phase_seconds(bare)) == {"total"}


def test_phase_inventory_matches_trace_lanes():
    # the schedlint ID005 contract, asserted at runtime too
    assert set(TRACE_LANE_FOR_PHASE) == set(PHASES)


def test_stream_hist_quantiles():
    h = StreamHist()
    for _ in range(99):
        h.observe(0.004)
    h.observe(28.0)
    # p50 lands inside the bucket owning 0.004; p99+ sees the outlier
    assert 0.0025 <= h.quantile(0.5) <= 0.005
    assert h.quantile(0.999) > 1.0
    assert h.max_seen == 28.0
    assert StreamHist().quantile(0.5) == 0.0


# ---- anomaly sentinel: synthetic injection -------------------------------


def test_injected_tunnel_stall_detected_within_one_cycle():
    m = SchedulerMetrics()
    fr, obs = _observed(metrics=m)
    for i in range(8):
        _commit_cycle(fr, float(i), device_ms=5.0)
    assert obs.anomalies() == []  # baseline traffic is quiet
    stalled = _commit_cycle(fr, 100.0, device_ms=28_000.0)
    evs = obs.anomalies()
    assert len(evs) == 1  # detected in the same cycle it was published
    ev = evs[0]
    assert ev["class"] == "tunnel_stall"
    assert ev["phase"] == "device"
    assert ev["seq"] == stalled.seq
    assert abs(ev["value_ms"] - 28_000.0) < 1.0
    # the seq links to a committed flight record (and thus the matching
    # /debug/trace window)
    assert any(r.seq == ev["seq"] for r in fr.snapshot())
    assert obs.anomaly_counts["tunnel_stall"] == 1
    text = m.expose().decode()
    assert 'scheduler_anomalies_total{class="tunnel_stall"} 1.0' in text
    # the stall fed the phase histogram winsorized: the NEXT identical
    # stall is still an outlier (the baseline did not chase it)
    again = _commit_cycle(fr, 200.0, device_ms=28_000.0)
    assert obs.anomalies()[-1]["seq"] == again.seq
    assert obs.anomaly_counts["tunnel_stall"] == 2
    # ...but the EXPORTED quantiles report the raw tail, not the
    # winsorized baseline: an operator watching p99 during a stall
    # episode must see the stall
    assert obs.quantile("device", 0.99) > 1.0


def test_warmup_stall_does_not_poison_the_baseline():
    fr, obs = _observed()  # warmup_cycles=4
    _commit_cycle(fr, 0.0, device_ms=5.0)
    # a stall INSIDE the warmup window: not classified (too little
    # history to page on)...
    _commit_cycle(fr, 1.0, device_ms=28_000.0)
    assert obs.anomalies() == []
    for i in range(2, 8):
        _commit_cycle(fr, float(i), device_ms=5.0)
    # ...but it was winsorized, not fed raw — so the p99 term did not
    # park at 28 s and the first post-warmup stall still classifies
    rec = _commit_cycle(fr, 100.0, device_ms=28_000.0)
    evs = obs.anomalies()
    assert [e["class"] for e in evs] == ["tunnel_stall"]
    assert evs[0]["seq"] == rec.seq


def test_stall_on_the_very_first_cycle_does_not_poison_baseline():
    """The rig is MOST stall-prone at startup (first-use buffer
    overhead, flaky tunnel): a 28 s outlier on cycle 1 — before any
    baseline exists — must be floor-winsorized like every other warmup
    outlier, not seed ewma/p99 at 28 s and mask the class."""
    fr, obs = _observed()  # warmup_cycles=4
    _commit_cycle(fr, 0.0, device_ms=28_000.0)  # the FIRST sample
    assert obs.anomalies() == []  # warmup: not classified
    for i in range(1, 8):
        _commit_cycle(fr, float(i), device_ms=5.0)
    rec = _commit_cycle(fr, 100.0, device_ms=28_000.0)
    evs = obs.anomalies()
    assert [e["class"] for e in evs] == ["tunnel_stall"]
    assert evs[0]["seq"] == rec.seq


def test_metrics_bucket_edges_cannot_drift():
    """metrics.py keeps a literal copy of PHASE_BUCKETS_S; wiring an
    observer to a metrics object whose exported histogram edges differ
    must refuse loudly instead of letting the exported histogram and
    the streaming quantile gauges silently disagree."""
    import pytest

    m = SchedulerMetrics()
    assert tuple(
        e for e in m.cycle_phase._upper_bounds if e != float("inf")
    ) == PHASE_BUCKETS_S  # the literal copy is in sync today
    CycleObserver(metrics=m)  # in-sync edges wire fine
    m.cycle_phase._upper_bounds = [0.5, 1.0, float("inf")]
    with pytest.raises(ValueError, match="drifted"):
        CycleObserver(metrics=m)


def test_fetch_stall_distinct_from_tunnel_stall():
    fr, obs = _observed()
    for i in range(8):
        _commit_cycle(fr, float(i), device_ms=5.0, fetch_ms=5.0)
    # the blocking fetch crawls while the device round-trip window stays
    # unremarkable: a transfer stall, not a stalled dispatch
    rec = _commit_cycle(fr, 100.0, device_ms=5.0, fetch_ms=2_000.0)
    evs = obs.anomalies()
    assert [e["class"] for e in evs] == ["fetch_stall"]
    assert evs[0]["phase"] == "decision_fetch"
    assert evs[0]["seq"] == rec.seq
    # when BOTH windows stall, tunnel_stall takes precedence (one event)
    _commit_cycle(fr, 200.0, device_ms=2_000.0, fetch_ms=2_000.0)
    assert [e["class"] for e in obs.anomalies()] == [
        "fetch_stall", "tunnel_stall",
    ]


def test_recompile_flip_attributes_dimension():
    m = SchedulerMetrics()
    fr, obs = _observed(metrics=m)
    base_sig = (("E", 256), ("MPN", 16), ("P", 8))
    _commit_cycle(fr, 0.0, sig=base_sig)
    assert obs.anomalies() == []  # first signature is not a flip
    _commit_cycle(fr, 1.0, sig=base_sig)
    assert obs.anomalies() == []  # unchanged signature is not a flip
    flip = _commit_cycle(
        fr, 2.0, sig=(("E", 512), ("MPN", 16), ("P", 8)),
        compile_ms=95_000.0, regime_flip=1,
    )
    evs = obs.anomalies()
    assert len(evs) == 1
    ev = evs[0]
    assert ev["class"] == "recompile" and ev["seq"] == flip.seq
    assert ev["detail"]["dims"] == ["E"]  # the flipping pad dimension
    assert ev["detail"]["from_sig"] == {"E": 256}
    assert ev["detail"]["to_sig"] == {"E": 512}
    assert abs(ev["value_ms"] - 95_000.0) < 1.0
    # a multi-dimension flip names every moved dimension
    _commit_cycle(
        fr, 3.0, sig=(("E", 256), ("MPN", 24), ("P", 8)), regime_flip=1,
    )
    assert obs.anomalies()[-1]["detail"]["dims"] == ["E", "MPN"]
    assert (
        'scheduler_anomalies_total{class="recompile"} 2.0'
        in m.expose().decode()
    )


def test_memoized_flip_flop_is_not_a_recompile():
    """A pad flip-flop riding the scheduler's _packed cache flips the
    signature every cycle but rebuilds nothing (no regime_flip stamp,
    ~zero cost): the sentinel must NOT raise per-cycle recompile events
    for it — an oscillating workload would otherwise flood the ring and
    grow scheduler_anomalies_total{class=recompile} unboundedly."""
    fr, obs = _observed()
    lo = (("P", 64),)
    hi = (("P", 128),)
    # first crossings genuinely rebuild (memo miss -> regime_flip)
    _commit_cycle(fr, 0.0, sig=lo, regime_flip=1, full_encodes=1)
    _commit_cycle(fr, 1.0, sig=hi, regime_flip=1, full_encodes=2)
    assert obs.anomaly_counts["recompile"] == 1  # first cycle is anchor
    # ...then the workload oscillates across the boundary: both regimes
    # are cached, every switch is a memo hit (and its full re-encode is
    # the shape change's fault, not a fold miss)
    for i in range(2, 12):
        _commit_cycle(
            fr, float(i), sig=lo if i % 2 == 0 else hi,
            full_encodes=i + 1,
        )
    assert obs.anomaly_counts["recompile"] == 1  # no spam
    assert obs.anomaly_counts["fold_miss"] == 0
    # a later genuine rebuild (e.g. after cache eviction) still fires,
    # with the dimension attributed from the same-cycle sig diff
    _commit_cycle(fr, 20.0, sig=(("P", 256),), regime_flip=1)
    ev = obs.anomalies()[-1]
    assert ev["class"] == "recompile" and ev["detail"]["dims"] == ["P"]


def test_fold_miss_only_without_regime_flip():
    fr, obs = _observed()
    sig = (("E", 256),)
    _commit_cycle(fr, 0.0, sig=sig, full_encodes=1)
    _commit_cycle(fr, 1.0, sig=sig, full_encodes=1)  # delta-path cycle
    assert obs.anomalies() == []
    # an UNexplained fall off the delta/fold path is a fold miss...
    miss = _commit_cycle(fr, 2.0, sig=sig, full_encodes=2)
    evs = obs.anomalies()
    assert [e["class"] for e in evs] == ["fold_miss"]
    assert evs[0]["seq"] == miss.seq
    assert evs[0]["detail"]["full_encodes"] == 1
    # ...but a full encode WITH a regime flip is the flip's fault: only
    # the recompile event is raised
    _commit_cycle(fr, 3.0, sig=(("E", 512),), full_encodes=3,
                  regime_flip=1)
    assert [e["class"] for e in obs.anomalies()] == [
        "fold_miss", "recompile",
    ]
    # a dictionary-growth recompile (spec.key() changed, every named
    # pad size identical — regime_flip stamped, signature unchanged) is
    # a recompile with no flipping dimension, NOT a fold miss
    _commit_cycle(
        fr, 4.0, sig=(("E", 512),), full_encodes=4, regime_flip=1,
    )
    ev = obs.anomalies()[-1]
    assert ev["class"] == "recompile"
    assert ev["detail"]["dims"] == []
    assert obs.anomaly_counts["fold_miss"] == 1  # unchanged


@pytest.mark.parametrize("declined, misses", [
    ((0, 0, 1, 2), 0),  # every full encode is one the fold asked for
    ((0, 0, 0, 1), 1),  # one of the two was not
    ((0, 0, 0, 0), 2),
    (None, 2),          # a record that keeps no such count (older program)
], ids=["all_declined", "one_unexplained", "none_declined", "no_count"])
def test_a_full_encode_the_fold_declined_into_is_no_fold_miss(
        declined, misses):
    """`fold_declined` rising beside `full_encodes` explains the full
    encode (more of the existing set changed than stayed: the encoder's
    own rule); what rises beyond it is still a miss."""
    fr, obs = _observed()
    sig = (("E", 256),)
    for i, full in enumerate((1, 1, 2, 3)):
        extra = {} if declined is None else {"fold_declined": declined[i]}
        _commit_cycle(fr, float(i), sig=sig, full_encodes=full, **extra)
    evs = obs.anomalies()
    assert [e["class"] for e in evs] == ["fold_miss"] * misses
    assert all(e["detail"]["full_encodes"] == 1 for e in evs)


def test_wedge_precursor_from_strike_deltas():
    fr, obs = _observed()
    _commit_cycle(fr, 0.0, retry_strikes_total=2)  # pre-existing strikes
    assert obs.anomalies() == []  # first observation is the anchor
    _commit_cycle(fr, 1.0, retry_strikes_total=2)
    assert obs.anomalies() == []  # no new strikes
    rec = _commit_cycle(fr, 2.0, retry_strikes_total=5)
    evs = obs.anomalies()
    assert [e["class"] for e in evs] == ["wedge_precursor"]
    assert evs[0]["seq"] == rec.seq
    assert evs[0]["detail"]["strikes"] == 3
    # the strike counter is PROCESS-global (RESILIENT_STRIKES): every
    # profile's record carries the same sum, so a multi-profile cycle
    # must not raise the same strike once per profile
    _commit_cycle(fr, 3.0, profile="gpu-sched", retry_strikes_total=5)
    _commit_cycle(fr, 3.1, retry_strikes_total=5)
    assert obs.anomaly_counts["wedge_precursor"] == 1
    _commit_cycle(fr, 4.0, profile="gpu-sched", retry_strikes_total=6)
    _commit_cycle(fr, 4.1, retry_strikes_total=6)
    assert obs.anomaly_counts["wedge_precursor"] == 2  # one new strike


def test_round_cap_hit_from_the_running_count():
    """The records' `round_cap_hits` is the scheduler's running total
    of cycles whose commit rounds ended at `max_rounds` with claimants
    unjudged: a cycle in which it rises is an anomaly of its own class,
    with the rounds run so far in the detail; the first record is the
    anchor, and a record without the count (a program that returns the
    latency subset) raises nothing."""
    fr, obs = _observed()
    _commit_cycle(fr, 0.0, round_cap_hits=1, commit_rounds=64)
    _commit_cycle(fr, 1.0, round_cap_hits=1, commit_rounds=70)
    _commit_cycle(fr, 2.0)
    assert obs.anomalies() == []
    rec = _commit_cycle(fr, 3.0, round_cap_hits=2, commit_rounds=134)
    (ev,) = obs.anomalies()
    assert (ev["class"], ev["seq"]) == ("round_cap_hit", rec.seq)
    assert ev["detail"] == {"commit_rounds": 134}
    # one total for every profile: the next profile's record of the
    # same cycle carries the same number and raises nothing
    _commit_cycle(fr, 3.1, profile="gpu-sched", round_cap_hits=2)
    assert obs.anomaly_counts["round_cap_hit"] == 1


def test_anomaly_ring_is_bounded_and_last_filters():
    fr, obs = _observed(ring=8)
    for i in range(8):
        _commit_cycle(fr, float(i), device_ms=5.0)
    for i in range(20):
        _commit_cycle(fr, 100.0 + i, device_ms=28_000.0)
    assert obs.anomaly_counts["tunnel_stall"] == 20  # counts keep going
    assert len(obs.anomalies()) == 8  # ring stays bounded
    assert len(obs.anomalies(last=3)) == 3
    assert obs.anomalies(last=0) == []


def test_failing_observer_detaches_without_killing_the_loop():
    fr = FlightRecorder(capacity=8)
    calls = {"n": 0}

    def bad(rec):
        calls["n"] += 1
        raise RuntimeError("observer bug")

    fr.observers.append(bad)
    _commit_cycle(fr, 0.0)
    _commit_cycle(fr, 1.0)  # does not raise
    assert calls["n"] == 1  # detached after the first failure
    assert fr.observers == []
    assert fr.cycles == 2


# ---- SLO engine ----------------------------------------------------------


def test_slo_engine_burn_rate_and_budget():
    slo = SloEngine(p99_ms=100.0, window_cycles=256)
    assert slo.enabled
    for _ in range(256):
        slo.note(0.05)  # 50 ms: within objective
    assert slo.burn_rate("fast") == 0.0
    assert slo.budget_remaining() == 1.0
    assert not slo.degraded()
    # fast window (256/16 = 16 cycles) of pure violations: burn rate
    # 1.0/0.01 = 100x, way past the 6x degraded threshold
    for _ in range(16):
        assert slo.note(0.5) is True
    assert slo.burn_rate("fast") == 100.0
    assert slo.degraded()
    # slow window: 16 violations vs a budget of 1% of 256 cycles
    assert abs(slo.burn_rate("slow") - (16 / 256) / 0.01) < 1e-9
    assert slo.budget_remaining() < 0  # overspent
    st = slo.status()
    assert st["degraded"] and st["violations"] == 16
    # disabled objective: everything reads neutral
    off = SloEngine(p99_ms=0.0)
    off.note(999.0)
    assert not off.enabled and not off.degraded()
    assert off.burn_rate("fast") == 0.0 and off.budget_remaining() == 1.0


def test_healthz_reports_fast_burn_as_degraded_not_503():
    fr, obs = _observed(slo_p99_ms=10.0, slo_window_cycles=256)
    health = staleness_healthz(lambda: {"bootId": "b"}, fr, 0.0,
                               observer=obs)
    ok, detail = health()
    assert ok and "slo" in detail and "degraded" not in detail
    for i in range(16):
        _commit_cycle(fr, float(i), device_ms=50.0)  # ~53 ms cycles
    ok, detail = health()
    assert ok  # degraded is a paging signal, not a liveness failure
    assert detail["degraded"] is True
    assert "fast-burn" in detail["degraded_reason"]
    assert detail["slo"]["burn_rate"]["fast"] >= 6.0


def test_slo_config_plumbs_to_observer():
    from k8s_scheduler_tpu.config.types import load_config
    from k8s_scheduler_tpu.core import Scheduler

    cfg = load_config("sloP99Ms: 250\nsloWindowCycles: 512")
    assert cfg.slo_p99_ms == 250.0 and cfg.slo_window_cycles == 512
    sched = Scheduler(config=cfg)
    assert sched.observer is not None
    assert sched.observer.slo.p99_ms == 250.0
    assert sched.observer.slo.windows["slow"].maxlen == 512
    # recorder disabled -> no records to observe -> no observer
    cfg_off = load_config("flightRecorderSize: 0")
    assert Scheduler(config=cfg_off).observer is None


# ---- debug endpoints -----------------------------------------------------


def _request(url, method="GET"):
    req = urllib.request.Request(url, method=method)
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def test_debug_anomalies_endpoint_shape_and_head_405():
    m = SchedulerMetrics()
    fr, obs = _observed(metrics=m)
    for i in range(8):
        _commit_cycle(fr, float(i), device_ms=5.0)
    stalled = _commit_cycle(fr, 100.0, device_ms=28_000.0)
    server = start_http_server(m, port=0, observer=obs)
    port = server.server_address[1]
    base = f"http://127.0.0.1:{port}"
    try:
        st, _, body = _request(f"{base}/debug/anomalies")
        assert st == 200
        payload = json.loads(body)
        assert [e["class"] for e in payload["anomalies"]] == [
            "tunnel_stall"
        ]
        assert payload["anomalies"][0]["seq"] == stalled.seq
        assert payload["anomaly_counts"]["tunnel_stall"] == 1
        assert payload["cycles"] == 9
        assert payload["phase_p50_ms"]["device"] > 0
        assert payload["slo"]["enabled"] is False
        # ?last=N trims the ring view, not the counters
        st, _, body = _request(f"{base}/debug/anomalies?last=1")
        assert json.loads(body)["anomaly_counts"]["tunnel_stall"] == 1
        # HEAD parity + 405 for mutating verbs, like every debug route
        gs, gh, gbody = _request(f"{base}/debug/anomalies")
        hs, hh, hbody = _request(f"{base}/debug/anomalies", "HEAD")
        assert (gs, hs) == (200, 200) and hbody == b""
        assert hh["Content-Length"] == str(len(gbody))
        st, headers, _ = _request(f"{base}/debug/anomalies", "POST")
        assert st == 405 and headers["Allow"] == "GET, HEAD"
    finally:
        server.shutdown()
    # without an observer the route 404s like other absent debug routes
    bare = start_http_server(SchedulerMetrics(), port=0)
    bport = bare.server_address[1]
    try:
        st, _, _ = _request(f"http://127.0.0.1:{bport}/debug/anomalies")
        assert st == 404
    finally:
        bare.shutdown()


def test_debug_trace_pod_filter_slices_to_touched_cycles():
    fr = FlightRecorder(capacity=16)
    for i in range(4):
        _commit_cycle(fr, float(i))
    # pod uid-1 was attempted in cycle 2 only (the timeline join key)
    fr.pod_event("uid-1", "pod-1", "Queued")
    fr.pod_event("uid-1", "pod-1", "Attempt", cycle=2, result="Bound")
    server = start_http_server(
        SchedulerMetrics(), port=0, recorder=fr,
        pod_timeline=fr.pods.get,
    )
    port = server.server_address[1]
    base = f"http://127.0.0.1:{port}"
    try:
        st, headers, body = _request(f"{base}/debug/trace?pod=uid-1")
        assert st == 200
        assert "attachment" in headers["Content-Disposition"]
        trace = json.loads(body)
        devices = [
            e["name"] for e in trace["traceEvents"]
            if e.get("ph") == "X" and e["name"].startswith("device cycle")
        ]
        assert devices == ["device cycle[2] slot=-1"]
        # the unfiltered trace still carries every cycle
        st, _, body = _request(f"{base}/debug/trace")
        full = json.loads(body)
        assert sum(
            1 for e in full["traceEvents"]
            if e.get("ph") == "X" and e["name"].startswith("device cycle")
        ) == 4
        # unknown pod: 404 with a JSON error, not an empty trace
        st, _, body = _request(f"{base}/debug/trace?pod=ghost")
        assert st == 404 and "not seen" in json.loads(body)["error"]
        # HEAD parity on the filtered route too
        hs, _, hbody = _request(f"{base}/debug/trace?pod=uid-1", "HEAD")
        assert hs == 200 and hbody == b""
    finally:
        server.shutdown()


# ---- live demonstration: the real scheduler ------------------------------


def test_live_scheduler_recompile_flip_attributed():
    """Drive the REAL Scheduler into a pad-regime flip: the second
    cycle's pending-pod count crosses the pad bucket, the packed regime
    rebuilds, and the observer must classify the recompile WITH the
    flipping dimension — within that same cycle."""
    from k8s_scheduler_tpu.core import Scheduler
    from k8s_scheduler_tpu.models import MakeNode, MakePod

    bound = {}
    sched = Scheduler(
        binder=lambda pod, node: bound.setdefault(pod.name, node),
        pad_bucket=8,
    )
    assert sched.observer is not None  # wired by the ctor
    for i in range(4):
        sched.on_node_add(
            MakeNode(f"n{i}").capacity({"cpu": "64"}).obj()
        )
    sched.on_pod_add(MakePod("p0").req({"cpu": "1"}).obj())
    sched.schedule_cycle()  # P pads to the first bucket
    assert sched.observer.anomalies() == []
    for i in range(1, 12):  # 12 pending pods: P crosses into bucket 16
        sched.on_pod_add(MakePod(f"p{i}").req({"cpu": "1"}).obj())
    sched.schedule_cycle()
    evs = [
        e for e in sched.observer.anomalies()
        if e["class"] == "recompile"
    ]
    assert len(evs) == 1
    ev = evs[0]
    assert "P" in ev["detail"]["dims"]
    assert (
        ev["detail"]["to_sig"]["P"] > ev["detail"]["from_sig"]["P"]
    )
    # the seq links to a real committed flight record of that cycle
    recs = {r.seq: r for r in sched.flight.snapshot()}
    assert ev["seq"] in recs
    assert recs[ev["seq"]].counts.get("regime_flip") == 1
    assert recs[ev["seq"]].phases.get("compile_ms", 0.0) >= 0.0
    # and the counter is visible on the metrics surface
    assert (
        'scheduler_anomalies_total{class="recompile"} 1.0'
        in sched.metrics.expose().decode()
    )
    assert len(bound) == 12  # scheduling itself was undisturbed

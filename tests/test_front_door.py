"""The submission front door (ISSUE 14): admission control,
WAL-before-ack durability, backpressure, drain, and the failover
contract.

Fast tier: admission semantics (accept / shed / invalid), the
durability contract's two fast halves (ack-implies-journaled,
rejected-never-journaled), the half-open degraded trickle, metrics,
the submit_bind flight-record phase, the HTTP POST path, gRPC
round-trip semantics, and graceful drain.

Slow tier: the kill -9 failover mid-loadgen (a real CLI process with
--submit-addr, an open-loop gRPC load, SIGKILL, restore — zero lost
acked pods, zero duplicate binds), the arrivals_via_api fuzz variant,
and the soak_chaos overload phase.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from k8s_scheduler_tpu.config import SchedulerConfiguration
from k8s_scheduler_tpu.core.scheduler import Scheduler
from k8s_scheduler_tpu.internal.cache import SchedulerCache
from k8s_scheduler_tpu.internal.queue import SchedulingQueue
from k8s_scheduler_tpu.service.admission import (
    AdmissionClosed,
    AdmissionController,
    FrontDoor,
)
from k8s_scheduler_tpu.state import DurableState
from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sched(state=None, binds=None, **cfg):
    cfg.setdefault("pod_initial_backoff_seconds", 0.05)
    cfg.setdefault("pod_max_backoff_seconds", 0.2)
    binds = binds if binds is not None else {}
    sched = Scheduler(
        config=SchedulerConfiguration(**cfg),
        binder=lambda p, n: binds.__setitem__(
            p.uid, binds.get(p.uid, 0) + 1
        ),
        state=state,
    )
    return sched, binds


def _restore_bare(state_dir):
    q, c = SchedulingQueue(), SchedulerCache()
    st = DurableState(state_dir, snapshot_interval_seconds=0)
    st.restore_into(q, c)
    st.journal.close()
    return q, c


# ---------------------------------------------------------------------------
# admission semantics (no dispatch needed)
# ---------------------------------------------------------------------------


def test_accept_is_atomic_and_counts_metrics():
    sched, _ = _sched()
    adm = AdmissionController(sched, queue_depth=100)
    pods = make_pods(5, seed=1, name_prefix="a-")
    res = adm.submit(pods)
    assert res.ok and res.accepted == 5 and res.queue_depth == 5
    assert not res.durable  # no state dir
    assert sched.queue.pending_counts()["active"] == 5
    text = sched.metrics.expose().decode()
    assert 'scheduler_admission_total{outcome="accepted"} 5.0' in text
    assert "scheduler_submit_ack_seconds_count 1.0" in text
    assert "scheduler_admission_queue_depth 5.0" in text


def test_shed_on_full_queue_whole_request():
    sched, _ = _sched()
    adm = AdmissionController(sched, queue_depth=6, retry_after_ms=123.0)
    assert adm.submit(make_pods(4, seed=2, name_prefix="b-")).ok
    res = adm.submit(make_pods(4, seed=3, name_prefix="c-"))
    assert res.shed == 4 and not res.ok
    assert "admission queue full" in res.reason
    assert res.retry_after_ms == 123.0
    # atomic: NONE of the shed request's pods were enqueued
    assert sched.queue.pending_counts()["active"] == 4
    assert adm.overloaded() == ""  # 4+1 <= 6: not saturated right now
    assert adm.submit(make_pods(2, seed=30, name_prefix="c2-")).ok
    assert "admission queue full" in adm.overloaded()  # 6+1 > 6
    text = sched.metrics.expose().decode()
    assert 'scheduler_admission_total{outcome="shed"} 4.0' in text


def test_invalid_submissions_reject_whole_request():
    sched, _ = _sched()
    adm = AdmissionController(sched, queue_depth=100)
    good = make_pods(2, seed=4, name_prefix="d-")
    bad = make_pods(1, seed=5, name_prefix="e-")[0]
    bad.metadata.uid = ""
    res = adm.submit(good + [bad])
    assert res.invalid and not res.ok
    assert sched.queue.pending_counts()["active"] == 0  # nothing in
    # duplicate uid within one request
    p = make_pods(1, seed=6, name_prefix="f-")[0]
    res = adm.submit([p, p])
    assert res.invalid
    # duplicate of a still-pending accepted uid
    assert adm.submit([p]).ok
    res = adm.submit([p])
    assert res.invalid and "already pending" in res.reason


def test_delete_before_bind_frees_the_uid():
    """A pod deleted before binding must leave the accepted-pending
    set: a re-created pod reusing the uid is a fresh admission, not
    an 'already pending' duplicate."""
    sched, _ = _sched()
    adm = AdmissionController(sched, queue_depth=100)
    p = make_pods(1, seed=27, name_prefix="del-")[0]
    assert adm.submit([p]).ok
    assert adm.submit([p]).invalid  # still pending: duplicate
    sched.on_pod_delete(p.uid)
    res = adm.submit([p])  # re-created pod, same uid: admitted
    assert res.ok, res.reason


def test_shed_on_degraded_ladder_with_halfopen_trickle():
    sched, _ = _sched()
    adm = AdmissionController(sched, queue_depth=256)
    sched.ladder.degrade("test: forced")
    # the flood sheds (past the half-open trickle bound of depth/8=32)
    res = adm.submit(make_pods(40, seed=7, name_prefix="g-"))
    assert res.shed and "degradation ladder at rung 1" in res.reason
    # with an EMPTY queue the door would still admit a probe — the
    # half-open trickle means "would shed right now" is false here
    assert adm.overloaded() == ""
    # ...but a probe trickle keeps flowing (depth/8 = 32, floor 16):
    # recovery evidence is traffic-driven, a closed door never heals
    res = adm.submit(make_pods(3, seed=8, name_prefix="h-"))
    assert res.ok


def test_shed_on_slo_fast_burn():
    sched, _ = _sched(slo_p99_ms=1.0)
    adm = AdmissionController(sched, queue_depth=256)
    for _ in range(64):
        sched.observer.slo.note(10.0)  # every cycle violates: burn >> 6x
    res = adm.submit(make_pods(40, seed=9, name_prefix="i-"))
    assert res.shed and "SLO fast-burn" in res.reason
    # the half-open trickle still admits a probe
    assert adm.submit(make_pods(2, seed=31, name_prefix="i2-")).ok


def test_draining_after_close():
    sched, _ = _sched()
    adm = AdmissionController(sched, queue_depth=100)
    adm.close()
    res = adm.submit(make_pods(1, seed=10, name_prefix="j-"))
    assert res.reason == "draining" and res.shed == 1
    with pytest.raises(AdmissionClosed):
        adm.node_churn(adds=make_cluster(1))


# ---------------------------------------------------------------------------
# the durability contract (fast halves)
# ---------------------------------------------------------------------------


def test_ack_implies_journaled_across_crash(tmp_path):
    """Crash between ack and dispatch: the acked pods must be fully
    recoverable by replay — no cycle ever ran, no snapshot, no seal."""
    st = DurableState(str(tmp_path), snapshot_interval_seconds=0)
    sched, _ = _sched(state=st)
    adm = AdmissionController(sched, queue_depth=100)
    adm.node_churn(adds=make_cluster(4))
    pods = make_pods(6, seed=11, name_prefix="k-")
    res = adm.submit(pods)
    assert res.ok and res.durable
    # simulate kill -9: no flush, no seal — just read the dir back
    q, c = _restore_bare(str(tmp_path))
    restored = {p.uid for p in q.all_pending()}
    assert {p.uid for p in pods} <= restored
    assert len(c.nodes()) == 4  # NodeChurn journaled too


def test_rejected_submission_never_journaled(tmp_path):
    st = DurableState(str(tmp_path), snapshot_interval_seconds=0)
    sched, _ = _sched(state=st)
    adm = AdmissionController(sched, queue_depth=4)
    assert adm.submit(make_pods(3, seed=12, name_prefix="l-")).ok
    shed = make_pods(4, seed=13, name_prefix="m-")
    assert adm.submit(shed).shed
    bad = make_pods(1, seed=14, name_prefix="n-")[0]
    bad.metadata.uid = ""
    assert adm.submit([bad]).invalid
    st.journal.flush()
    q, _c = _restore_bare(str(tmp_path))
    restored = {p.uid for p in q.all_pending()}
    assert len(restored) == 3
    assert not ({p.uid for p in shed} & restored)


def test_ack_not_durable_after_journal_death(tmp_path):
    """Durability lost mid-run: acks must degrade to durable=False,
    never block or crash."""
    st = DurableState(str(tmp_path), snapshot_interval_seconds=0)
    sched, _ = _sched(state=st)
    adm = AdmissionController(sched, queue_depth=100)
    assert adm.submit(make_pods(1, seed=15, name_prefix="o-")).durable
    st.journal.failed = "ENOSPC (test)"
    res = adm.submit(make_pods(1, seed=16, name_prefix="p-"))
    assert res.ok and not res.durable


# ---------------------------------------------------------------------------
# serving: submit_bind phase + drain
# ---------------------------------------------------------------------------


def test_submit_bind_phase_on_flight_record():
    sched, binds = _sched()
    adm = AdmissionController(sched, queue_depth=100)
    adm.node_churn(adds=make_cluster(4))
    assert adm.submit(make_pods(3, seed=17, name_prefix="q-")).ok
    sched.schedule_cycle()
    assert len(binds) == 3
    recs = [
        r for r in sched.flight.snapshot()
        if "submit_bind_ms" in r.phases
    ]
    assert recs, "no flight record carries the submit_bind phase"
    assert recs[-1].phases["submit_bind_ms"] > 0.0
    # the observer streamed it: scrape-time quantile is live
    assert sched.observer.quantile("submit_bind", 0.5) > 0.0


def test_front_door_drain_flushes_and_closes():
    sched, binds = _sched()
    adm = AdmissionController(sched, queue_depth=100)
    adm.node_churn(adds=make_cluster(4))
    fd = FrontDoor(adm)
    fd.start()
    assert adm.submit(make_pods(4, seed=18, name_prefix="r-")).ok
    drained = fd.stop()  # closes admission, empties the active tier
    assert drained
    assert adm.closed
    assert sched.queue.pending_counts()["active"] == 0
    assert adm.queue_depth() == 0  # the depth counts the queue's tiers
    assert len(binds) == 4
    assert adm.submit(
        make_pods(1, seed=19, name_prefix="s-")
    ).reason == "draining"


def test_resubmit_after_bind_is_rejected():
    """A client retrying a Submit whose ack was lost AFTER the pod
    bound must not re-admit it: note_bind has already dropped the uid
    from the accepted-pending set, so the cache (assumed or bound) is
    the dup authority — re-queueing a bound pod double-schedules it."""
    sched, binds = _sched()
    adm = AdmissionController(sched, queue_depth=100)
    adm.node_churn(adds=make_cluster(2))
    p = make_pods(1, seed=40, name_prefix="rb-")[0]
    assert adm.submit([p]).ok
    sched.schedule_cycle()
    assert binds.get(p.uid) == 1
    res = adm.submit([p])  # retry after bind: duplicate, not fresh
    assert res.invalid and "already bound" in res.reason
    sched.schedule_cycle()
    assert binds.get(p.uid) == 1  # still exactly one bind
    # a genuine delete frees the uid for re-creation
    sched.on_pod_delete(p.uid)
    assert adm.submit([p]).ok


def test_serve_loop_survives_cycle_exception():
    """A host-side exception escaping the cycle must not silently kill
    the serve thread while admission keeps acking: the loop logs,
    counts, backs off, and keeps serving — accepted pods dispatch the
    moment the fault clears."""
    sched, binds = _sched()
    adm = AdmissionController(sched, queue_depth=100)
    adm.node_churn(adds=make_cluster(2))
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise RuntimeError("injected host bug")
        return sched.schedule_cycle()

    fd = FrontDoor(adm, cycle_fn=flaky)
    fd._failure_backoff = 0.01
    fd.start()
    try:
        assert adm.submit(make_pods(2, seed=41, name_prefix="fl-")).ok
        deadline = time.monotonic() + 30
        while len(binds) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(binds) == 2, "loop never recovered from the fault"
        assert fd.cycle_failures == 2
    finally:
        fd.stop(drain=False)


def test_serve_loop_fails_shut_on_fatal_exit():
    """A BaseException killing the loop thread outright (the
    non-Exception escape the retry path cannot absorb) must close
    admission: the door never acks durable pods into a serve loop
    that no longer exists."""
    sched, _ = _sched()
    adm = AdmissionController(sched, queue_depth=100)

    def fatal():
        raise SystemExit(1)

    fd = FrontDoor(adm, cycle_fn=fatal)
    # the injected BaseException IS the test — keep pytest's
    # unhandled-thread-exception hook from flagging it as a warning
    old_hook = threading.excepthook
    threading.excepthook = lambda args: None
    fd.start()
    try:
        deadline = time.monotonic() + 10
        while not adm.closed and time.monotonic() < deadline:
            time.sleep(0.01)
        assert adm.closed
        res = adm.submit(make_pods(1, seed=42, name_prefix="ft-"))
        assert res.reason == "draining" and res.shed == 1
    finally:
        fd.stop(drain=False)
        threading.excepthook = old_hook


def test_local_front_door_confirms_binds_no_ttl_rebind():
    """The agentless CLI path (`--submit-addr`): run_local_cycle
    discards the response-collection list, so without the
    self-confirming binder chain every assumed bind would TTL-expire
    ('AssumeExpired') and re-bind forever. With it, binds are
    confirmed through the informer path each cycle: exactly one bind
    per pod outlives many TTL windows, and the pod lands bound (not
    assumed) in the cache."""
    from k8s_scheduler_tpu.service.admission import (
        self_confirming_front_door,
    )
    from k8s_scheduler_tpu.service.server import SchedulerService

    svc = SchedulerService(
        config=SchedulerConfiguration(
            pod_initial_backoff_seconds=0.05,
            pod_max_backoff_seconds=0.2,
        )
    )
    sched = svc.scheduler
    sched.cache._ttl = 0.05  # expiry chances galore within the test
    adm = AdmissionController(sched, queue_depth=100)
    fd = self_confirming_front_door(svc, adm)
    counts: dict[str, int] = {}
    inner = sched.binder  # the confirm-chained binder

    def counting(p, n):
        counts[p.uid] = counts.get(p.uid, 0) + 1
        inner(p, n)

    sched.binder = counting
    adm.node_churn(adds=make_cluster(2))
    pods = make_pods(3, seed=43, name_prefix="cf-")
    fd.start()
    try:
        assert adm.submit(pods).ok
        deadline = time.monotonic() + 60
        while len(counts) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(counts) == 3, "pods never bound"
        # outlive several TTL windows with the loop running: a missing
        # confirmation would AssumeExpired-requeue and re-bind here
        time.sleep(0.5)
        assert all(c == 1 for c in counts.values()), counts
        for p in pods:
            assert sched.cache.has_pod(p.uid)
            assert not sched.cache.is_assumed(p.uid)
    finally:
        fd.stop(drain=False)


# ---------------------------------------------------------------------------
# HTTP POST path + healthz
# ---------------------------------------------------------------------------


def test_http_submit_path_and_degraded_healthz():
    from k8s_scheduler_tpu.cmd.httpserver import (
        staleness_healthz,
        start_http_server,
        stop_http_server,
    )
    from k8s_scheduler_tpu.state.codec import pod_to_state

    sched, _ = _sched()
    adm = AdmissionController(sched, queue_depth=6, retry_after_ms=500.0)
    healthz = staleness_healthz(
        None, sched.flight, 0.0, observer=sched.observer,
        ladder=sched.ladder, admission=adm,
    )
    server = start_http_server(
        sched.metrics, port=0, healthz=healthz, admission=adm,
    )
    port = server.server_address[1]
    try:
        def post(body: bytes):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/submit", data=body,
                method="POST",
            )
            try:
                r = urllib.request.urlopen(req, timeout=10)
                return r.status, dict(r.headers), json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, dict(e.headers), json.loads(e.read())

        pods = make_pods(6, seed=20, name_prefix="t-")
        body = json.dumps(
            {"pods": [pod_to_state(p) for p in pods]}
        ).encode()
        status, _h, payload = post(body)
        assert status == 200 and payload["accepted"] == 6

        # over the bound: 429 + Retry-After
        more = make_pods(5, seed=21, name_prefix="u-")
        status, headers, payload = post(json.dumps(
            {"pods": [pod_to_state(p) for p in more]}
        ).encode())
        assert status == 429 and payload["shed"] == 5
        # RFC 7231: integer delta-seconds, rounded UP from 500 ms
        assert headers.get("Retry-After") == "1"

        # saturated: /healthz reports degraded (still 200)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10
        ) as r:
            assert r.status == 200
            detail = json.loads(r.read())
        assert detail["degraded"] is True
        assert "admission" in detail

        # garbage body: 400
        status, _h, payload = post(b"{not json")
        assert status == 400 and "error" in payload

        # oversized Content-Length: refused 413 BEFORE any read — the
        # bounded-memory contract holds on the HTTP path too
        with socket.create_connection(
            ("127.0.0.1", port), timeout=10
        ) as s:
            s.sendall(
                b"POST /submit HTTP/1.1\r\nHost: t\r\n"
                b"Content-Length: 10737418240\r\n\r\n"
            )
            first = s.recv(65536).split(b"\r\n", 1)[0]
        assert b"413" in first, first

        # POST anywhere else keeps the read-only 405 contract
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/metrics", data=b"x",
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 405
    finally:
        stop_http_server(server)


# ---------------------------------------------------------------------------
# gRPC round trip
# ---------------------------------------------------------------------------


def test_grpc_submit_shed_and_node_churn():
    import grpc

    from k8s_scheduler_tpu.service.client import SchedulerClient
    from k8s_scheduler_tpu.service.server import serve

    server, service, port = serve("127.0.0.1:0")
    client = SchedulerClient(f"127.0.0.1:{port}")
    try:
        # front door disabled: FAILED_PRECONDITION
        with pytest.raises(grpc.RpcError) as ei:
            client.submit(make_pods(1, seed=22, name_prefix="v-"))
        assert ei.value.code() == grpc.StatusCode.FAILED_PRECONDITION

        service.enable_front_door(
            queue_depth=6, retry_after_ms=250.0
        )
        resp = client.node_churn(adds=make_cluster(3))
        assert resp.boot_id == service.boot_id
        resp = client.submit(make_pods(4, seed=23, name_prefix="w-"))
        assert resp.accepted == 4 and resp.queue_depth == 4

        with pytest.raises(grpc.RpcError) as ei:
            client.submit(make_pods(4, seed=24, name_prefix="x-"))
        e = ei.value
        assert e.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
        md = dict(e.trailing_metadata() or ())
        assert md.get("retry-after-ms") == "250"

        # a NAMELESS pod is the wire-reachable invalid case (an empty
        # uid re-derives as namespace/name in ObjectMeta.__post_init__,
        # so it cannot survive the round trip)
        bad = make_pods(1, seed=25, name_prefix="y-")[0]
        bad.metadata.name = ""
        with pytest.raises(grpc.RpcError) as ei:
            client.submit([bad])
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT

        # draining: UNAVAILABLE on both RPCs
        service.admission.close()
        with pytest.raises(grpc.RpcError) as ei:
            client.submit(make_pods(1, seed=26, name_prefix="z-"))
        assert ei.value.code() == grpc.StatusCode.UNAVAILABLE
        with pytest.raises(grpc.RpcError) as ei:
            client.node_churn(deletes=["node-0"])
        assert ei.value.code() == grpc.StatusCode.UNAVAILABLE
    finally:
        client.close()
        server.stop(grace=0)


# ---------------------------------------------------------------------------
# slow tier
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fuzz_arrivals_via_api_bit_equal():
    from k8s_scheduler_tpu.fuzz import generate_trace, run_api_case

    failures = run_api_case(generate_trace(7))
    assert not failures, [str(f) for f in failures[:3]]


@pytest.mark.slow
def test_soak_overload_phase():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import soak_chaos

    result = soak_chaos.run_overload_phase(verbose=False)
    assert result["shed"] > 0
    assert result["max_queue_depth"] <= result["depth_bound"] + 8
    assert not result["lost"] and result["duplicate_binds"] == 0
    assert result["degraded_during_burst"] and result["final_rung"] == 0


@pytest.mark.slow
def test_kill9_failover_mid_loadgen(tmp_path):
    """The acceptance soak's failover half: a REAL CLI front door
    (--submit-addr + --state-dir) under open-loop gRPC load is
    SIGKILLed mid-flood; the restored state must hold every acked pod
    (zero lost), and a standby scheduler binds each exactly once."""
    state_dir = str(tmp_path / "state")
    acked_log = str(tmp_path / "acked.log")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        submit_port = s.getsockname()[1]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    server = subprocess.Popen(
        [sys.executable, "-m", "k8s_scheduler_tpu",
         "--address", "127.0.0.1:0",
         "--submit-addr", f"127.0.0.1:{submit_port}",
         "--http-port", "-1",
         "--state-dir", state_dir,
         "--admission-queue-depth", "4096"],
        cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    loadgen = None
    try:
        deadline = time.monotonic() + 120
        for line in server.stdout:
            if "front door: submissions on port" in line:
                break
            assert time.monotonic() < deadline, "server never came up"
        loadgen = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "scripts", "loadgen.py"),
             "--mode", "grpc", "--addr", f"127.0.0.1:{submit_port}",
             "--rate", "6000", "--duration", "30", "--batch", "4",
             "--nodes", "8", "--acked-log", acked_log],
            cwd=REPO, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        # let the flood run, then kill -9 the scheduler mid-load —
        # after enough acks AND enough wall time that the first cycles
        # completed, so the crash interleaves acked-pending, assumed,
        # and in-flight pods (not just a cold pre-dispatch queue)
        t_load = time.monotonic()
        deadline = t_load + 120
        while time.monotonic() < deadline:
            n_acked = 0
            if os.path.exists(acked_log) and os.path.getsize(acked_log):
                with open(acked_log) as f:
                    n_acked = sum(1 for _ in f)
            if n_acked >= 200 and time.monotonic() - t_load >= 15.0:
                break
            assert loadgen.poll() is None, loadgen.stdout.read()
            time.sleep(0.2)
        server.send_signal(signal.SIGKILL)
        server.wait()
        out, _ = loadgen.communicate(timeout=120)
        report = json.loads(out.strip().splitlines()[-1])
        assert report["stopped_draining"], (
            "loadgen never observed the kill"
        )
    finally:
        server.kill()
        server.wait()
        if loadgen is not None and loadgen.poll() is None:
            loadgen.kill()

    # the client-side ack journal is the oracle: every uid acked as
    # durable must be in the restored state — bound (in the cache) or
    # still pending — and bound at most once
    acked = []
    with open(acked_log) as f:
        for line in f:
            uid, durable = line.split()
            assert durable == "durable=True", line
            acked.append(uid)
    assert len(acked) >= 40
    q, c = _restore_bare(state_dir)
    pending = {p.uid for p in q.all_pending()}
    pending |= {e.pod.uid for e in q._in_flight.values()}
    bound = [p.uid for p, _n in c.existing_pods()]
    assert len(bound) == len(set(bound)), "duplicate binds in cache"
    tracked = pending | set(bound)
    lost = [u for u in acked if u not in tracked]
    assert not lost, (
        f"{len(lost)} acked pods lost across kill -9: {lost[:5]}"
    )

    # standby takeover: a fresh Scheduler on the same dir serves the
    # recovered queue and binds every remaining acked pod exactly once
    st = DurableState(state_dir, snapshot_interval_seconds=0)
    binds: dict[str, int] = {}
    standby = Scheduler(
        config=SchedulerConfiguration(
            pod_initial_backoff_seconds=0.05,
            pod_max_backoff_seconds=0.2,
        ),
        binder=lambda p, n: binds.__setitem__(
            p.uid, binds.get(p.uid, 0) + 1
        ),
        state=st,
    )
    assert standby.ladder.rung == 0
    deadline = time.monotonic() + 180
    while (
        standby.queue.pending_counts()["active"]
        and time.monotonic() < deadline
    ):
        standby.schedule_cycle()
        for pod, node in list(standby.cache.existing_pods()):
            pass  # no informer: assumed pods are fine for this check
    assert all(n == 1 for n in binds.values()), binds
    st.journal.close()

"""Process layer (SURVEY.md §2 C1, §5.3/§5.5): flags, health/metrics
endpoints, and file-lease leader election."""

import json
import multiprocessing
import os
import time
import urllib.request

import pytest

from k8s_scheduler_tpu.cmd import new_scheduler_command
from k8s_scheduler_tpu.cmd.httpserver import start_http_server
from k8s_scheduler_tpu.cmd.leaderelection import FileLease
from k8s_scheduler_tpu.metrics import SchedulerMetrics


def test_flag_surface_matches_upstream_names():
    ap = new_scheduler_command()
    args = ap.parse_args(
        ["--config", "x.yaml", "--leader-elect", "--http-port", "0"]
    )
    assert args.config == "x.yaml"
    assert args.leader_elect
    assert args.http_port == 0


def test_http_endpoints_serve_health_and_metrics():
    m = SchedulerMetrics()
    m.decisions.inc(42)
    server = start_http_server(m, port=0, healthz=lambda: (True, {"x": 1}))
    port = server.server_address[1]
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz") as r:
            body = json.loads(r.read())
            assert body["ok"] and body["x"] == 1
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics") as r:
            text = r.read().decode()
            assert "scheduler_pod_node_decisions_total 42.0" in text
        try:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
            assert False, "404 expected"
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        server.shutdown()


def _get(url):
    with urllib.request.urlopen(url) as r:
        return r.status, dict(r.headers), r.read()


def _request(url, method):
    req = urllib.request.Request(url, method=method)
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def test_http_head_answered_and_mutations_405():
    """Probes commonly use HEAD (the stdlib handler would 501); any
    mutating verb on the read-only surface gets 405 + Allow."""
    m = SchedulerMetrics()
    server = start_http_server(m, port=0, healthz=lambda: (True, {}))
    port = server.server_address[1]
    base = f"http://127.0.0.1:{port}"
    try:
        for path in ("/healthz", "/metrics"):
            gs, gh, gbody = _request(f"{base}{path}", "GET")
            hs, hh, hbody = _request(f"{base}{path}", "HEAD")
            assert (gs, hs) == (200, 200)
            assert hbody == b""  # HEAD: headers only
            # HEAD advertises the same payload size GET serves
            assert hh["Content-Length"] == str(len(gbody))
        hs, _, _ = _request(f"{base}/nope", "HEAD")
        assert hs == 404
        for method in ("POST", "PUT", "DELETE", "PATCH"):
            st, headers, _ = _request(f"{base}/metrics", method)
            assert st == 405, method
            assert headers["Allow"] == "GET, HEAD"
    finally:
        server.shutdown()


def test_debug_endpoints_serve_flightrecorder_trace_and_pods():
    from k8s_scheduler_tpu.core.flight_recorder import FlightRecorder

    fr = FlightRecorder(capacity=16)
    for i in range(4):
        rec = fr.start()
        rec.mark("dispatch_start", rec.t_start + 0.001)
        rec.mark("decision_end", rec.t_start + 0.004)
        rec.phases["encode_ms"] = 1.0
        rec.counts["pods"] = 3 + i
        fr.commit(rec)
    fr.pod_event("uid-1", "pod-1", "Queued")
    fr.pod_event("uid-1", "pod-1", "Bound", cycle=3, node="n1")
    timelines = {
        "uid-1": {"uid": "uid-1", "name": "pod-1", "state": "Bound"}
    }
    server = start_http_server(
        SchedulerMetrics(), port=0, recorder=fr,
        pod_timeline=timelines.get,
    )
    port = server.server_address[1]
    base = f"http://127.0.0.1:{port}"
    try:
        st, _, body = _get(f"{base}/debug/flightrecorder?last=2")
        payload = json.loads(body)
        assert st == 200
        assert [c["seq"] for c in payload["cycles"]] == [2, 3]
        assert payload["derived"]["cycles"] == 4.0
        st, headers, body = _get(f"{base}/debug/trace?last=4")
        assert st == 200
        assert "attachment" in headers["Content-Disposition"]
        trace = json.loads(body)
        assert any(
            e["ph"] == "X" and e["name"].startswith("device cycle")
            for e in trace["traceEvents"]
        )
        st, _, body = _get(f"{base}/debug/pods/uid-1")
        assert st == 200 and json.loads(body)["state"] == "Bound"
        st, _, _ = _request(f"{base}/debug/pods/ghost", "GET")
        assert st == 404
        # malformed ?last falls back instead of erroring
        st, _, _ = _get(f"{base}/debug/flightrecorder?last=banana")
        assert st == 200
    finally:
        server.shutdown()


def test_healthz_staleness_503_when_cycles_stop():
    from k8s_scheduler_tpu.cmd.httpserver import staleness_healthz
    from k8s_scheduler_tpu.core.flight_recorder import FlightRecorder

    t = {"now": 0.0}
    fr = FlightRecorder(capacity=4, now=lambda: t["now"])
    health = staleness_healthz(lambda: {"bootId": "b"}, fr, 5.0)
    server = start_http_server(SchedulerMetrics(), port=0, healthz=health)
    port = server.server_address[1]
    url = f"http://127.0.0.1:{port}/healthz"
    try:
        # no cycle ever completed: fresh process is healthy...
        t["now"] = 1.0
        st, _, body = _request(url, "GET")
        assert st == 200 and json.loads(body)["last_cycle_age_s"] == 1.0
        # ...but ages into 503 if the first cycle never lands (wedged)
        t["now"] = 6.0
        st, _, body = _request(url, "GET")
        assert st == 503
        assert "no cycle completed" in json.loads(body)["reason"]
        # a completed cycle resets the age
        rec = fr.start()
        rec.t_end = t["now"]
        fr.commit(rec)
        st, _, body = _request(url, "GET")
        assert st == 200 and json.loads(body)["cycles"] == 1
        # and stopping again goes stale again
        t["now"] = 20.0
        st, _, _ = _request(url, "GET")
        assert st == 503
        # deadline 0 = never stale (the config default)
        never = staleness_healthz(None, fr, 0.0)
        ok, detail = never()
        assert ok and detail["last_cycle_age_s"] == 14.0
    finally:
        server.shutdown()


def _hold_lease(path, hold_seconds, acquired):
    lease = FileLease(path, identity="other")
    assert lease.try_acquire()
    acquired.set()
    time.sleep(hold_seconds)
    lease.release()


def test_file_lease_single_holder(tmp_path):
    path = str(tmp_path / "lease")
    acquired = multiprocessing.Event()
    proc = multiprocessing.Process(
        target=_hold_lease, args=(path, 1.5, acquired)
    )
    proc.start()
    try:
        assert acquired.wait(10)
        mine = FileLease(path, identity="me")
        # flock is held by the other PROCESS: try_acquire must fail
        assert not mine.try_acquire()
        holder = mine.holder()
        assert holder and holder["holderIdentity"] == "other"
        # blocks until the holder releases, then wins
        assert mine.acquire(timeout=10)
        assert mine.is_leader()
        mine.release()
        assert not mine.is_leader()
    finally:
        proc.join(timeout=10)


def test_lease_intra_process_exclusion_and_holder_keeps_lock(tmp_path):
    # POSIX record locks never conflict within a process and are dropped
    # when ANY fd for the file closes — the FileLease registry must paper
    # over both (a leader reading its own heartbeat must not lose the lease)
    path = str(tmp_path / "lease")
    leader = FileLease(path, identity="leader")
    standby = FileLease(path, identity="standby")
    assert leader.try_acquire()
    try:
        assert not standby.try_acquire()  # same-process exclusion
        # holder() reads must not release the kernel lock
        assert leader.holder()["holderIdentity"] == "leader"
        assert standby.holder()["holderIdentity"] == "leader"
        assert not standby.try_acquire()
        assert leader.is_leader()
    finally:
        leader.release()
    assert standby.try_acquire()
    standby.release()


def test_lease_heartbeat_renews(tmp_path):
    path = str(tmp_path / "lease")
    lease = FileLease(path, identity="hb", renew_seconds=0.05)
    assert lease.try_acquire()
    try:
        first = lease.holder()["renewTime"]
        lease.start_renewing()

        def renewed():
            h = lease.holder()  # None between a renewal's truncate and write
            return h is not None and h["renewTime"] > first

        deadline = time.monotonic() + 10.0
        while not renewed():
            assert time.monotonic() < deadline, "no renewal in 10 s"
            time.sleep(0.01)
    finally:
        lease.release()


def test_lease_describe_and_leader_gauges(tmp_path):
    """scheduler_leader_state / scheduler_leader_lease_age_seconds are
    scrape-time views of the FileLease, and /healthz-style describe()
    surfaces identity + heartbeat age — not just a boolean."""
    path = str(tmp_path / "lease")
    leader = FileLease(path, identity="the-leader")
    standby = FileLease(path, identity="the-standby")
    assert leader.try_acquire()
    try:
        d = leader.describe()
        assert d["leader"] and d["holder"] == "the-leader"
        assert d["age_s"] >= 0.0 and d["path"] == path
        ds = standby.describe()
        assert not ds["leader"] and ds["holder"] == "the-leader"
        # the gauges evaluate the SAME lease at scrape time
        m = SchedulerMetrics()
        m.leader_state.set_function(
            lambda: 1.0 if leader.is_leader() else 0.0
        )
        m.leader_lease_age.set_function(leader.lease_age_seconds)
        text = m.expose().decode()
        assert "scheduler_leader_state 1.0" in text
        assert "scheduler_leader_lease_age_seconds" in text
        ms = SchedulerMetrics()
        ms.leader_state.set_function(
            lambda: 1.0 if standby.is_leader() else 0.0
        )
        assert "scheduler_leader_state 0.0" in ms.expose().decode()
    finally:
        leader.release()
    # no lease file content at all: age reads 0, no crash
    ghost = FileLease(str(tmp_path / "nope"))
    assert ghost.lease_age_seconds() == 0.0


def test_debug_state_endpoint(tmp_path):
    """/debug/state serves the DurableState status payload (journal
    lag/segments, snapshot + restore stats); absent without state."""
    from k8s_scheduler_tpu.internal.cache import SchedulerCache
    from k8s_scheduler_tpu.internal.queue import SchedulingQueue
    from k8s_scheduler_tpu.models import MakePod
    from k8s_scheduler_tpu.state import DurableState

    st = DurableState(str(tmp_path), snapshot_interval_seconds=0)
    q, c = SchedulingQueue(), SchedulerCache()
    st.attach(q, c)
    q.add(MakePod("p").obj())
    st.journal.flush()
    server = start_http_server(SchedulerMetrics(), port=0, state=st)
    port = server.server_address[1]
    try:
        st_, _, body = _get(f"http://127.0.0.1:{port}/debug/state")
        payload = json.loads(body)
        assert st_ == 200
        assert payload["journal"]["appended"] == 1
        assert payload["journal"]["fsync"] is True
        assert payload["last_restore"]["records_replayed"] == 0
    finally:
        server.shutdown()
    # without durable state the route 404s like other absent debug routes
    bare = start_http_server(SchedulerMetrics(), port=0)
    bport = bare.server_address[1]
    try:
        code, _, _ = _request(
            f"http://127.0.0.1:{bport}/debug/state", "GET"
        )
        assert code == 404
    finally:
        bare.shutdown()
    st.journal.close()


def test_pad_presizing_flows_from_yaml_to_encoder():
    """padExisting / padPodsPerNode (PERF.md 'fold-mode rig wedge'
    avoidance) must reach the per-profile encoders, and the encoded
    regime must honor them (E folded into the pow2 bucket, MPN into
    the bucket-of-8)."""
    from k8s_scheduler_tpu.config.types import load_config
    from k8s_scheduler_tpu.core.scheduler import Scheduler
    from k8s_scheduler_tpu.models import MakeNode, MakePod

    cfg = load_config(
        "padExisting: 300\npadPodsPerNode: 25\n"
    )
    assert cfg.pad_existing == 300 and cfg.pad_pods_per_node == 25
    sched = Scheduler(config=cfg)
    enc = sched._encoder
    assert enc.pad_existing == 300 and enc.pad_pods_per_node == 25
    nodes = [MakeNode("a").capacity({"cpu": "8"}).obj()]
    pods = [MakePod("p").req({"cpu": "1"}).obj()]
    ex = [(MakePod("e").req({"cpu": "1"}).obj(), "a")]
    snap = enc.encode(nodes, pods, existing=ex)
    assert snap.exist_valid.shape[0] == 512  # pow2 bucket of 300
    assert snap.node_pods.shape[1] == 32  # bucket-of-8 ABOVE the pad: a
    # depth within the operator's sizing must never outgrow the regime


@pytest.mark.parametrize("key, value", [
    ("multiCycleK", "4"),
    ("multiCycleMaxWaitMs", "5"),
    ("speculativeDispatch", "true"),
    ("incrementalEncode", "true"),
])
def test_config_of_an_older_deployment_still_loads(key, value):
    """A configuration file written for a process that still had the
    K-cycle batch path carries a key this one does not know: it loads,
    as any unknown key does, and the key changes nothing."""
    from k8s_scheduler_tpu.config.types import load_config

    cfg = load_config(f"{key}: {value}\npadExisting: 300\n")
    assert cfg == load_config("padExisting: 300\n")
    assert cfg.pad_existing == 300


# ---- thread-lifecycle regressions (schedlint TR003, ISSUE 12) -----------


def test_stop_http_server_joins_the_serve_thread():
    """The HTTP serve thread must have a shutdown JOIN story, not just
    daemon=True: stop_http_server drains it, closes the socket, and is
    idempotent (the CompileWarmer-leak class, machine-checked by TR003)."""
    import urllib.error
    import urllib.request

    from k8s_scheduler_tpu.cmd.httpserver import stop_http_server
    from k8s_scheduler_tpu.metrics import SchedulerMetrics

    server = start_http_server(SchedulerMetrics(), port=0)
    thread = server._serve_thread
    assert thread is not None and thread.is_alive()
    port = server.server_address[1]
    assert stop_http_server(server) is True
    assert not thread.is_alive()
    assert server._serve_thread is None
    # the listening socket is really gone, not merely unaccepted
    with pytest.raises((urllib.error.URLError, OSError)):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=0.5
        )
    # idempotent: a second stop is a no-op, not a crash
    assert stop_http_server(server) is True


def test_lease_release_joins_the_renewer(tmp_path):
    """FileLease.release must drain the renewer thread (the shutdown
    join mirroring CompileWarmer's drain-exit), so a released lease
    leaves no heartbeat writer behind to resurrect the file."""
    path = str(tmp_path / "lease")
    lease = FileLease(path, identity="joiner", renew_seconds=0.05)
    assert lease.try_acquire()
    lease.start_renewing()
    renewer = lease._renewer
    assert renewer is not None and renewer.is_alive()
    lease.release()
    assert not renewer.is_alive()
    assert lease._renewer is None
    # no post-release heartbeat: the file stops changing once released
    import os
    import time as _t

    before = os.stat(path).st_mtime_ns
    _t.sleep(0.15)
    assert os.stat(path).st_mtime_ns == before


HTTP_LINE = "serving /healthz /metrics on port "


def _start_cli(tmp_path, *extra, until=HTTP_LINE):
    """`python -m k8s_scheduler_tpu` as a child on ephemeral ports;
    returns (proc, lines printed up to the `until` line, the port that
    line ends in: the http port by default)."""
    import subprocess
    import sys

    proc = subprocess.Popen(
        [
            sys.executable, "-m", "k8s_scheduler_tpu",
            "--address", "127.0.0.1:0", "--http-port", "0",
            "--state-dir", str(tmp_path / "state"), *extra,
        ],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    lines = []
    for ln in proc.stdout:
        lines.append(ln.rstrip("\n"))
        if ln.startswith(until):
            return proc, lines, int(ln.rsplit(" ", 1)[1])
    proc.wait()
    raise AssertionError(
        f"CLI exited {proc.returncode} before serving:\n" + "\n".join(lines)
    )


def test_cli_build_line_and_build_info_name_the_device(tmp_path):
    """ISSUE 22: the `build:` line and `scheduler_build_info` carry the
    platform, device kind and device count of the process that holds
    the devices — where `benchmark/run.py` reads the device from (the
    harness never asks JAX) — the `encoder:` line says which
    snapshot-row encoder serves, and SIGTERM exits 0 with the state
    sealed."""
    import shlex
    import signal

    import jax

    proc, lines, port = _start_cli(tmp_path)
    try:
        build = next(ln for ln in lines if ln.startswith("build: "))
        fields = dict(
            kv.split("=", 1) for kv in shlex.split(build[len("build: "):])
        )
        dev = jax.devices()[0]
        assert fields["platform"] == dev.platform == "cpu"
        assert fields["device_kind"] == dev.device_kind
        assert int(fields["device_count"]) == len(jax.devices())
        assert fields["backend"] == "cpu" and fields["jax"] == jax.__version__
        assert any(ln.startswith("encoder: native=") for ln in lines)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics"
        ) as r:
            info = next(
                ln for ln in r.read().decode().splitlines()
                if ln.startswith("scheduler_build_info{")
            )
        for key in ("platform", "device_kind", "device_count"):
            assert f'{key}="{fields[key]}"' in info, info
    finally:
        proc.send_signal(signal.SIGTERM)
        tail = proc.communicate(timeout=60)[0]
    assert proc.returncode == 0, tail
    assert "durable state sealed" in tail


@pytest.mark.parametrize(
    "ready", ["scheduler shim listening on port ", HTTP_LINE],
    ids=["grpc_line", "http_line"],
)
def test_sigterm_at_a_ready_line_still_seals_the_state(tmp_path, ready):
    """ROADMAP D0: the handlers stand before the first line a
    supervisor can take as "ready", so a SIGTERM sent the moment such a
    line appears (the rest of start-up still ahead: the black box, the
    http server, the collector's install) exits 0 with the journal
    sealed, not killed by the default action."""
    import signal

    proc, lines, _port = _start_cli(tmp_path, until=ready)
    try:
        proc.send_signal(signal.SIGTERM)
        tail = proc.communicate(timeout=60)[0]
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode == 0, "\n".join(lines) + tail
    assert "durable state sealed" in tail


def test_build_line_survives_a_device_kind_with_spaces():
    """device_kind is "TPU v5 lite" on the chip: the line's values are
    shell-quoted so it still splits back into k=v fields."""
    import shlex

    from k8s_scheduler_tpu.cmd.main import build_line

    fp = {"device_kind": "TPU v5 lite", "platform": "tpu", "git": "x"}
    line = build_line(fp)
    assert line.startswith("build: ")
    fields = shlex.split(line[len("build: "):])
    assert dict(kv.split("=", 1) for kv in fields) == fp

"""Spans of the agent path (service/server.py `Update` and `Cycle`,
core/scheduler.schedule_cycle, core/pipeline's `sched.dispatch` anchor):
one trace per RPC with the RPC span as root, per phase and never per
pod, joined both ways to the flight records, on the profiler's clock
through one anchor per dispatch."""

from __future__ import annotations

import glob
import os
import re

import jax
import pytest

from k8s_scheduler_tpu.config import SchedulerConfiguration
from k8s_scheduler_tpu.core import pipeline as _pipeline
from k8s_scheduler_tpu.core import spans as _spans
from k8s_scheduler_tpu.core.observe import phase_seconds
from k8s_scheduler_tpu.core.spans import (
    AGENT_LANE_PID,
    AGENT_LANE_TID,
    AGENT_SPAN_NAMES,
    SPAN_NAMES,
    format_traceparent,
    spans_to_chrome_events,
)
from k8s_scheduler_tpu.models import MakeNode, MakePod
from k8s_scheduler_tpu.service import convert
from k8s_scheduler_tpu.service import scheduler_pb2 as pb
from k8s_scheduler_tpu.service.server import SchedulerService
from k8s_scheduler_tpu.state import DurableState

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "k8s_scheduler_tpu")

UPDATE_SPANS = {"rpc.update", "update.convert", "update.apply"}
CYCLE_SPANS = {"rpc.cycle", "cycle.lock_wait", "cycle.pop", "cycle.respond"}
# only in a cycle that refuses a pod
LOSER_SPANS = {"cycle.postfilter", "cycle.losers"}


class Metadata:
    """The part of a grpc.ServicerContext the handlers read."""

    def __init__(self, traceparent: str = "") -> None:
        self.traceparent = traceparent

    def invocation_metadata(self):
        return (("user-agent", "test"), ("traceparent", self.traceparent))

    def set_trailing_metadata(self, metadata) -> None:
        self.trailing = dict(metadata)


def service(state=None) -> SchedulerService:
    return SchedulerService(
        config=SchedulerConfiguration(
            pod_initial_backoff_seconds=0.05, pod_max_backoff_seconds=0.2
        ),
        state=state,
    )


def cluster_request(n_nodes: int = 3, n_pods: int = 5, tag: str = "p"):
    req = pb.UpdateRequest()
    for i in range(n_nodes):
        req.node_adds.append(convert.node_to(
            MakeNode(f"n{i}").capacity({"cpu": "8"}).obj()))
    for i in range(n_pods):
        req.pod_adds.append(pb.PodEvent(pod=convert.pod_to(
            MakePod(f"{tag}{i}").req({"cpu": "1"}).obj())))
    return req


@pytest.fixture()
def armed():
    rec = _spans.arm(rate=1.0)
    yield rec
    _spans.disarm()


def by_name(spans) -> dict:
    out: dict = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def assert_one_trace(spans, root_name: str, caller: str = ""):
    """`spans` are one RPC's: one trace id, the rpc.* span the root
    (child of `caller`), every other span its child and inside it."""
    assert len({s.trace_id for s in spans}) == 1
    (root,) = [s for s in spans if s.name == root_name]
    assert root.parent == caller
    for s in spans:
        if s is root:
            continue
        assert s.parent == root.span_id, s.name
        assert root.t0 <= s.t0 <= s.t1 <= root.t1, s.name
    return root


def test_update_and_cycle_give_exactly_the_table_spans(armed, tmp_path):
    st = DurableState(str(tmp_path), snapshot_interval_seconds=0)
    svc = service(st)
    req = cluster_request(n_nodes=3, n_pods=5)
    req.pod_deletes.append("default/none")
    svc.Update(req, None)
    spans = armed.snapshot()
    assert {s.name for s in spans} == UPDATE_SPANS and len(spans) == 3
    root = assert_one_trace(spans, "rpc.update")
    assert {k: root.attrs[k] for k in (
        "pod_adds", "pod_updates", "pod_deletes", "bind_failures",
        "node_events")} == {
        "pod_adds": 5, "pod_updates": 0, "pod_deletes": 1,
        "bind_failures": 0, "node_events": 3}
    named = by_name(spans)
    assert named["update.convert"][0].attrs["objects"] == 8
    assert named["update.apply"][0].attrs["objects"] == 9
    # the two phases abut: convert ends where apply starts
    assert named["update.convert"][0].t1 == named["update.apply"][0].t0

    before = len(spans)
    resp = svc.Cycle(pb.CycleRequest(), None)
    spans = armed.snapshot()[before:]
    # no compaction ran (interval 0 = journal only): no cycle.snapshot
    assert {s.name for s in spans} == CYCLE_SPANS and len(spans) == 4
    root = assert_one_trace(spans, "rpc.cycle")
    assert len(resp.bindings) == 5
    assert root.attrs["bindings"] == 5
    assert root.attrs["events"] == len(resp.events) > 0
    assert root.attrs["evictions"] == len(resp.evictions) == 0
    named = by_name(spans)
    assert named["cycle.pop"][0].attrs["pods"] == 5
    assert named["cycle.respond"][0].attrs == {
        "bindings": 5, "events": len(resp.events)}
    # the join runs both ways: the RPC names its flight records, and
    # each of them names the RPC's trace
    recs = svc.scheduler.flight.snapshot()
    assert root.attrs["seqs"] == [r.seq for r in recs] and recs
    assert all(root.trace_id in r.trace_ids for r in recs)
    # the phases are disjoint and in order inside the RPC
    lock, pop, resp_span = (named[n][0] for n in (
        "cycle.lock_wait", "cycle.pop", "cycle.respond"))
    assert lock.t1 <= pop.t0 <= pop.t1 <= recs[0].t_start
    assert recs[-1].t_end <= resp_span.t0

    # a cycle with nothing to pop is still one trace, with no record
    before = len(armed.snapshot())
    svc.Cycle(pb.CycleRequest(), None)
    spans = armed.snapshot()[before:]
    assert {s.name for s in spans} == CYCLE_SPANS
    root = assert_one_trace(spans, "rpc.cycle")
    assert root.attrs["seqs"] == [] and root.attrs["bindings"] == 0
    st.seal()


def test_traceparent_in_the_metadata_is_joined(armed):
    svc = service()
    tid, sid = _spans.new_trace_id(), _spans.new_span_id()
    ctx = Metadata(format_traceparent(tid, sid))
    svc.Update(cluster_request(), ctx)
    svc.Cycle(pb.CycleRequest(), ctx)
    spans = armed.snapshot()
    assert {s.trace_id for s in spans} == {tid}
    for root_name, names in (("rpc.update", UPDATE_SPANS),
                             ("rpc.cycle", CYCLE_SPANS)):
        assert_one_trace([s for s in spans if s.name in names],
                         root_name, caller=sid)
    assert all(tid in r.trace_ids
               for r in svc.scheduler.flight.snapshot())
    # a malformed header starts a trace of the RPC's own
    before = len(spans)
    svc.Update(pb.UpdateRequest(), Metadata("00-zz-zz-01"))
    (root,) = [s for s in armed.snapshot()[before:]
               if s.name == "rpc.update"]
    assert root.trace_id != tid and root.parent == ""


def test_cycle_snapshot_appears_only_in_a_cycle_that_compacted(
        armed, tmp_path):
    clock = [0.0]
    st = DurableState(str(tmp_path), snapshot_interval_seconds=15,
                      now=lambda: clock[0])
    svc = service(st)
    svc.Update(cluster_request(n_pods=2, tag="a"), None)
    svc.Cycle(pb.CycleRequest(), None)
    assert "cycle.snapshot" not in {s.name for s in armed.snapshot()}
    clock[0] = 16.0
    svc.Update(cluster_request(n_nodes=0, n_pods=2, tag="b"), None)
    before = len(armed.snapshot())
    svc.Cycle(pb.CycleRequest(), None)
    spans = armed.snapshot()[before:]
    assert {s.name for s in spans} == CYCLE_SPANS | {"cycle.snapshot"}
    root = assert_one_trace(spans, "rpc.cycle")
    named = by_name(spans)
    # after the last record, before the response is built
    recs = [r for r in svc.scheduler.flight.snapshot()
            if r.seq in root.attrs["seqs"]]
    assert recs[-1].t_end <= named["cycle.snapshot"][0].t0
    assert named["cycle.snapshot"][0].t1 <= named["cycle.respond"][0].t0
    # an empty cycle compacts too, when its interval has passed
    clock[0] = 32.0
    before = len(armed.snapshot())
    svc.Cycle(pb.CycleRequest(), None)
    assert {s.name for s in armed.snapshot()[before:]} == (
        CYCLE_SPANS | {"cycle.snapshot"})
    st.seal()


def test_cycle_snapshot_says_what_it_spliced_and_the_records_keep_the_total(
        armed, tmp_path):
    """`cycle.snapshot` carries the pod rows of its file, those of them
    the compaction serialised itself (the rest went in as kept
    fragments) and the file's bytes; every flight record of a scheduler
    with durable state carries the running total of the second as
    `snapshot_rows_encoded` (a record is committed before the
    compaction that follows its cycle)."""
    clock = [0.0]
    st = DurableState(str(tmp_path), snapshot_interval_seconds=15,
                      now=lambda: clock[0])
    svc = service(st)
    in_records, spans = [], []
    for i, (pods, nodes) in enumerate(((4, 3), (2, 0), (0, 0), (3, 0))):
        clock[0] = 16.0 * (i + 1)
        svc.Update(cluster_request(n_nodes=nodes, n_pods=pods,
                                   tag=f"t{i}-"), None)
        before = len(armed.snapshot())
        svc.Cycle(pb.CycleRequest(), None)
        (span,) = [s for s in armed.snapshot()[before:]
                   if s.name == "cycle.snapshot"]
        spans.append(span.attrs)
        if pods:  # a cycle that pops nothing commits no record
            in_records.append(svc.scheduler.flight.last_record().counts[
                "snapshot_rows_encoded"])
        assert span.attrs == {k: st.last_snapshot[k] for k in (
            "rows", "rows_encoded", "bytes")}
    # each compaction met the queue's in-flight entries of its cycle
    # and nothing older (the cache's rows came serialised); the cycle
    # that popped nothing dropped the in-flight set
    assert [a["rows_encoded"] for a in spans] == [4, 2, 0, 3]
    assert [a["rows"] for a in spans] == [8, 8, 6, 12]
    assert in_records == [0, 4, 6]
    assert st.rows_encoded == 9
    st.seal()
    # no durable state, no count
    plain = service()
    plain.Update(cluster_request(n_pods=1), None)
    plain.Cycle(pb.CycleRequest(), None)
    assert "snapshot_rows_encoded" not in (
        plain.scheduler.flight.last_record().counts)


def test_the_loser_loop_is_two_spans_a_mark_and_three_running_counts(
        armed):
    """A cycle that refuses a pod stamps the wait for the preemption
    program and the loser loop, children of `rpc.cycle`, in that order
    between the last bind and the response; a cycle that refuses none
    stamps neither. Every flight record carries the running totals the
    benchmark's `flight_count` reads, and `/metrics` the first two."""
    svc = service()
    req = cluster_request(n_nodes=3, n_pods=4)
    for i in range(3):  # 9 CPU on 8-CPU nodes: no node, no victim helps
        req.pod_adds.append(pb.PodEvent(pod=convert.pod_to(
            MakePod(f"big{i}").req({"cpu": "9"}).obj())))
    svc.Update(req, None)
    resp = svc.Cycle(pb.CycleRequest(), None)
    assert len(resp.bindings) == 4
    spans = armed.snapshot()[3:]
    assert {s.name for s in spans} == CYCLE_SPANS | LOSER_SPANS
    root = assert_one_trace(spans, "rpc.cycle")
    named = by_name(spans)
    (post,), (losers,) = named["cycle.postfilter"], named["cycle.losers"]
    assert post.attrs == {"losers": 3, "nominated": 0, "victims": 0}
    assert losers.attrs == {"losers": 3, "diagnosed": 3}
    assert post.t1 == losers.t0 <= losers.t1 <= named["cycle.respond"][0].t0
    (rec,) = [r for r in svc.scheduler.flight.snapshot()
              if r.seq in root.attrs["seqs"]]
    marks = rec.marks
    assert (marks["winners_end"] <= marks["postfilter_end"]
            <= marks["losers_end"] <= rec.t_end)
    assert phase_seconds(rec)["losers"] == (
        marks["losers_end"] - marks["postfilter_end"])
    first = {k: rec.counts[k] for k in (
        "commit_rounds", "rounds_parked", "refusals")}
    assert first["rounds_parked"] == first["refusals"] == 3
    assert first["commit_rounds"] >= 1
    text = svc.scheduler.metrics.expose()
    assert b"scheduler_rounds_parked_pods_total 3.0" in text
    assert (b"scheduler_commit_rounds_total %.1f"
            % first["commit_rounds"]) in text
    # a cycle with no loser: neither span, no mark, the totals stand
    svc.Update(cluster_request(n_nodes=0, n_pods=2, tag="q"), None)
    before = len(armed.snapshot())
    assert len(svc.Cycle(pb.CycleRequest(), None).bindings) == 2
    spans = armed.snapshot()[before:]
    assert {s.name for s in spans} == CYCLE_SPANS
    rec2 = svc.scheduler.flight.snapshot()[-1]
    assert "losers_end" not in rec2.marks
    assert "losers" not in phase_seconds(rec2)
    assert rec2.counts["refusals"] == rec2.counts["rounds_parked"] == 3
    assert rec2.counts["commit_rounds"] > first["commit_rounds"]


ZONE_KEY = "topology.kubernetes.io/zone"


def test_the_rounds_two_new_counts_on_the_record_metrics_and_rpc_cycle(
        armed):
    """One spread group over two zones, `maxSkew` 1, one zone with room
    for a single pod: the level-fill places three (1 + 2), the spread
    guard revokes claims on the way, and no loop ends at its cap. The
    flight record keeps both as running totals beside `commit_rounds`,
    `/metrics` counts them, and `rpc.cycle` carries the cycle's own."""
    svc = service()
    req = pb.UpdateRequest()
    for i, cpu in enumerate(("1", "8")):
        req.node_adds.append(convert.node_to(
            MakeNode(f"n{i}").capacity({"cpu": cpu})
            .labels({ZONE_KEY: f"z{i}"}).obj()))
    for i in range(6):
        req.pod_adds.append(pb.PodEvent(pod=convert.pod_to(
            MakePod(f"s{i}").req({"cpu": "1"}).labels({"app": "blue"})
            .spread(1, ZONE_KEY, {"app": "blue"}).obj())))
    svc.Update(req, None)
    assert len(svc.Cycle(pb.CycleRequest(), None).bindings) == 3
    (root,) = [s for s in armed.snapshot() if s.name == "rpc.cycle"]
    (rec,) = [r for r in svc.scheduler.flight.snapshot()
              if r.seq in root.attrs["seqs"]]
    assert rec.counts["round_cap_hits"] == root.attrs["round_cap_hits"] == 0
    revoked = rec.counts["spread_revoked"]
    assert revoked == root.attrs["spread_revoked"] > 0
    text = svc.scheduler.metrics.expose()
    assert b"scheduler_round_cap_hits_total 0.0" in text
    assert (b"scheduler_spread_revoked_claims_total %.1f" % revoked) in text
    # a second cycle: the record's totals stand or grow, the span's
    # counts are that cycle's own
    svc.Update(cluster_request(n_nodes=0, n_pods=1, tag="q"), None)
    svc.Cycle(pb.CycleRequest(), None)
    root2 = [s for s in armed.snapshot() if s.name == "rpc.cycle"][-1]
    rec2 = svc.scheduler.flight.snapshot()[-1]
    assert rec2.counts["spread_revoked"] == (
        revoked + root2.attrs["spread_revoked"])


def test_unarmed_no_span_and_no_annotation_object(monkeypatch):
    made = []
    real = jax.profiler.TraceAnnotation

    def counting(*a, **kw):
        made.append(a)
        return real(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    assert not _spans.ARMED
    # a ring left over from an earlier arm() must stay untouched
    ring = _spans.RECORDER
    count = ring.count if ring is not None else 0
    svc = service()
    svc.Update(cluster_request(), None)
    resp = svc.Cycle(pb.CycleRequest(), None)
    assert len(resp.bindings) == 5
    assert made == []
    assert _spans.RECORDER is ring
    assert (ring.count if ring is not None else 0) == count
    recs = svc.scheduler.flight.snapshot()
    assert recs and all(r.trace_ids == () for r in recs)
    # the seqs are kept either way: they cost one list append a record
    assert svc.scheduler.last_cycle_seqs == [r.seq for r in recs]
    # armed, the same cycle makes exactly one: the dispatch's anchor
    _spans.arm(rate=1.0)
    try:
        svc.Update(cluster_request(n_nodes=0, tag="q"), None)
        svc.Cycle(pb.CycleRequest(), None)
    finally:
        _spans.disarm()
    assert [a[0] for a in made] == ["sched.dispatch"]


def test_armed_with_no_context_the_per_pod_sites_do_no_lookup(
        armed, monkeypatch):
    calls = []
    real = _spans.ctx_for

    def counting(uid):
        calls.append(uid)
        return real(uid)

    monkeypatch.setattr(_spans, "ctx_for", counting)
    svc = service()
    svc.Update(cluster_request(), None)
    resp = svc.Cycle(pb.CycleRequest(), None)
    assert len(resp.bindings) == 5
    assert calls == []  # bind.confirm, dispatch, ...: all skipped
    assert {s.name for s in armed.snapshot()} <= AGENT_SPAN_NAMES
    # with one pod bound to a trace the sites run again, for every pod
    # of the cycle (the flag is per cycle, the lookup per pod)
    pod = MakePod("traced").req({"cpu": "1"}).obj()
    assert _spans.register(pod.uid) is not None
    req = cluster_request(n_nodes=0, n_pods=2, tag="r")
    req.pod_adds.append(pb.PodEvent(pod=convert.pod_to(pod)))
    svc.Update(req, None)
    svc.Cycle(pb.CycleRequest(), None)
    assert pod.uid in calls and len(set(calls)) == 3
    mine = {s.name for s in armed.snapshot()
            if s.attrs.get("uid") == pod.uid}
    assert {"dispatch", "decision.row", "apply.fold",
            "bind.confirm"} <= mine


def test_dispatch_anchors_agree_on_the_profiler_clock(armed, tmp_path):
    """A jax.profiler trace around three cycles: every dispatch left a
    `sched.dispatch` event that carries its record's seq and the
    recorder clock, and the clock offsets they give agree within 1 ms."""
    from jax.profiler import ProfileData

    svc = service()
    svc.Update(cluster_request(n_pods=2, tag="warm"), None)
    svc.Cycle(pb.CycleRequest(), None)  # compiles, outside the trace
    n_warm = len(svc.scheduler.flight.snapshot())
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for k in range(3):
            svc.Update(cluster_request(n_nodes=0, n_pods=2, tag=f"c{k}-"),
                       None)
            assert len(svc.Cycle(pb.CycleRequest(), None).bindings) == 2
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    anchors = [
        (dict(e.stats), e.start_ns)
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name == "sched.dispatch"
    ]
    fr = svc.scheduler.flight
    recs = fr.snapshot()[n_warm:]
    assert sorted(a["seq"] for a, _ in anchors) == [r.seq for r in recs]
    assert len(anchors) == 3
    offsets_us = [start_ns / 1e3 - a["t_us"] for a, start_ns in anchors]
    assert max(offsets_us) - min(offsets_us) < 1000.0
    # t_us is the record's own dispatch mark, on the recorder's epoch
    for a, _ in anchors:
        (r,) = [r for r in recs if r.seq == a["seq"]]
        mark_us = (r.marks["dispatch_start"] - fr.epoch) * 1e6
        assert 0.0 <= a["t_us"] - mark_us < 50_000.0


def test_dispatch_anchor_is_inert_without_an_anchor():
    import contextlib

    assert isinstance(_pipeline._dispatch_anchor(None, lambda: 0.0),
                      contextlib.nullcontext)
    with _pipeline._dispatch_anchor((7, 1.0), lambda: 3.5) as a:
        assert isinstance(a, jax.profiler.TraceAnnotation)


def test_agent_rpcs_render_on_one_lane(armed):
    svc = service()
    for k in range(3):
        svc.Update(cluster_request(n_nodes=3 if k == 0 else 0,
                                   n_pods=2, tag=f"l{k}-"), None)
        svc.Cycle(pb.CycleRequest(), None)
    pod_ctx = _spans.TraceContext(_spans.new_trace_id(),
                                  _spans.new_span_id())
    armed.record("bind.confirm", pod_ctx, 1.0, 2.0, uid="default/x")
    spans = armed.snapshot()
    events = spans_to_chrome_events(spans, epoch=armed.epoch)
    slices = [e for e in events if e["ph"] == "X"]
    agent = [e for e in slices if e["name"] in AGENT_SPAN_NAMES]
    assert len(agent) == len(spans) - 1
    assert {(e["pid"], e["tid"]) for e in agent} == {
        (AGENT_LANE_PID, AGENT_LANE_TID)}
    # six RPCs, six traces, ONE named lane: not a track per trace
    lanes = [e for e in events if e["ph"] == "M"
             and e["name"] == "thread_name"
             and e["pid"] == AGENT_LANE_PID]
    assert [e["args"]["name"] for e in lanes] == [
        "agent RPCs (Update/Cycle)"]
    # names and args are as for every other span
    (cyc,) = [e for e in agent if e["name"] == "rpc.cycle"][-1:]
    (span,) = [s for s in spans if s.span_id == cyc["args"]["span_id"]]
    assert cyc["args"] == {
        "trace_id": span.trace_id, "span_id": span.span_id,
        "parent": span.parent, **span.attrs}
    # the pod's span keeps its own per-trace track
    (pod_ev,) = [e for e in slices if e["name"] == "bind.confirm"]
    assert pod_ev["pid"] == _spans.TRACE_TRACK_PID


def test_span_inventory_has_twenty_five_names():
    assert len(SPAN_NAMES) == len(set(SPAN_NAMES)) == 25
    # the agent's own six (service/client.py) have a lane to themselves
    assert set(_spans.CLIENT_SPAN_NAMES) == {
        n for n in SPAN_NAMES if n.startswith("client.")}
    assert len(_spans.CLIENT_SPAN_NAMES) == 6
    # the collector's passes (core/collector.py) share the agent's lane
    assert AGENT_SPAN_NAMES == UPDATE_SPANS | CYCLE_SPANS | LOSER_SPANS | {
        "cycle.snapshot", "gc.pass"}


@pytest.mark.parametrize("name", sorted(
    UPDATE_SPANS | CYCLE_SPANS | LOSER_SPANS | {"cycle.snapshot"}))
def test_each_agent_span_is_stamped_at_one_site(name):
    sites = []
    for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            text = f.read()
        sites += [path for _ in re.finditer(
            r"record_span\(\s*\"" + re.escape(name) + "\"", text)]
    assert len(sites) == 1, sites


def test_update_counts_confirmations_applied_and_returned(armed):
    """`rpc.update` says how many binds were confirmed by reference and
    how many went back to the agent; `update.apply` counts them among
    its objects; a request without any reads 0 and 0."""
    svc = service()
    svc.Update(cluster_request(n_nodes=3, n_pods=4), None)
    (first,) = [s for s in armed.snapshot() if s.name == "rpc.update"]
    assert (first.attrs["bind_confirms"],
            first.attrs["confirm_fallbacks"]) == (0, 0)
    bindings = svc.Cycle(pb.CycleRequest(), None).bindings
    assert len(bindings) == 4
    before = len(armed.snapshot())
    req = pb.UpdateRequest(bind_confirms=[
        pb.BindConfirm(pod_uid=b.pod_uid, node_name=b.node_name)
        for b in bindings
    ])
    # one of them again (bound by now) and one the server never saw
    req.bind_confirms.add(pod_uid=bindings[0].pod_uid,
                          node_name=bindings[0].node_name)
    req.bind_confirms.add(pod_uid="default/none", node_name="n0")
    resp = svc.Update(req, None)
    assert resp.bind_confirms_applied == 4
    assert list(resp.unconfirmed) == [bindings[0].pod_uid, "default/none"]
    named = by_name(armed.snapshot()[before:])
    root = named["rpc.update"][0]
    assert {k: root.attrs[k] for k in (
        "bind_confirms", "confirm_fallbacks", "pod_updates")} == {
        "bind_confirms": 4, "confirm_fallbacks": 2, "pod_updates": 0}
    assert named["update.convert"][0].attrs["objects"] == 0
    assert named["update.apply"][0].attrs["objects"] == 6
    assert svc.scheduler.cache.counts() == {
        "nodes": 3, "bound": 4, "assumed": 0}
    # the pod's timeline shows the confirmation as the full path does
    last = svc.scheduler.flight.pods.get(bindings[1].pod_uid)["events"][-1]
    assert (last["kind"], last["node"]) == (
        "BoundObserved", bindings[1].node_name)


PROTO = os.path.join(PACKAGE, "service", "scheduler.proto")


def test_generated_module_is_the_proto_compiled(tmp_path):
    """`scheduler_pb2.py` holds exactly what protoc makes of
    `scheduler.proto` today: every message, field and number."""
    import shutil
    import subprocess

    from google.protobuf import descriptor_pb2

    protoc = shutil.which("protoc")
    if protoc is None:
        pytest.skip("no protoc in this image")
    out = tmp_path / "set.pb"
    subprocess.run(
        [protoc, f"--proto_path={os.path.dirname(PROTO)}",
         f"--descriptor_set_out={out}", PROTO], check=True)
    (compiled,) = descriptor_pb2.FileDescriptorSet.FromString(
        out.read_bytes()).file
    loaded = descriptor_pb2.FileDescriptorProto()
    pb.DESCRIPTOR.CopyToProto(loaded)

    def strip(messages):
        # a descriptor set spells out each field's JSON name; the
        # generated module leaves it to the runtime
        for m in messages:
            for f in m.field:
                f.ClearField("json_name")
            strip(m.nested_type)

    strip(compiled.message_type)
    strip(loaded.message_type)
    assert compiled == loaded


@pytest.mark.parametrize("message,field,number,kind", [
    ("BindConfirm", "pod_uid", 1, "string"),
    ("BindConfirm", "node_name", 2, "string"),
    ("UpdateRequest", "bind_confirms", 17, "repeated BindConfirm"),
    ("UpdateResponse", "bind_confirms_applied", 2, "int32"),
    ("UpdateResponse", "unconfirmed", 3, "repeated string"),
])
def test_proto_text_and_generated_module_agree(message, field, number, kind):
    with open(PROTO) as f:
        body = re.search(
            r"^message " + message + r" \{(.*?)^\}", f.read(), re.M | re.S
        ).group(1)
    assert re.search(
        rf"^\s*{kind} {field} = {number};", body, re.M), (message, field)
    fd = pb.DESCRIPTOR.message_types_by_name[message].fields_by_name[field]
    assert fd.number == number
    assert fd.is_repeated == kind.startswith("repeated ")
    base = kind.removeprefix("repeated ")
    if base == "BindConfirm":
        assert fd.message_type.name == "BindConfirm"
    else:
        assert fd.type == {"string": fd.TYPE_STRING,
                           "int32": fd.TYPE_INT32}[base]


@pytest.mark.parametrize("pct,n_nodes,want", [
    (0, 160, {"sample_k": 100, "sample_narrowed_pods": 5}),
    (0, 60, {}),  # under upstream's 100-node floor: nothing is traced
    (100, 160, {}),
])
def test_sample_counts_on_the_record_and_on_rpc_cycle(
        armed, pct, n_nodes, want):
    """A cycle program that samples nodes (percentageOfNodesToScore
    under 100 on a cluster of 100 nodes or more) hands back the k in
    force and the pods it cost a candidate: both are on the cycle's
    flight record and on `rpc.cycle`. A program that does not sample
    stamps neither."""
    svc = SchedulerService(config=SchedulerConfiguration(
        percentage_of_nodes_to_score=pct))
    svc.Update(cluster_request(n_nodes=n_nodes, n_pods=5), None)
    assert len(svc.Cycle(pb.CycleRequest(), None).bindings) == 5
    (root,) = [s for s in armed.snapshot() if s.name == "rpc.cycle"]
    keys = ("sample_k", "sample_narrowed_pods")
    assert {k: root.attrs[k] for k in keys if k in root.attrs} == want
    (rec,) = [r for r in svc.scheduler.flight.snapshot()
              if r.seq in root.attrs["seqs"]]
    assert {k: rec.counts[k] for k in keys if k in rec.counts} == want

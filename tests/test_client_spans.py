"""The agent's side of `Update` and `Cycle` (service/client.py): six
`client.*` spans stamped in the agent's process while the server's ring
is armed, shipped with the next RPC as one bounded metadata entry, and
stored in the server's ring on the recorder's clock (core/spans.ingest)
with `rpc.update` / `rpc.cycle` as children of `client.update` /
`client.cycle`; read by six data-only per-layer metrics
(benchmark/layers/client_*.json).

Served in-process as tests/test_agent_flush.py does: a real gRPC server
on an ephemeral port. Client and server then share one `perf_counter`,
so the offset the server derives has to come out as nothing."""

from __future__ import annotations

import json
import os
import sys
import time
from concurrent import futures

import grpc
import pytest

from k8s_scheduler_tpu.core import spans as _spans
from k8s_scheduler_tpu.core.spans import (
    CLIENT_LANE_TID,
    CLIENT_SPAN_ATTRS,
    CLIENT_SPAN_NAMES,
    CLIENT_SPANS_KEY,
    PLACE_WINDOW_S,
    SHIP_MAX_BYTES,
    SHIP_MAX_SPANS,
    Outbox,
    spans_to_chrome_events,
)
from k8s_scheduler_tpu.models import MakeNode, MakePod
from k8s_scheduler_tpu.service import client as client_mod
from k8s_scheduler_tpu.service.client import SchedulerAgent, SchedulerClient
from k8s_scheduler_tpu.service.server import SchedulerService, add_to_server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.lib import program_spans  # noqa: E402

FLOOR = 2048
BLOCK_KIDS = ("client.build", "client.send", "client.ack_wait")


class Served:
    """A served scheduler whose `Update` handler can be made slow, and
    which keeps every call's invocation metadata."""

    def __init__(self) -> None:
        self.service = SchedulerService()
        self.delay = 0.0
        self.metadata: list[dict] = []
        for name in ("Update", "Cycle"):
            setattr(self.service, name, self._seen(getattr(self.service, name)))
        self.server = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
        add_to_server(self.service, self.server)
        port = self.server.add_insecure_port("127.0.0.1:0")
        self.server.start()
        self.client = SchedulerClient(f"127.0.0.1:{port}")
        self.agent = SchedulerAgent(self.client, lambda *a: None)

    def _seen(self, inner):
        def handler(request, context):
            self.metadata.append(dict(context.invocation_metadata()))
            time.sleep(self.delay)
            return inner(request, context)
        return handler

    def close(self) -> None:
        self.client.close()
        self.server.stop(grace=None)


@pytest.fixture()
def served():
    s = Served()
    yield s
    s.close()


@pytest.fixture()
def armed():
    rec = _spans.arm(rate=1.0)
    yield rec
    _spans.disarm()


@pytest.fixture()
def small_floor(monkeypatch):
    monkeypatch.setattr(client_mod, "FLUSH_FLOOR_BYTES", FLOOR)


def nodes(n=8):
    return [MakeNode(f"n{i}").capacity({"cpu": "64", "pods": "110"}).obj()
            for i in range(n)]


def pods(n, tag="p"):
    return [MakePod(f"{tag}{i}").req({"cpu": "100m"}).obj() for i in range(n)]


def settle(s, seconds: float = 20.0) -> float:
    """The client's first call hears that the ring is armed, and the
    server places nothing until it has bounded the client's clock from
    both sides: a few small calls, as a served agent's warm-up makes
    (more where other tests' threads still hold the interpreter: here
    both ends of every call share one lock, and the tests want the
    bounds closer than the server asks). Returns the clock once the
    calls lie well behind."""
    s.agent.upsert_node(nodes(1)[0])
    assert s.client.tracing
    limit = time.monotonic() + seconds
    while time.monotonic() < limit:
        s.client.cycle()
        s.agent.delete_pod("default/none")
        peer = _spans._peers.get(s.client.outbox.client_id)
        if peer and peer.up - peer.lo < PLACE_WINDOW_S / 2:
            time.sleep(0.01)
            return _spans.now()
    raise AssertionError("the client's clock was never bounded to 5 ms")


def since(recorder, t: float) -> dict:
    """The ring's spans that began after `t`, by name."""
    out: dict = {}
    for sp in recorder.snapshot():
        if sp.t0 >= t:
            out.setdefault(sp.name, []).append(sp)
    return out


def iteration(s, wave, known):
    """The benchmark's loop: a block of upserts, `Cycle`, a block of
    confirmations by reference."""
    with s.agent.batched():
        for p in wave:
            s.agent.upsert_pod(p)
    resp = s.client.cycle()
    with s.agent.batched():
        for b in resp.bindings:
            s.agent.upsert_pod(known[b.pod_uid], bound_node=b.node_name)
    return resp


def test_the_inventory_has_six_client_spans_on_a_lane_of_their_own(armed):
    assert CLIENT_SPAN_NAMES == (
        "client.batch", "client.build", "client.send", "client.ack_wait",
        "client.update", "client.cycle")
    assert set(CLIENT_SPAN_NAMES) == set(CLIENT_SPAN_ATTRS)
    assert not set(CLIENT_SPAN_NAMES) & _spans.AGENT_SPAN_NAMES
    ctx = _spans.TraceContext(_spans.new_trace_id(), _spans.new_span_id())
    armed.record("rpc.update", ctx, 2.0, 3.0)
    armed.ingest(_spans.Span(ctx.trace_id, "ab" * 8, "", "client.build",
                             1.0, 2.5, attrs={"objects": 7}))
    events = spans_to_chrome_events(armed.snapshot(), epoch=0.0)
    lanes = {e["tid"]: e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert lanes == {
        _spans.AGENT_LANE_TID: "agent RPCs (Update/Cycle)",
        CLIENT_LANE_TID: "agent process (client.*)"}
    (build,) = [e for e in events if e["name"] == "client.build"]
    assert (build["pid"], build["tid"]) == (
        _spans.AGENT_LANE_PID, CLIENT_LANE_TID)
    assert build["args"] == {"trace_id": ctx.trace_id, "span_id": "ab" * 8,
                             "parent": "", "objects": 7}


def test_each_rpc_lies_inside_its_client_span_and_names_it_as_parent(
        served, armed, small_floor):
    s = served
    mark = settle(s)
    for n in nodes():
        s.agent.upsert_node(n)
    wave = pods(120)
    known = {p.uid: p for p in wave}
    assert len(iteration(s, wave, known).bindings) == 120
    s.client.cycle()  # carries the confirmations' spans
    got = since(armed, mark)
    by_id = {sp.span_id: sp for group in got.values() for sp in group}
    joined = 0
    for rpc, caller in (("rpc.update", "client.update"),
                        ("rpc.cycle", "client.cycle")):
        for sp in got[rpc]:
            parent = by_id.get(sp.parent)
            if parent is None:  # the last Cycle's own span is not shipped
                assert rpc == "rpc.cycle" and sp is got[rpc][-1]
                continue
            joined += 1
            assert parent.name == caller
            assert parent.trace_id == sp.trace_id
            # exactly, not to the millisecond ISSUE 40 allows: both
            # bounds on the client's clock hold for this very call
            assert parent.t0 <= sp.t0 and sp.t1 <= parent.t1
    assert joined == len(got["rpc.update"]) + 1 and joined > 6
    # a Cycle is a trace of its own, and says what came back
    (cyc,) = [sp for sp in got["client.cycle"] if sp.attrs["bindings"]]
    assert cyc.parent == "" and cyc.attrs["bindings"] == 120
    # an Update of a block is the block's child, and counts what it sent
    batches = {sp.span_id: sp for sp in got["client.batch"]}
    for up in got["client.update"]:
        if up.parent:
            assert up.trace_id == batches[up.parent].trace_id
    sent = [up for up in got["client.update"] if up.parent]
    assert sum(up.attrs["objects"] for up in sent) == 240
    assert sum(up.attrs["bytes"] for up in sent) == sum(
        b.attrs["bytes"] for b in batches.values())
    assert len(sent) == sum(b.attrs["requests"] for b in batches.values())


def test_a_block_is_its_builds_sends_and_waits(served, armed, small_floor):
    """`client.batch` less `client.build`, `client.send` and
    `client.ack_wait` is under 1% of it, or under a millisecond: over
    a block of many chunks, against a server that takes its time."""
    s = served
    mark = settle(s)
    s.delay = 0.01
    with s.agent.batched():
        for p in pods(200):
            s.agent.upsert_pod(p)
            time.sleep(0.0002)
    s.delay = 0.0
    s.client.cycle()
    got = since(armed, mark)
    (block,) = got["client.batch"]
    assert block.attrs["requests"] >= 3
    assert block.attrs["bytes"] > FLOOR
    kids = [sp for n in BLOCK_KIDS for sp in got[n]
            if sp.parent == block.span_id]
    for sp in kids:
        assert block.t0 <= sp.t0 <= sp.t1 <= block.t1, sp.name
    # one build a request, and one more where the last object of the
    # block was the one that sent a chunk on
    assert len(got["client.build"]) - block.attrs["requests"] in (0, 1)
    assert len(got["client.send"]) == block.attrs["requests"]
    assert sum(sp.attrs["objects"] for sp in got["client.build"]) == 200
    whole = block.t1 - block.t0
    rest = whole - sum(sp.t1 - sp.t0 for sp in kids)
    assert 0.0 <= rest < max(0.01 * whole, 1e-3), (rest, whole)


def test_ack_wait_is_stamped_only_where_the_wait_blocked(served, armed):
    s = served
    mark = settle(s)
    s.delay = 0.05
    call = s.client.update_future(client_mod.pb.UpdateRequest(
        pod_deletes=["default/a"]))
    assert not call.done()
    call.result()  # blocks for the server's 50 ms
    s.delay = 0.0
    call = s.client.update_future(client_mod.pb.UpdateRequest(
        pod_deletes=["default/b"]))
    while not call.done():
        time.sleep(0.001)
    call.result()  # in hand already: no wait, no span
    s.client.cycle()
    got = since(armed, mark)
    (wait,) = got["client.ack_wait"]
    slow, fast = got["client.update"]
    assert wait.parent == slow.span_id and slow.parent == ""
    # the wait began inside its request, and blocked for the server's
    # 50 ms: both starts are the agent's thread's. The two ENDS are two
    # threads' (the request's is stamped by gRPC's done-callback, the
    # wait's by the agent's thread once it wakes with the response), so
    # either may trail the other by a thread switch (2.5 ms seen in a
    # loaded tier-1 run: a wait of 56.6 ms in a request of 54.1): they
    # are held together, not in order
    assert slow.t0 <= wait.t0 and 0.04 < wait.t1 - wait.t0
    assert abs(wait.t1 - slow.t1) < 0.1, (wait.t1, slow.t1)
    # outside a block the request is the root and its send hangs on it
    assert [sp.parent for sp in got["client.send"]] == [
        slow.span_id, fast.span_id]
    # the done-callback took the time: the request does not stretch
    # over the polling that followed it
    assert fast.t1 - fast.t0 < 0.04


def test_an_unarmed_server_gets_no_metadata_and_no_clock_is_read(
        served, monkeypatch, small_floor):
    reads = []
    real = _spans.now

    def counting():
        reads.append(1)
        return real()

    monkeypatch.setattr(client_mod._spans, "now", counting)
    assert not _spans.ARMED
    s = served
    for n in nodes():
        s.agent.upsert_node(n)
    wave = pods(60)
    assert len(iteration(s, wave, {p.uid: p for p in wave}).bindings) == 60
    assert not s.client.tracing and s.client.block is None
    assert len(s.metadata) > 10
    for md in s.metadata:
        assert "traceparent" not in md and CLIENT_SPANS_KEY not in md
    assert reads == []
    assert s.client.outbox.shipment(0.0) == _spans._SHIP_HEAD.pack(
        s.client.outbox.client_id, 0.0, 0)
    # armed, the first call is still bare: the client hears from its
    # response that the ring is armed, and stamps from the next call on
    _spans.arm(rate=1.0)
    try:
        at = len(s.metadata)
        s.agent.delete_pod("default/none")
        s.agent.delete_pod("default/none")
        first, second = s.metadata[at:]
        assert CLIENT_SPANS_KEY not in first and "traceparent" not in first
        assert s.client.tracing and reads
        assert len(second[CLIENT_SPANS_KEY]) <= SHIP_MAX_BYTES
        assert _spans.parse_traceparent(second["traceparent"])
    finally:
        _spans.disarm()
    # ... and hears of a ring disarmed the same way
    s.agent.delete_pod("default/none")
    assert not s.client.tracing
    at = len(s.metadata)
    s.agent.delete_pod("default/none")
    assert CLIENT_SPANS_KEY not in s.metadata[at]


def test_the_outbox_holds_one_call_s_worth_and_drops_the_oldest(armed):
    box = Outbox()
    tid = _spans.new_trace_id()
    for i in range(SHIP_MAX_SPANS + 5):
        box.add("client.send", tid, f"{i:016x}", "", float(i), i + 0.5, i)
    assert box.dropped == 5
    blob = box.shipment(1000.0)
    assert len(blob) <= SHIP_MAX_BYTES < len(blob) + _spans._SHIP_SPAN.size
    assert box.dropped == 0 and len(box.shipment(0.0)) == _spans._SHIP_HEAD.size
    # the server's side: two calls bound the clock (this client's runs
    # 10 s behind the recorder's), then the spans are stored, the
    # newest ones, and the first says how many went missing
    assert _spans.ingest(box.shipment(999.9999), "aa" * 8, 1010.0, 1010.1) == 0
    box.add("client.update", tid, "aa" * 8, "", 999.9998, 1000.1001)
    assert _spans.ingest(box.shipment(1001.0), "bb" * 8, 1011.0002, 1011.2) == 1
    assert _spans.ingest(blob, "", 1012.0, 1012.1) == SHIP_MAX_SPANS
    stored = armed.snapshot()[-SHIP_MAX_SPANS:]
    assert [sp.span_id for sp in stored] == [
        f"{i:016x}" for i in range(5, SHIP_MAX_SPANS + 5)]
    assert stored[0].attrs == {"bytes": 5, "dropped": 5}
    assert stored[1].attrs == {"bytes": 6}
    assert abs(stored[0].t0 - 15.0) < 1e-3


def test_spans_that_cannot_be_placed_are_counted_not_guessed(armed):
    """A link whose best round trip is 30 ms bounds the offset to 15 ms
    either way, wider than PLACE_WINDOW_S: nothing is stored, whatever
    is shipped. Once a call gets through fast enough, the next spans
    are, and the first of them carries the count. A malformed entry
    stores nothing."""
    box = Outbox()
    tid = _spans.new_trace_id()

    def call(k: int, way: float) -> int:
        """Call k leaves the client at k (its clock), is handled from
        k + 100 + way to k + 100.5 + way (the recorder's) and is back
        at the client `way` later."""
        sid = f"{k:016x}"
        blob = box.shipment(float(k))
        stored = _spans.ingest(blob, sid, k + 100 + way, k + 100.5 + way)
        box.add("client.update", tid, sid, "", float(k), k + 0.5 + 2 * way)
        return stored

    assert 0.03 > PLACE_WINDOW_S
    assert [call(k, 0.015) for k in range(4)] == [0, 0, 0, 0]
    assert call(4, 0.0004) == 0  # closes the upper bound only
    assert armed.count == 0
    assert call(5, 0.0004) == 1  # ... and its span, shipped now, the lower
    (sp,) = armed.snapshot()
    assert sp.span_id == f"{4:016x}" and sp.attrs == {
        "bytes": 0, "objects": 0, "unplaced": 4}
    assert abs(sp.t0 - 104.0) <= 1e-3
    for junk in (b"", b"x" * 7, box.shipment(7.0) + b"x"):
        assert _spans.ingest(junk, "", 1.0, 2.0) == 0
    assert armed.count == 1


def export_of(recorder) -> dict:
    """`/debug/traces`' span events as `program_spans.collect` groups
    them."""
    spans: dict = {}
    for ev in spans_to_chrome_events(recorder.snapshot(),
                                     epoch=recorder.epoch):
        if ev["ph"] == "X" and ev["args"].get("span_id"):
            spans.setdefault(ev["name"], []).append(ev)
    return spans


def test_a_row_holds_its_first_block_and_cycle_and_the_confirmations_before(
        served, armed, small_floor):
    """`program_spans.cycle_rows` over the export: a `client.*` span has
    no `rpc.*` ancestor, so it goes to the iteration in which it began.
    Iteration i's first block and `client.cycle` are in row i, its
    confirmations (they begin after its `rpc.cycle` has ended) in row
    i + 1, with the `rpc.update`s they called."""
    s = served
    settle(s)
    for n in nodes():
        s.agent.upsert_node(n)
    s.client.cycle()
    time.sleep(0.01)
    t_first = _spans.now()
    sizes = (90, 30, 60)
    waves = [pods(n, f"w{k}-") for k, n in enumerate(sizes)]
    known = {p.uid: p for w in waves for p in w}
    for wave in waves:
        assert len(iteration(s, wave, known).bindings) == len(wave)
    s.client.cycle()
    rows = program_spans.cycle_rows(
        export_of(armed), {}, (t_first - armed.epoch) * 1e6, 1e18)
    assert len(rows) == 4  # the three iterations and the last Cycle
    spans = since(armed, t_first)
    blocks = spans["client.batch"]
    assert len(blocks) == 6
    ms = [(sp.t1 - sp.t0) * 1e3 for sp in blocks]
    want = [ms[0], ms[1] + ms[2], ms[3] + ms[4], ms[5]]
    for row, batch_ms in zip(rows, want):
        assert row["client.batch"] == pytest.approx(batch_ms, abs=1e-3)
    cycles = spans["client.cycle"]
    for row, cyc in zip(rows, cycles):  # the last one is never shipped
        assert row["client.cycle"] == pytest.approx(
            (cyc.t1 - cyc.t0) * 1e3, abs=1e-3)
        assert row["client.cycle"] >= row["rpc.cycle"]
    assert rows[3]["client.cycle"] == 0.0 and len(cycles) == 3
    # the requests as both sides count them, row by row
    for row in rows:
        assert row["client.update"] >= row["rpc.update"] > 0.0


LAYERS = ("client_batch_ms", "client_build_ms", "client_send_ms",
          "client_ack_wait_ms", "client_update_ms", "client_cycle_ms")


@pytest.mark.parametrize("name", LAYERS)
def test_a_client_layer_file_reads_its_span_or_nothing(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    with open(os.path.join(REPO, "benchmark", "layers", name + ".json")) as f:
        spec = json.load(f)
    # the five cells the benchmark had when the entry was accepted, in
    # order, and any cell a later PR appended: a cell whose own entries
    # leave the stem out (`sp5000-preempt.sat`) is not in the list
    every = [w["name"] for w in bench["workloads"]]
    cells = entry["workloads"]
    assert cells[:5] == every[:5] and set(cells) <= set(every)
    assert entry == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "client and servicer (service/)",
        "moves": "pods_bound_per_s", "workloads": cells}
    assert (spec["name"], spec["unit"], spec["workloads"], spec["layer"],
            spec["source_kind"]) == (name, "ms", cells, entry["layer"],
                                     "program_span")
    (span,) = spec["select"]
    assert span == "client." + name[len("client_"):-len("_ms")]
    assert span in CLIENT_SPAN_NAMES
    assert spec["reduce"] == ("mean" if span == "client.ack_wait"
                              else "median")
    rows = [{span: 10.0, "rpc.cycle": 5.0}, {span: 0.0, "rpc.cycle": 5.0},
            {span: 50.0, "rpc.cycle": 5.0}]
    value = program_spans.read(spec, {"program": {"cycles": rows}})
    assert value == (20.0 if spec["reduce"] == "mean" else 10.0)
    # a program that stamps no such span (this PR's parent): None, not 0
    bare = [{"rpc.cycle": 5.0, "rpc.update": 3.0}] * 3
    assert program_spans.read(spec, {"program": {"cycles": bare}}) is None
    assert program_spans.read(spec, {"program": None}) is None

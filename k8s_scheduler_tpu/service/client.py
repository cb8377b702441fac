"""Client agent for the gRPC shim.

`SchedulerClient` is the raw stub (hand-written; no grpc_python_plugin in
the image). `SchedulerAgent` is the cluster-side logic the reference keeps
in-process: it mirrors the informer stream to the shim, carries bindings
back, and — because the shim is stateless like upstream's scheduler
(SURVEY.md §5.3) — recovers from a shim restart by re-listing everything it
knows. A binding the agent fails to apply is reported as a bind_failure so
the shim forgets the assumption and backs the pod off.

The agent's side of `Update` and `Cycle` is traced (core/spans), while
the server it talks to has its span ring armed and only then: every
armed `Update` and `Cycle` response carries the server's `traceparent`
in its trailing metadata, and `SchedulerClient.tracing` follows the
last one heard. Against an unarmed server every stamp site below is one
attribute load and a falsy branch, no clock is read and no metadata is
sent. Armed, six spans are stamped on this process's `perf_counter`, per
block and per request, never per pod:

- `client.batch`: the outermost `batched()` block, entry to exit
  (`requests`, `bytes`); a trace of its own, and the parent of
- `client.build`: a chunk opened (block entry, or the flush before it
  returned) to its send beginning: the agent converting and its
  caller's loop (`objects`);
- `client.send`: `update_future()` called to returned: the request
  serialised and handed to gRPC (`bytes`);
- `client.ack_wait`: the wait for the `Update` in flight, where it
  blocked: the agent standing still because the server is the slower
  side;
- `client.update`: one `Update` request, send begun to the response
  landed (a done-callback takes the time, so it does not stretch while
  the agent builds the next chunk; `bytes`, `objects`); child of the
  block, or the root of a trace of its own (its `client.send` and
  `client.ack_wait` then its children); the server's `rpc.update` is
  its child, by the `traceparent` the request carries;
- `client.cycle`: `SchedulerClient.cycle()` called to the decoded
  response returned (`bindings`, `events`); a trace of its own, the
  server's `rpc.cycle` its child.

A completed span waits in the client's `Outbox` and goes with the next
`Update` or `Cycle` as one binary metadata entry
(`core/spans.CLIENT_SPANS_KEY`, at most `SHIP_MAX_BYTES`: what does not
fit is dropped oldest first, and the count goes along), with this
process's clock at the hand-off; the server stores them in its ring on
its own clock (`core/spans.ingest`), so `/debug/traces` holds both
sides of every RPC. What is lost: the spans of a client's first call
(it has not heard yet that the ring is armed), those the server could
not place (counted there), and whatever completes after the process's
last RPC, which is never shipped.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Iterator

import grpc

from ..core import spans as _spans
from ..models.api import Node, Pod, PodGroup
from . import convert
from . import scheduler_pb2 as pb
from .server import SERVICE_NAME


class _Block:
    """An open `client.batch`: the ids its children name, when it and
    its open chunk began, and what its requests add up to."""

    __slots__ = ("trace_id", "span_id", "t0", "opened", "requests", "bytes")

    def __init__(self, t0: float) -> None:
        self.trace_id, self.span_id = _spans.new_trace_id(), _spans.new_span_id()
        self.t0 = self.opened = t0
        self.requests = self.bytes = 0


class UpdateCall:
    """An `Update` in flight: gRPC's future, and what the client does
    at the call's end, once, whichever of `result()` and `exception()`
    is asked first: it hears from the response whether the server's ring
    is armed and, where the call was traced, stamps the part of the wait
    that blocked (`client.ack_wait`) and the request (`client.update`)."""

    __slots__ = ("_client", "_future", "_span")

    def __init__(self, client: "SchedulerClient", future: grpc.Future,
                 span: tuple | None) -> None:
        self._client, self._future, self._span = client, future, span

    def done(self) -> bool:
        return self._future.done()

    def result(self) -> pb.UpdateResponse:
        self._settle()
        return self._future.result()

    def exception(self):
        self._settle()
        return self._future.exception()

    def _settle(self) -> None:
        client, future, span = self._client, self._future, self._span
        if client is None:
            return
        self._client = None  # schedlint: disable=TR001 -- a call is waited for by the one thread that made it (the agent's); the write only marks it settled
        if span is not None:
            trace_id, span_id, block_id, t0, size, objects, landed = span
            if not future.done():
                t_wait = _spans.now()
                future.exception()
                client.outbox.add(
                    "client.ack_wait", trace_id, _spans.new_span_id(),
                    block_id or span_id, t_wait, _spans.now())
        if future.exception() is None:
            client._heard(future.trailing_metadata())
        if span is not None:
            # the callback runs on gRPC's thread and may trail the wait
            client.outbox.add(
                "client.update", trace_id, span_id, block_id, t0,
                landed[0] if landed else _spans.now(), size, objects)


def _objects(request: pb.UpdateRequest) -> int:
    """The objects a request carries: every field of it is a list."""
    return sum(len(value) for _, value in request.ListFields())


class SchedulerClient:
    """Thin typed stub over a grpc channel."""

    def __init__(self, target: str, channel: grpc.Channel | None = None) -> None:
        self.channel = channel or grpc.insecure_channel(target)
        # effective W3C traceparent from the last submit's trailing
        # metadata ("" until a traced submit acks)
        self.last_traceparent = ""
        # whether the server's span ring is armed, by its last Update or
        # Cycle response: the client.* spans are stamped only while it is
        self.tracing = False
        # completed client.* spans, until the next Update or Cycle
        self.outbox = _spans.Outbox()
        # the open batched() block of the agent on this client, traced
        self.block: _Block | None = None
        mk = self.channel.unary_unary
        self._update = mk(
            f"/{SERVICE_NAME}/Update",
            # update_future() serialises: the size is a span's attr
            request_serializer=None,
            response_deserializer=pb.UpdateResponse.FromString,
        )
        self._cycle = mk(
            f"/{SERVICE_NAME}/Cycle",
            request_serializer=pb.CycleRequest.SerializeToString,
            response_deserializer=pb.CycleResponse.FromString,
        )
        self._health = mk(
            f"/{SERVICE_NAME}/Health",
            request_serializer=pb.HealthRequest.SerializeToString,
            response_deserializer=pb.HealthResponse.FromString,
        )
        self._metrics = mk(
            f"/{SERVICE_NAME}/Metrics",
            request_serializer=pb.MetricsRequest.SerializeToString,
            response_deserializer=pb.MetricsResponse.FromString,
        )
        self._inspect = mk(
            f"/{SERVICE_NAME}/Inspect",
            request_serializer=pb.InspectRequest.SerializeToString,
            response_deserializer=pb.InspectResponse.FromString,
        )
        self._submit = mk(
            f"/{SERVICE_NAME}/Submit",
            request_serializer=pb.SubmitRequest.SerializeToString,
            response_deserializer=pb.SubmitResponse.FromString,
        )
        self._node_churn = mk(
            f"/{SERVICE_NAME}/NodeChurn",
            request_serializer=pb.NodeChurnRequest.SerializeToString,
            response_deserializer=pb.NodeChurnResponse.FromString,
        )

    def update(self, request: pb.UpdateRequest, timeout: float = 10.0):
        """Traced, a future and a wait for it, so that one rule stamps
        every `Update`: the hand-off is `client.send`, the wait
        `client.ack_wait`. Untraced, the blocking call it always was (a
        future costs gRPC a thread, ~0.4 ms)."""
        if self.tracing:
            return self.update_future(request, timeout=timeout).result()
        resp, call = self._update.with_call(
            request.SerializeToString(), timeout=timeout)
        self._heard(call.trailing_metadata())
        return resp

    def update_future(
        self, request: pb.UpdateRequest, timeout: float = 10.0
    ) -> UpdateCall:
        """`update` without the wait: the request is serialised and
        handed to gRPC before this returns, and `.result()` gives the
        response or raises what `update` would have raised."""
        if not self.tracing:
            return UpdateCall(self, self._update.future(
                request.SerializeToString(), timeout=timeout), None)
        t0 = _spans.now()
        block = self.block
        trace_id = block.trace_id if block else _spans.new_trace_id()
        span_id = _spans.new_span_id()
        data = request.SerializeToString()
        future = self._update.future(
            data, timeout=timeout, metadata=self._calling(trace_id, span_id))
        landed: list[float] = []
        future.add_done_callback(lambda _: landed.append(_spans.now()))
        block_id = ""
        if block:
            block_id = block.span_id
            block.requests += 1
            block.bytes += len(data)
        self.outbox.add(
            "client.send", trace_id, _spans.new_span_id(),
            block_id or span_id, t0, _spans.now(), len(data))
        return UpdateCall(self, future, (
            trace_id, span_id, block_id, t0, len(data), _objects(request),
            landed))

    def cycle(self, timeout: float = 120.0) -> pb.CycleResponse:
        tracing, metadata = self.tracing, None
        if tracing:
            t0 = _spans.now()
            trace_id, span_id = _spans.new_trace_id(), _spans.new_span_id()
            metadata = self._calling(trace_id, span_id)
        resp, call = self._cycle.with_call(
            pb.CycleRequest(), timeout=timeout, metadata=metadata)
        self._heard(call.trailing_metadata())
        if tracing:
            self.outbox.add(
                "client.cycle", trace_id, span_id, "", t0, _spans.now(),
                len(resp.bindings), len(resp.events))
        return resp

    # ---- the agent's side of the trace (module docstring) ----------------

    def _calling(self, trace_id: str, span_id: str) -> tuple:
        """A traced call's metadata: the `client.*` span the server's
        `rpc.*` is the child of, and the spans completed since the last
        call with this clock's reading at the hand-off."""
        return (
            ("traceparent", _spans.format_traceparent(trace_id, span_id)),
            (_spans.CLIENT_SPANS_KEY, self.outbox.shipment(_spans.now())),
        )

    def _heard(self, trailing) -> None:
        """A response's trailing metadata says whether the ring is
        armed (service/server._agents_side)."""
        self.tracing = any(  # schedlint: disable=TR001 -- a flag, not state: a client's calls come from its agent's one thread, and a write that raced another would cost one call's spans
            key == "traceparent" for key, _ in trailing or ())

    def block_begins(self) -> None:
        if self.tracing:
            self.block = _Block(_spans.now())

    def chunk_built(self, chunk: pb.UpdateRequest) -> None:
        """The open chunk is about to be sent (or the block ends)."""
        block = self.block
        if block is not None:
            self.outbox.add(
                "client.build", block.trace_id, _spans.new_span_id(),
                block.span_id, block.opened, _spans.now(), _objects(chunk))

    def chunk_opened(self) -> None:
        block = self.block
        if block is not None:
            block.opened = _spans.now()

    def block_ends(self) -> None:
        """Its last chunk is acknowledged and the response handled."""
        block, self.block = self.block, None
        if block is not None:
            self.outbox.add(
                "client.batch", block.trace_id, block.span_id, "", block.t0,
                _spans.now(), block.requests, block.bytes)

    def health(self, timeout: float = 5.0) -> pb.HealthResponse:
        return self._health(pb.HealthRequest(), timeout=timeout)

    def metrics_text(self, timeout: float = 10.0) -> bytes:
        return self._metrics(pb.MetricsRequest(), timeout=timeout).prometheus_text

    def inspect(
        self,
        kind: str = "flightrecorder",
        last: int = 0,
        pod_uid: str = "",
        timeout: float = 10.0,
    ) -> dict:
        """Pull flight-recorder data (cycle records / Perfetto trace /
        per-pod timeline) decoded from the JSON payload; raises
        RuntimeError when the server reports an inspection error."""
        import json

        resp = self._inspect(
            pb.InspectRequest(kind=kind, last=last, pod_uid=pod_uid),
            timeout=timeout,
        )
        if not resp.ok:
            raise RuntimeError(f"Inspect({kind!r}): {resp.error}")
        return json.loads(resp.json.decode())

    def submit(
        self, pods, timeout: float = 30.0, traceparent: str = "",
    ) -> pb.SubmitResponse:
        """Submit pending pods through the admission front door.
        `pods` are models.api.Pod objects. Raises grpc.RpcError with
        RESOURCE_EXHAUSTED on shed (retry-after hint in the trailing
        metadata key "retry-after-ms"), INVALID_ARGUMENT on malformed
        pods, UNAVAILABLE while the server drains.

        `traceparent` (W3C) joins the submission's trace spans to the
        caller's trace; either way the server's effective traceparent
        (the caller's, or a head-sampled root it minted) comes back in
        the trailing metadata and lands in `self.last_traceparent`
        ("" when tracing is unarmed or the pod was not sampled)."""
        request = pb.SubmitRequest(
            pods=[convert.pod_to(p) for p in pods]
        )
        metadata = (
            (("traceparent", traceparent),) if traceparent else None
        )
        resp, call = self._submit.with_call(
            request, timeout=timeout, metadata=metadata
        )
        self.last_traceparent = ""
        for key, value in call.trailing_metadata() or ():
            if key == "traceparent":
                self.last_traceparent = value
                break
        return resp

    def node_churn(
        self, adds=(), updates=(), deletes=(), timeout: float = 30.0
    ) -> pb.NodeChurnResponse:
        """Node churn through the front door (journaled before ack;
        never shed)."""
        return self._node_churn(
            pb.NodeChurnRequest(
                adds=[convert.node_to(n) for n in adds],
                updates=[convert.node_to(n) for n in updates],
                deletes=list(deletes),
            ),
            timeout=timeout,
        )

    def close(self) -> None:
        self.channel.close()


# bind_applier(pod_uid, pod_name, namespace, node_name) -> None; raise = failed
BindApplier = Callable[[str, str, str, str], None]

# an open batched() request is sent on once it holds this much,
# whatever is in flight (the one in flight is waited for first): the
# server keeps gRPC's default 4 MiB receive limit (service/server.py
# sets none), and a 10k-pod re-list or bind confirmation is larger
MAX_UPDATE_BYTES = 3 * 1024 * 1024
# ... and, while no Update of this agent is in flight, already once it
# holds this much: the server converts and applies one chunk while the
# agent converts the next. A request's fixed cost is about a millisecond
# on each side of the wire (and three spans when the ring is armed); a
# whole pod is ~240 bytes and ~30 us to convert on each side, so 128 KiB
# is ~500 pods and ~15 ms of work on each side against that
# millisecond. What is in flight is ONE chunk, so a server slower than
# the agent gets few large chunks and a faster one many of this size; a
# batch that never reaches it is one request
FLUSH_FLOOR_BYTES = 128 * 1024


class SchedulerAgent:
    """Mirrors cluster objects into the shim and applies its decisions.

    Keeps a local store of every live object so a full re-list can be
    replayed after the shim restarts (same recovery the reference gets from
    client-go informers re-listing into a fresh scheduler process)."""

    def __init__(self, client: SchedulerClient, bind_applier: BindApplier,
                 evict_applier: Callable[[str, str], None] | None = None,
                 event_applier: Callable[["pb.Event"], None] | None = None,
                 cycle_timeout: float = 120.0) -> None:
        self.client = client
        # a regime's first Cycle compiles its programs inside the RPC
        # (minutes for a 10k x 5k cluster on a cold cache): callers that
        # serve such a cluster raise this
        self.cycle_timeout = cycle_timeout
        self.bind_applier = bind_applier
        self.evict_applier = evict_applier or (lambda uid, node: None)
        # posts each drained scheduler event as a Kubernetes Event
        self.event_applier = event_applier or (lambda ev: None)
        # informer-side mirror of the cluster view, NOT WAL-tracked
        # state (the server's cache._nodes is the durable copy)
        self._node_mirror: dict[str, Node] = {}
        self._pods: dict[str, tuple[Pod, str]] = {}  # uid -> (pod, bound_node)
        self._groups: dict[str, PodGroup] = {}
        self._pvcs: dict[str, object] = {}
        self._pvs: dict[str, object] = {}
        self._classes: dict[str, object] = {}
        self._pdbs: dict[str, object] = {}
        self._pending_failures: list[str] = []
        self._boot_id: str | None = None  # shim incarnation last fed state
        self._batch: pb.UpdateRequest | None = None  # open batched() request
        self._batch_bytes = 0
        # the chunk of the open batch the server has not acknowledged
        # yet, and its call: never more than this one (batched())
        self._unacked: tuple[pb.UpdateRequest, UpdateCall] | None = None

    # ---- informer-side entry points -------------------------------------

    def upsert_node(self, node: Node) -> None:
        known = node.name in self._node_mirror
        self._node_mirror[node.name] = node
        self._send(
            pb.UpdateRequest(
                **{
                    ("node_updates" if known else "node_adds"): [
                        convert.node_to(node)
                    ]
                }
            )
        )

    def delete_node(self, name: str) -> None:
        self._node_mirror.pop(name, None)
        self._send(pb.UpdateRequest(node_deletes=[name]))

    def upsert_pod(self, pod: Pod, bound_node: str = "") -> None:
        """Send `pod` as pending, or as bound to `bound_node`.

        A bound pod that is THE OBJECT this agent last sent as pending
        is the confirmation of a binding the shim made itself, and goes
        as a `BindConfirm(uid, node)`: the shim holds that pod assumed
        and moves it to bound, nothing is converted or shipped twice.
        Anything else (an unknown uid, another object, an entry already
        bound) goes as the whole pod. The rule reads what was stored,
        so a pod mutated in place and never upserted again is outside
        it, as it is outside `relist()`, which would send the mutated
        object: hand the agent a new object for a changed pod."""
        known = self._pods.get(pod.uid)
        self._pods[pod.uid] = (pod, bound_node)
        if bound_node and known and known[0] is pod and not known[1]:
            self._send(pb.UpdateRequest(bind_confirms=[
                pb.BindConfirm(pod_uid=pod.uid, node_name=bound_node)
            ]))
            return
        ev = pb.PodEvent(pod=convert.pod_to(pod), bound_node=bound_node)
        self._send(
            pb.UpdateRequest(
                **{("pod_updates" if known else "pod_adds"): [ev]}
            )
        )

    def delete_pod(self, uid: str) -> None:
        self._pods.pop(uid, None)
        self._send(pb.UpdateRequest(pod_deletes=[uid]))

    def add_pod_group(self, group: PodGroup) -> None:
        self._groups[group.name] = group
        self._send(
            pb.UpdateRequest(
                pod_groups=[pb.PodGroup(name=group.name,
                                        min_member=group.min_member)]
            )
        )

    # ---- volume objects (VolumeBinding inputs) ---------------------------

    def upsert_pvc(self, pvc) -> None:
        self._pvcs[pvc.key] = pvc
        self._send(pb.UpdateRequest(pvc_upserts=[convert.pvc_to(pvc)]))

    def delete_pvc(self, key: str) -> None:
        self._pvcs.pop(key, None)
        self._send(pb.UpdateRequest(pvc_deletes=[key]))

    def upsert_pv(self, pv) -> None:
        self._pvs[pv.name] = pv
        self._send(pb.UpdateRequest(pv_upserts=[convert.pv_to(pv)]))

    def delete_pv(self, name: str) -> None:
        self._pvs.pop(name, None)
        self._send(pb.UpdateRequest(pv_deletes=[name]))

    def upsert_storage_class(self, sc) -> None:
        self._classes[sc.name] = sc
        self._send(
            pb.UpdateRequest(storage_class_upserts=[convert.storage_class_to(sc)])
        )

    def delete_storage_class(self, name: str) -> None:
        self._classes.pop(name, None)
        self._send(pb.UpdateRequest(storage_class_deletes=[name]))

    def upsert_pdb(self, pdb) -> None:
        self._pdbs[pdb.key] = pdb
        self._send(pb.UpdateRequest(pdb_upserts=[convert.pdb_to(pdb)]))

    def delete_pdb(self, key: str) -> None:
        self._pdbs.pop(key, None)
        self._send(pb.UpdateRequest(pdb_deletes=[key]))

    # ---- the cycle -------------------------------------------------------

    def run_cycle(self) -> pb.CycleResponse:
        """Flush failures, run one cycle, apply bindings/evictions."""
        if self._pending_failures:
            self._send(pb.UpdateRequest(bind_failures=self._pending_failures))
            self._pending_failures = []
        def cycle():
            return self.client.cycle(timeout=self.cycle_timeout)

        resp = self._with_recovery(cycle)
        if self._boot_changed(resp.boot_id):
            # the shim restarted since we fed it state and the cycle ran
            # against an empty cache — replay everything and re-run
            self.relist()
            resp = self._with_recovery(cycle)
        with self.batched():  # the confirmations, in requests that fit
            for b in resp.bindings:
                try:
                    self.bind_applier(
                        b.pod_uid, b.pod_name, b.pod_namespace, b.node_name
                    )
                except Exception:
                    self._pending_failures.append(b.pod_uid)
                    continue
                pod, _ = self._pods.get(b.pod_uid, (None, ""))
                if pod is not None:
                    self.upsert_pod(pod, bound_node=b.node_name)
        for ev in resp.evictions:
            self.evict_applier(ev.pod_uid, ev.node_name)
        for ev in resp.events:
            self.event_applier(ev)
        return resp

    # ---- transport + recovery -------------------------------------------

    def _boot_changed(self, boot_id: str) -> bool:
        """Track the shim incarnation; True when a restart was detected
        (a restarted shim at the same address answers RPCs normally but
        holds empty state — the boot_id is the only tell)."""
        if self._boot_id == boot_id:
            return False
        first = self._boot_id is None
        self._boot_id = boot_id
        return not first

    @contextlib.contextmanager
    def batched(self) -> Iterator[None]:
        """Coalesce the upserts/deletes inside the block into few Update
        RPCs, flushed while the block still builds them: the open batch
        is sent on as a chunk whenever it holds FLUSH_FLOOR_BYTES and
        the previous chunk has been acknowledged, and at
        MAX_UPDATE_BYTES (the server's message limit) whatever is in
        flight, after a wait for it. So the server converts and applies
        one chunk while the agent converts the next, the chunk size
        follows how fast the server acknowledges, and a batch under the
        floor is one request. Never two Updates of this agent in flight
        (the server would keep no order between them): chunks arrive in
        the order of the calls. The block's exit sends what is left and
        returns once the last chunk is acknowledged, so a Cycle asked
        after it sees every object. Every chunk's response is handled as
        an Update's outside a block is (`_acknowledged`), no later than
        the exit. Nesting reuses the open batch."""
        if self._batch is not None:
            yield
            return
        self._batch, self._batch_bytes = pb.UpdateRequest(), 0
        self.client.block_begins()
        try:
            yield
            self.client.chunk_built(self._batch)
            self._collect()
            if self._batch_bytes:
                self._send_now(self._batch)
            self.client.block_ends()
        finally:
            self._batch = None
            self.client.block = None  # where the block raised: no span
            if self._unacked is not None:
                # the block raised with a chunk on its way: wait for it,
                # or the next Update would be a second one in flight
                _, call = self._unacked
                self._unacked = None
                call.exception()

    def _send(self, request: pb.UpdateRequest) -> None:
        if self._batch is None:
            self._send_now(request)
            return
        self._batch.MergeFrom(request)
        self._batch_bytes += request.ByteSize()
        if self._batch_bytes >= MAX_UPDATE_BYTES or (
            self._batch_bytes >= FLUSH_FLOOR_BYTES
            and (self._unacked is None or self._unacked[1].done())
        ):
            self._flush()

    def _flush(self) -> None:
        """Send the open batch on as a chunk, once the chunk before it
        has been acknowledged and its response handled."""
        self.client.chunk_built(self._batch)
        self._collect()
        chunk = self._batch
        self._batch, self._batch_bytes = pb.UpdateRequest(), 0
        self._unacked = (chunk, self.client.update_future(chunk))
        self.client.chunk_opened()

    def _collect(self) -> None:
        """Wait for the chunk in flight, if any, and handle its response."""
        if self._unacked is None:
            return
        request, call = self._unacked
        self._unacked = None
        resp = self._with_recovery(
            call.result, retry=lambda: self.client.update(request)
        )
        self._acknowledged(request, resp)

    def _send_now(self, request: pb.UpdateRequest) -> None:
        resp = self._with_recovery(lambda: self.client.update(request))
        self._acknowledged(request, resp)

    def _acknowledged(
        self, request: pb.UpdateRequest, resp: pb.UpdateResponse
    ) -> None:
        """What an Update's response asks of the agent; nothing of this
        agent is in flight when it runs."""
        if self._boot_changed(resp.boot_id):
            # state before this delta is gone: replay everything (the delta
            # itself was applied to the fresh shim, and relist re-sends the
            # full store including it, which is idempotent)
            self.relist()
            return  # every pod went with its bound node: nothing to resend
        # confirmations the shim held no assumption for (expired, already
        # bound, unknown) come back in `unconfirmed`; one that does not
        # know the field answers 0 and nothing, and then it is all of
        # them. They go again at once as whole pods, in requests that fit
        sent = request.bind_confirms
        again = set(resp.unconfirmed)
        if resp.bind_confirms_applied + len(again) != len(sent):
            again = {c.pod_uid for c in sent}
        full, size = pb.UpdateRequest(), 0
        for c in sent:
            if c.pod_uid not in again or c.pod_uid not in self._pods:
                continue
            ev = full.pod_updates.add(
                pod=convert.pod_to(self._pods[c.pod_uid][0]),
                bound_node=c.node_name,
            )
            size += ev.ByteSize()
            if size >= MAX_UPDATE_BYTES:
                self._send_now(full)
                full, size = pb.UpdateRequest(), 0
        if size:
            self._send_now(full)

    def _with_recovery(self, call, retry=None):
        """`call()`, and once more (`retry()`, where another call has to
        be made for it: a future gives its first answer again) after a
        relist if the shim was unavailable."""
        try:
            return call()
        except grpc.RpcError as e:
            if e.code() not in (
                grpc.StatusCode.UNAVAILABLE,
                grpc.StatusCode.DEADLINE_EXCEEDED,
            ):
                raise
            # shim restarted (or hiccuped): replay the full state, retry once
            self.relist()
            return (retry or call)()

    def relist(self) -> None:
        """Replay everything we know into a (possibly fresh) shim."""
        req = pb.UpdateRequest()
        for node in self._node_mirror.values():
            req.node_adds.append(convert.node_to(node))
        for g in self._groups.values():
            req.pod_groups.append(
                pb.PodGroup(name=g.name, min_member=g.min_member)
            )
        for pod, bound in self._pods.values():
            req.pod_adds.append(
                pb.PodEvent(pod=convert.pod_to(pod), bound_node=bound)
            )
        for pvc in self._pvcs.values():
            req.pvc_upserts.append(convert.pvc_to(pvc))
        for pv in self._pvs.values():
            req.pv_upserts.append(convert.pv_to(pv))
        for sc in self._classes.values():
            req.storage_class_upserts.append(convert.storage_class_to(sc))
        for pdb in self._pdbs.values():
            req.pdb_upserts.append(convert.pdb_to(pdb))
        resp = self.client.update(req)
        self._boot_id = resp.boot_id

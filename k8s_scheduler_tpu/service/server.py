"""gRPC shim server: snapshot deltas in, bindings out.

The cluster-integration boundary from SURVEY.md §7 step 7 / §5.8: where the
reference talks HTTPS watch/Binding-POST to the API server itself, the TPU
scheduler runs behind this service and a thin agent (client.py) owns the
cluster store conversation. Per the north star, one `Cycle` RPC returns
pod->node bindings for the WHOLE pending set.

Bind dispatch is optimistic (upstream assume-then-bind-async): a binding
returned from `Cycle` is assumed in the cache; the agent confirms the ones
it applied by reference (`Update(bind_confirms=[(uid, node)])`: the assumed
pod becomes bound; a bound `pod_update` carrying the whole pod does the
same) and reports failed Binding POSTs in `Update(bind_failures=[...])`,
which forgets the assumption and requeues with backoff. If the
confirmation never arrives the assumed-pod TTL expires and the pod is
requeued (no double-bind either way — fault tests in
tests/test_service.py).

The driver underneath runs the split-phase serving pipeline
(core/pipeline.py): inside `Cycle`, the response's `bindings` are
collected from the winner bind loop, which blocks only on the slimmed
decision fetch — preemption nominations, evictions, and FailedScheduling
events ride the deferred programs that resolve while winners bind, so a
mostly-schedulable cycle's bindings are never gated on diagnostics.
`forced_sync` (config `forcedSync` or the serve() argument) restores
strictly sequential execution for tests and latency measurement.

The grpc servicer/stub glue is hand-written (the image has protoc for
messages but no grpc_python_plugin); method handler wiring mirrors what
grpc_tools would generate.
"""

from __future__ import annotations

import contextlib
import threading
import uuid
from concurrent import futures

import grpc

from ..config import SchedulerConfiguration
from ..core import spans as _spans
from ..core.scheduler import Scheduler
from ..metrics import SchedulerMetrics
from ..models.api import PodGroup
from . import convert
from . import scheduler_pb2 as pb

SERVICE_NAME = "k8sschedtpu.Scheduler"


class SchedulerService:
    """Implements the four RPCs against one host-side Scheduler."""

    def __init__(self, config: SchedulerConfiguration | None = None,
                 scheduler: Scheduler | None = None,
                 profile_every: int = 0,
                 metrics: SchedulerMetrics | None = None,
                 forced_sync: bool | None = None,
                 state=None) -> None:
        # the injectable binder collects into the in-progress response;
        # one cycle at a time (serialized by _cycle_lock)
        self._bindings: list[pb.Binding] = []
        self.scheduler = scheduler or Scheduler(
            config=config, binder=self._collect_binding, metrics=metrics,
            forced_sync=forced_sync, state=state,
        )
        if scheduler is not None:
            scheduler.binder = self._collect_binding
            if metrics is not None:
                # rebind like the binder above: an injected scheduler must
                # still report into the registry the caller will serve
                scheduler.metrics = metrics
        self._cycle_lock = threading.Lock()
        self._uid_index: dict[str, object] = {}  # uid -> last seen Pod
        # incarnation id: a restarted shim at the same address must be
        # distinguishable from the one the agent fed state to (§5.3)
        self.boot_id = uuid.uuid4().hex
        # every N Cycle RPCs, run the per-plugin profiling pass so the
        # plugin-latency histograms stay populated in steady serving
        self.profile_every = int(profile_every)
        self._cycle_count = 0
        # Update RPCs handled: the scheduler holds the running total,
        # because its flight records carry it (None with no servicer)
        self.scheduler.update_rpcs = 0
        # submission front door (service/admission.py): None until
        # enable_front_door() — the Submit/NodeChurn RPCs answer
        # FAILED_PRECONDITION while disabled
        self.admission = None

    @property
    def collector(self):
        """The scheduler's core/collector.CollectorPolicy, set by
        cmd/main.main() and by nothing else: None leaves the
        interpreter's collector alone."""
        return self.scheduler.collector

    @collector.setter
    def collector(self, policy) -> None:
        self.scheduler.collector = policy

    def enable_front_door(self, **kwargs):
        """Attach an AdmissionController (idempotent) so the Submit /
        NodeChurn RPCs serve; returns the controller. The CLI calls
        this when --submit-addr is given."""
        if self.admission is None:
            from .admission import AdmissionController

            self.admission = AdmissionController(
                self.scheduler, **kwargs
            )
        return self.admission

    def run_local_cycle(self):
        """One scheduling cycle on the FRONT-DOOR serve loop,
        serialized against agent-driven Cycle RPCs by the same lock.
        Bindings are applied host-side (assume + events) exactly as in
        Cycle; the response-collection list is discarded — there is no
        RPC response to carry it."""
        with self._cycle_lock:
            self._bindings = []
            stats = self.scheduler.schedule_cycle()
            self._bindings = []
        self._cycle_left_standing(None)
        return stats

    def _cycle_left_standing(self, context) -> None:
        """The collector's turn (core/collector), outside the RPC: once
        gRPC has sent `Cycle`'s response and closed the call, which is
        when it runs a context's callbacks. A pass holds the interpreter
        lock, so one placed before the return would be paid by the agent
        inside `Cycle`; after it, it overlaps with the agent handling
        the response and building its next `Update` in another process,
        for as long as that takes: a few milliseconds since the
        confirmations go by reference, so a freeze hides there and a
        sweep does not (which is why the policy keeps sweeps rare). The
        front door's loop has no caller, and an in-process one (no
        context) no such moment: there it runs at once."""
        collector = self.collector
        if collector is None:
            return
        if context is None or not context.add_callback(collector.cycle_done):
            collector.cycle_done()

    def _collect_binding(self, pod, node_name: str) -> None:
        self._bindings.append(
            pb.Binding(
                pod_uid=pod.uid,
                pod_name=pod.name,
                pod_namespace=pod.namespace,
                node_name=node_name,
            )
        )

    # ---- RPCs ------------------------------------------------------------

    def Update(self, request: pb.UpdateRequest, context) -> pb.UpdateResponse:
        """Two passes in the request's order: every proto converted to
        its API object, then the informer handlers applied. Conversion
        touches no scheduler state, so the order of effects is what one
        interleaved pass gave; a request with an unparseable object now
        fails before any of it is applied. The request is applied as a
        group: each of its pod lists goes to ONE handler call
        (`on_pods_add`, `on_pods_update`, `confirm_pods`,
        `on_pods_delete`), and with a durable state attached the whole
        pass runs inside its `batch()` scope, so what the request
        journals (a sub-record a pod a store, in the order and with the
        clock values the handlers gave them) is appended as ONE `batch`
        record where the pass ends, before the response leaves: no new
        op, and no flush, fsync or acknowledgement moved (`rpc.update`
        carries the records appended as `journal_records`; the flight
        records carry the journal's running total). Armed, the RPC is
        one trace (core/spans): `rpc.update` with `update.convert` and
        `update.apply` as its children, four clock reads in all, and the
        child of the caller's `client.update` where the call names one
        (`_agents_side`). Each
        request handled is counted (`update_rpcs` in the flight records,
        `scheduler_update_rpcs_total`): an agent's batched() block is
        several."""
        armed = _spans.ARMED
        if armed:
            trace, caller = _spans.rpc_context(_traceparent(context))
            t_in = _spans.now()
        s = self.scheduler
        node_adds = [convert.node_from(n) for n in request.node_adds]
        node_updates = [convert.node_from(n) for n in request.node_updates]
        pod_adds = [
            (convert.pod_from(ev.pod), ev.bound_node)
            for ev in request.pod_adds
        ]
        pod_updates = [
            (convert.pod_from(ev.pod), ev.bound_node)
            for ev in request.pod_updates
        ]
        pvcs = [convert.pvc_from(c) for c in request.pvc_upserts]
        pvs = [convert.pv_from(v) for v in request.pv_upserts]
        storage_classes = [
            convert.storage_class_from(sc)
            for sc in request.storage_class_upserts
        ]
        pdbs = [convert.pdb_from(pdb) for pdb in request.pdb_upserts]
        if armed:
            t_converted = _spans.now()
        state = s.state
        records = state.journal.seq() if state is not None else 0
        # one journal group for the request: its emissions reach the
        # journal as ONE batch record where the scope closes, as a
        # cycle's do
        with state.batch() if state is not None else contextlib.nullcontext():
            for node in node_adds:
                s.on_node_add(node)
            for node in node_updates:
                s.on_node_update(node)
            for name in request.node_deletes:
                s.on_node_delete(name)
            for g in request.pod_groups:
                s.add_pod_group(PodGroup(g.name, g.min_member))
            self._uid_index.update((pod.uid, pod) for pod, _ in pod_adds)
            s.on_pods_add(pod_adds)
            self._uid_index.update((pod.uid, pod) for pod, _ in pod_updates)
            s.on_pods_update(pod_updates)
            # bindings of the previous Cycle, confirmed by reference;
            # any uid the cache does not hold assumed on that node goes
            # back to the agent untouched, to be sent in full
            unconfirmed = s.confirm_pods(
                [(c.pod_uid, c.node_name) for c in request.bind_confirms]
            )
            confirmed = len(request.bind_confirms) - len(unconfirmed)
            pod_deletes = list(request.pod_deletes)
            for uid in pod_deletes:
                self._uid_index.pop(uid, None)
            s.on_pods_delete(pod_deletes)
            for uid in request.bind_failures:
                # agent's Binding POST failed: forget + backoff (upstream
                # handleBindingCycleError)
                s.cache.forget(uid)
                pod = self._uid_index.get(uid)
                if pod is not None:
                    s.queue.requeue_backoff(pod)
            for pvc in pvcs:
                s.on_pvc_upsert(pvc)
            for key in request.pvc_deletes:
                s.on_pvc_delete(key)
            for pv in pvs:
                s.on_pv_upsert(pv)
            for name in request.pv_deletes:
                s.on_pv_delete(name)
            for sc in storage_classes:
                s.on_storage_class_upsert(sc)
            for name in request.storage_class_deletes:
                s.on_storage_class_delete(name)
            for pdb in pdbs:
                s.on_pdb_upsert(pdb)
            for key in request.pdb_deletes:
                s.on_pdb_delete(key)
            # /metrics follows what this request added, confirmed and
            # deleted now, not at the next cycle's end
            s.stamp_store_gauges()
        if state is not None:
            records = state.journal.seq() - records
        if armed:
            t_out = _spans.now()
            converted = (
                len(node_adds) + len(node_updates) + len(pod_adds)
                + len(pod_updates) + len(pvcs) + len(pvs)
                + len(storage_classes) + len(pdbs)
            )
            node_events = (
                len(node_adds) + len(node_updates)
                + len(request.node_deletes)
            )
            _spans.record_span(
                "update.convert", trace, t_in, t_converted,
                objects=converted,
            )
            _spans.record_span(
                "update.apply", trace, t_converted, t_out,
                objects=converted + len(request.node_deletes)
                + len(request.pod_groups) + len(request.bind_confirms)
                + len(request.pod_deletes)
                + len(request.bind_failures) + len(request.pvc_deletes)
                + len(request.pv_deletes)
                + len(request.storage_class_deletes)
                + len(request.pdb_deletes),
            )
            _spans.record_span(
                "rpc.update", trace, t_in, t_out, root_of=caller,
                pod_adds=len(pod_adds), pod_updates=len(pod_updates),
                pod_deletes=len(request.pod_deletes),
                bind_failures=len(request.bind_failures),
                node_events=node_events, bind_confirms=confirmed,
                confirm_fallbacks=len(unconfirmed),
                journal_records=records,
            )
            _agents_side(context, trace, caller, t_in, t_out)
        s.update_rpcs += 1
        s.metrics.update_rpcs.inc()
        return pb.UpdateResponse(
            boot_id=self.boot_id, bind_confirms_applied=confirmed,
            unconfirmed=unconfirmed,
        )

    def Cycle(self, request: pb.CycleRequest, context) -> pb.CycleResponse:
        """Armed, the RPC is one trace (core/spans): `rpc.cycle` is the
        root; the servicer stamps `cycle.lock_wait` and `cycle.respond`
        and the scheduler, handed the trace, stamps `cycle.pop` and
        `cycle.snapshot` and puts the trace id on the flight records it
        commits. `rpc.cycle`'s self time (its duration less its
        children and less the `total` of the records in `seqs`) is what
        `schedule_cycle` does outside all of them."""
        armed = _spans.ARMED
        trace = None
        if armed:
            trace, caller = _spans.rpc_context(_traceparent(context))
            t_in = _spans.now()
        with self._cycle_lock:
            if armed:
                t_locked = _spans.now()
            self._bindings = []
            s = self.scheduler
            stats = s.schedule_cycle(trace=trace)
            self._cycle_count += 1
            if self.profile_every and self._cycle_count % self.profile_every == 0:
                s.profile_cycle()
            if armed:
                t_scheduled = _spans.now()
            resp = pb.CycleResponse(
                boot_id=self.boot_id,
                bindings=list(self._bindings),
                stats=pb.CycleStats(
                    attempted=stats.attempted,
                    scheduled=stats.scheduled,
                    unschedulable=stats.unschedulable,
                    bind_errors=stats.bind_errors,
                    preemptors=stats.preemptors,
                    victims=stats.victims,
                    gang_dropped=stats.gang_dropped,
                    cycle_seconds=stats.cycle_seconds,
                ),
            )
            # nominations + evictions were applied to host state by the
            # driver; surface them from its per-cycle decision log
            for pod, node in s.last_nominations:
                resp.nominations.append(
                    pb.Nomination(pod_uid=pod.uid, node_name=node)
                )
            for pod, node in s.last_evictions:
                resp.evictions.append(
                    pb.Eviction(
                        pod_uid=pod.uid, pod_name=pod.name, node_name=node
                    )
                )
            # drain Scheduled/FailedScheduling/Preempted events so the
            # agent can post them as real Kubernetes Events
            for ev in s.events.drain():
                resp.events.append(
                    pb.Event(
                        type=ev.type,
                        reason=ev.reason,
                        pod_uid=ev.pod_uid,
                        pod_name=ev.pod_name,
                        message=ev.message,
                    )
                )
            if armed:
                t_out = _spans.now()
                n_bind, n_ev = len(resp.bindings), len(resp.events)
                _spans.record_span(
                    "cycle.lock_wait", trace, t_in, t_locked
                )
                _spans.record_span(
                    "cycle.respond", trace, t_scheduled, t_out,
                    bindings=n_bind, events=n_ev,
                )
                _spans.record_span(
                    "rpc.cycle", trace, t_in, t_out, root_of=caller,
                    seqs=list(s.last_cycle_seqs), bindings=n_bind,
                    events=n_ev, evictions=len(resp.evictions),
                    **s.last_cycle_counts,
                )
        if armed:
            _agents_side(context, trace, caller, t_in, t_out)
        self._cycle_left_standing(context)
        return resp

    def Health(self, request: pb.HealthRequest, context) -> pb.HealthResponse:
        return pb.HealthResponse(ok=True, status="ok", boot_id=self.boot_id)

    def Metrics(self, request: pb.MetricsRequest, context) -> pb.MetricsResponse:
        return pb.MetricsResponse(
            prometheus_text=self.scheduler.metrics.expose()
        )

    def Inspect(self, request: pb.InspectRequest, context) -> pb.InspectResponse:
        """Flight-recorder introspection over the agent's channel: the
        same payloads the /debug HTTP endpoints serve (cycle records,
        Perfetto trace, per-pod timeline), JSON-encoded."""
        import json

        fr = self.scheduler.flight
        kind = request.kind or "flightrecorder"
        last = request.last if request.last > 0 else 128
        # kind="pod" stays available with the recorder disabled — the
        # timeline join degrades to the events-ring half, exactly like
        # the /debug/pods HTTP endpoint
        if fr is None and kind in ("flightrecorder", "trace"):
            return pb.InspectResponse(
                ok=False, error="flight recorder disabled "
                "(flightRecorderSize: 0)",
            )
        if kind == "flightrecorder":
            payload = {
                "cycles": fr.to_dicts(last=last),
                "derived": fr.derived(last=last),
            }
        elif kind == "trace":
            from ..core.flight_recorder import to_chrome_trace

            payload = to_chrome_trace(
                fr.snapshot(last=last), epoch=fr.epoch
            )
        elif kind == "pod":
            payload = self.scheduler.pod_timeline(request.pod_uid)
            if payload is None:
                return pb.InspectResponse(
                    ok=False,
                    error=f"pod {request.pod_uid!r} not seen",
                )
        else:
            return pb.InspectResponse(
                ok=False,
                error=f"unknown kind {kind!r} "
                "(flightrecorder | trace | pod)",
            )
        return pb.InspectResponse(
            ok=True, json=json.dumps(payload).encode()
        )

    # ---- the submission front door (service/admission.py) ---------------

    def Submit(self, request: pb.SubmitRequest, context) -> pb.SubmitResponse:
        """Admission-controlled pod intake: whole-request accept or
        reject. Shed answers RESOURCE_EXHAUSTED with a retry-after-ms
        trailing-metadata hint; an OK ack means every pod was journaled
        through the WAL (group fsync) first — `durable` reports
        whether that barrier actually held (no state dir = false).

        Trace context (core/spans) rides gRPC metadata, not the proto:
        a W3C `traceparent` invocation-metadata entry joins the
        submission's spans to the caller's trace, and the ack's
        trailing metadata echoes the effective traceparent back (the
        caller's own, or the head-sampled root the scheduler minted)."""
        adm = self.admission
        if adm is None:
            context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                "front door disabled (start with --submit-addr or "
                "enable_front_door())",
            )
        try:
            pods = [convert.pod_from(p) for p in request.pods]
        except (ValueError, KeyError, TypeError) as e:
            # the proto contract: malformed pods answer
            # INVALID_ARGUMENT (an unparseable quantity here would
            # otherwise surface as UNKNOWN, which retrying clients
            # treat as transient and hammer forever)
            context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"unparseable pod in submission: {e}",
            )
        res = adm.submit(pods, traceparent=_traceparent(context))
        if res.invalid:
            context.abort(
                grpc.StatusCode.INVALID_ARGUMENT, res.reason
            )
        if res.reason == "draining":
            context.abort(
                grpc.StatusCode.UNAVAILABLE,
                "front door draining (shutdown in progress)",
            )
        if res.shed:
            context.set_trailing_metadata(
                (("retry-after-ms", f"{res.retry_after_ms:g}"),)
            )
            context.abort(
                grpc.StatusCode.RESOURCE_EXHAUSTED,
                f"admission shed: {res.reason}",
            )
        if res.traceparent:
            context.set_trailing_metadata(
                (("traceparent", res.traceparent),)
            )
        return pb.SubmitResponse(
            boot_id=self.boot_id,
            accepted=res.accepted,
            durable=res.durable,
            queue_depth=res.queue_depth,
        )

    def NodeChurn(
        self, request: pb.NodeChurnRequest, context
    ) -> pb.NodeChurnResponse:
        adm = self.admission
        if adm is None:
            context.abort(
                grpc.StatusCode.FAILED_PRECONDITION,
                "front door disabled (start with --submit-addr or "
                "enable_front_door())",
            )
        from .admission import AdmissionClosed

        try:
            adds = [convert.node_from(n) for n in request.adds]
            updates = [convert.node_from(n) for n in request.updates]
        except (ValueError, KeyError, TypeError) as e:
            context.abort(
                grpc.StatusCode.INVALID_ARGUMENT,
                f"unparseable node in churn request: {e}",
            )
        try:
            durable = adm.node_churn(
                adds=adds,
                updates=updates,
                deletes=list(request.deletes),
            )
        except AdmissionClosed:
            context.abort(
                grpc.StatusCode.UNAVAILABLE,
                "front door draining (shutdown in progress)",
            )
        return pb.NodeChurnResponse(
            boot_id=self.boot_id, durable=durable
        )


def _traceparent(context) -> str:
    """The W3C `traceparent` of the call's invocation metadata, "" when
    there is none (or no context: an in-process caller)."""
    if context is None:
        return ""
    for key, value in context.invocation_metadata() or ():
        if key == "traceparent":
            return value
    return ""


def _agents_side(context, trace, caller: str, t_in: float,
                 t_out: float) -> None:
    """The end of an armed `Update` or `Cycle`, its `rpc.*` span
    recorded: tell the caller that the ring is armed, as `Submit` does,
    by the effective `traceparent` in the trailing metadata
    (service/client.py stamps its `client.*` spans only while it sees
    one), and take in the spans the call carried
    (`core/spans.ingest`)."""
    if context is None:
        return
    context.set_trailing_metadata((("traceparent", trace.traceparent()),))
    for key, value in context.invocation_metadata() or ():
        if key == _spans.CLIENT_SPANS_KEY:
            _spans.ingest(value, caller, t_in, t_out)
            return


_RPCS = {
    "Update": (pb.UpdateRequest, pb.UpdateResponse),
    "Cycle": (pb.CycleRequest, pb.CycleResponse),
    "Health": (pb.HealthRequest, pb.HealthResponse),
    "Metrics": (pb.MetricsRequest, pb.MetricsResponse),
    "Inspect": (pb.InspectRequest, pb.InspectResponse),
    "Submit": (pb.SubmitRequest, pb.SubmitResponse),
    "NodeChurn": (pb.NodeChurnRequest, pb.NodeChurnResponse),
}


def add_to_server(servicer: SchedulerService, server: grpc.Server) -> None:
    handlers = {
        name: grpc.unary_unary_rpc_method_handler(
            getattr(servicer, name),
            request_deserializer=req.FromString,
            response_serializer=resp.SerializeToString,
        )
        for name, (req, resp) in _RPCS.items()
    }
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(SERVICE_NAME, handlers),)
    )


def serve(
    address: str = "127.0.0.1:50051",
    config: SchedulerConfiguration | None = None,
    max_workers: int = 4,
    profile_every: int = 0,
    metrics: SchedulerMetrics | None = None,
    forced_sync: bool | None = None,
    state=None,  # state.DurableState | None (restore-then-journal)
) -> tuple[grpc.Server, SchedulerService, int]:
    """Start the shim; returns (server, servicer, bound_port)."""
    service = SchedulerService(
        config=config, profile_every=profile_every, metrics=metrics,
        forced_sync=forced_sync, state=state,
    )
    # no SO_REUSEPORT: a second shim on the same address must fail loudly,
    # not silently split the accept queue with the first
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers),
        options=(("grpc.so_reuseport", 0),),
    )
    add_to_server(service, server)
    port = server.add_insecure_port(address)
    if port == 0 and not address.rstrip().endswith(":0"):
        # grpc signals bind failure by returning port 0; only an explicit
        # ":0" (ephemeral) request may legitimately come back remapped
        server.stop(grace=0)
        raise OSError(f"failed to bind gRPC address {address!r}")
    server.start()
    return server, service, port


def main() -> None:  # pragma: no cover - exercised via the CLI
    import argparse

    ap = argparse.ArgumentParser(description="TPU scheduler gRPC shim")
    ap.add_argument("--address", default="127.0.0.1:50051")
    args = ap.parse_args()
    server, _, port = serve(args.address)
    print(f"scheduler shim listening on port {port}", flush=True)
    server.wait_for_termination()


if __name__ == "__main__":  # pragma: no cover
    main()

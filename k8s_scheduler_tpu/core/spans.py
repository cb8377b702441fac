"""Pod-lifecycle tracing: span recorder, trace-context propagation.

Every other observability surface is cycle-centric (flight records,
phase histograms, the anomaly sentinel); since the front door landed,
the unit of work users experience is a POD REQUEST: Submit ->
admission -> WAL ack barrier -> dispatch -> decision row -> bind fold
-> confirm. This module makes that whole life one trace:

- `SpanRecorder` — a bounded ring of `Span`s with the same
  seqlock-style publication discipline as the cycle flight recorder
  (core/flight_recorder.FlightRecorder): a writer's cost is the span
  construction plus ONE list-slot store; readers copy the ring without
  blocking writers, retry while a commit tears the copy, and trim to
  the trailing window no commit could have torn. Unlike the flight
  recorder, spans are written from SEVERAL threads (gRPC/HTTP submit
  workers, the serve loop, informer threads); slot sequence numbers
  come from `itertools.count` (atomic in CPython), so concurrent
  writers never race a slot index read-modify-write.
- Arming — the PR 8 fault-hook pattern (core/faults.py): a module
  global `ARMED` flag plus `arm()`/`disarm()`. Unarmed, every stamp
  site pays ONE module-attribute load and a falsy branch; armed, a
  stamp is dict stores into a Span plus the slot store. The scheduler
  never imports anything trace-specific on the unarmed path.
- Context propagation — `register(uid, traceparent)` binds a pod uid
  to a trace at admission time: an explicit W3C-style `traceparent`
  joins the caller's trace; absent one, deterministic head sampling
  (`sampled(uid)`, a uid-hash coin at the armed sample rate) decides
  per pod. The uid -> context map is the cross-thread join: spans
  emitted on the submit thread (validate/journal/ack), the serve
  thread (buffer wait, dispatch, decision row, apply fold, bind
  confirm) and anywhere else all look the context up by uid and land
  in ONE trace. `release(uid)` drops the binding at the pod's
  terminal event (bound / deleted).
- The agent path — an `Update` or `Cycle` RPC (service/server.py) is
  one trace of its own (`rpc_context`: its own id, or the caller's
  when the call carries a `traceparent`): the `rpc.*` span is the root
  (`record_span(..., root_of=<caller's span or "">)`) and the phases
  are its children. Stamps are per RPC and per phase, never per pod,
  and no context is registered; `any_context()` lets the scheduler
  skip its per-pod stamp sites in every cycle where none is.
- The agent's side — `service/client.py` stamps six `client.*` spans
  around its `Update`s and `Cycle`s, in ITS process, and ships the
  completed ones as one binary metadata entry of its next RPC
  (`Outbox`, at most `SHIP_MAX_BYTES` a call). The armed servicer hands
  the entry to `ingest`, which bounds the client's clock against the
  recorder's from both sides (`_Peer`) and stores the spans in the ring
  under the ids the client minted, on the recorder's clock: `rpc.*` is
  the child of `client.update` / `client.cycle` by the `traceparent`
  the same call carried. A call whose spans cannot be placed (the
  bounds wider apart than `PLACE_WINDOW_S`) stores none of them and
  counts them.
- Export — `spans_to_chrome_events` renders per-trace tracks that
  `to_chrome_trace` merges into the cycle lanes (one Perfetto view
  shows a pod's spans overlapping the batch that served it; the
  agent RPC spans share one lane beside the host lane), and
  `to_otlp_json` / `export_otlp_dir` produce OTLP-JSON resource spans
  for external ingestion (`--trace-export-dir`, size-rotated).

`SPAN_NAMES` below is the pinned span inventory; schedlint's ID010
check keeps it, the README "## Distributed tracing" span table, and
the metrics docstring from drifting apart. Stdlib-only (no jax /
numpy / prometheus) so the state layer, tools and tests can import it
without a backend.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import os
import re
import struct
import threading
import time as _time
import uuid
from collections import deque
from typing import Any, Callable, Iterable

# The pinned span-name inventory — every stamp site emits one of
# these. Grouped by the thread that stamps them:
#   submit thread:  submit.validate (request validation + dup check),
#                   submit.journal (the informer-path enqueue, which
#                   appends q.add through the WAL), ack.barrier (the
#                   group-commit fsync wait; one span PER SUBMITTER,
#                   all joined to the shared flush seq via the
#                   `flush_seq` attr)
#   serve thread:   dispatch (device dispatch window),
#                   decision.row (the cycle's slimmed decision
#                   transfer), apply.fold (winner bind loop ->
#                   postfilter), bind.confirm (the pod's bind),
#                   preempt.victim (an eviction this pod's nomination
#                   caused; attrs name the victim)
#   agent RPC thread (service/server.py; one trace per RPC, the rpc.*
#   span its root and the others its children — see AGENT_SPAN_NAMES):
#                   rpc.update (Update handler entry -> return),
#                   update.convert (proto -> API objects),
#                   update.apply (the informer handlers), rpc.cycle
#                   (Cycle handler entry -> return; attr `seqs` joins
#                   the flight records committed under it),
#                   cycle.lock_wait (waiting for the cycle lock),
#                   cycle.pop (schedule_cycle entry -> the first
#                   profile's record starts), cycle.snapshot (the
#                   journal compaction, only when one ran),
#                   cycle.postfilter (winners bound -> the preemption
#                   program's output in hand: the host's wait for
#                   `packed_preempt`) and cycle.losers (from there to
#                   the last loser requeued: the diagnosis fetch, the
#                   messages, the events, the parks and their journal
#                   records), both only in a cycle that refused a pod,
#                   cycle.respond (schedule_cycle returned -> response
#                   built)
#   whichever thread the collector runs on (core/collector.py; a trace
#   of its own each, no parent): gc.pass — a placed operation (attr
#                   `kind`: freeze | sweep) on the thread that ran
#                   `cycle_done`, gRPC's serving thread after a `Cycle`
#                   or the front door's loop; a generation-2 pass the
#                   interpreter started itself (`auto_full`) on the
#                   thread whose allocation set it off
#   the agent's process (service/client.py; stamped there per block and
#   per request, never per pod, shipped with the agent's next RPC and
#   stored here by `ingest` on the recorder's clock — see
#   CLIENT_SPAN_NAMES): client.batch (the outermost
#                   `SchedulerAgent.batched()` block, entry -> its last
#                   chunk acknowledged; a trace of its own),
#                   client.build (a chunk opened -> its send begins:
#                   the agent converting), client.send (an `Update`
#                   serialised and handed to gRPC), client.ack_wait
#                   (the agent blocked on the `Update` in flight; only
#                   where it blocked), all three children of
#                   client.batch, client.update (one `Update` request,
#                   send begins -> response in the agent's hands;
#                   `rpc.update` is its child), client.cycle
#                   (`SchedulerClient.cycle()` called -> the decoded
#                   response returned; a trace of its own, `rpc.cycle`
#                   its child)
SPAN_NAMES = (
    "submit.validate",
    "submit.journal",
    "ack.barrier",
    "dispatch",
    "decision.row",
    "apply.fold",
    "bind.confirm",
    "preempt.victim",
    "rpc.update",
    "update.convert",
    "update.apply",
    "rpc.cycle",
    "cycle.lock_wait",
    "cycle.pop",
    "cycle.snapshot",
    "cycle.postfilter",
    "cycle.losers",
    "cycle.respond",
    "gc.pass",
    "client.batch",
    "client.build",
    "client.send",
    "client.ack_wait",
    "client.update",
    "client.cycle",
)

# the agent path's spans (Update / Cycle): per RPC and per phase, never
# per pod, so they render on one lane instead of a track per trace. The
# collector's passes share the lane: a pass holds the interpreter lock,
# so it lies inside the RPC it delayed or between two, never across an
# edge
AGENT_SPAN_NAMES = frozenset(
    n for n in SPAN_NAMES
    if n.startswith(("rpc.", "update.", "cycle.", "gc."))
)

# the agent's own spans, in the order of their index on the wire, each
# with the names of the integers it ships (`Outbox.add`'s `a`, `b`).
# They overlap the RPC lane's spans without nesting in them (a
# `client.build` runs under the previous chunk's `rpc.update`), so
# they render on a lane of their own
CLIENT_SPAN_ATTRS = {
    "client.batch": ("requests", "bytes"),
    "client.build": ("objects",),
    "client.send": ("bytes",),
    "client.ack_wait": (),
    "client.update": ("bytes", "objects"),
    "client.cycle": ("bindings", "events"),
}
CLIENT_SPAN_NAMES = tuple(n for n in SPAN_NAMES if n in CLIENT_SPAN_ATTRS)

# default head-sampling rate (absent an explicit traceparent): 1/64
DEFAULT_SAMPLE_RATE = 1.0 / 64.0

_TRACEPARENT_RE = re.compile(
    r"^00-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)

# uid -> TraceContext bound at most this deep (LRU): a pod parked
# unschedulable forever must not pin its context entry
_MAX_CONTEXTS = 65_536


@dataclasses.dataclass
class Span:
    """One completed operation in a pod's trace. Times are absolute
    recorder-clock seconds (perf_counter, the same clock the flight
    recorder stamps marks with, so span slices and cycle lanes rebase
    against one epoch). Spans are immutable once recorded — the ring
    replaces slots, it never mutates them."""

    trace_id: str  # 32 hex chars (W3C trace-id)
    span_id: str  # 16 hex chars
    parent: str  # 16 hex chars, "" for a root span
    name: str  # one of SPAN_NAMES
    t0: float
    t1: float
    seq: int = -1  # recorder slot sequence (assigned by record())
    attrs: dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self, epoch: float = 0.0) -> dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent": self.parent,
            "name": self.name,
            "t0_s": round(self.t0 - epoch, 6),
            "t1_s": round(self.t1 - epoch, 6),
            "dur_ms": round(max(self.t1 - self.t0, 0.0) * 1e3, 4),
            "attrs": dict(self.attrs),
        }


@dataclasses.dataclass(frozen=True)
class TraceContext:
    """A pod's binding to a trace, created at admission. `span_id` is
    the parent every span emitted for the pod names: a locally minted
    root id for head-sampled pods, the caller's span id when an
    explicit traceparent joined us to an existing trace. `tenant` is
    the pod's virtual cluster ("" in single-tenant mode): every span
    recorded under the context inherits it as a `tenant` attr, so one
    trace view shows per-tenant lanes."""

    trace_id: str
    span_id: str
    tenant: str = ""

    def traceparent(self) -> str:
        return format_traceparent(self.trace_id, self.span_id)


# ---- W3C traceparent helpers --------------------------------------------


def parse_traceparent(value: str) -> "tuple[str, str] | None":
    """(trace_id, parent_span_id) from a W3C traceparent header, or
    None when malformed / all-zero (the spec's invalid sentinels)."""
    m = _TRACEPARENT_RE.match((value or "").strip().lower())
    if m is None:
        return None
    trace_id, span_id = m.group(1), m.group(2)
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def format_traceparent(trace_id: str, span_id: str) -> str:
    # flags 01: sampled (we only hold contexts for sampled pods)
    return f"00-{trace_id}-{span_id}-01"


def new_trace_id() -> str:
    return uuid.uuid4().hex


def new_span_id() -> str:
    return os.urandom(8).hex()


def sampled(uid: str, rate: float) -> bool:
    """Deterministic head-sampling coin: the same uid at the same rate
    always decides the same way (a retry of a shed submission keeps
    its sampling fate), and distinct uids decide independently. rate
    >= 1 samples everything, <= 0 nothing."""
    if rate >= 1.0:
        return True
    if rate <= 0.0:
        return False
    h = hashlib.blake2b(uid.encode(), digest_size=8).digest()
    return int.from_bytes(h, "big") / 2.0**64 < rate


# ---- the span ring -------------------------------------------------------


class SpanRecorder:
    """Bounded multi-writer ring of completed spans.

    Writer cost: one Span construction + one list-slot store (the
    slot index comes from an `itertools.count`, whose `next()` is
    atomic under CPython — concurrent submit/serve/informer threads
    never race an index increment). `_commits` publishes like the
    flight recorder's seqlock generation; its increment is a benign
    multi-writer race (a lost increment can only cost a reader one
    extra retry) because the snapshot's trailing-window trim — keep
    only the newest run of seqs no commit could have torn — is the
    correctness backstop, exactly as it is for FlightRecorder."""

    def __init__(
        self,
        capacity: int = 8192,
        now: Callable[[], float] = _time.perf_counter,
        wall: Callable[[], float] = _time.time,
    ) -> None:
        self.capacity = max(int(capacity), 1)
        self.now = now
        self._ring: "list[Span | None]" = [None] * self.capacity
        self._seq = itertools.count()
        self._commits = 0
        self.epoch = now()
        self.wall_epoch = wall()

    # ---- writer side -----------------------------------------------------

    def record(
        self,
        name: str,
        ctx: TraceContext,
        t0: float,
        t1: float,
        *,
        root_of: "str | None" = None,
        **attrs: Any,
    ) -> Span:
        """Record one completed span under `ctx` (parent = the
        context's root/caller span id). A tenant-scoped context stamps
        its tenant on every span it records — one stamp site, so no
        emitter can forget the attribution. `root_of` records the
        context's OWN span instead — the one its children name as
        parent — as a child of the span id given ("" for a trace
        that starts here): an agent RPC's root span."""
        if ctx.tenant and "tenant" not in attrs:
            attrs["tenant"] = ctx.tenant
        span = Span(
            trace_id=ctx.trace_id,
            span_id=new_span_id() if root_of is None else ctx.span_id,
            parent=ctx.span_id if root_of is None else root_of,
            name=name,
            t0=t0,
            t1=t1,
            attrs=attrs,
        )
        return self.ingest(span)

    def ingest(self, span: Span) -> Span:
        """Store a span under the ids it came with: `record`'s tail,
        and the way in for a span another process minted (the agent's
        `client.*`, already on this recorder's clock)."""
        span.seq = next(self._seq)
        self._ring[span.seq % self.capacity] = span
        # publish AFTER the slot store (GIL-ordered); see class doc
        # for why the racy increment is safe here
        self._commits += 1  # schedlint: disable=TR001 -- benign seqlock-generation race: the snapshot trim is the correctness backstop
        return span

    # ---- reader side -----------------------------------------------------

    @property
    def count(self) -> int:
        """Spans recorded (approximate under concurrent writers —
        monotonic, may trail by in-flight commits)."""
        return self._commits

    def snapshot(self, last: "int | None" = None) -> "list[Span]":
        """Consistent copy of the most recent `last` spans (oldest
        first). Same discipline as FlightRecorder.snapshot: retry the
        lock-free copy while a commit lands in it, then trim to the
        trailing contiguous-capacity window."""
        ring: "list[Span | None]" = []
        for _ in range(8):
            before = self._commits
            ring = list(self._ring)
            if self._commits == before:
                break
        spans = sorted(
            (s for s in ring if s is not None), key=lambda s: s.seq
        )
        if spans:
            spans = [
                s for s in spans
                if s.seq > spans[-1].seq - self.capacity
            ]
        if last is not None:
            n = max(int(last), 0)
            spans = spans[-n:] if n else []
        return spans

    def for_trace(self, trace_id: str) -> "list[Span]":
        return [s for s in self.snapshot() if s.trace_id == trace_id]

    def for_uid(self, uid: str) -> "list[Span]":
        """Spans whose `uid` attr names the pod — the /debug join for
        pods whose context has already been released."""
        return [
            s for s in self.snapshot() if s.attrs.get("uid") == uid
        ]

    def to_dicts(self, last: "int | None" = None) -> "list[dict]":
        return [s.to_dict(epoch=self.epoch) for s in self.snapshot(last)]


# ---- module arming (the PR 8 fault-hook pattern) -------------------------

# Hot sites gate on `spans.ARMED` (one module-attribute load + branch
# unarmed); cross-package sites that must not import core (the state
# layer) reach this module through sys.modules, exactly like
# state/journal.py reaches core.faults.
ARMED = False
RECORDER: "SpanRecorder | None" = None
_RATE = DEFAULT_SAMPLE_RATE
# span-name -> count callback (the CLI wires the
# scheduler_trace_spans_total counter here; tests leave it None)
_COUNTER: "Callable[[str], None] | None" = None

_ctx_lock = threading.Lock()
_contexts: "dict[str, TraceContext]" = {}


def arm(
    recorder: "SpanRecorder | None" = None,
    rate: float = DEFAULT_SAMPLE_RATE,
    counter: "Callable[[str], None] | None" = None,
) -> SpanRecorder:
    """Install `recorder` (a fresh default-capacity one when None) as
    the process-wide span sink and flip every stamp site live."""
    global ARMED, RECORDER, _RATE, _COUNTER
    RECORDER = recorder if recorder is not None else SpanRecorder()
    _RATE = float(rate)
    _COUNTER = counter
    ARMED = True
    return RECORDER


def disarm() -> None:
    """Flip every stamp site back to the one-flag-load path and drop
    the uid -> context map (the recorder stays readable for post-hoc
    export until the next arm() replaces it)."""
    global ARMED, _COUNTER
    ARMED = False
    _COUNTER = None
    with _ctx_lock:
        _contexts.clear()
    with _peer_lock:
        _peers.clear()


def now() -> float:
    """The armed recorder's clock (perf_counter unless a test
    injected another) — stamp sites use this so span times and
    flight-record marks share one base."""
    rec = RECORDER
    return rec.now() if rec is not None else _time.perf_counter()


# ---- context registry (the cross-thread trace join) ----------------------


def register(
    uid: str, traceparent: str = "", tenant: str = ""
) -> "TraceContext | None":
    """Bind `uid` to a trace at admission: join the caller's trace
    when `traceparent` parses, else head-sample at the armed rate.
    `tenant` names the pod's virtual cluster (multi-tenant front door;
    "" otherwise) and rides the context onto every recorded span.
    Returns the context (None = unsampled or unarmed). Idempotent for
    an already-registered uid (a duplicate submit keeps the original
    binding)."""
    if not ARMED:
        return None
    parsed = parse_traceparent(traceparent) if traceparent else None
    if parsed is None and not sampled(uid, _RATE):
        return None
    with _ctx_lock:
        ctx = _contexts.get(uid)
        if ctx is None:
            if parsed is not None:
                ctx = TraceContext(*parsed, tenant=tenant)
            else:
                ctx = TraceContext(
                    new_trace_id(), new_span_id(), tenant=tenant
                )
            _contexts[uid] = ctx
            if len(_contexts) > _MAX_CONTEXTS:
                # drop the oldest insertion (dicts iterate in order)
                _contexts.pop(next(iter(_contexts)))
    return ctx


def ctx_for(uid: str) -> "TraceContext | None":
    with _ctx_lock:
        return _contexts.get(uid)


def any_context() -> bool:
    """Whether any pod is bound to a trace right now, without the
    lock: the scheduler asks once per cycle and skips its per-pod
    stamp sites when none is, which is always so on the agent path
    (only Submit registers contexts)."""
    return bool(_contexts)


def rpc_context(traceparent: str = "") -> "tuple[TraceContext, str]":
    """One agent RPC is one trace: (the context its phases record
    under, the root span's parent). A `traceparent` that parses joins
    the caller's trace, the caller's span the root's parent; otherwise
    the trace starts here and the root has none."""
    parsed = parse_traceparent(traceparent) if traceparent else None
    trace_id, parent = parsed if parsed is not None else (new_trace_id(), "")
    return TraceContext(trace_id, new_span_id()), parent


def release(uid: str) -> None:
    """Drop the uid's trace binding at its terminal event (bound /
    deleted). Recorded spans stay in the ring; only the LIVE join is
    released."""
    with _ctx_lock:
        _contexts.pop(uid, None)


def record_span(
    name: str,
    ctx: TraceContext,
    t0: float,
    t1: float,
    *,
    root_of: "str | None" = None,
    **attrs: Any,
) -> None:
    """The armed stamp: one span into the module recorder. Callers
    gate on `ARMED` themselves (that IS the unarmed fast path); a
    stamp racing a concurrent disarm is dropped silently."""
    rec = RECORDER
    if rec is None:
        return
    rec.record(name, ctx, t0, t1, root_of=root_of, **attrs)
    _count(name)


def _count(name: str) -> None:
    cb = _COUNTER
    if cb is not None:
        try:
            cb(name)
        except Exception:  # schedlint: disable=RB001 -- observability counter failure must never reach a stamp site on the serve/submit path
            pass


# ---- the agent's spans: shipped by the client, ingested here -------------

# the invocation-metadata key of the one binary entry a call carries
CLIENT_SPANS_KEY = "client-spans-bin"
# ... and its size limit: what does not fit is dropped, oldest first
SHIP_MAX_BYTES = 4096
# the entry: a head (who ships: 8 random bytes a client; the client's
# clock when it handed the call to gRPC; spans it dropped since its last
# call) and fixed-width spans (index into CLIENT_SPAN_NAMES, trace id,
# span id, parent, t0 and t1 on the client's clock, two integers named
# by CLIENT_SPAN_ATTRS)
_SHIP_HEAD = struct.Struct("<8sdI")
_SHIP_SPAN = struct.Struct("<B16s8s8sddII")
SHIP_MAX_SPANS = (SHIP_MAX_BYTES - _SHIP_HEAD.size) // _SHIP_SPAN.size
# a client's spans are stored only while the two bounds on its clock
# stand no wider apart than this: a stored span may sit that far from
# its true place on the recorder's clock, and usually sits within a
# fraction of it (the two ways of the wire cost about the same). The
# bounds close to the connection's best round trip AS PYTHON SEES IT:
# 1.3-3.6 ms on one host (gRPC's call set-up, a thread hand-off on each
# side; PERF.md section 6), where the clocks are one and the offset
# came out as 0.1-0.6 ms. The nesting of `rpc.*` in its `client.*`
# parent does not hang on the width: see `ingest`
PLACE_WINDOW_S = 10e-3
_MAX_PEERS = 64
_MAX_EXITS = 16


class Outbox:
    """The completed `client.*` spans of one client, waiting for its
    next RPC. Bounded to what one call may carry: a span added to a full
    outbox pushes the oldest out, and `shipment` tells the server how many
    went that way. A span completed after the process's last RPC is
    never shipped."""

    def __init__(self) -> None:
        self.client_id = os.urandom(8)
        self._spans: deque = deque(maxlen=SHIP_MAX_SPANS)
        self.dropped = 0

    def add(self, name: str, trace_id: str, span_id: str, parent: str,
            t0: float, t1: float, a: int = 0, b: int = 0) -> None:
        if len(self._spans) == SHIP_MAX_SPANS:
            self.dropped += 1
        self._spans.append(_SHIP_SPAN.pack(
            CLIENT_SPAN_NAMES.index(name), bytes.fromhex(trace_id),
            bytes.fromhex(span_id), bytes.fromhex(parent), t0, t1,
            min(a, 0xFFFFFFFF), min(b, 0xFFFFFFFF)))

    def shipment(self, sent: float) -> bytes:
        """The metadata entry of a call handed to gRPC at `sent` on the
        client's clock; empties the outbox."""
        entry = _SHIP_HEAD.pack(
            self.client_id, sent, self.dropped) + b"".join(self._spans)
        self._spans.clear()
        self.dropped = 0
        return entry


class _Peer:
    """What the recorder knows of one shipping client's clock: the
    offset (recorder's less client's) bounded from both sides. `up` is
    the least (handler entry - the client's clock at send) and `lo` the
    greatest (handler exit - the client's clock at the response) over
    its calls: each sample is off by one way of the wire, never by less
    than nothing. `exits` holds the handler exits by the `client.*` span
    that called, until that span arrives."""

    __slots__ = ("up", "lo", "offset", "exits", "unplaced")

    def __init__(self) -> None:
        self.up, self.lo = float("inf"), float("-inf")
        self.offset: "float | None" = None
        self.exits: "dict[str, float]" = {}
        self.unplaced = 0


_peer_lock = threading.Lock()
_peers: "dict[bytes, _Peer]" = {}


def ingest(blob: bytes, caller: str, t_in: float, t_out: float) -> int:
    """Take in the `client.*` spans one `Update` or `Cycle` carried
    (`CLIENT_SPANS_KEY`), the armed servicer's call once its own `rpc.*`
    span is recorded; `caller` is the `client.*` span the call's
    `traceparent` named, `t_in` and `t_out` the handler's entry and exit
    on the recorder's clock. Returns the spans stored.

    One clock: the spans come on the client's `perf_counter` and are
    stored on the recorder's, by an offset between the two bounds
    `_Peer` keeps: their midpoint, taken once and again when the bounds
    have closed away from it, so that a client's spans keep their order
    whatever call carried them. Both bounds hold for every call they
    were taken from, so with any offset between them a `client.update`
    or `client.cycle` begins before its `rpc.*` child and ends after
    it: exactly, however wide they stand. How far the pair may sit from
    its true place is that width: on one host both clocks are
    CLOCK_MONOTONIC and the bounds close around 0 to the loopback's
    round trip, across hosts to the connection's best. While a client
    is bounded from one side only (its first calls) or the bounds stand
    wider apart than PLACE_WINDOW_S, the call's spans are dropped and
    counted, never guessed: the count rides as `unplaced` on the next
    span stored for that client, as the client's own `dropped` does.
    Bounds that cross (a clock was stepped, or drifted over a long
    connection) start again from this call. A malformed entry stores
    nothing and raises nothing."""
    rec = RECORDER
    n, rest = divmod(len(blob) - _SHIP_HEAD.size, _SHIP_SPAN.size)
    if rec is None or n < 0 or rest:
        return 0
    client_id, sent, dropped = _SHIP_HEAD.unpack_from(blob)
    shipped = [
        s for s in _SHIP_SPAN.iter_unpack(blob[_SHIP_HEAD.size:])
        if s[0] < len(CLIENT_SPAN_NAMES)
    ]
    with _peer_lock:
        peer = _peers.pop(client_id, None) or _Peer()
        _peers[client_id] = peer  # newest last
        if len(_peers) > _MAX_PEERS:
            _peers.pop(next(iter(_peers)))
        peer.up = min(peer.up, t_in - sent)
        for s in shipped:
            left = peer.exits.pop(s[2].hex(), None)
            if left is not None:
                peer.lo = max(peer.lo, left - s[5])
        if caller:
            peer.exits[caller] = t_out
            if len(peer.exits) > _MAX_EXITS:
                peer.exits.pop(next(iter(peer.exits)))
        if peer.lo > peer.up:
            peer.up, peer.lo, peer.offset = t_in - sent, float("-inf"), None
        width = peer.up - peer.lo
        if width > PLACE_WINDOW_S:  # also while `lo` is unbounded
            peer.unplaced += len(shipped)
            return 0
        middle, offset = (peer.up + peer.lo) / 2, peer.offset
        if offset is None or abs(offset - middle) > width / 4:
            offset = peer.offset = middle
        extra = {}
        if dropped:
            extra["dropped"] = dropped
        if peer.unplaced:
            extra["unplaced"], peer.unplaced = peer.unplaced, 0
    for index, trace_id, span_id, parent, t0, t1, a, b in shipped:
        name = CLIENT_SPAN_NAMES[index]
        attrs = dict(zip(CLIENT_SPAN_ATTRS[name], (a, b)), **extra)
        extra = {}
        rec.ingest(Span(
            trace_id.hex(), span_id.hex(),
            parent.hex() if any(parent) else "", name,
            t0 + offset, t1 + offset, attrs=attrs))
        _count(name)
    return len(shipped)


# ---- export --------------------------------------------------------------

# chrome-trace: span tracks render in their own process group so
# Perfetto shows them under (and time-aligned with) the cycle lanes
TRACE_TRACK_PID = 2
# agent RPC spans: ONE lane in the cycle lanes' own process (pid 1,
# core/flight_recorder._slice), sorted above its host lane — a track
# per RPC would be thousands of one-slice tracks
AGENT_LANE_PID = 1
AGENT_LANE_TID = 4
# ... and the agent's own spans one lane above it: the caller, the
# servicer, the host, the device, top down
CLIENT_LANE_TID = 5
# (span names, tid, sort index, lane name)
_LANES = (
    (AGENT_SPAN_NAMES, AGENT_LANE_TID, 0, "agent RPCs (Update/Cycle)"),
    (frozenset(CLIENT_SPAN_NAMES), CLIENT_LANE_TID, -1,
     "agent process (client.*)"),
)
_LANE_OF = {n: tid for names, tid, _, _ in _LANES for n in names}


def spans_to_chrome_events(
    spans: Iterable[Span], epoch: float = 0.0
) -> "list[dict]":
    """Chrome-trace events for per-trace tracks: one tid per trace_id
    (named by the trace's pod uids), each span an `X` slice whose args
    carry the span/parent ids and attrs — the flight-record `seq` attr
    included, which is the exemplar join back to the cycle lanes. The
    agent RPC spans (AGENT_SPAN_NAMES) share one lane beside the host
    lane; children nest inside their RPC by time. The agent's own
    (CLIENT_SPAN_NAMES) take the lane above it."""
    events: "list[dict]" = []
    tids: "dict[str, int]" = {}
    uids: "dict[str, set]" = {}
    tenants: "dict[str, set]" = {}
    spans = list(spans)
    lanes = set()
    for s in spans:
        if s.name in _LANE_OF:
            lanes.add(_LANE_OF[s.name])
            continue
        tid = tids.setdefault(s.trace_id, len(tids) + 1)
        uid = s.attrs.get("uid")
        if uid:
            uids.setdefault(s.trace_id, set()).add(uid)
        tn = s.attrs.get("tenant")
        if tn:
            tenants.setdefault(s.trace_id, set()).add(tn)
    for _, lane, at, title in _LANES:
        if lane not in lanes:
            continue
        events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": AGENT_LANE_PID,
                "tid": lane,
                "args": {"name": title},
            }
        )
        events.append(
            {
                "name": "thread_sort_index",
                "ph": "M",
                "pid": AGENT_LANE_PID,
                "tid": lane,
                "args": {"sort_index": at},
            }
        )
    if tids:
        events.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": TRACE_TRACK_PID,
                "args": {"name": "pod traces"},
            }
        )
    for trace_id, tid in tids.items():
        pods = ",".join(sorted(uids.get(trace_id, ()))) or "?"
        # tenant-scoped traces lead with the tenant so Perfetto's
        # track list groups one virtual cluster's lanes together
        tn = ",".join(sorted(tenants.get(trace_id, ())))
        prefix = f"tenant {tn} " if tn else ""
        events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": TRACE_TRACK_PID,
                "tid": tid,
                "args": {
                    "name": f"{prefix}trace {trace_id[:8]} pod={pods}"
                },
            }
        )
        events.append(
            {
                "name": "thread_sort_index",
                "ph": "M",
                "pid": TRACE_TRACK_PID,
                "tid": tid,
                "args": {"sort_index": tid},
            }
        )
    for s in spans:
        lane = _LANE_OF.get(s.name)
        events.append(
            {
                "name": s.name,
                "ph": "X",
                "pid": AGENT_LANE_PID if lane else TRACE_TRACK_PID,
                "tid": lane or tids[s.trace_id],
                "ts": round((s.t0 - epoch) * 1e6, 3),
                "dur": round(max(s.t1 - s.t0, 0.0) * 1e6, 3),
                "cat": "pod-trace",
                "args": {
                    "trace_id": s.trace_id,
                    "span_id": s.span_id,
                    "parent": s.parent,
                    **s.attrs,
                },
            }
        )
    return events


def _otlp_value(v: Any) -> dict:
    if isinstance(v, bool):
        return {"boolValue": v}
    if isinstance(v, int):
        return {"intValue": str(v)}
    if isinstance(v, float):
        return {"doubleValue": v}
    return {"stringValue": str(v)}


def to_otlp_json(
    spans: Iterable[Span],
    epoch: float,
    wall_epoch: float,
    service_name: str = "tpu-scheduler",
) -> dict:
    """OTLP/JSON (the OTLP file-exporter shape: one resourceSpans
    entry, spans with hex ids and unix-nano times anchored at the
    recorder's wall epoch) for external ingestion."""

    def nanos(t: float) -> str:
        return str(int((t - epoch + wall_epoch) * 1e9))

    return {
        "resourceSpans": [
            {
                "resource": {
                    "attributes": [
                        {
                            "key": "service.name",
                            "value": {"stringValue": service_name},
                        }
                    ]
                },
                "scopeSpans": [
                    {
                        "scope": {"name": "k8s_scheduler_tpu.core.spans"},
                        "spans": [
                            {
                                "traceId": s.trace_id,
                                "spanId": s.span_id,
                                **(
                                    {"parentSpanId": s.parent}
                                    if s.parent else {}
                                ),
                                "name": s.name,
                                "kind": 1,  # SPAN_KIND_INTERNAL
                                "startTimeUnixNano": nanos(s.t0),
                                "endTimeUnixNano": nanos(s.t1),
                                "attributes": [
                                    {
                                        "key": k,
                                        "value": _otlp_value(v),
                                    }
                                    for k, v in s.attrs.items()
                                ],
                            }
                            for s in spans
                        ],
                    }
                ],
            }
        ]
    }


def export_otlp_dir(
    recorder: SpanRecorder,
    directory: str,
    max_bytes: int = 64 << 20,
) -> "str | None":
    """Dump the recorder's current window as one OTLP-JSON file in
    `directory` (created if needed), then rotate: oldest dumps are
    deleted until the directory's spans-*.json total is back under
    `max_bytes`. Returns the written path (None when the ring is
    empty). Called at shutdown by the CLI; safe to call repeatedly —
    each call writes the next spans-NNNNNN.json in sequence."""
    spans = recorder.snapshot()
    if not spans:
        return None
    os.makedirs(directory, exist_ok=True)
    existing = sorted(
        f for f in os.listdir(directory)
        if f.startswith("spans-") and f.endswith(".json")
    )
    nxt = 0
    if existing:
        try:
            nxt = int(existing[-1][len("spans-"):-len(".json")]) + 1
        except ValueError:
            nxt = len(existing)
    path = os.path.join(directory, f"spans-{nxt:06d}.json")
    payload = to_otlp_json(spans, recorder.epoch, recorder.wall_epoch)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    # size rotation, oldest-first, never the file just written
    files = sorted(
        f for f in os.listdir(directory)
        if f.startswith("spans-") and f.endswith(".json")
    )
    total = sum(
        os.path.getsize(os.path.join(directory, f)) for f in files
    )
    for f in files[:-1]:
        if total <= max_bytes:
            break
        fp = os.path.join(directory, f)
        total -= os.path.getsize(fp)
        os.remove(fp)
    return path

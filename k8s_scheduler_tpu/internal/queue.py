"""SchedulingQueue: active / backoff / unschedulable tiers.

The reference's `PriorityQueue` (`internal/queue/scheduling_queue.go` —
[UNVERIFIED], mount empty; SURVEY.md §2 C3) is a heap popped one pod at a
time by 16-way goroutine consumers. The TPU design schedules the WHOLE
ready set per cycle, so the heap collapses to set bookkeeping:

- `active`: pods ready for the next cycle. `pop_ready()` drains it (the
  batch analogue of Pop); ordering is re-derived by the encoder's
  `pod_order` (PrioritySort), so no heap is needed host-side.
- `backoff`: pods that failed recently, with an expiry deadline
  (exponential per-pod backoff, initial/max from config — upstream
  podInitialBackoffSeconds/podMaxBackoffSeconds). `flush_backoff()` moves
  expired entries back to active (upstream's flushBackoffQCompleted).
- `unschedulable`: pods that found no node and wait for a cluster event.
  `move_all_to_active_or_backoff(event)` relocates them (upstream
  MoveAllToActiveOrBackoffQueue on informer events), honoring the
  event→plugin queueing-hint table below.

Pods handed out by `pop_ready()` are tracked as in-flight until the cycle
requeues or drops them; a delete arriving mid-cycle marks the uid so the
requeue discards it instead of resurrecting a deleted pod. All public
methods take the queue lock — informer callbacks may run on other threads
than the scheduling loop (same discipline as SchedulerCache).

Time is injected (`now` callable) so tests drive the clock.

Durability contract (state/ package): every public mutator reads the
clock EXACTLY ONCE, applies its change through non-emitting internal
helpers, and emits EXACTLY ONE journal record carrying that clock value
— so replaying the record stream under a clock pinned to each record's
timestamp reproduces this queue bit-identically (attempt counts, backoff
expiries, tier membership, in-flight set). Internal helpers never emit
and never read the clock themselves. A list form (`add_many`,
`update_many`, `delete_many`: what an `Update` request's pod lists go
through) is its single-object mutator over a list, in the list's order:
one hold of the lock and one clock read for the list, the single's
record for each pod with that clock value, one step of the intake
observer for each (queue, event) by its count; the single is the list
form at length 1.
"""

from __future__ import annotations

import dataclasses
import threading
import time as _time
from typing import Callable, Iterable, Sequence

from ..models.api import Pod
from .cache import _codec as _shared_codec

# Cluster events (the reference's framework.ClusterEvent resource/action
# pairs, collapsed to the ones that matter for requeueing).
EVENT_NODE_ADD = "NodeAdd"
EVENT_NODE_UPDATE = "NodeUpdate"
EVENT_NODE_DELETE = "NodeDelete"
EVENT_POD_ADD = "PodAdd"
EVENT_POD_UPDATE = "PodUpdate"
EVENT_POD_DELETE = "PodDelete"
EVENT_PVC_CHANGE = "PvcChange"  # PVC add/update (e.g. became bound)
EVENT_PV_CHANGE = "PvChange"  # PV add/update (e.g. became available)
EVENT_STORAGE_CLASS_CHANGE = "StorageClassChange"
EVENT_UNSCHEDULABLE_TIMEOUT = "UnschedulableTimeout"

# Which failure reasons (plugin names) an event can unstick — the
# queueing-hint registry (upstream EventsToRegister). A pod rejected by
# plugin X only requeues on events in HINTS[X]. Unknown reasons requeue on
# everything (conservative default, matches hintless upstream behavior).
QUEUEING_HINTS: dict[str, frozenset[str]] = {
    "NodeResourcesFit": frozenset(
        {EVENT_NODE_ADD, EVENT_NODE_UPDATE, EVENT_POD_DELETE}
    ),
    "NodeAffinity": frozenset({EVENT_NODE_ADD, EVENT_NODE_UPDATE}),
    "NodeName": frozenset({EVENT_NODE_ADD, EVENT_NODE_UPDATE}),
    "NodeUnschedulable": frozenset({EVENT_NODE_ADD, EVENT_NODE_UPDATE}),
    "TaintToleration": frozenset({EVENT_NODE_ADD, EVENT_NODE_UPDATE}),
    "NodePorts": frozenset({EVENT_NODE_ADD, EVENT_POD_DELETE}),
    "InterPodAffinity": frozenset(
        {EVENT_NODE_ADD, EVENT_POD_ADD, EVENT_POD_UPDATE, EVENT_POD_DELETE}
    ),
    "PodTopologySpread": frozenset(
        {EVENT_NODE_ADD, EVENT_POD_ADD, EVENT_POD_UPDATE, EVENT_POD_DELETE}
    ),
    "Coscheduling": frozenset({EVENT_POD_ADD, EVENT_POD_DELETE,
                               EVENT_NODE_ADD, EVENT_NODE_UPDATE}),
    "VolumeBinding": frozenset({
        EVENT_NODE_ADD, EVENT_NODE_UPDATE, EVENT_PVC_CHANGE,
        EVENT_PV_CHANGE, EVENT_STORAGE_CLASS_CHANGE,
    }),
}


def _codec_pod():
    """The journal's pod serializer, via the ONE lazy codec binding
    shared with SchedulerCache (cache._codec): bound on first use so
    schedulers without durability never import state/, and journaling
    mutators skip per-call import machinery inside the queue lock."""
    return _shared_codec()[0]


@dataclasses.dataclass
class _QueuedPod:
    pod: Pod
    attempts: int = 0  # scheduling attempts so far (drives backoff length)
    backoff_expiry: float = 0.0
    # plugins that rejected it (() = unknown -> requeue on any event). A pod
    # requeues when the event can cure ANY of its reasons (upstream: the
    # union of the failed plugins' EventsToRegister hints).
    unschedulable_reasons: tuple[str, ...] = ()
    enqueued_at: float = 0.0
    # snapshot fragment: the state dict of `pod` as the entry's last
    # journal record carried it, until a compaction has serialised it,
    # and those bytes from then on (state/codec.pod_fragment). A field
    # of the entry, so it moves and goes with it; None with no journal
    # attached and after `load_state`. While it is the dict, the cycle
    # that binds the pod journals its `c.assume` from it
    # (`in_flight_states`) and makes no second one
    frag: dict | bytes | None = None


class SchedulingQueue:
    def __init__(
        self,
        initial_backoff_seconds: float = 1.0,
        max_backoff_seconds: float = 10.0,
        unschedulable_timeout_seconds: float = 300.0,
        now: Callable[[], float] = _time.monotonic,
        on_enqueue: Callable[[str, str, int], None] | None = None,
        journal: Callable[[str, float, dict], None] | None = None,
    ) -> None:
        self._initial = initial_backoff_seconds
        self._max = max_backoff_seconds
        self._timeout = unschedulable_timeout_seconds
        self._now = now
        # (queue_name, event, pods) observer for EVERY tier entry — feeds
        # the upstream scheduler_queue_incoming_pods_total metric; kept
        # in the queue so no transition undercounts. A list form steps
        # it once by the list's count
        self._on_enqueue = on_enqueue or (lambda queue, event, n=1: None)
        # (op, t, data) observer for the write-ahead journal (state/):
        # None = durability disabled. DurableState.attach wires it.
        self._journal = journal
        self._lock = threading.RLock()
        self._active: dict[str, _QueuedPod] = {}
        self._backoff: dict[str, _QueuedPod] = {}
        self._unschedulable: dict[str, _QueuedPod] = {}
        self._in_flight: dict[str, _QueuedPod] = {}
        self._deleted_in_flight: set[str] = set()
        # pending pods deleted since the process began: with the cache's
        # count, what core/collector sweeps by (not state)
        self.departed = 0

    def set_journal(
        self, journal: Callable[[str, float, dict], None] | None
    ) -> None:
        with self._lock:
            self._journal = journal

    def _emit(self, op: str, t: float, data: dict) -> None:
        if self._journal is not None:
            self._journal(op, t, data)

    # ---- intake ----------------------------------------------------------

    def add(self, pod: Pod) -> None:
        """New pod (informer Add): straight to active."""
        self.add_many((pod,))

    def add_many(self, pods: Iterable[Pod]) -> None:
        """`add` for a list of pods, in its order: one hold of the lock
        and one clock read for the list, a record a pod, and one step
        of the intake observer by the list's count."""
        with self._lock:
            now = self._now()
            n = 0
            for pod in pods:
                state = self._pod_state(pod)
                self._add_locked(pod, now, state)
                if state is not None:
                    self._emit("q.add", now, {"pod": state})
                n += 1
            if n:
                self._on_enqueue("active", EVENT_POD_ADD, n)

    def _pod_state(self, pod: Pod) -> dict | None:
        """The state dict for the record a mutator is about to journal,
        which the entry then keeps as its fragment: the fragment is the
        pod as last journaled for that entry, never a second
        `pod_to_state`. None with no journal attached."""
        if self._journal is None:
            return None
        return _codec_pod()(pod)

    def _add_locked(self, pod: Pod, now: float, state: dict | None) -> None:
        """The entry made and placed; its caller steps the intake
        observer (`active`, `PodAdd`)."""
        uid = pod.uid
        self._backoff.pop(uid, None)
        self._unschedulable.pop(uid, None)
        self._active[uid] = _QueuedPod(pod, enqueued_at=now, frag=state)

    def update(self, pod: Pod) -> None:
        """Spec/labels changed: an update can unstick its own pod."""
        self.update_many((pod,))

    def update_many(self, pods: Iterable[Pod]) -> None:
        """`update` for a list of pods, in its order: one hold of the
        lock and one clock read for the list, a record a pod, and one
        step of the intake observer for each (queue, event) by its
        count."""
        with self._lock:
            now = self._now()
            entered: dict[tuple[str, str], int] = {}
            for pod in pods:
                state = self._pod_state(pod)
                if state is not None:
                    self._emit("q.update", now, {"pod": state})
                into = self._update_locked(pod, now, state)
                if into is not None:
                    entered[into] = entered.get(into, 0) + 1
            for (queue, event), n in entered.items():
                self._on_enqueue(queue, event, n)

    def _update_locked(
        self, pod: Pod, now: float, state: dict | None
    ) -> tuple[str, str] | None:
        """One pod's update applied; the (queue, event) it entered a
        tier under, None where it stayed where it was."""
        uid = pod.uid
        for tier in (self._active, self._backoff, self._unschedulable):
            if uid in tier:
                entry = tier[uid]
                entry.pod = pod
                entry.frag = state
                if tier is not self._unschedulable:
                    return None
                # the update may cure the failure, but the pod's
                # backoff window still applies (upstream checks
                # isPodBackingOff here) — otherwise a controller
                # touching annotations defeats exponential backoff
                del tier[uid]
                if entry.backoff_expiry > now:
                    self._backoff[uid] = entry
                    return "backoff", EVENT_POD_UPDATE
                self._active[uid] = entry
                return "active", EVENT_POD_UPDATE
        if uid in self._in_flight:
            # being scheduled right now: refresh the in-flight object so
            # a requeue carries the new spec, but do NOT double-enqueue
            entry = self._in_flight[uid]
            entry.pod = pod
            entry.frag = state
            return None
        self._add_locked(pod, now, state)
        return "active", EVENT_POD_ADD

    def delete(self, pod_uid: str) -> None:
        self.delete_many((pod_uid,))

    def delete_many(self, pod_uids: Iterable[str]) -> None:
        """`delete` for a list of uids, in its order: one hold of the
        lock and one clock read for the list, a record for every uid
        that was queued or in flight."""
        with self._lock:
            now = self._now()
            tiers = (self._active, self._backoff, self._unschedulable)
            for pod_uid in pod_uids:
                changed = False
                for tier in tiers:
                    if tier.pop(pod_uid, None) is not None:
                        changed = True
                        self.departed += 1
                if pod_uid in self._in_flight:
                    # mark so the cycle's requeue discards instead of
                    # resurrecting a deleted pod
                    self._deleted_in_flight.add(pod_uid)
                    changed = True
                if changed:  # a no-op delete journals nothing (replay-exact)
                    self._emit("q.delete", now, {"uid": pod_uid})

    # ---- cycle boundary --------------------------------------------------

    def pop_ready(self, hold: bool = False) -> list[Pod]:
        """Drain the active tier — the whole next cycle's pending set.
        Flushes expired backoff first so a ready pod is never left behind.

        `hold=True` ACCUMULATES into the in-flight set instead of
        replacing it, and keeps the deleted-in-flight tombstones. No
        serving code passes it; a journal written by an older process
        may hold `q.pop {"hold": true}` records, and the replayer
        (state/manager.py) must reproduce the exact in-flight set a
        takeover recovers (ROADMAP D16)."""
        with self._lock:
            now = self._now()
            # journal only a pop that changes SOMETHING: drains pods,
            # flushes backoff, or retires a previous in-flight set — an
            # idle scheduler's empty cycles must not grow the journal
            had_inflight = not hold and (
                bool(self._in_flight) or bool(self._deleted_in_flight)
            )
            flushed = self._flush_backoff_locked(now, "BackoffComplete")
            ready = [e.pod for e in self._active.values()]
            for e in self._active.values():
                e.attempts += 1
            if hold:
                self._in_flight.update(self._active)
            else:
                self._in_flight = dict(self._active)
                self._deleted_in_flight.clear()
            self._active.clear()
            if ready or flushed or had_inflight:
                self._emit(
                    "q.pop", now, {"hold": True} if hold else {}
                )
            return ready

    def in_flight_states(
        self, pods: Sequence[Pod]
    ) -> list[dict | None] | None:
        """For each of a cycle's popped pods, the state dict its
        in-flight entry keeps, under one hold of the lock for the list:
        `pod_to_state(pod)` as the entry's last record carried it, so
        the cycle's bind loop need not make it a second time
        (`SchedulerCache.prepare_rows`). None for a pod whose entry is
        gone, holds another object (an `Update` refreshed it; the cycle
        binds the one it popped) or keeps no dict (restored, or met by
        a compaction, which left bytes); None for the list with no
        journal attached, where no entry keeps anything."""
        with self._lock:
            if self._journal is None:
                return None
            get = self._in_flight.get
            out = []
            for pod in pods:
                e = get(pod.uid)
                out.append(
                    e.frag
                    if e is not None and e.pod is pod
                    and type(e.frag) is dict else None
                )
            return out

    def retire_in_flight(self, uids: Sequence[str]) -> None:
        """Drop these pods (and their delete tombstones) from the
        in-flight set: their outcomes were applied.

        Serving retires implicitly — the next pop replaces the whole
        set — but hold pops only ever ACCUMULATE, so a journal that
        holds them also holds `q.retire` records, which the replayer
        (state/manager.py) applies through this method; it is its one
        caller (ROADMAP D16). Pods the failure paths already requeued
        are not in the set — the membership filter skips them."""
        with self._lock:
            live = [
                u for u in uids
                if u in self._in_flight or u in self._deleted_in_flight
            ]
            if not live:
                return
            now = self._now()
            self._emit("q.retire", now, {"uids": live})
            for u in live:
                self._in_flight.pop(u, None)
                self._deleted_in_flight.discard(u)

    def requeue_unschedulable(
        self, pod: Pod, reasons: Sequence[str] | str = ()
    ) -> None:
        """Cycle found no node (AddUnschedulableIfNotPresent). Goes to the
        unschedulable tier to wait for an event; backoff still advances so
        an event-triggered retry honors it. `reasons` names the rejecting
        plugins (drives the queueing-hint check on later events)."""
        if isinstance(reasons, str):
            reasons = (reasons,) if reasons else ()
        with self._lock:
            now = self._now()
            uid = pod.uid
            # journal BEFORE the deleted-in-flight check: the discard
            # branch mutates state too (clears the tombstone + in-flight
            # entry), and replay must take the same branch it took live
            state = self._pod_state(pod)
            if state is not None:
                self._emit(
                    "q.unsched", now,
                    {"pod": state, "reasons": list(reasons)},
                )
            if uid in self._deleted_in_flight:
                self._deleted_in_flight.discard(uid)
                self._in_flight.pop(uid, None)
                return
            self._active.pop(uid, None)
            self._backoff.pop(uid, None)
            entry = self._in_flight.pop(uid, None) or _QueuedPod(pod)
            entry.pod = pod
            entry.frag = state
            entry.unschedulable_reasons = tuple(reasons)
            entry.enqueued_at = now
            entry.backoff_expiry = now + self._backoff_for(entry.attempts)
            self._unschedulable[uid] = entry
            self._on_enqueue("unschedulable", "ScheduleAttemptFailure")

    def requeue_backoff(self, pod: Pod, event: str = "BindError") -> None:
        """Transient failure (e.g. bind error): retry after backoff."""
        with self._lock:
            now = self._now()
            uid = pod.uid
            # journal before the deleted-in-flight check (see
            # requeue_unschedulable: the discard branch mutates state)
            state = self._pod_state(pod)
            if state is not None:
                self._emit(
                    "q.backoff", now, {"pod": state, "event": event}
                )
            if uid in self._deleted_in_flight:
                self._deleted_in_flight.discard(uid)
                self._in_flight.pop(uid, None)
                return
            self._active.pop(uid, None)
            self._unschedulable.pop(uid, None)
            entry = self._in_flight.pop(uid, None) or _QueuedPod(pod)
            entry.pod = pod
            entry.frag = state
            entry.backoff_expiry = now + self._backoff_for(entry.attempts)
            self._backoff[uid] = entry
            self._on_enqueue("backoff", event)

    def _backoff_for(self, attempts: int) -> float:
        return min(self._initial * (2 ** max(attempts - 1, 0)), self._max)

    # ---- event-driven movement ------------------------------------------

    def flush_backoff(self) -> int:
        with self._lock:
            now = self._now()
            n = self._flush_backoff_locked(now, "BackoffComplete")
            if n:  # no-op flushes journal nothing
                self._emit("q.flush_backoff", now, {})
            return n

    def _flush_backoff_locked(self, now: float, event: str) -> int:
        expired = [
            u for u, e in self._backoff.items() if e.backoff_expiry <= now
        ]
        for u in expired:
            self._active[u] = self._backoff.pop(u)
            self._on_enqueue("active", event)
        return len(expired)

    def flush_unschedulable_timeout(self) -> int:
        """Upstream flushUnschedulablePodsLeftover: pods stuck too long
        retry even without an event."""
        with self._lock:
            now = self._now()
            stuck = [
                u for u, e in self._unschedulable.items()
                if now - e.enqueued_at >= self._timeout
            ]
            for u in stuck:
                self._move_out(u, EVENT_UNSCHEDULABLE_TIMEOUT, now)
            if stuck:  # no-op sweeps journal nothing
                self._emit("q.flush_timeout", now, {})
            return len(stuck)

    def move_all_to_active_or_backoff(self, event: str) -> int:
        """Informer event: move unschedulable pods whose failure the event
        can cure (queueing hints) to backoff (or active if expired)."""
        with self._lock:
            now = self._now()
            moved = 0
            for u in list(self._unschedulable):
                reasons = self._unschedulable[u].unschedulable_reasons
                if reasons and not any(
                    event in QUEUEING_HINTS.get(r, frozenset({event}))
                    for r in reasons
                ):
                    continue
                self._move_out(u, event, now)
                moved += 1
            if moved:
                # gated: this runs on EVERY informer event — journaling
                # the no-op moves would dominate the journal at scale
                self._emit("q.move", now, {"event": event})
            return moved

    def _move_out(self, uid: str, event: str, now: float) -> None:
        entry = self._unschedulable.pop(uid, None)
        if entry is None:
            return
        if entry.backoff_expiry > now:
            self._backoff[uid] = entry
            self._on_enqueue("backoff", event)
        else:
            self._active[uid] = entry
            self._on_enqueue("active", event)

    # ---- durability (state/ package) -------------------------------------

    def recover_in_flight(self) -> int:
        """Takeover recovery: requeue pods that were IN FLIGHT when the
        previous leader died — their cycle's outcome records never made
        it to the journal, so without this they would be silently
        dropped by the next pop_ready's in-flight reset. Attempts are
        preserved (the crashed attempt never concluded); a pod the
        informer re-added meanwhile keeps its fresher active entry.
        Journaled like any mutator, so a crash right after recovery
        replays it. The Scheduler calls this once after
        DurableState.attach; replay applies it via the q.recover op."""
        with self._lock:
            now = self._now()
            n = 0
            for uid, e in self._in_flight.items():
                if uid in self._deleted_in_flight:
                    continue
                if uid not in self._active:
                    e.enqueued_at = now
                    self._active[uid] = e
                    self._on_enqueue("active", "LeaderTakeover")
                    n += 1
            had = bool(self._in_flight) or bool(self._deleted_in_flight)
            self._in_flight = {}
            self._deleted_in_flight.clear()
            if had:
                self._emit("q.recover", now, {})
            return n

    def dump_state(self) -> dict:
        """Full durable state as JSON-able plain data (snapshot payload).
        Tier entry order is insertion order and is part of the contract —
        replay reproduces it, so digests compare order-sensitively."""
        from ..state.codec import pod_to_state

        def entry(e: _QueuedPod) -> dict:
            return {
                "pod": pod_to_state(e.pod),
                "attempts": e.attempts,
                "backoff_expiry": e.backoff_expiry,
                "reasons": list(e.unschedulable_reasons),
                "enqueued_at": e.enqueued_at,
            }

        with self._lock:
            return {
                "active": [entry(e) for e in self._active.values()],
                "backoff": [entry(e) for e in self._backoff.values()],
                "unschedulable": [
                    entry(e) for e in self._unschedulable.values()
                ],
                "in_flight": [entry(e) for e in self._in_flight.values()],
                "deleted_in_flight": sorted(self._deleted_in_flight),
            }

    def dump_state_json(self) -> tuple[bytes, int, int]:
        """`dump_state()` as the compact JSON a snapshot body holds,
        byte for byte what `json.dumps` makes of it, with the rows it
        holds and how many of them were serialised here: an entry met
        before is its kept fragment with its scalar fields (which
        change without a pod record) spliced after it."""
        from ..state.codec import json_bytes, pod_fragment

        with self._lock:
            encoded = 0
            tiers = []
            for tier in (
                self._active, self._backoff,
                self._unschedulable, self._in_flight,
            ):
                rows = []
                for e in tier.values():
                    frag = e.frag
                    if type(frag) is not bytes:
                        frag = e.frag = pod_fragment(frag, e.pod)
                        encoded += 1
                    rows.append(b'{"pod":%b,%b' % (frag, json_bytes({
                        "attempts": e.attempts,
                        "backoff_expiry": e.backoff_expiry,
                        "reasons": list(e.unschedulable_reasons),
                        "enqueued_at": e.enqueued_at,
                    })[1:]))
                tiers.append(b",".join(rows))
            body = (
                b'{"active":[%b],"backoff":[%b],"unschedulable":[%b],'
                b'"in_flight":[%b],"deleted_in_flight":%b}'
            ) % (*tiers, json_bytes(sorted(self._deleted_in_flight)))
            rows = (
                len(self._active) + len(self._backoff)
                + len(self._unschedulable) + len(self._in_flight)
            )
            return body, rows, encoded

    def load_state(self, state: dict) -> None:
        """Inverse of dump_state: replace this queue's contents. Expiry
        and enqueue timestamps are restored verbatim — they are
        CLOCK_MONOTONIC values valid on the host that wrote them (the
        same-host failover contract; see state/__init__)."""
        from ..state.codec import pod_from_state

        def entry(d: dict) -> _QueuedPod:
            return _QueuedPod(
                pod=pod_from_state(d["pod"]),
                attempts=int(d.get("attempts", 0)),
                backoff_expiry=float(d.get("backoff_expiry", 0.0)),
                unschedulable_reasons=tuple(d.get("reasons", ())),
                enqueued_at=float(d.get("enqueued_at", 0.0)),
            )

        with self._lock:
            for name, tier in (
                ("active", self._active),
                ("backoff", self._backoff),
                ("unschedulable", self._unschedulable),
                ("in_flight", self._in_flight),
            ):
                tier.clear()
                for d in state.get(name, ()):
                    e = entry(d)
                    tier[e.pod.uid] = e
            self._deleted_in_flight = set(state.get("deleted_in_flight", ()))

    # ---- introspection ---------------------------------------------------

    def attempts_of(self, uid: str) -> int:
        """Scheduling attempts the in-flight pod has used (1 = first try)."""
        with self._lock:
            e = self._in_flight.get(uid)
            return e.attempts if e else 1

    def pending_counts(self) -> dict[str, int]:
        """Tier sizes, keyed like the upstream pending_pods{queue=...}
        metric labels."""
        with self._lock:
            return {
                "active": len(self._active),
                "backoff": len(self._backoff),
                "unschedulable": len(self._unschedulable),
            }

    def all_pending(self) -> Iterable[Pod]:
        with self._lock:
            entries = [
                e.pod
                for tier in (self._active, self._backoff, self._unschedulable)
                for e in tier.values()
            ]
        return entries

    def __len__(self) -> int:
        with self._lock:
            return (
                len(self._active)
                + len(self._backoff)
                + len(self._unschedulable)
            )

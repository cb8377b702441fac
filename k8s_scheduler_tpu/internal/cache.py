"""SchedulerCache: node/pod stores with assume/confirm/forget lifecycle.

The reference's cache (`internal/cache/cache.go` — [UNVERIFIED], mount
empty; SURVEY.md §2 C4) keeps a per-node `NodeInfo` aggregate mutated by
informer events, plus "assumed" pods: optimistically placed by the
scheduling cycle before the API bind confirms, expiring on a TTL if the
confirmation never lands. This port keeps the same lifecycle but the
aggregation itself lives in the snapshot encoder (structure-of-arrays
tensors); the cache's job is to own the object lists the encoder consumes
and to answer "which pods count as existing on node X right now".

Lifecycle (mirrors upstream):
    assume(pod, node)      cycle picked a node; counts as existing at once
    finish_binding(pod)    bind RPC dispatched; TTL starts
    confirm(pod)           API bound event arrived; assumed -> bound
    forget(pod)            bind failed; drop the assumption
    cleanup_expired()      assumed-pod TTL sweep (upstream cleanupAssumedPods)

Time is injected for tests. Thread-safety: a single RLock around
mutations — the cycle runs single-threaded; informer callbacks may come
from elsewhere (re-entrant so the durable-state snapshot can hold it
across a consistent dump).

Durability contract (state/ package): same as SchedulingQueue — each
public mutator reads the clock once, applies, and emits one journal
record with that clock value, so replay under a pinned clock reproduces
assumed-pod TTL deadlines exactly. A list form (`add_pods`,
`remove_pods`, `confirm_pods`) is its single-object mutator over a
list: one hold of the lock and one clock read for the list, the
single's record for each pod, all with that clock value; the single
is the list form at length 1.
"""

from __future__ import annotations

import dataclasses
import threading
import time as _time
from typing import Callable, Iterable

from ..models.api import Node, Pod

# codec bindings for journal emission, bound on first use so schedulers
# without durability never import state/ — and journaling mutators skip
# per-call import machinery inside the cache lock
_pod_to_state = _node_to_state = _json_bytes = None


def _codec():
    global _pod_to_state, _node_to_state, _json_bytes
    if _pod_to_state is None:
        from ..state.codec import json_bytes, node_to_state, pod_to_state

        _pod_to_state = pod_to_state
        _node_to_state = node_to_state
        _json_bytes = json_bytes
    return _pod_to_state, _node_to_state, _json_bytes


def _row_open(pod_json: bytes, node_json: bytes) -> bytes:
    """How a pod's row of a snapshot opens, bound or assumed."""
    return b'{"pod":%b,"node":%b' % (pod_json, node_json)


@dataclasses.dataclass
class _AssumedPod:
    pod: Pod
    node_name: str
    binding_finished: bool = False
    deadline: float = 0.0


class SchedulerCache:
    def __init__(
        self,
        assumed_pod_ttl_seconds: float = 30.0,
        now: Callable[[], float] = _time.monotonic,
        journal: Callable[[str, float, dict], None] | None = None,
    ) -> None:
        self._ttl = assumed_pod_ttl_seconds
        self._now = now
        self._lock = threading.RLock()
        self._journal = journal
        self._nodes: dict[str, Node] = {}
        self._bound: dict[str, tuple[Pod, str]] = {}  # uid -> (pod, node)
        self._assumed: dict[str, _AssumedPod] = {}
        # uid -> snapshot fragment of a bound or assumed pod: the
        # opening of its row in a snapshot, `{"pod":<pod>,"node":<name>`,
        # serialised once, where the pod was journaled, from the state
        # dict that record carries (`_pod_state`). A bound row is that
        # and `}`; an assumed one closes with `finished` and `deadline`,
        # which move without a pod record; `confirm` moves the pod and
        # not its node, so the fragment stays as it is. Kept beside
        # `_bound` / `_assumed`, not inside: what `existing_pods()`
        # hands the encoder does not change. Goes when the row goes;
        # empty with no journal attached, with one that never compacts
        # (`set_journal`) and after `load_state`
        self._frags: dict[str, bytes] = {}
        # the node rows as the last compaction serialised them, until a
        # node event
        self._nodes_json: bytes | None = None
        self._frag_at_entry = True  # set_journal
        # pods and nodes dropped since the process began: what
        # core/collector sweeps by (not state: never journaled)
        self.departed = 0

    def set_journal(
        self,
        journal: Callable[[str, float, dict], None] | None,
        compacts: bool = True,
    ) -> None:
        """`compacts`: whether the journal's owner takes periodic
        snapshots. Where it never does (`snapshotInterval: 0`, journal
        only) no fragment is made where a pod is journaled: the one
        snapshot such a process writes, the seal at exit, serialises
        each row from its pod, as it does a restored row."""
        with self._lock:
            self._journal = journal
            self._frag_at_entry = compacts

    def _emit(self, op: str, data: dict) -> None:
        if self._journal is not None:
            self._journal(op, self._now(), data)

    def _emit_node(self, op: str, node: Node) -> None:
        if self._journal is not None:
            self._journal(
                op, self._now(), {"node": _codec()[1](node)}
            )

    def _pod_state(
        self, pod: Pod, node_name: str,
        row: tuple[dict, bytes | None] | None = None,
    ) -> dict | None:
        """The state dict for the record a mutator is about to journal;
        the row's fragment is made from it here, so it is the pod as
        last journaled and never a second `pod_to_state`. A pod that
        enters the cache stays for ~15 cycles and as many compactions as
        fall in them, so it is serialised where it enters (the queue's
        entries, most of which no compaction ever meets, wait for one:
        `_QueuedPod.frag`). With no journal attached there is no record,
        no dict and no fragment, with one that never compacts no
        fragment (and none stays from before). `row` is that dict and
        its bytes made ahead of the call (`prepare_rows`): the same
        record and the same fragment, with nothing serialised here."""
        if self._journal is None:
            state = None
        else:
            to_state, _, json_bytes = _codec()
            if row is not None:
                state, pod_json = row
            else:
                state, pod_json = to_state(pod), None
        if state is not None and self._frag_at_entry:
            self._frags[pod.uid] = _row_open(
                json_bytes(state) if pod_json is None else pod_json,
                json_bytes(node_name),
            )
        else:
            self._frags.pop(pod.uid, None)
        return state

    # ---- node events -----------------------------------------------------

    def add_node(self, node: Node) -> None:
        with self._lock:
            self._nodes[node.name] = node
            self._nodes_json = None
            self._emit_node("c.add_node", node)

    def update_node(self, node: Node) -> None:
        with self._lock:
            self._nodes[node.name] = node
            self._nodes_json = None
            self._emit_node("c.update_node", node)

    def remove_node(self, node_name: str) -> None:
        with self._lock:
            if self._nodes.pop(node_name, None) is not None:
                self.departed += 1
                self._nodes_json = None
                self._emit("c.remove_node", {"name": node_name})

    # ---- pod events (bound pods observed via informer) -------------------

    def add_pod(self, pod: Pod, node_name: str) -> None:
        """A bound pod appeared (or an assumed pod's bind was observed)."""
        self.add_pods(((pod, node_name),))

    def add_pods(self, pairs: Iterable[tuple[Pod, str]]) -> None:
        """`add_pod` for a list of (pod, node name), in its order: one
        hold of the lock and one clock read for the list, a record a
        pod."""
        with self._lock:
            now = self._now() if self._journal is not None else 0.0
            assumed, bound = self._assumed, self._bound
            for pod, node_name in pairs:
                assumed.pop(pod.uid, None)
                bound[pod.uid] = (pod, node_name)
                state = self._pod_state(pod, node_name)
                if state is not None:
                    self._journal(
                        "c.add_pod", now, {"pod": state, "node": node_name}
                    )

    def remove_pod(self, pod_uid: str) -> None:
        self.remove_pods((pod_uid,))

    def remove_pods(self, pod_uids: Iterable[str]) -> None:
        """`remove_pod` for a list of uids, in its order: one hold of
        the lock and one clock read for the list, a record for every
        pod that was held."""
        with self._lock:
            now = self._now() if self._journal is not None else 0.0
            assumed, bound, frags = self._assumed, self._bound, self._frags
            for pod_uid in pod_uids:
                b = bound.pop(pod_uid, None)
                a = assumed.pop(pod_uid, None)
                if b is not None or a is not None:
                    self.departed += 1
                    frags.pop(pod_uid, None)
                    if self._journal is not None:
                        self._journal("c.remove_pod", now, {"uid": pod_uid})

    # ---- assume lifecycle ------------------------------------------------

    def prepare_rows(
        self, states: list[dict | None] | None
    ) -> list[tuple[dict, bytes | None] | None] | None:
        """What `assume` would serialise for each pod of a cycle, made
        before the cycle's decisions land: for every state dict the
        queue's in-flight entries keep (`SchedulingQueue.in_flight_states`),
        the dict with the pod's half of its snapshot fragment, which
        does not depend on the node; the dict alone under a journal
        that never compacts (no fragment at entry). Touches no store and
        takes no lock. None with no journal attached."""
        if states is None or self._journal is None:
            return None
        if not self._frag_at_entry:
            return [None if s is None else (s, None) for s in states]
        json_bytes = _codec()[2]
        return [None if s is None else (s, json_bytes(s)) for s in states]

    def assume(
        self, pod: Pod, node_name: str,
        row: tuple[dict, bytes | None] | None = None,
    ) -> None:
        """`row`: this pod's entry of `prepare_rows`, where the caller
        holds one made from this very object as it stands; the record
        and the fragment are then made from it and not from the pod."""
        with self._lock:
            if pod.uid in self._bound:
                # raise WITHOUT emitting: a refused assume must not be
                # replayed (replay would refuse it again and abort)
                raise ValueError(f"pod {pod.name} already bound")
            self._assumed[pod.uid] = _AssumedPod(pod, node_name)
            state = self._pod_state(pod, node_name, row)
            if state is not None:
                self._emit("c.assume", {"pod": state, "node": node_name})

    def finish_binding(self, pod_uid: str) -> None:
        with self._lock:
            now = self._now()
            a = self._assumed.get(pod_uid)
            if a is not None:
                a.binding_finished = True
                a.deadline = now + self._ttl
                if self._journal is not None:
                    self._journal("c.finish_binding", now, {"uid": pod_uid})

    def confirm(
        self, pod_uid: str, node_name: str | None = None
    ) -> Pod | None:
        """Bind confirmed by the cluster store (add_pod also confirms):
        the assumed pod becomes bound, and is returned. Given
        `node_name`, only an assumption on that node is confirmed; with
        none held there (unknown uid, already bound, expired, another
        node) nothing changes and None is returned. The check and the
        move are one step under the lock, so no TTL sweep falls between
        them."""
        return self.confirm_pods(((pod_uid, node_name),))[0]

    def confirm_pods(
        self, confirms: Iterable[tuple[str, str | None]]
    ) -> list[Pod | None]:
        """`confirm` for a list of (uid, node name or None), in its
        order: what each returns, under one hold of the lock and one
        clock read for the list, a record for every pod confirmed."""
        out = []
        with self._lock:
            now = self._now() if self._journal is not None else 0.0
            assumed, bound = self._assumed, self._bound
            for pod_uid, node_name in confirms:
                a = assumed.get(pod_uid)
                if a is None or node_name not in (None, a.node_name):
                    out.append(None)
                    continue
                del assumed[pod_uid]
                # the same pod on the same node: its fragment stays
                bound[pod_uid] = (a.pod, a.node_name)
                if self._journal is not None:
                    self._journal("c.confirm", now, {"uid": pod_uid})
                out.append(a.pod)
        return out

    def forget(self, pod_uid: str) -> None:
        with self._lock:
            if self._assumed.pop(pod_uid, None) is not None:
                self.departed += 1
                self._frags.pop(pod_uid, None)
                self._emit("c.forget", {"uid": pod_uid})

    def is_assumed(self, pod_uid: str) -> bool:
        with self._lock:
            return pod_uid in self._assumed

    def has_pod(self, pod_uid: str) -> bool:
        """Known to the cluster state: bound or assumed."""
        with self._lock:
            return pod_uid in self._bound or pod_uid in self._assumed

    def cleanup_expired(self) -> list[tuple[Pod, str]]:
        """Drop assumed pods whose bind confirmation never arrived;
        returns (pod, node_name) pairs so the caller can requeue AND
        explain the expiry (events ring + pod timeline — upstream logs
        and drops; the informer re-delivers the pod as still-pending)."""
        with self._lock:
            now = self._now()
            gone = [
                u for u, a in self._assumed.items()
                if a.binding_finished and a.deadline <= now
            ]
            out = []
            for u in gone:
                a = self._assumed.pop(u)
                self._frags.pop(u, None)
                out.append((a.pod, a.node_name))
            self.departed += len(out)
            if out and self._journal is not None:
                # gated: this sweep runs every cycle — an idle scheduler
                # must not grow the journal with no-op records. Emits the
                # SAME `now` the sweep used (read-clock-once contract): a
                # second read could stamp a later t under which replay
                # would expire deadlines this sweep did not.
                self._journal("c.expire", now, {})
            return out

    # ---- durability (state/ package) -------------------------------------

    def dump_state(self) -> dict:
        from ..state.codec import node_to_state, pod_to_state

        with self._lock:
            return {
                "nodes": [
                    node_to_state(n) for n in self._nodes.values()
                ],
                "bound": [
                    {"pod": pod_to_state(p), "node": n}
                    for p, n in self._bound.values()
                ],
                "assumed": [
                    {
                        "pod": pod_to_state(a.pod),
                        "node": a.node_name,
                        "finished": a.binding_finished,
                        "deadline": a.deadline,
                    }
                    for a in self._assumed.values()
                ],
            }

    def dump_state_json(self) -> tuple[bytes, int, int]:
        """`dump_state()` as the compact JSON a snapshot body holds,
        byte for byte what `json.dumps` makes of it, with the pod rows
        it holds and how many of them were serialised here: those with
        no fragment (restored by `load_state`, or placed while no
        journal was attached), once. Every other bound row is joined as
        it stands, an assumed one closed with its `finished` and
        `deadline`; the node rows are serialised after a node event
        only."""
        from ..state.codec import json_bytes, node_to_state, pod_fragment

        with self._lock:
            frags = self._frags
            if self._nodes_json is None:
                self._nodes_json = json_bytes(
                    [node_to_state(n) for n in self._nodes.values()]
                )
            encoded = 0
            rows = list(map(frags.get, self._bound))
            if None in rows:
                for i, (uid, (pod, node)) in enumerate(self._bound.items()):
                    if rows[i] is None:
                        rows[i] = frags[uid] = _row_open(
                            pod_fragment(None, pod), json_bytes(node)
                        )
                        encoded += 1
            bound = b"},".join(rows) + b"}" if rows else b""
            assumed = []
            for uid, a in self._assumed.items():
                frag = frags.get(uid)
                if frag is None:
                    frag = frags[uid] = _row_open(
                        pod_fragment(None, a.pod), json_bytes(a.node_name)
                    )
                    encoded += 1
                assumed.append(frag + b"," + json_bytes({
                    "finished": a.binding_finished,
                    "deadline": a.deadline,
                })[1:])
            body = b'{"nodes":%b,"bound":[%b],"assumed":[%b]}' % (
                self._nodes_json, bound, b",".join(assumed)
            )
            return body, len(rows) + len(assumed), encoded

    def load_state(self, state: dict) -> None:
        from ..state.codec import node_from_state, pod_from_state

        with self._lock:
            self._nodes.clear()
            self._bound.clear()
            self._assumed.clear()
            self._frags.clear()
            self._nodes_json = None
            for d in state.get("nodes", ()):
                n = node_from_state(d)
                self._nodes[n.name] = n
            for d in state.get("bound", ()):
                p = pod_from_state(d["pod"])
                self._bound[p.uid] = (p, d["node"])
            for d in state.get("assumed", ()):
                p = pod_from_state(d["pod"])
                self._assumed[p.uid] = _AssumedPod(
                    pod=p,
                    node_name=d["node"],
                    binding_finished=bool(d.get("finished", False)),
                    deadline=float(d.get("deadline", 0.0)),
                )

    # ---- snapshot --------------------------------------------------------

    def nodes(self) -> list[Node]:
        with self._lock:
            return list(self._nodes.values())

    def existing_pods(self) -> list[tuple[Pod, str]]:
        """Bound + assumed pods — what the encoder treats as `existing`."""
        with self._lock:
            out = list(self._bound.values())
            out.extend((a.pod, a.node_name) for a in self._assumed.values())
            return out

    def counts(self) -> dict[str, int]:
        with self._lock:
            return {
                "nodes": len(self._nodes),
                "bound": len(self._bound),
                "assumed": len(self._assumed),
            }

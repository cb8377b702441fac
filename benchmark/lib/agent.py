"""The agent loop: what a cluster-side agent does with the scheduler,
timed from the client's side.

One loop, as `service/client.py` has it: complete resident pods down to
the configuration's `resident_target`, upsert every pod that is due,
`Cycle`, stamp each returned binding with the time the response arrived,
confirm the bindings and apply the evictions as `run_cycle` does, repeat.
Pods finish (the traffic file's `completions` block): before every cycle
the agent deletes `max(0, resident - resident_target)` bound pods, drawn
uniformly from those not on a probe-pool node by a generator of its own,
seeded from `--seed`, in the same batched `Update` as the upserts. So a
run's resident set is held at the size the configuration states, and does
not grow with what the program binds. The traffic file says when a pod is
due:

- `closed_depth`: before every cycle the server's pending set is topped
  up to `depth` (saturation; judged on pods bound per second);
- `open_rate`: pod i is due at t0 + (i // burst) * burst / rate, whatever
  the scheduler does (judged on due-to-bind latency).

A pod's latency runs from when it was DUE, so the wait behind a slow
cycle counts. The due-time schedule is `scripts/loadgen.py`'s.
"""

from __future__ import annotations

import dataclasses
import re
import time

import numpy as np

from k8s_scheduler_tpu.service.client import SchedulerAgent, SchedulerClient

from .child import BenchError
from .reference import Cycle

WARM_CYCLE_TIMEOUT_S = 1100.0
CYCLE_TIMEOUT_S = 120.0
# the completion draw's stream of `--seed`: never the deployment's, so
# the pods a seed offers do not move with what a run completes
COMPLETIONS_STREAM = 1
_DIAGNOSIS = re.compile(r"^0/(\d+) nodes are available: (.*)\.$")


class StrictAgent(SchedulerAgent):
    """An agent whose recovery path is a failure: `relist()` is what the
    stock agent does after an RPC error or a server restart, and a run
    that quietly replayed its state would be timed over both."""

    def relist(self) -> None:
        raise BenchError(
            "the agent fell into relist(): an RPC failed or timed out, "
            "or the server lost its state"
        )


@dataclasses.dataclass
class Span:
    """Client-side walls of one loop iteration, seconds."""

    t_start: float
    update_s: float  # upserts before the cycle
    cycle_s: float  # the Cycle RPC
    confirm_s: float  # binding confirmations after it
    offered: int
    bound: int


def parse_diagnosis(message: str) -> tuple[int, int]:
    """(nodes the diagnosis rejected, nodes it counted) of a
    FailedScheduling message; (0, 1) when it is not a node count (a gang
    or host-plugin refusal: not a claim that no node fits)."""
    m = _DIAGNOSIS.match(message)
    if not m:
        return 0, 1
    rejected = sum(
        int(part.split(" ", 1)[0])
        for part in m.group(2).split(", ") if part[:1].isdigit()
    )
    return rejected, int(m.group(1))


class Driver:
    """The agent-side truth: what was sent, what came back, when."""

    def __init__(self, port: int, dep, completions: dict | None = None,
                 seed: int = 0) -> None:
        """`completions` is the traffic file's block; without one the
        agent completes nothing and the resident set is all it bound."""
        self.dep = dep
        if completions and completions["order"] != "uniform":
            raise BenchError(
                f"completions in {completions['order']!r} order: this "
                "agent draws uniformly from the resident pods")
        self.resident_target = (
            int(dep.cfg["resident_target"]) if completions else None)
        self._draw = np.random.default_rng((seed, COMPLETIONS_STREAM))
        self._pool_nodes = {
            dep.nodes[i].name for pool in dep.pools for i in pool.nodes}
        self._held: set[str] = set()  # bound pods the server holds
        self._finishable: list[str] = []  # those not on a pool node
        self._slot: dict[str, int] = {}  # uid -> index in _finishable
        self.client = SchedulerClient(f"127.0.0.1:{port}")
        self.agent = StrictAgent(
            self.client, bind_applier=lambda *_: None,
            cycle_timeout=CYCLE_TIMEOUT_S,
        )
        self.pods: dict[str, object] = {}  # every pod ever offered
        self.pending: set[str] = set()
        self.bound_at: dict[str, float] = {}
        self.cycles: list[Cycle] = []
        self.spans: list[Span] = []
        self._probe_out: dict[int, str] = {}  # pool -> uid awaiting a bind
        self._probe_next = 0.0
        self.probe_rounds = 0  # rounds of one probe per pool that fell due

    # ---- set-up -----------------------------------------------------

    def load(self) -> None:
        with self.agent.batched():
            for n in self.dep.nodes:
                self.agent.upsert_node(n)
            for pod, node in self.dep.init:
                self.agent.upsert_pod(pod, bound_node=node)
                self._placed(pod.uid, node)

    def warm(self, pods) -> None:
        """One batch of `depth` pods: compiles or loads the regime and
        pins the P pad; then cycles until nothing more binds, so the
        window starts from a settled queue."""
        self.agent.cycle_timeout = WARM_CYCLE_TIMEOUT_S
        # a probe per pool rides along: its preferred term is part of
        # the regime, and must not arrive first inside the window
        self.step(pods, probes=True)
        for _ in range(8):
            if not self.pending or not self.step([], probes=False).bound:
                break
        self.agent.cycle_timeout = CYCLE_TIMEOUT_S

    # ---- the resident set -------------------------------------------

    @property
    def resident(self) -> int:
        """Bound pods the server holds, by what was sent to it."""
        return len(self._held)

    def _placed(self, uid: str, node: str) -> None:
        self._held.add(uid)
        if node not in self._pool_nodes:  # probes and their loads stay
            self._slot[uid] = len(self._finishable)
            self._finishable.append(uid)

    def _left(self, uid: str) -> None:
        """A resident pod is gone (completed or evicted): O(1), the last
        of the list takes its slot."""
        self._held.discard(uid)
        i = self._slot.pop(uid, None)
        if i is None:
            return
        last = self._finishable.pop()
        if last != uid:
            self._finishable[i] = last
            self._slot[last] = i

    def completions_due(self) -> list[str]:
        """The pods that finish before the next cycle: as many as the
        resident set stands over `resident_target`, drawn without
        replacement from the resident pods not on a pool node."""
        if self.resident_target is None:
            return []
        n = min(max(self.resident - self.resident_target, 0),
                len(self._finishable))
        picks = self._draw.choice(len(self._finishable), size=n,
                                  replace=False)
        done = [self._finishable[i] for i in picks]
        for uid in done:
            self._left(uid)
        return done

    # ---- one iteration ----------------------------------------------

    def _probes_due(self, now: float) -> list:
        """At most one probe per pool in flight, so a pool's choices are
        sequential by construction; a new round every `period_s`."""
        if now < self._probe_next:
            return []
        self._probe_next = now + self.dep.cfg["probe"]["period_s"]
        self.probe_rounds += 1
        out = []
        for pool in self.dep.pools:
            if self._probe_out.get(pool.index) in self.pending:
                continue
            pod = self.dep.probe(pool)
            self._probe_out[pool.index] = pod.uid
            out.append(pod)
        return out

    def step(self, due, probes: bool = True) -> Span:
        t0 = time.monotonic()
        if probes:
            due = list(due) + self._probes_due(t0)
        completed = self.completions_due()
        with self.agent.batched():
            for uid in completed:
                self.agent.delete_pod(uid)
            for pod in due:
                if pod.uid in self.pods:
                    raise BenchError(f"duplicate pod uid {pod.uid}")
                self.pods[pod.uid] = pod
                self.pending.add(pod.uid)
                self.agent.upsert_pod(pod)
        t1 = time.monotonic()
        offered = set(self.pending)
        resp = self.client.cycle(timeout=self.agent.cycle_timeout)
        t2 = time.monotonic()
        bindings = [(b.pod_uid, b.node_name) for b in resp.bindings]
        with self.agent.batched():
            for uid, node in bindings:
                self.bound_at[uid] = t2
                self.pending.discard(uid)
                if uid in self.pods:
                    self.agent.upsert_pod(self.pods[uid], bound_node=node)
                    self._placed(uid, node)
            for ev in resp.evictions:
                self.agent.delete_pod(ev.pod_uid)
                self._left(ev.pod_uid)
        t3 = time.monotonic()
        st = resp.stats
        if st.bind_errors or st.scheduled != len(bindings):
            raise BenchError(f"the cycle's own accounting is off: {st}")
        self.cycles.append(Cycle(
            offered=offered,
            completed=completed,
            bindings=bindings,
            evictions=[(ev.pod_uid, ev.node_name) for ev in resp.evictions],
            refused=[
                (ev.pod_uid, *parse_diagnosis(ev.message), ev.message)
                for ev in resp.events if ev.reason == "FailedScheduling"
            ],
        ))
        span = Span(t0, t1 - t0, t2 - t1, t3 - t2, len(offered),
                    len(bindings))
        self.spans.append(span)
        return span

    # ---- the window -------------------------------------------------

    def run_closed(self, pool_of_pods, depth: int, seconds: float) -> float:
        """Top the pending set up to `depth` before every cycle; returns
        the window's length (the last cycle is let finish)."""
        t0 = time.monotonic()
        nxt = 0
        while time.monotonic() - t0 < seconds:
            want = max(depth - len(self.pending) - len(self.dep.pools), 0)
            if nxt + want > len(pool_of_pods):
                raise BenchError(
                    f"the window used all {len(pool_of_pods)} pods built "
                    "for it: raise pods_budget_per_s in the traffic file"
                )
            span = self.step(pool_of_pods[nxt:nxt + want])
            nxt += want
            if not want and not span.bound:
                time.sleep(0.005)  # a full queue in backoff: do not spin
        return time.monotonic() - t0

    def run_open(self, pods, due_s, seconds: float, drain_s: float,
                 pad: int) -> tuple[float, float, list[float]]:
        """Offer pod i at t0 + due_s[i]; returns (t0, window length,
        how late each pod was sent). The server's pending set is never
        taken past `pad`, the warm-up batch that pinned the P pad: what
        is due beyond it waits at the agent (its latency still runs from
        when it was due), because one cycle over the pad compiles a new
        regime, arrivals pile up behind the compile, and the next cycle
        compiles a larger one still. After the window, cycle on without
        arrivals for at most `drain_s`, until every pod is bound."""
        t0 = time.monotonic()
        nxt, late = 0, []
        while True:
            now = time.monotonic() - t0
            if now >= seconds and nxt >= len(pods):
                break
            room = pad - len(self.pending) - len(self.dep.pools)
            end = nxt
            while (end < len(pods) and due_s[end] <= now
                   and end - nxt < room):
                end += 1
            if end == nxt and not self.pending:
                # nothing due and nothing queued: wait for the next pod
                target = due_s[nxt] if nxt < len(pods) else seconds
                time.sleep(min(max(target - now, 0.0), 0.05))
                continue
            late.extend(now - due_s[i] for i in range(nxt, end))
            span = self.step(pods[nxt:end])
            if not span.bound and end == nxt:
                time.sleep(0.005)  # a queue in backoff: do not spin
            nxt = end
        window = time.monotonic() - t0
        t_drain = time.monotonic()
        while self.pending and time.monotonic() - t_drain < drain_s:
            if not self.step([], probes=False).bound:
                time.sleep(0.05)
        return t0, window, late

    def close(self) -> None:
        self.client.close()

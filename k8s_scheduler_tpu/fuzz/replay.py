"""Trace replay: the live engine vs the trace-semantics oracle.

Both sides consume the SAME trace, drive the SAME host bookkeeping
classes (`SchedulingQueue`, `SchedulerCache` — deliberately shared: the
differential isolates the DECISION ENGINE, and the queue/cache are
plain Python already covered by the journal-replay exactness suite),
and advance the same fake clock. The only thing that differs is who
decides: the batched JAX programs behind `Scheduler.schedule_cycle`,
or `oracle.schedule_cycle_trace`.

Per cycle each side records (pending uids, binds, unschedulable+
reasons, nominations, evictions, gang drops, PDB overruns); after each
cycle the harness plays the informer back — bind confirmations
(`on_pod_add(pod, node)`) and eviction deletes (`on_pod_delete`) — and
ticks the clock past the max backoff, so requeued pods return
deterministically. `compare()` asserts the two streams bit-equal,
cycle by cycle.

Standing invariants checked engine-side every cycle (chaos traces,
where faults make the queues legitimately diverge from the oracle's,
keep these as their whole contract):

- no node capacity overcommit (every resource, bound+assumed);
- gang all-or-nothing (placed members + running members >= minMember);
- zero duplicate binds (a uid binds at most once while bound);
- zero lost accepted pods at end of trace (bound, or still in a tier);
- PDB respected (per-cycle eviction count within disruptionsAllowed;
  overruns — legal only as the kernel's documented last resort — are
  recorded per cycle and must MATCH the oracle's, which re-derives the
  last-resort choice independently).

Chaos traces additionally assert the PR 8 soak invariants: the
watchdog bounds every injected hang, the ladder recovers to rung 0 on
the recovery tail, and (when a state dir is given) the journal
restores to a digest-identical queue/cache.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time as _time

from .. import oracle
from ..internal.cache import SchedulerCache
from ..internal.queue import (
    EVENT_NODE_ADD,
    EVENT_NODE_DELETE,
    EVENT_NODE_UPDATE,
    EVENT_POD_ADD,
    EVENT_POD_DELETE,
    SchedulingQueue,
)
from ..models.api import Pod
from ..ops import preemption as preemption_ops
from .trace import (
    Trace,
    materialize,
    materialize_event,
)


@dataclasses.dataclass
class Failure:
    """One check that did not hold. `cls` is the failure CLASS the
    shrinker preserves (shrink-to-a-different-bug is a rejected
    reduction); `cycle` anchors truncation; `detail` is human-readable
    and carries the first diverging payloads."""

    cls: str
    cycle: int = -1
    detail: str = ""

    def __str__(self) -> str:
        at = f" at cycle {self.cycle}" if self.cycle >= 0 else ""
        return f"{self.cls}{at}: {self.detail}"


@dataclasses.dataclass
class ReplayResult:
    records: list  # per-cycle dicts
    failures: list  # list[Failure] (invariants; chaos checks)
    binds: list  # flattened [(uid, node), ...] in bind order
    stats: dict


def _require_scan_mode(cfgd: dict) -> None:
    """The differential is defined for the SCAN engine only: its
    decisions are exact vs the sequential oracle, and its reject
    attribution is at-turn (oracle.schedule_cycle_trace mirrors that).
    The rounds engine diverges by design (integer rounding, hash
    tie-break) and attributes against the final state — a rounds trace
    here would report phantom divergences, so refuse it loudly."""
    mode = cfgd.get("commit_mode", "scan")
    if mode != "scan":
        raise ValueError(
            f"fuzz replay requires commit_mode='scan', got {mode!r} "
            "(the rounds engine's legal divergences need the "
            "soak_differential-style validity/regret checks, not "
            "bit-equality)"
        )


class _Clock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> None:
        self.t += dt


def _alloc_tol(used: float, alloc: float) -> bool:
    return used > alloc * (1 + 1e-5) + 1e-5


def _capacity_violations(cache: SchedulerCache) -> list[str]:
    by_node: dict[str, dict[str, float]] = {}
    for pod, node in cache.existing_pods():
        agg = by_node.setdefault(node, {})
        for r, v in pod.resource_requests().items():
            agg[r] = agg.get(r, 0.0) + v
    out = []
    nodes = {n.name: n for n in cache.nodes()}
    for name, agg in by_node.items():
        nd = nodes.get(name)
        if nd is None:
            continue  # node deleted out from under its pods (churn)
        for r, v in agg.items():
            if _alloc_tol(v, nd.status.allocatable.get(r, 0.0)):
                out.append(
                    f"node {name}: {r} overcommitted "
                    f"({v} > {nd.status.allocatable.get(r, 0.0)})"
                )
    return out


def _pdb_overruns(pdbs, evicted_pods) -> list[int]:
    """Per-PDB count of this cycle's evictions beyond its budget."""
    out = []
    for pdb in pdbs:
        n = sum(
            1 for p in evicted_pods
            if p.namespace == pdb.namespace
            and oracle.match_label_selector(pdb.selector, p.metadata.labels)
        )
        out.append(max(0, n - pdb.disruptions_allowed))
    return out


def _gang_violations(groups, existing_before, binds, all_pods) -> list[str]:
    """All-or-nothing: any group that placed >=1 member this cycle must
    reach minMember counting members already running."""
    if not groups:
        return []
    running: dict[str, int] = {}
    for pod, _n in existing_before:
        if pod.spec.pod_group:
            running[pod.spec.pod_group] = running.get(pod.spec.pod_group, 0) + 1
    placed: dict[str, int] = {}
    for uid, _node in binds:
        g = all_pods[uid].spec.pod_group if uid in all_pods else ""
        if g:
            placed[g] = placed.get(g, 0) + 1
    out = []
    for g in groups:
        got = placed.get(g.name, 0)
        if got and got + running.get(g.name, 0) < g.min_member:
            out.append(
                f"gang {g.name}: {got} placed + "
                f"{running.get(g.name, 0)} running < minMember "
                f"{g.min_member}"
            )
    return out


# --------------------------------------------------------------------------
# engine side
# --------------------------------------------------------------------------


def replay_engine(
    trace: Trace, *, state_dir: str = "", via_api: bool = False
) -> ReplayResult:
    """Drive the trace through a LIVE Scheduler — the real dispatch
    path (split-phase pipeline, and sharded serving where the trace's
    config asks for it). Chaos traces arm the
    trace's FaultPlan for the duration.

    `via_api` (the ISSUE 14 `arrivals_via_api` variant) routes every
    pending-pod arrival through a REAL gRPC Submit round trip and
    every node add/update/delete through NodeChurn — localhost server,
    wire-format conversion, admission layer and all — instead of the
    direct informer calls. Deletions and bound-pod confirmations stay
    direct (they are informer traffic, not submissions), the admission
    depth bound is lifted (equality is the contract under test, not
    load shedding), and the harness still drives `schedule_cycle`
    itself so the frozen-clock cadence is identical: any stream
    difference vs the direct-enqueue engine is the API path's doing."""
    import jax as _jax

    from k8s_scheduler_tpu.config import SchedulerConfiguration
    from k8s_scheduler_tpu.core import Scheduler, faults

    cfgd = trace.config
    _require_scan_mode(cfgd)
    devices = int(cfgd.get("shard_devices", 0))
    if devices > 1 and len(_jax.devices()) < devices:
        raise RuntimeError(
            f"trace wants shardDevices={devices} but only "
            f"{len(_jax.devices())} devices are visible (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 before "
            "importing jax)"
        )
    cfg = SchedulerConfiguration(
        commit_mode=cfgd.get("commit_mode", "scan"),
        gang_scheduling=bool(cfgd.get("gang_scheduling", True)),
        shard_devices=devices,
        dispatch_deadline_ms=float(cfgd.get("dispatch_deadline_ms", 0.0)),
        degrade_promote_cycles=int(cfgd.get("degrade_promote_cycles", 2)),
        fault_spec=trace.fault_spec,
        speculative_compile=False,
        # the repo's executable cache keys on spec/profile/kind, NOT on
        # the traced HLO — a reused chaos state_dir could serve an
        # executable compiled across an engine_bug patch boundary.
        # "off" beats "": with a state dir, "" DERIVES a cache path.
        # Warmth still comes from jax's persistent compilation cache,
        # which keys on the HLO and is therefore mutation-safe.
        compile_cache_dir="off",
        state_dir=state_dir,
        snapshot_interval_seconds=0.0,
    )
    clock = _Clock()
    cycle_binds: list[tuple[Pod, str]] = []
    cycle_evicts: list[tuple[Pod, str]] = []
    state = None
    if state_dir:
        from k8s_scheduler_tpu.state import DurableState

        state = DurableState(state_dir, snapshot_interval_seconds=0)
    sched = Scheduler(
        config=cfg,
        binder=lambda pod, node: cycle_binds.append((pod, node)),
        evictor=lambda pod, node: cycle_evicts.append((pod, node)),
        now=clock,
        pad_bucket=int(cfgd.get("pad_bucket", 8)),
        state=state,
    )

    popped: list[list[str]] = []
    orig_pop = sched.queue.pop_ready

    def pop_capture(hold: bool = False):
        ready = orig_pop(hold)
        popped.append([p.uid for p in ready])
        return ready

    sched.queue.pop_ready = pop_capture
    unsched_log: list[tuple[str, tuple]] = []
    orig_unsched = sched.queue.requeue_unschedulable

    def unsched_capture(pod, reasons=()):
        r = (reasons,) if isinstance(reasons, str) else tuple(reasons)
        unsched_log.append((pod.uid, r))
        return orig_unsched(pod, reasons=reasons)

    sched.queue.requeue_unschedulable = unsched_capture
    backoff_log: list[tuple[str, str]] = []
    orig_backoff = sched.queue.requeue_backoff

    def backoff_capture(pod, event="BindError"):
        backoff_log.append((pod.uid, event))
        return orig_backoff(pod, event=event)

    sched.queue.requeue_backoff = backoff_capture

    api_server = None
    api_client = None
    if via_api:
        from concurrent import futures as _futures

        import grpc as _grpc

        from ..service.client import SchedulerClient
        from ..service.server import SchedulerService, add_to_server

        svc = SchedulerService(scheduler=sched)
        # the servicer ctor rebinds the binder to its Cycle-response
        # collector; the replay's capture binder must win (Cycle is
        # never called here — the harness drives schedule_cycle
        # directly so the frozen-clock cadence matches the direct run)
        sched.binder = lambda pod, node: cycle_binds.append((pod, node))
        svc.enable_front_door(queue_depth=0)
        api_server = _grpc.server(
            _futures.ThreadPoolExecutor(max_workers=2),
            options=(("grpc.so_reuseport", 0),),
        )
        add_to_server(svc, api_server)
        api_port = api_server.add_insecure_port("127.0.0.1:0")
        api_server.start()
        api_client = SchedulerClient(f"127.0.0.1:{api_port}")

    objs = materialize(trace)
    pdbs = objs["pdbs"]
    groups = objs["pod_groups"]
    for nd in objs["nodes"]:
        sched.on_node_add(nd)
    for g in groups:
        sched.add_pod_group(g)
    for c in objs["pvcs"]:
        sched.on_pvc_upsert(c)
    for v in objs["pvs"]:
        sched.on_pv_upsert(v)
    for s in objs["storage_classes"]:
        sched.on_storage_class_upsert(s)
    for p in pdbs:
        sched.on_pdb_upsert(p)

    records: list[dict] = []
    failures: list[Failure] = []
    all_binds: list[tuple[str, str]] = []
    all_pods: dict[str, Pod] = {}
    added: set[str] = set()
    deleted: set[str] = set()
    evicted: set[str] = set()
    bound_now: set[str] = set()
    walls: dict[int, float] = {}
    try:
        for ci, events in enumerate(trace.cycles):
            for raw in events:
                ev = materialize_event(raw)
                op = ev["op"]
                if op == "add_pod":
                    all_pods[ev["pod"].uid] = ev["pod"]
                    added.add(ev["pod"].uid)
                    if api_client is not None:
                        _api_submit(
                            api_client, ev["pod"], ci, failures, sched
                        )
                    else:
                        sched.on_pod_add(ev["pod"])
                elif op == "add_bound_pod":
                    all_pods[ev["pod"].uid] = ev["pod"]
                    added.add(ev["pod"].uid)
                    bound_now.add(ev["pod"].uid)
                    sched.on_pod_add(ev["pod"], ev["bind_node"])
                elif op == "delete_pod":
                    deleted.add(ev["uid"])
                    bound_now.discard(ev["uid"])
                    sched.on_pod_delete(ev["uid"])
                elif op == "add_node":
                    if api_client is not None:
                        api_client.node_churn(adds=[ev["node"]])
                    else:
                        sched.on_node_add(ev["node"])
                elif op == "update_node":
                    if api_client is not None:
                        api_client.node_churn(updates=[ev["node"]])
                    else:
                        sched.on_node_update(ev["node"])
                elif op == "delete_node":
                    if api_client is not None:
                        api_client.node_churn(deletes=[ev["name"]])
                    else:
                        sched.on_node_delete(ev["name"])
                else:
                    raise ValueError(f"unknown trace op {op!r}")
            existing_before = sched.cache.existing_pods()
            cycle_binds.clear()
            cycle_evicts.clear()
            unsched_log.clear()
            backoff_log.clear()
            n_pops_before = len(popped)
            t_wall = _time.perf_counter()
            sched.schedule_cycle()
            walls[ci + 1] = _time.perf_counter() - t_wall

            binds = [(p.uid, n) for p, n in cycle_binds]
            for uid, node in binds:
                if uid in bound_now:
                    failures.append(Failure(
                        "invariant/duplicate_bind", ci,
                        f"{uid} bound again (-> {node}) while bound",
                    ))
                bound_now.add(uid)
            evs = [(p.uid, n) for p, n in cycle_evicts]
            noms = [(p.uid, n) for p, n in sched.last_nominations]
            pend = [u for lst in popped[n_pops_before:] for u in lst]
            records.append({
                "cycle": ci,
                "pending": pend,
                "binds": binds,
                "unschedulable": list(unsched_log),
                "nominated": noms,
                "evicted": [u for u, _n in evs],
                "gang_dropped": sorted(
                    u for u, r in unsched_log if r == ("Coscheduling",)
                ),
                "pdb_overruns": _pdb_overruns(
                    pdbs, [p for p, _n in cycle_evicts]
                ),
                "requeues": list(backoff_log),
                "rung": sched.ladder.rung,
            })
            all_binds.extend(binds)
            for msg in _capacity_violations(sched.cache):
                failures.append(Failure("invariant/capacity", ci, msg))
            for msg in _gang_violations(
                groups, existing_before, binds, all_pods
            ):
                failures.append(Failure("invariant/gang", ci, msg))

            # informer playback: bind confirmations + eviction deletes
            for pod, node in cycle_binds:
                sched.on_pod_add(pod, node)
            for pod, _node in cycle_evicts:
                evicted.add(pod.uid)
                bound_now.discard(pod.uid)
                sched.on_pod_delete(pod.uid)
            clock.tick(trace.tick_s)

        # ---- end-of-trace accounting ----
        tracked = {p.uid for p in sched.queue.all_pending()}
        tracked |= {p.uid for p, _n in sched.cache.existing_pods()}
        lost = sorted(added - deleted - evicted - tracked)
        if lost:
            failures.append(Failure(
                "invariant/lost_pods", len(trace.cycles) - 1,
                f"accepted pods neither bound nor queued: {lost[:6]}",
            ))
        if trace.chaos:
            failures.extend(_chaos_checks(trace, sched, walls, state_dir))
        stats = {
            "bound": len(all_binds),
            "added": len(added),
            "degradations": sched.ladder.degradations,
            "final_rung": sched.ladder.rung,
            "fired_points": sorted(
                faults.plan().fired_points()
            ) if faults.plan() is not None else [],
        }
    finally:
        from k8s_scheduler_tpu.core import faults as _faults

        _faults.disarm()
        if api_client is not None:
            with contextlib.suppress(Exception):
                api_client.close()
        if api_server is not None:
            with contextlib.suppress(Exception):
                api_server.stop(grace=0)
        if state is not None:
            with contextlib.suppress(Exception):
                state.journal.flush()
            with contextlib.suppress(Exception):
                state.journal.close()
    return ReplayResult(records, failures, all_binds, stats)


def _api_submit(client, pod, cycle: int, failures: list, sched) -> None:
    """One Submit round trip; a rejection is recorded as a failure
    (the unbounded-depth front door must accept every generated
    arrival — anything else is an API-path bug the variant exists to
    catch) and the pod falls back to direct enqueue so the stream
    comparison still runs to completion."""
    import grpc as _grpc

    try:
        client.submit([pod])
    except _grpc.RpcError as e:
        failures.append(Failure(
            "via_api/rejected", cycle,
            f"Submit({pod.uid}) -> {e.code().name}: {e.details()}",
        ))
        # keep both engines' inputs identical despite the failure
        sched.on_pod_add(pod)


def _chaos_checks(trace, sched, walls, state_dir) -> list[Failure]:
    """The PR 8 soak invariants, asserted on a chaos replay: watchdog
    bound held, ladder recovered on the tail, digest-verified restore."""
    import re

    from k8s_scheduler_tpu.core import faults

    out: list[Failure] = []
    deadline_ms = float(trace.config.get("dispatch_deadline_ms", 0.0))
    plan = faults.plan()
    hang_fired = plan is not None and "fetch_hang" in plan.fired_points()
    for m in re.finditer(
        r"fetch_hang@cycle=(\d+)[^;]*?ms=([0-9.]+)", trace.fault_spec
    ):
        cyc, hang_ms = int(m.group(1)), float(m.group(2))
        if not (hang_fired and deadline_ms and hang_ms > 2 * deadline_ms):
            continue
        # two-part watchdog proof, robust to in-cycle compile cost (a
        # retrace recovery can legally spend seconds rebuilding programs
        # in the same host cycle): (a) the loop never slept the full
        # hang; (b) the ladder recorded a deadline-classified step —
        # the watchdog, not the hang expiring, ended the fetch
        wall = walls.get(cyc, 0.0) * 1e3
        if wall >= hang_ms:
            out.append(Failure(
                "chaos/watchdog", cyc,
                f"serve loop blocked {wall:.0f}ms >= the injected "
                f"{hang_ms:.0f}ms hang (deadline {deadline_ms:.0f}ms)",
            ))
        if not any(
            e["reason"].startswith("deadline")
            for e in sched.ladder.transitions
        ):
            out.append(Failure(
                "chaos/watchdog", cyc,
                "fetch_hang fired but no deadline-classified ladder "
                "step was recorded — the watchdog never expired the "
                "fetch",
            ))
    if sched.ladder.rung != sched.ladder.floor:
        out.append(Failure(
            "chaos/ladder", len(trace.cycles) - 1,
            f"ladder never recovered: rung {sched.ladder.rung} "
            f"(floor {sched.ladder.floor}) after the recovery tail",
        ))
    if state_dir:
        from k8s_scheduler_tpu.state import DurableState, state_digest

        with contextlib.suppress(Exception):
            sched.state.journal.flush()
        live = state_digest(sched.queue, sched.cache)
        q2 = SchedulingQueue()
        c2 = SchedulerCache()
        st2 = DurableState(state_dir, snapshot_interval_seconds=0)
        try:
            st2.restore_into(q2, c2)
            restored = state_digest(q2, c2)
        finally:
            with contextlib.suppress(Exception):
                st2.journal.close()
        if restored != live:
            out.append(Failure(
                "chaos/digest", len(trace.cycles) - 1,
                "journal restore digest != live queue/cache digest",
            ))
    return out


# --------------------------------------------------------------------------
# oracle side
# --------------------------------------------------------------------------


def replay_oracle(trace: Trace) -> ReplayResult:
    """Drive the trace through the sequential oracle under IDENTICAL
    host bookkeeping: same queue/cache classes, same informer playback,
    same clock ticks — so any stream difference is the decision
    engine's."""
    _require_scan_mode(trace.config)
    clock = _Clock()
    queue = SchedulingQueue(
        initial_backoff_seconds=1.0, max_backoff_seconds=10.0, now=clock
    )
    cache = SchedulerCache(now=clock)
    objs = materialize(trace)
    pdbs = objs["pdbs"]
    groups = objs["pod_groups"]
    pvcs = {c.key: c for c in objs["pvcs"]}
    pvs = {v.name: v for v in objs["pvs"]}
    classes = {s.name: s for s in objs["storage_classes"]}
    for nd in objs["nodes"]:
        cache.add_node(nd)
    gang = bool(trace.config.get("gang_scheduling", True))

    records: list[dict] = []
    failures: list[Failure] = []
    all_binds: list[tuple[str, str]] = []
    all_pods: dict[str, Pod] = {}
    added: set[str] = set()
    deleted: set[str] = set()
    evicted: set[str] = set()

    def informer_bound(pod: Pod, node: str) -> None:
        queue.delete(pod.uid)
        cache.add_pod(pod, node)
        queue.move_all_to_active_or_backoff(EVENT_POD_ADD)

    def informer_delete(uid: str) -> None:
        cache.remove_pod(uid)
        queue.delete(uid)
        queue.move_all_to_active_or_backoff(EVENT_POD_DELETE)

    for ci, events in enumerate(trace.cycles):
        for raw in events:
            ev = materialize_event(raw)
            op = ev["op"]
            if op == "add_pod":
                all_pods[ev["pod"].uid] = ev["pod"]
                added.add(ev["pod"].uid)
                queue.add(ev["pod"])
            elif op == "add_bound_pod":
                all_pods[ev["pod"].uid] = ev["pod"]
                added.add(ev["pod"].uid)
                informer_bound(ev["pod"], ev["bind_node"])
            elif op == "delete_pod":
                deleted.add(ev["uid"])
                informer_delete(ev["uid"])
            elif op == "add_node":
                cache.add_node(ev["node"])
                queue.move_all_to_active_or_backoff(EVENT_NODE_ADD)
            elif op == "update_node":
                cache.update_node(ev["node"])
                queue.move_all_to_active_or_backoff(EVENT_NODE_UPDATE)
            elif op == "delete_node":
                cache.remove_node(ev["name"])
                queue.move_all_to_active_or_backoff(EVENT_NODE_DELETE)
            else:
                raise ValueError(f"unknown trace op {op!r}")

        # the cycle, mirroring Scheduler.schedule_cycle's host order
        for pod, _node in cache.cleanup_expired():
            queue.requeue_backoff(pod, event="AssumeExpired")
        queue.flush_unschedulable_timeout()
        pending = queue.pop_ready()
        rec = {
            "cycle": ci, "pending": [p.uid for p in pending],
            "binds": [], "unschedulable": [], "nominated": [],
            "evicted": [], "gang_dropped": [], "pdb_overruns":
            [0] * len(pdbs), "requeues": [], "rung": 0,
        }
        cycle_binds: list[tuple[Pod, str]] = []
        cycle_evicts: list[Pod] = []
        if pending:
            nodes = cache.nodes()
            existing = cache.existing_pods()
            out = oracle.schedule_cycle_trace(
                nodes, pending, existing,
                pod_groups=groups, pvcs=list(pvcs.values()),
                pvs=list(pvs.values()),
                storage_classes=list(classes.values()),
                pdbs=pdbs, gang_scheduling=gang,
                budget=preemption_ops.DEFAULT_BUDGET,
                scan_budget=preemption_ops.DEFAULT_SCAN_BUDGET,
            )
            # winners bind in pending order (the engine's apply order)
            for i, pod in enumerate(pending):
                ni = out.decisions[i].node_index
                if ni < 0:
                    continue
                node = nodes[ni].name
                cache.assume(pod, node)
                cache.finish_binding(pod.uid)
                rec["binds"].append((pod.uid, node))
                cycle_binds.append((pod, node))
            nominated = {o.pod_index: o.node_index for o in out.preemptions}
            for i, pod in enumerate(pending):
                if out.decisions[i].node_index >= 0:
                    continue
                if i in nominated:
                    pod.nominated_node_name = nodes[nominated[i]].name
                    rec["nominated"].append(
                        (pod.uid, pod.nominated_node_name)
                    )
                reasons = out.reasons.get(i, ())
                rec["unschedulable"].append((pod.uid, tuple(reasons)))
                queue.requeue_unschedulable(pod, reasons=reasons)
            vict: set[int] = set()
            for o in out.preemptions:
                vict.update(o.victims)
            for e in sorted(vict):
                vpod = existing[e][0]
                rec["evicted"].append(vpod.uid)
                cycle_evicts.append(vpod)
            rec["gang_dropped"] = sorted(
                pending[i].uid for i in out.dropped
            )
            rec["pdb_overruns"] = _pdb_overruns(pdbs, cycle_evicts)
        records.append(rec)
        all_binds.extend(rec["binds"])
        for pod, node in cycle_binds:
            informer_bound(pod, node)
        for vpod in cycle_evicts:
            evicted.add(vpod.uid)
            informer_delete(vpod.uid)
        clock.tick(trace.tick_s)

    tracked = {p.uid for p in queue.all_pending()}
    tracked |= {p.uid for p, _n in cache.existing_pods()}
    lost = sorted(added - deleted - evicted - tracked)
    if lost:
        failures.append(Failure(
            "invariant/lost_pods", len(trace.cycles) - 1,
            f"oracle-side accepted pods neither bound nor queued: "
            f"{lost[:6]}",
        ))
    for msg in _capacity_violations(cache):
        failures.append(Failure(
            "invariant/capacity", len(trace.cycles) - 1,
            f"oracle-side {msg}",
        ))
    return ReplayResult(
        records, failures, all_binds, {"bound": len(all_binds)}
    )


# --------------------------------------------------------------------------
# comparison + the one-call driver
# --------------------------------------------------------------------------

_PER_CYCLE_KEYS = (
    "pending", "binds", "unschedulable", "nominated", "evicted",
    "gang_dropped", "pdb_overruns",
)


def compare(eng: ReplayResult, orc: ReplayResult) -> list[Failure]:
    """Bit-equality of the two decision streams, cycle by cycle (first
    diverging cycle + field named)."""
    out: list[Failure] = []
    for er, orr in zip(eng.records, orc.records):
        for key in _PER_CYCLE_KEYS:
            if er[key] != orr[key]:
                out.append(Failure(
                    f"divergence/{key}", er["cycle"],
                    f"engine={er[key]!r} oracle={orr[key]!r}",
                ))
        if out:
            return out
    return out


def compare_via_api(
    eng_api: ReplayResult, eng_direct: ReplayResult
) -> list[Failure]:
    """Per-cycle bit-equality of the arrivals-via-API engine against
    the direct-enqueue engine on the same trace. Both engines share
    the exact cadence (same trace, same clock), so even cycle
    placement must match: any difference is the Submit/NodeChurn path
    perturbing state — conversion loss, ordering drift, or admission
    side effects."""
    out: list[Failure] = []
    for er, orr in zip(eng_api.records, eng_direct.records):
        for key in _PER_CYCLE_KEYS + ("requeues", "rung"):
            if er[key] != orr[key]:
                out.append(Failure(
                    f"via_api/{key}", er["cycle"],
                    f"via-api={er[key]!r} direct={orr[key]!r}",
                ))
        if out:
            return out
    return out


def run_api_case(trace: Trace) -> list[Failure]:
    """The `arrivals_via_api` variant (ISSUE 14): replay the trace
    with every arrival through real Submit/NodeChurn RPCs, then again
    with direct enqueue, and require bit-equal streams. Engine bugs
    cancel out of an engine-vs-engine comparison — decision
    correctness stays the oracle differential's job; this variant
    hunts API-path bugs specifically."""
    eng_api = replay_engine(trace, via_api=True)
    failures = list(eng_api.failures)
    eng_direct = replay_engine(trace)
    failures.extend(eng_direct.failures)
    failures.extend(compare_via_api(eng_api, eng_direct))
    return failures


def _tenant_registry(trace: Trace):
    """One fresh TenantRegistry per replay side — fresh objects too
    (the engine mutates pods in place, same rule as materialize)."""
    from ..state.codec import node_from_state
    from ..tenancy import TenantRegistry

    reg = TenantRegistry()
    for tid, cfg in sorted(trace.config["tenancy"]["tenants"].items()):
        reg.create(
            tid, quota=int(cfg.get("quota", 0)),
            weight=float(cfg.get("weight", 1.0)),
        )
    for d in trace.nodes:
        n = node_from_state(d)
        reg.add_node(n.metadata.namespace, n)
    return reg


def run_tenant_case(
    trace: Trace, *, bug: "str | None" = None
) -> list[Failure]:
    """Replay one multi-tenant trace (generate_multitenant_trace)
    through the packed arena AND the per-tenant sequential reference,
    and require each tenant's decision stream bit-equal between the
    two — the isolation property: no tenant's placements may depend on
    which other tenants share its bucket. Also checks the decision
    streams never cross tenants (a decision's pod uid must carry its
    tenant's namespace). `bug="tenant_row_skew"` arms the arena's
    deliberate cross-tenant leak (rolling result rows within a bucket)
    for harness self-tests — the differential must CATCH it."""
    from ..state.codec import node_from_state, pod_from_state
    from ..tenancy import MultiTenantArena, TenantError

    kw = dict(
        commit_mode=trace.config.get("commit_mode", "scan"),
        gang_scheduling=bool(trace.config.get("gang_scheduling", True)),
    )
    regs = (_tenant_registry(trace), _tenant_registry(trace))
    packed = MultiTenantArena(regs[0], **kw)
    seq = MultiTenantArena(regs[1], sequential=True, **kw)
    if bug == "tenant_row_skew":
        packed.inject = "row_skew"
    elif bug is not None:
        raise ValueError(f"unknown tenant-case bug {bug!r}")

    failures: list[Failure] = []
    for ci, evs in enumerate(trace.cycles):
        for ev in evs:
            op = ev["op"]
            for reg in regs:
                # TenantError is a legal no-op during shrinking (the
                # event that created the target may have been dropped);
                # both sides raise identically, so skipping keeps them
                # in lockstep
                try:
                    if op == "add_pod":
                        reg.route(pod_from_state(ev["pod"]))
                    elif op == "delete_pod":
                        reg.remove_pod(ev["tenant"], ev["uid"])
                    elif op == "suspend_tenant":
                        reg.suspend(ev["tenant"])
                    elif op == "resume_tenant":
                        reg.resume(ev["tenant"])
                    elif op == "add_node":
                        n = node_from_state(ev["node"])
                        reg.add_node(n.metadata.namespace, n)
                    else:
                        raise ValueError(
                            f"unknown tenant-trace op {op!r}"
                        )
                except TenantError:
                    continue
        packed.run_cycle()
        seq.run_cycle()
        for tid, uid, _node in packed.last_decisions:
            if not uid.startswith(f"{tid}/"):
                failures.append(Failure(
                    "tenant/cross_leak", ci,
                    f"decision for tenant {tid!r} carries foreign pod "
                    f"{uid!r}",
                ))
        by_t: dict[str, list] = {}
        by_t_ref: dict[str, list] = {}
        for tid, uid, node in packed.last_decisions:
            by_t.setdefault(tid, []).append((uid, node))
        for tid, uid, node in seq.last_decisions:
            by_t_ref.setdefault(tid, []).append((uid, node))
        if by_t != by_t_ref:
            tid = next(
                t for t in sorted(set(by_t) | set(by_t_ref))
                if by_t.get(t) != by_t_ref.get(t)
            )
            failures.append(Failure(
                "tenant/decision_divergence", ci,
                f"tenant {tid!r} packed {by_t.get(tid)} != sequential "
                f"{by_t_ref.get(tid)}",
            ))
            break  # registries diverged; later cycles are noise
    return failures


def run_case(
    trace: Trace, *, state_dir: str = "", bug: "str | None" = None
) -> list[Failure]:
    """Replay one trace end to end and return every failure: engine
    invariants (+ chaos checks), oracle invariants, and — for plain
    traces — the differential divergences. `bug` injects a deliberate
    engine mutation (see `engine_bug`) for harness self-tests.

    Multi-tenant traces (config["tenancy"]) route to the arena-vs-
    sequential differential instead (run_tenant_case) — same plain-data
    trace format, same shrinker, same corpus, different oracle."""
    if trace.config.get("tenancy"):
        return run_tenant_case(trace, bug=bug)
    with engine_bug(bug):
        eng = replay_engine(trace, state_dir=state_dir)
    failures = list(eng.failures)
    if trace.chaos:
        return failures
    orc = replay_oracle(trace)
    failures.extend(orc.failures)
    failures.extend(compare(eng, orc))
    return failures


@contextlib.contextmanager
def engine_bug(name: "str | None"):
    """Deliberately break the ENGINE (never the oracle) for harness
    self-tests: the fuzzer must CATCH a seeded bug, and the shrinker
    tests reduce a trace that fails under it.

    - `tiebreak`: mutate the shard-invariant claim-path tie-break
      (ops/argsel.argmax_first) from first-max to LAST-max — the exact
      class of silent wrongness PR 9 eliminated; every equal-score
      placement flips, the kind of bug only a differential oracle sees.

    Program memos are per-Scheduler; jax's persistent compilation
    cache keys on the traced HLO (mutation-safe); and replay_engine
    pins the repo's spec-keyed executable cache OFF (it does NOT key
    on HLO, so it could otherwise serve a stale executable across the
    patch boundary). Callers must not reuse a Scheduler across the
    boundary — run_case never does.
    """
    if name is None:
        yield
        return
    if name != "tiebreak":
        raise ValueError(f"unknown engine bug {name!r}")
    import jax
    import jax.numpy as jnp

    from ..ops import argsel

    orig = argsel.argmax_first

    def argmax_last(x, axis: int = -1):
        ax = axis if axis >= 0 else x.ndim + axis
        m = jnp.max(x, axis=ax, keepdims=True)
        idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, ax)
        return jnp.max(jnp.where(x == m, idx, jnp.int32(-1)), axis=ax)

    argsel.argmax_first = argmax_last
    try:
        yield
    finally:
        argsel.argmax_first = orig

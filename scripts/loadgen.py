#!/usr/bin/env python
"""Open-loop load generator for the submission front door (ISSUE 14).

Arrival-rate-driven, never closed-loop: submission i is DUE at
t0 + i/rate regardless of how fast acks or binds come back, so an
overloaded scheduler actually overloads (and must shed) instead of
silently throttling the generator. Rates are pods/minute to match the
10k-1M pods/min ROADMAP target.

Two modes:

- **inproc** (default) — spins the whole front door in this process on
  `front_door_drive` below (the same harness the soak_chaos overload
  phase uses): exact per-pod submit->bind latency from the binder's
  own timestamps, one JSON object out.

      JAX_PLATFORMS=cpu python scripts/loadgen.py --rate 30000 --duration 10

- **grpc** — drives a LIVE scheduler's Submit RPC (started with
  `python -m k8s_scheduler_tpu --submit-addr ...`): client-side ack
  latency + shed accounting, optional `--acked-log` journal of every
  acked uid (fsynced per batch) so a kill -9 failover harness can
  assert zero lost acked pods against the restored state. Server-side
  submit->bind quantiles ride the `submit_bind` phase gauges on
  /metrics and /debug/anomalies.

      python scripts/loadgen.py --mode grpc --addr 127.0.0.1:50052 \\
          --rate 60000 --duration 30 --nodes 16 --acked-log /tmp/acked
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _pctl(xs: list[float], q: float) -> float:
    ys = sorted(xs)
    if not ys:
        return 0.0
    k = min(len(ys) - 1, max(0, int(round(q / 100.0 * (len(ys) - 1)))))
    return ys[k]


def front_door_drive(
    duration_s: float,
    rate_pps: float,
    queue_depth: int = 0,
    n_nodes: int = 16,
    batch: int = 4,
    state_dir: str = "",
    fault_spec: str = "",
    deadline_ms: float = 0.0,
    drain_timeout_s: float = 60.0,
    promote_cycles: int = 4,
    name_prefix: str = "ld",
    release_after_bind: bool = True,
    on_tick=None,
) -> dict:
    """The shared open-loop front-door harness (ISSUE 14): one real
    Scheduler behind an AdmissionController + FrontDoor serve loop; the
    caller's thread plays the open-loop client — submissions fire at
    wall-clock arrival times derived from `rate_pps` REGARDLESS of how
    fast binds complete (arrival-rate-driven, never closed-loop), so
    overload actually overloads instead of self-throttling. Used by
    this tool's in-process mode and scripts/soak_chaos.py's overload
    phase, so the load tool and the soak can never assert different
    invariants of the same front door.

    Returns raw facts: `sched`/`admission` (live handles), `acked`
    (uid -> submit wall time), `binds` (uid -> (count, bind wall
    time)), `ack_lat_s`, `shed`/`accepted` counts, `max_depth` (the
    deepest queue_depth any ack/shed reported), `duplicate_binds`,
    `lost` (acked pods that neither bound nor remain tracked),
    `drained`. Leaves any fault plan ARMED (caller disarms), exactly
    like soak_chaos.chaos_serve_drive."""
    from k8s_scheduler_tpu.config import SchedulerConfiguration
    from k8s_scheduler_tpu.core.scheduler import Scheduler
    from k8s_scheduler_tpu.service.admission import (
        AdmissionController,
        FrontDoor,
    )
    from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

    state = None
    if state_dir:
        from k8s_scheduler_tpu.state import DurableState

        state = DurableState(state_dir, snapshot_interval_seconds=0)
    cfg_obj = SchedulerConfiguration(
        admission_queue_depth=queue_depth,
        dispatch_deadline_ms=deadline_ms,
        degrade_promote_cycles=promote_cycles,
        fault_spec=fault_spec,
        pod_initial_backoff_seconds=0.05,
        pod_max_backoff_seconds=0.2,
        # pre-sized pads: regime flips mid-drive would bill compile
        # time to submit->bind latency
        pad_existing=2048,
        pad_pods_per_node=512,
        compile_cache_dir="off",
        speculative_compile=False,
    )
    binds: dict[str, tuple[int, float]] = {}
    confirm_q: "collections.deque" = collections.deque()

    def binder(p, n):
        c, t = binds.get(p.uid, (0, 0.0))
        binds[p.uid] = (c + 1, time.perf_counter())
        confirm_q.append((p, n))

    sched = Scheduler(config=cfg_obj, binder=binder, state=state)
    admission = AdmissionController(sched)
    for nd in make_cluster(n_nodes):
        admission.node_churn(adds=[nd])

    def confirm_binds():
        # informer playback on the loop thread (a real deployment's
        # agent confirms via Update): without it an assumed pod
        # expires on the TTL and re-binds, which the duplicate-bind
        # invariant would — correctly — flag. With
        # `release_after_bind` the confirmed pod is then deleted (a
        # fast-jobs workload): node capacity recycles, so the drive
        # measures SERVING throughput instead of filling n_nodes and
        # stalling on cluster capacity
        while confirm_q:
            p, n = confirm_q.popleft()
            sched.on_pod_add(p, n)
            if release_after_bind:
                sched.on_pod_delete(p.uid)

    fd = FrontDoor(admission, post_cycle=confirm_binds)
    fd.start()
    acked: dict[str, float] = {}
    ack_lat: list[float] = []
    shed = 0
    max_depth = 0
    seq = 0
    t_start = time.perf_counter()
    t0 = t_start  # reassigned when the open-loop window opens
    try:
        # warmup OUTSIDE the timed window: the first dispatch compiles
        warm = make_pods(batch, seed=999, name_prefix=f"{name_prefix}w-")
        r = admission.submit(warm)
        assert r.ok, f"warmup submission rejected: {r.reason}"
        # warmup pods are NOT recorded in `acked`: their bind time
        # embeds the first-dispatch compile, and joining them into the
        # submit->bind latencies would make the gated p99 report
        # compile noise instead of the steady-state SLO (they are
        # asserted fully bound right here, so the lost/dup accounting
        # does not need them)
        while len(binds) < len(warm):
            if time.perf_counter() - t_start > 120:
                raise AssertionError("warmup never bound (compile hang?)")
            time.sleep(0.01)

        # the open-loop window: arrival i is DUE at t0 + i/rate; send
        # every batch that is due, sleep only until the next arrival
        t0 = time.perf_counter()
        interval = batch / rate_pps
        n_batches = max(int(duration_s / interval), 1)
        for i in range(n_batches):
            due = t0 + i * interval
            now = time.perf_counter()
            if now < due:
                time.sleep(due - now)
            seq += 1
            pods = make_pods(
                batch, seed=10_000 + seq,
                name_prefix=f"{name_prefix}{seq}-",
            )
            t_sub = time.perf_counter()
            res = admission.submit(pods)
            if res.queue_depth > max_depth:
                max_depth = res.queue_depth
            if res.ok:
                ack_lat.append(time.perf_counter() - t_sub)
                for p in pods:
                    acked[p.uid] = t_sub
            else:
                shed += res.shed
            if on_tick is not None:
                # mid-burst probe hook: soak_chaos's overload phase
                # evaluates the real /healthz closure in here
                on_tick(sched, admission, res)
        # drain: every acked pod resolves (bound, or parked in a tier),
        # and — when a fault plan degraded the ladder — rung 0 returns.
        # While the ladder sits below rung 0 a probe trickle keeps
        # flowing (promotion counts clean DISPATCHING cycles: a silent
        # queue earns no recovery evidence; this is the recovery-tail
        # role the fuzz chaos traces generate explicitly)
        deadline = time.perf_counter() + drain_timeout_s
        while (
            (any(u not in binds for u in acked) or sched.ladder.rung > 0)
            and time.perf_counter() < deadline
        ):
            if sched.ladder.rung > 0:
                seq += 1
                probe = make_pods(
                    1, seed=90_000 + seq,
                    name_prefix=f"{name_prefix}rt{seq}-",
                )
                r = admission.submit(probe)
                if r.ok:
                    acked[probe[0].uid] = time.perf_counter()
            time.sleep(0.05)
    finally:
        drained = fd.stop()
    tracked = {p.uid for p in sched.queue.all_pending()}
    bind_ts = [t for _c, t in binds.values() if t >= t0]
    return {
        "sched": sched,
        "admission": admission,
        "state": state,
        "acked": acked,
        "binds": binds,
        "ack_lat_s": ack_lat,
        "accepted": len(acked),
        "shed": shed,
        "max_depth": max_depth,
        "wall_s": time.perf_counter() - t_start,
        # serving rate over the open-loop window (warmup excluded):
        # binds landed after t0, divided by the window they landed in —
        # the capacity estimate soak_chaos's overload phase calibrates on
        "bind_rate_pps": (
            len(bind_ts) / max(max(bind_ts) - t0, 1e-6)
            if bind_ts else 0.0
        ),
        "duplicate_binds": sum(
            1 for c, _t in binds.values() if c > 1
        ),
        "lost": sorted(set(acked) - set(binds) - tracked),
        "drained": drained,
        "cycles": fd.cycles,
    }


def run_inproc(args) -> dict:
    rate_pps = args.rate / 60.0
    d = front_door_drive(
        duration_s=args.duration,
        rate_pps=rate_pps,
        queue_depth=args.queue_depth,
        n_nodes=args.nodes,
        batch=args.batch,
        state_dir=args.state_dir,
        name_prefix="lg",
    )
    bind_ms = sorted(
        (t - d["acked"][u]) * 1e3
        for u, (_c, t) in d["binds"].items()
        if u in d["acked"]
    )
    ack_ms = [v * 1e3 for v in d["ack_lat_s"]]
    total = d["accepted"] + d["shed"]
    out = {
        "name": "front_door",
        "mode": "inproc",
        "rate_pods_per_min": args.rate,
        "duration_s": args.duration,
        "accepted": d["accepted"],
        "shed": d["shed"],
        "shed_rate": round(d["shed"] / max(total, 1), 4),
        "scheduled": len(d["binds"]),
        "duplicate_binds": d["duplicate_binds"],
        "lost": d["lost"],
        "max_queue_depth": d["max_depth"],
        "bind_rate_pps": round(d["bind_rate_pps"], 1),
        "submit_ack_p50_ms": round(_pctl(ack_ms, 50), 3),
        "submit_ack_p99_ms": round(_pctl(ack_ms, 99), 3),
        "submit_bind_p50_ms": round(_pctl(bind_ms, 50), 3),
        "submit_bind_p99_ms": round(_pctl(bind_ms, 99), 3),
        "drained": d["drained"],
        "durable": bool(args.state_dir),
    }
    if d["state"] is not None:
        d["state"].seal()
    return out


def _tenant_picker(ids: list, dist: str, seed: int):
    """Per-batch tenant selection: `roundrobin` exercises every virtual
    cluster evenly (the packing/fairness smoke), `zipf` concentrates
    load on a few hot tenants (rank-weighted 1/r) — the shape that
    actually trips per-tenant quota and weighted-fair sheds."""
    if dist == "roundrobin":
        import itertools

        it = itertools.cycle(ids)
        return lambda: next(it)
    import random

    rng = random.Random(seed)
    weights = [1.0 / (r + 1) for r in range(len(ids))]
    return lambda: rng.choices(ids, weights)[0]


def run_tenants(args) -> dict:
    """Multi-tenant in-proc mode (--tenants N): the open-loop generator
    in front of TenantFrontHost + AdmissionController + the arena
    packer. A batch carries ONE tenant (its pods' namespace); the serve
    side runs an arena cycle between arrivals, so the output reports
    both admission outcomes (quota/fair sheds per tenant) and packing
    efficiency (dispatches vs tenants folded, builds after warmup)."""
    from k8s_scheduler_tpu.service.admission import AdmissionController
    from k8s_scheduler_tpu.tenancy import TenantFrontHost, TenantRegistry
    from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

    ids = [f"vc-{i:03d}" for i in range(args.tenants)]
    reg = TenantRegistry()
    host = TenantFrontHost(reg)
    for tid in ids:
        reg.create(tid, quota=args.tenant_quota)
        # same seed per tenant on purpose: identical node shapes keep
        # the fleet in one spec bucket (the headline packing regime)
        for nd in make_cluster(args.nodes_per_tenant, seed=7):
            nd.metadata.namespace = tid
            nd.metadata.uid = f"{tid}/{nd.metadata.name}"
            host.on_node_add(nd)
    adm = AdmissionController(
        host, queue_depth=args.queue_depth or None, tenants=reg,
    )
    pick = _tenant_picker(ids, args.tenant_dist, args.seed)

    rate_pps = args.rate / 60.0
    interval = args.batch / rate_pps
    n_batches = max(int(args.duration / interval), 1)
    ack_ms: list[float] = []
    accepted = shed = invalid = 0
    shed_by: dict[str, int] = {}
    t0 = time.perf_counter()
    for i in range(n_batches):
        due = t0 + i * interval
        now = time.perf_counter()
        if now < due:
            time.sleep(due - now)
        tid = pick()
        pods = make_pods(
            args.batch, seed=args.seed + i,
            name_prefix=f"{args.prefix}{i}-",
        )
        for p in pods:
            p.metadata.namespace = tid
            p.metadata.uid = f"{tid}/{p.metadata.name}"
        t_sub = time.perf_counter()
        res = adm.submit(pods)
        ack_ms.append((time.perf_counter() - t_sub) * 1e3)
        accepted += res.accepted
        shed += res.shed
        invalid += len(res.invalid)
        if res.shed:
            shed_by[tid] = shed_by.get(tid, 0) + res.shed
        host.schedule_cycle()
    # drain: standing demand left by the open-loop window (stop once a
    # cycle binds nothing — what remains is capacity-starved, not queued)
    for _ in range(64):
        if host.schedule_cycle().bound == 0:
            break
    st = reg.status()
    arena = host.arena
    total = accepted + shed
    return {
        "name": "tenant_front_door",
        "mode": "inproc",
        "tenants": args.tenants,
        "tenant_dist": args.tenant_dist,
        "rate_pods_per_min": args.rate,
        "duration_s": args.duration,
        "accepted": accepted,
        "shed": shed,
        "invalid": invalid,
        "shed_rate": round(shed / max(total, 1), 4),
        "shed_tenants": len(shed_by),
        "bound": st["bound"],
        "pending": st["pending"],
        "arena_dispatches": arena.packer.dispatches,
        "arena_builds": arena.packer.builds,
        "tenants_packed": arena.packer.tenants_packed,
        "tenants_per_dispatch": round(
            arena.packer.tenants_packed
            / max(arena.packer.dispatches, 1), 2,
        ),
        "submit_ack_p50_ms": round(_pctl(ack_ms, 50), 3),
        "submit_ack_p99_ms": round(_pctl(ack_ms, 99), 3),
    }


def run_grpc(args) -> dict:
    import grpc

    from k8s_scheduler_tpu.service.client import SchedulerClient
    from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

    client = SchedulerClient(args.addr)
    if args.nodes:
        client.node_churn(adds=make_cluster(args.nodes))
    log_f = open(args.acked_log, "a") if args.acked_log else None
    rate_pps = args.rate / 60.0
    interval = args.batch / rate_pps
    n_batches = max(int(args.duration / interval), 1)
    ack_ms: list[float] = []
    accepted = shed = 0
    retry_after: list[float] = []
    draining = False
    t0 = time.perf_counter()
    for i in range(n_batches):
        due = t0 + i * interval
        now = time.perf_counter()
        if now < due:
            time.sleep(due - now)
        pods = make_pods(
            args.batch, seed=args.seed + i,
            name_prefix=f"{args.prefix}{i}-",
        )
        t_sub = time.perf_counter()
        try:
            resp = client.submit(pods)
        except grpc.RpcError as e:
            if e.code() == grpc.StatusCode.RESOURCE_EXHAUSTED:
                shed += len(pods)
                for k, v in e.trailing_metadata() or ():
                    if k == "retry-after-ms":
                        retry_after.append(float(v))
                continue
            if e.code() == grpc.StatusCode.UNAVAILABLE:
                # server draining (shutdown) or killed mid-load: an
                # open-loop generator records it and stops — the acks
                # already on disk are the failover contract
                draining = True
                break
            raise
        ack_ms.append((time.perf_counter() - t_sub) * 1e3)
        accepted += resp.accepted
        if log_f is not None:
            # the acked-uid journal is the failover oracle: fsync per
            # batch so a parent that kill -9s BOTH of us still reads
            # every uid whose ack reached this client
            for p in pods:
                log_f.write(f"{p.uid} durable={resp.durable}\n")
            log_f.flush()
            os.fsync(log_f.fileno())
    total = accepted + shed
    out = {
        "name": "front_door",
        "mode": "grpc",
        "addr": args.addr,
        "rate_pods_per_min": args.rate,
        "duration_s": args.duration,
        "accepted": accepted,
        "shed": shed,
        "shed_rate": round(shed / max(total, 1), 4),
        "submit_ack_p50_ms": round(_pctl(ack_ms, 50), 3),
        "submit_ack_p99_ms": round(_pctl(ack_ms, 99), 3),
        "retry_after_ms_seen": sorted(set(retry_after)),
        "stopped_draining": draining,
    }
    if log_f is not None:
        log_f.close()
    client.close()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=("inproc", "grpc"), default="inproc")
    ap.add_argument(
        "--rate", type=float, default=30000.0,
        help="open-loop arrival rate, pods per MINUTE (default 30k)",
    )
    ap.add_argument("--duration", type=float, default=10.0,
                    help="open-loop window, seconds")
    ap.add_argument("--batch", type=int, default=8,
                    help="pods per Submit request")
    ap.add_argument("--nodes", type=int, default=16,
                    help="nodes to create (grpc: pushed via NodeChurn)")
    ap.add_argument("--queue-depth", type=int, default=0,
                    help="inproc: admission bound (0 = unbounded)")
    ap.add_argument("--state-dir", default="",
                    help="inproc: durable state dir (WAL-before-ack on)")
    ap.add_argument("--addr", default="127.0.0.1:50052",
                    help="grpc: the front door's --submit-addr")
    ap.add_argument("--acked-log", default="",
                    help="grpc: append every acked uid here (fsynced "
                    "per batch; the kill -9 failover oracle)")
    ap.add_argument(
        "--tenants", type=int, default=0,
        help="inproc: drive N virtual clusters through the tenant "
        "arena front door (0 = the single-cluster front door)",
    )
    ap.add_argument(
        "--tenant-dist", choices=("roundrobin", "zipf"),
        default="roundrobin",
        help="per-batch tenant selection: even coverage vs hot-tenant "
        "skew (zipf is what trips quota/fair-share sheds)",
    )
    ap.add_argument("--nodes-per-tenant", type=int, default=2,
                    help="tenant mode: nodes per virtual cluster")
    ap.add_argument("--tenant-quota", type=int, default=0,
                    help="tenant mode: per-tenant accepted-unbound "
                    "ceiling (0 = unlimited)")
    ap.add_argument("--seed", type=int, default=50_000)
    ap.add_argument("--prefix", default="lg")
    args = ap.parse_args()
    if args.mode == "inproc" and args.tenants > 0:
        out = run_tenants(args)
    else:
        out = run_inproc(args) if args.mode == "inproc" else run_grpc(args)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())

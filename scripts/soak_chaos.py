#!/usr/bin/env python
"""Chaos soak: replay a workload while every fault class fires, assert
the degradation ladder's invariants hold and measure MTTR.

Four phases (each selectable; default = all):

- **serve** — one in-process Scheduler (flight recorder + observer +
  compile cache + dispatch watchdog) serves a steady arrival stream
  while a scripted `FaultPlan` fires every injection point that does
  not kill durability: `fetch_delay`, `fetch_hang` (longer than
  `dispatchDeadlineMs` — the watchdog must bound it), `device_error`
  in all three marker classes, `clock_skew`, `cache_torn`, and
  `cache_enospc`. Invariants asserted:
    * the serve loop is NEVER blocked past the deadline (the hang
      cycle's wall time stays far below the injected hang);
    * zero lost accepted pods (every added pod ends bound or still
      tracked in a queue tier);
    * zero duplicate binds (each uid binds at most once);
    * the ladder recovered to rung 0 by the end (MTTR reported);
    * a warm restart against the same compile-cache dir neither
      crashes on the torn entry nor misses every entry.
- **overload** — chaos fusion for the edge (ISSUE 14): arrivals at
  >= 2x measured capacity through the REAL submission API
  (loadgen.front_door_drive, the load tool's own harness) with a
  fetch_hang mid-burst. Asserts bounded admission-queue depth,
  shed-not-lost (every acked pod binds exactly once), /healthz
  degraded DURING the burst, and ladder recovery to rung 0 after it.
- **enospc** — a Scheduler with durable state takes a
  `journal_enospc` hit: the writer dies, DurableState degrades to
  stateless (the documented path), and serving CONTINUES — pods still
  bind after durability is gone.
- **crash** — soak_failover-style kill -9 while the child is BELOW the
  top rung (a fetch_hang degraded it): the parent restores into fresh
  queue/cache and asserts the restored digest matches an op boundary
  the child logged (nothing lost, duplicated, or half-applied) AND
  that degradation state did not leak into the restore — a fresh
  Scheduler starts at rung 0.

Standalone:

    JAX_PLATFORMS=cpu python scripts/soak_chaos.py --smoke

A smoke subset runs as tests/test_faults.py::test_soak_chaos_smoke
(marked slow).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the repo for the package, scripts/ for loadgen's front_door_drive
sys.path[:0] = [REPO, os.path.join(REPO, "scripts")]


def _mean(xs):
    return sum(xs) / len(xs) if xs else 0.0


# ---------------------------------------------------------------------------
# phase 1: chaos serve
# ---------------------------------------------------------------------------

# every non-durability fault class, scripted against warm cycles (the
# first cycles compile; faults land after the programs are warm so the
# deadline assertion measures the fetch, not a compile)
SERVE_PLAN = (
    "seed=7;"
    "cache_enospc@cycle=1:n=1;"
    "cache_torn@cycle=1:n=1;"
    "fetch_delay@cycle=6:ms=120:n=1;"
    "fetch_hang@cycle=8:ms={hang_ms}:n=1;"
    "device_error@cycle=12:kind=transport:n=1;"
    "device_error@cycle=16:kind=corrupt:n=1;"
    "device_error@cycle=20:kind=wedge:n=1;"
    "clock_skew@cycle=24:ms=250:n=1"
)


def chaos_serve_drive(
    fault_spec: str,
    cycles: int,
    deadline_ms: float,
    pods_per_cycle: int = 4,
    n_nodes: int = 16,
    cache_dir: str = "off",
    promote_cycles: int = 4,
    drain_timeout_s: float = 30.0,
) -> dict:
    """The chaos-serve drive (ISSUE 9): one real Scheduler (dispatch
    watchdog + ladder + pre-sized pads so no regime flip pollutes the
    timing) serves a steady arrival stream under `fault_spec`, then
    drains until every added pod bound and the ladder promoted home
    (or `drain_timeout_s` expires).

    Returns raw facts — `sched` (live handle), `added`, `binds`
    (uid -> bind count), per-cycle `walls`, `degraded_cycles` (flight
    records with rung > 0), `episodes_ms` (completed recovery episodes),
    `duplicate_binds`, `lost` — and leaves the fault plan ARMED so the
    caller can probe `faults.plan()`; the caller must
    `faults.disarm()` when done."""
    from k8s_scheduler_tpu.config import SchedulerConfiguration
    from k8s_scheduler_tpu.core.scheduler import Scheduler
    from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

    cfg_obj = SchedulerConfiguration(
        dispatch_deadline_ms=deadline_ms,
        degrade_promote_cycles=promote_cycles,
        fault_spec=fault_spec,
        # backoff short so DispatchFailed pods retry within the drive
        pod_initial_backoff_seconds=0.05,
        pod_max_backoff_seconds=0.2,
        # pre-sized pads: the oscillation-free workload must not flip
        # regimes, so the deadline assertions are compile-free
        pad_existing=2048,
        pad_pods_per_node=512,
        compile_cache_dir=cache_dir,
        speculative_compile=False,
    )
    binds: dict[str, int] = {}
    added: set[str] = set()
    sched = Scheduler(
        config=cfg_obj,
        binder=lambda p, n: binds.__setitem__(
            p.uid, binds.get(p.uid, 0) + 1
        ),
    )
    for nd in make_cluster(n_nodes):
        sched.on_node_add(nd)
    walls: dict[int, float] = {}
    t_run = time.perf_counter()
    for i in range(1, cycles + 1):
        for p in make_pods(
            pods_per_cycle, seed=5000 + i, name_prefix=f"cz{i}-"
        ):
            sched.on_pod_add(p)
            added.add(p.uid)
        t0 = time.perf_counter()
        sched.schedule_cycle()
        walls[i] = time.perf_counter() - t0
    # drain tail: requeued pods bind, ladder promotes home
    drain_deadline = time.perf_counter() + drain_timeout_s
    while (
        len(binds) < len(added) or sched.ladder.rung > 0
    ) and time.perf_counter() < drain_deadline:
        sched.schedule_cycle()
        time.sleep(0.02)
    recs = sched.flight.snapshot(last=4096)
    return {
        "sched": sched,
        "added": added,
        "binds": binds,
        "walls": walls,
        "wall_s": time.perf_counter() - t_run,
        "degraded_cycles": sum(
            1 for r in recs if r.counts.get("rung", 0) > 0
        ),
        "episodes_ms": sched.ladder.recovery_episodes_ms(),
        "duplicate_binds": sum(1 for n in binds.values() if n > 1),
        "lost": sorted(
            added - set(binds)
            - {p.uid for p in sched.queue.all_pending()}
        ),
    }


def run_serve_phase(
    cycles: int = 48,
    deadline_ms: float = 300.0,
    hang_ms: float = 4000.0,
    pods_per_cycle: int = 4,
    cache_dir: str = "",
    verbose: bool = True,
) -> dict:
    # chaos_serve_drive under the wider fault plan (cache/clock
    # classes), then the warm-restart check over the chaos-written
    # compile cache
    from k8s_scheduler_tpu.core import faults

    try:
        d = chaos_serve_drive(
            fault_spec=SERVE_PLAN.format(hang_ms=hang_ms),
            cycles=cycles,
            deadline_ms=deadline_ms,
            pods_per_cycle=pods_per_cycle,
            cache_dir=cache_dir or "off",
        )
        sched = d["sched"]
        plan = faults.plan()
        mttr = d["episodes_ms"]
        result = {
            "phase": "serve",
            "cycles": cycles,
            "added": len(d["added"]),
            "bound": len(d["binds"]),
            "duplicate_binds": d["duplicate_binds"],
            "lost": d["lost"],
            "hang_cycle_wall_ms": round(d["walls"][8] * 1e3, 1),
            "deadline_ms": deadline_ms,
            "hang_ms": hang_ms,
            "fired_points": sorted(
                plan.fired_points()
            ) if plan else [],
            "degradations": sched.ladder.degradations,
            "degraded_cycles": d["degraded_cycles"],
            "final_rung": sched.ladder.rung,
            "mttr_ms": round(_mean(mttr), 1),
            "mttr_max_ms": round(max(mttr), 1) if mttr else 0.0,
            "fetch_failure_events": sum(
                1 for e in sched.events.events()
                if e.reason == "FetchFailed"
            ),
        }
    finally:
        faults.disarm()

    # invariants
    assert not result["lost"], f"lost accepted pods: {result['lost']}"
    assert result["duplicate_binds"] == 0, "duplicate binds"
    assert result["bound"] == result["added"], (
        f"only {result['bound']}/{result['added']} pods bound"
    )
    assert result["hang_cycle_wall_ms"] < hang_ms * 0.5, (
        f"serve loop blocked {result['hang_cycle_wall_ms']}ms against a "
        f"{deadline_ms}ms deadline — watchdog failed"
    )
    assert result["final_rung"] == 0, "ladder never recovered to normal"
    assert result["degradations"] >= 2, "plan fired but nothing degraded"
    expect = {
        "cache_enospc", "cache_torn", "fetch_delay", "fetch_hang",
        "device_error", "clock_skew",
    }
    missing = expect - set(result["fired_points"])
    assert not missing, f"fault classes never fired: {missing}"

    if cache_dir:
        # warm restart against the chaos-written cache: the torn entry
        # must be refused (recompile), never a crash
        from k8s_scheduler_tpu.config import SchedulerConfiguration
        from k8s_scheduler_tpu.core import compile_cache as _cc
        from k8s_scheduler_tpu.core.scheduler import Scheduler
        from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

        _cc.clear_loaded_memo()
        sched2 = Scheduler(
            config=SchedulerConfiguration(
                pad_existing=2048, pad_pods_per_node=512,
                compile_cache_dir=cache_dir,
                speculative_compile=False,
            ),
            binder=lambda p, n: None,
        )
        for nd in make_cluster(16):
            sched2.on_node_add(nd)
        for p in make_pods(pods_per_cycle, seed=99, name_prefix="wz-"):
            sched2.on_pod_add(p)
        sched2.schedule_cycle()
        cc = sched2._compile_cache
        result["warm_cache"] = cc.status() if cc is not None else {}
        assert cc is not None and cc.hits + cc.misses > 0
    if verbose:
        print(json.dumps(result), flush=True)
    return result


# ---------------------------------------------------------------------------
# phase 1b: overload through the real submission API (chaos fusion for
# the edge, ISSUE 14)
# ---------------------------------------------------------------------------


def run_overload_phase(verbose: bool = True) -> dict:
    """Arrival rate >= 2x measured capacity through the REAL front
    door (loadgen.front_door_drive — the same harness the load tool
    drives, so tool and soak can never drift), with a
    fetch_hang firing MID-BURST so the degradation ladder engages
    while the door is already shedding. Invariants:

    - the admission queue depth never exceeds its bound (+one batch);
    - the door actually shed (RESOURCE_EXHAUSTED, never silent drops);
    - shed-not-lost: every ACKED pod binds exactly once by drain;
    - /healthz reports degraded:true at some point DURING the burst
      (admission saturation is a paging signal) and clean after it;
    - the ladder recovers to rung 0 after the burst, with the hang
      step deadline-classified (the watchdog ended it, not the hang).
    """
    from loadgen import front_door_drive

    from k8s_scheduler_tpu.cmd.httpserver import staleness_healthz
    from k8s_scheduler_tpu.core import faults

    depth_bound = 64
    deadline_ms, hang_ms = 300.0, 2500.0
    try:
        cal = front_door_drive(
            duration_s=1.0, rate_pps=400.0, queue_depth=depth_bound,
            name_prefix="oc",
        )
        cap = max(cal["bind_rate_pps"], 20.0)

        degraded_seen = {"burst": False}
        probe_state: dict = {}

        def probe(sched, admission, _res):
            # the REAL /healthz closure, evaluated inside the burst:
            # admission saturation (or the hang's ladder step) must
            # surface as degraded:true while the door sheds
            if "fn" not in probe_state:
                probe_state["fn"] = staleness_healthz(
                    None, sched.flight, 0.0, observer=sched.observer,
                    ladder=sched.ladder, admission=admission,
                )
            _ok, detail = probe_state["fn"]()
            if detail.get("degraded"):
                degraded_seen["burst"] = True

        d = front_door_drive(
            duration_s=6.0,
            rate_pps=cap * 2.5,
            queue_depth=depth_bound,
            batch=8,
            deadline_ms=deadline_ms,
            fault_spec=(
                f"seed=17;fetch_hang@cycle=8..100000:ms={hang_ms}:n=1"
            ),
            name_prefix="ov",
            on_tick=probe,
        )
        sched = d["sched"]
        plan = faults.plan()
        fn_after = staleness_healthz(
            None, sched.flight, 0.0, observer=sched.observer,
            ladder=sched.ladder, admission=d["admission"],
        )
        _ok, after = fn_after()
        result = {
            "phase": "overload",
            "capacity_pps": round(cap, 1),
            "rate_pps": round(cap * 2.5, 1),
            "accepted": d["accepted"],
            "shed": d["shed"],
            "bound": len(d["binds"]),
            "duplicate_binds": d["duplicate_binds"],
            "lost": d["lost"],
            "max_queue_depth": d["max_depth"],
            "depth_bound": depth_bound,
            "degraded_during_burst": degraded_seen["burst"],
            "degraded_after": bool(after.get("degraded", False)),
            "final_rung": sched.ladder.rung,
            "degradations": sched.ladder.degradations,
            "fired_points": sorted(
                plan.fired_points()
            ) if plan else [],
            "drained": d["drained"],
        }
    finally:
        faults.disarm()

    assert result["shed"] > 0, (
        "overload burst never shed — the admission bound is not "
        f"engaging at {result['rate_pps']} pps vs capacity "
        f"{result['capacity_pps']} pps"
    )
    assert result["max_queue_depth"] <= depth_bound + 8, (
        f"queue depth {result['max_queue_depth']} exceeded the bound "
        f"{depth_bound}: backpressure is not bounding memory"
    )
    assert not result["lost"], (
        f"acked pods lost under overload: {result['lost'][:6]}"
    )
    assert result["duplicate_binds"] == 0, "duplicate binds"
    missing = {u for u in d["acked"] if u not in d["binds"]}
    assert not missing, (
        f"shed-not-lost violated: {len(missing)} acked pods never "
        f"bound ({sorted(missing)[:4]})"
    )
    assert "fetch_hang" in result["fired_points"], (
        "the mid-burst fetch_hang never fired"
    )
    assert result["degradations"] >= 1 and any(
        t["reason"].startswith("deadline")
        for t in sched.ladder.transitions
    ), "no deadline-classified ladder step: the watchdog never expired"
    assert result["degraded_during_burst"], (
        "/healthz never reported degraded during the burst"
    )
    assert result["final_rung"] == 0 and not result["degraded_after"], (
        "front door did not recover to rung 0 / clean healthz"
    )
    if verbose:
        print(json.dumps(result), flush=True)
    return result


# ---------------------------------------------------------------------------
# phase 2: journal ENOSPC -> stateless degrade, serving continues
# ---------------------------------------------------------------------------


def run_enospc_phase(state_dir: str, verbose: bool = True) -> dict:
    from k8s_scheduler_tpu.config import SchedulerConfiguration
    from k8s_scheduler_tpu.core import faults
    from k8s_scheduler_tpu.core.scheduler import Scheduler
    from k8s_scheduler_tpu.state import DurableState
    from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

    st = DurableState(state_dir, snapshot_interval_seconds=0)
    cfg = SchedulerConfiguration(
        fault_spec="journal_enospc@cycle=3:n=1",
        pad_existing=512, pad_pods_per_node=256,
        pod_initial_backoff_seconds=0.05,
    )
    binds: list[str] = []
    sched = Scheduler(
        config=cfg, binder=lambda p, n: binds.append(p.uid), state=st
    )
    try:
        for nd in make_cluster(8):
            sched.on_node_add(nd)
        for i in range(1, 9):
            for p in make_pods(3, seed=7000 + i, name_prefix=f"en{i}-"):
                sched.on_pod_add(p)
            sched.schedule_cycle()
            if i == 3:
                # give the poll-cadence writer time to hit the injected
                # ENOSPC and die before asserting the degrade
                try:
                    st.journal.flush(timeout=5.0)
                except Exception:
                    pass  # a dead writer raises StateError — expected
        binds_after = len(binds)
    finally:
        faults.disarm()
    result = {
        "phase": "enospc",
        "journal_failed": st.journal.failed,
        "emitters_detached": sched.queue._journal is None,
        "bound": binds_after,
    }
    assert st.journal.failed is not None, "journal writer survived ENOSPC"
    assert result["emitters_detached"], "queue still journaling"
    assert binds_after > 9, "serving stopped after durability loss"
    if verbose:
        print(json.dumps(result), flush=True)
    return result


# ---------------------------------------------------------------------------
# phase 3: kill -9 while degraded -> digest-verified restore at rung 0
# ---------------------------------------------------------------------------


def run_crash_child(state_dir: str, digest_log: str) -> int:
    """Child: a real Scheduler with durable state and a fetch_hang plan
    that degrades it, logging the queue/cache digest after EVERY public
    mutation (soak_failover's discipline: journal drains only at the
    per-cycle flush barrier, so every durable boundary is logged)."""
    from k8s_scheduler_tpu.config import SchedulerConfiguration
    from k8s_scheduler_tpu.core.scheduler import Scheduler
    from k8s_scheduler_tpu.state import DurableState, state_digest
    from k8s_scheduler_tpu.utils.synth import make_cluster, make_pods

    st = DurableState(state_dir, snapshot_interval_seconds=0)
    st.journal._poll_s = 60.0  # drain only at flush barriers
    cfg = SchedulerConfiguration(
        dispatch_deadline_ms=200.0,
        fault_spec="fetch_hang@cycle=3:ms=60000:n=1",
        pad_existing=512, pad_pods_per_node=256,
        pod_initial_backoff_seconds=0.05,
    )
    sched = Scheduler(config=cfg, binder=lambda p, n: None, state=st)
    q, c = sched.queue, sched.cache
    f = open(digest_log, "a")
    counter = {"i": 0}

    def log_line(kind: str) -> None:
        f.write(f"{kind} {counter['i']} {state_digest(q, c)}\n")
        f.flush()
        os.fsync(f.fileno())

    def _wrap(obj, name):
        orig = getattr(obj, name)

        def wrapped(*a, **k):
            r = orig(*a, **k)
            counter["i"] += 1
            log_line("op")
            return r

        setattr(obj, name, wrapped)

    for name in (
        "add", "update", "delete", "pop_ready", "requeue_unschedulable",
        "requeue_backoff", "flush_backoff", "flush_unschedulable_timeout",
        "move_all_to_active_or_backoff", "recover_in_flight",
        "retire_in_flight",
    ):
        _wrap(q, name)
    for name in (
        "add_node", "update_node", "remove_node", "add_pod",
        "remove_pod", "assume", "finish_binding", "confirm", "forget",
        "cleanup_expired",
    ):
        _wrap(c, name)

    for nd in make_cluster(8):
        sched.on_node_add(nd)
    log_line("start")
    for i in range(1, 200):
        for p in make_pods(3, seed=8000 + i, name_prefix=f"cr{i}-"):
            sched.on_pod_add(p)
        sched.schedule_cycle()
        st.journal.flush()
        log_line("flushed")
        if sched.ladder.rung > 0:
            # below the top rung: tell the parent we are degraded (it
            # kills us mid-degradation from here on)
            log_line("degraded")
        time.sleep(0.01)
    return 0


def run_crash_phase(state_dir: str, verbose: bool = True) -> dict:
    """Parent: spawn the child, SIGKILL it once it reports a degraded
    rung, then restore and check the failover invariants."""
    digest_log = os.path.join(state_dir, "digests.txt")
    if os.path.exists(digest_log):
        os.unlink(digest_log)
    child = subprocess.Popen(
        [
            sys.executable, os.path.abspath(__file__),
            "--crash-child", "--state-dir", state_dir,
            "--digest-log", digest_log,
        ],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    deadline = time.monotonic() + 300
    degraded_seen = False
    try:
        while time.monotonic() < deadline:
            if child.poll() is not None:
                raise RuntimeError(
                    f"crash child exited early rc={child.returncode}"
                )
            if os.path.exists(digest_log):
                with open(digest_log) as f:
                    if any(
                        line.startswith("degraded") for line in f
                    ):
                        degraded_seen = True
                        break
            time.sleep(0.05)
    finally:
        child.send_signal(signal.SIGKILL)
        child.wait()
    assert degraded_seen, "child never reported a degraded rung"

    # standby restore into a BARE queue/cache pair (digest comparable
    # to the child's op-boundary log: the Scheduler ctor's journaled
    # recover_in_flight would move the state past the logged boundary)
    from k8s_scheduler_tpu.internal.cache import SchedulerCache
    from k8s_scheduler_tpu.internal.queue import SchedulingQueue
    from k8s_scheduler_tpu.state import DurableState, state_digest

    q = SchedulingQueue(
        initial_backoff_seconds=0.05, max_backoff_seconds=0.2,
    )
    c = SchedulerCache()
    st = DurableState(state_dir, snapshot_interval_seconds=0)
    st.restore_into(q, c)
    dig = state_digest(q, c)
    digests: set[str] = set()
    with open(digest_log) as f:
        for line in f:
            parts = line.strip().split()
            if len(parts) == 3 and len(parts[2]) == 64:
                digests.add(parts[2])
    st.journal.close()
    # real standby takeover: a Scheduler attached to the same state dir
    # restores the dead (degraded) active's queue/cache — and its
    # ladder starts at the TOP rung, because degradation state is
    # process-local and never journaled as authoritative
    from k8s_scheduler_tpu.config import SchedulerConfiguration
    from k8s_scheduler_tpu.core.scheduler import Scheduler

    st2 = DurableState(state_dir, snapshot_interval_seconds=0)
    standby = Scheduler(
        config=SchedulerConfiguration(
            pad_existing=512, pad_pods_per_node=256,
        ),
        binder=lambda p, n: None,
        state=st2,
    )
    result = {
        "phase": "crash",
        "boundaries": len(digests),
        "digest_matched": dig in digests,
        "restored_rung": standby.ladder.rung,
        "restored_pending": dict(standby.queue.pending_counts()),
        "replayed": st2.last_restore.get("records_replayed"),
    }
    st2.journal.close()
    assert dig in digests, (
        "restored digest matches no op boundary the degraded child "
        "recorded — state lost, duplicated, or half-applied"
    )
    assert result["restored_rung"] == 0, (
        "degradation state leaked into the takeover: a standby must "
        "start at the top rung"
    )
    if verbose:
        print(json.dumps(result), flush=True)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--phases", default="serve,overload,enospc,crash",
        help="comma list: serve, overload, enospc, crash",
    )
    ap.add_argument("--cycles", type=int, default=48)
    ap.add_argument("--deadline-ms", type=float, default=300.0)
    ap.add_argument("--hang-ms", type=float, default=4000.0)
    ap.add_argument("--smoke", action="store_true",
                    help="short plan: every fault class fires once")
    ap.add_argument("--state-dir", default="")
    ap.add_argument("--digest-log", default="")
    ap.add_argument("--crash-child", action="store_true", help="internal")
    args = ap.parse_args()
    if args.crash_child:
        return run_crash_child(
            args.state_dir,
            args.digest_log
            or os.path.join(args.state_dir, "digests.txt"),
        )
    import tempfile

    base = args.state_dir or tempfile.mkdtemp(prefix="soak-chaos-")
    phases = [p.strip() for p in args.phases.split(",") if p.strip()]
    cycles = 30 if args.smoke else args.cycles
    results = []
    if "serve" in phases:
        results.append(run_serve_phase(
            cycles=cycles,
            deadline_ms=args.deadline_ms,
            hang_ms=args.hang_ms,
            cache_dir=os.path.join(base, "compile_cache"),
        ))
    if "overload" in phases:
        results.append(run_overload_phase())
    if "enospc" in phases:
        results.append(run_enospc_phase(os.path.join(base, "enospc")))
    if "crash" in phases:
        results.append(run_crash_phase(os.path.join(base, "crash")))
    print(json.dumps({
        "soak_chaos": "ok",
        "phases": [r["phase"] for r in results],
        "mttr_ms": next(
            (r["mttr_ms"] for r in results if "mttr_ms" in r), 0.0
        ),
    }), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())

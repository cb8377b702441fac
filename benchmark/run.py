#!/usr/bin/env python3
"""One run of one benchmark cell: the served scheduler on the agent path.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A plain gRPC client that never initialises a JAX backend (asserted). It
starts the scheduler as the one process on the chip, loads the cell's
deployment from `--seed`, warms the regime, drives the cell's traffic
for `--seconds`, checks every binding against the plain reference, and
prints the contract line last. Everything that belongs to one cell, one
configuration or one per-layer metric is a file found by its name in
`BENCHMARK.json`: `benchmark/workloads/<cell>.json`,
`benchmark/configs/<config>.json` (+ the server's YAML),
`benchmark/layers/<metric>.json`.

    JAX_PLATFORMS=cpu python benchmark/run.py --rehearse [--workload <cell>]

runs the same control flow at the cut sizes in each configuration's
`rehearse` block, accepts a server that is not on a TPU, and prints a
line that says it is a rehearsal: never the contract line.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import yaml  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.lib import (  # noqa: E402
    agent, generate, program_spans, reduce, reference, xplane)
from benchmark.lib.child import BenchError, Server, counter_total  # noqa: E402

SCRATCH = os.path.join(ROOT, ".bench")  # git-ignored; caches outlast a run
DEADLINE_S = 1180  # the first run of a cell in a checkout compiles


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"BENCHMARK.json names no {what} {name!r}")


def due_times(count: int, rate: float, burst: int) -> list[float]:
    """Pod i is due (i // burst) * burst / rate seconds into the window."""
    return [(i // burst) * burst / rate for i in range(count)]


def server_yaml(cfg: dict, rehearse: bool, workdir: str) -> str:
    if not rehearse:
        return os.path.join(HERE, "configs", cfg["server_config"])
    path = os.path.join(workdir, "server.json")  # JSON is YAML
    with open(path, "w") as f:
        json.dump(cfg["rehearse"]["server"], f)
    return path


def pad_existing(yaml_path: str) -> int:
    """`padExisting` as the server was given it (JSON is YAML)."""
    with open(yaml_path) as f:
        return int(yaml.safe_load(f)["padExisting"])


def run_cell(bench: dict, cell: dict, args, rehearse: bool) -> dict:
    traffic = load_json("workloads", cell["name"] + ".json")
    cfg = load_json("configs", cell["config"] + ".json")
    if traffic["config"] != cell["config"]:
        raise BenchError(f"{cell['name']}: traffic file is for another config")
    cut = cfg["rehearse"] if rehearse else None
    if rehearse:
        traffic = {**traffic, **traffic.get("rehearse", {})}
    seconds = float(args.seconds)
    tag = "rehearse" if rehearse else "run"
    workdir = os.path.join(SCRATCH, f"{tag}-{cell['name']}-{args.seed}")
    cache = os.path.join(SCRATCH, "cache-cpu" if rehearse else "cache")
    os.makedirs(cache, exist_ok=True)

    server = drv = None
    timers: list[threading.Timer] = []
    served_whole = False
    try:
        for start in ("first", "after_the_compile"):
            shutil.rmtree(workdir, ignore_errors=True)  # never a kept journal
            os.makedirs(workdir)
            dep = generate.deployment(
                cfg, args.seed,
                {k: v for k, v in (cut or {}).items() if k != "server"},
            )
            depth = dep.cfg["depth"]
            served_yaml = server_yaml(cfg, rehearse, workdir)
            pad = pad_existing(served_yaml)
            server = Server(
                ROOT, workdir, served_yaml,
                aot_dir=os.path.join(cache, "aot"),
                jax_cache_dir=os.path.join(cache, "jax"),
                traced=bool(args.trace),
            )
            build = server.started(require_tpu=not rehearse,
                                   chips=cell["chips"])
            say(server=build, workload=cell["name"], seed=args.seed,
                start=start)
            drv = agent.Driver(server.grpc_port, dep,
                               traffic.get("completions"), args.seed)
            t_load = time.monotonic()
            drv.load()
            load_s = time.monotonic() - t_load
            # every pod object of the window is built before the clock starts
            if traffic["loop"] == "closed_depth":
                budget = int(
                    traffic["pods_budget_per_s"] * (seconds + 5)) + depth
                window_pods, due = dep.pending(budget, "pod"), None
            else:
                rate = float(traffic["rate_per_s"])
                due = due_times(int(rate * seconds), rate,
                                int(traffic.get("burst", 1)))
                window_pods = dep.pending(len(due), "pod")
            t_warm = time.monotonic()
            # the warm-up batch of `depth` pods pins the P pad for the
            # run; the configuration's unschedulable pods are of it
            stay = dep.unschedulable()
            drv.warm(dep.pending(depth - len(stay), "warm") + stay)
            warm_s = time.monotonic() - t_warm
            m_start = server.metrics()
            compiled_in_setup = counter_total(
                m_start, "scheduler_compile_cache_misses_total")
            if not compiled_in_setup:
                break
            # this set-up compiled and wrote the executable store (about
            # 1 GB at 5,000 nodes). A window served by the process that
            # compiled, or over the write-back, reads slower (PERF.md
            # section 6): flush, seal this child, and serve the window
            # from a child that loads. Only a checkout's first run pays
            os.sync()
            if start == "first":
                drv.close()
                server.stop()
        n_warm_cycles = len(drv.cycles)
        warm_left = len(drv.pending)
        if args.trace:
            at = seconds * 0.3
            timers = [
                threading.Timer(at, server.signal, (signal.SIGUSR1,)),
                threading.Timer(at + float(traffic.get("trace_s", 4.0)),
                                server.signal, (signal.SIGUSR2,)),
            ]
            for t in timers:
                t.daemon = True
                t.start()
        wall0 = time.time()
        setup_s = time.monotonic() - _T_PROCESS
        if due is None:
            t0 = time.monotonic()
            window = drv.run_closed(window_pods, depth, seconds)
            late = []
        else:
            t0, window, late = drv.run_open(
                window_pods, due, seconds,
                float(traffic.get("drain_s", 10.0)), depth)
        t_done = time.monotonic()
        wall1 = wall0 + window
        m_end = server.metrics()
        for t in timers:
            t.join()
        spans = [s for s in drv.spans[n_warm_cycles:]
                 if s.t_start < t0 + window]
        records = [
            r for r in server.flight_records(min(len(drv.spans), 60000))
            if wall0 <= r["wall_start"] <= wall1
        ]
        health = server.health()
        trace = program = None
        if args.trace:
            # the program's spans, while the child lives to serve them
            program_events = server.trace_events(
                min(len(drv.spans), 60000))
            done = os.path.join(server.trace_dir, "trace.done")
            limit = time.monotonic() + 90.0
            while not os.path.exists(done) and time.monotonic() < limit:
                time.sleep(0.2)
            if not os.path.exists(done):
                raise server.fail("the traced server wrote no trace")
            with open(done) as f:
                trace_meta = json.load(f)
        drv.close()
        server.stop()
        if args.trace:
            path = xplane.find_trace(server.trace_dir)
            trace = xplane.reduce_trace(
                path, trace_meta["stop"] - trace_meta["start"]
            ) if path else None
            if trace:
                trace["t0_wall"] = trace_meta["wall_start"]
            program = program_spans.collect(
                program_events, records, wall0, wall1, path,
                trace["window_s"] if trace else None)
        device_line = next(
            (json.loads(ln[len("bench_device: "):])
             for ln in server.log().splitlines()
             if ln.startswith("bench_device: ")), None)
        if device_line is None:
            raise server.fail("the server printed no bench_device: line")
        served_whole = True
    finally:
        for t in timers:
            t.cancel()
        if server is not None:
            server.kill()
        # the last run's server log per cell, and every failed run's
        log_copy = os.path.join(SCRATCH, (
            f"last-server-{cell['name']}.log" if served_whole
            else f"failed-server-{cell['name']}-{args.seed}.log"))
        if server is not None and os.path.exists(server.log_path):
            shutil.copy(server.log_path, log_copy)
        shutil.rmtree(workdir, ignore_errors=True)

    # ---- the check: after the window, outside set-up ------------------
    t_check = time.monotonic()
    verdict = reference.check_run(
        dep.nodes, dep.init, drv.pods, drv.cycles, dep.pools,
        drv.probe_rounds, drv.resident_target)
    check_s = time.monotonic() - t_check
    compiled = counter_total(m_end, "scheduler_compile_cache_misses_total") \
        - counter_total(m_start, "scheduler_compile_cache_misses_total")
    loaded = counter_total(m_end, "scheduler_compile_cache_hits_total") \
        - counter_total(m_start, "scheduler_compile_cache_hits_total")
    rung = health["degradation"]
    # the server's existing set is what was loaded and everything it
    # bound, less what it evicted and what the agent completed: the
    # reference's replay counts it after every cycle's confirmations,
    # and the largest is what the E pad has to hold. Past the pad the
    # encoder leaves the delta path and the next regime compiles inside
    # the window: said here in plain words
    existing_peak = max(verdict.resident_after, default=len(dep.init))
    at_start = verdict.resident_at_start[n_warm_cycles:]
    # ... and the server's own count of the pods it holds (bound and
    # assumed, stamped at the last cycle's end) is the replay's: a
    # delete or a confirmation that never reached it shows here
    held = m_end.get('scheduler_cache_size{type="pods"}')
    if held is None:
        raise BenchError("/metrics holds no scheduler_cache_size{type=pods}")
    served = {
        "existing_over_pad": [max(0, existing_peak - pad), 0],
        "server_resident_drift": [
            abs(int(held) - verdict.resident_after[-1]), 0],
        "programs_compiled_in_window": [compiled, 0],
        "programs_loaded_in_window": [loaded, 0],
        "ladder_degradations": [
            rung["degradations"] + (rung["name"] != "normal"), 0],
        "retry_strikes": [counter_total(
            m_end, "scheduler_program_retry_strikes_total"), 0],
        "fetch_failures": [counter_total(
            m_end, "scheduler_fetch_failures_total"), 0],
    }
    window_pod_uids = [p.uid for p in window_pods]
    in_window = [u for u, t in drv.bound_at.items() if t0 <= t <= t0 + window]
    offered = sum(1 for u in drv.pods if not u.split("/")[-1].startswith(
        ("warm-", "probe-load-")))
    if due is not None:
        end = t_done
        lat = [
            (drv.bound_at.get(uid, end) - (t0 + d)) * 1e3
            for uid, d in zip(window_pod_uids, due)
        ]
        unbound = sum(1 for u in window_pod_uids if u not in drv.bound_at)
    else:
        sent = {u for c in drv.cycles[n_warm_cycles:] for u in c.offered}
        lat = [(drv.bound_at[u] - t0) * 1e3 for u in in_window if u in sent]
        unbound = len(drv.pending)
    compared = {**verdict.counts, **served}
    say(
        compared=compared,
        problems=verdict.problems[:8], check_s=round(check_s, 3),
        check_covers="every cycle, every binding, every refusal",
    )
    if verdict.open_refusals:
        say(refusals_left_open=verdict.open_refusals,
            warm_cycles=n_warm_cycles,
            window_records=[{"seq": r["seq"], **r["counts"]}
                            for r in records])
    correct = verdict.ok and all(v[0] <= v[1] for v in served.values())
    e2e = {"pods_bound_per_s": len(in_window) / window, "setup_s": setup_s}
    say(
        facts={
            "window_s": window, "cycles": len(spans),
            "warm_cycles": n_warm_cycles, "warm_left_pending": warm_left,
            "compiled_in_setup": compiled_in_setup, "start": start,
            "load_s": round(load_s, 3), "warm_s": round(warm_s, 3),
            "bound_in_window": len(in_window), "unbound_at_end": unbound,
            "latency_ms": {
                "p50": statistics.median(lat) if lat else None,
                "p95": reduce.percentile(lat, 95) if lat else None,
                "n": len(lat),
            },
            "generator_late_ms": {
                "p50": statistics.median(late) * 1e3 if late else None,
                "max": max(late) * 1e3 if late else None,
            },
            "pending_mid_end": [
                spans[len(spans) // 2].offered if spans else 0,
                spans[-1].offered if spans else 0,
            ],
            "flight_records": len(records),
            "existing_peak": existing_peak, "pad_existing": pad,
            "pad_headroom_share": 1.0 - existing_peak / pad,
            "completed": verdict.counts["completed"],
            "resident_target": drv.resident_target,
            "resident_at_start": [min(at_start, default=None),
                                  max(at_start, default=None)],
        },
    )
    src = {"spans": spans, "flight": records, "trace": trace,
           "program": program,
           "latency_ms": lat if due is not None else None}
    if program:
        say(program_spans=program["table"])
    names = bench["per_layer"] if args.trace else bench["end_to_end"]
    metrics = {}
    for m in names:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        if args.trace:
            value = reduce.read_layer(
                load_json("layers", m["name"] + ".json"), src)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif m["name"] in e2e:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = {
        "platform": build["platform"], "kind": build["device_kind"],
        "count": int(build["device_count"]),
        "memory_peak_bytes": device_line["peak_bytes_in_use"],
    }
    line = {
        "correct": bool(correct), "attempted": offered,
        "failed": verdict.wrongly_refused,  # an RPC error ends the run
        "metrics": metrics, "device": device,
    }
    if args.trace and trace:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        line["breakdown"] = {
            "device_ops": sorted(
                ([k, v] for k, v in trace["by_program"].items()),
                key=lambda kv: -kv[1])[:10],
            "idle_gaps": [
                [gap_name(start, trace, records), secs]
                for start, secs in trace["gaps"]
            ],
        }
    # each number compared beside its limit, last in the line
    line["compared"] = {
        k: v for k, v in compared.items() if isinstance(v, list)}
    return line


def gap_name(start_s: float, trace: dict, records: list) -> str:
    """What the server's host was doing when an idle gap began: the last
    flight-recorder mark before it inside a cycle, or `between_cycles`
    (the server waits for the agent's Updates and the next Cycle). The
    trace counts from start_trace and the recorder stamps wall time, so
    the two line up to about a tenth of a second: enough for gaps of
    seconds, and said so in PERF.md."""
    at = trace["t0_wall"] + start_s
    for r in records:
        base = r["wall_start"] - r["t_start_s"]
        if r["wall_start"] <= at <= base + r["t_end_s"]:
            name = "cycle_start"
            for t, k in sorted(
                    (v + base, k) for k, v in r.get("marks_s", {}).items()):
                if t <= at:
                    name = k
            return "in_cycle_after_" + name
    return "between_cycles"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    signal.signal(signal.SIGALRM, lambda *_: (_ for _ in ()).throw(
        BenchError(f"not done in {DEADLINE_S}s")))
    signal.alarm(DEADLINE_S)
    try:
        if args.rehearse:
            cells = ([find(bench["workloads"], args.workload, "workload")]
                     if args.workload else bench["workloads"])
            args.seconds = args.seconds or 4.0
            for cell in cells:
                for trace in ((args.trace,) if args.workload else (0, 1)):
                    args.trace = trace
                    line = run_cell(bench, cell, args, rehearse=True)
                    say(rehearsal=cell["name"], trace=trace,
                        not_a_chip_run=True, would_print=line)
            print(json.dumps({"rehearsal": True, "cells": len(cells),
                              "note": "a rehearsal on the CPU at cut sizes: "
                              "no number here is a measurement"}), flush=True)
            return 0
        if not args.workload or args.seconds <= 0:
            raise BenchError("--workload and --seconds are required")
        cell = find(bench["workloads"], args.workload, "workload")
        line = run_cell(bench, cell, args, rehearse=False)
    except BenchError as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
    # this parent is a client: the chip belonged to the child alone
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            print("benchmark: FAILED: the harness initialised a JAX "
                  "backend", file=sys.stderr, flush=True)
            return 1
    for name, (value, limit) in line["compared"].items():
        print(f"compared: {name} {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

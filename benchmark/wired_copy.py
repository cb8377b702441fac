#!/usr/bin/env python3
"""A scratch copy of this benchmark with the `program_span` reader wired.

    python benchmark/wired_copy.py .bench_wired
    python .bench_wired/benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace 1

`benchmark/lib/program_spans.py` reads the agent path's spans
(`rpc.update`, `rpc.cycle` and their children) and splits the device's
idle time by them, but `benchmark/lib/reduce.py` looks a reader up in a
dict, and the events have to be fetched while the child lives: both are
edits to files the benchmark already has, which only a `benchmark` PR
may make. Until one does, this is how a builder reads the spans: the
copy is this checkout's benchmark with `lib/program_spans.wiring.txt`
applied (a key in `READERS`, `Server.trace_events`, the fetch under
`--trace 1`, a `program_spans` line with the self-time table), the
eighteen metrics of `METRICS` as layer files and `per_layer` entries,
and the program and the executable store linked in, so the copy's runs
load what the checkout's compiled. Nothing the driver runs reads this
file, the patch or the copy.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
LINKED = ("k8s_scheduler_tpu", ".bench")

SERVICER = "client and servicer (service/)"
HOST = "host cycle (core/scheduler.py, models/encoding.py)"
DEVICE = "device programs (core/cycle.py, ops/)"
# name: unit, layer, BENCHMARK.json's source, select, reduce, what.
# Rows are per `rpc.cycle` span with the `rpc.update` spans that began
# since the previous one ended; each exists as .sat and .steady
METRICS = {
    "update_servicer_ms": (
        "ms", SERVICER, "program_span", "rpc.update", "median",
        "median over the window's rpc.cycle spans of the summed rpc.update "
        "spans (Update handler entry to return) that began since the "
        "previous rpc.cycle ended"),
    "update_convert_ms": (
        "ms", SERVICER, "program_span", "update.convert", "median",
        "the same over update.convert: proto to API objects"),
    "update_apply_ms": (
        "ms", SERVICER, "program_span", "update.apply", "median",
        "the same over update.apply: the informer handlers (cache, queue, "
        "journal append, encoder delta)"),
    "cycle_servicer_ms": (
        "ms", SERVICER, "program_span", "rpc.cycle", "median",
        "median rpc.cycle span: Cycle handler entry to return"),
    "cycle_respond_ms": (
        "ms", SERVICER, "program_span", "cycle.respond", "median",
        "median cycle.respond span: schedule_cycle returned to response "
        "built"),
    "cycle_snapshot_ms": (
        "ms", HOST, "program_span", "cycle.snapshot", "mean",
        "sum of the window's cycle.snapshot spans (journal compactions that "
        "ran) over its count of rpc.cycle spans: a mean, the median is 0"),
    "idle_in_update_pct": (
        "%", DEVICE, "device_trace", "rpc.update", "idle_pct",
        "share of the traced window in which the device ran no operation "
        "and an rpc.update span was open (and no rpc.cycle), clocks joined "
        "by the sched.dispatch anchors"),
    "idle_in_cycle_pct": (
        "%", DEVICE, "device_trace", "rpc.cycle", "idle_pct",
        "the same with an rpc.cycle span open"),
    "idle_outside_rpc_pct": (
        "%", DEVICE, "device_trace", "outside", "idle_pct",
        "the same with neither open: the server waits for the agent "
        "(client conversion, wire)"),
}
CELLS = {"sat": ("sp5000-mixed.sat", "pods_bound_per_s"),
         "steady": ("sp5000-mixed.steady", "bind_latency_p50_ms")}


def layers() -> list[tuple[dict, dict]]:
    """(layer file, `per_layer` entry) of each metric in each cell."""
    out = []
    for metric, (unit, layer, source, select, how, what) in METRICS.items():
        for suffix, (cell, moves) in CELLS.items():
            entry = {
                "name": f"{metric}.{suffix}", "unit": unit,
                "better": "lower", "source": source, "layer": layer,
                "moves": moves, "workloads": [cell],
            }
            spec = {
                "name": entry["name"], "layer": layer, "moves": moves,
                "workloads": [cell], "unit": unit,
                "source_kind": "program_span", "select": [select],
                "reduce": how, "what": what,
            }
            out.append((spec, entry))
    return out


def build(dest: str) -> None:
    dest = os.path.abspath(dest)
    if os.path.exists(dest):
        shutil.rmtree(dest)
    shutil.copytree(
        HERE, os.path.join(dest, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(ROOT, ".bench"), exist_ok=True)
    for name in LINKED:
        os.symlink(os.path.join(ROOT, name), os.path.join(dest, name))
    with open(os.path.join(HERE, "lib", "program_spans.wiring.txt")) as f:
        subprocess.run(["patch", "-p1", "--quiet"], stdin=f, cwd=dest,
                       check=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for spec, entry in layers():
        bench["per_layer"].append(entry)
        with open(os.path.join(dest, "benchmark", "layers",
                               spec["name"] + ".json"), "w") as f:
            json.dump(spec, f, indent=1)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        sys.exit(__doc__.split("\n\n")[1])
    build(sys.argv[1])

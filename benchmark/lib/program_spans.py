"""From the program's own spans to numbers: the agent path's `Update`
and `Cycle` RPCs as the servicer sees them, and the device's idle time
split by which of them was open.

Three sources, all taken under `--trace 1` only:

- the `X` events of `/debug/traces` (`core/spans.spans_to_chrome_events`
  and `core/flight_recorder.to_chrome_trace`): the spans `rpc.update`,
  `update.convert`, `update.apply`, `rpc.cycle`, `cycle.lock_wait`,
  `cycle.pop`, `cycle.snapshot`, `cycle.respond`, with `ts` and `dur` in
  microseconds of the recorder's clock since its epoch, `args.span_id`,
  `args.parent`, and on `rpc.cycle` `args.seqs`, the flight records it
  committed; and the `cycle[<seq>]` slices, those records' `total`;
- the `.xplane.pb`: the device operations, and the `sched.dispatch`
  host events `core/pipeline` wraps around every dispatch, whose stats
  carry `t_us`, the recorder's clock at the event's start. The event's
  own start is on the profiler's clock (nanoseconds since the trace
  began), so each one gives the offset between the two clocks. They
  have to agree within `ANCHOR_SPREAD_US`; where they do not, or none
  is found, nothing that needs both clocks is reported;
- the traced window's length, as the child timed it.

A program without these spans (the parent of the PR that added them)
gives no `rpc.*` event: `collect` then returns None and every metric
read from it is left out of the line.

`run.py` does not call this yet: `program_spans.wiring.txt` is the
edit that makes it, and `benchmark/wired_copy.py` a copy that has it.
"""

from __future__ import annotations

import re
import statistics

from .xplane import DEVICE_PLANE, OPS_LINE

ANCHOR = "sched.dispatch"
ANCHOR_SPREAD_US = 1000.0
UPDATE_CHILDREN = ("update.convert", "update.apply")
CYCLE_CHILDREN = ("cycle.lock_wait", "cycle.pop", "cycle.snapshot",
                  "cycle.respond")
_RECORD = re.compile(r"^cycle\[(\d+)\]$")


# ---- intervals: sorted, disjoint lists of (start, end) ------------------

def merge(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def intersect(a: list, b: list) -> list:
    """The parts of merged `a` inside merged `b`."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(a: list, lo: float, hi: float) -> list:
    """[lo, hi] less merged `a`."""
    out, at = [], lo
    for s, e in intersect(a, [(lo, hi)]):
        if s > at:
            out.append((at, s))
        at = e
    if hi > at:
        out.append((at, hi))
    return out


def length(a: list) -> float:
    return sum(e - s for s, e in a)


# ---- the two sources ----------------------------------------------------

def read_xplane(path: str) -> tuple[list, list]:
    """(anchors, device planes) of a profiler trace: anchors as
    (t_us on the recorder's clock, start in ns on the profiler's), and
    per device plane its operations as (start, end) in ns."""
    from jax.profiler import ProfileData

    anchors, planes = [], []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    planes.append([
                        (e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events])
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == ANCHOR:
                    t_us = dict(e.stats).get("t_us")
                    if t_us is not None:
                        anchors.append((float(t_us), float(e.start_ns)))
    return anchors, planes


def clock_offset_us(anchors: list) -> tuple[float | None, float | None]:
    """(profiler clock less recorder clock in us, how far the anchors
    disagree about it); the offset is None where they disagree by more
    than ANCHOR_SPREAD_US or there is none: no guess is made."""
    offsets = [start_ns / 1e3 - t_us for t_us, start_ns in anchors]
    if not offsets:
        return None, None
    spread = max(offsets) - min(offsets)
    if spread > ANCHOR_SPREAD_US:
        return None, spread
    return statistics.median(offsets), spread


def idle_partition(spans: dict, offset_us: float, planes: list,
                   window_s: float) -> dict | None:
    """Percent of the traced window in which the device ran no
    operation, split by what the servicer had open: an `rpc.cycle`
    span, else an `rpc.update` span, else neither (the server waits for
    the agent). The three sum to the idle share; a span that straddles
    an edge of the window counts for the part inside it. Averaged over
    the device planes, as `xplane.reduce_trace` averages busy time."""
    if not planes or window_s <= 0:
        return None
    lo, hi = 0.0, window_s * 1e9

    def on_profiler_clock(name):
        return merge(
            ((s["ts"] + offset_us) * 1e3,
             (s["ts"] + s["dur"] + offset_us) * 1e3)
            for s in spans.get(name, ()))

    in_cycle = on_profiler_clock("rpc.cycle")
    in_update = intersect(
        on_profiler_clock("rpc.update"), complement(in_cycle, lo, hi))
    out = {"rpc.cycle": 0.0, "rpc.update": 0.0, "outside": 0.0}
    for ops in planes:
        idle = complement(merge(ops), lo, hi)
        cyc = length(intersect(idle, in_cycle))
        upd = length(intersect(idle, in_update))
        out["rpc.cycle"] += cyc
        out["rpc.update"] += upd
        out["outside"] += length(idle) - cyc - upd
    scale = 100.0 / (len(planes) * hi)
    return {k: v * scale for k, v in out.items()}


def cycle_rows(spans: dict, record_ms: dict, lo_us: float,
               hi_us: float) -> list[dict]:
    """One row per `rpc.cycle` span that began in [lo_us, hi_us], in
    milliseconds: the span, its children, the `total` of its flight
    records (`records`, None when one is missing from the export) and
    its self time; and the `Update`s that began since the previous
    `rpc.cycle` ended, summed: `rpc.update`, its two children and its
    self time."""
    cycles = sorted(spans.get("rpc.cycle", ()), key=lambda s: s["ts"])
    updates = sorted(spans.get("rpc.update", ()), key=lambda s: s["ts"])
    children: dict = {}
    for name in UPDATE_CHILDREN + CYCLE_CHILDREN:
        for s in spans.get(name, ()):
            by = children.setdefault(s["args"]["parent"], {})
            by[name] = by.get(name, 0.0) + s["dur"] / 1e3
    rows, u = [], 0
    for c in cycles:
        mine = []  # began since the previous cycle ended
        while u < len(updates) and updates[u]["ts"] < c["ts"] + c["dur"]:
            mine.append(updates[u])
            u += 1
        if not lo_us <= c["ts"] <= hi_us:
            continue
        row = {"rpc.cycle": c["dur"] / 1e3, "updates": len(mine)}
        own = children.get(c["args"]["span_id"], {})
        for name in CYCLE_CHILDREN:
            row[name] = own.get(name, 0.0)
        seqs = c["args"].get("seqs", [])
        row["records"] = (
            sum(record_ms[q] for q in seqs)
            if all(q in record_ms for q in seqs) else None)
        row["cycle.self"] = None if row["records"] is None else (
            row["rpc.cycle"] - row["records"]
            - sum(row[name] for name in CYCLE_CHILDREN))
        row["rpc.update"] = sum(s["dur"] for s in mine) / 1e3
        for name in UPDATE_CHILDREN:
            row[name] = sum(
                children.get(s["args"]["span_id"], {}).get(name, 0.0)
                for s in mine)
        row["update.self"] = row["rpc.update"] - sum(
            row[name] for name in UPDATE_CHILDREN)
        rows.append(row)
    return rows


def collect(events, records: list, wall0: float, wall1: float,
            trace_path: str | None, window_s: float | None) -> dict | None:
    """Everything the `program_span` reader reads, from one traced run.
    `events` are `/debug/traces`' traceEvents; `records` the window's
    `/debug/flightrecorder` records, whose wall and recorder stamps
    place [wall0, wall1] on the recorder's clock."""
    spans: dict = {}
    record_ms: dict = {}
    for ev in events or ():
        if ev.get("ph") != "X":
            continue
        name = ev["name"]
        if name.startswith(("rpc.", "update.", "cycle.")):
            spans.setdefault(name, []).append(ev)
        else:
            m = _RECORD.match(name)
            if m:
                record_ms[int(m.group(1))] = ev["dur"] / 1e3
    if "rpc.cycle" not in spans or not records:
        return None
    # wall clock less recorder clock, from the records' two stamps
    delta = statistics.median(
        r["wall_start"] - r["t_start_s"] for r in records)
    rows = cycle_rows(spans, record_ms, (wall0 - delta) * 1e6,
                      (wall1 - delta) * 1e6)
    idle, n_anchors, spread = None, 0, None
    if trace_path:
        anchors, planes = read_xplane(trace_path)
        offset, spread = clock_offset_us(anchors)
        n_anchors = len(anchors)
        if offset is not None and window_s:
            idle = idle_partition(spans, offset, planes, window_s)
    # the self-time table PERF.md prints, beside what the clocks and the
    # idle gave: medians, and means, which add up (a compaction runs in
    # one cycle of several, so its median is 0)
    keys = ("rpc.update", *UPDATE_CHILDREN, "update.self", "rpc.cycle",
            *CYCLE_CHILDREN, "records", "cycle.self")
    columns = {
        k: vals for k in keys
        if (vals := [r[k] for r in rows if r[k] is not None])
    }
    table = {
        "cycles": len(rows),
        "median_ms": {k: statistics.median(v) for k, v in columns.items()},
        "mean_ms": {k: statistics.fmean(v) for k, v in columns.items()},
        "anchors": n_anchors,
        "anchor_spread_us": spread,
        "idle_pct": idle,
    }
    return {"cycles": rows, "idle": idle, "table": table}


def read(spec: dict, src: dict):
    """The `program_span` reader. `select` names row keys (span names)
    summed per `rpc.cycle`, reduced over the window's cycles by `median`
    or `mean`; or, with `idle_pct`, the one part of the idle partition."""
    program = src.get("program")
    if not program:
        return None
    if spec["reduce"] == "idle_pct":
        idle = program["idle"]
        return None if idle is None else idle[spec["select"][0]]
    series = [sum(row[k] for k in spec["select"])
              for row in program["cycles"]]
    if not series:
        return None
    if spec["reduce"] == "median":
        return statistics.median(series)
    if spec["reduce"] == "mean":
        return sum(series) / len(series)
    raise ValueError(f"unknown reduction {spec['reduce']!r}")

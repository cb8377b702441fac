"""From the program's own spans to numbers: the agent path's `Update`
and `Cycle` RPCs as the servicer sees them, and the device's idle time
split by which of them was open.

Three sources, all taken under `--trace 1` only:

- the `X` events of `/debug/traces` (`core/spans.spans_to_chrome_events`
  and `core/flight_recorder.to_chrome_trace`): every span the export
  carries, which is an event with `args.span_id` (`rpc.update`,
  `update.convert`, `update.apply`, `rpc.cycle`, `cycle.lock_wait`,
  `cycle.pop`, `cycle.snapshot`, `cycle.respond` today; whatever a later
  PR stamps, under its own name), with `ts` and `dur` in microseconds
  of the recorder's clock since its epoch, `args.parent`, and on
  `rpc.cycle` `args.seqs`, the flight records it committed; and the
  `cycle[<seq>]` slices, those records' `total`;
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

A per-layer metric over a span is a layer file with `"source_kind":
"program_span"`, the span's name under `select` and `median` or `mean`
under `reduce`, and a `per_layer` entry: data only.
"""

from __future__ import annotations

import bisect
import re
import statistics

from .xplane import DEVICE_PLANE, OPS_LINE

ANCHOR = "sched.dispatch"
ANCHOR_SPREAD_US = 1000.0
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
    """One row per `rpc.cycle` span that began in [lo_us, hi_us]: an
    iteration, in milliseconds. A row holds, under its own name, the
    summed duration of EVERY span name the window's spans carry (0.0
    where the iteration has none), so a span stamped by a later PR needs
    no edit here. A span belongs to the iteration whose `rpc.cycle` or
    `rpc.update` it descends from by `args.parent`; one with no such
    ancestor goes, with what descends from it, to the iteration in which
    it began (the previous `rpc.cycle`'s end to this one's end), as an
    `rpc.update` itself does whatever called it. Beside
    the names: `updates` (count), `records` (the `total` of the flight
    records in `seqs`, None when one is missing from the export),
    `cycle.self` (the span less its records and its direct children)
    and `update.self` (the `Update`s less their direct children)."""
    cycles = sorted(spans.get("rpc.cycle", ()), key=lambda s: s["ts"])
    index = {c["args"]["span_id"]: i for i, c in enumerate(cycles)}
    # the cycle lock serialises `Cycle`s, so the ends are sorted too
    ends = [c["ts"] + c["dur"] for c in cycles]
    by_id = {s["args"]["span_id"]: s
             for group in spans.values() for s in group}
    owner: dict = {}  # span_id -> index into `cycles`, or None

    def iteration(s) -> int | None:
        sid = s["args"]["span_id"]
        if sid not in owner:
            owner[sid] = None  # a parent loop ends here
            parent = by_id.get(s["args"].get("parent"))
            if s["name"] == "rpc.cycle":
                owner[sid] = index[sid]
            elif parent is not None and s["name"] != "rpc.update":
                owner[sid] = iteration(parent)
            else:
                i = bisect.bisect_right(ends, s["ts"])
                owner[sid] = i if i < len(ends) else None
        return owner[sid]

    sums: list[dict] = [{} for _ in cycles]
    updates = [0] * len(cycles)
    # per iteration, the direct children of its `rpc.cycle` and of its
    # `rpc.update`s (a child is in its parent's iteration), for self time
    under = {"rpc.cycle": [0.0] * len(cycles),
             "rpc.update": [0.0] * len(cycles)}
    for name, group in spans.items():
        for s in group:
            i = iteration(s)
            if i is None:
                continue
            ms = s["dur"] / 1e3
            sums[i][name] = sums[i].get(name, 0.0) + ms
            updates[i] += name == "rpc.update"
            parent = by_id.get(s["args"].get("parent"))
            if parent is not None and parent["name"] in under:
                under[parent["name"]][i] += ms
    window = [i for i, c in enumerate(cycles) if lo_us <= c["ts"] <= hi_us]
    names = sorted({k for i in window for k in sums[i]})
    rows = []
    for i in window:
        row = {k: sums[i].get(k, 0.0) for k in names}
        row["updates"] = updates[i]
        seqs = cycles[i]["args"].get("seqs", [])
        row["records"] = (
            sum(record_ms[q] for q in seqs)
            if all(q in record_ms for q in seqs) else None)
        row["cycle.self"] = None if row["records"] is None else (
            row["rpc.cycle"] - row["records"] - under["rpc.cycle"][i])
        row["update.self"] = (
            row.get("rpc.update", 0.0) - under["rpc.update"][i])
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
        if ev.get("args", {}).get("span_id"):  # a span, not a lane slice
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
    columns = {
        k: vals for k in (rows[0] if rows else ()) if k != "updates"
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
    summed per iteration and reduced over the window's by `median` or
    `mean`; or, with `idle_pct`, the one part of the idle partition.
    None, and never an error, where the program gave no spans or no
    span of the window carries a selected name: the parent of the PR
    that stamps a span prints its line without that span's metric."""
    program = src.get("program")
    if not program:
        return None
    select = spec["select"]
    if spec["reduce"] == "idle_pct":
        return (program["idle"] or {}).get(select[0])
    series = [sum(row[k] for k in select) for row in program["cycles"]
              if all(row.get(k) is not None for k in select)]
    if not series:
        return None
    if spec["reduce"] == "median":
        return statistics.median(series)
    if spec["reduce"] == "mean":
        return sum(series) / len(series)
    raise ValueError(f"unknown reduction {spec['reduce']!r}")

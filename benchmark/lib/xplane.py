"""From a profiler trace (`.xplane.pb`) to device time.

Read with `jax.profiler.ProfileData`, which needs no backend. A device
plane is one named `/device:TPU:<n>`; its `XLA Ops` line holds one event
per operation that ran on the chip and its `XLA Modules` line one per
program. Busy time is the UNION of the operation intervals (operations
of overlapping programs are not counted twice), averaged over the
device planes; the window is the traced window's length where the caller
knows it (the child times start_trace to stop_trace), else from the
first to the last operation. Programs are named by `core/cycle._unique`, which makes
`jit_<kind>_<digest>` deterministic: sums are keyed by `<kind>`.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_PROGRAM = re.compile(r"^(?:jit_)?(.*?)(?:_[0-9a-f]{6,})?(?:\(\d+\))?$")


def find_trace(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def union_seconds(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def program_kind(module_name: str) -> str:
    return _PROGRAM.match(module_name.strip()).group(1) or module_name


def reduce_trace(path: str, window_s: float | None = None) -> dict | None:
    """{'busy_s', 'window_s', 'planes', 'by_program': {kind: seconds},
    'launches': {kind: n}, 'gaps': [(start_s, seconds)]} or None
    when the trace holds no device plane (a CPU rehearsal)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    per_plane, by_program, launches = [], {}, {}
    lo, hi = None, None
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
            elif line.name == MODULES_LINE:
                for e in line.events:
                    kind = program_kind(e.name)
                    by_program[kind] = (
                        by_program.get(kind, 0.0) + e.duration_ns / 1e9)
                    launches[kind] = launches.get(kind, 0) + 1
        if ops:
            per_plane.append(ops)
            lo = min(s for s, _ in ops) if lo is None else min(
                lo, min(s for s, _ in ops))
            hi = max(e for _, e in ops) if hi is None else max(
                hi, max(e for _, e in ops))
    if not per_plane:
        return None
    busy = [union_seconds(ops) / 1e9 for ops in per_plane]
    # the child times its window from start_trace's RETURN, and event
    # times count from where the trace began, inside that call: an
    # operation can end past the timed length. The window is at least
    # as long as its last operation says, so busy never counts time the
    # window does not hold (and `program_spans.idle_partition`, given
    # this window, sums to the same idle share)
    if window_s is not None:
        window_s = max(window_s, hi / 1e9)
    # idle gaps of the first device plane, longest first, as (start,
    # length) in seconds since the trace began (event times count from
    # there). With the traced window's length given, the idle stretches
    # before the first and after the last operation are gaps too.
    ops = sorted(per_plane[0])
    gaps, end = [], ops[0][1]
    if window_s is not None:
        gaps.append((0.0, ops[0][0] / 1e9))
    for s, e in ops[1:]:
        if s > end:
            gaps.append((end / 1e9, (s - end) / 1e9))
        end = max(end, e)
    if window_s is not None and window_s > end / 1e9:
        gaps.append((end / 1e9, window_s - end / 1e9))
    gaps.sort(key=lambda g: -g[1])
    n = len(per_plane)
    return {
        "busy_s": sum(busy) / n,
        "window_s": window_s if window_s is not None else (hi - lo) / 1e9,
        "planes": n,
        "by_program": {k: v / n for k, v in by_program.items()},
        "launches": {k: v // n for k, v in launches.items()},
        "gaps": gaps[:10],
    }

"""From spans, flight records and the trace to numbers.

A per-layer metric is one file, `benchmark/layers/<name>.json`: the kind
of source it reads (`client_span`, `client_latency`, `flight_phase`,
`flight_count`, `trace_ops`, `program_span`: the program's own spans,
`program_spans.py`), what it
selects there, and how the selection is reduced.
Adding a metric over an existing kind of source is adding a file and a
`BENCHMARK.json` entry: over a span a later PR stamps, too. A reader
that finds nothing to read returns None and the harness leaves the
metric out of the line.
"""

from __future__ import annotations

import math
import statistics

from . import program_spans


def percentile(values, q: float) -> float:
    """Nearest-rank percentile, q in (0, 100]: the smallest value with at
    least q% of the series at or below it."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of an empty series")
    rank = max(math.ceil(q / 100.0 * len(ordered)), 1)
    return ordered[rank - 1]


def _reduce(series, how: str):
    series = list(series)
    if not series:
        return None
    if how == "median":
        return statistics.median(series)
    if how == "p95":
        return percentile(series, 95)
    raise ValueError(f"unknown reduction {how!r}")


def read_client_span(spec: dict, src: dict):
    """Per loop iteration, the sum of the selected client walls."""
    return _reduce(
        (sum(getattr(s, f) for f in spec["select"]) * spec.get("scale", 1.0)
         for s in src["spans"] if s.offered),
        spec["reduce"],
    )


def read_client_latency(spec: dict, src: dict):
    """Due -> binding received over the pods due in the window, as the
    open loop stamps them; nothing to read in a closed loop."""
    return _reduce(src.get("latency_ms") or (), spec["reduce"])


def flight_phases(record: dict) -> dict[str, float]:
    """One `/debug/flightrecorder` record as {phase: milliseconds}, by
    the arithmetic of `core/observe.phase_seconds` (copied: the windows
    are lenses, not a partition — `device` is the HOST's wait from
    dispatch returned to decisions landed, and contains the fetch)."""
    m, ph = record.get("marks_s", {}), record.get("phases_ms", {})
    out = {"total": (record["t_end_s"] - record["t_start_s"]) * 1e3}
    fold = ph.get("fold_ms", 0.0)
    if "encode_ms" in ph:
        out["encode"] = max(ph["encode_ms"] - fold, 0.0)
    if fold > 0.0:
        out["fold"] = fold
    for name, key in (("dispatch", "dispatch_ms"),
                      ("decision_fetch", "decision_wait_ms"),
                      ("diag_lag", "diag_lag_ms"), ("compile", "compile_ms")):
        if key in ph:
            out[name] = ph[key]
    for name, a, b in (("device", "dispatch_end", "decision_end"),
                       ("bind", "apply_start", "winners_end"),
                       ("postfilter", "winners_end", "postfilter_end")):
        if a in m and b in m and m[b] >= m[a]:
            out[name] = (m[b] - m[a]) * 1e3
    return out


def read_flight_phase(spec: dict, src: dict):
    """Per server cycle that ran a dispatch, the sum of the selected
    phases; cycles that carry none of them (an empty pop) are left out."""
    series = []
    for r in src["flight"]:
        ph = flight_phases(r)
        if any(p in ph for p in spec["select"]):
            series.append(sum(ph.get(p, 0.0) for p in spec["select"])
                          * spec.get("scale", 1.0))
    return _reduce(series, spec["reduce"])


def read_flight_count(spec: dict, src: dict):
    """The window's rise of one running count of the flight records
    (`full_encodes`: the encoder's total when the record was committed)
    over the cycles it rose through: last less first, over the records
    between them. None where fewer than two records carry the count (a
    program that keeps no such count, a window of one cycle)."""
    if spec["reduce"] != "rise_per_cycle":
        raise ValueError(f"unknown reduction {spec['reduce']!r}")
    (name,) = spec["select"]
    series = [r["counts"][name] for r in src["flight"]
              if name in r.get("counts", {})]
    if len(series) < 2:
        return None
    return (series[-1] - series[0]) / (len(series) - 1)


def read_trace_ops(spec: dict, src: dict):
    """From the reduced device trace: `busy_per_launch` (busy seconds
    over launches of the program named in `per`) or `idle_pct`."""
    tr = src.get("trace")
    if not tr:
        return None
    launches = tr["launches"].get(spec.get("per", ""), 0)
    what = spec["reduce"]
    if what == "idle_pct":
        return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
    if not launches:
        return None
    if what == "busy_per_launch":
        return tr["busy_s"] / launches * spec.get("scale", 1.0)
    raise ValueError(f"unknown reduction {what!r}")


READERS = {
    "client_span": read_client_span,
    "client_latency": read_client_latency,
    "flight_phase": read_flight_phase,
    "flight_count": read_flight_count,
    "trace_ops": read_trace_ops,
    "program_span": program_spans.read,
}


def read_layer(spec: dict, src: dict):
    return READERS[spec["source_kind"]](spec, src)


def spread(values) -> float:
    """Interquartile distance over the median, as the contract takes it."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)

"""The `program_span` reader checked against itself (run by hand with the
other yardstick tests, not part of tests/):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- a hand-written event list: `Update`s grouped by the `Cycle` they
  precede, children by parent, self times, the snapshot mean;
- a span name outside the eight the reader was written for is summed
  under its own name, by descent or by when it began; a layer file that
  selects a name no span of the window carries reads None;
- on the recorded export the rows and the nine metrics equal the old
  arithmetic's (fixed lists of children), kept here for that;
- the idle partition sums to the idle share, and a span that straddles
  the traced window counts for the part inside it;
- anchors that disagree, or a trace with none, give no idle share;
- a program without the spans gives nothing at all;
- a rehearsal under --trace 1 prints the six span metrics of its cell
  (the CPU has no device plane).
"""


import json
import os
import statistics
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.lib import program_spans as ps  # noqa: E402
from benchmark.lib import reduce  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
SPECS = {}
for _m in BENCHMARK["per_layer"]:
    with open(os.path.join(ROOT, "benchmark", "layers",
                           _m["name"] + ".json")) as _f:
        _spec = json.load(_f)
    if _spec["source_kind"] == "program_span":
        SPECS[_m["name"]] = _spec
CELLS = ("sat", "default", "steady")

SPAN_METRICS = ("update_servicer_ms", "update_convert_ms", "update_apply_ms",
                "cycle_servicer_ms", "cycle_respond_ms", "cycle_snapshot_ms")
IDLE_METRICS = ("idle_in_update_pct", "idle_in_cycle_pct",
                "idle_outside_rpc_pct")


def X(name, ts_ms, dur_ms, span_id="", parent="", **args):
    return {"name": name, "ph": "X", "pid": 1, "tid": 4,
            "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
            "args": {"trace_id": "t", "span_id": span_id,
                     "parent": parent, **args}}


def update(sid, ts, convert, apply, extra=0.0):
    return [
        X("rpc.update", ts, convert + apply + extra, sid),
        X("update.convert", ts, convert, sid + "c", sid),
        X("update.apply", ts + convert, apply, sid + "a", sid),
    ]


def cycle(sid, ts, pop, record, respond, seq, snapshot=0.0, rest=0.0):
    out = [
        X("rpc.cycle", ts, 1 + pop + record + snapshot + respond + rest,
          sid, seqs=[seq]),
        X("cycle.lock_wait", ts, 1, sid + "l", sid),
        X("cycle.pop", ts + 1, pop, sid + "p", sid, pods=9),
        X(f"cycle[{seq}]", ts + 1 + pop, record, seq=seq),
        X("cycle.respond", ts + 1 + pop + record + snapshot, respond,
          sid + "r", sid),
    ]
    if snapshot:
        out.append(X("cycle.snapshot", ts + 1 + pop + record, snapshot,
                     sid + "s", sid))
    return out


# three loop iterations on the recorder's clock, in ms: two Updates
# (upsert, confirm) then a Cycle; the first iteration is warm-up,
# before the window; the third compacts
EVENTS = (
    update("w1", 0, 40, 60) + cycle("wc", 200, 10, 900, 30, seq=0)
    + update("u1", 2000, 100, 200) + update("u2", 2400, 50, 150, extra=10)
    + cycle("c1", 3000, 20, 500, 80, seq=1, rest=40)
    + update("u3", 4000, 110, 210) + update("u4", 4400, 60, 160)
    + cycle("c2", 5000, 30, 600, 100, seq=2, snapshot=300, rest=60)
    + [{"name": "thread_name", "ph": "M", "pid": 1, "tid": 4,
        "args": {"name": "agent RPCs (Update/Cycle)"}}]
)
# wall = recorder + 1000 s; the window opens after the warm-up cycle
RECORDS = [{"wall_start": 1000.0 + t, "t_start_s": t} for t in (3.021, 5.031)]
WALL = (1001.5, 1006.5)


def collected(events=EVENTS):
    return ps.collect(events, RECORDS, *WALL, None, None)


def read(name, program):
    return reduce.read_layer(SPECS[name], {"program": program})


def test_updates_are_grouped_by_the_cycle_they_precede():
    rows = collected()["cycles"]
    assert [r["updates"] for r in rows] == [2, 2]  # warm-up left out
    first, second = rows
    assert first["rpc.update"] == pytest.approx(300 + 210)
    assert first["update.convert"] == pytest.approx(150)
    assert first["update.apply"] == pytest.approx(350)
    assert first["update.self"] == pytest.approx(10)
    assert first["rpc.cycle"] == pytest.approx(1 + 20 + 500 + 80 + 40)
    assert first["records"] == pytest.approx(500)
    assert first["cycle.self"] == pytest.approx(40)
    assert second["cycle.snapshot"] == pytest.approx(300)
    assert second["cycle.self"] == pytest.approx(60)
    # an Update that begins while a Cycle is open counts with that Cycle
    late = collected(list(EVENTS) + update("u5", 5100, 5, 5))["cycles"]
    assert [r["updates"] for r in late] == [2, 3]


@pytest.mark.parametrize("cell", CELLS)
def test_span_metrics_read_the_rows(cell):
    program = collected()
    got = {m: read(f"{m}.{cell}", program) for m in SPAN_METRICS}
    assert got["update_servicer_ms"] == pytest.approx((510 + 540) / 2)
    assert got["update_convert_ms"] == pytest.approx((150 + 170) / 2)
    assert got["update_apply_ms"] == pytest.approx((350 + 370) / 2)
    assert got["cycle_servicer_ms"] == pytest.approx((641 + 1091) / 2)
    assert got["cycle_respond_ms"] == pytest.approx((80 + 100) / 2)
    # a mean over the window's cycles: one compaction of 300 ms in two
    assert got["cycle_snapshot_ms"] == pytest.approx(150)
    # no trace was given: nothing that needs both clocks is reported
    assert all(read(f"{m}.{cell}", program) is None for m in IDLE_METRICS)
    table = program["table"]
    assert table["cycles"] == 2 and table["idle_pct"] is None
    assert table["mean_ms"]["cycle.snapshot"] == pytest.approx(150)
    assert table["mean_ms"]["rpc.cycle"] == pytest.approx(sum(
        table["mean_ms"][k] for k in (
            *CYCLE_CHILDREN, "records", "cycle.self")))
    assert table["median_ms"]["cycle.self"] == pytest.approx(50)


def test_a_record_missing_from_the_export_gives_no_self_time():
    events = [e for e in EVENTS if e["name"] != "cycle[2]"]
    rows = collected(events)["cycles"]
    assert rows[1]["records"] is None and rows[1]["cycle.self"] is None
    assert rows[0]["cycle.self"] == pytest.approx(40)


def test_a_program_without_the_spans_gives_nothing():
    lanes_only = [e for e in EVENTS if e["name"].startswith("cycle[")]
    assert ps.collect(lanes_only, RECORDS, *WALL, None, None) is None
    assert ps.collect(None, RECORDS, *WALL, None, None) is None
    assert ps.collect(EVENTS, [], *WALL, None, None) is None
    for m in SPAN_METRICS + IDLE_METRICS:
        assert read(m + ".sat", None) is None


def test_interval_arithmetic():
    a = ps.merge([(5, 9), (0, 2), (1, 3), (9, 9), (8, 12)])
    assert a == [(0, 3), (5, 12)]
    assert ps.intersect(a, [(2, 6), (11, 20)]) == [(2, 3), (5, 6), (11, 12)]
    assert ps.complement(a, -1, 14) == [(-1, 0), (3, 5), (12, 14)]
    assert ps.complement(a, 1, 10) == [(3, 5)]
    assert ps.length(a) == 10


def test_idle_partition_sums_to_the_idle_share_and_clips_to_the_window():
    spans = {}
    for ev in EVENTS:
        if ev["ph"] == "X":
            spans.setdefault(ev["name"], []).append(ev)
    # the profiler's clock began 2.5 s into the recorder's: u1 (2.0-2.3)
    # lies before the window, u2 (2.4-2.61) straddles its start, c2
    # (5.0-6.091) straddles the end of a 3 s window
    offset_us = -2.5e6
    ms = 1e6  # ns
    ops = [(600 * ms, 900 * ms),  # 3.1-3.4 s: inside c1 (3.0-3.641)
           (1600 * ms, 1700 * ms),  # 4.1-4.2 s: inside u3 (4.0-4.32)
           (2950 * ms, 3100 * ms)]  # runs past the window's end
    got = ps.idle_partition(spans, offset_us, [ops], 3.0)
    busy = 300 + 100 + 50
    assert sum(got.values()) == pytest.approx((3000 - busy) / 30)
    # Cycle open: c1 641 less 300 busy, c2 500 inside less 50 busy
    assert got["rpc.cycle"] == pytest.approx((341 + 450) / 30)
    # Update open: u2's 110 inside, u3 320 less 100 busy, u4 220
    assert got["rpc.update"] == pytest.approx((110 + 220 + 220) / 30)
    assert got["outside"] == pytest.approx(
        (3000 - busy - 791 - 550) / 30)
    # both kinds open at once counts as in Cycle
    spans["rpc.update"].append(X("rpc.update", 3000, 641, "ov"))
    again = ps.idle_partition(spans, offset_us, [ops], 3.0)
    assert again == pytest.approx(got)
    # two planes: the average
    two = ps.idle_partition(spans, offset_us, [ops, []], 3.0)
    assert sum(two.values()) == pytest.approx(
        ((3000 - busy) + 3000) / 2 / 30)
    assert ps.idle_partition(spans, offset_us, [], 3.0) is None


def test_anchors_that_disagree_give_no_offset():
    good = [(1_000_000.0, 5_000_000_000.0), (3_000_000.0, 7_000_400_000.0)]
    offset, spread = ps.clock_offset_us(good)
    assert spread == pytest.approx(400.0)
    assert offset == pytest.approx(4_000_200.0)
    bad = good + [(4_000_000.0, 8_002_000_000.0)]  # 2 ms off
    offset, spread = ps.clock_offset_us(bad)
    assert offset is None and spread == pytest.approx(2000.0)
    assert ps.clock_offset_us([]) == (None, None)


def test_a_trace_without_anchors_gives_no_idle_share():
    """The recorded trace has device planes and predates the anchors:
    the span metrics are read, the idle shares are not guessed."""
    path = os.path.join(HERE, "data", "recorded.xplane.pb")
    anchors, planes = ps.read_xplane(path)
    assert anchors == [] and len(planes) >= 1 and planes[0]
    program = ps.collect(EVENTS, RECORDS, *WALL, path, 3.0)
    assert program["idle"] is None and program["table"]["anchors"] == 0
    assert read("cycle_servicer_ms.sat", program) is not None
    assert all(read(m + ".sat", program) is None for m in IDLE_METRICS)




# ---- new span names, and names nobody stamps ------------------------------

def test_a_span_outside_the_first_eight_is_summed_under_its_own_name():
    """What a later PR's span needs: a layer file and an entry, no edit
    here. `encode.arena` descends from an `rpc.cycle` two levels down
    (through a span of another new name); `gc.pass` has no parent, and
    belongs to the iteration in which it began."""
    events = list(EVENTS) + [
        X("cycle.encode", 3025, 200, "e1", "c1"),
        X("encode.arena", 3030, 70, "e1a", "e1"),
        X("encode.arena", 3110, 30, "e1b", "e1"),
        X("encode.arena", 5040, 45, "e2a", "c2"),
        X("gc.pass", 2700, 400, "g1"),  # between u2 and c1: iteration 1
        X("gc.pass", 3650, 90, "g2"),  # after c1 ended: iteration 2
        X("gc.pass", 5500, 110, "g3"),  # inside c2
        X("gc.pass", 9000, 50, "g4"),  # after the last Cycle: nobody's
        X("gc.pass", 100, 60, "g0"),  # warm-up: outside the window
    ]
    program = collected(events)
    first, second = program["cycles"]
    assert first["encode.arena"] == pytest.approx(100)
    assert second["encode.arena"] == pytest.approx(45)
    assert first["cycle.encode"] == pytest.approx(200)
    assert second["cycle.encode"] == 0.0  # carried by the window, not here
    assert first["gc.pass"] == pytest.approx(400)
    assert second["gc.pass"] == pytest.approx(90 + 110)
    # self time is less the DIRECT children only, whatever their names
    assert first["cycle.self"] == pytest.approx(40 - 200)
    assert second["cycle.self"] == pytest.approx(60 - 45)
    # the eight old names read as before
    plain = collected()["cycles"]
    for old, new in zip(plain, program["cycles"]):
        for k in ("rpc.update", "update.convert", "update.apply",
                  "update.self", "rpc.cycle", "cycle.pop", "cycle.respond",
                  "cycle.snapshot", "records", "updates"):
            assert new[k] == pytest.approx(old[k]), k
    layer = {"source_kind": "program_span", "reduce": "median"}
    assert reduce.read_layer(
        {**layer, "select": ["gc.pass"]},
        {"program": program}) == pytest.approx((400 + 200) / 2)
    assert reduce.read_layer(
        {**layer, "select": ["encode.arena", "gc.pass"], "reduce": "mean"},
        {"program": program}) == pytest.approx((500 + 245) / 2)
    assert program["table"]["mean_ms"]["gc.pass"] == pytest.approx(300)


@pytest.mark.parametrize("how", ["median", "mean", "idle_pct"])
def test_a_name_no_span_carries_reads_none_and_never_raises(how):
    """The parent of the PR that stamps `gc.pass` runs under that PR's
    layer file: its line leaves the metric out."""
    spec = {"source_kind": "program_span", "select": ["gc.pass"],
            "reduce": how}
    program = collected()
    assert reduce.read_layer(spec, {"program": program}) is None
    # beside a name the window does carry, too: no half sum
    both = dict(spec, select=["rpc.cycle", "gc.pass"])
    if how != "idle_pct":
        assert reduce.read_layer(both, {"program": program}) is None
    # carried only before the window: not a span of the window
    early = collected(list(EVENTS) + [X("gc.pass", 100, 60, "g0")])
    assert reduce.read_layer(spec, {"program": early}) is None
    # a window with no Cycle in it, a run with no program export
    empty = ps.collect(EVENTS, RECORDS, 2000.0, 2001.0, None, None)
    assert empty["cycles"] == []
    assert reduce.read_layer(spec, {"program": empty}) is None
    assert reduce.read_layer(spec, {"program": None}) is None
    assert reduce.read_layer(spec, {}) is None
    # a row whose `records` is missing is left out, not added as None
    gone = collected([e for e in EVENTS if e["name"] != "cycle[2]"])
    assert reduce.read_layer(
        dict(spec, select=["records"], reduce="median"),
        {"program": gone}) == pytest.approx(500)


# ---- the old arithmetic, kept to hold the nine metrics where they were -----

UPDATE_CHILDREN = ("update.convert", "update.apply")
CYCLE_CHILDREN = ("cycle.lock_wait", "cycle.pop", "cycle.snapshot",
                  "cycle.respond")


def old_cycle_rows(spans, record_ms, lo_us, hi_us):
    """`program_spans.cycle_rows` as PR 25 wrote it: fixed lists."""
    cycles = sorted(spans.get("rpc.cycle", ()), key=lambda s: s["ts"])
    updates = sorted(spans.get("rpc.update", ()), key=lambda s: s["ts"])
    children = {}
    for name in UPDATE_CHILDREN + CYCLE_CHILDREN:
        for s in spans.get(name, ()):
            by = children.setdefault(s["args"]["parent"], {})
            by[name] = by.get(name, 0.0) + s["dur"] / 1e3
    rows, u = [], 0
    for c in cycles:
        mine = []
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


def old_read(spec, rows):
    series = [sum(row[k] for k in spec["select"]) for row in rows]
    if spec["reduce"] == "median":
        return statistics.median(series)
    return sum(series) / len(series)


def recorded_export():
    with open(os.path.join(HERE, "data", "recorded_spans.json")) as f:
        rec = json.load(f)
    spans, record_ms = {}, {}
    for ev in rec["events"]:
        if ev.get("ph") != "X":
            continue
        if ev["name"].startswith(("rpc.", "update.", "cycle.")):
            spans.setdefault(ev["name"], []).append(ev)
        elif ev["name"].startswith("cycle["):
            record_ms[int(ev["name"][6:-1])] = ev["dur"] / 1e3
    delta = statistics.median(
        r["wall_start"] - r["t_start_s"] for r in rec["records"])
    lo, hi = ((w - delta) * 1e6 for w in rec["wall"])
    return rec, old_cycle_rows(spans, record_ms, lo, hi)


def test_rows_on_the_recorded_export_equal_the_old_arithmetic():
    rec, old = recorded_export()
    program = ps.collect(rec["events"], rec["records"], *rec["wall"],
                         None, None)
    new = program["cycles"]
    assert len(new) == len(old) >= 5
    assert any(r["cycle.snapshot"] > 0 for r in old), "a compaction ran"
    for mine, theirs in zip(new, old):
        assert set(theirs) <= set(mine)
        for k, v in theirs.items():
            assert mine[k] == pytest.approx(v, rel=1e-12, abs=1e-9), k


@pytest.mark.parametrize("metric", SPAN_METRICS)
@pytest.mark.parametrize("cell", CELLS)
def test_the_nine_metrics_on_the_recorded_export_keep_their_values(
        metric, cell):
    """Six of the nine are read from rows; the three idle shares come
    from `idle_partition`, which this PR did not touch (tested above)."""
    rec, old = recorded_export()
    program = ps.collect(rec["events"], rec["records"], *rec["wall"],
                         None, None)
    spec = SPECS[f"{metric}.{cell}"]
    assert read(f"{metric}.{cell}", program) == pytest.approx(
        old_read(spec, old), rel=1e-12)


def test_every_cell_has_the_nine_as_entries_of_its_own():
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    for cell, workload, moves in (
            ("sat", "sp5000-mixed.sat", "pods_bound_per_s"),
            ("default", "sp5000-default.sat", "pods_bound_per_s"),
            ("steady", "sp5000-mixed.steady", "pods_bound_per_s")):
        for m in SPAN_METRICS + IDLE_METRICS:
            entry = BENCHMARK["per_layer"][names.index(f"{m}.{cell}")]
            assert entry["workloads"] == [workload]
            assert entry["moves"] == moves
            assert SPECS[entry["name"]]["source_kind"] == "program_span"
    # the count is BENCHMARK.json's: the nine and `gc_pass_ms`, a cell
    stems = SPAN_METRICS + IDLE_METRICS + ("gc_pass_ms",)
    assert len(SPECS) == sum(
        m["name"].split(".")[0] in stems for m in BENCHMARK["per_layer"])


def test_rehearsal_prints_the_span_metrics():
    """A whole rehearsal under --trace 1: the harness fetches the spans
    while the child lives, and since the CPU has no device plane the
    line carries the span metrics of its cell and none of the three
    idle shares (nine with a device plane, on the chip)."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--rehearse", "--workload", "sp5000-mixed.steady", "--trace", "1",
         "--seed", "3000000019", "--seconds", "3"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    said = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    table = next(d["program_spans"] for d in said if "program_spans" in d)
    assert table["cycles"] >= 1 and table["anchors"] >= 1
    assert table["anchor_spread_us"] <= ps.ANCHOR_SPREAD_US
    line = next(d["would_print"] for d in said if "would_print" in d)
    assert line["correct"] is True
    got = line["metrics"]
    for m in SPAN_METRICS:
        if m == "cycle_snapshot_ms":  # no compaction in 3 s: left out
            continue
        assert got[m + ".steady"]["value"] >= 0.0, m
        assert m + ".sat" not in got
    assert not any(m + ".steady" in got for m in IDLE_METRICS)
    # inside-out is no larger than outside-in
    assert (got["cycle_servicer_ms.steady"]["value"]
            <= got["cycle_rpc_ms.steady"]["value"])
    assert (got["update_servicer_ms.steady"]["value"]
            <= got["update_rpc_ms.steady"]["value"])
    # means add where medians need not
    mean = table["mean_ms"]
    assert (mean["update.convert"] + mean["update.apply"]
            <= mean["rpc.update"] * 1.001)

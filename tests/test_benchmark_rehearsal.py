"""The benchmark's own rehearsal, in every cell, traced and untraced.

`benchmark/run.py` (`BENCHMARK.json` `command`) judges every PR on the
chip. Its `--rehearse` mode runs the same harness against the same
served program on the CPU at cut sizes: the server child starts, prints
its `build:` and `encoder:` lines, serves `Update` / `Cycle`, answers
`/debug/traces` and `/debug/flightrecorder`, and exits sealed on
SIGTERM; every binding is checked against `benchmark/lib/reference.py`.
A product change that breaks one of those contacts (a span or a flight
phase renamed, a `/debug` field, a YAML key, a log line) fails here and
not in the driver's check a PR later. No number of a rehearsal is a
measurement, and none is asserted.

The cells come from `BENCHMARK.json`, so a cell a later PR adds is
covered without an edit. This file is one xdist worker's (`--dist
loadfile`): the cases share `.bench/` (git-ignored: `cache-cpu` and the
server logs, ~10 MB) and run one after another.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)

# read from spans and flight marks in every window, so printed by every
# traced rehearsal (`cycle_snapshot_ms` is left out where no compaction
# ran; the CPU has no device plane for `device_busy_ms` and the idle
# shares)
ALWAYS_READ = (
    "update_servicer_ms", "update_convert_ms", "update_apply_ms",
    "cycle_servicer_ms", "cycle_respond_ms", "encode_ms", "apply_ms",
    "device_wait_ms", "gc_pass_ms",
    # the agent's own spans (PR 40), shipped into the server's ring:
    # one file and entry each, read in every cell
    "client_batch_ms", "client_build_ms", "client_send_ms",
    "client_ack_wait_ms", "client_update_ms", "client_cycle_ms",
)
# ... and from the loser loop's two spans (PR 36), in a cell whose
# configuration states `unschedulable.count` > 0: its queue holds pods
# that fit nowhere, and their backoff (2 s after the warm-up has refused
# them twice) runs out inside the rehearsal's 4 s window. A
# configuration with none (`sp500-basic`: the source has no such pod)
# refuses nothing and stamps neither span. The counts beside them
# (`commit_rounds_per_cycle`, `rounds_parked_per_cycle`,
# `refusals_per_cycle`, and `gc_sweeps_per_cycle`,
# `fold_declined_per_cycle`) need two records in the window to rise
# through, like `full_encodes_per_cycle`: printed, not owed
WHERE_PODS_FIT_NOWHERE = ("loser_loop_ms", "postfilter_ms")
# ... but for the servicer's count of `Update` RPCs (PR 39), which every
# record of a served scheduler carries: every cell's rehearsal runs
# several cycles, and each follows at least its two `Update`s
RPCS_PER_CYCLE = "update_rpcs_per_cycle"
# ... and for the collector policy's two counts (PRs 38 and 41), which
# every record of a server that `main()` started carries: 0.0 or more
COLLECTOR_COUNTS = ("gc_sweeps_per_cycle", "gc_sweeps_deferred_per_cycle")


def owed_by(cell: str) -> tuple:
    (entry,) = [w for w in BENCHMARK["workloads"] if w["name"] == cell]
    with open(os.path.join(
            ROOT, "benchmark", "configs", entry["config"] + ".json")) as f:
        stuck = json.load(f).get("unschedulable", {}).get("count", 0)
    return ALWAYS_READ + (WHERE_PODS_FIT_NOWHERE if stuck else ())


@pytest.mark.parametrize("trace", (0, 1), ids=("untraced", "traced"))
@pytest.mark.parametrize(
    "cell", [w["name"] for w in BENCHMARK["workloads"]])
def test_rehearsal(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, as the cell's one chip
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--rehearse",
         "--workload", cell, "--trace", str(trace),
         "--seed", "3000000019"],
        cwd=ROOT, env=env, text=True, capture_output=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    rows = [json.loads(ln) for ln in out.stdout.splitlines()]
    (row,) = [r for r in rows if "would_print" in r]
    assert (row["rehearsal"], row["trace"]) == (cell, trace)
    line = row["would_print"]
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0
    for name, (value, limit) in line["compared"].items():
        assert value <= limit, (name, value, limit)
    # pods finish: the delete path ran, the resident set is held at the
    # cut target (a cycle of the open loop that follows one which bound
    # little may start a pod under it; none starts over it:
    # `resident_over_target`), and the replay and the server agree on
    # what is resident
    (facts,) = [r["facts"] for r in rows if "facts" in r]
    assert facts["completed"] > 0
    assert facts["resident_at_start"][1] == facts["resident_target"]
    for name in ("bad_completions", "resident_over_target",
                 "server_resident_drift"):
        assert line["compared"][name] == [0, 0], name
    printed = set(line["metrics"])
    if not trace:
        assert {"pods_bound_per_s", "setup_s"} <= printed
        return
    of_cell = {m["name"] for m in BENCHMARK["per_layer"]
               if cell in m["workloads"]}
    assert printed <= of_cell, printed - of_cell
    bases = owed_by(cell)
    owed = {n for n in of_cell if n.split(".")[0] in bases}
    assert len(owed) == len(bases), owed
    assert owed <= printed, owed - printed
    assert line["metrics"][RPCS_PER_CYCLE]["value"] >= 2.0
    for name in COLLECTOR_COUNTS:
        assert line["metrics"][name]["value"] >= 0.0

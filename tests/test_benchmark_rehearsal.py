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

The cells come from `BENCHMARK.json`, and what a cell owes comes from
its own files (PR 44), so a cell a later PR adds is covered without an
edit: completions are owed where its traffic file has a `completions`
block, the loser loop's spans where its configuration states stuck
pods, preemption's facts and metrics where its configuration has
`templates` of more than one priority, and of the stems below those
that its own `per_layer` entries name. The five cells accepted before
PR 44 are named, and owe every stem: none of theirs can be dropped
unseen. This file is one xdist worker's (`--dist loadfile`): the cases
share `.bench/` (git-ignored: `cache-cpu` and the server logs, ~10 MB)
and run one after another.
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark.lib import agent, generate, reference  # noqa: E402
from benchmark.lib.child import Server  # noqa: E402

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
# record of a served scheduler carries: a cell whose iterations all
# offer and confirm follows each cycle with at least its two `Update`s
# (one whose iterations mostly offer and confirm nothing,
# `sp5000-preempt.sat`, reads 1.75 and has no such entry)
RPCS_PER_CYCLE = "update_rpcs_per_cycle"
# ... and for the collector policy's two counts (PRs 38 and 41), which
# every record of a server that `main()` started carries: 0.0 or more
COLLECTOR_COUNTS = ("gc_sweeps_per_cycle", "gc_sweeps_deferred_per_cycle")
# ... and, in a cell whose configuration has `templates` of more than
# one priority (PR 44: pending pods that fit only once lower ones are
# evicted), from the loser loop's two spans and from the counts every
# record keeps of ITS cycle (`mean_per_cycle` reads one record)
WHERE_PODS_PREEMPT = (
    "postfilter_ms", "loser_loop_ms", "nominations_per_cycle",
    "victims_per_cycle", "bound_per_cycle", "backoff_held_per_cycle",
    "nominated_dispatched_per_cycle", "nominated_bound_per_cycle",
)
CHECK_F = ("bad_nominations", "bad_evictions", "victims_not_lower",
           "nominations_without_room", "needless_victims")
# the cells the benchmark had before PR 44 owe every stem of
# `ALWAYS_READ`; a later cell owes those its own entries name
FULL_DUTY = (
    "sp5000-mixed.sat", "sp5000-mixed.steady", "sp5000-default.sat",
    "sp5000-unschedulable.sat", "sp500-basic.sat",
)


def load(*parts) -> dict:
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def files_of(cell: str) -> tuple:
    """The cell's configuration and traffic file."""
    (entry,) = [w for w in BENCHMARK["workloads"] if w["name"] == cell]
    return (load("configs", entry["config"] + ".json"),
            load("workloads", cell + ".json"))


def preempts(cfg: dict) -> bool:
    return len({p for t in cfg.get("templates", {}).values()
                for p in t["priorities"]}) > 1


def owed_by(cell: str, cfg: dict, of_cell: set) -> set:
    """The entries of the cell that a traced rehearsal must print: of
    `ALWAYS_READ` those the cell names, and one each of what its files
    call for."""
    must = set(ALWAYS_READ if cell in FULL_DUTY else ())
    if cfg.get("unschedulable", {}).get("count", 0):
        must.update(WHERE_PODS_FIT_NOWHERE)
    if preempts(cfg):
        must.update(WHERE_PODS_PREEMPT)
    stems = {n: n.split(".")[0] for n in of_cell}
    owed = {n for n, stem in stems.items()
            if stem in must or stem in ALWAYS_READ}
    assert sorted(stems[n] for n in owed if stems[n] in must) == sorted(
        must), owed
    return owed


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
    (facts,) = [r["facts"] for r in rows if "facts" in r]
    cfg, traffic = files_of(cell)
    if "completions" in traffic:
        # pods finish: the delete path ran and the resident set is held
        # at the cut target (a cycle of the open loop that follows one
        # which bound little may start a pod under it; none starts over
        # it: `resident_over_target`)
        assert facts["completed"] > 0
        assert facts["resident_at_start"][1] == facts["resident_target"]
        for name in ("bad_completions", "resident_over_target"):
            assert line["compared"][name] == [0, 0], name
    else:
        assert facts["completed"] == 0
    # the replay and the server agree on what is resident
    assert line["compared"]["server_resident_drift"] == [0, 0]
    if preempts(cfg):
        # preemption is the work, and check (f) judged it
        assert facts["nominations"] > 0 and facts["victims"] > 0
        for name in CHECK_F:
            assert line["compared"][name] == [0, 0], name
    printed = set(line["metrics"])
    if not trace:
        assert {"pods_bound_per_s", "setup_s"} <= printed
        return
    of_cell = {m["name"] for m in BENCHMARK["per_layer"]
               if cell in m["workloads"]}
    assert printed <= of_cell, printed - of_cell
    owed = owed_by(cell, cfg, of_cell)
    assert owed <= printed, owed - printed
    if cell in FULL_DUTY:
        assert {RPCS_PER_CYCLE, *COLLECTOR_COUNTS} <= of_cell
    if RPCS_PER_CYCLE in of_cell:
        assert line["metrics"][RPCS_PER_CYCLE]["value"] >= 2.0
    for name in of_cell.intersection(COLLECTOR_COUNTS):
        assert line["metrics"][name]["value"] >= 0.0


def test_the_servers_count_follows_the_deletes_of_a_cycles_victims(
        monkeypatch):
    """`server_resident_drift` holds the server's own
    `scheduler_cache_size{type="pods"}` to the replay's count after the
    LAST cycle. Preemption's victims leave the replay in the cycle that
    evicted them and the server's cache when the agent's next `Update`
    deletes them, so a run that ends on an evicting cycle reads 0 only
    if the gauge is stamped when that `Update` has been applied
    (PR 44; before it, at a cycle's end alone: such a run read that
    cycle's victims, `correct: false` with nothing else amiss). Driven
    as `run.py` drives `sp5000-preempt.sat`'s rehearsal and stopped at
    the first cycle whose response carries evictions: where a 1 s
    window happens to end is not relied on."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # one CPU device
    cfg, traffic = files_of("sp5000-preempt.sat")
    assert preempts(cfg) and "completions" not in traffic
    cut = {k: v for k, v in cfg["rehearse"].items() if k != "server"}
    seed = 3000000019
    dep = generate.deployment(cfg, seed, cut)
    workdir = os.path.join(bench_run.SCRATCH, f"victims-{seed}")
    cache = os.path.join(bench_run.SCRATCH, "cache-cpu")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    os.makedirs(cache, exist_ok=True)
    server = Server(
        ROOT, workdir, bench_run.server_yaml(cfg, True, workdir),
        aot_dir=os.path.join(cache, "aot"),
        jax_cache_dir=os.path.join(cache, "jax"), traced=False)
    drv = agent.Driver(server.grpc_port, dep, None, seed)
    try:
        server.started(require_tpu=False, chips=1)
        drv.load()
        drv.step(dep.pending(dep.cfg["depth"], "warm"))
        for _ in range(50):
            if drv.cycles[-1].evictions:
                break
            drv.step([])
        victims = len(drv.cycles[-1].evictions)
        assert victims and len(drv.cycles[-1].nominations) * 3 == victims
        held = server.metrics()['scheduler_cache_size{type="pods"}']
        verdict = reference.check_run(
            dep.nodes, dep.init, drv.pods, drv.cycles, dep.pools,
            drv.probe_rounds, drv.resident_target)
        server.stop()
    finally:
        drv.close()
        server.kill()
        shutil.rmtree(workdir, ignore_errors=True)
    assert verdict.ok, verdict.problems
    # the replay took this cycle's victims out, and so has the server
    assert verdict.resident_after[-1] == drv.resident
    assert int(held) == verdict.resident_after[-1], victims


def tight_skew_breaches(dep, pods: dict, cycles: list) -> list:
    """The run replayed through `reference.Cluster` under the TIGHT
    reading of DoNotSchedule skew: for every pod a cycle bound and every
    constraint it carries, the count of its domain at the cycle's END
    less the minimum over the domains at its END is at most `maxSkew`
    (every carrier of the cell has one `maxSkew`, so the bound at
    placement time shows at the end). The harness's check (c) holds the
    count at the cycle's START + 1 against that minimum, which cannot
    see a zone over-filled within one cycle (PERF.md section 7: the
    tight form is owed to a `benchmark` PR)."""
    cl = reference.Cluster(dep.nodes)
    for pod, node in dep.init:
        cl.add(pod, cl.index[node])
    found = []
    for ci, cyc in enumerate(cycles):
        for uid in cyc.completed:
            cl.remove(uid)
        for uid, node in cyc.bindings:
            cl.add(pods[uid], cl.index[node])
        for uid, node in cyc.bindings:
            for key, sel, skew in reference._terms(pods[uid]).spread:
                here = cl.per_domain(key, cl.match[sel])
                d = cl.domain(key)[0][cl.index[node]]
                if here[d] - here.min() > skew:
                    found.append((ci, uid, int(here[d] - here.min())))
        for uid, _node in cyc.evictions:
            cl.remove(uid)
    return found


def test_one_spread_group_holds_the_tight_skew_rule_at_every_cycles_end(
        monkeypatch):
    """`sp5000-spread.sat`'s rehearsal, driven as `run.py` drives it:
    every cycle binds ~250 pods of ONE spread group (`maxSkew` 1, six
    zones). The run passes the harness's own check and the tight rule
    above; the same run with its last cycle's spread pods moved into the
    zone that stood lowest at that cycle's start passes check (c), whose
    count is taken at the cycle's START, and fails the tight rule: the
    control that shows the tight rule sees what (c) cannot."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)  # one CPU device
    cfg, traffic = files_of("sp5000-spread.sat")
    cut = {k: v for k, v in cfg["rehearse"].items() if k != "server"}
    seed = 3000000019
    dep = generate.deployment(cfg, seed, cut)
    depth = dep.cfg["depth"]
    workdir = os.path.join(bench_run.SCRATCH, f"tight-{seed}")
    cache = os.path.join(bench_run.SCRATCH, "cache-cpu")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    os.makedirs(cache, exist_ok=True)
    server = Server(
        ROOT, workdir, bench_run.server_yaml(cfg, True, workdir),
        aot_dir=os.path.join(cache, "aot"),
        jax_cache_dir=os.path.join(cache, "jax"), traced=False)
    drv = agent.Driver(server.grpc_port, dep, traffic["completions"], seed)
    try:
        server.started(require_tpu=False, chips=1)
        drv.load()
        seconds = 2.0  # run.py's own budget for a window of that length
        window_pods = dep.pending(int(
            traffic["rehearse"]["pods_budget_per_s"] * (seconds + 5))
            + depth, "pod")
        drv.warm(dep.pending(depth, "warm"))
        drv.run_closed(window_pods, depth, seconds)
        server.stop()
    finally:
        drv.close()
        server.kill()
        shutil.rmtree(workdir, ignore_errors=True)

    def verdict(cycles):
        return reference.check_run(
            dep.nodes, dep.init, drv.pods, cycles, dep.pools,
            drv.probe_rounds, drv.resident_target)

    assert len(drv.cycles) >= 4
    sound = verdict(drv.cycles)
    assert sound.ok, sound.problems
    assert sound.counts["refused"] == 0
    assert tight_skew_breaches(dep, drv.pods, drv.cycles) == []

    # the control: the last cycle's spread pods, all into one zone
    last = drv.cycles[-1]
    spread = [u for u, _n in last.bindings
              if reference._terms(drv.pods[u]).spread]
    assert len(spread) > 100
    cl = reference.Cluster(dep.nodes)
    for pod, node in dep.init:
        cl.add(pod, cl.index[node])
    for cyc in drv.cycles[:-1]:
        for uid in cyc.completed:
            cl.remove(uid)
        for uid, node in cyc.bindings:
            cl.add(drv.pods[uid], cl.index[node])
    for uid in last.completed:
        cl.remove(uid)
    key, sel, skew = reference._terms(drv.pods[spread[0]]).spread[0]
    assert skew == 1
    lowest = int(np.argmin(cl.per_domain(key, cl.watch(sel))))
    plain = len(dep.nodes) - sum(len(p.nodes) for p in dep.pools)
    into = [dep.nodes[i].name for i in range(plain)
            if cl.domain(key)[0][i] == lowest]
    moved = dict(zip(spread, (into[j % len(into)]
                              for j in range(len(spread)))))
    altered = drv.cycles[:-1] + [dataclasses.replace(
        last, bindings=[(u, moved.get(u, n)) for u, n in last.bindings])]
    loose = verdict(altered)
    assert loose.counts["constraint_breaches"] == [0, 0], loose.problems
    breaches = tight_skew_breaches(dep, drv.pods, altered)
    assert breaches and {ci for ci, _u, _s in breaches} == {
        len(altered) - 1}
    assert max(s for _c, _u, s in breaches) > 100

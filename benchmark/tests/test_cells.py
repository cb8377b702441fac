"""The benchmark's data files checked against `BENCHMARK.json` (run by hand,
with the rest of `benchmark/tests`):

- every configuration, cell and per-layer metric `BENCHMARK.json` names has
  its files, and they say what the entry says;
- `sp5000-default` is `sp5000-mixed` with `percentageOfNodesToScore` left
  out and nothing else, and its cell and eighteen metrics are the
  saturated cell's under their own names;
- every configuration's E pad is the power of two above the resident
  set it states (`resident_target` + `depth` + the probe loads: no rate
  input since PR 35), and a run that outgrows its pad says so:
  `existing_over_pad` over 0, `correct: false`;
- every per-layer metric's reader returns None, and never raises, over a
  run that has nothing for it: no loop iteration, flight records without
  phases, no trace. A program that lacks what a metric reads (the parent
  of the PR that adds it) then prints a line without that metric;
- the rehearsal passes in every cell, traced and untraced.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest
import yaml

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark.lib import reduce  # noqa: E402

DEFAULT_METRICS = (
    "update_rpc_ms", "cycle_rpc_ms", "encode_ms", "apply_ms",
    "device_wait_ms", "device_busy_ms", "device_idle_pct",
    "update_servicer_ms", "update_convert_ms", "update_apply_ms",
    "cycle_servicer_ms", "cycle_respond_ms", "cycle_snapshot_ms",
    "idle_in_update_pct", "idle_in_cycle_pct", "idle_outside_rpc_pct",
    "gc_pass_ms", "full_encodes_per_cycle",
)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return yaml.safe_load(f) if parts[-1].endswith(".yaml") \
            else json.load(f)


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)


@pytest.mark.parametrize(
    "entry", BENCHMARK["configs"], ids=lambda e: e["name"])
def test_configuration_files(entry):
    assert entry["file"] == f"benchmark/configs/{entry['name']}.json"
    cfg = load("configs", entry["name"] + ".json")
    assert cfg["name"] == entry["name"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    server = load("configs", cfg["server_config"])
    assert server["padExisting"] >= cfg["init_pods"] + cfg["depth"]
    assert cfg["rehearse"]["server"]["padExisting"] < server["padExisting"]
    cells = [w for w in BENCHMARK["workloads"] if w["config"] == cfg["name"]]
    assert cells, "a configuration that no cell runs is never measured"


def pad_by_rule(cfg: dict) -> tuple[int, int]:
    """(what a run may hold, the power of two above it): the rule of
    `sp5000-mixed.json`'s `assumed`, from the file's own sizes. The
    resident set at a cycle's start, what one cycle can bind on top of
    it, and the probe pools' load pods; no rate and no run length."""
    holds = (
        cfg["resident_target"] + cfg["depth"]
        + cfg["probe"]["pools"] * cfg["probe"]["nodes_per_pool"])
    return holds, 1 << holds.bit_length()


@pytest.mark.parametrize(
    "entry", BENCHMARK["configs"], ids=lambda e: e["name"])
def test_the_existing_pad_is_what_the_configurations_rule_gives(entry):
    cfg = load("configs", entry["name"] + ".json")
    holds, pad = pad_by_rule(cfg)
    server = load("configs", cfg["server_config"])
    assert server["padExisting"] == pad, (holds, pad)
    # the window opens under the target and the warm-up batch fills it
    assert cfg["init_pods"] + cfg["depth"] <= cfg["resident_target"] + 64
    # the rate inputs are no inputs: at 0 the old formula, which tier 1
    # (tests/test_benchmark_pads.py) still computes, gives the same pad
    rule = cfg["pad_rule"]
    assert (rule["rate_ref_pods_per_s"], rule["factor"],
            rule["iteration_s"]) == (0, 0.0, 0.0)
    cut = cfg["rehearse"]
    assert cut["resident_target"] + cut["depth"] < cut["server"]["padExisting"]
    assert cut["init_pods"] < cut["resident_target"]


def test_the_mixed_pad_rule_in_numbers():
    cfg = load("configs", "sp5000-mixed.json")
    holds, pad = pad_by_rule(cfg)
    assert holds == 140000 + 10000 + 64 == 150064 and pad == 262144
    assert any(a.startswith("padExisting: 262144") and "150,064" in a
               for a in cfg["assumed"])
    assert cfg["reduced"] == {}


@pytest.mark.parametrize(
    "cell", BENCHMARK["workloads"], ids=lambda c: c["name"])
def test_cell_files(cell):
    traffic = load("workloads", cell["name"] + ".json")
    assert (traffic["name"], traffic["config"], traffic["traffic"]) == (
        cell["name"], cell["config"], cell["traffic"])
    assert traffic["why"] == cell["why"]
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert traffic["loop"] in ("closed_depth", "open_rate")
    # pods finish in every cell, in the one order the agent draws
    assert traffic["completions"] == {"order": "uniform"}
    assert any(a.startswith("completions:") for a in traffic["assumed"])
    reported = [m for m in BENCHMARK["per_layer"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
    assert reported, "every cell reports a per-layer metric"


@pytest.mark.parametrize(
    "entry", BENCHMARK["per_layer"], ids=lambda e: e["name"])
def test_layer_files_and_their_readers_on_an_empty_run(entry):
    spec = load("layers", entry["name"] + ".json")
    for key in ("name", "layer", "moves", "workloads", "unit"):
        assert spec[key] == entry[key], key
    assert spec["source_kind"] in reduce.READERS
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    assert set(entry["workloads"]) <= cells
    # a window in which nothing ran, a program whose records carry no
    # mark or phase, a server that wrote no trace, a program that gave
    # no span (or none in the window, or no anchors), a trace in which
    # the program the metric names was never launched
    bare = {"t_start_s": 0.0, "t_end_s": 1.0}
    for src in (
        {"spans": [], "flight": [], "trace": None, "program": None},
        {"spans": [], "flight": [bare, dict(bare, marks_s={}, phases_ms={})],
         "trace": None,
         "program": {"cycles": [], "idle": None, "table": {}}},
        {"spans": [], "flight": [],
         "program": {"cycles": [{"another.span": 1.0, "updates": 0,
                                 "records": None, "cycle.self": None}],
                     "idle": {}, "table": {}}},
        {"spans": [], "flight": []},
    ):
        assert reduce.read_layer(spec, src) is None
    if spec["source_kind"] == "trace_ops" and "per" in spec:
        other = {"launches": {"another_program": 3}, "busy_s": 1.0,
                 "window_s": 2.0}
        assert reduce.read_layer(
            spec, {"spans": [], "flight": [], "trace": other}) is None


def test_sp5000_default_is_sp5000_mixed_at_the_stock_percentage():
    mixed, default = (load("configs", n + ".json")
                      for n in ("sp5000-mixed", "sp5000-default"))
    for key in ("nodes", "pods", "init_pods", "resident_target", "depth",
                "probe", "plugins", "precision", "reduced", "pad_rule"):
        assert default[key] == mixed[key], key
    assert default["nodes"] == {
        "count": 5000, "cpu": "4", "memory": "32Gi", "pods": 110,
        "taint_fraction": 0.1}
    assert (default["init_pods"], default["resident_target"],
            default["depth"]) == (130000, 140000, 10000)
    assert (default["pods"]["cpu"], default["pods"]["memory"],
            default["pods"]["num_apps"]) == ("100m", "500Mi", 500)
    assert default["guarantees"][:4] == mixed["guarantees"]
    assert default["guarantees"][4:] == [
        "a pod with fewer than k feasible nodes is offered all of them"]
    assert any("nextStartNodeIndex" in a for a in default["assumed"])
    # the rehearsal: 160 nodes, adaptive 49%, k = 100 < 160
    cut_m, cut_d = mixed["rehearse"], default["rehearse"]
    assert cut_d["nodes"]["count"] == 160
    assert "percentageOfNodesToScore" not in cut_d["server"]
    assert cut_d["server"] == {k: v for k, v in cut_m["server"].items()
                               if k != "percentageOfNodesToScore"}
    assert {k: v for k, v in cut_d.items() if k != "server"} == {
        k: v for k, v in cut_m.items() if k != "server"}
    y_m, y_d = (load("configs", n + ".yaml")
                for n in ("sp5000-mixed", "sp5000-default"))
    assert y_m.pop("percentageOfNodesToScore") == 100
    assert "percentageOfNodesToScore" not in y_d
    # the two YAMLs differ in exactly that one key, the pad included
    assert y_d == y_m and y_d["padExisting"] == 262144
    entry = next(c for c in BENCHMARK["configs"]
                 if c["name"] == "sp5000-default")
    assert "numFeasibleNodesToFind" in entry["source"]
    assert "5000Nodes_10000Pods" in entry["source"]


def test_sp5000_default_sat_is_the_saturated_cell_under_its_own_names():
    cell = next(w for w in BENCHMARK["workloads"]
                if w["name"] == "sp5000-default.sat")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sp5000-default", "sat", 1)
    sat, mine = (load("workloads", n + ".sat.json")
                 for n in ("sp5000-mixed", "sp5000-default"))
    assert (mine["loop"], mine["pods_budget_per_s"], mine["trace_s"]) == (
        "closed_depth", 5000, 12.0)
    assert mine["rehearse"] == sat["rehearse"]
    for metric in DEFAULT_METRICS:
        theirs = load("layers", metric + ".sat.json")
        ours = load("layers", metric + ".default.json")
        assert ours.pop("name") == metric + ".default"
        assert ours.pop("workloads") == ["sp5000-default.sat"]
        assert ours["moves"] == "pods_bound_per_s"
        del theirs["name"], theirs["workloads"]
        assert ours == theirs, metric
    mine_in_bench = [m["name"] for m in BENCHMARK["per_layer"]
                     if m.get("workloads") == ["sp5000-default.sat"]]
    assert mine_in_bench == [m + ".default" for m in DEFAULT_METRICS]


def test_a_run_that_outgrows_its_pad_says_so(tmp_path):
    """A copy of the benchmark whose rehearsal pad (512) is under the
    resident set its rehearsal holds (520 + a cycle's 248): the line
    reads `correct: false` and `existing_over_pad` says why, beside
    whatever the program did about it (regimes compiled inside the
    window)."""
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy)
    os.symlink(os.path.join(ROOT, "k8s_scheduler_tpu"),
               copy / "k8s_scheduler_tpu")
    path = copy / "benchmark" / "configs" / "sp5000-mixed.json"
    cfg = json.loads(path.read_text())
    cfg["rehearse"]["server"]["padExisting"] = 512
    path.write_text(json.dumps(cfg))
    out = subprocess.run(
        [sys.executable, str(copy / "benchmark" / "run.py"), "--rehearse",
         "--workload", "sp5000-mixed.sat", "--trace", "0",
         "--seed", "3000000019", "--seconds", "4"],
        capture_output=True, text=True, timeout=1700,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    said = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    facts = next(d["facts"] for d in said if "facts" in d)
    line = next(d["would_print"] for d in said if "would_print" in d)
    over, limit = line["compared"]["existing_over_pad"]
    assert facts["pad_existing"] == 512 and limit == 0
    assert over == facts["existing_peak"] - 512 > 0
    assert facts["pad_headroom_share"] < 0
    assert line["correct"] is False
    assert list(line)[-1] == "compared"


def test_the_rehearsal_passes_in_every_cell_traced_and_untraced():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
         "--seed", "3000000019"],
        capture_output=True, text=True, timeout=1700,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    said = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    lines = {(d["rehearsal"], d["trace"]): d["would_print"]
             for d in said if "would_print" in d}
    assert sorted(lines) == sorted(
        (w["name"], t) for w in BENCHMARK["workloads"] for t in (0, 1))
    # pods finish in every cell's rehearsal: the delete path runs, the
    # resident set is flat at the cut target from the window's first
    # cycle to its last, and the replay and the server agree on it
    all_facts = [d["facts"] for d in said if "facts" in d]
    assert len(all_facts) == len(lines)
    for facts in all_facts:
        assert facts["completed"] > 0
        assert facts["resident_at_start"] == [facts["resident_target"]] * 2
        assert facts["existing_peak"] <= facts["resident_target"] + 256
    for (cell, trace), line in lines.items():
        assert line["correct"] is True, (cell, trace)
        assert line["failed"] == 0
        assert line["compared"]["existing_over_pad"] == [0, 0]
        for name in ("bad_completions", "resident_over_target",
                     "server_resident_drift"):
            assert line["compared"][name] == [0, 0], (cell, trace, name)
        if trace:
            (full,) = [v["value"] for k, v in line["metrics"].items()
                       if k.startswith("full_encodes_per_cycle.")]
            assert 0.0 <= full <= 1.0
        if trace == 0:
            assert {"pods_bound_per_s", "setup_s"} <= set(line["metrics"])
        else:
            # the CPU has no device plane: the host metrics of the cell
            assert line["metrics"], (cell, trace)
            assert all(cell in next(
                m["workloads"] for m in BENCHMARK["per_layer"]
                if m["name"] == name) for name in line["metrics"])

"""What the benchmark's data files hold the program to, read with no
process started.

- Per configuration under `benchmark/configs/`, the served YAML's
  `padExisting` is the power of two above the resident set the JSON
  states: `resident_target` + `depth` + the probe pools' load pods (the
  arithmetic of `benchmark/tests/test_cells.py`, which is run by hand;
  the pad follows the resident set, not the rate, since PR 35), or,
  where a configuration states no target because nothing completes,
  `init_pods` + `depth` + the load pods, at size and in the cut. The
  three rate inputs of the rule before that are read only where a
  configuration still carries them, and must then stand at 0. Past the
  pad the encoder leaves the delta path and programs compile inside the
  window: the run reads `existing_over_pad` > 0 and `correct: false`.
- Every span name a `program_span` layer file selects is in
  `core/spans.SPAN_NAMES`, and every phase a `flight_phase` layer file
  selects is in `core/observe.PHASES`: one case a name, so that a
  rename fails in under a second with the name in the test's id.
"""

import glob
import json
import os

import pytest
import yaml

from k8s_scheduler_tpu.core.observe import PHASES
from k8s_scheduler_tpu.core.spans import SPAN_NAMES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return yaml.safe_load(f) if parts[-1].endswith(".yaml") \
            else json.load(f)


BENCHMARK = load("BENCHMARK.json")


@pytest.mark.parametrize(
    "entry", BENCHMARK["configs"], ids=lambda e: e["name"])
def test_the_existing_pad_is_what_the_configurations_rule_gives(entry):
    cfg = load(entry["file"])
    served = load("benchmark", "configs", cfg["server_config"])
    cut = cfg["rehearse"]
    rule = cfg["pad_rule"]

    def probe_loads(c):
        return c["probe"]["pools"] * c["probe"]["nodes_per_pool"]

    if "resident_target" not in cfg:
        # nothing completes (`sp5000-preempt`, PR 44: the resident set
        # only falls), so the pad holds all that can ever be resident:
        # `init_pods + depth +` the probe loads, at size and in the cut
        assert "init_pods + depth" in rule["holds"]
        for c, pad in ((cfg, served["padExisting"]),
                       (cut, cut["server"]["padExisting"])):
            assert "resident_target" not in c
            holds = c["init_pods"] + c["depth"] + probe_loads(c)
            assert pad == 1 << holds.bit_length(), holds
        assert cut["server"]["padExisting"] < served["padExisting"]
        return
    holds = cfg["resident_target"] + cfg["depth"] + probe_loads(cfg)
    assert served["padExisting"] == 1 << holds.bit_length(), holds
    # the window opens under the target and the warm-up batch fills it
    assert cfg["init_pods"] + cfg["depth"] <= cfg["resident_target"] + 64
    # no rate is an input: a file that still names the old rule's three
    # (until a benchmark PR deletes the keys) holds them at 0
    assert "resident_target + depth" in rule["holds"]
    assert all(rule[k] == 0 for k in (
        "rate_ref_pods_per_s", "factor", "iteration_s") if k in rule)
    # the rehearsal's cut server is the same program under a smaller
    # pad, which holds the cut resident set the same way
    assert cut["server"]["padExisting"] < served["padExisting"]
    assert cut["resident_target"] + cut["depth"] < (
        cut["server"]["padExisting"])
    assert cut["init_pods"] < cut["resident_target"]


def selected(source_kind: str) -> list[str]:
    """The distinct names the layer files of one kind select."""
    names = set()
    for path in glob.glob(os.path.join(BENCH, "layers", "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if spec["source_kind"] == source_kind:
            names.update(spec["select"])
    return sorted(names)


# `outside` is the reader's own word (benchmark/lib/program_spans.py):
# the device's idle time under no rpc.* span
@pytest.mark.parametrize(
    "name", [n for n in selected("program_span") if n != "outside"])
def test_a_selected_span_is_one_the_program_stamps(name):
    assert name in SPAN_NAMES


@pytest.mark.parametrize("name", selected("flight_phase"))
def test_a_selected_phase_is_one_the_flight_recorder_keeps(name):
    assert name in PHASES


def test_the_loser_loop_is_a_phase_beside_its_span():
    """`loser_loop_ms.*` reads the span (a mean: the median of a phase
    that is 0 in every other cycle says nothing); the recorder's
    histograms see the same window as the phase `losers`."""
    assert {"cycle.postfilter", "cycle.losers"} <= set(
        selected("program_span"))
    assert "losers" in PHASES and "postfilter" in PHASES

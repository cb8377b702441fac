"""Scenario fuzzer + trace-level differential oracle (ISSUE 11).

Four layers:

- generator/format units: seeded determinism, dict/file round-trips;
- shrinker units: synthetic (engine-free) checkers prove the reducer
  reaches the documented minimum AND never shrinks to a different
  failure class;
- corpus replay (fast tier): every committed minimal repro under
  tests/corpus/ replays CLEAN against the current engine — each file
  is the regression test for a bug class the differential once caught;
- smoke: live differential cases across the axes (plain / gangs+PDBs /
  sharded / chaos), plus the harness self-test — a deliberately seeded engine bug (mutated claim-path
  tie-break) must be CAUGHT, and the corpus repro must reproduce its
  recorded class when the bug is re-injected.
"""

from __future__ import annotations

import copy
import glob
import os
import tempfile

import pytest

from k8s_scheduler_tpu.fuzz import (
    Failure,
    engine_bug,
    generate_trace,
    replay_artifact,
    run_case,
    shrink_trace,
    trace_from_dict,
    trace_to_dict,
)
from k8s_scheduler_tpu.fuzz.trace import load_trace, save_trace

CORPUS_DIR = os.path.join(os.path.dirname(__file__), "corpus")


# ---- generator + format -------------------------------------------------


def test_generator_is_deterministic():
    a = trace_to_dict(generate_trace(7))
    b = trace_to_dict(generate_trace(7))
    assert a == b
    assert a != trace_to_dict(generate_trace(8))
    # kwargs are part of the stamp: the same seed with different axes
    # must still be reproducible, not equal
    c = trace_to_dict(generate_trace(7, devices=4))
    assert c == trace_to_dict(generate_trace(7, devices=4))
    assert c != a


def test_generator_covers_the_plugin_inventory():
    """Across a seed band the generator exercises the full scenario
    inventory: gangs, PDBs, PV topology, taints, spreads, affinity,
    priorities, churn, chaos plans."""
    import json

    seen = set()
    for seed in range(40):
        t = generate_trace(seed)
        if t.pod_groups:
            seen.add("gangs")
        if t.pdbs:
            seen.add("pdbs")
        if any(v.get("na") for v in t.pvs):
            seen.add("pv_topology")
        blob = json.dumps(t.cycles)
        if '"tol"' in blob:
            seen.add("tolerations")
        if '"tsc"' in blob:
            seen.add("spread")
        if '"af"' in blob:
            seen.add("affinity")
        if '"pri": 100' in blob:
            seen.add("preemption_pressure")
        if '"delete_pod"' in blob:
            seen.add("pod_churn")
        if '"delete_node"' in blob or '"update_node"' in blob:
            seen.add("node_churn")
    t = generate_trace(3, chaos=True)
    if t.fault_spec:
        seen.add("chaos")
    assert seen == {
        "gangs", "pdbs", "pv_topology", "tolerations", "spread",
        "affinity", "preemption_pressure", "pod_churn", "node_churn",
        "chaos",
    }


def test_trace_roundtrips(tmp_path):
    t = generate_trace(11, chaos=True)
    d = trace_to_dict(t)
    assert trace_to_dict(trace_from_dict(d)) == d
    p = str(tmp_path / "t.json")
    save_trace(p, t)
    assert trace_to_dict(load_trace(p)) == d


# ---- shrinker units (synthetic checkers: no engine, no compile) ---------


def _poison_check(trace):
    """Synthetic bug: fails iff any arrival carries priority 10. The
    documented minimum: 1 node, 1 cycle, 1 event, no volume/PDB/gang
    objects, the pod stripped to its priority."""
    for ci, evs in enumerate(trace.cycles):
        for ev in evs:
            if ev.get("op") == "add_pod" and (
                ev["pod"].get("s", {}).get("pri") == 10
            ):
                return Failure("synthetic/poison", ci, "poison present")
    return None


def _seeded_poisoned_trace():
    for seed in range(100):
        t = generate_trace(seed)
        if _poison_check(t) is not None:
            return t
    raise AssertionError("no seed in range produced a priority-10 pod")


def test_shrinker_reaches_the_documented_minimum():
    t = _seeded_poisoned_trace()
    f = _poison_check(t)
    mint, minf = shrink_trace(t, f, _poison_check, max_evals=3000)
    assert minf.cls == "synthetic/poison"
    assert _poison_check(mint) is not None
    assert len(mint.nodes) == 1  # the shrinker keeps >=1 node
    assert len(mint.cycles) == 1
    assert sum(len(evs) for evs in mint.cycles) == 1
    assert not mint.pvs and not mint.pvcs and not mint.pdbs
    assert not mint.pod_groups and not mint.storage_classes
    (ev,) = mint.cycles[0]
    # every strippable attribute is gone; the load-bearing one stays
    s = ev["pod"]["s"]
    assert s.get("pri") == 10
    for k in ("af", "tsc", "tol", "sel", "vol", "pg"):
        assert k not in s


def test_shrinker_preserves_the_failure_class():
    """No shrink-to-a-different-bug: a reduction that flips the failure
    class is rejected even when it would still 'fail'."""
    t = _seeded_poisoned_trace()

    def two_class_check(trace):
        base = _poison_check(trace)
        if base is None:
            return None
        if len(trace.nodes) >= 3:
            return Failure("synthetic/big", base.cycle, "poison, >=3 nodes")
        return Failure("synthetic/small", base.cycle, "poison, <3 nodes")

    assert len(t.nodes) >= 3  # generator minimum is 4
    f = two_class_check(t)
    assert f.cls == "synthetic/big"
    mint, minf = shrink_trace(t, f, two_class_check, max_evals=3000)
    assert minf.cls == "synthetic/big"
    # node removal stopped exactly where the class would have flipped
    assert len(mint.nodes) == 3
    assert two_class_check(mint).cls == "synthetic/big"


def test_shrinker_input_is_not_mutated():
    t = _seeded_poisoned_trace()
    before = copy.deepcopy(trace_to_dict(t))
    shrink_trace(t, _poison_check(t), _poison_check, max_evals=500)
    assert trace_to_dict(t) == before


def test_replay_refuses_rounds_mode():
    """The differential is defined for the scan engine (exact vs the
    oracle; at-turn attribution). A rounds-mode trace must be refused
    loudly, never silently compared into phantom divergences."""
    from k8s_scheduler_tpu.fuzz.replay import replay_engine, replay_oracle

    t = generate_trace(0)
    t.config["commit_mode"] = "rounds"
    with pytest.raises(ValueError, match="scan"):
        replay_engine(t)
    with pytest.raises(ValueError, match="scan"):
        replay_oracle(t)


def test_engine_bug_patch_restores():
    from k8s_scheduler_tpu.ops import argsel

    orig = argsel.argmax_first
    with engine_bug("tiebreak"):
        assert argsel.argmax_first is not orig
    assert argsel.argmax_first is orig
    with pytest.raises(ValueError):
        with engine_bug("not_a_bug"):
            pass


# ---- corpus replay (fast tier: the committed regression suite) ----------


def _corpus_files():
    return sorted(glob.glob(os.path.join(CORPUS_DIR, "*.json")))


def test_corpus_exists():
    assert _corpus_files(), "tests/corpus/ must hold >=1 minimal repro"


@pytest.mark.parametrize("path", _corpus_files())
def test_corpus_replays_clean(path):
    """Every committed minimal repro must replay with ZERO divergences
    and zero invariant violations against the current engine — each
    file pins a bug class the differential once caught."""
    failures = replay_artifact(path)
    assert not failures, [str(f) for f in failures]


# ---- live differential smoke ---------------------------------------------


def test_fuzz_differential_plain_seed():
    """One full plain case: random trace (churn, priorities, taints,
    spreads) through the live engine and the trace oracle — bit-equal
    streams, zero invariant violations."""
    failures = run_case(generate_trace(2))
    assert not failures, [str(f) for f in failures]


def test_fuzz_differential_sharded_seed():
    """Sharded serving (shardDevices=4 on the virtual CPU mesh) must
    stay bit-identical to the oracle — PR 9's shard-invariant
    tie-breaking is what makes this assertion exact."""
    failures = run_case(generate_trace(31, devices=4))
    assert not failures, [str(f) for f in failures]


def test_fuzz_chaos_seed(tmp_path):
    """Chaos fusion: a random FaultPlan over a random trace. The PR 8
    soak invariants hold throughout — watchdog bound, no lost/duplicate
    binds, ladder recovered on the tail, digest-verified restore."""
    t = generate_trace(30, chaos=True)
    assert t.fault_spec
    failures = run_case(t, state_dir=str(tmp_path / "state"))
    assert not failures, [str(f) for f in failures]


def test_fuzz_catches_seeded_tiebreak_bug():
    """Harness self-test: with the claim-path tie-break deliberately
    mutated (first-max -> last-max), the differential must report a
    bind-stream divergence — the exact silent-wrongness class PR 9
    eliminated and the reason bit-equality is assertable at all."""
    failures = run_case(generate_trace(1), bug="tiebreak")
    assert any(f.cls == "divergence/binds" for f in failures), (
        [str(f) for f in failures]
    )


def test_corpus_repro_still_catches_its_bug():
    """The committed minimal repro, replayed WITH its recorded engine
    mutation, must reproduce the recorded failure class — proof the
    oracle still catches the class, not just that the engine is
    currently correct."""
    for path in _corpus_files():
        from k8s_scheduler_tpu.fuzz import load_artifact

        art = load_artifact(path)
        if not art["bug"]:
            continue
        failures = replay_artifact(path, with_bug=True)
        assert any(f.cls == art["failure"].cls for f in failures), (
            path, [str(f) for f in failures],
        )


def test_fuzz_soak_smoke():
    """The scripts/fuzz_scheduler.py smoke path, in-process: a handful
    of seeds across the axes with shrink disabled."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    with tempfile.TemporaryDirectory() as td:
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "scripts", "fuzz_scheduler.py"),
             "--smoke", "--no-shrink", "--artifact-dir", td],
            capture_output=True, text=True, timeout=1500, env=env,
            cwd=repo,
        )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert '"fuzz": "ok"' in proc.stdout

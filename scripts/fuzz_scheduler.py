#!/usr/bin/env python
"""Scenario-fuzzer soak: random cluster traces through the live engine
vs the trace-semantics oracle, chaos fused in, failures auto-shrunk.

Default soak mixes plain differential cases (bit-equal bind streams +
standing invariants) with chaos cases (random FaultPlan over a random
trace; PR 8 soak invariants) across device counts {1, 4}:

    JAX_PLATFORMS=cpu python scripts/fuzz_scheduler.py 10        # minutes
    python scripts/fuzz_scheduler.py --smoke                     # a few seeds
    python scripts/fuzz_scheduler.py --seed 1234 --devices 4     # one case
    python scripts/fuzz_scheduler.py --replay tests/corpus/x.json
    python scripts/fuzz_scheduler.py --seed 1 --inject-bug tiebreak

Every failure is stamped `FUZZ-FAIL seed=<s> devices=<d> chaos=<0|1>
api=<0|1> bug=<name> fault_spec=<spec> class=<cls>` — the run is
reproducible from that log line alone (`--seed/--devices/--chaos/
--via-api/--inject-bug` re-derive the identical trace) — then
shrunk to a minimal repro and written as a corpus artifact
(fuzz/corpus.py format) under --artifact-dir for triage or promotion
into tests/corpus/.

Exit status: 0 = no failures, 1 = failures, 2 = usage error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the 8-device virtual CPU mesh must exist before jax initializes —
# sharded cases (devices {4}) dispatch over it (tests/conftest.py does
# the same; harmless for devices=1)
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _stamp(trace, bug, failure, via_api=False) -> str:
    return (
        f"FUZZ-FAIL seed={trace.seed} "
        f"devices={max(int(trace.config.get('shard_devices', 0)), 1)} "
        f"chaos={int(trace.chaos)} "
        f"api={int(via_api)} "
        f"bug={bug or '-'} fault_spec={trace.fault_spec or '-'} "
        f"class={failure.cls}"
    )


def _run_with_tmp_state(trace, bug, via_api=False):
    """run_case with a self-cleaning state dir for chaos traces (the
    digest-restore check needs a journal; a soak + shrink loop must
    not leave hundreds of journal dirs under /tmp). `via_api` routes
    arrivals through the real Submit/NodeChurn RPCs and compares
    against the direct-enqueue engine (run_api_case; plain traces
    only — the engine bug hooks and chaos state dirs stay with the
    oracle differential)."""
    from k8s_scheduler_tpu.fuzz import run_api_case, run_case

    if via_api:
        return run_api_case(trace)
    if not trace.chaos:
        return run_case(trace, bug=bug)
    with tempfile.TemporaryDirectory(prefix="fuzz-state-") as sd:
        return run_case(trace, state_dir=sd, bug=bug)


def run_one(seed, *, devices, chaos, bug, artifact_dir, shrink,
            shrink_evals, via_api=False) -> "tuple[int, str | None]":
    """Returns (n_failures, artifact_path | None)."""
    from k8s_scheduler_tpu.fuzz import (
        generate_trace,
        save_artifact,
        shrink_trace,
    )

    trace = generate_trace(seed, devices=devices, chaos=chaos)
    failures = _run_with_tmp_state(trace, bug, via_api=via_api)
    if not failures:
        return 0, None
    first = failures[0]
    print(_stamp(trace, bug, first, via_api=via_api), flush=True)
    for f in failures[:5]:
        print(f"  {f}", flush=True)
    path = None
    if shrink:
        def check(tr):
            fs = _run_with_tmp_state(tr, bug, via_api=via_api)
            return fs[0] if fs else None

        mint, minf = shrink_trace(
            trace, first, check, max_evals=shrink_evals
        )
        os.makedirs(artifact_dir, exist_ok=True)
        path = os.path.join(
            artifact_dir,
            f"repro_seed{seed}_{minf.cls.replace('/', '_')}.json",
        )
        save_artifact(
            path, mint, minf, bug=bug,
            note=_stamp(trace, bug, first, via_api=via_api),
        )
        print(
            f"  shrunk to {sum(len(c) for c in mint.cycles)} events / "
            f"{len(mint.cycles)} cycles / {len(mint.nodes)} nodes "
            f"-> {path}", flush=True,
        )
    return len(failures), path


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("minutes", nargs="?", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=None,
                    help="run exactly one seed instead of a soak")
    ap.add_argument("--devices", type=int, default=0,
                    help="shardDevices for --seed runs (soak mixes 1/4)")
    ap.add_argument("--chaos", action="store_true")
    ap.add_argument("--via-api", action="store_true",
                    help="arrivals_via_api variant: route every pod "
                    "arrival through a real gRPC Submit round trip and "
                    "node churn through NodeChurn, and require "
                    "bit-equal streams vs the direct-enqueue engine")
    ap.add_argument("--inject-bug", default=None, choices=("tiebreak",),
                    help="deliberately mutate the engine (self-test: "
                    "the differential must catch it)")
    ap.add_argument("--replay", default="",
                    help="replay a corpus artifact instead of fuzzing "
                    "(exit 1 if it fails clean-side)")
    ap.add_argument("--replay-with-bug", action="store_true",
                    help="with --replay: re-inject the recorded bug "
                    "and expect the recorded failure class")
    ap.add_argument("--smoke", action="store_true",
                    help="a handful of seeds across the axes, no clock")
    ap.add_argument("--no-shrink", action="store_true")
    ap.add_argument("--shrink-evals", type=int, default=150)
    ap.add_argument("--artifact-dir", default="fuzz-artifacts")
    args = ap.parse_args()
    if args.via_api and (args.chaos or args.inject_bug):
        ap.error(
            "--via-api is an engine-vs-engine variant for plain "
            "traces; chaos and bug injection belong to the oracle "
            "differential"
        )

    from k8s_scheduler_tpu.utils.compilation_cache import (
        enable_compilation_cache,
    )

    enable_compilation_cache()

    if args.replay:
        from k8s_scheduler_tpu.fuzz import load_artifact, replay_artifact

        art = load_artifact(args.replay)
        failures = replay_artifact(
            args.replay, with_bug=args.replay_with_bug
        )
        if args.replay_with_bug:
            ok = any(f.cls == art["failure"].cls for f in failures)
            print(json.dumps({
                "replay": args.replay, "with_bug": art["bug"],
                "expected_class": art["failure"].cls,
                "reproduced": ok,
            }), flush=True)
            return 0 if ok else 1
        for f in failures:
            print(f"  {f}", flush=True)
        print(json.dumps({
            "replay": args.replay, "clean": not failures,
        }), flush=True)
        return 1 if failures else 0

    kw = dict(
        artifact_dir=args.artifact_dir,
        shrink=not args.no_shrink,
        shrink_evals=args.shrink_evals,
        bug=args.inject_bug,
    )
    if args.seed is not None:
        n, _p = run_one(
            args.seed, devices=args.devices, chaos=args.chaos,
            via_api=args.via_api, **kw,
        )
        print(json.dumps({"seed": args.seed, "failures": n}), flush=True)
        return 1 if n else 0

    # the soak: plain, chaos and arrivals-via-API cases interleaved,
    # devices {1, 4} — (seed, devices, chaos, via_api)
    seeds = (
        [(s, 1, False, False) for s in range(100, 103)]
        + [(110, 4, False, False),
           (111, 1, True, False),
           (113, 1, False, True)]
    ) if args.smoke else None
    deadline = None if args.smoke else time.time() + args.minutes * 60
    total = failures_n = cases = 0
    artifacts = []
    seed = 10_000
    while True:
        if seeds is not None:
            if cases >= len(seeds):
                break
            s, devices, chaos, via_api = seeds[cases]
        else:
            if time.time() >= deadline or failures_n >= 5:
                break
            s = seed
            seed += 1
            devices = 4 if s % 4 == 3 else 1
            chaos = s % 5 == 2
            # every eleventh plain case routes arrivals through the
            # real Submit/NodeChurn RPCs (engine-vs-engine; chaos and
            # bug injection stay with the oracle differential)
            via_api = s % 11 == 4 and not chaos
        n, path = run_one(
            s, devices=devices, chaos=chaos, via_api=via_api, **kw
        )
        cases += 1
        total += n
        failures_n += bool(n)
        if path:
            artifacts.append(path)
        if cases % 10 == 0:
            print(
                f"  {cases} cases, {failures_n} failing", flush=True
            )
    print(json.dumps({
        "fuzz": "ok" if not failures_n else "FAIL",
        "cases": cases,
        "failing_cases": failures_n,
        "artifacts": artifacts,
    }), flush=True)
    return 1 if failures_n else 0


if __name__ == "__main__":
    sys.exit(main())

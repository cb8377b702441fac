"""Scenario fuzzer + trace-level differential oracle (ISSUE 11).

The correctness backstop for every scale item: a seeded generator
(`trace.py`) emits cluster traces of several cycles — pod
arrivals/deletions, node add/drain/churn, gangs, priority bands with
preemption pressure, taints/tolerations, PV topology, zone spreads,
disruption budgets —
which `replay.py` drives through BOTH the live `Scheduler` (the real
dispatch path, the sharded variant included) and the slow
sequential oracle extended with trace semantics
(`oracle.schedule_cycle_trace`), asserting bit-equal bind streams plus
standing per-cycle invariants. `shrink.py` reduces failing traces to
minimal repros; `corpus.py` serializes them into the committed format
`tests/corpus/` replays in the fast tier.

Entry points: `scripts/fuzz_scheduler.py` (open-ended soak + replay
CLI), `tests/test_fuzz.py` (fast differential cases, corpus replay,
shrinker units, slow smoke).
"""

from .corpus import load_artifact, replay_artifact, save_artifact  # noqa: F401
from .replay import (  # noqa: F401
    Failure,
    engine_bug,
    replay_engine,
    replay_oracle,
    run_api_case,
    run_case,
    run_tenant_case,
)
from .shrink import shrink_trace  # noqa: F401
from .trace import (  # noqa: F401
    Trace,
    generate_multitenant_trace,
    generate_trace,
    trace_from_dict,
    trace_to_dict,
)
